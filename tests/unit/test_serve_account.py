"""``deepspeed_tpu.telemetry.serve_account``: the serve loop's own account of
a profiler trace. Over the v5e trace recorded under ``perfbench/testdata``
(an older loop: one span a token, synchronous admissions, no ``request_id``
on the first token's read) and over a synthetic trace of the loop as it
stands, built from a small simulation of one host thread and one in-order
device queue: two admissions nested in one iteration, a step dispatched two
ahead, a run dispatched before the trace began, enqueues that land inside
the NEXT admission's span, and one 3 s hole. Enclosure in time gives the
wrong answers there and the account the right ones, by the id chain; a run
the chain does not place is in no row and is counted. A CPU run: counts and
joins, no speed."""
import io
import os
import types

import pytest

from deepspeed_tpu.inference import engine
from deepspeed_tpu.telemetry import scopes, serve_account as sa, spans
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                    "perfbench", "testdata")
RECORDED = os.path.join(DATA, "serve_spans.xplane.pb.gz")
US = 1000


# ---------------------------------------------------------------------------
# a trace as plain objects (what ``account`` reads of a ``ProfileData``)
# ---------------------------------------------------------------------------
def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def trace(lines_by_plane):
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=plane, lines=[
            types.SimpleNamespace(name=line, events=events)
            for line, events in lines.items()])
        for plane, lines in lines_by_plane.items()])


def copy_of(profile, drop=()):
    """A loaded trace as plain objects, the stats named ``drop`` left
    out."""
    return trace({plane.name: {line.name: [
        ev(e.name, e.start_ns, e.duration_ns,
           **{k: v for k, v in dict(e.stats).items() if k not in drop})
        for e in line.events] for line in plane.lines}
        for plane in profile.planes})


class Sim:
    """One host thread that dispatches programs and one device that runs
    them in order. ``dispatch`` writes the runtime's chain (the enqueue
    ``late`` after the call, on a queue thread) and the run; ``truth``
    keeps which span dispatched which run."""
    DUR = {"prefill": 1100 * US, "decode_k": 1000 * US, "splice": 40 * US,
           "set_token": 5 * US}

    def __init__(self):
        self.t = 0                  # the host's clock
        self.free = 0               # when the device's queue is empty
        self.python, self.main, self.queue = [], [], []
        self.modules, self.ops = [], []
        self.run_id = 99
        self.truth = {}             # run_id -> (span name, its key)
        self.open = []

    def run_on_device(self, name, after, dur=None):
        self.run_id += 1
        a = max(self.free + 10 * US, after)
        b = a + (dur or self.DUR[name])
        self.modules.append(ev("jit_%s(1)" % name, a, b - a,
                               run_id=self.run_id))
        self.ops.append(ev("%%fusion.%d = f32[8]{0} fusion(f32[8] %%p)"
                           % self.run_id, a, b - a))
        self.free = b
        return self.run_id, a, b

    def dispatch(self, name, key, late=300 * US, dur=None):
        """A jitted call from inside the innermost open span."""
        a = self.t
        rid, start, end = self.run_on_device(name, a + 260 * US, dur)
        self.main.append(ev("tpu::System::Execute", a + 200 * US, 10 * US,
                            _p=1000 + rid))
        self.queue.append(ev("tpu::System::Execute=>IssueSequencedEvent",
                             a + late, 30 * US, _c=1000 + rid))
        self.queue.append(ev("DoEnqueueProgram", a + late + 5 * US,
                             20 * US, run_id=rid))
        self.truth[rid] = key
        self.t = a + 250 * US
        return rid, start, end

    def enter(self, name, **attrs):
        self.open.append((name, self.t, attrs))
        self.t += 5 * US

    def leave(self):
        name, a, attrs = self.open.pop()
        self.t += 5 * US
        self.python.append(ev(spans.SPAN_PREFIX + name, a, self.t - a,
                              **attrs))

    def wait(self, until):
        self.t = max(self.t, until) + 60 * US    # a read's way back

    def profile(self):
        return trace({
            "/device:TPU:0": {"XLA Modules": self.modules,
                              "XLA Ops": self.ops},
            "/host:CPU": {"python": self.python, "main/1": self.main,
                          "tfrt-non-blocking-queue/2": self.queue}})


@pytest.fixture(scope="module")
def sim():
    """The loop as it stands since PR 53, over five decode steps."""
    s = Sim()
    s.ends = {}                     # step ordinal -> end of its run
    # step 0 was dispatched before the trace began: a run, no host events
    _, _, s.ends[0] = s.run_on_device("decode_k", 50 * US)
    s.t = 200 * US
    unread = [0]

    def step(n, ahead, read=True):
        s.enter(spans.SERVE_DECODE_STEP, lanes_active=4, ahead=ahead)
        _, _, s.ends[n] = s.dispatch("decode_k", ("step", n))
        unread.append(n)
        if read:
            s.enter(spans.SERVE_DECODE_READ)
            s.wait(s.ends[unread.pop(0)])
            s.leave()
        s.leave()

    # iteration 1: two admissions, both open until their first tokens
    s.enter(spans.SERVE_ITERATION, decode_steps=1)
    prefill_ends = {}
    for rid, lane, bucket in ((7, 0, 128), (8, 1, 256)):
        s.enter(spans.SERVE_ADMIT, request_id=rid, lane=lane, bucket=bucket,
                prompt_len=bucket - 3, queue_wait_us=11)
        s.enter(spans.SERVE_PREFILL, chunks=1)
        # the enqueue lands 700 us later: inside the next admission
        _, _, prefill_ends[rid] = s.dispatch(
            "prefill", ("admit", rid), late=700 * US,
            dur=(1100 if rid == 7 else 1800) * US)
        s.dispatch("set_token", None)
        s.leave()
        s.enter(spans.SERVE_SPLICE)
        s.dispatch("splice", None, late=600 * US)
        s.leave()
    s.enter(spans.SERVE_STATS)
    s.leave()
    step(1, ahead=1)
    for rid in (7, 8):
        s.enter(spans.SERVE_FIRST_TOKEN_READ, behind_step=1, request_id=rid)
        s.wait(prefill_ends[rid])
        s.leave()
        s.enter(spans.SERVE_EMIT, request_id=rid)
        s.leave()
    s.leave()       # admission 8
    s.leave()       # admission 7
    s.leave()       # iteration 1

    # iteration 2 reads nothing: the host is then two steps ahead
    for n, ahead, read in ((2, 1, False), (3, 2, True)):
        s.enter(spans.SERVE_ITERATION, decode_steps=n)
        s.enter(spans.SERVE_STATS)
        s.leave()
        step(n, ahead, read)
        s.enter(spans.SERVE_DELIVER)
        if n == 3:
            s.t += 3_000_000 * US   # a callback that blocks: a 3 s hole
        s.leave()
        s.leave()
    s.hole_after = s.ends[3]
    for n in (4, 5):
        s.enter(spans.SERVE_ITERATION, decode_steps=n)
        step(n, 2)
        s.leave()
    return s


@pytest.fixture(scope="module")
def acc(sim):
    return sa.account(sim.profile())


@pytest.fixture(scope="module")
def recorded():
    return scopes.load_trace(RECORDED)


def without_runs(account):
    """The tables that need no join, and the admissions' host columns."""
    own = ("prefill_runs", "prefill_device_ms", "run_ids")
    return [[{k: v for k, v in a.items() if k not in own}
             for a in account.admissions],
            account.gaps, account.iterations, account.totals]


# ---------------------------------------------------------------------------
# the synthetic trace
# ---------------------------------------------------------------------------
def test_each_prefill_is_its_own_admissions_by_run_id(sim, acc):
    by_request = {a["request_id"]: a for a in acc.admissions}
    assert sorted(by_request) == [7, 8]
    for rid, a in by_request.items():
        assert a["prefill_runs"] == 1
        assert [sim.truth[r] for r in a["run_ids"]] == [("admit", rid)]
        assert a["bucket"] == {7: 128, 8: 256}[rid] and a["lane"] == rid - 7
    assert by_request[7]["prefill_device_ms"] == pytest.approx(1.1)
    assert by_request[8]["prefill_device_ms"] == pytest.approx(1.8)
    # each read is the admission's own, though both lie inside both spans
    assert by_request[7]["first_token_read_ms"] \
        < by_request[8]["first_token_read_ms"]
    assert acc.joins[sa.PROGRAM_PREFILL] == {
        sa.JOIN_CHAIN: 2, sa.JOIN_NONE: 0}


def test_time_to_first_token_ends_with_the_requests_own_emit(sim, acc):
    """The queue's wait, then from the admission's start to the end of the
    ``ds:serve.emit`` that names the request: both admissions are open
    around both emits, and each takes its own."""
    emits = {dict(e.stats)["request_id"]: e for e in sim.python
             if e.name.endswith(spans.SERVE_EMIT)}
    for a in acc.admissions:
        e = emits[a["request_id"]]
        assert a["ttft_ms"] == pytest.approx(
            0.011 + (e.start_ns + e.duration_ns - a["start_ns"]) / 1e6)
        assert a["ttft_ms"] > a["first_token_read_ms"]
    by_request = {a["request_id"]: a for a in acc.admissions}
    assert by_request[7]["ttft_ms"] < by_request[8]["ttft_ms"]


def test_enclosure_in_time_gives_both_admissions_the_sum(sim):
    """What ``prefill_device_ms_p50`` reads: the spans nest and stay open
    while the prefills run, so each encloses both runs."""
    profile = sim.profile()
    red = tr.reduce_trace(profile)
    prog = ps.Program(red=red, spans=ps.read_spans(profile), rows=None)
    assert ps.module_ms_by_span(prog, spans.SERVE_ADMIT,
                                sa.PROGRAM_PREFILL) \
        == pytest.approx([2.9, 2.9])


def test_each_step_is_its_own_spans_however_far_ahead(sim, acc):
    assert [s["step"] for s in acc.steps] == [1, 2, 3, 4, 5]
    assert [sim.truth[s["run_id"]] for s in acc.steps] == [
        ("step", n) for n in (1, 2, 3, 4, 5)]
    assert [s["ahead"] for s in acc.steps] == [1, 1, 2, 2, 2]
    assert [s["admissions"] for s in acc.steps] == [2, 0, 0, 0, 0]
    assert acc.steps[0]["buckets"] == [128, 256]
    # the run before the trace's first call is nobody's, and is counted
    assert acc.joins[sa.PROGRAM_DECODE_K] == {
        sa.JOIN_CHAIN: 5, sa.JOIN_NONE: 1}
    # run 3 starts while the host is inside step 4's or 5's span: by
    # enclosure it would be theirs
    run3 = acc.steps[2]
    later = [p for p in sim.python if p.name.endswith(spans.SERVE_DECODE_STEP)
             and p.start_ns > run3["dispatch_ns"]]
    assert run3["start_ns"] > run3["dispatch_ns"] + 1000 * US and later


def test_stall_is_what_ran_between_two_decode_runs(sim, acc):
    first = acc.steps[0]
    assert first["stall_prefill_ms"] == pytest.approx(2.9)
    assert first["stall_other_ms"] == pytest.approx(2 * (0.04 + 0.005))
    assert first["stall_idle_ms"] == pytest.approx(0.07)    # 7 x 10 us
    for s in acc.steps:
        assert round(s["stall_ms"] * 1e3) == round(1e3 * (
            s["stall_prefill_ms"] + s["stall_other_ms"]
            + s["stall_idle_ms"]))
    assert acc.steps[1]["stall_ms"] == pytest.approx(0.01)
    assert [(row[0], row[1]) for row in sa.stall_by_buckets(acc)] == [
        ("(none)", 4), ("2 admissions", 1)]
    # the hole is the stall of the step behind it, all of it idle
    assert acc.steps[3]["stall_ms"] == pytest.approx(
        acc.steps[3]["stall_idle_ms"]) and acc.steps[3]["stall_ms"] > 2990
    assert sa.select(acc.steps, "stall_ms", {"admissions": [1, None]}) \
        == [first["stall_ms"]]
    assert len(sa.select(acc.steps, "stall_ms", {"admissions": [0, 0]})) == 4


def test_the_hole_is_one_gap_and_the_longest(sim, acc):
    (totals,) = acc.totals
    hole = max(acc.gaps, key=lambda g: g["ms"])
    assert hole["ms"] == totals["idle_gap_max_ms"] > 2990
    assert hole["start_ns"] == sim.hole_after
    assert (hole["after"], hole["before"]) == (sa.PROGRAM_DECODE_K,) * 2
    # the device ran dry while the host handed a step's tokens on
    assert hole["host_at_start"] == spans.SERVE_DELIVER
    assert sa.gaps_by_cause(acc)[0][4:] == ("deliver", "decode_read")
    assert sum(g["ms"] > 1 for g in acc.gaps) == 1
    assert totals["idle_ms"] == pytest.approx(
        sum(g["ms"] for g in acc.gaps) + totals["short_gaps_ms"]
        + totals["edge_ms"])
    # the gaps of 10 us between two programs are summed, not listed
    assert totals["short_gaps"] >= 8
    assert all(g["ms"] > sa.GAP_FLOOR_NS / 1e6 for g in acc.gaps)
    causes = sa.gaps_by_cause(acc)
    assert causes[0][0] == 1 and causes[0][1] == hole["ms"]


def test_host_time_of_an_iteration_is_the_span_less_its_reads(sim, acc):
    assert [i["admissions"] for i in acc.iterations] == [2, 0, 0, 0, 0]
    assert [i["step"] for i in acc.iterations] == [1, 2, 3, 4, 5]
    for i in acc.iterations:
        assert i["host_ms"] == pytest.approx(i["ms"] - i["wait_ms"])
    # iteration 2 reads nothing; iteration 3 waits for step 1
    assert acc.iterations[1]["wait_ms"] == 0
    assert acc.iterations[2]["wait_ms"] > 0.4
    # the hole is the host's own: the iteration whose deliver held it
    free = sorted(sa.select(acc.iterations, "host_ms",
                            {"admissions": [0, 0]}))
    assert len(free) == 4 and free[2] < 0.5 and free[3] > 2990
    assert acc.iterations[2]["host_ms"] == free[3]


def test_without_the_ids_no_run_is_anyones_and_each_is_counted(sim, acc):
    """No ``run_id`` anywhere (another runtime): no step row, no prefill
    time, nothing in a percentile; what needs no join reads the same."""
    stripped = sa.account(copy_of(sim.profile(), drop=("run_id", "_p", "_c")))
    assert stripped.steps == []
    assert stripped.joins == {
        sa.PROGRAM_PREFILL: {sa.JOIN_CHAIN: 0, sa.JOIN_NONE: 2},
        sa.PROGRAM_DECODE_K: {sa.JOIN_CHAIN: 0, sa.JOIN_NONE: 6}}
    assert [a["prefill_runs"] for a in stripped.admissions] == [0, 0]
    assert sa.select(stripped.admissions, "prefill_device_ms") == []
    assert without_runs(stripped) == without_runs(acc)


def test_a_trace_cut_mid_chain_leaves_that_run_alone_out(sim, acc):
    profile = sim.profile()
    cut = acc.steps[-1]["run_id"]
    for line in profile.planes[1].lines:
        line.events[:] = [e for e in line.events
                          if dict(e.stats).get("run_id") != cut]
    again = sa.account(profile)
    assert again.steps == acc.steps[:-1]
    assert again.joins[sa.PROGRAM_DECODE_K] == {
        sa.JOIN_CHAIN: 4, sa.JOIN_NONE: 2}
    assert again.admissions == acc.admissions


def test_the_window_keeps_what_lies_inside_it(sim, acc):
    lo = acc.steps[1]["start_ns"] - 1
    hi = acc.steps[2]["end_ns"] + 1
    part = sa.account(sim.profile(), window=(lo, hi))
    assert [s["step"] for s in part.steps] == [2, 3]
    # the run before the window still bounds the first stall
    assert part.steps[0]["stall_ms"] == acc.steps[1]["stall_ms"]
    assert part.admissions == [] and part.totals[0]["window_ms"] \
        == pytest.approx((hi - lo) / 1e6)


def test_no_device_plane_is_none(sim):
    profile = sim.profile()
    profile.planes = profile.planes[1:]
    assert sa.account(profile) is None


# ---------------------------------------------------------------------------
# the recorded trace (a loop from before PR 32)
# ---------------------------------------------------------------------------
def test_program_names_are_the_engines():
    assert sa.PROGRAM_PREFILL == engine.PROGRAM_PREFILL
    assert sa.PROGRAM_DECODE_K == engine.PROGRAM_DECODE_K
    assert engine.PROGRAM_PREFILL_MORE.startswith(sa.PROGRAM_PREFILL)


def test_recorded_every_prefill_run_is_one_admissions(recorded):
    acc = sa.account(recorded)
    assert acc.joins == {
        sa.PROGRAM_PREFILL: {sa.JOIN_CHAIN: 3, sa.JOIN_NONE: 0},
        sa.PROGRAM_DECODE_K: {sa.JOIN_CHAIN: 8, sa.JOIN_NONE: 0}}
    assert [a["request_id"] for a in acc.admissions] == [6, 7, 8]
    assert [a["run_ids"] for a in acc.admissions] == [[157], [163], [169]]
    assert [a["bucket"] for a in acc.admissions] == [64, 128, 128]
    # the read is the enclosing admission's where it names no request
    assert all(a["first_token_read_ms"] > 0.4 for a in acc.admissions)
    assert [s["run_id"] for s in acc.steps] \
        == [175, 178, 181, 184, 187, 190, 193, 196]
    assert [s["admissions"] for s in acc.steps] == [3] + [0] * 7
    assert [s["lanes_active"] for s in acc.steps] == [3, 3, 3, 2, 1, 1, 1, 1]


def test_recorded_stall_adds_up_to_the_microsecond(recorded):
    acc = sa.account(recorded)
    stalls = [s for s in acc.steps if s["stall_ms"] is not None]
    assert len(stalls) == 7         # the first has no decode run before it
    for s in stalls:
        assert round(s["stall_ms"] * 1e3) == round(1e3 * (
            s["stall_prefill_ms"] + s["stall_other_ms"]
            + s["stall_idle_ms"]))
        assert s["stall_prefill_ms"] == 0 and s["stall_other_ms"] > 0


def test_recorded_gaps_sum_to_the_benchmarks_idle_seconds(recorded):
    red = tr.reduce_trace(recorded, window_span="window")
    prog = ps.Program(red=red, spans=ps.read_spans(recorded), rows=None)
    (totals,) = sa.account(recorded, window=red.window).totals
    assert totals["idle_ms"] / 1e3 == pytest.approx(ps.idle_seconds(prog))
    acc = sa.account(recorded, window=red.window)
    assert totals["idle_ms"] == pytest.approx(
        sum(g["ms"] for g in acc.gaps) + totals["short_gaps_ms"]
        + totals["edge_ms"])
    # the window's span opens before the trace's first device event: idle
    # time, and no gap of the device's
    assert totals["edge_ms"] > 0
    assert min(g["start_ns"] for g in acc.gaps) > red.window[0]


def test_recorded_first_token_is_the_first_of_a_span_a_token(recorded):
    """The recorded loop wrote one ``ds:serve.emit`` a token: a request's
    time to its first token ends with the first that names it."""
    acc = sa.account(recorded)
    emits = {}
    for plane in recorded.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == spans.SPAN_PREFIX + spans.SERVE_EMIT:
                    emits.setdefault(dict(e.stats)["request_id"], []).append(
                        e.start_ns + e.duration_ns)
    assert all(len(ends) > 1 for ends in emits.values())
    for a in acc.admissions:
        assert a["ttft_ms"] == pytest.approx(
            a["queue_wait_us"] / 1e3
            + (min(emits[a["request_id"]]) - a["start_ns"]) / 1e6)
        assert a["ttft_ms"] > a["first_token_read_ms"]


def test_recorded_without_run_ids_counts_every_run_as_not_joined(recorded):
    want = sa.account(recorded)
    got = sa.account(copy_of(recorded, drop=("run_id",)))
    assert got.steps == [] and got.joins == {
        sa.PROGRAM_PREFILL: {sa.JOIN_CHAIN: 0, sa.JOIN_NONE: 3},
        sa.PROGRAM_DECODE_K: {sa.JOIN_CHAIN: 0, sa.JOIN_NONE: 8}}
    assert without_runs(got) == without_runs(want)


def test_the_module_prints_the_three_tables(recorded, capsys):
    assert sa.main([RECORDED]) == 0
    out = capsys.readouterr().out
    for head in ("steps: 8", "admissions: 3", "gaps over 20 us: ",
                 "3 joined by run_id, 0 not joined", "ttft p50",
                 "iterations: 7, 6 without an admission"):
        assert head in out, out
    buf = io.StringIO()
    sa.report(sa.account(recorded), out=buf)
    assert buf.getvalue() == out
    assert sa.main([]) == 2
