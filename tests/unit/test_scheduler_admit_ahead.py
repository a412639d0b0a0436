"""An iteration of the plain loop hands the device everything it has
before it waits for any of it (``inference/scheduler.py``, PR 53): every
free lane's prefill, first token (``set_token``) and splice, then the decode
step with the new lanes in it; only then the host reads the step that was
in flight and, after it, the first tokens in admission order. A first token
is therefore seen as late as any other token: a request that ends there
costs one dropped row and its lane is refilled an iteration later. Under
greedy decoding every request must still get exactly the stream a plain
synchronous loop over a batch of one gives it, and the device's queue must
hold the programs the parent's loop dispatched, in the parent's order."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import scheduler as scheduler_mod
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.telemetry import spans
from deepspeed_tpu.telemetry.scopes import DispatchedProgram
from unit.test_scheduler_decode_ahead import (
    BUCKET,
    Journal,
    Recorder,
    _check_streams,
    _engine,
    _prompts,
    _with_first_occurrence_at,
    reference,
)

LETTER = {"prefill": "P", "prefill_more": "M", "set_token": "T",
          "splice": "S", "decode_k": "D", "copy_tree": "C"}
# What the parent's loop (04ca7fb) dispatched for ``_seeded_run`` over four
# lanes, by the tokens each request asks for: one word an iteration, each
# admission's prefill, set_token and splice, then the step.
PARENT_SEQUENCES = {
    (8, 7, 6, 5, 9, 9, 5, 4, 6, 6):
        "PTSPTSPTSPTSD D D D D PTSD PTSD PTSD PTSD D D D PTSPTSD D D D D D",
    (9, 7, 5, 3, 6, 6, 6, 6, 4, 4):
        "PTSPTSPTSPTSD D D PTSD D PTSD D PTSD D PTSPTSD D PTSD D D D",
    (6, 6, 6, 6, 3, 5, 7, 9, 4, 4):
        "PTSPTSPTSPTSD D D D D D PTSPTSPTSPTSD D D PTSD D PTSD D D D",
}
# The one place where the order is not the parent's: lane 1's end sits in
# the step in flight while lane 0 is admitted. The parent's admission had
# just read that step and refilled lane 1 in the same iteration; this loop
# reads it after its own step is dispatched and refills lane 1 in the next.
LATE_REFILL = (5, 6, 9, 9, 4, 4)
LATE_REFILL_AT_PARENT = "PTSPTSPTSPTSD D D D D PTSPTSD D D D"
LATE_REFILL_NOW = "PTSPTSPTSPTSD D D D D PTSD PTSD D D D"
# the speculative run's decode steps at the parent
SPEC_ONE_TOKEN_DECODE_STEPS_AT_PARENT = 2


@pytest.fixture(scope="module")
def eng():
    return _engine()


def _scheduler(eng, slots, **kw):
    return ContinuousBatchingScheduler(eng, slots=slots,
                                       prompt_bucket=BUCKET, **kw)


class Watch:
    """What a run hands the device and what it reads back, in order:
    ``("dispatch", program)`` for every call of a ``DispatchedProgram``,
    ``("read",)`` for every ``np.asarray`` of a device array inside the
    scheduler's module, ``("emit", request, token, done)`` from ``on_token``,
    ``("open", span)`` / ``("close", span)`` for every span the scheduler
    opens, which must close in reverse.
    It listens inside ``with watch:`` (the reference loop dispatches the
    engine's programs too)."""

    def __init__(self, monkeypatch):
        self.log, self.open, self.closed = [], [], []
        self.listening = False
        watch = self
        real_call = DispatchedProgram.__call__

        def call(prog, *args):
            if watch.listening:
                watch.log.append(("dispatch", prog.fn.__name__))
            return real_call(prog, *args)

        monkeypatch.setattr(DispatchedProgram, "__call__", call)

        class Numpy:
            def __getattr__(self, name):
                return getattr(np, name)

            @staticmethod
            def asarray(x, *a, **k):
                if isinstance(x, jax.Array):
                    watch.log.append(("read",))
                return np.asarray(x, *a, **k)

        monkeypatch.setattr(scheduler_mod, "np", Numpy())

        class Span:
            def __init__(self, name, attrs):
                self.name, self.attrs = name, attrs

            def __enter__(self):
                watch.open.append(self)
                watch.log.append(("open", self))
                return self

            def __exit__(self, *exc):
                assert watch.open.pop() is self, "spans closed out of order"
                watch.closed.append(self)
                watch.log.append(("close", self))
                return False

        monkeypatch.setattr(scheduler_mod, "span",
                            lambda name, **attrs: Span(name, attrs))

    def __enter__(self):
        self.listening = True

    def __exit__(self, *exc):
        self.listening = False

    def on_token(self, rid, token, done):
        self.log.append(("emit", rid, int(token), bool(done)))

    def words(self):
        """The dispatched programs, one word an iteration (up to and with
        its decode step; what follows the last step is the last word)."""
        out, word = [], ""
        for e in self.log:
            if e[0] == "dispatch":
                word += LETTER[e[1]]
                if e[1] == "decode_k":
                    out.append(word)
                    word = ""
        return out + ([word] if word else [])

    def admissions_by_iteration(self):
        return [w.count("P") for w in self.words() if w.endswith("D")]

    def assert_nothing_read_inside_an_iterations_dispatches(self):
        """From an iteration's first prefill to its decode step the host
        reads no device array."""
        dispatching = False
        for e in self.log:
            if e == ("dispatch", "prefill"):
                dispatching = True
            elif e == ("dispatch", "decode_k"):
                dispatching = False
            elif e[0] == "read":
                assert not dispatching, "a host read between the dispatches"

    def assert_first_tokens_follow_the_step_in_flight(self):
        """After every decode step's dispatch: the read of the step in
        flight and its emits, then one read and one emit an admission, in
        admission order, each that request's FIRST emit."""
        seen, i = set(), 0
        words = self.words()
        for n, word in enumerate(w for w in words if w.endswith("D")):
            # walk to this word's decode dispatch
            while self.log[i] != ("dispatch", "decode_k"):
                i += 1
            i += 1
            tail = []
            while i < len(self.log) and self.log[i][0] != "dispatch":
                if self.log[i][0] in ("read", "emit"):
                    tail.append(self.log[i])
                i += 1
            firsts, others = [], []
            for k, e in enumerate(tail):
                if e[0] != "emit":
                    continue
                if e[1] in seen:
                    others.append(k)
                else:
                    # its own read goes right before it
                    assert tail[k - 1] == ("read",), (n, k, tail)
                    firsts.append(k)
                    seen.add(e[1])
            assert len(firsts) == word.count("P") + word.count("C"), (
                n, word, tail)
            assert [tail[k][1] for k in firsts] == sorted(
                tail[k][1] for k in firsts)
            if n and firsts and others:     # (the run's first step reads
                assert max(others) < min(firsts)    # itself, afterwards)


def _run(sched, watch, submits, **run_kw):
    """``submits``: ``(prompt, max_new, eos)`` in queue order. Returns the
    stats and ``{request id: (prompt, max_new, eos)}``."""
    wants = {}
    for prompt, max_new, eos in submits:
        wants[sched.submit(prompt, max_new_tokens=max_new, eos_token_id=eos,
                           stream_callback=watch.on_token)] = (
            prompt, max_new, eos)
    with watch:
        return sched.run(**run_kw), wants


def _check(eng, stats, watch, wants):
    rec = Recorder()
    for e in watch.log:
        if e[0] == "emit":
            rec(*e[1:])
    _check_streams(eng, stats, rec, wants)
    assert watch.open == []


# ---------------------------------------------------------------------------
# (a) two and three admissions in one iteration
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("together", [2, 3])
def test_several_admissions_in_one_iteration(eng, monkeypatch, together):
    """Four lanes; ``together`` of them end at the same step with requests
    waiting, so one iteration admits that many: all their prefills,
    set_tokens and splices are dispatched, then the step, and no device
    array is read in between."""
    watch = Watch(monkeypatch)
    sched = _scheduler(eng, 4)
    prompts = _prompts(4 + together + 1, seed=21)
    outs = [4] * together + [9] * (4 - together) + [5] * (together + 1)
    stats, wants = _run(sched, watch,
                        [(p, n, None) for p, n in zip(prompts, outs)])
    _check(eng, stats, watch, wants)
    admissions = watch.admissions_by_iteration()
    assert admissions[0] == 4 and together in admissions[1:], admissions
    watch.assert_nothing_read_inside_an_iterations_dispatches()
    watch.assert_first_tokens_follow_the_step_in_flight()
    assert stats.first_tokens_behind_step == len(wants)
    assert stats.summary()["first_tokens_behind_step"] == len(wants)
    # every step but the run's first, which reads itself, and the one
    # after it left with the step before it unread
    assert stats.decode_steps_ahead == stats.decode_steps - 2


def test_no_more_than_four_admissions_wait_for_their_first_token(
        eng, monkeypatch):
    """A run's first iteration has every lane free. A prefill's lane cache
    is allocated at its dispatch and lives until its splice has run, so
    the loop holds ``ADMISSIONS_IN_FLIGHT`` of them at most: before the
    fifth and the sixth prefill it reads the first and the second first
    token (``behind_step`` 0). The programs and their order stay the
    parent's."""
    assert scheduler_mod.ADMISSIONS_IN_FLIGHT == 4
    watch = Watch(monkeypatch)
    sched = _scheduler(eng, 6)
    prompts = _prompts(8, seed=32)
    stats, wants = _run(sched, watch, [(p, 3 + i % 3, None)
                                       for i, p in enumerate(prompts)])
    _check(eng, stats, watch, wants)
    assert watch.words()[0] == "PTS" * 6 + "D"
    unread = most = 0
    for e in watch.log:
        if e == ("dispatch", "prefill"):
            unread += 1
            most = max(most, unread)
        elif e[0] == "emit" and sum(
                1 for f in watch.log[:watch.log.index(e)]
                if f[0] == "emit" and f[1] == e[1]) == 0:
            unread -= 1
    assert most == 4
    reads = [s.attrs["behind_step"] for s in watch.closed
             if s.name == spans.SERVE_FIRST_TOKEN_READ]
    assert reads[:6] == [0, 0, 1, 1, 1, 1] and set(reads[6:]) == {1}
    assert stats.first_tokens_behind_step == len(wants) - 2
    prefills = [i for i, e in enumerate(watch.log)
                if e == ("dispatch", "prefill")]
    emits = [i for i, e in enumerate(watch.log) if e[0] == "emit"]
    ids = sorted(wants)
    assert prefills[3] < emits[0] < prefills[4] < emits[1] < prefills[5]
    assert [watch.log[i][1] for i in emits[:6]] == ids[:6]


# ---------------------------------------------------------------------------
# (b) a request that ends at its first token
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("how", ["one_token", "eos"])
def test_a_request_ending_at_its_first_token_costs_a_row_and_an_iteration(
        eng, monkeypatch, how):
    """The end is seen after the iteration's step left with the lane: that
    step's row for the lane is dropped, and the request waiting for the
    lane is admitted in the NEXT iteration (the parent admitted it in the
    same one)."""
    if how == "eos":
        prompt, eos = _with_first_occurrence_at(eng, 0)
        ending = (prompt, 9, eos)
    else:
        ending = (_prompts(1, seed=22)[0], 1, None)
    others = _prompts(2, seed=23)
    watch = Watch(monkeypatch)
    sched = _scheduler(eng, 2)
    stats, wants = _run(sched, watch, [ending, (others[0], 6, None),
                                       (others[1], 4, None)])
    _check(eng, stats, watch, wants)
    words = watch.words()
    assert words[0] == "PTSPTSD" and words[1] == "PTSD", words
    ids = sorted(wants)
    first_emit = [e for e in watch.log if e[0] == "emit"][0]
    assert first_emit[1] == ids[0] and first_emit[3]    # done at token 1
    # the step of the first iteration computed a row for the ended request
    assert stats.decode_tokens_discarded >= 1
    assert len(stats.completions[0].tokens) == 1
    watch.assert_nothing_read_inside_an_iterations_dispatches()
    watch.assert_first_tokens_follow_the_step_in_flight()


def test_requests_that_all_end_at_admission_are_refilled_lane_by_lane(
        eng, monkeypatch):
    """Six one-token requests over two lanes: three iterations of two
    admissions and one step each, every row of every step dropped."""
    watch = Watch(monkeypatch)
    sched = _scheduler(eng, 2)
    stats, wants = _run(sched, watch,
                        [(p, 1, None) for p in _prompts(6, seed=24)])
    _check(eng, stats, watch, wants)
    assert watch.words() == ["PTSPTSD"] * 3
    assert stats.decode_steps == 3 and stats.decode_tokens_discarded == 6
    assert [c.request_id for c in stats.completions] == sorted(wants)


# ---------------------------------------------------------------------------
# (c) a hand-off admission beside a cold one
# ---------------------------------------------------------------------------
def test_a_hand_off_beside_a_cold_admission(eng, monkeypatch):
    """The hand-off's first token is a host int: nothing to wait for, its
    emit in admission order all the same; its cache is copied, not
    prefilled (``C`` for ``P``)."""
    prompts = _prompts(3, seed=25)
    reference(eng, prompts[0], 2)       # builds the engine's programs
    lp = -(-len(prompts[1]) // BUCKET) * BUCKET
    ids = np.zeros((1, lp), np.int32)
    mask = np.zeros((1, lp), bool)
    ids[0, lp - len(prompts[1]):] = prompts[1]
    mask[0, lp - len(prompts[1]):] = True
    logits, sub = eng._chunked_prefill(jnp.asarray(ids), jnp.asarray(mask))
    first = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
    watch = Watch(monkeypatch)
    sched = _scheduler(eng, 3)
    wants = {}
    for i, p in enumerate(prompts):
        wants[sched.submit(p, max_new_tokens=5 + i,
                           stream_callback=watch.on_token,
                           kv_handoff=(first, sub) if i == 1 else None)] = (
            p, 5 + i, None)
    with watch:
        stats = sched.run()
    _check(eng, stats, watch, wants)
    assert watch.words()[0] == "PTSCTSPTSD"
    firsts = [e[1] for e in watch.log if e[0] == "emit"][:3]
    assert firsts == sorted(wants)      # admission order, hand-off second
    watch.assert_nothing_read_inside_an_iterations_dispatches()
    assert stats.first_tokens_behind_step == 3


# ---------------------------------------------------------------------------
# (d) journal replay after a kill between the dispatch and the first token
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kill_at_emit", [1, 2, 9, 14])
def test_journal_replay_after_a_kill_around_a_first_token(eng, kill_at_emit):
    """A callback that raises at the run's ``kill_at_emit``-th token: the
    first or second first token of the first iteration (the second
    admission's is then dispatched, spliced, stepped over and never read),
    or a token of a later iteration with an admission in it. The journal
    holds a prefix of every stream; a replay gives each its reference."""
    prompts = _prompts(5, seed=26)
    wants = {i: (p, 4 + 2 * (i % 3), None) for i, p in enumerate(prompts)}
    journal = Journal()
    first = _scheduler(eng, 2, journal=journal)

    class Killed(Exception):
        pass

    emits = {"n": 0}

    def on_token(rid, token, done):
        emits["n"] += 1
        if emits["n"] == kill_at_emit:
            raise Killed

    for i, (p, want, _) in wants.items():
        assert first.submit(p, max_new_tokens=want,
                            stream_callback=on_token) == i
    with pytest.raises(Killed):
        first.run()
    held = {i: dict(e, tokens=list(e["tokens"]))
            for i, e in journal.entries.items()}
    for i, e in held.items():           # a prefix of the truth, no more
        ref = reference(eng, *wants[i])
        assert e["tokens"] == ref[:len(e["tokens"])]
        assert e["done"] == (len(e["tokens"]) == len(ref))

    journal2 = Journal()
    second = _scheduler(eng, 2, journal=journal2)
    rec, new_of = Recorder(), {}
    for i, e in held.items():
        if not e["done"]:
            new_of[second.submit(e["prompt"], max_new_tokens=e["max"],
                                 stream_callback=rec,
                                 replay_tokens=e["tokens"] or None)] = i
    stats = second.run()
    assert sorted(c.request_id for c in stats.completions) == sorted(new_of)
    for c in stats.completions:
        i = new_of[c.request_id]
        ref = reference(eng, *wants[i])
        assert c.tokens == ref
        assert held[i]["tokens"] + rec.tokens.get(c.request_id, []) == ref
        assert journal2.entries[c.request_id]["tokens"] == ref
    # replays are admissions like any other: none read before its step
    assert stats.first_tokens_behind_step == len(new_of)


# ---------------------------------------------------------------------------
# (e) drain, and a raise between the dispatch and the first-token read
# ---------------------------------------------------------------------------
def test_drain_begun_between_the_dispatch_and_the_first_token(
        eng, monkeypatch):
    """``begin_drain`` from the callback of a token of the step in flight,
    delivered after an admission's dispatch and before its first token is
    read: the admitted request is in its lane and finishes, the queue
    stays."""
    watch = Watch(monkeypatch)
    sched = _scheduler(eng, 2)
    prompts = _prompts(5, seed=27)
    outs = [3, 8, 5, 5, 5]
    began = []

    def on_token(rid, token, done):
        watch.on_token(rid, token, done)
        # request 1's fourth token is step 3's, delivered in the iteration
        # that admits request 2 into the lane request 0 left
        if rid == rids[1] and sum(
                1 for e in watch.log
                if e[0] == "emit" and e[1] == rid) == 4:
            began.append(len(watch.log))
            sched.begin_drain("test")

    rids = [sched.submit(p, max_new_tokens=n, stream_callback=on_token)
            for p, n in zip(prompts, outs)]
    with watch:
        stats = sched.run()
    wants = {rids[i]: (prompts[i], outs[i], None) for i in range(3)}
    _check(eng, stats, watch, wants)
    assert [r.request_id for r, _ in sched._pending] == rids[3:]
    assert watch.admissions_by_iteration()[:4] == [2, 0, 0, 1]
    # the drain began after request 2's dispatch and before its first token
    before = watch.log[:began[0]]
    assert [e[1] for e in before if e[0] == "dispatch"][-4:] == [
        "prefill", "set_token", "splice", "decode_k"]
    assert not any(e[0] == "emit" and e[1] == rids[2] for e in before)


@pytest.mark.parametrize("how", ["poll_fn_raises", "step_callback_raises",
                                 "first_token_callback_raises"])
def test_nothing_is_left_open_when_run_raises_around_an_admission(
        eng, monkeypatch, how):
    """No decode step is left in flight and no span open, whether the
    raise comes from ``poll_fn``, from the callback of a token of the step
    in flight (an admission dispatched, its first token unread: it is
    nobody's), or from the callback of the first of two first tokens."""
    watch = Watch(monkeypatch)
    sched = _scheduler(eng, 2)
    sched._ensure_compiled()
    dispatched, waited = [], []
    real = eng._decode_k_fn

    def spy(*args):
        out = real(*args)
        dispatched.append(out[1])
        return out

    monkeypatch.setattr(eng, "_decode_k_fn", spy)
    real_wait = jax.block_until_ready
    monkeypatch.setattr(scheduler_mod.jax, "block_until_ready",
                        lambda x: waited.append(x) or real_wait(x))

    class Stop(Exception):
        pass

    prompts = _prompts(4, seed=28)
    outs = [3, 8, 5, 5]
    polls = {"n": 0}

    def poll():
        polls["n"] += 1
        if how == "poll_fn_raises" and polls["n"] == 4:
            raise Stop

    def on_token(rid, token, done):
        watch.on_token(rid, token, done)
        mine = sum(1 for e in watch.log if e[0] == "emit" and e[1] == rid)
        if how == "step_callback_raises" and rid == rids[1] and mine == 4:
            raise Stop                  # request 2 dispatched, unread
        if how == "first_token_callback_raises" and rid == rids[0] \
                and mine == 1:
            raise Stop                  # request 1's first token unread

    rids = [sched.submit(p, max_new_tokens=n, stream_callback=on_token)
            for p, n in zip(prompts, outs)]
    with pytest.raises(Stop), watch:
        sched.run(poll_fn=poll)
    assert watch.open == []
    admits = [s for s in watch.closed if s.name == spans.SERVE_ADMIT]
    assert len(admits) == watch.log.count(("dispatch", "prefill")) > 0
    assert len(waited) == 1 and waited[0] is dispatched[-1]
    emitted = {e[1] for e in watch.log if e[0] == "emit"}
    if how == "step_callback_raises":
        assert rids[2] not in emitted and watch.words()[3] == "PTSD"
    if how == "first_token_callback_raises":
        assert emitted == {rids[0]} and watch.words() == ["PTSPTSD"]


# ---------------------------------------------------------------------------
# (f) a deadline shed among an iteration's admissions
# ---------------------------------------------------------------------------
def test_a_deadline_shed_among_an_iterations_admissions(eng, monkeypatch):
    watch = Watch(monkeypatch)
    sched = _scheduler(eng, 2)
    prompts = _prompts(5, seed=29)
    shed = []
    sched.reject_callback = lambda rid, reason: shed.append((rid, reason))
    wants = {}
    for i in range(2):
        wants[sched.submit(prompts[i], max_new_tokens=4,
                           stream_callback=watch.on_token)] = (
            prompts[i], 4, None)
    doomed = sched.submit(prompts[2], max_new_tokens=4,
                          stream_callback=watch.on_token, deadline_s=1e-4)
    for i in (3, 4):
        wants[sched.submit(prompts[i], max_new_tokens=3,
                           stream_callback=watch.on_token)] = (
            prompts[i], 3, None)
    time.sleep(0.002)
    with watch:
        stats = sched.run()
    _check(eng, stats, watch, wants)
    assert shed == [(doomed, "deadline")] and sched.deadline_shed_count == 1
    # both lanes end together: the shed request is popped between the two
    # admissions of that iteration and takes no lane
    assert watch.admissions_by_iteration().count(2) == 2
    watch.assert_nothing_read_inside_an_iterations_dispatches()
    watch.assert_first_tokens_follow_the_step_in_flight()


# ---------------------------------------------------------------------------
# the order itself
# ---------------------------------------------------------------------------
def _seeded_run(eng, watch, outs=tuple(PARENT_SEQUENCES)[0]):
    """Four lanes, ``len(outs)`` seeded prompts, all queued before the
    run; request ``i`` asks for ``outs[i]`` tokens."""
    sched = _scheduler(eng, 4)
    prompts = _prompts(len(outs), seed=30)
    return _run(sched, watch, [(p, n, None) for p, n in zip(prompts, outs)])


@pytest.mark.parametrize("outs", PARENT_SEQUENCES,
                         ids=lambda o: "-".join(map(str, o)))
def test_the_dispatched_programs_are_the_parents_in_the_parents_order(
        eng, monkeypatch, outs):
    watch = Watch(monkeypatch)
    stats, wants = _seeded_run(eng, watch, outs)
    _check(eng, stats, watch, wants)
    assert " ".join(watch.words()) == PARENT_SEQUENCES[outs]
    watch.assert_nothing_read_inside_an_iterations_dispatches()
    watch.assert_first_tokens_follow_the_step_in_flight()


def test_a_lane_whose_end_is_in_the_step_in_flight_waits_an_iteration(
        eng, monkeypatch):
    watch = Watch(monkeypatch)
    stats, wants = _seeded_run(eng, watch, LATE_REFILL)
    _check(eng, stats, watch, wants)
    assert " ".join(watch.words()) == LATE_REFILL_NOW != LATE_REFILL_AT_PARENT
    assert sorted(LATE_REFILL_NOW.replace(" ", "")) \
        == sorted(LATE_REFILL_AT_PARENT.replace(" ", "") + "D")
    watch.assert_nothing_read_inside_an_iterations_dispatches()
    watch.assert_first_tokens_follow_the_step_in_flight()


def test_every_admission_span_is_open_from_its_dispatch_to_its_emit(
        eng, monkeypatch):
    """One ``serve.admit`` an admission, opened before its prefill is
    dispatched and closed after its first token's emit; those of one
    iteration nest, the first outermost, around the iteration's decode
    step; ``serve.first_token_read`` says ``behind_step`` 1."""
    watch = Watch(monkeypatch)
    stats, wants = _seeded_run(eng, watch)
    admits = [s for s in watch.closed if s.name == spans.SERVE_ADMIT]
    assert sorted(s.attrs["request_id"] for s in admits) == sorted(wants)
    for a in admits:
        lo, hi = watch.log.index(("open", a)), watch.log.index(("close", a))
        inside = watch.log[lo:hi]
        mine = [e for e in inside
                if e[0] == "emit" and e[1] == a.attrs["request_id"]]
        assert len(mine) == 1           # its first token, and no later one
        at = inside.index(mine[0])
        assert ("dispatch", "prefill") in inside[:at]
        assert inside[:at].count(("dispatch", "decode_k")) == 1
    for name in (spans.SERVE_PREFILL, spans.SERVE_SPLICE,
                 spans.SERVE_FIRST_TOKEN_READ):
        assert len([s for s in watch.closed if s.name == name]) \
            == len(admits)
    reads = [s for s in watch.closed
             if s.name == spans.SERVE_FIRST_TOKEN_READ]
    assert all(s.attrs["behind_step"] == 1 for s in reads)
    # each read names its admission's request, in admission order
    assert [s.attrs["request_id"] for s in reads] == [
        s.attrs["request_id"]
        for s in sorted(admits, key=lambda a: watch.log.index(("open", a)))]
    # the first iteration's four: closed in reverse of their opening
    first = [s.attrs["request_id"] for s in admits[:4]]
    assert first == sorted(first, reverse=True)
    steps = [s for s in watch.closed if s.name == spans.SERVE_DECODE_STEP]
    assert [s.attrs["ahead"] for s in steps] == [0, 0] + [1] * (
        len(steps) - 2)


# ---------------------------------------------------------------------------
# the speculative loop keeps its order
# ---------------------------------------------------------------------------
def test_speculative_loop_reads_each_first_token_at_its_admission(
        eng, monkeypatch):
    """Its next input is the host's: each admission's first token is read
    before the next admission's dispatch (``behind_step`` 0), a request
    that ends there frees its lane for the next one at once, and the
    counts are the parent's."""
    watch = Watch(monkeypatch)
    sched = ContinuousBatchingScheduler(eng, slots=3, prompt_bucket=BUCKET,
                                        draft_engine=_engine(), spec_k=4)
    prompts = _prompts(6, seed=31)
    outs = [1, 7, 1, 8, 9, 1]
    stats, wants = _run(sched, watch,
                        [(p, n, None) for p, n in zip(prompts, outs)])
    _check(eng, stats, watch, wants)
    assert stats.decode_steps == SPEC_ONE_TOKEN_DECODE_STEPS_AT_PARENT
    assert stats.decode_steps_ahead == 0
    assert stats.decode_tokens_discarded == 0
    assert stats.first_tokens_behind_step == 0
    reads = [s for s in watch.closed
             if s.name == spans.SERVE_FIRST_TOKEN_READ]
    assert len(reads) == 6 and all(
        s.attrs["behind_step"] == 0 for s in reads)
    assert [s.attrs["request_id"] for s in reads] == sorted(wants)
    # the lanes of the one-token requests were refilled in the same
    # iteration: five admissions before the first verify pass
    emits = [e for e in watch.log if e[0] == "emit"]
    assert [e[1] for e in emits[:5]] == sorted(wants)[:5]
