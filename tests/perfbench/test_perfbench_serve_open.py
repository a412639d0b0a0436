"""The open-loop kind: a schedule that does not wait for the server, and a
verdict that is about the answers and nothing else."""
import contextlib
import json
import os
import time
import types

import pytest

import rehearsal
from perfbench.traffic_kinds import serve_open

TRAFFIC = dict(rehearsal.TRAFFIC["tiny-closed"], kind="serve_open",
               drain_seconds=0.1)


def _env(seed, rate, seconds=1.0, vocab=128):
    env = types.SimpleNamespace(
        traffic=dict(TRAFFIC, rate_per_s=rate),
        config={"model": {"vocab_size": vocab}}, seed=seed, seconds=seconds,
        trace=False, t_open=None, t_close=None,
        span=lambda name: contextlib.nullcontext())

    def open_window(host="host", trace_now=True):
        env.t_open = time.monotonic()

    def close_window():
        env.t_close = time.monotonic()

    env.open_window, env.close_window = open_window, close_window
    return env


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_the_schedule_is_the_seeds_and_every_seed_offers_the_same_work(seed):
    a = serve_open.plan(_env(seed, rate=80.0))
    b = serve_open.plan(_env(seed, rate=80.0))
    c = serve_open.plan(_env(seed + 1, rate=80.0))
    assert a.due == b.due != c.due
    assert len(a.due) == len(c.due) == 80          # rate x seconds, always
    assert a.due == sorted(a.due) and 0.0 < a.due[0] and a.due[-1] < 1.0
    assert a.requests[:40] == b.requests[:40]
    # ten whole cycles of the grid: each length exactly ten times, any seed
    lengths = sorted(len(p) for p, _ in c.requests[:40])
    assert lengths == sorted(TRAFFIC["prompt_lengths"] * 10)


def test_the_arrivals_are_a_poisson_stream_and_not_a_shaped_one():
    """Exponential gaps conditioned on the count alone: the number that
    falls into a tenth of the window varies as a binomial's does (a
    schedule that gave every slice its share would have no variance), and
    the gaps' coefficient of variation is an exponential's."""
    import numpy as np

    counts, cvs = [], []
    for seed in range(200):
        due = np.array(serve_open.plan(_env(seed, rate=720.0)).due)
        counts.append(int(np.sum((0.4 <= due) & (due < 0.5))))
        gaps = np.diff(due)
        cvs.append(gaps.std() / gaps.mean())
    # binomial(720, 0.1): mean 72, variance 64.8
    assert abs(np.mean(counts) - 72.0) < 2.0
    assert 45.0 < np.var(counts) < 90.0
    assert 0.95 < np.mean(cvs) < 1.05


class StuckScheduler:
    """Calls ``poll_fn`` like the real one and finishes the ramp; of the
    window's requests it answers only every other one, at once, and leaves
    the rest queued for ever."""
    prompt_bucket = 16

    def __init__(self):
        self.submitted, self.waiting = [], []

    def submit(self, prompt, max_new_tokens, stream_callback):
        rid = len(self.submitted)
        self.submitted.append(time.monotonic())
        self.waiting.append((rid, max_new_tokens, stream_callback))
        return rid

    def run(self, poll_fn):
        ramp = len(self.waiting)
        while True:
            poll_fn()
            keep = []
            for rid, want, cb in self.waiting:
                if rid < ramp or rid % 2 == 0:
                    for i in range(want):
                        cb(rid, 1, i == want - 1)
                else:
                    keep.append((rid, want, cb))
            self.waiting = keep
            time.sleep(0.002)


def _stuck_run(seed=3, rate=60.0):
    env = _env(seed, rate)
    plan = serve_open.plan(env)
    system = types.SimpleNamespace(
        scheduler=StuckScheduler(),
        first_token_margin=lambda prompt, token: {"margin": 0.0,
                                                  "tolerance": 0.01})
    record = serve_open.drive(env, system, plan)
    return env, plan, system, record


def test_arrivals_keep_their_schedule_while_requests_pile_up():
    env, plan, system, record = _stuck_run()
    sched = system.scheduler
    assert record["arrivals"] == len(plan.due) == 60
    sent = sched.submitted[len(plan.ramp):]
    assert len(sent) == 60
    for t, due in zip(sent, plan.due):
        # never early; late by at most the fake's iteration and a scheduling
        # hiccup of this machine, though 30 requests never finish
        assert -1e-6 <= t - (env.t_open + due) < 0.25
    assert len(record["in_flight"]) == 30 == len(record["at_close"])
    # the drain gave up after drain_seconds, with the queue still standing
    assert 0.1 <= record["drain_s"] < 0.5


def test_a_standing_queue_at_close_is_reported_and_still_correct():
    env, plan, system, record = _stuck_run()
    verdict = serve_open.check(env, system, plan, record)
    series = serve_open.series(env, system, plan, record)
    assert series["queue_depth_at_close"] == 30 \
        == verdict["queue_depth_at_close"]
    assert verdict["correct"] is True and verdict["failed"] == 0
    assert verdict["attempted"] == 30
    values = serve_open.end_to_end(series)
    assert values["queue_depth_at_close"] == 30
    assert values["unfinished_after_drain"] == 30
    # tokens of the requests due in the window: the answered half's
    served = sum(r.want for r in record["done"] if not r.ramp)
    assert series["tokens"] == served == series["tokens_in_window"]
    assert values["serve_out_tokens_per_s"] \
        == pytest.approx(served / series["window_s"])
    # each request is timed from when it was due, not when it got through
    assert min(series["ttft_ms"]) >= 0.0 and len(series["lateness_ms"]) == 60


class SlowScheduler(StuckScheduler):
    """Answers every request of the window, each 50 ms after it came."""

    def run(self, poll_fn):
        ramp = len(self.waiting)
        while True:
            poll_fn()
            now, keep = time.monotonic(), []
            for rid, want, cb in self.waiting:
                if rid < ramp or now - self.submitted[rid] >= 0.05:
                    for i in range(want):
                        cb(rid, 1, i == want - 1)
                else:
                    keep.append((rid, want, cb))
            self.waiting = keep
            time.sleep(0.002)


def test_what_the_drain_finishes_counts_and_the_window_alone_counts_less():
    env = _env(5, rate=200.0)
    plan = serve_open.plan(env)
    system = types.SimpleNamespace(scheduler=SlowScheduler())
    record = serve_open.drive(env, system, plan)
    series = serve_open.series(env, system, plan, record)
    assert record["at_close"] and not record["in_flight"]
    assert record["drain_s"] < 0.1              # ended when it was empty
    offered = sum(want for _, want in plan.requests[:200])
    assert series["tokens"] == offered > series["tokens_in_window"]
    values = serve_open.end_to_end(series)
    assert values["serve_out_tokens_per_s"] \
        > values["tokens_in_window_per_s"] > 0
    # times to the first token are the window's own
    after = [r for r in record["done"] if r.times[0] > env.t_close]
    assert after and len(series["ttft_ms"]) == 200 - len(after)


def test_a_wrong_answer_still_fails_it():
    env, plan, system, record = _stuck_run()
    record["done"][-1].tokens.append(1)         # one token too many
    verdict = serve_open.check(env, system, plan, record)
    assert verdict["correct"] is False and verdict["failed"] == 1


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal checkout with three more open-loop cells: one offered
    far more than a CPU serves, one whose queue holds a single request, and
    one with seconds of nothing between arrivals."""
    root = rehearsal.make_root(tmp_path_factory.mktemp("open"))
    small_queue = json.loads(json.dumps(rehearsal.CONFIGS["tiny-gpt"]))
    small_queue["name"] = "tiny-gpt-queue1"
    small_queue["serve"]["serving"]["max_pending"] = 1
    rehearsal._write(root, "perfbench/configs/tiny-gpt-queue1.json",
                     small_queue)
    cells = {"open-flood": ("tiny-gpt", 600.0),
             "open-reject": ("tiny-gpt-queue1", 600.0),
             "open-sparse": ("tiny-gpt", 1.5)}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-gpt-queue1", "source": "test", "reduced": [],
        "file": "perfbench/configs/tiny-gpt-queue1.json", "why": "test"})
    for name, (config, rate) in cells.items():
        rehearsal._write(root, f"perfbench/traffic/{name}.json",
                         dict(TRAFFIC, rate_per_s=rate))
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": name, "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "tiny-serve-open" in m.get("workloads", ()):
                m["workloads"].append(name)
    rehearsal._write(root, "BENCHMARK.json", bench)
    return root


def _verdict(err):
    line = [ln for ln in err.splitlines() if '"event": "run"' in ln][-1]
    return json.loads(line)


def test_more_than_it_serves_closes_with_a_queue_and_is_correct(root):
    rc, last, err = rehearsal.run_cell(root, "open-flood", trace=1,
                                       seconds=2.0)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"]["open_queue_depth_at_close"]["value"] > 0
    assert "arrival_lateness_p95_ms" in last["metrics"]
    run = _verdict(err)
    assert run["verdict"]["queue_depth_at_close"] > 0
    # every arrival was due before the close and was submitted by then
    assert run["verdict"]["arrivals"] == 1200
    assert run["compiles_in_window"] == 0


def test_a_rejected_submit_is_a_failed_operation(root):
    rc, last, err = rehearsal.run_cell(root, "open-reject", seconds=2.0)
    assert rc == 0, err[-2000:]
    assert last["correct"] is False and last["failed"] > 0
    assert _verdict(err)["verdict"]["rejected"] == ["QueueFullError"]


def test_an_empty_system_waits_for_the_next_arrival(root):
    """Three arrivals in two seconds: the scheduler's ``run`` returns when
    nothing is queued or active, so the generator has to hold it."""
    rc, last, err = rehearsal.run_cell(root, "open-sparse", seconds=2.0)
    assert rc == 0, err[-2000:]
    run = _verdict(err)
    assert run["window_s"] >= 2.0 and run["verdict"]["arrivals"] == 3
    assert last["correct"] is True and last["attempted"] >= 1


@pytest.mark.parametrize("trace", [0, 1])
def test_the_open_cell_rehearses_like_the_others(root, trace):
    rc, last, err = rehearsal.run_cell(root, "tiny-serve-open", trace=trace,
                                       seed=2 ** 31 + 11)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    want = {"serve_out_tokens_per_s", "gap_p95_ms", "setup_s"} if not trace \
        else {"compiles_in_window.serve", "sched_lane_occupancy",
              "open_queue_depth_at_close", "arrival_lateness_p95_ms"}
    assert want <= set(last["metrics"])
