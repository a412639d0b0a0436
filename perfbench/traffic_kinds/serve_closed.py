"""A closed loop of clients against the continuous-batching scheduler.

``clients`` callers, each submitting its next request from the
``stream_callback`` that ends its previous one, driven through
``scheduler.run(poll_fn=...)``: callers that wait for their reply. The
lengths are stratified, not sampled: the traffic file fixes a grid of prompt
lengths and one of output lengths, every cycle of ``len(grid)`` requests
uses each value once, and the seed chooses only the order, the pairing and
the token ids. So every seed offers the same work, in another order.

One ``run()`` call holds ramp and window. The ramp is each client's first
request: together they prefill once in every bucket the grid uses (which
compiles or loads every program the window will run) and they carry
staggered output lengths, so the clients leave the ramp out of step with
each other. When every client has completed its ramp request the window
opens; ``--seconds`` later ``poll_fn`` closes it and ends the run. Only
requests submitted inside the window count for time-to-first-token; every
token streamed inside it counts for throughput and gaps.

A traced run keeps the whole window, so that its host-clock series have as
many samples as an untraced run's, and records the profiler's trace over the
last ``trace_seconds`` of it.
"""
import time
from dataclasses import dataclass, field

import numpy as np

from perfbench import stats

ROLE = "serve"


class WindowClosed(Exception):
    """Raised from ``poll_fn`` to end ``scheduler.run`` with the window."""


@dataclass
class Req:
    client: int
    prompt: list
    want: int
    ramp: bool
    t_submit: float = 0.0
    times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)


def bucketed(n, bucket):
    return -(-n // bucket) * bucket


@dataclass
class Plan:
    prompt_lengths: list
    output_lengths: list
    clients: int
    bucket: int
    vocab: int
    seed: int
    ramp: list                      # [(prompt, n_out)] one per client
    requests: list                  # [(prompt, n_out)] pregenerated
    cursor: int = 0

    def cycle(self, c):
        """The ``c``-th cycle of requests: each grid value once."""
        rng = np.random.default_rng([self.seed, 1, c])
        n = len(self.prompt_lengths)
        ps, os_ = rng.permutation(n), rng.permutation(n)
        return [(rng.integers(0, self.vocab,
                              size=self.prompt_lengths[i]).tolist(),
                 int(self.output_lengths[j])) for i, j in zip(ps, os_)]

    def next_request(self):
        if self.cursor >= len(self.requests):   # a faster server than planned
            self.requests.extend(
                self.cycle(len(self.requests) // len(self.prompt_lengths)))
        self.cursor += 1
        return self.requests[self.cursor - 1]


def plan(env):
    p = env.traffic
    vocab = int(env.config["model"]["vocab_size"])
    prompts = [int(x) for x in p["prompt_lengths"]]
    outs = [int(x) for x in p["output_lengths"]]
    bucket, clients = int(p["prompt_bucket"]), int(p["clients"])
    if len(prompts) != len(outs):
        raise ValueError("the two grids must have one length")
    limit = int(p["max_positions"])
    if bucketed(max(prompts), bucket) + max(outs) > limit:
        raise ValueError("bucketed prompt + output exceeds max_positions")
    # ramp: one prompt in every bucket the grid uses, outputs staggered
    by_bucket = {}
    for n in prompts:
        by_bucket.setdefault(bucketed(n, bucket), n)
    lengths = sorted(by_bucket.values(), reverse=True)
    if len(lengths) > clients:
        raise ValueError(f"the grid uses {len(lengths)} prefill buckets and "
                         f"the ramp has {clients} clients to warm them")
    rng = np.random.default_rng([env.seed, 2])
    step = int(p["ramp_output_step"])
    ramp = [(rng.integers(0, vocab, size=lengths[c % len(lengths)]).tolist(),
             step * (c + 1)) for c in range(clients)]
    out = Plan(prompt_lengths=prompts, output_lengths=outs, clients=clients,
               bucket=bucket, vocab=vocab, seed=env.seed, ramp=ramp,
               requests=[])
    for c in range(-(-int(p["pregenerate_requests"]) // len(prompts))):
        out.requests.extend(out.cycle(c))
    return out


def warm_up(env, system, plan):
    """Nothing apart from the ramp, which shares the window's ``run()``."""
    if system.scheduler.prompt_bucket != plan.bucket:
        raise ValueError(
            f"the scheduler buckets prompts by "
            f"{system.scheduler.prompt_bucket}, the traffic file by "
            f"{plan.bucket}")


def drive(env, system, plan):
    sched = system.scheduler
    seconds = env.seconds
    trace_from = seconds - float(env.traffic["trace_seconds"])
    live, by_rid, done_reqs, events = {}, {}, [], []
    state = {"ramp_left": plan.clients}

    def submit(client, prompt, want, ramp):
        req = Req(client=client, prompt=prompt, want=want, ramp=ramp)
        req.t_submit = time.monotonic()
        rid = sched.submit(prompt, max_new_tokens=want,
                           stream_callback=on_token)
        live[rid] = by_rid[rid] = req

    def on_token(rid, token, done):
        now = time.monotonic()
        with env.span("stream_callback"):
            req = live[rid]
            req.times.append(now)
            req.tokens.append(int(token))
            if done:
                del live[rid]
                done_reqs.append(req)
                if req.ramp:
                    state["ramp_left"] -= 1
                if env.t_close is None:
                    submit(req.client, *plan.next_request(), ramp=False)

    def poll():
        with env.span("poll"):
            if env.t_open is None:
                if state["ramp_left"] == 0:
                    env.open_window(host="scheduler",
                                    trace_now=trace_from <= 0)
                return
            elapsed = time.monotonic() - env.t_open
            if elapsed >= seconds:
                env.close_window()
                raise WindowClosed
            if env.trace and not env.tracing and elapsed >= trace_from:
                env.start_trace()

    def on_event(ev):       # telemetry bus, traced runs only
        if ev.get("kind") in ("serve.admit", "serve.stats"):
            events.append((time.monotonic(), ev))

    for c, (prompt, want) in enumerate(plan.ramp):
        submit(c, prompt, want, ramp=True)
    if env.trace:
        system.subscribe(on_event)
    try:
        sched.run(poll_fn=poll)
    except WindowClosed:
        pass
    finally:
        if env.trace:
            system.unsubscribe(on_event)
    if env.t_close is None:
        raise RuntimeError("the scheduler ran dry before the window closed")
    return {"done": done_reqs, "in_flight": list(live.values()),
            "by_rid": by_rid, "events": events}


def series(env, system, plan, record):
    """Named series for end-to-end metrics and readers, all from the
    benchmark's own clock around ``stream_callback``."""
    lo, hi = env.t_open, env.t_close
    reqs = record["done"] + record["in_flight"]
    tokens = sum(1 for r in reqs for t in r.times if lo <= t <= hi)
    ttft = [(r.times[0] - r.t_submit) * 1e3 for r in reqs
            if r.t_submit >= lo and r.times and r.times[0] <= hi]
    gaps = [(b - a) * 1e3 for r in reqs
            for a, b in zip(r.times, r.times[1:]) if a >= lo and b <= hi]
    # cache positions a lane holds when it emits its k-th token
    live = [bucketed(len(r.prompt), plan.bucket) + k for r in reqs
            for k, t in enumerate(r.times) if lo <= t <= hi]
    out = {"tokens": tokens, "window_s": hi - lo, "ttft_ms": ttft,
           "gap_ms": gaps, "live_positions": live,
           "requests_completed": sum(1 for r in record["done"]
                                     if lo <= r.times[-1] <= hi)}
    # scheduler events (traced runs): admit -> first token, lanes in use
    out["admit_ms"] = [
        (record["by_rid"][ev["request_id"]].times[0] - t) * 1e3
        for t, ev in record["events"]
        if ev["kind"] == "serve.admit" and lo <= t <= hi
        and record["by_rid"][ev["request_id"]].times]
    out["lanes_active"] = [ev["lanes_active"] for t, ev in record["events"]
                           if ev["kind"] == "serve.stats" and lo <= t <= hi]
    return out


def end_to_end(s):
    """The cell's end-to-end metrics from ``series`` and, beside them for
    the log, other statistics of the same samples (the harness prints only
    what ``BENCHMARK.json`` names)."""
    ttft, gap = s["ttft_ms"], s["gap_ms"]
    return {
        "serve_out_tokens_per_s": s["tokens"] / s["window_s"],
        "ttft_p95_ms": stats.percentile(ttft, 95),
        "gap_p95_ms": stats.percentile(gap, 95),
        "ttft_tail10_ms": stats.tail_mean(ttft, 0.1),
        "gap_tail10_ms": stats.tail_mean(gap, 0.1),
        "ttft_p50_ms": stats.percentile(ttft, 50),
        "ttft_p90_ms": stats.percentile(ttft, 90),
        "ttft_tail20_ms": stats.tail_mean(ttft, 0.2),
        "ttft_mean_ms": sum(ttft) / len(ttft),
        "gap_p50_ms": stats.percentile(gap, 50),
        "gap_p99_ms": stats.percentile(gap, 99),
        "gap_tail5_ms": stats.tail_mean(gap, 0.05),
        "gap_mean_ms": sum(gap) / len(gap),
        "n_ttft": len(ttft), "n_gap": len(gap),
        "ttft_highest_supported_percentile":
            stats.highest_supported(len(ttft)),
        "requests_completed": s["requests_completed"],
    }


def check(env, system, plan, record):
    """Every request completed in the window has exactly the tokens it asked
    for, all inside the vocabulary; and for a seeded sample of them the
    plain reference's logits at the prompt's last position put the served
    first token within the builder's tolerance of the maximum."""
    lo, hi = env.t_open, env.t_close
    done = [r for r in record["done"] if lo <= r.times[-1] <= hi]
    bad = [r for r in done if len(r.tokens) != r.want
           or not all(0 <= t < plan.vocab for t in r.tokens)]
    rng = np.random.default_rng([env.seed, 3])
    k = min(int(env.traffic["reference_samples"]), len(done))
    sample = [done[i] for i in rng.choice(len(done), size=k, replace=False)]
    margins = [system.first_token_margin(r.prompt, r.tokens[0])
               for r in sample]
    ok = [m["margin"] <= m["tolerance"] for m in margins]
    return {"correct": bool(done) and not bad and all(ok),
            "attempted": len(done), "failed": len(bad),
            "in_flight_at_close": len(record["in_flight"]),
            "reference": margins}
