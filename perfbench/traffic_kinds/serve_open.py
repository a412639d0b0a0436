"""An open loop of independent users against the continuous-batching
scheduler.

Requests arrive on a schedule that is fixed before the window opens and
kept whether or not earlier requests have finished: a Poisson process from
``--seed``, exponential gaps at ``rate_per_s``, conditioned on its count and
on nothing else. ``rate_per_s`` times ``--seconds`` arrivals (rounded) fall
inside the window: the cumulative sums of one more exponential gap than
there are arrivals, scaled so that the arrival after the last falls on the
close. Arrivals bunch and thin out as independent users' do, over seconds
as well: one window's last two seconds hold fifty requests and another's
twenty, queues form and drain, and some windows close with a queue. The
lengths are the closed kind's stratified grids (every cycle of 40 uses each
value once), so every seed offers the same requests at other moments and in
another order.

The ramp is the closed kind's: one request per lane, one in every prefill
bucket the grid uses, so that every program the window will run has been
compiled or loaded. The window opens when the last ramp request is done, on
an empty system, and lasts ``--seconds``.

The generator lives in ``scheduler.run``'s ``poll_fn``, which the scheduler
calls before every iteration: it submits every arrival that is due. When
nothing is queued or active it waits there for the next arrival, because a
``run`` that finds the system empty returns. An arrival that falls due
while an iteration runs is submitted when that iteration ends; the
scheduler could not have admitted it earlier. Each request is timed from
when it was DUE, not from when it was submitted, so the generator's
lateness (a series of its own) is inside the time to first token and not
hidden by it.

The tokens per second served (the closed kind's end-to-end metric, from
``series["tokens"]``) are the tokens of the requests that were due in the
window, over the window's length. After the close nothing more arrives
and the scheduler runs on for at most ``drain_seconds`` to finish what it
holds; what it streams then counts, what it has not streamed by then does
not. Below the rate the system sustains that is the offered load, whatever
stood in the lanes at the instant of the close; a system that falls behind
by more than the drain can hold loses the rest. (Counting the tokens
streamed inside the window only, the log's ``tokens_in_window_per_s``,
subtracts one sample of the backlog, which a Poisson stream moves by 1-1.6%
of a 45 s window from seed to seed: PERF.md, section 6, PR 27.) Gaps and
times to the first token are taken inside the window only.

``correct`` is the closed kind's and nothing else: every request completed
inside the window has exactly the tokens it asked for, all in the
vocabulary; the seeded sample's first tokens lie within the configuration's
tolerance of the reference's largest logit; and no ``submit`` was rejected
or shed (each one that is counts in ``failed``). Whether a queue stood or
grew when the window closed, how late the generator was, how full the lanes
were and how many requests were in flight at close are traffic, not wrong
answers: they are series for the per-layer metrics and fields of the log.
"""
import dataclasses
import time

import numpy as np

from perfbench.traffic_kinds import serve_closed
from perfbench.traffic_kinds.serve_closed import Req, WindowClosed

ROLE = "serve"


@dataclasses.dataclass
class Plan(serve_closed.Plan):
    due: list = dataclasses.field(default_factory=list)  # s after open


def plan(env):
    base = serve_closed.plan(env)
    rate = float(env.traffic["rate_per_s"])
    n = int(round(rate * env.seconds))
    rng = np.random.default_rng([env.seed, 4])
    at = np.cumsum(rng.exponential(1.0 / rate, size=n + 1))
    due = (at[:n] * (env.seconds / at[n])).tolist()
    return Plan(**{f.name: getattr(base, f.name)
                   for f in dataclasses.fields(base)}, due=due)


warm_up = serve_closed.warm_up


def drive(env, system, plan):
    sched = system.scheduler
    seconds = env.seconds
    trace_from = seconds - float(env.traffic["trace_seconds"])
    drain = float(env.traffic["drain_seconds"])
    live, by_rid, done_reqs, events = {}, {}, [], []
    rejected, lateness = [], []
    state = {"ramp_left": len(plan.ramp), "next": 0}

    def submit(prompt, want, ramp, t_due):
        # t_submit is the DUE time: every later time counts from it
        req = Req(client=-1, prompt=prompt, want=want, ramp=ramp,
                  t_submit=t_due)
        try:
            rid = sched.submit(prompt, max_new_tokens=want,
                               stream_callback=on_token)
        except Exception as e:  # rejected or shed: a failed operation
            rejected.append(type(e).__name__)
            state["ramp_left"] -= ramp
            return
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
                state["ramp_left"] -= req.ramp

    def poll():
        with env.span("poll"):
            if env.t_close is not None:     # draining: nothing arrives
                if not live or time.monotonic() >= state["drain_until"]:
                    raise WindowClosed
                return
            if env.t_open is None:
                if state["ramp_left"]:
                    return
                env.open_window(host="scheduler", trace_now=trace_from <= 0)
            while True:
                elapsed = time.monotonic() - env.t_open
                if env.trace and not env.tracing and trace_from <= elapsed \
                        < seconds:
                    env.start_trace()
                while state["next"] < len(plan.due) \
                        and plan.due[state["next"]] <= elapsed:
                    t_due = env.t_open + plan.due[state["next"]]
                    state["next"] += 1
                    lateness.append((time.monotonic() - t_due) * 1e3)
                    submit(*plan.next_request(), ramp=False, t_due=t_due)
                if elapsed >= seconds:      # every arrival was due by now
                    state["at_close"] = [bool(r.times)
                                         for r in live.values()]
                    env.close_window()    # a traced run's stops its trace
                    state["drain_until"] = time.monotonic() + drain
                    return
                if live:
                    return
                # an empty system: wait here for the next arrival (or the
                # trace's start, or the close), or run() would return
                wake = [seconds] + plan.due[state["next"]:state["next"] + 1]
                if env.trace and not env.tracing:
                    wake.append(trace_from)
                time.sleep(max(0.0, min(wake)
                               - (time.monotonic() - env.t_open)))

    def on_event(ev):       # telemetry bus, traced runs only
        if ev.get("kind") in ("serve.admit", "serve.stats"):
            events.append((time.monotonic(), ev))

    t0 = time.monotonic()
    for prompt, want in plan.ramp:
        submit(prompt, want, ramp=True, t_due=t0)
    if env.trace:
        system.subscribe(on_event)
    try:
        sched.run(poll_fn=poll)     # returns if the drain empties it
    except WindowClosed:
        pass
    finally:
        if env.trace:
            system.unsubscribe(on_event)
    if env.t_close is None:
        raise RuntimeError("the scheduler returned before the window "
                           "closed")
    return {"done": done_reqs, "in_flight": list(live.values()),
            "by_rid": by_rid, "events": events, "rejected": rejected,
            "lateness_ms": lateness, "arrivals": state["next"],
            "at_close": state["at_close"],
            "drain_s": time.monotonic() - env.t_close}


def queue_depth_at_close(record):
    """Requests submitted that had no first token when the window closed:
    queued, or in their prefill."""
    return sum(1 for started in record["at_close"] if not started)


def series(env, system, plan, record):
    """The closed kind's series (time to first token counts from the due
    time here) and the open loop's own."""
    out = serve_closed.series(env, system, plan, record)
    out["tokens_in_window"] = out["tokens"]
    # of the requests due in the window, streamed by the end of the drain
    out["tokens"] = sum(len(r.times) for r in record["by_rid"].values()
                        if not r.ramp)
    out["lateness_ms"] = record["lateness_ms"]
    out["arrivals"] = record["arrivals"]
    out["rejected"] = len(record["rejected"])
    out["in_flight_at_close"] = len(record["at_close"])
    out["queue_depth_at_close"] = queue_depth_at_close(record)
    out["drain_s"] = record["drain_s"]
    out["unfinished_after_drain"] = len(record["in_flight"])
    return out


def end_to_end(s):
    """The closed kind's statistics of the same samples and, for the log,
    what the open loop adds (the harness prints only what
    ``BENCHMARK.json`` names)."""
    out = serve_closed.end_to_end(s)
    late = sorted(s["lateness_ms"])
    out.update(
        tokens_in_window_per_s=s["tokens_in_window"] / s["window_s"],
        arrivals=s["arrivals"], rejected=s["rejected"],
        offered_requests_per_s=s["arrivals"] / s["window_s"],
        in_flight_at_close=s["in_flight_at_close"],
        queue_depth_at_close=s["queue_depth_at_close"],
        drain_s=s["drain_s"],
        unfinished_after_drain=s["unfinished_after_drain"],
        lateness_p50_ms=late[len(late) // 2] if late else 0.0,
        lateness_max_ms=late[-1] if late else 0.0)
    return out


def check(env, system, plan, record):
    """The closed kind's verdict (tokens, vocabulary, reference margins),
    with every rejected ``submit`` a failed operation. Queue, lateness and
    requests in flight at close are in the log and decide nothing."""
    verdict = serve_closed.check(env, system, plan, record)
    rejected = record["rejected"]
    verdict["attempted"] += len(rejected)
    verdict["failed"] += len(rejected)
    verdict["correct"] = verdict["correct"] and not rejected
    verdict["rejected"] = sorted(set(rejected))
    verdict["arrivals"] = record["arrivals"]
    verdict["in_flight_at_close"] = len(record["at_close"])
    verdict["queue_depth_at_close"] = queue_depth_at_close(record)
    return verdict
