"""A closed loop of clients whose contexts are RESIDENT when the window
opens: decode over long caches, with no admission inside the window.

``clients`` callers on as many lanes, each with one long prompt and an
answer of ``output_tokens`` tokens. Set-up submits every client's request
and drives ``scheduler.run(poll_fn=...)`` until each has streamed
``ramp_tokens`` tokens: by then every prefill bucket has compiled or
loaded its program, every cache is built and the decode program has run.
The window opens there and closes ``--seconds`` later; every token
streamed inside it counts for throughput and gaps. A request that ends
inside the window is followed by its client's next, as in
``serve_closed`` (at the sizes of the cell that brought this kind none
does: the answers outlast the window). The run ends where the window
closes, or one scheduler iteration later where that poll finds every lane
between a request that has just ended and its queued successor.

The prompt lengths are stratified, not sampled: the traffic file fixes a
grid, every cycle of ``len(grid)`` requests uses each value once, and the
seed chooses only the order and the token ids. Every request asks for the
same ``output_tokens``, so which request would end first is the order of
admission and not a pairing the seed draws. So every seed offers the same
work, in another order.

The generator's pieces, the series and the recorded request are
``serve_closed``'s, imported, and so are the end-to-end metrics but for
the time to first token (no request is submitted inside the window); the
ramp and ``check`` are this kind's own.

``check`` judges the timed path's own output at the timed sizes. Every
token streamed in the window lies in the vocabulary and no request has
more tokens than it asked for. When the window closes the requests are in
flight, each in a lane of the scheduler's cache that the timed decode
steps wrote; for ``reference_samples`` seeded lanes of them (the
builder's ``live_lanes``) the lane has taken in exactly the tokens its
client was streamed, and ONE pass of the plain reference, teacher-forced
over prompt + served tokens (the builder's ``judge_lane``), gives

* the margin of every served token, the first included, ``(largest logit
  - logit of the served token) / std`` at its position, held by
  ``serve_closed_decoded.judge_decode``'s three margin statistics, the
  first token also by the configuration's ``first_token_tolerance``;
* the relative norm of the difference between what the lane keeps a
  position and the reference's, over every row its request wrote:
  ``mean_state_error`` (keys and values, lanes and layers),
  ``first_layer_head_state_error`` (the first layer's, by KV head),
  ``mean_tail_error`` (the index keys);
* the selection itself: of the rows each lane's LAST decode step attended
  over, as the decode program left them in the lane's cache, the share
  that is not among the rows a query chooses of the lane's own stored
  index keys, scored in float64, lanes and layers: ``mean_selection_miss``
  for the reference's query (whose distance from the program's, a few
  rows at the boundary of a set, is the system's own rounding) and
  ``mean_choice_miss`` for the query the step left beside its rows (given
  its own inputs a step chooses as float32 scores do, to a row).

The limits are the configuration's ``serve.decode_check``. A run that
closes with no request in flight has nothing to read and is not correct.
"""
import time
from dataclasses import dataclass

import numpy as np

from perfbench.traffic_kinds import serve_closed
from perfbench.traffic_kinds.serve_closed import (  # noqa: F401
    ROLE,
    Req,
    WindowClosed,
    bucketed,
    series,
    warm_up,
)
from perfbench.traffic_kinds.serve_closed_decoded import judge_decode


@dataclass
class Plan:
    prompt_lengths: list
    output_tokens: int
    ramp_tokens: int
    clients: int
    bucket: int
    vocab: int
    seed: int
    cursor: int = 0
    _made: tuple = (None, None)     # the cycle last made, and its number

    def cycle(self, c):
        """The ``c``-th cycle of requests: each prompt length once."""
        rng = np.random.default_rng([self.seed, 1, c])
        return [(rng.integers(0, self.vocab,
                              size=self.prompt_lengths[i]).tolist(),
                 self.output_tokens)
                for i in rng.permutation(len(self.prompt_lengths))]

    def next_request(self):
        """Requests in cycle order; a cycle is made when it is reached."""
        c, i = divmod(self.cursor, len(self.prompt_lengths))
        self.cursor += 1
        if self._made[0] != c:
            self._made = (c, self.cycle(c))
        return self._made[1][i]


def plan(env):
    p = env.traffic
    prompts = [int(x) for x in p["prompt_lengths"]]
    bucket, clients = int(p["prompt_bucket"]), int(p["clients"])
    out, ramp = int(p["output_tokens"]), int(p["ramp_tokens"])
    if bucketed(max(prompts), bucket) + out > int(p["max_positions"]):
        raise ValueError("bucketed prompt + output exceeds max_positions")
    if not 1 <= ramp < out:
        raise ValueError("ramp_tokens must lie in 1..output_tokens - 1: a "
                         "request that ends in the ramp is not resident")
    if clients % len(prompts):
        raise ValueError("the clients are whole cycles of the grid, so "
                         "that every seed's ramp offers the same work")
    return Plan(prompt_lengths=prompts, output_tokens=out, ramp_tokens=ramp,
                clients=clients, bucket=bucket,
                vocab=int(env.config["model"]["vocab_size"]), seed=env.seed)


def drive(env, system, plan):
    sched = system.scheduler
    seconds = env.seconds
    trace_from = seconds - float(env.traffic["trace_seconds"])
    live, by_rid, done_reqs, events = {}, {}, [], []
    state = {"ramping": plan.clients}

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
            if req.ramp and len(req.tokens) == plan.ramp_tokens:
                state["ramping"] -= 1
            if done:
                del live[rid]
                done_reqs.append(req)
                if env.t_close is None:
                    submit(req.client, *plan.next_request(), ramp=False)

    def poll():
        with env.span("poll"):
            if env.t_open is None:
                if state["ramping"] == 0:
                    env.open_window(host="scheduler",
                                    trace_now=trace_from <= 0)
                return
            elapsed = time.monotonic() - env.t_open
            if elapsed >= seconds:
                if env.t_close is None:
                    env.close_window()
                # One ``output_tokens`` for all: requests admitted together
                # end at one step, and at the poll after it their
                # successors are queued and no lane holds a request. What
                # the run leaves has to hold one for ``check`` to read, so
                # the scheduler admits them (one iteration) before it ends;
                # their tokens fall after the close and count for nothing.
                if any(r.tokens for r in live.values()):
                    raise WindowClosed
                return
            if env.trace and not env.tracing and elapsed >= trace_from:
                env.start_trace()

    def on_event(ev):       # telemetry bus, traced runs only
        if ev.get("kind") in ("serve.admit", "serve.stats"):
            events.append((time.monotonic(), ev))

    for c in range(plan.clients):
        submit(c, *plan.next_request(), ramp=True)
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


def end_to_end(s):
    """``serve_closed.end_to_end`` of the same series (the metrics' names
    live in that module alone: ``test_perfbench_contract.py``). No request
    is submitted inside the window at the sizes this kind is for, and the
    closed kind's statistics of the times to first token need one: they
    are given a placeholder, and what they made of it is dropped."""
    if s["ttft_ms"]:
        return serve_closed.end_to_end(s)
    out = serve_closed.end_to_end(dict(s, ttft_ms=[0.0]))
    return dict({k: v for k, v in out.items() if not k.startswith("ttft_")},
                n_ttft=0)


def judge_selection(judged, limits):
    """The two statistics of the builder's ``selection`` of each sampled
    lane (``miss_by_layer``, ``choice_miss_by_layer``) beside their
    limits, and whether both hold; what else the builder reads of the
    selection rides along."""
    def of(name):
        return np.asarray([j["selection"][name] for j in judged], np.float64)

    miss, own = of("miss_by_layer"), of("choice_miss_by_layer")
    read = {"mean_selection_miss": float(miss.mean()),
            "mean_choice_miss": float(own.mean()),
            "selection_miss_by_lane": miss.mean(1).tolist()}
    for name in judged[0]["selection"]:
        read["selection_" + name] = of(name).mean(0).tolist()
    held = {k + "_max": float(limits[k + "_max"])
            for k in ("mean_selection_miss", "mean_choice_miss")}
    return read, held, all(read[k[:-4]] <= v for k, v in held.items())


def check(env, system, plan, record):
    lo, hi = env.t_open, env.t_close
    reqs = record["done"] + record["in_flight"]
    inside = [r for r in reqs if any(lo <= t <= hi for t in r.times)]
    bad = [r for r in inside if len(r.tokens) > r.want
           or not all(0 <= t < plan.vocab for t in r.tokens)]
    serve = env.config["serve"]
    tolerance = float(serve["first_token_tolerance"])
    limits = serve["decode_check"]
    # before anything else runs on the device: what the window left there
    lanes = system.live_lanes(int(env.traffic["reference_samples"]),
                              np.random.default_rng([env.seed, 4]))
    t0 = time.monotonic()
    held = [record["by_rid"][lane["request_id"]] for lane in lanes]
    streamed = all(r.tokens == lane["tokens"] for r, lane in zip(held, lanes))
    judged = [system.judge_lane(r.prompt, lane)
              for r, lane in zip(held, lanes)]
    reference_s = time.monotonic() - t0
    first = [{"margin": float(j["margin"][0]), "tolerance": tolerance,
              "prompt_len": len(r.prompt), "outputs": len(r.tokens),
              "is_argmax": j["margin"][0] == 0.0}
             for r, j in zip(held, judged)]
    first_ok = all(f["margin"] <= tolerance for f in first)
    margins = [x for j in judged for x in j["margin"]]
    decode = {"positions": 0, "lanes": 0, "ok": False}
    if lanes:
        decode = judge_decode(margins, tolerance, limits,
                              [j["errors"] for j in judged])
        read, limit, chosen_ok = judge_selection(judged, limits)
        decode = dict(decode, **read, limits=dict(decode["limits"], **limit),
                      ok=decode["ok"] and chosen_ok)
    return {"correct": (bool(inside) and not bad and first_ok and streamed
                        and decode["ok"]),
            "attempted": len(inside), "failed": len(bad),
            "in_flight_at_close": len(record["in_flight"]),
            "reference": first, "decode": decode,
            "live_lanes": [{"lane": lane["lane"], "prompt_len": len(r.prompt),
                            "taken_in": len(lane["tokens"])}
                           for r, lane in zip(held, lanes)],
            "live_lanes_streamed_their_tokens": streamed,
            "reference_s": reference_s}
