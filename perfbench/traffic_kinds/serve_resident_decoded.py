"""``serve_resident`` (a closed loop of clients whose contexts are RESIDENT
when the window opens: decode over long caches, nothing admitted inside the
window) for a system whose decode step leaves no selection to judge: the
plan, the ramp, the drive, the series and the end-to-end metrics are that
kind's, imported; ``check`` is its own, without the selection.

``check`` judges the timed path's own output at the timed sizes. Every
token streamed in the window lies in the vocabulary and no request has more
tokens than it asked for. When the window closes the requests are in
flight, each in a lane of the scheduler's cache that the timed decode steps
wrote; for ``reference_samples`` seeded lanes of them (the builder's
``live_lanes``) the lane has taken in exactly the tokens its client was
streamed, and ONE pass of the plain reference, teacher-forced over prompt +
served tokens (the builder's ``judge_lane``), gives

* the margin of every served token, the first included, ``(largest logit -
  logit of the served token) / std`` at its position, held by
  ``serve_closed_decoded.judge_decode``'s three margin statistics, the
  first token also by the configuration's ``first_token_tolerance``;
* the relative norm of the difference between what the lane keeps and the
  reference's keys and values for the same tokens, by the builder's
  reading of its layers: ``mean_state_error`` (``by_layer``: the layers
  that keep every position, over every row the request wrote),
  ``mean_tail_error`` (``tail_by_layer``: the layers that keep a window's
  ring, over the rows the ring holds at the close, each matched to the
  reference's position by what the ring says it holds),
  ``first_layer_head_state_error`` (``by_head``: the model's first layer,
  by KV head, the largest head).

The limits are the configuration's ``serve.decode_check``. A run that
closes with no request in flight has nothing to read and is not correct.
"""
import time

import numpy as np

from perfbench.traffic_kinds.serve_closed_decoded import judge_decode
from perfbench.traffic_kinds.serve_resident import (  # noqa: F401
    ROLE,
    Plan,
    drive,
    end_to_end,
    plan,
    series,
    warm_up,
)


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
