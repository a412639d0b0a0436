"""``serve_resident`` (a closed loop of clients whose contexts are RESIDENT
when the window opens: decode over long caches, nothing admitted inside the
window) for a system whose layers keep different things by KIND and whose
full layers choose the rows they attend over: the plan, the ramp, the
drive, the series and the end-to-end metrics are that kind's, imported, and
so is its judgement of the selection; ``check`` is its own.

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
  reference's for the same tokens, by kind of layer: ``mean_state_error``
  (``by_layer``: the full layers' latents and rotary keys, over every row
  the request wrote), ``mean_index_key_error`` (``index_by_layer``: their
  index keys), ``mean_tail_error`` (``tail_by_layer``: the window layers'
  latents and rotary keys, over the rows a ring holds at the close, each
  matched to the reference's position by what the ring says it holds),
  ``first_layer_head_state_error`` (``by_head``: the model's first layer's
  latent);
* the selection (``serve_resident.judge_selection``): of the rows each
  lane's LAST decode step attended over in a full layer, the share that is
  not among the rows a query chooses of the lane's own stored index keys,
  scored in float64: ``mean_selection_miss`` for the reference's query,
  ``mean_choice_miss`` for the query the step left beside its rows;
* and of EVERY live lane (the builder's ``judge_steps``), its last decode
  step replayed by the reference from the lane's own stored rows and the
  step's own chosen rows: ``mean_step_row_error``, the relative norm of
  the difference between the row each layer after the first wrote for the
  step's token and the replayed one. The statistics above compare against
  the reference's own pass, so under seeded weights the choice's noise is
  most of them (PERF.md, PR 59); this one is given the choice and the
  state, so what it reads is one step's arithmetic: the decode softmax of
  both kinds and the routers.

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
    judge_selection,
    plan,
    series,
    warm_up,
)


def judge_index_keys(judged, limits):
    """``mean_index_key_error`` of the sampled lanes beside its limit."""
    index = np.asarray([j["errors"]["index_by_layer"] for j in judged],
                       np.float64)
    held = float(limits["mean_index_key_error_max"])
    return ({"mean_index_key_error": float(index.mean()),
             "index_key_error_by_layer": index.mean(0).tolist()},
            {"mean_index_key_error_max": held}, float(index.mean()) <= held)


def judge_steps(errors, limits):
    """``mean_step_row_error`` of ``errors [lanes, layers - 1]`` (the
    builder's ``judge_steps``) beside its limit; no lane is not correct."""
    held = float(limits["mean_step_row_error_max"])
    if not errors.size:
        return ({"step_lanes": 0}, {"mean_step_row_error_max": held}, False)
    return ({"mean_step_row_error": float(errors.mean()),
             "largest_step_row_error": float(errors.max()),
             "step_row_error_by_layer": errors.mean(0).tolist(),
             "step_row_errors": errors.round(5).tolist(),
             "step_lanes": len(errors)},
            {"mean_step_row_error_max": held}, float(errors.mean()) <= held)


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
    steps = np.asarray(system.judge_steps(record["by_rid"]), np.float64)
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
        for read, limit, ok in (judge_index_keys(judged, limits),
                                judge_selection(judged, limits),
                                judge_steps(steps, limits)):
            decode = dict(decode, **read,
                          limits=dict(decode["limits"], **limit),
                          ok=decode["ok"] and ok)
        # for the record (held to nothing): where the margins' tail lies
        decode["margin_quantiles"] = dict(zip(
            ("p50", "p90", "p99", "p999"), np.quantile(
                margins, (0.5, 0.9, 0.99, 0.999)).tolist()))
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
