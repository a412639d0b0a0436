"""``serve_closed`` with a verdict that follows the decode, not only the
first token.

The generator, the ramp, the driver, the series and the end-to-end metrics
are ``serve_closed``'s, re-exported unchanged. ``check`` is this kind's
own: as there, every request completed in the window has exactly the
tokens it asked for, all inside the vocabulary, and the served first token
of a seeded sample of them lies within the configuration's
``first_token_tolerance`` of the reference's largest last-position logit;
and, for the same sample, every served token after the first is held
against the plain reference teacher-forced over prompt + served tokens
(the builder's ``decoded_stats``): at each output position the margin
``(largest logit - logit of the served token) / std``. A model whose
decode runs through a state that is carried from token to token can be
right at the first token and wrong from the second.

The decode is judged on statistics over the sample's positions, not on its
worst token: with random weights some positions are near-ties, and bf16
takes the other side of a near-tie now and then (a hard limit on single
tokens refuses a run in sixty: PERF.md, section 7). The configuration's
``serve.decode_check`` gives the limits, set between what the system
reads over many seeds and what a deliberately lower precision reads:

* ``mean_margin_max``: the mean margin over all positions;
* ``share_within_tolerance_min``: the share of positions whose margin is
  at most ``first_token_tolerance``;
* ``largest_margin_max``: a cap on the largest single margin.

The margins say that the served tokens are the reference's, and a token is
the same under errors far larger than any arithmetic's (a state kept in
half the bits, or weights in 8, moved no margin on the chip). So the
precision of the decode is read from what the window itself left on the
device: when the window closes, ``live_lanes`` requests are in flight,
each in a lane of the scheduler's cache that the timed decode steps wrote.
For a seeded sample of them the builder's ``live_lanes`` takes the lane's
recurrent state and convolution tail out of that cache, with the tokens
the lane has taken in, which must be the ones the client was streamed; the
reference then gives the state after the same tokens, and per layer (and
per head) the norm of the difference over the norm of the reference's is
held by:

* ``mean_state_error_max``: the mean over lanes and layers;
* ``first_layer_head_state_error_max``: of the first layer, whose inputs
  carry the least error of their own (the error grows with depth), the
  largest head's error, each head's averaged over the lanes: how a state
  is stored shows in the heads that remember longest;
* ``mean_tail_error_max``: the same mean for the convolution's tail.

A run that closes with no request in flight has nothing to read and is
not correct.
"""
import time

import numpy as np

from perfbench.traffic_kinds.serve_closed import (  # noqa: F401
    ROLE,
    drive,
    end_to_end,
    plan,
    series,
    warm_up,
)


def judge_decode(margins, tolerance, limits, lanes=None):
    """The statistics of ``margins`` (a flat list over positions) and of
    ``lanes`` (the builder's ``state_errors`` of each sampled live lane)
    beside their limits, and whether all of them hold."""
    m = np.asarray(margins, np.float64)
    read = {"positions": int(m.size), "mean_margin": float(m.mean()),
            "share_within_tolerance": float((m <= tolerance).mean()),
            "largest_margin": float(m.max())}
    held = {k: float(limits[k]) for k in (
        "mean_margin_max", "share_within_tolerance_min",
        "largest_margin_max")}
    ok = (read["mean_margin"] <= held["mean_margin_max"]
          and read["share_within_tolerance"]
          >= held["share_within_tolerance_min"]
          and read["largest_margin"] <= held["largest_margin_max"])
    if lanes is not None:
        by_layer = np.asarray([e["by_layer"] for e in lanes], np.float64)
        first = np.asarray([e["by_head"][0] for e in lanes], np.float64)
        tails = np.asarray([e["tail_by_layer"] for e in lanes], np.float64)
        read.update(
            lanes=len(lanes), mean_state_error=float(by_layer.mean()),
            first_layer_head_state_error=float(first.mean(0).max()),
            mean_tail_error=float(tails.mean()),
            state_error_by_layer=by_layer.mean(0).tolist(),
            first_layer_state_error_by_head=first.mean(0).tolist(),
            tail_error_by_layer=tails.mean(0).tolist())
        for stat in ("mean_state_error", "first_layer_head_state_error",
                     "mean_tail_error"):
            held[stat + "_max"] = float(limits[stat + "_max"])
            ok = ok and read[stat] <= held[stat + "_max"]
    return dict(read, limits=held, ok=bool(ok))


def check(env, system, plan, record):
    lo, hi = env.t_open, env.t_close
    done = [r for r in record["done"] if lo <= r.times[-1] <= hi]
    bad = [r for r in done if len(r.tokens) != r.want
           or not all(0 <= t < plan.vocab for t in r.tokens)]
    rng = np.random.default_rng([env.seed, 3])
    k = min(int(env.traffic["reference_samples"]), len(done))
    sample = [done[i] for i in rng.choice(len(done), size=k, replace=False)]
    serve = env.config["serve"]
    tolerance = float(serve["first_token_tolerance"])
    limits = serve["decode_check"]
    # before anything else runs on the device: what the window left there
    lanes = system.live_lanes(int(limits["live_lanes"]),
                              np.random.default_rng([env.seed, 4]))
    t0 = time.monotonic()
    stats = [system.decoded_stats(r.prompt, r.tokens) for r in sample]
    held = [record["by_rid"][lane["request_id"]] for lane in lanes]
    streamed = all(r.tokens == lane["tokens"] for r, lane in zip(held, lanes))
    errors = [system.state_errors(r.prompt, lane)
              for r, lane in zip(held, lanes)]
    reference_s = time.monotonic() - t0
    first = [{"margin": float(s["margin"][0]), "tolerance": tolerance,
              "prompt_len": len(r.prompt), "outputs": len(r.tokens),
              "is_argmax": s["margin"][0] == 0.0}
             for r, s in zip(sample, stats)]
    first_ok = all(f["margin"] <= tolerance for f in first)
    after = [x for s in stats for x in s["margin"][1:]]
    decode = judge_decode(after, tolerance, limits, errors) \
        if after and lanes else {"positions": len(after), "lanes": len(lanes),
                                 "ok": False}
    return {"correct": (bool(done) and not bad and first_ok and streamed
                        and decode["ok"]),
            "attempted": len(done), "failed": len(bad),
            "in_flight_at_close": len(record["in_flight"]),
            "reference": first, "decode": decode,
            "live_lanes": [{"lane": lane["lane"], "prompt_len": len(r.prompt),
                            "taken_in": len(lane["tokens"])}
                           for r, lane in zip(held, lanes)],
            "live_lanes_streamed_their_tokens": streamed,
            "reference_s": reference_s}
