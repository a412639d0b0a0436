"""The flash-attention calls' share of their roofline: the least time the
chip could take for each call's FLOPs and bytes (from its shapes, by
``perfbench.flops``) over the device time the calls took.

The trace names a Mosaic call only by its HLO instruction, so the kind of
call is read from its signature: the forward returns ``(o, lse)``, the
backward for dQ one tensor, the backward for dK and dV two of one shape.
"""
import re

from perfbench import flops
from perfbench import trace_reduce as tr

_TENSOR = re.compile(r"(\w+)\[([\d,]+)\]")


def classify(text):
    """``(kind, bh, t, d)`` of a flash custom call's HLO text, or None."""
    head = text.split(" custom-call(", 1)[0].split(" = ", 1)[-1]
    results = [(dt, [int(x) for x in dims.split(",")])
               for dt, dims in _TENSOR.findall(head)]
    if not results or len(results[0][1]) != 3:
        return None
    bh, t, d = results[0][1]
    if len(results) == 1:
        return "bwd_dq", bh, t, d
    if len(results) == 2 and results[1][1] == results[0][1]:
        return "bwd_dkv", bh, t, d
    if len(results) == 2 and results[1][0] == "f32":
        return "fwd", bh, t, d
    return None


def read(ctx):
    shape = ctx.system.info.get("flash")
    if not shape:
        return None
    lo, hi = ctx.red.window
    least = actual = 0.0
    bounds = {}
    for dev in ctx.red.devices.values():
        for o in dev.ops:
            if tr.MOSAIC_TARGET not in o.text or o.start < lo or o.end > hi:
                continue
            call = classify(o.text)
            if call is None:
                continue
            kind, bh, t, d = call
            secs, bound = flops.roofline_seconds(
                flops.flash_call_flops(kind, bh, t, d, shape["causal"]),
                flops.flash_call_bytes(kind, bh, t, d, shape["itemsize"]),
                ctx.env.peak)
            least += secs
            actual += o.dur / 1e9
            bounds[bound] = bounds.get(bound, 0) + 1
    if not actual:
        return None
    ctx.notes["flash_roofline_bound"] = bounds
    return 100.0 * least / actual
