"""The flash calls' share of their roofline, as the reader this one
borrows ``classify`` from computes it, over the Mosaic calls whose HLO
instruction name starts with ``prefix`` only: in a program that holds other Mosaic calls
(the compiler's ragged-dot kernels) the flash calls are those that keep the
kernel's name (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``)."""
from perfbench import flops
from perfbench import trace_reduce as tr
from perfbench.readers.flash_roofline import classify


def read(ctx, prefix):
    shape = ctx.system.info.get("flash")
    if not shape:
        return None
    lo, hi = ctx.red.window
    least = actual = 0.0
    bounds = {}
    for dev in ctx.red.devices.values():
        for o in dev.ops:
            if not o.name.startswith(prefix) or tr.MOSAIC_TARGET not in o.text \
                    or o.start < lo or o.end > hi:
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
