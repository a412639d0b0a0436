"""The window flash calls' share of their roofline: the least time the chip
could take for each call's FLOPs and bytes under its window
(``perfbench.swa_flops``: the band's pairs, K and V at their own head
count) over the device time the calls took. A call is a Mosaic call whose
HLO instruction name starts with ``prefix`` (``window_flash_fwd``,
``window_flash_bwd_dq``, ``window_flash_bwd_dkv``); its kind is read from
its signature as ``flash_roofline.classify`` reads it, the window and the
KV heads from the builder's ``info["window_flash"]``. None where the
program holds no such call (a program from before the kernels existed) or
the builder says nothing of a window."""
from perfbench import flops, swa_flops
from perfbench import trace_reduce as tr
from perfbench.readers.flash_roofline import classify


def read(ctx, prefix):
    shape = ctx.system.info.get("window_flash")
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
                swa_flops.window_flash_call_flops(kind, bh, t, d,
                                                  shape["window"]),
                swa_flops.window_flash_call_bytes(
                    kind, bh, shape["kv_heads"], t, d, shape["itemsize"]),
                ctx.env.peak)
            least += secs
            actual += o.dur / 1e9
            bounds[bound] = bounds.get(bound, 0) + 1
    if not actual:
        return None
    ctx.notes["window_flash_roofline_bound"] = bounds
    return 100.0 * least / actual
