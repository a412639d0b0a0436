"""The grouped matmuls' share of their roofline: the least time the chip
could take for each call's FLOPs and bytes (from the builder's shapes, by
``perfbench.moe_flops``) over the device time the calls took. A call is a
device operation whose HLO instruction name starts with ``prefix`` (XLA's
TPU compiler names its kernel ``ragged-dot-none.<n>``) and not with
``skip`` (its small ``ragged-dot-metadata`` call, which is no matmul);
forward, the rows' gradient and the matrices' gradient all have the one
count."""
from perfbench import flops, moe_flops
from perfbench import trace_reduce as tr


def read(ctx, prefix, skip):
    shape = ctx.system.info.get("grouped_matmul")
    if not shape:
        return None
    lo, hi = ctx.red.window
    calls = [o for dev in ctx.red.devices.values() for o in dev.ops
             if o.name.startswith(prefix) and not o.name.startswith(skip)
             and lo <= o.start and o.end <= hi]
    if not calls:
        return None
    least, bound = flops.roofline_seconds(
        moe_flops.grouped_matmul_flops(
            shape["rows"], shape["d_model"], shape["d_hidden"]),
        moe_flops.grouped_matmul_bytes(
            shape["rows"], shape["d_model"], shape["d_hidden"],
            shape["groups"], shape["itemsize"]), ctx.env.peak)
    actual = sum(o.dur for o in calls) / 1e9
    ctx.notes["grouped_matmul_roofline"] = {
        "calls": len(calls), "bound": bound,
        "least_ms_per_call": least * 1e3,
        "actual_ms_per_call": actual / len(calls) * 1e3}
    return 100.0 * least * len(calls) / actual
