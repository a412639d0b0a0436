"""The decode step's share of its bandwidth roofline where window layers
stand beside layers that see everything: the bytes a step must move (every
weight the step reads once, of the held experts' matrices those of the
experts that got a row; every LIVE row of the full layers; each lane's
newest ``min(context, window)`` rows of the window layers:
``perfbench/afmoe_flops.py`` ``decode_step_bytes``) over the chip's HBM
bandwidth, over the step's median device time. None where the builder
gives no such sizes or the run no live positions."""
from perfbench import afmoe_flops, stats
from perfbench import trace_reduce as tr


def read(ctx):
    info = ctx.system.info
    steps = tr.module_durations_ms(ctx.red, info.get("decode_program", ""))
    sizes, weights = info.get("attention"), info.get("weights")
    seen = [getattr(ctx.system, name, lambda: None)() for name in (
        "mean_live_window_positions", "mean_live_positions")]
    load = getattr(ctx.system, "step_expert_load", lambda: {})()
    share = load.get("experts_with_rows_share")
    if not steps or not sizes or not weights or None in seen \
            or share is None:
        return None
    weight_bytes = afmoe_flops.decode_weight_bytes(
        experts_read=share * info["experts_held"], **weights)
    nbytes = afmoe_flops.decode_step_bytes(
        weight_bytes, seen[0], seen[1], sizes["window_layers"],
        sizes["full_layers"], sizes["heads"]["n_kv_heads"],
        sizes["heads"]["head_dim"], sizes["itemsize"])
    least_ms = nbytes / (ctx.env.peak["hbm_gb_per_s"] * 1e9) * 1e3
    ctx.notes["decode_roofline_window"] = {
        "weight_bytes": weight_bytes, "experts_with_rows_share": share,
        "live_window_positions": seen[0], "live_positions": seen[1],
        "bytes": nbytes, "least_ms": least_ms}
    return 100.0 * least_ms / stats.percentile(steps, 50)
