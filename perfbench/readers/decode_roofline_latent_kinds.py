"""The decode step's share of its roofline where latent attention of two
kinds stands side by side: the bytes a step must move (every weight the
step reads once, of the held experts' matrices those of the experts that
got a row; each lane's newest ``min(context, window)`` latents and rotary
keys in the window layers; every LIVE index key and the CHOSEN latents and
rotary keys in the full layers: ``perfbench/dots3_flops.py``
``decode_step``) over the chip's HBM bandwidth, or the operations of both
absorbed forms and the indexer's scores over the chip's bf16 peak where
that takes longer, over the step's median device time. None where the
builder gives no such sizes or the run no live positions."""
from perfbench import dots3_flops, stats
from perfbench import trace_reduce as tr


def read(ctx):
    info = ctx.system.info
    steps = tr.module_durations_ms(ctx.red, info.get("decode_program", ""))
    sizes, weights = info.get("attention"), info.get("weights")
    seen = [getattr(ctx.system, name, lambda: None)() for name in (
        "mean_live_window_positions", "mean_live_positions",
        "mean_live_chosen_positions")]
    load = getattr(ctx.system, "step_expert_load", lambda: {})()
    share = load.get("experts_with_rows_share")
    if not steps or not sizes or not weights or None in seen \
            or share is None or "indexer" not in sizes:
        return None
    weight_bytes = dots3_flops.decode_weight_bytes(
        experts_read=share * info["experts_held"], **weights)
    need = dots3_flops.decode_step(
        weight_bytes, info["slots"], *seen, sizes["window_layers"],
        sizes["full_layers"], sizes["window"], sizes["full"],
        sizes["indexer"]["ix_heads"], sizes["indexer"]["ix_dim"],
        sizes["itemsize"])
    by_bytes = need["bytes"] / (ctx.env.peak["hbm_gb_per_s"] * 1e9) * 1e3
    by_flops = need["flops"] / (ctx.env.peak["bf16_tflops"] * 1e12) * 1e3
    ctx.notes["decode_roofline_latent_kinds"] = {
        "weight_bytes": weight_bytes, "experts_with_rows_share": share,
        "live_window_positions": seen[0], "live_positions": seen[1],
        "live_chosen_positions": seen[2], "bytes": need["bytes"],
        "flops": need["flops"], "least_ms_by_bytes": by_bytes,
        "least_ms_by_flops": by_flops}
    return 100.0 * max(by_bytes, by_flops) / stats.percentile(steps, 50)
