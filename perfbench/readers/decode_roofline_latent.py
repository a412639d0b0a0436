"""The decode step's share of its roofline where a lane's cache is latent
attention's: the bytes a step must move (every weight the step reads once,
the held experts' matrices among them, and every LIVE latent and rotary
key once) over the chip's HBM bandwidth, or the absorbed attention's
operations over the chip's bf16 peak where that takes longer (128 heads
over one latent sit on the chip's ridge), over the step's median device
time. None where the builder gives no ``latent_attention`` sizes or the
run no live positions."""
from perfbench import mla_flops, stats
from perfbench import trace_reduce as tr
from perfbench.readers import latent_attention_roofline


def read(ctx):
    info = ctx.system.info
    steps = tr.module_durations_ms(ctx.red, info.get("decode_program", ""))
    found = latent_attention_roofline.step_counts(ctx)
    if not steps or not found:
        return None
    sizes, lanes, positions = found
    layers = sizes["layers"]
    attention = mla_flops.absorbed_step(
        lanes, positions, **{k: v for k, v in sizes.items() if k != "layers"})
    nbytes = info["weight_bytes"] \
        + positions * info["kv_bytes_per_position"]
    by_bytes = nbytes / (ctx.env.peak["hbm_gb_per_s"] * 1e9) * 1e3
    by_flops = layers * attention["flops"] \
        / (ctx.env.peak["bf16_tflops"] * 1e12) * 1e3
    ctx.notes["decode_roofline_latent"] = {
        "weight_bytes": info["weight_bytes"], "lanes_active": lanes,
        "live_positions": positions,
        "latent_bytes_read": positions * info["kv_bytes_per_position"],
        "least_ms_by_bytes": by_bytes, "least_ms_by_attention_flops": by_flops}
    return 100.0 * max(by_bytes, by_flops) / stats.percentile(steps, 50)
