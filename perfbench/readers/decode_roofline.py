"""The decode step's share of its bandwidth roofline: the bytes a step
must read (every weight once, and the live part of the KV cache: the
positions the active lanes really hold) over the chip's HBM bandwidth,
over the step's median device time."""
from perfbench import stats
from perfbench import trace_reduce as tr


def read(ctx):
    info = ctx.system.info
    steps = tr.module_durations_ms(ctx.red, info.get("decode_program", ""))
    live = ctx.series.get("live_positions")
    if not steps or not live:
        return None
    lanes = ctx.series.get("lanes_active")
    active = sum(lanes) / len(lanes) if lanes else info["slots"]
    nbytes = info["weight_bytes"] + active * (sum(live) / len(live)) \
        * info["kv_bytes_per_position"]
    least_ms = nbytes / (ctx.env.peak["hbm_gb_per_s"] * 1e9) * 1e3
    return 100.0 * least_ms / stats.percentile(steps, 50)
