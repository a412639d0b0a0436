"""The decode step's share of its bandwidth roofline where a lane holds
recurrent state beside keys and values: the bytes a step must move (every
weight read once, the live part of the KV cache read, and each active
lane's state and convolution tail read and written once) over the chip's
HBM bandwidth, over the step's median device time. None where the builder
gives no ``state_bytes_per_lane``."""
from perfbench import stats
from perfbench import trace_reduce as tr


def read(ctx):
    info = ctx.system.info
    steps = tr.module_durations_ms(ctx.red, info.get("decode_program", ""))
    live = ctx.series.get("live_positions")
    state = info.get("state_bytes_per_lane")
    if not steps or not live or not state:
        return None
    lanes = ctx.series.get("lanes_active")
    active = sum(lanes) / len(lanes) if lanes else info["slots"]
    nbytes = info["weight_bytes"] + active * (
        (sum(live) / len(live)) * info["kv_bytes_per_position"]
        + 2.0 * state)
    least_ms = nbytes / (ctx.env.peak["hbm_gb_per_s"] * 1e9) * 1e3
    ctx.notes["decode_roofline_state"] = {
        "weight_bytes": info["weight_bytes"], "lanes_active": active,
        "state_bytes_moved": 2.0 * state * active, "least_ms": least_ms}
    return 100.0 * least_ms / stats.percentile(steps, 50)
