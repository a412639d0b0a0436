"""The decode step's share of its bandwidth roofline where attention runs
over the positions an indexer chooses: the bytes a step must move (every
weight the step reads once, the held experts' matrices among them; every
LIVE index key once; each lane's chosen rows of keys and values once:
``perfbench/dsa_flops.py`` ``decode_step_bytes``) over the chip's HBM
bandwidth, over the step's median device time. None where the builder
gives no ``selected_attention`` sizes or the run no live positions."""
from perfbench import dsa_flops, stats
from perfbench import trace_reduce as tr
from perfbench.readers import selected_attention_roofline


def read(ctx):
    info = ctx.system.info
    steps = tr.module_durations_ms(ctx.red, info.get("decode_program", ""))
    found = selected_attention_roofline.step_counts(ctx)
    if not steps or not found:
        return None
    sizes, live, chosen = found
    nbytes = dsa_flops.decode_step_bytes(
        info["weight_bytes"], live, chosen, sizes["layers"],
        sizes["n_kv_heads"], sizes["head_dim"], sizes["ix_dim"],
        sizes["itemsize"])
    least_ms = nbytes / (ctx.env.peak["hbm_gb_per_s"] * 1e9) * 1e3
    ctx.notes["decode_roofline_selected"] = {
        "weight_bytes": info["weight_bytes"], "live_positions": live,
        "selected_positions": chosen, "bytes": nbytes,
        "least_ms": least_ms}
    return 100.0 * least_ms / stats.percentile(steps, 50)
