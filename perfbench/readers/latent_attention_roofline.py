"""Latent attention's share of its roofline inside the decode program: the
least time the chip could take for the absorbed attention of one decode
step (``perfbench/mla_flops.py`` ``absorbed_step``: per layer the products
with the decompression matrices, and scores and weighted sums over every
LIVE latent, each read once; the live positions are the mean of what the
program's ``serve.stats`` events count from the scheduler's clocks, the
rows that the requests in the lanes have written) over the device time of
the program's instructions under ``scopes``, per run of the program in the
traced window. ``program`` is ``[module, name]`` of a constant the program
exports. None where the program has no such scopes, the builder no
``latent_attention`` sizes or the run no live positions."""
from perfbench import flops, mla_flops
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr


def step_counts(ctx):
    """``(sizes, lanes, positions)`` of a decode step of this run, or
    None."""
    sizes = ctx.system.info.get("latent_attention")
    live = getattr(ctx.system, "mean_live_positions", None)
    positions = live() if live else None
    if not sizes or not positions:
        return None
    lanes = ctx.series.get("lanes_active")
    lanes = sum(lanes) / len(lanes) if lanes else ctx.system.info["slots"]
    return sizes, lanes, positions


def read(ctx, scopes, program):
    prog = ps.of(ctx)
    found = step_counts(ctx)
    name = ps.program_constant(*program)
    if prog is None or prog.rows is None or not found or not name:
        return None
    sizes, lanes, positions = found
    sc = prog.scopes
    actual = sum(r["seconds"] for r in prog.rows
                 if r["program"] == name and sc.has_scope(r["path"], *scopes))
    runs = tr.module_runs(ctx.red, name)
    if not actual or not runs:
        return None
    layers = sizes["layers"]
    need = mla_flops.absorbed_step(
        lanes, positions, **{k: v for k, v in sizes.items() if k != "layers"})
    least, bound = flops.roofline_seconds(need["flops"], need["bytes"],
                                          ctx.env.peak)
    ctx.notes["latent_attention_roofline"] = {
        "runs": runs, "layers": layers, "lanes": lanes,
        "live_positions": positions, "bound": bound,
        "least_ms_per_layer": least * 1e3,
        "actual_ms_per_layer": actual / (runs * layers) * 1e3}
    return 100.0 * least * runs * layers / actual
