"""A part of the selection's share of its roofline inside the decode
program, where attention runs over the positions an indexer chooses: the
least time the chip could take for one decode step's indexer scores
(``which`` "index": ``perfbench/dsa_flops.py`` ``index_step``, every LIVE
position's index key read once a lane and layer) or for its attention over
the chosen rows (``which`` "attention": ``chosen_attention_step``, each
lane's ``min(topk, live)`` rows of keys and of values read once), by the
roofline of ``peaks.json``, times the layers, over the device time of the
program's instructions under ``scopes``, per run of the program in the
traced window. The live positions are the mean over the window of what the
program's ``serve.stats`` counts (the builder's ``mean_live_positions``);
the chosen ones what the decode program left in the cache's
``chosen_rows`` at the window's last step, all lanes (its
``mean_selected_positions``: a program that attends without choosing
leaves none, and nothing is read). ``program`` is
``[module, name]`` of a constant the program exports. None where the
program has no such scopes, the builder no ``selected_attention`` sizes or
the run no live positions."""
from perfbench import dsa_flops, flops
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr


def step_counts(ctx):
    """``(sizes, live positions, chosen positions)`` of a decode step of
    this run, or None."""
    sizes = ctx.system.info.get("selected_attention")
    live = getattr(ctx.system, "mean_live_positions", None)
    chosen = getattr(ctx.system, "mean_selected_positions", None)
    live, chosen = live() if live else None, chosen() if chosen else None
    if not sizes or not live or not chosen:
        return None
    return sizes, live, chosen


def least(sizes, live, chosen, which):
    if which == "index":
        return dsa_flops.index_step(live, sizes["ix_heads"], sizes["ix_dim"],
                                    sizes["itemsize"])
    return dsa_flops.chosen_attention_step(
        chosen, sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"],
        sizes["itemsize"])


def read(ctx, which, scopes, program):
    prog = ps.of(ctx)
    found = step_counts(ctx)
    name = ps.program_constant(*program)
    if prog is None or prog.rows is None or not found or not name:
        return None
    sizes, live, chosen = found
    sc = prog.scopes
    actual = sum(r["seconds"] for r in prog.rows
                 if r["program"] == name and sc.has_scope(r["path"], *scopes))
    runs = tr.module_runs(ctx.red, name)
    if not actual or not runs:
        return None
    need = least(sizes, live, chosen, which)
    seconds, bound = flops.roofline_seconds(need["flops"], need["bytes"],
                                            ctx.env.peak)
    layers = sizes["layers"]
    ctx.notes["selected_attention_roofline:" + which] = {
        "runs": runs, "layers": layers, "live_positions": live,
        "selected_positions": chosen, "bound": bound,
        "least_ms_per_layer": seconds * 1e3,
        "actual_ms_per_layer": actual / (runs * layers) * 1e3}
    return 100.0 * seconds * runs * layers / actual
