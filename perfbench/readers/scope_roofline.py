"""A scope's share of its roofline inside one program: the least time the
chip could take for the operations and bytes the scope's work needs in
each run of the program (from the builder's ``info[counts]``: ``flops`` and
``bytes`` of one call and ``calls_per_step``, computed from shapes by a
function kept with the benchmark) over the device time of the program's
instructions under the scope, both over the program's runs inside the
traced window. ``program`` is ``[module, name]`` of a constant the program
exports. None where the program has no such scope or the builder no such
counts."""
from perfbench import flops
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr


def read(ctx, scope, program, counts):
    prog = ps.of(ctx)
    need = ctx.system.info.get(counts)
    name = ps.program_constant(*program)
    if prog is None or prog.rows is None or not need or not name:
        return None
    sc = prog.scopes
    actual = sum(r["seconds"] for r in prog.rows
                 if r["program"] == name and sc.has_scope(r["path"], scope))
    runs = tr.module_runs(ctx.red, name)
    if not actual or not runs:
        return None
    least, bound = flops.roofline_seconds(need["flops"], need["bytes"],
                                          ctx.env.peak)
    calls = runs * need["calls_per_step"]
    ctx.notes["scope_roofline:" + scope] = {
        "runs": runs, "calls": calls, "bound": bound,
        "least_ms_per_call": least * 1e3,
        "actual_ms_per_call": actual / calls * 1e3}
    return 100.0 * least * calls / actual
