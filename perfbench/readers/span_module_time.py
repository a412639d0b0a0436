"""A percentile, over the spans called ``span``, of the device time of the
programs that ran inside each (the trace's ``XLA Modules`` line), in
milliseconds. ``program`` names a constant the program exports
(``[module, name]``): the programs whose name starts with its value
count."""
from perfbench import program_spans as ps
from perfbench import stats


def read(ctx, span, program, q):
    prog = ps.of(ctx)
    prefix = ps.program_constant(*program)
    if prog is None or not prefix:
        return None
    return stats.percentile(ps.module_ms_by_span(prog, span, prefix), q)
