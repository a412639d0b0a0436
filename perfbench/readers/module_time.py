"""A percentile of the device time of one compiled program per run (the
trace's ``XLA Modules`` line), in milliseconds. ``program`` is a key of the
builder's ``info`` that holds the program's name."""
from perfbench import stats
from perfbench import trace_reduce as tr


def read(ctx, program, q):
    name = ctx.system.info.get(program)
    if not name:
        return None
    return stats.percentile(tr.module_durations_ms(ctx.red, name), q)
