"""A percentile of the duration of one of the program's own spans
(``ds:<span>`` in the profiler's trace), in milliseconds."""
from perfbench import program_spans as ps
from perfbench import stats


def read(ctx, span, q):
    prog = ps.of(ctx)
    if prog is None:
        return None
    return stats.percentile([s.dur_ms for s in ps.named(prog, span)], q)
