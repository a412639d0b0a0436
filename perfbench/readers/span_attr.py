"""A percentile of a numeric attribute of one of the program's own spans,
times ``scale`` (the program records microseconds; a metric may want
milliseconds)."""
from perfbench import program_spans as ps
from perfbench import stats


def read(ctx, span, attr, q, scale=1.0):
    prog = ps.of(ctx)
    if prog is None:
        return None
    values = [float(s.attrs[attr]) * scale for s in ps.named(prog, span)
              if attr in s.attrs]
    return stats.percentile(values, q)
