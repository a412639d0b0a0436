"""The mean of a numeric attribute of one of the program's own spans,
times ``scale``: with a 0/1 attribute and a scale of 100, the percentage
of the spans that carry a 1. Spans without the attribute (a program from
before it existed) are left out; none left gives None."""
from perfbench import program_spans as ps


def read(ctx, span, attr, scale=1.0):
    prog = ps.of(ctx)
    if prog is None:
        return None
    values = [float(s.attrs[attr]) for s in ps.named(prog, span)
              if attr in s.attrs]
    return scale * sum(values) / len(values) if values else None
