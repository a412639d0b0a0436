"""Share of the traced window in which the device ran nothing while the
host was under one of the program's own spans (``inside``), less the part
under another (``outside``): which part of the serve loop each idle gap
belongs to. In percent of the window, averaged over the chips."""
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr


def read(ctx, inside, outside=None):
    prog = ps.of(ctx)
    window = tr.window_seconds(ctx.red)
    if prog is None or not window or not ps.named(prog, inside):
        return None
    return 100.0 * ps.idle_seconds(prog, inside, outside) / window
