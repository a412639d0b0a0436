"""Share of the traced window in which a collective (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute) runs or is in
flight, averaged over the chips."""
from perfbench import trace_reduce as tr


def read(ctx):
    flight, _ = tr.collective_seconds(ctx.red)
    window = tr.window_seconds(ctx.red)
    if not flight or not window:
        return None
    return 100.0 * flight / window
