"""1 - the union of the device-operation intervals over the traced window,
averaged over the chips, in percent."""
from perfbench import trace_reduce as tr


def read(ctx):
    window = tr.window_seconds(ctx.red)
    if not window or not ctx.red.devices:
        return None
    return 100.0 * (1.0 - tr.busy_seconds(ctx.red) / window)
