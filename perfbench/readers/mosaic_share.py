"""Device time of the Mosaic (Pallas) custom calls over device busy time."""
from perfbench import trace_reduce as tr


def read(ctx):
    mosaic = sum(tr.op_seconds(
        ctx.red, keep=lambda o: tr.MOSAIC_TARGET in o.text).values())
    busy = tr.busy_seconds(ctx.red)
    if not mosaic or not busy:
        return None
    return 100.0 * mosaic / busy
