"""Device time of the operations whose HLO instruction name starts with
``prefix`` over device busy time. On the TPU a Pallas kernel of the
program's keeps its kernel's name (``flash_fwd.3``), so a prefix tells the
flash calls from the compiler's own Mosaic calls (``ragged-dot-none.7``)."""
from perfbench import trace_reduce as tr


def read(ctx, prefix):
    named = sum(tr.op_seconds(
        ctx.red, keep=lambda o: o.name.startswith(prefix)).values())
    busy = tr.busy_seconds(ctx.red)
    if not named or not busy:
        return None
    return 100.0 * named / busy
