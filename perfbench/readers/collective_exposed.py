"""Milliseconds per step in which a collective runs or is in flight and no
other operation runs on that chip, averaged over the chips."""
from perfbench import trace_reduce as tr


def read(ctx):
    flight, exposed = tr.collective_seconds(ctx.red)
    steps = tr.module_runs(ctx.red, ctx.system.info.get("step_program", ""))
    if not flight or not steps:
        return None
    return exposed * 1e3 / steps
