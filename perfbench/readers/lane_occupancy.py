"""Active lanes over slots, averaged over the scheduler's iterations in the
window (its ``serve.stats`` events)."""


def read(ctx):
    lanes = ctx.series.get("lanes_active")
    slots = ctx.system.info.get("slots")
    if not lanes or not slots:
        return None
    return 100.0 * sum(lanes) / len(lanes) / slots
