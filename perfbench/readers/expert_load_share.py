"""One count of the program's ``moe.load`` event (telemetry bus) for the
cell's batch over another, in percent: the routers' pairs as the program
counted them in one forward pass after the window. None where the system
has no such event or the event lacks either count."""


def read(ctx, part, whole):
    load = getattr(ctx.system, "expert_load", None)
    if load is None:
        return None
    event = load()
    if not event.get(whole) or event.get(part) is None:
        return None
    return 100.0 * event[part] / event[whole]
