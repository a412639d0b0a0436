"""One field of the program's ``moe.load`` event (telemetry bus) for the
cell's batch: the routers' load per expert, counted by the program in one
forward pass after the window. None where the system has no such event."""


def read(ctx, field):
    load = getattr(ctx.system, "expert_load", None)
    if load is None:
        return None
    return load().get(field)
