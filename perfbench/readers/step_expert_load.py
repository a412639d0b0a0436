"""One share of the program's ``moe.load`` event (telemetry bus) for a
decode step's rows, one seeded token a lane, counted by the program in one
forward pass after the window (the builder's ``step_expert_load``), in
percent. None where the system has no such pass or the event no such
field (a program from before it existed)."""


def read(ctx, field):
    load = getattr(ctx.system, "step_expert_load", None)
    value = None if load is None else load().get(field)
    return None if value is None else 100.0 * value
