"""A share of a lane's cache, from the program's ``serve.cache_plan`` event
(telemetry bus, published once when the scheduler lays its lane cache
out): the bytes of the fields ``part`` over the bytes of the field
``whole``, in percent. None where the system kept no such event (a
program from before it existed)."""


def read(ctx, part, whole):
    plan = getattr(ctx.system, "cache_plan", None)
    if not plan or not plan.get(whole):
        return None
    return 100.0 * sum(plan[f] for f in part) / plan[whole]
