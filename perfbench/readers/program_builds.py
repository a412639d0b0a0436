"""Set-up by the program's own account: what the program's build log
(``deepspeed_tpu.telemetry.builds``, handed out by ``program_builds()`` on
the system under test: its scheduler when it serves, its engine otherwise)
holds of the programs built before the window opened. The log keeps one
row per stage of each build (``trace``, ``lower``, ``compile_or_load``) on
the monotonic clock that ``env.t_open`` is on, says of each compile whether
the persistent cache served it, and keeps the wall time of the first call
of each specialisation of a dispatched program, from the call to the end of
its compile or load.

``what`` chooses the number:

* ``seconds``: the length of the union of the intervals of ``stage`` (a
  nested jit is traced inside its caller's tracing: a sum would count it
  twice);
* ``programs``: the compiles or loads that ended, one-operation eager
  programs included;
* ``cache_misses``: of those, the ones the cache was asked for and did not
  have;
* ``first_dispatch``: the sum of the first calls' wall times.

None where the program has no such log (a checkout from before it), and
where the trace has no device plane (a rehearsal on the CPU: times of a
machine nobody measures are left out like the rest) unless
``without_device`` is set, which only the tests do. The first call leaves
the cut log's summary in the run's notes: the stages' seconds inside the
first calls, each first call, and the programs the cache did not serve.
"""
from perfbench import trace_reduce as tr

_ATTR = "_program_builds"
COMPILE_OR_LOAD = "compile_or_load"


def union_seconds(intervals):
    return tr.total(tr.union(intervals))


def summary(log, t_open):
    """What the run's log line says of the cut log."""
    rows, stages = log["rows"], sorted(log["seconds"])
    inside = {s: union_seconds((r["start"], r["end"]) for r in rows
                               if r["stage"] == s and "dispatch" in r)
              for s in stages}
    calls = []
    for d in log["dispatches"]:
        mine = [r for r in rows if r.get("key") == d["key"]
                and r.get("dispatch") == d["program"]
                and d["start"] <= r["end"] <= d["end"]]
        calls.append([d["program"], d["key"],
                      round(d["first_dispatch_s"], 4)] + [
            round(union_seconds((r["start"], r["end"]) for r in mine
                                if r["stage"] == s), 4) for s in stages])
    compiled = [r for r in rows if r["stage"] == COMPILE_OR_LOAD]
    return {
        "since_entry_s": None if log.get("entered") is None
        else t_open - log["entered"],
        "rows": {s: sum(1 for r in rows if r["stage"] == s)
                 for s in stages},
        "seconds": log["seconds"], "seconds_in_first_calls": inside,
        "first_calls": {"columns": ["program", "key", "first_dispatch_s"]
                        + stages, "rows": calls},
        "not_from_cache": sorted(
            r["program"] for r in compiled if r.get("cache_hit") is False),
        "cache_not_asked": sum(
            1 for r in compiled if r.get("cache_hit") is None),
    }


def of(ctx):
    """The build log cut at the window's opening, loaded once per run;
    None on a checkout whose program keeps none."""
    log = getattr(ctx, _ATTR, None)
    if log is None:
        owner = getattr(ctx.system, "scheduler", None) \
            or getattr(ctx.system, "engine", None)
        builds = getattr(owner, "program_builds", None)
        if builds is None:
            return None
        log = builds(before=ctx.env.t_open)
        setattr(ctx, _ATTR, log)
        ctx.notes["program_builds"] = summary(log, ctx.env.t_open)
    return log


def read(ctx, what, stage=None, without_device=False):
    if not ctx.red.devices and not without_device:
        return None
    log = of(ctx)
    if log is None:
        return None
    if what == "seconds":
        return log["seconds"][stage]
    if what == "first_dispatch":
        calls = log["dispatches"]
        return sum(d["first_dispatch_s"] for d in calls) if calls else None
    compiled = [r for r in log["rows"] if r["stage"] == COMPILE_OR_LOAD]
    if what == "programs":
        return len(compiled)
    if what == "cache_misses":
        return sum(1 for r in compiled if r.get("cache_hit") is False)
    raise ValueError(f"program_builds: nothing called {what!r}")
