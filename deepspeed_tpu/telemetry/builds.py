"""Which programs this process built, and what each build cost.

JAX reports every program it builds as three timed stages: tracing the
Python function to a jaxpr, lowering the jaxpr to an MLIR module, and the
backend's compile, which a persistent-cache hit turns into a load. This
module keeps them: one process-wide :data:`build_log`, fed by one pair of
``jax.monitoring`` listeners that the entry points register
(``initialize`` / ``init_inference`` / ``build_serving`` call
:meth:`BuildLog.listen`), with one row per stage of each build on
``time.monotonic()``:

    {"program": JAX's fun_name, "stage": "trace" | "lower" |
     "compile_or_load", "start": .., "end": .., "nth": ..}

``nth`` counts the rows of that stage and name so far (2: that program was
built again, for another shape or after a cache eviction). A
``compile_or_load`` row also says whether the persistent cache served it
(``cache_hit``: True, False when the cache was asked and had nothing, None
when JAX did not ask it). A row that ended inside the first call of a
``telemetry.scopes.DispatchedProgram`` specialisation carries that
program's name (``dispatch``) and the specialisation (``key``: a prompt
bucket, a scan length), and each such first call is a row of its own in
``dispatches`` with its wall time from the call to the end of its compile
or load (``first_dispatch_s``): less the three stages inside it, that is
JAX's own path to the executable.

A nested jit is traced inside its caller's tracing, so a stage's seconds
are the length of the UNION of its rows' intervals (:func:`union_seconds`),
never their sum.

When a program's compile or load ends the log publishes one bus event,
``program.built`` (docs/observability.md "Program builds"). Nothing here is
switched on or off: like ``telemetry.span``, whoever asks
(``program_builds()`` on the engines and the scheduler) gets it. The
listeners run only when JAX builds something; a program that is already
built costs nothing.

stdlib only at import; jax is imported where the listeners are registered
and where a span is written.
"""
import threading
import time

from deepspeed_tpu.telemetry.bus import KIND_PROGRAM_BUILT, publish
from deepspeed_tpu.telemetry.spans import PROGRAM_BUILD, span

TRACE = "trace"
LOWER = "lower"
COMPILE_OR_LOAD = "compile_or_load"
STAGES = (TRACE, LOWER, COMPILE_OR_LOAD)

# JAX's duration events (jax/_src/dispatch.py), each with ``fun_name``
STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE_OR_LOAD,
}
# the persistent cache's plain events (jax/_src/compiler.py), fired inside
# the backend-compile interval of the program they are about: the cache was
# asked for it, and the cache had it
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def union_seconds(intervals):
    """The length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def _traced_name(program):
    """The name a lowered or compiled ``program`` (``jit(f)``, ``pmap(f)``)
    was traced under (``f``)."""
    return program[program.index("(") + 1:-1] \
        if program.endswith(")") and "(" in program else program


class _Building(threading.local):
    """What one thread is in the middle of building."""
    first_call = None   # (row of ``dispatches``, its span, perf_counter)
    traces = None       # {name: its last trace row} since the last compile
    lower = None        # the last lower row no compile has taken
    cache_hit = None    # what the cache said of the compile in flight


class BuildLog:
    """The builds of one process. ``rows`` and ``dispatches`` only grow;
    a row's ``nth`` and its place in ``rows`` are taken under one lock (JAX
    builds on whatever thread calls it); what a thread is in the middle of
    building is kept per thread."""

    def __init__(self):
        self.entered = None     # monotonic time of the first entry point
        self.rows = []
        self.dispatches = []
        self._counts = {}
        self._lock = threading.Lock()
        self._local = _Building()
        # bound once: jax.monitoring finds a listener again by equality
        self._listeners = (self._on_duration, self._on_event)

    def listen(self):
        """Register the log's two listeners with ``jax.monitoring``; each
        entry point calls this. However often it is called, and whatever
        ``jax.monitoring.clear_event_listeners()`` did in between, each
        listener is registered exactly once afterwards."""
        from jax import monitoring

        if self.entered is None:
            self.entered = time.monotonic()
        on_duration, on_event = self._listeners
        for unregister, register, fn in (
                (monitoring.unregister_event_duration_listener,
                 monitoring.register_event_duration_secs_listener,
                 on_duration),
                (monitoring.unregister_event_listener,
                 monitoring.register_event_listener, on_event)):
            try:
                unregister(fn)
            except (AssertionError, ValueError):    # was not registered
                pass
            register(fn)

    def first_call(self, fn, key):
        """The call of the jitted ``fn`` that follows on this thread is the
        first under the specialisation ``key``: JAX is about to trace,
        lower and compile or load it. Opens the row of ``dispatches`` and
        the span ``ds:program.build``; both end when that program's
        compile or load does (:meth:`_end_first_call`), so the call itself
        stays as it was: no frame and no ``with`` between a caller and the
        program."""
        self._end_first_call(built=False)
        row = {"program": "jit(%s)" % getattr(fn, "__name__", "?"),
               "key": repr(key)}
        scope = span(PROGRAM_BUILD, program=row["program"], key=row["key"])
        scope.__enter__()
        row["start"] = time.monotonic()
        self._local.first_call = (row, scope, time.perf_counter())

    def _end_first_call(self, built=True):
        """Close this thread's open first call, if any. One that the next
        first call closes, not its own compile (the program raised, or was
        built already), is not kept."""
        if self._local.first_call is None:
            return
        row, scope, t0 = self._local.first_call
        self._local.first_call = None
        row["first_dispatch_s"] = time.perf_counter() - t0
        row["end"] = time.monotonic()
        scope.__exit__(None, None, None)
        if built:
            self.dispatches.append(row)

    # -- listeners ---------------------------------------------------------
    def _on_event(self, event, **kw):
        if event == CACHE_REQUEST_EVENT:
            self._local.cache_hit = False
        elif event == CACHE_HIT_EVENT:
            self._local.cache_hit = True

    def _on_duration(self, event, secs, **kw):
        stage = STAGE_OF_EVENT.get(event)
        if stage is None:
            return
        # JAX fires the event as the stage ends: its end is now
        end = time.monotonic()
        local = self._local
        program = str(kw.get("fun_name", "?"))
        row = {"program": program, "stage": stage, "start": end - secs,
               "end": end}
        first = local.first_call
        if first is not None:
            row["dispatch"] = first[0]["program"]
            row["key"] = first[0]["key"]
        if stage == TRACE:
            # by name: lowering traces helpers of its own after the
            # program's trace has ended, so the last row is not the program's
            if local.traces is None:
                local.traces = {}
            local.traces[program] = row
        elif stage == LOWER:
            local.lower = row
        else:
            row["cache_hit"], local.cache_hit = local.cache_hit, None
        with self._lock:
            row["nth"] = self._counts[stage, program] = \
                self._counts.get((stage, program), 0) + 1
            self.rows.append(row)
        if stage == COMPILE_OR_LOAD:
            if first is not None and first[0]["program"] == program:
                self._end_first_call()
            self._publish_built(row, local)

    def _publish_built(self, row, local):
        """``program.built`` for a compile or load that just ended, with
        the tracing and the lowering that led to it on this thread (a
        program lowered from a cached trace, or compiled from a lowering
        made earlier, has no such row: the field is left out)."""
        # what this thread traced since its last compile belongs to this
        # build or to none
        lower, traces = local.lower, local.traces or {}
        local.lower = local.traces = None
        if lower is not None and lower["program"] != row["program"]:
            lower = None
        trace = traces.get(_traced_name(row["program"]))
        fields = {
            "program": row["program"], "key": row.get("key"),
            "trace_s": trace and trace["end"] - trace["start"],
            "lower_s": lower and lower["end"] - lower["start"],
            "compile_or_load_s": row["end"] - row["start"],
            "cache_hit": row["cache_hit"], "nth": row["nth"],
            "since_entry_s": None if self.entered is None
            else row["end"] - self.entered}
        publish(KIND_PROGRAM_BUILT, **{
            k: v for k, v in fields.items() if v is not None})

    # -- readers -----------------------------------------------------------
    def snapshot(self, before=None):
        """``{"entered", "rows", "dispatches", "seconds"}``: copies of the
        rows (of those that ended at or before the monotonic time
        ``before``, when given) and, per stage, the length of the union of
        their intervals."""
        def ended(rows):
            return [dict(r) for r in list(rows)
                    if before is None or r["end"] <= before]

        rows = ended(self.rows)
        return {"entered": self.entered, "rows": rows,
                "dispatches": ended(self.dispatches),
                "seconds": {stage: union_seconds(
                    (r["start"], r["end"]) for r in rows
                    if r["stage"] == stage) for stage in STAGES}}


# The process-global log, like ``bus.telemetry_bus``: JAX's listeners are
# process-wide, so there is one log however many engines there are.
build_log = BuildLog()
