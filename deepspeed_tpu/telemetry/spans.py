"""Host spans on the profiler's clock.

``span`` is the only way the program writes a span. It returns a
``jax.profiler.TraceAnnotation``, which does nothing measurable while no
profiler session is recording and, while one is (``jax.profiler.start_trace``
/ ``start_server``, or a benchmark's traced run), writes the span into the
same ``.xplane.pb``, on the same clock, as the device's events. So there is
no switch, no configuration key and no second trace format: whoever records
a trace gets the spans (docs/observability.md "Profiler spans and scopes").

Attributes are host scalars the caller already holds (the bus's contract);
they come back as the event's stats. A span never publishes a bus event and
never waits for the device.
"""

SPAN_PREFIX = "ds:"

# span names (without the prefix); the names are the contract with whoever
# reads a trace
SERVE_ITERATION = "serve.iteration"
SERVE_ADMIT = "serve.admit"
SERVE_PREFILL = "serve.prefill"
SERVE_FIRST_TOKEN_READ = "serve.first_token_read"
SERVE_SPLICE = "serve.splice"
# a request's first token handed on (one an admission, ``request_id``): the
# end of its time-to-first-token on the trace's clock
SERVE_EMIT = "serve.emit"
# one decode step's tokens handed to their lanes (no attribute: the account
# of a trace names the span the host was in around a gap of the device's)
SERVE_DELIVER = "serve.deliver"
SERVE_STATS = "serve.stats"
SERVE_DECODE_STEP = "serve.decode_step"
SERVE_DECODE_READ = "serve.decode_read"
TRAIN_PHASE = "train."      # + the engine's phase name
# the first call of one specialisation of a dispatched program, up to the
# end of its compile or load (attrs ``program``, ``key``; telemetry/builds.py)
PROGRAM_BUILD = "program.build"


# ``jax.profiler.TraceAnnotation``, resolved by the first span (the
# package imports no jax at import; an import statement a call cost more
# than the annotation itself while no session records)
_annotation = None


def span(name, **attrs):
    """A context manager that records ``ds:<name>`` with ``attrs`` while a
    profiler session is active and is a no-op otherwise. An attribute that
    is None is left out: what a caller has nothing to say about is not
    written as a number."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    if None in attrs.values():
        attrs = {k: v for k, v in attrs.items() if v is not None}
    return _annotation(SPAN_PREFIX + name, **attrs)
