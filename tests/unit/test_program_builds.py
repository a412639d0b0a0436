"""The build log (telemetry/builds.py; docs/observability.md "Program
builds"): JAX's own trace / lower / compile-or-load events kept as rows on
the monotonic clock, the first call of each ``DispatchedProgram``
specialisation timed and named, one ``program.built`` bus event per
program, and ``program_builds()`` on the engines and the scheduler. On the
CPU: counts, order and clocks, never a speed."""
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

import deepspeed_tpu
from deepspeed_tpu import serving
from deepspeed_tpu.models.transformer_lm import GPT
from deepspeed_tpu.parallel.mesh import MeshTopology
from deepspeed_tpu.telemetry import builds, scopes, spans
from deepspeed_tpu.telemetry.builds import build_log
from deepspeed_tpu.telemetry.bus import KIND_PROGRAM_BUILT, telemetry_bus
from unit.simple_model import random_token_batches, tiny_gpt_config


@pytest.fixture
def listening():
    build_log.listen()
    return build_log


def _rows(log, since, program=None, stage=None):
    return [r for r in log.rows[since:]
            if (program is None or r["program"] == program)
            and (stage is None or r["stage"] == stage)]


def _fresh(name):
    """A jitted function nobody has built yet, under a name of its own."""
    def f(x):
        return x * 2.0 + 1.0

    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


# ---------------------------------------------------------------------------
# rows
# ---------------------------------------------------------------------------
def test_first_call_adds_a_row_per_stage_and_the_second_adds_none(listening):
    f = _fresh("builds_case_one")
    x = jnp.ones((3,), jnp.float32)
    jax.block_until_ready(x)
    since, t0 = len(listening.rows), time.monotonic()
    f(x)
    t1 = time.monotonic()
    got = [r for r in listening.rows[since:]
           if "builds_case_one" in r["program"]]
    assert [(r["program"], r["stage"]) for r in got] == [
        ("builds_case_one", builds.TRACE),
        ("jit(builds_case_one)", builds.LOWER),
        ("jit(builds_case_one)", builds.COMPILE_OR_LOAD)]
    for r in got:
        assert t0 <= r["start"] <= r["end"] <= t1    # the monotonic clock
        assert r["nth"] == 1 and "key" not in r
    assert all(a["end"] <= b["start"] for a, b in zip(got, got[1:]))
    # the CPU has no persistent cache here: asked, and not served
    assert got[-1]["cache_hit"] is False
    assert all("cache_hit" not in r for r in got[:-1])
    n = len(listening.rows)
    f(x)
    assert len(listening.rows) == n


def test_a_new_shape_is_a_second_build(listening):
    f = _fresh("builds_case_two")
    since = len(listening.rows)
    f(jnp.ones((3,), jnp.float32))
    f(jnp.ones((5,), jnp.float32))
    compiled = _rows(listening, since, "jit(builds_case_two)",
                     builds.COMPILE_OR_LOAD)
    assert [r["nth"] for r in compiled] == [1, 2]
    assert [r["nth"] for r in _rows(listening, since, "builds_case_two",
                                    builds.TRACE)] == [1, 2]


def test_a_nested_jit_does_not_lengthen_the_trace_union(listening):
    inner = _fresh("builds_nested_inner")

    def outer(x):
        return inner(x) + inner(x * 3.0)

    outer.__name__ = "builds_nested_outer"
    x = jnp.ones((4,), jnp.float32)
    jax.block_until_ready(x)
    since = len(listening.rows)
    jax.jit(outer)(x)
    traced = [r for r in _rows(listening, since, stage=builds.TRACE)]
    out = next(r for r in traced if r["program"] == "builds_nested_outer")
    nested = [r for r in traced if r["program"] == "builds_nested_inner"]
    assert nested and all(
        out["start"] <= r["start"] and r["end"] <= out["end"] for r in nested)
    spans_ = [(r["start"], r["end"]) for r in traced]
    assert builds.union_seconds(spans_) \
        == pytest.approx(out["end"] - out["start"])
    assert sum(b - a for a, b in spans_) > out["end"] - out["start"]
    # no program of its own for the nested function
    assert not _rows(listening, since, "jit(builds_nested_inner)")


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0), ([(1.0, 2.0)], 1.0), ([(1.0, 3.0), (2.0, 2.5)], 2.0),
    ([(1.0, 2.0), (1.5, 4.0), (6.0, 7.0)], 4.0),
    ([(5.0, 6.0), (1.0, 2.0)], 2.0), ([(1.0, 2.0), (2.0, 3.0)], 2.0)])
def test_union_seconds(intervals, want):
    assert builds.union_seconds(intervals) == pytest.approx(want)


def test_snapshot_cuts_at_a_monotonic_time_and_copies(listening):
    f = _fresh("builds_case_cut")
    f(jnp.ones((2,), jnp.float32))
    cut = time.monotonic()
    f(jnp.ones((6,), jnp.float32))
    whole, early = listening.snapshot(), listening.snapshot(before=cut)
    assert set(whole) == {"entered", "rows", "dispatches", "seconds"}
    assert set(whole["seconds"]) == set(builds.STAGES)

    def mine(s):
        return [r for r in s["rows"]
                if r["program"] == "jit(builds_case_cut)"
                and r["stage"] == builds.COMPILE_OR_LOAD]

    assert len(mine(whole)) == 2 and len(mine(early)) == 1
    assert all(r["end"] <= cut for r in early["rows"])
    assert all(early["seconds"][s] <= whole["seconds"][s]
               for s in builds.STAGES)
    assert whole["entered"] == listening.entered <= cut
    mine(whole)[0]["program"] = "changed"       # a copy, not the log's row
    assert mine(listening.snapshot())


# ---------------------------------------------------------------------------
# listeners
# ---------------------------------------------------------------------------
def _registered():
    on_duration, on_event = build_log._listeners
    return (jax_monitoring.get_event_duration_listeners().count(on_duration),
            jax_monitoring.get_event_listeners().count(on_event))


def _tiny_engine():
    return deepspeed_tpu.init_inference(
        GPT(tiny_gpt_config(scan_layers=True)), dtype="fp32", seed=0)


def test_two_entry_point_calls_leave_one_listener():
    _tiny_engine()
    engine = _tiny_engine()
    assert _registered() == (1, 1)
    serving.build_serving(engine, {"slots": 2, "prompt_bucket": 16})
    assert _registered() == (1, 1)
    entered = build_log.entered
    assert entered is not None and entered <= time.monotonic()
    _tiny_engine()
    assert build_log.entered == entered     # the first entry point's time


def test_the_log_survives_clear_event_listeners():
    others = (jax_monitoring.get_event_duration_listeners(),
              jax_monitoring.get_event_listeners(),
              jax_monitoring.get_event_time_span_listeners(),
              jax_monitoring.get_scalar_listeners())
    try:
        build_log.listen()
        jax.monitoring.clear_event_listeners()
        assert _registered() == (0, 0)
        f = _fresh("builds_case_cleared")
        n = len(build_log.rows)
        f(jnp.ones((3,), jnp.float32))
        assert len(build_log.rows) == n         # nobody listened
        _tiny_engine()                          # an entry point
        assert _registered() == (1, 1)
        _tiny_engine()
        assert _registered() == (1, 1)
        f(jnp.ones((7,), jnp.float32))
        assert _rows(build_log, n, "jit(builds_case_cleared)",
                     builds.COMPILE_OR_LOAD)
    finally:
        # what the test session's other listeners were, after ours
        for fn in others[0]:
            if fn not in jax_monitoring.get_event_duration_listeners():
                jax.monitoring.register_event_duration_secs_listener(fn)
        for fn in others[1]:
            if fn not in jax_monitoring.get_event_listeners():
                jax.monitoring.register_event_listener(fn)
        for fn in others[2]:
            jax.monitoring.register_event_time_span_listener(fn)
        for fn in others[3]:
            jax.monitoring.register_scalar_listener(fn)


def test_rows_from_many_threads_lose_no_count():
    """JAX fires its events on whatever thread builds: more threads than
    cores, a short switch interval, one program name. Every row is kept and
    the ``nth`` of the name are 1..n, each once."""
    import sys

    log = builds.BuildLog()
    event = next(e for e, s in builds.STAGE_OF_EVENT.items()
                 if s == builds.TRACE)
    workers, each = 4 * (os.cpu_count() or 2), 400

    def work():
        for _ in range(each):
            log._on_duration(event, 1e-6, fun_name="shared")

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(before)
    assert sorted(r["nth"] for r in log.rows) \
        == list(range(1, workers * each + 1))
    assert [r["nth"] for r in log.rows] == sorted(r["nth"] for r in log.rows)


def test_what_a_thread_builds_is_that_threads(listening):
    """The cache's events and the stages that lead to a compile are matched
    per thread: a build on another thread neither takes nor leaves a
    pending stage."""
    events = []
    telemetry_bus.subscribe(events.append)
    try:
        f = _fresh("builds_case_thread")
        t = threading.Thread(target=lambda: f(jnp.ones((3,), jnp.float32)))
        t.start()
        t.join()
    finally:
        telemetry_bus.unsubscribe(events.append)
    ev = next(e for e in events if e["kind"] == KIND_PROGRAM_BUILT
              and e["program"] == "jit(builds_case_thread)")
    assert ev["trace_s"] > 0 and ev["lower_s"] > 0
    assert ev["compile_or_load_s"] > 0 and ev["cache_hit"] is False


# ---------------------------------------------------------------------------
# the first call of a dispatched program
# ---------------------------------------------------------------------------
def test_first_call_carries_the_key_and_later_calls_append_nothing(listening):
    f = _fresh("builds_case_dispatched")
    prog = scopes.DispatchedProgram(f, key=lambda a: a[0].shape)
    x = jnp.ones((1, 16), jnp.float32)
    jax.block_until_ready(x)
    since, calls = len(listening.rows), len(listening.dispatches)
    t0 = time.monotonic()
    prog(x)
    t1 = time.monotonic()
    call, = listening.dispatches[calls:]
    assert call["program"] == "jit(builds_case_dispatched)"
    assert call["key"] == "(1, 16)"
    assert t0 <= call["start"] <= call["end"] <= t1
    assert 0 < call["first_dispatch_s"] <= t1 - t0
    got = [r for r in listening.rows[since:]
           if "builds_case_dispatched" in r["program"]]
    assert [r["stage"] for r in got] == list(builds.STAGES)
    for r in got:
        assert r["key"] == "(1, 16)"
        assert r["dispatch"] == "jit(builds_case_dispatched)"
        assert call["start"] <= r["start"] and r["end"] <= call["end"]
    # the three stages lie inside the first call
    assert sum(r["end"] - r["start"] for r in got) \
        <= call["first_dispatch_s"]
    n, m = len(listening.rows), len(listening.dispatches)
    for _ in range(3):
        prog(x)
    assert (len(listening.rows), len(listening.dispatches)) == (n, m)
    assert listening._local.first_call is None     # closed by its compile
    # another bucket is another first call
    prog(jnp.ones((1, 32), jnp.float32))
    assert [d["key"] for d in listening.dispatches[calls:]] \
        == ["(1, 16)", "(1, 32)"]
    assert list(prog.avals) == [(1, 16), (1, 32)]
    assert len(prog.lowered()) == 2


def test_the_call_is_the_frame_it_was():
    """Every call of a serving program goes through ``__call__``: the key,
    one dict lookup, the program; the first call's bookkeeping is one
    method call in the branch that kept the avals already. Its frame is
    what it was before the build log: three locals, three stack slots. On
    the v5e's host a ``with`` around the first call (three more slots
    under every jitted call of the serve loop) cost the serve cells' ramp
    1.2 s of 17.5 with every program unchanged (PERF.md, section 6,
    PR 37), so the first call is closed from the log's side, when its
    compile or load ends, and this holds the frame."""
    import dis

    code = scopes.DispatchedProgram.__call__.__code__
    assert code.co_varnames == ("self", "args", "k")
    assert code.co_stacksize == 3
    ops = [i.opname for i in dis.get_instructions(code)]
    assert "BEFORE_WITH" not in ops and ops.count("CONTAINS_OP") == 1
    # one way out: the program's own call
    assert ops.count("CALL_FUNCTION_EX") == 1


def test_a_first_call_that_builds_nothing_is_not_kept(listening):
    """A first call under a new key whose program raised, or was built
    already, ends no compile: the next first call on the thread closes it
    and drops it, and its key names no later row."""
    def bad(x):
        raise RuntimeError("no such program")

    bad.__name__ = "builds_case_bad"
    prog = scopes.DispatchedProgram(bad, key=lambda a: a[0].shape)
    calls = len(listening.dispatches)
    with pytest.raises(RuntimeError):
        prog(jnp.ones((2,), jnp.float32))
    assert listening._local.first_call[0]["program"] \
        == "jit(builds_case_bad)"
    good = scopes.DispatchedProgram(_fresh("builds_case_good"),
                                    key=lambda a: a[0].shape)
    since = len(listening.rows)
    good(jnp.ones((2,), jnp.float32))
    assert [d["program"] for d in listening.dispatches[calls:]] \
        == ["jit(builds_case_good)"]
    assert {r["dispatch"] for r in listening.rows[since:]
            if "dispatch" in r} == {"jit(builds_case_good)"}
    assert listening._local.first_call is None


def test_first_call_is_a_span_in_a_profiler_session(listening, tmp_path):
    prog = scopes.DispatchedProgram(_fresh("builds_case_span"),
                                    key=lambda a: a[0].shape)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        prog(jnp.ones((1, 8), jnp.float32))
        prog(jnp.ones((1, 8), jnp.float32))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[-1]
    found = [dict(e.stats) for plane in scopes.load_trace(path).planes
             for line in plane.lines for e in line.events
             if e.name == spans.SPAN_PREFIX + spans.PROGRAM_BUILD]
    assert found == [{"program": "jit(builds_case_span)", "key": "(1, 8)"}]


# ---------------------------------------------------------------------------
# the bus event
# ---------------------------------------------------------------------------
def test_program_built_reaches_a_subscriber_with_host_scalars(listening):
    events = []
    telemetry_bus.subscribe(events.append)
    try:
        prog = scopes.DispatchedProgram(_fresh("builds_case_event"),
                                        key=lambda a: a[0].shape)
        prog(jnp.ones((1, 24), jnp.float32))
        prog(jnp.ones((1, 24), jnp.float32))
        prog(jnp.ones((1, 48), jnp.float32))
    finally:
        telemetry_bus.unsubscribe(events.append)
    mine = [e for e in events if e["kind"] == KIND_PROGRAM_BUILT
            and e["program"] == "jit(builds_case_event)"]
    assert [(e["key"], e["nth"]) for e in mine] == [("(1, 24)", 1),
                                                    ("(1, 48)", 2)]
    for e in mine:
        assert set(e) == {"ts", "kind", "rank", "severity", "program", "key",
                          "trace_s", "lower_s", "compile_or_load_s",
                          "cache_hit", "nth", "since_entry_s"}
        for k, v in e.items():      # the bus's contract: host scalars
            assert type(v) in (str, int, float, bool), (k, type(v))
        assert e["trace_s"] > 0 and e["lower_s"] > 0
        assert e["compile_or_load_s"] > 0 and e["cache_hit"] is False
        assert e["since_entry_s"] > 0
    # an eager one-operation program is built like any other: no key
    eager = [e for e in events if e["kind"] == KIND_PROGRAM_BUILT
             and "key" not in e]
    assert eager and all(e["program"].startswith("jit(") for e in eager)


def test_the_flight_recorder_keeps_the_event(listening):
    from deepspeed_tpu.telemetry import FlightRecorder

    rec = FlightRecorder(ring_events=64, bus=telemetry_bus)
    try:
        _fresh("builds_case_recorder")(jnp.ones((3,), jnp.float32))
    finally:
        rec.close()
    kept = [e for e in rec.events()
            if e.get("kind") == KIND_PROGRAM_BUILT]
    assert any(e["program"] == "jit(builds_case_recorder)" for e in kept)


# ---------------------------------------------------------------------------
# the accessors
# ---------------------------------------------------------------------------
def test_scheduler_and_inference_engine_hand_out_the_log():
    engine = _tiny_engine()
    sched = serving.build_serving(engine, {"slots": 2, "prompt_bucket": 16})
    calls = len(build_log.dispatches)
    events = []
    telemetry_bus.subscribe(events.append)
    try:
        for prompt in ([5, 9, 3], list(range(1, 20))):
            sched.submit(prompt, max_new_tokens=3)
        sched.run()
    finally:
        telemetry_bus.unsubscribe(events.append)
    # lowering a model traces helpers of its own after the program's trace
    # has ended: the event still finds the program's trace, by name
    built = {(e["program"], e.get("key")): e for e in events
             if e["kind"] == KIND_PROGRAM_BUILT}
    for name in (("jit(prefill)", "(1, 16)"), ("jit(prefill)", "(1, 32)"),
                 ("jit(decode_k)", "((2,), 1)")):
        assert built[name]["trace_s"] > 0 and built[name]["lower_s"] > 0
    log = sched.program_builds()
    assert log["rows"] == engine.program_builds()["rows"]
    mine = log["dispatches"][calls:]
    by_program = {}
    for d in mine:
        by_program.setdefault(d["program"], []).append(d["key"])
    # a prefill program per bucket, one decode program, splice, first token
    assert sorted(by_program["jit(prefill)"]) == ["(1, 16)", "(1, 32)"]
    assert len(by_program["jit(decode_k)"]) == 1
    assert {"jit(splice)", "jit(set_token)"} <= set(by_program)
    cut = mine[0]["end"]
    assert [d["key"] for d in sched.program_builds(before=cut)[
        "dispatches"][calls:]] == [mine[0]["key"]]
    # a second run builds nothing
    n = len(build_log.dispatches)
    sched.submit([4, 4, 4], max_new_tokens=3)
    sched.run()
    assert len(build_log.dispatches) == n


def test_training_engine_hands_out_the_log_with_its_step_program():
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "steps_per_print": 10 ** 9}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(tiny_gpt_config()), config=cfg,
        topology=MeshTopology(devices=jax.devices()[:1]))
    since = len(build_log.rows)
    batches = random_token_batches(2, 2, 16, 128)
    engine.train_batch(iter(batches))
    log = engine.program_builds()
    steps = [r for r in log["rows"][since:]
             if r["program"] == "jit(train_step)"]
    assert [r["stage"] for r in steps] == [builds.LOWER,
                                           builds.COMPILE_OR_LOAD]
    assert all("key" not in r for r in steps)   # not a DispatchedProgram
    assert any(r["program"] == "train_step" and r["stage"] == builds.TRACE
               for r in log["rows"][since:])
    n = len(build_log.rows)
    engine.train_batch(iter(batches))
    assert not [r for r in build_log.rows[n:]
                if r["program"] == "jit(train_step)"]
