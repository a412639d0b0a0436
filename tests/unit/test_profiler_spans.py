"""Profiler spans and scopes inside the program (docs/observability.md
"Profiler spans and scopes"): ``telemetry.span`` writes ``ds:`` spans into a
JAX profiler session and is silent without one; ``program_scopes()`` says
which source scope each HLO instruction of a dispatched program belongs to.
CPU profiler sessions carry the host spans (with their attributes); device
times come only from a chip (tests/perfbench reads the recorded traces)."""
import glob
import os

import jax
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.engine import PROGRAM_DECODE_K, PROGRAM_PREFILL
from deepspeed_tpu.inference.scheduler import (PROGRAM_SPLICE,
                                               ContinuousBatchingScheduler)
from deepspeed_tpu.models.transformer_lm import GPT
from deepspeed_tpu.parallel.mesh import MeshTopology
from deepspeed_tpu.runtime.engine import PROGRAM_TRAIN_STEP
from deepspeed_tpu.telemetry import scopes, spans
from deepspeed_tpu.telemetry.bus import (KIND_SERVE_ADMIT, KIND_SERVE_STATS,
                                         telemetry_bus)
from unit.simple_model import random_token_batches, tiny_gpt_config

SERVE_SPANS = [spans.SERVE_ITERATION, spans.SERVE_ADMIT, spans.SERVE_PREFILL,
               spans.SERVE_FIRST_TOKEN_READ, spans.SERVE_SPLICE,
               spans.SERVE_EMIT, spans.SERVE_DELIVER, spans.SERVE_STATS,
               spans.SERVE_DECODE_STEP, spans.SERVE_DECODE_READ]
PROMPTS = [[5, 9, 3], list(range(1, 20)), [7] * 9]


class _Session:
    """A profiler session around ``fn``; the ``ds:`` spans it recorded as
    ``(name, start_ns, end_ns, attrs)``, outermost first."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "trace")

    def run(self, fn):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))[-1]
        found = []
        for plane in scopes.load_trace(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(spans.SPAN_PREFIX):
                        found.append((e.name[len(spans.SPAN_PREFIX):],
                                      e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
        found.sort(key=lambda s: (s[1], -s[2]))
        return out, found


def _scheduler(**kw):
    cfg = tiny_gpt_config(scan_layers=True)
    engine = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=0)
    return ContinuousBatchingScheduler(engine, slots=2, prompt_bucket=16,
                                       **kw)


def _serve(sched, prompts=PROMPTS, new=4):
    ids = [sched.submit(p, max_new_tokens=new) for p in prompts]
    stats = sched.run()
    by_id = {c.request_id: c.tokens for c in stats.completions}
    return ids, [by_id[i] for i in ids]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One tiny scheduler run inside a profiler session."""
    sched = _scheduler()
    _serve(sched)                       # compile outside the session
    (ids, tokens), found = _Session(
        tmp_path_factory.mktemp("serve")).run(lambda: _serve(sched))
    return sched, ids, tokens, found


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.mark.parametrize("name", SERVE_SPANS)
def test_scheduler_run_records_every_serve_span(served, name):
    _, _, _, found = served
    assert any(s[0] == name for s in found), sorted({s[0] for s in found})


@pytest.mark.parametrize("child,parent", [
    (spans.SERVE_PREFILL, spans.SERVE_ADMIT),
    (spans.SERVE_FIRST_TOKEN_READ, spans.SERVE_ADMIT),
    (spans.SERVE_SPLICE, spans.SERVE_ADMIT),
    (spans.SERVE_ADMIT, spans.SERVE_ITERATION),
    (spans.SERVE_STATS, spans.SERVE_ITERATION),
    (spans.SERVE_DECODE_STEP, spans.SERVE_ITERATION),
    (spans.SERVE_EMIT, spans.SERVE_ADMIT),
    (spans.SERVE_DECODE_READ, spans.SERVE_DECODE_STEP),
])
def test_serve_spans_nest_as_documented(served, child, parent):
    _, _, _, found = served
    parents = [s for s in found if s[0] == parent]
    kids = [s for s in found if s[0] == child]
    assert kids and all(any(_inside(k, p) for p in parents) for k in kids)


def test_one_admit_span_per_request_with_its_attributes(served):
    sched, ids, _, found = served
    admits = [s for s in found if s[0] == spans.SERVE_ADMIT]
    assert sorted(a[3]["request_id"] for a in admits) == sorted(ids)
    for a in admits:
        attrs = a[3]
        assert attrs["bucket"] == sched._bucketed_len(attrs["prompt_len"])
        assert attrs["queue_wait_us"] >= 0 and attrs["lane"] in (0, 1)
        assert "queue_depth" in attrs
    # each admission encloses exactly one prefill, one splice and one
    # first-token read of its own, in that order (PR 53: the read comes
    # after the iteration's decode step is dispatched): the k-th of each
    # is the k-th admission's, and follows its sibling
    for name in (spans.SERVE_PREFILL, spans.SERVE_SPLICE,
                 spans.SERVE_FIRST_TOKEN_READ):
        kids = [s for s in found if s[0] == name]
        assert len(kids) == len(admits)
        assert all(_inside(k, a) for k, a in zip(kids, admits))
    for p, s, r in zip(*([x for x in found if x[0] == name] for name in (
            spans.SERVE_PREFILL, spans.SERVE_SPLICE,
            spans.SERVE_FIRST_TOKEN_READ))):
        assert p[2] <= s[1] and s[2] <= r[1]
    prefill = [s for s in found if s[0] == spans.SERVE_PREFILL]
    assert all(p[3]["chunks"] == 1 for p in prefill)    # dense cache
    steps = [s for s in found if s[0] == spans.SERVE_DECODE_STEP]
    assert all(1 <= s[3]["lanes_active"] <= 2 for s in steps)
    iters = [s for s in found if s[0] == spans.SERVE_ITERATION]
    assert [s[3]["decode_steps"] for s in iters] == sorted(
        s[3]["decode_steps"] for s in iters)


def test_decode_step_spans_say_whether_they_ran_ahead(served):
    """``ahead`` is 1 on a step dispatched with the step before it unread,
    whose read is then that span's child; 0 on the first step of the run,
    which is read on its own after the first tokens it follows, and on the
    second, which finds nothing unread. The step of an iteration with an
    admission is ahead like any other (PR 53) and lies inside the
    admission's span, which closes after the first token's emit."""
    _, _, _, found = served
    steps = [s for s in found if s[0] == spans.SERVE_DECODE_STEP]
    reads = [s for s in found if s[0] == spans.SERVE_DECODE_READ]
    assert [s[3]["ahead"] for s in steps] == [0, 0] + [1] * (len(steps) - 2)
    for s in steps:
        assert len([r for r in reads if _inside(r, s)]) == s[3]["ahead"]
    assert sum(s[3]["ahead"] for s in steps) == len(reads)
    admits = [s for s in found if s[0] == spans.SERVE_ADMIT]
    assert len(admits) == 3
    for a in admits:
        inside = [s for s in steps if _inside(s, a)]
        assert len(inside) == 1
        assert inside[0][3]["ahead"] == (0 if inside[0] is steps[0] else 1)


def test_two_admissions_of_one_iteration_nest(served):
    """The run's first iteration admits into both lanes: the second
    admission's span lies inside the first one's, each holds its own
    prefill and splice, and the first tokens are read in admission order,
    after the step, inside both (``behind_step`` 1)."""
    _, ids, _, found = served
    admits = [s for s in found if s[0] == spans.SERVE_ADMIT]
    outer, inner = admits[0], admits[1]
    assert [a[3]["request_id"] for a in admits[:2]] == ids[:2]
    assert _inside(inner, outer) and not _inside(admits[2], outer)
    prefills = [s for s in found if s[0] == spans.SERVE_PREFILL]
    assert not _inside(prefills[0], inner) and _inside(prefills[1], inner)
    reads = [s for s in found if s[0] == spans.SERVE_FIRST_TOKEN_READ]
    assert all(r[3]["behind_step"] == 1 for r in reads)
    step = next(s for s in found if s[0] == spans.SERVE_DECODE_STEP)
    assert _inside(step, inner)
    assert step[2] <= reads[0][1] and reads[0][2] <= reads[1][1]
    assert _inside(reads[0], inner) and _inside(reads[1], inner)
    emits = [s for s in found if s[0] == spans.SERVE_EMIT][:2]
    assert [e[3]["request_id"] for e in emits] == ids[:2]
    assert all(_inside(e, inner) for e in emits)


def test_one_emit_span_a_request_and_one_deliver_span_a_delivered_step(
        served):
    """A request's first token is the one token with a span of its own,
    right behind the read that names the same request; the others are
    their step's ``ds:serve.deliver``: one a delivered step, after that
    step's dispatch, with no attribute of its own."""
    _, ids, tokens, found = served
    emits = [s for s in found if s[0] == spans.SERVE_EMIT]
    reads = [s for s in found if s[0] == spans.SERVE_FIRST_TOKEN_READ]
    admits = [s for s in found if s[0] == spans.SERVE_ADMIT]
    assert [e[3]["request_id"] for e in emits] == ids
    assert [r[3]["request_id"] for r in reads] == ids
    for a, r, e in zip(admits, reads, emits):
        assert a[3]["request_id"] == r[3]["request_id"]
        assert _inside(r, a) and _inside(e, a) and r[2] <= e[1]
    steps = [s for s in found if s[0] == spans.SERVE_DECODE_STEP]
    delivers = [s for s in found if s[0] == spans.SERVE_DELIVER]
    assert len(delivers) == len(steps) < sum(len(t) for t in tokens)
    assert all(d[3] == {} for d in delivers)
    # the run's last step is delivered after the loop
    iters = [s for s in found if s[0] == spans.SERVE_ITERATION]
    assert all(any(_inside(d, i) for i in iters) for d in delivers[:-1])
    assert delivers[-1][1] >= iters[-1][2]
    assert all(step[1] < d[1] for step, d in zip(steps, delivers))
    # a token after the first has no span of its own
    assert not any(_inside(e, d) for e in emits for d in delivers)


def test_summary_counts_the_first_tokens_read_behind_their_step():
    sched = _scheduler()
    ids = [sched.submit(p, max_new_tokens=3) for p in PROMPTS]
    stats = sched.run()
    assert stats.first_tokens_behind_step == len(ids)
    assert stats.summary()["first_tokens_behind_step"] == len(ids)
    assert stats.summary()["decode_steps_ahead"] == stats.decode_steps - 2


def test_greedy_tokens_identical_with_and_without_a_session(served):
    sched, _, traced_tokens, _ = served
    _, plain = _serve(sched)
    assert plain == traced_tokens


def test_serve_events_agree_with_the_spans_and_keep_their_keys():
    sched = _scheduler()
    seen = []
    telemetry_bus.subscribe(seen.append)
    try:
        _serve(sched, prompts=PROMPTS[:2], new=3)
    finally:
        telemetry_bus.unsubscribe(seen.append)
    admits = [e for e in seen if e["kind"] == KIND_SERVE_ADMIT]
    assert [e["bucket"] for e in admits] == [16, 32]
    assert all({"request_id", "lane", "prompt_len", "replayed",
                "queue_wait_s", "queue_depth"} <= set(e) for e in admits)
    snaps = [e for e in seen if e["kind"] == KIND_SERVE_STATS]
    assert snaps and all(
        {"queue_depth", "lanes_active", "shed", "deadline_shed",
         "decode_steps", "draining", "kv_resident_bytes",
         "kv_unquantized_bytes"} <= set(e) for e in snaps)
    kv = sched.kv_cache_stats()
    assert {e["kv_resident_bytes"] for e in snaps} == {kv["resident_bytes"]}


def test_kv_geometry_is_read_once_per_run(monkeypatch):
    """``serve.stats`` fires every iteration; what cannot change in a run
    (the KV geometry, the HBM size behind it) is computed once."""
    from deepspeed_tpu.telemetry import memory

    sched = _scheduler()
    calls = []
    real = memory.hbm_bytes
    monkeypatch.setattr(memory, "hbm_bytes",
                        lambda **kw: calls.append(1) or real(**kw))
    _, tokens = _serve(sched)
    assert len(tokens) == len(PROMPTS) and len(calls) == 1


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def _train_engine(**cfg_overrides):
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
        "tpu": {"use_pallas_optimizer": True},
    }
    config.update(cfg_overrides)
    cfg = tiny_gpt_config(scan_layers=True, remat=True, remat_policy="full",
                          n_positions=128, use_flash_attention=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), config=config,
        topology=MeshTopology(devices=jax.devices()[:1]))
    return engine, iter(random_token_batches(8, 4, 128, 128) * 4)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Two steps of a tiny GPT inside a profiler session (after one
    outside it, to compile), beside an untraced twin."""
    engine, it = _train_engine(sentinel={"enabled": True})
    engine.train_batch(it)
    losses, found = _Session(tmp_path_factory.mktemp("train")).run(
        lambda: [float(engine.train_batch(it)) for _ in range(2)])
    twin, it2 = _train_engine(sentinel={"enabled": True})
    plain = [float(twin.train_batch(it2)) for _ in range(3)]
    return engine, losses, plain[1:], found


@pytest.mark.parametrize("phase", ["dataloader", "h2d", "compiled_step",
                                   "post_step_bookkeeping", "sentinel"])
def test_train_batch_records_its_phases(trained, phase):
    _, _, _, found = trained
    mine = [s for s in found if s[0] == spans.TRAIN_PHASE + phase]
    assert len(mine) == 2 and [s[3]["step"] for s in mine] in (
        [1, 2], [2, 3])     # bookkeeping runs after the step counter moved


def test_train_phases_follow_one_another(trained):
    _, _, _, found = trained
    train = [s for s in found if s[0].startswith(spans.TRAIN_PHASE)]
    assert all(a[2] <= b[1] for a, b in zip(train, train[1:]))


def test_loss_identical_with_and_without_a_session(trained):
    _, losses, plain, _ = trained
    assert losses == plain


def test_phase_context_is_one_object_per_phase(trained):
    engine = trained[0]
    ctx = engine._prof_phase("h2d")
    assert type(ctx).__name__ == "_PhaseSpan"
    assert not hasattr(ctx, "gen")      # no generator-based manager inside


def test_span_without_a_session_adds_no_event_fence_or_wait(monkeypatch):
    """The healthy path: a span outside a profiler session publishes
    nothing on the bus, and neither fences nor waits for the device."""
    from deepspeed_tpu.utils import timer

    waits, events = [], []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append("block") or x)
    monkeypatch.setattr(timer, "fence",
                        lambda *a, **k: waits.append("fence"))
    telemetry_bus.subscribe(events.append)
    try:
        for i in range(100):
            with telemetry.span(spans.SERVE_EMIT, request_id=i, lane=1):
                pass
    finally:
        telemetry_bus.unsubscribe(events.append)
    assert waits == [] and events == []


@pytest.mark.parametrize("attrs,want", [
    ({"request_id": 3, "lane": None}, {"request_id": 3}),
    ({"ahead": None}, {}),
    ({"ahead": 0, "lane": 0}, {"ahead": 0, "lane": 0}),
    ({}, {}),
])
def test_span_leaves_out_what_the_caller_has_nothing_to_say_about(
        monkeypatch, attrs, want):
    made = []
    monkeypatch.setattr(spans, "_annotation",
                        lambda name, **kw: made.append((name, kw)))
    spans.span(spans.SERVE_ADMIT, **attrs)
    assert made == [(spans.SPAN_PREFIX + spans.SERVE_ADMIT, want)]


def test_span_resolves_the_annotation_once(monkeypatch):
    monkeypatch.setattr(spans, "_annotation", None)
    first = spans.span(spans.SERVE_STATS)
    assert spans._annotation is jax.profiler.TraceAnnotation
    assert type(first) is type(spans.span(spans.SERVE_STATS, x=1)) \
        is jax.profiler.TraceAnnotation


def test_a_traced_step_waits_no_more_than_an_untraced_one(monkeypatch,
                                                          tmp_path):
    engine, it = _train_engine()
    for _ in range(3):
        engine.train_batch(it)
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(1) or real(x))
    engine.train_batch(it)
    untraced = len(waits)
    _Session(tmp_path).run(lambda: engine.train_batch(it))
    assert len(waits) - untraced == untraced


# ---------------------------------------------------------------------------
# scope tables
# ---------------------------------------------------------------------------
def _scoped(table, program, *names):
    return [n for n, p in table[program].items()
            if all(scopes.has_scope(p, x) for x in names)]


@pytest.mark.parametrize("scope", [
    scopes.SCOPE_OPTIMIZER, scopes.SCOPE_LM_HEAD_CE, scopes.SCOPE_ATTN_CORE,
    scopes.SCOPE_GRAD_NORM_CLIP, scopes.SCOPE_GRAD_CAST, scopes.SCOPE_REMAT])
def test_train_step_scopes(trained, scope):
    table = trained[0].program_scopes()
    assert list(table) == [PROGRAM_TRAIN_STEP]
    assert _scoped(table, PROGRAM_TRAIN_STEP, scope)


def test_rematerialised_attention_keeps_jaxs_component(trained):
    table = trained[0].program_scopes()
    both = _scoped(table, PROGRAM_TRAIN_STEP, scopes.SCOPE_REMAT,
                   scopes.SCOPE_ATTN_CORE)
    assert both and all("checkpoint" in scopes.split_path(
        table[PROGRAM_TRAIN_STEP][n]) for n in both)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv", "fused_adam"])
def test_step_hlo_names_the_pallas_kernels(trained, kernel):
    """Interpret mode puts the kernel's ``name`` on the name stack of the
    operations that emulate it (on the TPU it names the custom call)."""
    table = trained[0].program_scopes()
    assert _scoped(table, PROGRAM_TRAIN_STEP, kernel)
    if kernel == "fused_adam":
        assert _scoped(table, PROGRAM_TRAIN_STEP, kernel,
                       scopes.SCOPE_OPTIMIZER)
    else:
        assert _scoped(table, PROGRAM_TRAIN_STEP, kernel,
                       scopes.SCOPE_ATTN_CORE)


def test_serving_scopes_cover_prefill_decode_and_splice(served):
    sched = served[0]
    table = sched.program_scopes()
    assert {PROGRAM_PREFILL, PROGRAM_DECODE_K, PROGRAM_SPLICE} <= set(table)
    # (the cache read's few operations fuse into attention's: XLA keeps
    # the op_name of a fusion's root)
    for scope in (scopes.SCOPE_KV_CACHE_WRITE, scopes.SCOPE_ATTN_CORE,
                  scopes.SCOPE_SAMPLE, scopes.SCOPE_LM_HEAD):
        assert _scoped(table, PROGRAM_DECODE_K, scope), scope
    assert _scoped(table, PROGRAM_PREFILL, scopes.SCOPE_KV_CACHE_WRITE)
    # two prompt buckets ran: one entry, scopes the two agree on
    assert len(sched.engine._prefill_fn.avals) == 2
    assert sched.engine.program_scopes().keys() <= table.keys()


def test_kv_leaf_shapes_know_the_stacked_and_the_per_layer_leaf(served):
    from deepspeed_tpu.inference.engine import carried_leaf_shapes

    sched = served[0]
    shapes = carried_leaf_shapes(
        sched.lane_cache.shapes,
        sched.lane_cache.leaves)[scopes.SCOPE_KV_CACHE_CARRY]
    # [layers, slots, positions, kv heads, head dim] and one layer of it
    assert shapes == {(2, 2, 64, 4, 8), (2, 64, 4, 8)}


def test_compile_cache_key_covers_the_metadata_scopes_are_read_from(
        monkeypatch, tmp_path):
    """``program_scopes()`` reads op_names from the executable's own text;
    an executable read back from the persistent cache keeps the metadata of
    whoever compiled it, so metadata has to be part of the key."""
    from deepspeed_tpu.utils import compile_cache

    flag = "jax_compilation_cache_include_metadata_in_key"
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    before = getattr(jax.config, flag)
    try:
        jax.config.update(flag, False)
        compile_cache.ensure_compile_cache()
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)
