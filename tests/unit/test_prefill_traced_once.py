"""The ``[1, T]`` admission prefill is traced ONCE for all of a scheduler's
prompt buckets where the model allows it (``InferenceEngine.plan_prefill``,
``GPTConfig.prefill_bucket_dependence``): the program a bucket runs is its
own program's to the bit, the model's Python runs once, what the rule
admits among the eight serving families at tiny and real widths exports for
the TPU and everything else is refused by name, and every call the plan
does not cover takes the route it always took."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import brumby_tiny
import deepseek_v2_tiny
import dots3_tiny
import falcon_h1_tiny
import keye_vl_tiny
import lfm2_tiny
import trinity_tiny
from deepspeed_tpu.inference.engine import InferenceEngine, export_prefill
from deepspeed_tpu.inference.lane_cache import LaneLayout
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
from deepspeed_tpu.parallel.mesh import (
    reset_default_topology,
    set_default_topology,
)
from deepspeed_tpu.telemetry import telemetry_bus
from deepspeed_tpu.telemetry.builds import build_log
from deepspeed_tpu.telemetry.bus import KIND_SERVE_PREFILL_PLAN
from perfbench.builders import (
    afmoe_serve,
    brumby_serve,
    deepseek_v2_serve,
    dots3_serve,
    falcon_h1_serve,
    keye_vl_serve,
    lfm2_serve,
)
from unit import test_scheduler_decode_ahead as plain_loop

BUCKET = plain_loop.BUCKET
CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       os.pardir, os.pardir, "perfbench", "configs")


def _gpt_config(dtype=jnp.float32, **kw):
    return GPTConfig(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                     n_head=4, dtype=dtype, param_dtype=dtype,
                     scan_layers=True, **kw)


def _gpt(dtype=jnp.float32, **kw):
    """A tiny dense decoder's engine; a second one of the same arguments
    holds the same parameters (``seed=0``) and, asked for no plan, traces a
    bucket at a time as every engine did."""
    return InferenceEngine(
        GPT(_gpt_config(dtype, **kw)),
        {"dtype": "fp32" if dtype == jnp.float32 else "bf16"}, seed=0)


def _unplanned(dtype=jnp.float32, **kw):
    eng = _gpt(dtype, **kw)
    eng._materialize(jnp.zeros((1, BUCKET), jnp.int32))
    eng._build_decode_fns()
    return eng


def _planned(eng):
    """``eng`` under a scheduler that has asked it for a plan, with the
    plans it published."""
    plans = []

    def keep(event):
        if event["kind"] == KIND_SERVE_PREFILL_PLAN:
            plans.append(event)

    telemetry_bus.subscribe(keep)
    try:
        sched = ContinuousBatchingScheduler(eng, slots=4,
                                            prompt_bucket=BUCKET)
        sched._ensure_compiled()
        sched._ensure_compiled()    # asked again: decides nothing new
    finally:
        telemetry_bus.unsubscribe(keep)
    return sched, plans


def _left_padded(tokens, real, seed):
    ids = np.zeros((1, tokens), np.int32)
    mask = np.zeros((1, tokens), bool)
    ids[0, tokens - real:] = np.random.RandomState(seed).randint(
        1, 128, real)
    mask[0, tokens - real:] = True
    return jnp.asarray(ids), jnp.asarray(mask)


# dense attention's other forms are admitted with it: held to the bit too
DENSE = {
    "fp32": (jnp.float32, {}),
    "bf16": (jnp.bfloat16, {}),
    "rotary_grouped_heads": (jnp.float32, dict(
        rotary=True, learned_positions=False, n_kv_head=2)),
    "alibi_int8_cache": (jnp.bfloat16, dict(
        alibi=True, learned_positions=False, kv_cache_dtype="int8")),
}


@pytest.mark.parametrize("form", sorted(DENSE))
def test_a_bucket_runs_its_own_programs_values_to_the_bit(form):
    dtype, kw = DENSE[form]
    eng = _gpt(dtype, **kw)
    _, plans = _planned(eng)
    assert [(p["traced"], p["granule"], p["buckets_max"]) for p in plans] \
        == [("once", BUCKET, 256 // BUCKET)]
    every = _unplanned(dtype, **kw)
    for tokens, real in ((BUCKET, 5), (3 * BUCKET, 17), (2 * BUCKET, 16)):
        ids, mask = _left_padded(tokens, real, seed=tokens)
        got = eng._prefill_fn(eng.params, ids, mask)
        want = every._prefill_fn(every.params, ids, mask)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want)):
            assert a.dtype == b.dtype and a.shape == b.shape, path
            assert np.array_equal(np.asarray(a), np.asarray(b)), path
    # the compiled module keeps the name a trace and the accounts key on
    lowered = eng._prefill_fn.lowered()
    assert len(lowered) == 3
    assert all("module @jit_prefill" in low.as_text() for low in lowered)


def test_a_scheduler_run_gives_the_plain_loops_streams():
    eng = _gpt()
    sched, plans = _planned(eng)
    assert plans[0]["traced"] == "once"
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 128, n).tolist()
               for n in (3, 8, 9, 20, 23, 5, 17)]
    asked = {sched.submit(prompt, max_new_tokens=6): prompt
             for prompt in prompts}
    got = {c.request_id: c.tokens for c in sched.run().completions}
    assert sorted(got) == sorted(asked)
    # the reference prefills through an engine that traces a bucket at a
    # time
    every = _unplanned()
    for rid, prompt in asked.items():
        assert got[rid] == plain_loop.reference(every, prompt, 6), rid
    # three buckets (8, 16, 24 tokens), each a specialisation of one trace
    assert sorted(eng._prefill_fn.avals) == [(1, 8), (1, 16), (1, 24)]


def _prefill_rows(since):
    return [r for r in build_log.rows[since:]
            if r.get("dispatch") == "jit(prefill)"]


def test_the_build_log_shows_the_model_traced_once():
    """JAX reports a ``trace`` row named ``prefill`` for the model's one
    tracing, ahead of the first bucket's own build inside its first call,
    and one for the function of the same name that calls the exported
    module, at every bucket (milliseconds). What says that the model's
    Python ran is the rows of the helpers it jits on the way (``_take``,
    ``_where``, ...): they lie under the first bucket's key alone, inside
    the first ``prefill`` row. No stage's interval lies inside another
    stage's, so a stage's seconds count nothing twice. A second call of a
    bucket builds nothing."""
    build_log.listen()
    eng = _gpt()
    _planned(eng)
    since = len(build_log.rows)
    buckets = (BUCKET, 3 * BUCKET, 2 * BUCKET)
    for tokens in buckets:
        eng._prefill_fn(eng.params, *_left_padded(tokens, 3, seed=1))
    rows = _prefill_rows(since)
    by_key = {repr((1, t)): [r for r in rows if r["key"] == repr((1, t))]
              for t in buckets}
    first, *later = (by_key[repr((1, t))] for t in buckets)

    def traced(rows):
        return [r["program"] for r in rows if r["stage"] == "trace"]

    model, wrapper = [r for r in first if r["stage"] == "trace"
                      and r["program"] == "prefill"]
    helpers = [r for r in first if r["stage"] == "trace"
               and r["program"] != "prefill"]
    assert any(model["start"] <= r["start"] and r["end"] <= model["end"]
               for r in helpers)
    assert not any(wrapper["start"] <= r["start"] <= wrapper["end"]
                   for r in helpers)
    # the export's tracing, then its lowering, then the bucket's three
    own = [r for r in first if r["program"] in ("prefill", "jit(prefill)")]
    assert [r["stage"] for r in own] == [
        "trace", "lower", "trace", "lower", "compile_or_load"]
    assert all(a["end"] <= b["start"] + 1e-6 for a, b in zip(own, own[1:]))
    for rows_of_bucket in later:
        assert traced(rows_of_bucket) == ["prefill"]
    # one compile a bucket, as ever: what counts programs counts the same
    assert [r["key"] for r in rows if r["stage"] == "compile_or_load"
            and r["program"] == "jit(prefill)"] \
        == [repr((1, t)) for t in buckets]
    n = len(build_log.rows)
    eng._prefill_fn(eng.params, *_left_padded(3 * BUCKET, 9, seed=2))
    assert len(build_log.rows) == n
    # where each bucket is traced, each has the model's helpers under it
    every = _unplanned()
    since = len(build_log.rows)
    for tokens in buckets:
        every._prefill_fn(every.params, *_left_padded(tokens, 3, seed=1))
    rows = _prefill_rows(since)
    for t in buckets:
        of = [r for r in rows if r["key"] == repr((1, t))]
        assert traced(of).count("prefill") == 1
        assert set(traced(of)) - {"prefill"}


# ---------------------------------------------------------------------------
# the rule against what jax.export does
# ---------------------------------------------------------------------------
def _real(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _gpt_cell():
    """The GPT serve cells' model at its widths (2048 x 16 heads, vocab
    50,257, bf16), two of its 24 scanned layers: the loop's body is traced
    once whatever their number."""
    c = _real("gpt-1.3b-bf16")
    m, s = c["model"], c["serve"]
    return GPTConfig(
        vocab_size=m["vocab_size"], n_positions=m["n_positions"],
        n_embd=m["n_embd"], n_layer=2, n_head=m["n_head"],
        mlp_ratio=m["mlp_ratio"], activation=m["activation"],
        tie_word_embeddings=m["tie_word_embeddings"], dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, scan_layers=True,
        use_flash_attention=s["use_flash_attention"]), 64


def _tiny(builder, tiny):
    section = dict(tiny["serve"], param_dtype="bfloat16",
                   compute_dtype="bfloat16")
    return (builder.model_config(tiny, section),
            tiny["serve"]["serving"].get("prompt_bucket", 16))


def _cell(builder, name):
    c = _real(name)
    return (builder.model_config(c),
            c["serve"]["serving"].get("prompt_bucket", 64))


# family: its tiny configuration and its cell's, each -> (GPTConfig, bucket),
# and what the rule names where it refuses (None: one trace serves both)
FAMILIES = {
    "gpt": (lambda: (_gpt_config(), BUCKET), _gpt_cell, None),
    "falcon-h1": (
        lambda: _tiny(falcon_h1_serve, falcon_h1_tiny.TINY_FALCON_H1),
        lambda: _cell(falcon_h1_serve, "falcon-h1-34b-6layer"),
        "ssd_chunked_scan"),
    "brumby": (lambda: _tiny(brumby_serve, brumby_tiny.TINY_BRUMBY),
               lambda: _cell(brumby_serve, "brumby-14b-5layer"),
               "retention_chunked"),
    "deepseek-v2": (
        lambda: _tiny(deepseek_v2_serve, deepseek_v2_tiny.TINY_DEEPSEEK),
        lambda: _cell(deepseek_v2_serve, "deepseek-v2-ep8-5layer"),
        "experts"),
    "keye-vl": (lambda: _tiny(keye_vl_serve, keye_vl_tiny.TINY_KEYE),
                lambda: _cell(keye_vl_serve, "keye-vl-2.0-ep8-6layer"),
                "experts"),
    "lfm2": (lambda: _tiny(lfm2_serve, lfm2_tiny.TINY_LFM2),
             lambda: _cell(lfm2_serve, "lfm2-8b-a1b-12layer"), "experts"),
    "trinity": (lambda: _tiny(afmoe_serve, trinity_tiny.TINY_TRINITY),
                lambda: _cell(afmoe_serve, "trinity-large-ep8-5layer"),
                "experts"),
    "dots3": (lambda: _tiny(dots3_serve, dots3_tiny.TINY_DOTS3),
              lambda: _cell(dots3_serve, "dots3-note-ep8-5layer"),
              "experts"),
}


@pytest.mark.parametrize("size", ["tiny", "real"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_what_the_rule_admits_exports(family, size):
    """One-sided: where ``prefill_bucket_dependence`` is None, ``jax.export``
    of the engine's own ``prefill`` for the TPU succeeds over the
    parameters' shapes alone (nothing is materialised, compiled or run), at
    the family's tiny and at its cell's widths; everything else the rule
    refuses by the name of what decides, and nothing is tried."""
    reset_default_topology()
    tiny, real, refused_for = FAMILIES[family]
    cfg, granule = (tiny if size == "tiny" else real)()
    why = cfg.prefill_bucket_dependence
    if refused_for is not None:
        assert refused_for in why
        return
    assert why is None
    eng = InferenceEngine(GPT(cfg), {"dtype": "bf16"}, seed=0)
    set_default_topology(eng.topology)
    eng._build_decode_fns()
    exported = export_prefill(
        eng._prefill_fn.fn, LaneLayout(eng.module, 1)._abstract_params(),
        granule, cfg.n_positions // granule, platforms=["tpu"])
    ids, mask = exported.in_avals[-2:]
    assert str(ids.shape) == str(mask.shape) == f"(1, {granule}*b)"
    assert exported.platforms == ("tpu",)


# ---------------------------------------------------------------------------
# what the plan leaves where it was
# ---------------------------------------------------------------------------
def test_a_batch_of_generate_traces_the_model_as_ever():
    build_log.listen()
    eng = _gpt()
    _planned(eng)
    since = len(build_log.rows)
    prompts = np.random.RandomState(3).randint(1, 128, (2, BUCKET))
    got = eng.generate(prompts, max_new_tokens=4)
    rows = [r for r in _prefill_rows(since) if r["stage"] == "trace"]
    assert [r["key"] for r in rows if r["program"] == "prefill"] \
        == [repr((2, BUCKET))]
    assert {r["program"] for r in rows} - {"prefill"}   # the model ran
    other = _gpt()
    assert np.array_equal(np.asarray(got), np.asarray(
        other.generate(prompts, max_new_tokens=4)))
    # a span that is no whole bucket (a prefix cache's cut) likewise
    ids, mask = _left_padded(BUCKET + 3, BUCKET + 3, seed=4)
    a = eng._prefill_fn(eng.params, ids, mask)
    b = other._prefill_fn(other.params, ids, mask)
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_parameters_split_over_tp_keep_a_trace_a_bucket():
    reset_default_topology()
    eng = InferenceEngine(
        GPT(_gpt_config()),
        {"dtype": "fp32", "tensor_parallel": {"tp_size": 2}}, seed=0)
    _, plans = _planned(eng)
    assert [p["traced"] for p in plans] == ["per_bucket"]
    assert "tp" in plans[0]["why"]
    assert eng._prefill_fn.before_first is None
    reset_default_topology()


def test_the_continuation_and_verification_programs_are_as_they_were():
    eng = _gpt()
    eng._materialize(jnp.zeros((1, BUCKET), jnp.int32))
    eng._build_decode_fns()
    before = (eng._prefill_more_fn.fn, eng._verify_greedy_fn.fn,
              eng._decode_k_fn.fn)
    _planned(eng)
    assert before == (eng._prefill_more_fn.fn, eng._verify_greedy_fn.fn,
                      eng._decode_k_fn.fn)
    # a prefix cache's continuation runs prefill_more over what a planned
    # prefill left, and gives what one pass gives
    ids, mask = _left_padded(2 * BUCKET, 2 * BUCKET, seed=5)
    _, cache = eng._prefill_fn(eng.params, ids[:, :BUCKET], mask[:, :BUCKET])
    logits, _ = eng._prefill_more_fn(eng.params, ids[:, BUCKET:],
                                     mask[:, BUCKET:], cache)
    every = _unplanned()
    whole, _ = every._prefill_fn(every.params, ids, mask)
    assert np.allclose(np.asarray(logits), np.asarray(whole), atol=1e-5)


@pytest.mark.parametrize("why, kw", [
    ("sparse layout", dict(sparse=True)),
    ("experts", dict(moe_num_experts=4, moe_top_k=1)),
])
def test_models_the_rule_refuses_say_why_and_keep_their_route(why, kw):
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32, scan_layers=True)
    if kw.pop("sparse", False):
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import apply_sparse_attention

        model = apply_sparse_attention(
            GPT(GPTConfig(rotary=True, learned_positions=False, **base)),
            {"mode": "local_sliding_window", "block": BUCKET,
             "num_sliding_window_blocks": 3})
    else:
        model = GPT(GPTConfig(**base, **kw))
    assert why in model.config.prefill_bucket_dependence
    eng = InferenceEngine(model, {"dtype": "fp32"}, seed=0)
    _, plans = _planned(eng)
    assert [(p["traced"], p["why"]) for p in plans] \
        == [("per_bucket", model.config.prefill_bucket_dependence)]
    assert eng._prefill_fn.fn is eng._prefill_per_bucket
    assert eng._prefill_fn.before_first is None
