"""AFMoE / Trinity's stack (attention layers of two kinds side by side: a
window of positions with rotary and every position without, each kind with
its own cache, a ring beside a dense one; a gated attention output, four
norms a layer, sigmoid-routed experts of which a share is held beside a
shared one) on the normal serving path, at a small size on the CPU with
seeded weights, against the plain reference the benchmark's cell uses
(``perfbench/reference/afmoe.py``). The tiny model keeps the shape of the
thing: a leading dense sliding layer, then sliding, sliding, full, sliding;
a window of 8 positions and a ring of 12 rows, so that the contexts below
pass the window several times and the ring wraps.

Tolerances. Program and reference are float32 with every matmul at
``highest`` (the fixture below), so they differ by the ORDER of float32
sums alone. Logits of these tiny models are ~0.5 in size and came out 5e-7
apart; 1e-5 leaves the sums an order of magnitude and is three orders under
what bfloat16 operands give."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import serving
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.lane_cache import LaneLayout
from deepspeed_tpu.models import kind_attention, kind_stacks
from deepspeed_tpu.models.transformer_lm import (
    GPT,
    AttentionKind,
    GPTConfig,
    MixedCacheError,
    gpt_tp_rules,
    num_params,
)
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.telemetry import scopes, telemetry_bus
from perfbench.builders import afmoe_serve
from perfbench.reference import afmoe as reference
from trinity_tiny import TINY_TRINITY

SIZES = reference.sizes(TINY_TRINITY)
VOCAB = TINY_TRINITY["vocab_size"]
WINDOW = TINY_TRINITY["sliding_window"]
RING = WINDOW + TINY_TRINITY["serve"]["window_slack"]
BUCKET = 8
ATOL = 1e-5


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def model_config(**changes):
    return dataclasses.replace(afmoe_serve.model_config(TINY_TRINITY),
                               **changes)


def served(slots=4, seed=3, **changes):
    eng = deepspeed_tpu.init_inference(GPT(model_config(**changes)),
                                       dtype="fp32", seed=seed)
    sched = serving.build_serving(eng, {"slots": slots,
                                        "prompt_bucket": BUCKET})
    sched._ensure_compiled()
    return eng, sched


@pytest.fixture(scope="module")
def fp32():
    return served()


def tokens(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, VOCAB, size=n)


def init(cfg, seed=0):
    model = GPT(cfg)
    return model, model.init(jax.random.PRNGKey(seed),
                             jnp.zeros((1, 8), jnp.int32))["params"]


# ---------------------------------------------------------------------------
# the declaration, and what follows it
# ---------------------------------------------------------------------------
def test_the_kinds_declare_window_ring_and_rotary_once():
    cfg = model_config()
    assert cfg.layer_types == ("window", "window", "window", "attention",
                               "window")
    assert cfg.attention_kind("window") == AttentionKind(
        WINDOW, RING, True, scopes.SCOPE_WINDOW_ATTN)
    assert cfg.attention_kind("attention") == AttentionKind(
        None, None, False, scopes.SCOPE_FULL_ATTN)
    assert cfg.pass_tokens == RING - WINDOW
    got = [(r.stack, r.first_layer, r.first_param, r.first_cache, r.length)
           for r in kind_stacks.layer_runs(cfg)]
    assert got == [("window_dense", 0, 0, 0, 1), ("window", 1, 0, 1, 2),
                   ("attention", 3, 0, 0, 1), ("window", 4, 2, 3, 1)]
    leaves = {(leaf.held_by, leaf.name): leaf for leaf in cfg.cache_leaves}
    assert set(leaves) == {(kind, name) for kind in ("attention", "window")
                           for name in ("cached_key", "cached_value")}
    assert leaves["window", "cached_key"].counted_as == ("window",)
    assert leaves["attention", "cached_key"].counted_as == ()
    assert cfg.layers_holding(leaves["window", "cached_key"]) == 4
    assert cfg.layers_holding(leaves["attention", "cached_key"]) == 1
    # a stack without window layers declares nothing of the sort: its
    # attention is the whole-model fields' and CausalSelfAttention's
    plain = GPTConfig(n_layer=2, n_embd=32, n_head=4, vocab_size=128)
    assert plain.attention_kind(None) is None and plain.pass_tokens is None
    assert LaneLayout(GPT(plain), 2).window is None


@pytest.mark.parametrize("fault", [
    "no_window", "fields_without_the_kind", "rotary_kind_unknown",
    "rotary_off", "alibi", "learned_positions", "sparse", "unrolled"])
def test_a_declaration_that_cannot_be_run_is_refused(fault):
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig

    changes = {
        "no_window": dict(sliding_window=None),
        "fields_without_the_kind": dict(layer_types=("attention",) * 5),
        "rotary_kind_unknown": dict(rotary_kinds=("conv",)),
        "rotary_off": dict(rotary=False),
        "alibi": dict(alibi=True),
        "learned_positions": dict(learned_positions=True),
        "sparse": dict(sparse_attention=FixedSparsityConfig(num_heads=4)),
        "unrolled": dict(scan_layers=False)}[fault]
    with pytest.raises(ValueError):
        model_config(**changes)


def test_an_int8_store_is_refused_with_its_reason():
    with pytest.raises(MixedCacheError, match="one scale a .position") as err:
        model_config(kv_cache_dtype="int8")
    assert err.value.feature == "kv_cache_dtype='int8'"


@pytest.mark.parametrize("feature", ["draft_engine", "prefix_cache"])
def test_what_is_not_built_over_two_kinds_of_cache_is_refused_at_build(
        fp32, feature):
    from deepspeed_tpu.serving.prefix_cache import PrefixCache

    eng, _ = fp32
    asked = {"draft_engine": dict(draft_engine=eng, spec_k=2),
             "prefix_cache": dict(prefix_cache=PrefixCache())}[feature]
    with pytest.raises(MixedCacheError, match="ring of rows") as err:
        serving.ContinuousBatchingScheduler(
            eng, slots=2, prompt_bucket=BUCKET, **asked)
    assert err.value.feature.startswith(feature)
    assert {"draft_engine": "verify pass", "prefix_cache":
            "promotion boundary"}[feature] in str(err.value)


def test_num_params_and_tp_rules_follow_the_declaration(fp32):
    eng, _ = fp32
    cfg = eng.module.config
    # the count leaves the experts to the MoE layer: compare without them
    dense = model_config(moe_num_experts=0, moe_experts_held=None,
                         first_k_dense=0)
    _, params = init(dense)
    assert num_params(dense) == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    assert gpt_tp_rules("h/window/attn/c_gate/kernel", (3, 32, 32)) == \
        gpt_tp_rules("h/window/attn/c_attn/kernel", (3, 32, 64))
    assert cfg.attn_output_gate and cfg.post_norms


def test_parameters_are_stacked_per_kind_of_block(fp32):
    eng, _ = fp32
    h = eng.params["h"]
    assert sorted(h) == ["attention", "window", "window_dense"]
    assert h["window"]["attn"]["c_attn"]["kernel"].shape == (3, 32, 64)
    assert h["window"]["attn"]["c_gate"]["kernel"].shape == (3, 32, 32)
    assert h["window_dense"]["mlp"]["c_fc"]["kernel"].shape == (1, 32, 48)
    # 16 experts scored, 4 held, one shared
    assert h["window"]["mlp"]["gate"]["kernel"].shape == (3, 32, 16)
    assert h["window"]["mlp"]["expert_bias"].shape == (3, 16)
    assert h["window"]["mlp"]["experts"]["wi"].shape == (3, 4, 32, 16)
    assert h["attention"]["mlp"]["shared"]["c_fc"]["kernel"].shape \
        == (1, 32, 16)
    assert sorted(k for k in h["attention"] if k.startswith("ln")) == [
        "ln_1", "ln_1_post", "ln_2", "ln_2_post"]


# ---------------------------------------------------------------------------
# the forward pass in each form, and the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("form", ["train", "passes", "steps", "chunk"])
def test_each_form_gives_the_references_logits(form):
    """Without a cache (``train``); a prefill in passes of ``pass_tokens``
    (``passes``); one token at a time from the fifth on, through the
    decode kernel (``steps``); passes of 3 tokens on a cache that exists
    (``chunk``). 40 positions pass the window of 8 five times and wrap the
    ring of 12 three times."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    ids = jnp.asarray(np.stack([tokens(40, seed=s) for s in (0, 1)]))
    want = np.stack([reference.logits(params, np.asarray(row), SIZES)
                     for row in ids])
    # (each pass one compiled program, as test_dots3.py's: outside
    # ``jax.jit`` every operation of the model is compiled by itself)
    if form == "train":
        got = jax.jit(model.apply)({"params": params}, ids)
    else:
        first, step = {"passes": (4, 4), "steps": (4, 1),
                       "chunk": (4, 3)}[form]
        got, state = jax.jit(lambda ids: model.apply(
            {"params": params}, ids, decode=True, mutable=["cache"]))(
                ids[:, :first])
        parts, at = [got], first
        more = jax.jit(lambda cache, ids: model.apply(
            {"params": params, "cache": cache}, ids, decode=True,
            mutable=["cache"]))
        while at < 40:
            out, state = more(state["cache"], ids[:, at:at + step])
            parts.append(out)
            at += step
        got = jnp.concatenate(parts, axis=1)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)


def test_a_pass_longer_than_the_rings_slack_is_refused():
    model, params = init(model_config())
    ids = jnp.asarray(tokens(24)[None])
    _, state = model.apply({"params": params}, ids[:, :RING - WINDOW + 1],
                           decode=True, mutable=["cache"])
    with pytest.raises(ValueError, match="pass_tokens"):
        model.apply({"params": params, "cache": state["cache"]},
                    ids[:, :RING - WINDOW + 2], decode=True,
                    mutable=["cache"])
    cfg = model.config
    assert engine_mod.prefill_chunk_spans(cfg, 4) is None
    assert engine_mod.prefill_chunk_spans(cfg, 10) == [(0, 4), (4, 8),
                                                       (8, 10)]
    assert engine_mod.continuation_chunk_spans(cfg, 16, 22) == [(16, 20),
                                                                (20, 22)]


def attention_of(kind, x, decode_from=None):
    """One ``KindAttention`` layer over ``x [1, T, C]``: without a cache,
    or a cache made over the first ``decode_from`` rows and one token at a
    time from there (the decode kernel)."""
    layer = kind_attention.KindAttention(model_config(), kind)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    if decode_from is None:
        return layer.apply({"params": params}, x)
    out, state = layer.apply({"params": params}, x[:, :decode_from],
                             decode=True, mutable=["cache"])
    parts = [out]
    # one compiled step, not the layer's operations one by one at each
    step = jax.jit(lambda cache, x: layer.apply(
        {"params": params, "cache": cache}, x, decode=True,
        mutable=["cache"]))
    for t in range(decode_from, x.shape[1]):
        out, state = step(state["cache"], x[:, t:t + 1])
        parts.append(out)
    return jnp.concatenate(parts, axis=1)


@pytest.mark.parametrize("decode_from", [None, 3])
def test_the_window_is_exact_at_its_edge(decode_from):
    """Row 0's key and value reach query ``i`` where ``i - 0 < window``: a
    change of row 0 moves the outputs of rows 0..window-1 (``i - j =
    window - 1`` is seen) and leaves row ``window`` and every later one as
    it was, to the bit; a layer that sees everything moves them all."""
    cfg = model_config()
    x = jax.random.normal(jax.random.PRNGKey(1), (1, WINDOW + 4, 32))
    moved = x.at[0, 0].add(1.0)
    for name in ("window", "attention"):
        kind = cfg.attention_kind(name)
        a = np.asarray(attention_of(kind, x, decode_from))[0]
        b = np.asarray(attention_of(kind, moved, decode_from))[0]
        changed = np.abs(a - b).max(-1) > 0
        if name == "window":
            assert changed[:WINDOW].all() and not changed[WINDOW:].any()
        else:
            assert changed.all()


def test_layers_that_see_everything_carry_no_rotary(fp32):
    """The same prompt at two pad offsets (buckets of 8: 13 tokens after 3
    pads, and after 11): a window layer's cached keys are turned by the
    shift, the full layer's are the same numbers."""
    eng, _ = fp32
    prompt = tokens(13, seed=5)

    def keys(pads):
        ids = np.zeros((1, pads + 13), np.int32)
        mask = np.zeros((1, pads + 13), bool)
        ids[0, pads:], mask[0, pads:] = prompt, True
        _, cache = eng._chunked_prefill(jnp.asarray(ids), jnp.asarray(mask))
        full = np.asarray(cache["h"]["attention"]["attn"]["cached_key"])[
            0, 0, pads:pads + 13]
        ring = cache["h"]["window"]["attn"]
        at = np.asarray(ring["slot_pos"])[0, 0]
        newest = [int(np.nonzero(at == pads + t)[0][0])
                  for t in range(13 - WINDOW, 13)]
        return full, np.asarray(ring["cached_key"])[0, 0, newest]

    (full_a, ring_a), (full_b, ring_b) = keys(3), keys(11)
    np.testing.assert_allclose(full_a, full_b, atol=ATOL, rtol=0)
    assert np.abs(ring_a - ring_b).max() > 0.1


# ---------------------------------------------------------------------------
# the expert layer: sigmoid scores over all, a share held, one shared expert
# ---------------------------------------------------------------------------
def test_the_renormaliser_is_the_models_own():
    logits = jax.random.normal(jax.random.PRNGKey(2), (6, 16)) * 3
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    got = topk_routing(logits, 2, True, scale=2.448, scoring="sigmoid",
                       bias=bias, renorm_eps=1e-20)
    s = np.asarray(jax.nn.sigmoid(logits), np.float64)
    for t in range(6):
        chosen = np.argsort(-(s[t] + np.asarray(bias)), kind="stable")[:2]
        assert sorted(chosen) == sorted(np.asarray(got.experts[t]))
        want = 2.448 * s[t, np.asarray(got.experts[t])] / s[t, chosen].sum()
        np.testing.assert_allclose(np.asarray(got.weights[t]), want,
                                   rtol=1e-6)
    # the default is what it was: 1e-6 under the sum
    old = topk_routing(logits, 2, True, scoring="sigmoid", bias=bias)
    mid = topk_routing(logits, 2, True, scoring="sigmoid", bias=bias,
                       renorm_eps=1e-6)
    assert (np.asarray(old.weights) == np.asarray(mid.weights)).all()
    assert (np.asarray(old.weights) < np.asarray(got.weights) / 2.448).all()


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """Four devices hold 4 of the 16 experts each (the cell's eight hold 32
    of 256): each share's output less the shared expert is its routed part;
    the four parts plus the shared expert ONCE are the uncut reference's
    layer."""
    def layer(held):
        return MoE(d_model=32, d_hidden=16, num_experts=16, k=2,
                   drop_tokens=False, gated_experts=True,
                   norm_topk_prob=True, n_shared=1, routed_scale=2.448,
                   experts_held=held, scoring="sigmoid", expert_bias=True,
                   expert_bias_init=0.1, renorm_eps=1e-20,
                   dtype=jnp.float32, param_dtype=jnp.float32)

    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 32))
    params = layer(None).init(jax.random.PRNGKey(5), x)["params"]
    sh = params["shared"]
    shared = reference.swiglu(x[0], sh["c_gate"]["kernel"],
                              sh["c_fc"]["kernel"], sh["c_proj"]["kernel"])
    total = shared
    for first in range(0, 16, 4):
        mine = dict(params, experts=jax.tree.map(
            lambda a: a[first:first + 4], params["experts"]))
        out = layer((first, 4)).apply({"params": mine}, x)[0][0]
        total = total + (out - shared)
    uncut = dict(SIZES, held=(0, 16))
    want = reference.moe(
        x[0], params, uncut,
        lambda name, e: params["experts"][name][e])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=ATOL, rtol=0)
    whole = layer(None).apply({"params": params}, x)[0][0]
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=ATOL, rtol=0)


def test_expert_load_says_the_share_of_experts_that_got_a_row(fp32):
    from deepspeed_tpu.moe.utils import publish_expert_load

    eng, _ = fp32
    seen = []
    telemetry_bus.subscribe(seen.append)
    try:
        load = publish_expert_load(
            eng.module, eng.params,
            {"input_ids": tokens(3, seed=9)[:, None].astype(np.int32)})
    finally:
        telemetry_bus.unsubscribe(seen.append)
    counts = np.asarray(load["tokens_per_expert"])
    assert counts.shape == (4, 4) and load["held"] == 4
    assert load["experts_with_rows_share"] == (counts > 0).mean() < 1.0
    assert any(ev.get("kind") == "moe.load" for ev in seen)


# ---------------------------------------------------------------------------
# the scheduler's cache: a ring beside a dense leaf
# ---------------------------------------------------------------------------
def reference_greedy(params, prompt, n):
    """(Padded on the right to one length: a causal model's rows never
    read the padding, and every length then shares one compile.)"""
    seq = list(prompt)
    for _ in range(n):
        ids = np.zeros((64,), np.int64)
        ids[:len(seq)] = seq
        row = reference.logits(params, ids, SIZES,
                               positions=[len(seq) - 1])[0]
        seq.append(int(row.argmax()))
    return seq[len(prompt):]


def test_the_scheduler_serves_the_references_greedy_tokens_with_lanes_reused(
        fp32):
    """Five ragged prompts (left-padded into buckets of 8, prefilled in
    passes of 4) over two lanes: the third to fifth are spliced into lanes
    whose rings have wrapped under the requests before them, beside a live
    lane, and every token is the plain reference's argmax over prompt +
    tokens so far; the longest context, 45 rows, passes the window five
    times."""
    eng, _ = fp32
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    prompts = [tokens(n, seed=1).tolist() for n in (5, 16, 21, 3, 30)]
    got = {}
    rids = [sched.submit(p, max_new_tokens=12 + i,
                         stream_callback=lambda r, t, d: got.setdefault(
                             r, []).append(int(t)))
            for i, p in enumerate(prompts)]
    stats = sched.run()
    assert stats.decode_steps > 0
    for i, (rid, prompt) in enumerate(zip(rids, prompts)):
        assert got[rid] == reference_greedy(eng.params, prompt, 12 + i), i


def test_a_lane_may_not_run_past_the_layers_that_keep_every_position(fp32):
    """The full layer bounds a lane's positions; a stack of window layers
    alone would stream."""
    eng, sched = fp32
    assert not sched.lane_cache.streams and not sched._streaming
    with pytest.raises(ValueError, match="exceeds the KV cache capacity"):
        sched.submit(tokens(30).tolist(), max_new_tokens=40)
    only = LaneLayout(GPT(model_config(
        n_layer=2, first_k_dense=0, layer_types=("window", "window"))), 2)
    assert only.streams


def test_the_cache_holds_the_windows_rows_beside_every_position(fp32):
    """A window layer's leaf has the ring's rows whatever ``n_positions``
    is, the full layer's ``n_positions``; the plan event and
    ``kv_cache_stats`` count the window's bytes apart."""
    eng, _ = fp32
    seen = []
    telemetry_bus.subscribe(seen.append)
    try:
        sched = serving.build_serving(eng, {"slots": 3,
                                            "prompt_bucket": BUCKET})
        sched._ensure_compiled()
    finally:
        telemetry_bus.unsubscribe(seen.append)
    shapes = sched.lane_cache.shapes["h"]
    assert shapes["window"]["attn"]["cached_key"].shape == (4, 3, RING, 2, 8)
    assert shapes["window"]["attn"]["slot_pos"].shape == (4, 3, RING)
    assert shapes["attention"]["attn"]["cached_key"].shape \
        == (1, 3, 64, 2, 8)
    assert "slot_pos" not in shapes["attention"]["attn"]
    empty = sched.lane_cache.empty()["h"]
    assert (np.asarray(empty["window"]["attn"]["slot_pos"]) == -1).all()
    geo = sched.kv_cache_stats()
    row = 2 * 2 * 8 * 4
    assert geo["window_bytes_per_lane"] == 4 * RING * row
    assert geo["kv_bytes_per_lane"] - geo["window_bytes_per_lane"] \
        > 64 * row
    assert geo["leaf_layers"] == {"cached_key": 5, "cached_value": 5}
    plan, = [ev for ev in seen if ev.get("kind") == "serve.cache_plan"]
    assert plan["window_bytes_per_lane"] == geo["window_bytes_per_lane"]
    assert plan["decode_attention"] == "live_blocks"


def test_the_cuts_geometry_at_the_published_widths():
    """``LaneLayout`` over abstract parameters at the cell's real size: a
    lane keeps 4,352 rows in each window layer and 24,576 in the full one,
    and the window's share is the issue's 40-42%."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs",
                           "trinity-large-ep8-5layer.json")) as fh:
        config = json.load(fh)
    layout = LaneLayout(GPT(afmoe_serve.model_config(config)), 24)
    shapes = layout.shapes["h"]
    assert shapes["window"]["attn"]["cached_key"].shape \
        == (4, 24, 4352, 8, 128)
    assert shapes["attention"]["attn"]["cached_key"].shape \
        == (1, 24, 24576, 8, 128)
    geo = layout.geometry()
    assert geo["window_bytes_per_lane"] == 4 * 4352 * 4096
    assert 0.40 < geo["window_bytes_per_lane"] / geo["bytes_per_lane"] < 0.42
    assert geo["bytes_per_lane"] * 24 < 4.14e9
    assert config["bytes"]["lane_cache_bytes"] == geo["resident_bytes"]


class Stop(Exception):
    pass


def test_lanes_at_exit_hold_what_each_kind_keeps_of_every_token_taken_in(
        fp32):
    """What the benchmark's check reads: ``positions`` of a lane, a kind
    at a time. The full layer's rows are the request's prompt and tokens,
    exactly those ``valid`` marks; a ring holds the newest rows, each where
    ``slot_pos`` says; all are the reference's keys and values (rotary
    counting cache rows in the window layers)."""
    eng, _ = fp32
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    sched.retain_lanes = True
    prompts = {sched.submit(tokens(n, seed=2).tolist(), max_new_tokens=40): n
               for n in (5, 21)}

    def poll(state={"n": 0}):
        state["n"] += 1
        if state["n"] > 25:
            raise Stop

    with pytest.raises(Stop):
        sched.run(poll_fn=poll)
    kept = sched.lanes_at_exit
    assert sorted(kept.live) == [0, 1]
    for lane, comp in kept.live.items():
        n_prompt = prompts[comp.request_id]
        full = kept.positions(lane, "attention")
        ring = kept.positions(lane, "window")
        assert full["cached_key"].shape == (1, 64, 2, 8)
        assert ring["cached_key"].shape == (4, RING, 2, 8)
        assert ring["slot_pos"].shape == (4, RING) and "slot_pos" not in full
        first = -(-n_prompt // BUCKET) * BUCKET - n_prompt
        n = n_prompt + len(comp.tokens)
        assert n > 2 * RING
        valid = np.asarray(full["valid"])[0]
        assert valid.sum() == n and valid[first:first + n].all()
        seq = tokens(n_prompt, seed=2).tolist() + list(comp.tokens)
        _, states = reference.hidden_and_states(
            eng.params, np.asarray(seq), SIZES, offset=first)
        np.testing.assert_allclose(
            full["cached_key"][0, first:first + n], states[3][0],
            atol=ATOL, rtol=0)
        for i, layer in enumerate((0, 1, 2, 4)):
            at = np.asarray(ring["slot_pos"])[i]
            assert sorted(at) == list(range(first + n - RING, first + n))
            assert (at % RING == np.arange(RING)).all()
            for name, want in zip(("cached_key", "cached_value"),
                                  states[layer]):
                np.testing.assert_allclose(
                    np.asarray(ring[name])[i], want[at - first], atol=ATOL,
                    rtol=0)


def test_rewind_steps_both_kinds_of_cache_back():
    """``LaneLayout.rewind`` over a ring that has wrapped beside a dense
    leaf: three tokens are taken in on top of a snapshot and two of them
    rejected; the cache then continues as if it had taken in one, and its
    logits are the reference's."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    layout = LaneLayout(model, 1)
    ids = jnp.asarray(tokens(40, seed=7)[None])
    want = reference.logits(params, np.asarray(ids[0]), SIZES)
    more = jax.jit(lambda cache, ids: model.apply(
        {"params": params, "cache": cache}, ids, decode=True,
        mutable=["cache"]))
    _, state = model.apply({"params": params}, ids[:, :4], decode=True,
                           mutable=["cache"])
    cache = state["cache"]
    for at in range(4, 30):
        _, state = more(cache, ids[:, at:at + 1])
        cache = state["cache"]
    snapshot = layout.copy(cache)
    wrong = (ids[:, 30:33] + 1) % VOCAB
    wrong = wrong.at[0, 0].set(ids[0, 30])      # the first is accepted
    _, state = more(cache, wrong)
    cache = layout.rewind(snapshot, state["cache"], jnp.asarray([2]))
    ring = cache["h"]["window"]["attn"]
    assert int(ring["cache_index"][0, 0]) == 31
    assert sorted(np.asarray(ring["slot_pos"])[0, 0]) == list(range(19, 31))
    got = []
    for at in range(31, 40):
        out, state = more(cache, ids[:, at:at + 1])
        cache = state["cache"]
        got.append(np.asarray(out)[0, 0])
    np.testing.assert_allclose(np.stack(got), want[31:40], atol=ATOL, rtol=0)


# ---------------------------------------------------------------------------
# scopes, and which path a decode step takes
# ---------------------------------------------------------------------------
def test_a_decode_steps_layers_of_both_kinds_go_through_the_kernel(
        fp32, monkeypatch):
    from deepspeed_tpu.ops.pallas import decode_attention as da

    calls = []
    real = da.decode_attention

    def spy(q, k_cache, *args, **kw):
        calls.append(k_cache.shape)
        return real(q, k_cache, *args, **kw)

    monkeypatch.setattr(da, "decode_attention", spy)
    eng, _ = served(seed=4)
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    sched.submit(tokens(5).tolist(), max_new_tokens=3)
    sched.run()
    # out of the kind's STACKED leaf, one call a run of equal kind: three
    # runs of window layers for one of full (the calls on one layer's own
    # leaf are the shape probes')
    stacked = [shape for shape in calls if len(shape) == 5 and shape[1] == 2]
    assert set(stacked) == {(4, 2, RING, 2, 8), (1, 2, 64, 2, 8)}
    assert stacked.count((4, 2, RING, 2, 8)) \
        == 3 * stacked.count((1, 2, 64, 2, 8)) > 0
    table = sched.program_scopes()

    def scopes_of(program):
        return {c for path in table[program].values() if path
                for c in scopes.split_path(path)}

    for program in ("jit_decode_k", "jit_prefill", "jit_prefill_more"):
        assert {scopes.SCOPE_WINDOW_ATTN, scopes.SCOPE_FULL_ATTN,
                scopes.SCOPE_MOE_ROUTER, scopes.SCOPE_MOE_SHARED,
                scopes.SCOPE_KV_CACHE_WRITE} <= scopes_of(program), program
        assert scopes.SCOPE_ATTN_CORE not in scopes_of(program)
