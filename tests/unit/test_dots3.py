"""dots3's stack (latent attention of two kinds side by side: heads over a
dense latent cache read through an indexer's choice of rows, heads of
another count and width over a ring of latents that sees a window; a gate a
head; sigmoid-routed experts of which a share is held beside a shared one)
on the normal serving path, at a small size on the CPU with seeded weights,
against the plain reference the benchmark's cell uses
(``perfbench/reference/dots3.py``). The tiny model keeps the shape of the
thing: a leading dense full layer, a full expert layer, three sliding ones;
a window of 5 positions in a ring of 8, an indexer that chooses 8 rows, so
that the contexts below pass the window many times, wrap the ring and pass
``index_topk`` (the selection bites from the ninth row on).

Tolerances. Program and reference are float32 with every matmul at
``highest`` (the fixture below), so they differ by the ORDER of float32
sums alone. Logits of these tiny models are ~0.5 in size and came out 1e-6
apart; 1e-5 leaves the sums an order of magnitude."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import serving
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.lane_cache import LaneLayout
from deepspeed_tpu.models import kind_stacks, latent_attention
from deepspeed_tpu.models.transformer_lm import (
    GPT,
    IndexKeyError,
    LatentCacheError,
    MixedCacheError,
    decode_attention_block,
)
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.ops import indexed_attention
from deepspeed_tpu.telemetry import scopes, telemetry_bus
from dots3_tiny import TINY_DOTS3
from perfbench.builders import dots3_serve
from perfbench.reference import dots3 as reference

SIZES = reference.sizes(TINY_DOTS3)
VOCAB = TINY_DOTS3["vocab_size"]
WINDOW = TINY_DOTS3["sliding_window_size"]
RING = WINDOW + TINY_DOTS3["serve"]["window_slack"]
TOPK = TINY_DOTS3["index_topk"]
BUCKET = 8
ATOL = 1e-5


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield
    # this file compiles several hundred small programs (two kinds of
    # layer, three forms each, a reference a layer kind): let each test's
    # executables go, or the CPU compiler runs out of address space
    jax.clear_caches()


def model_config(**changes):
    return dataclasses.replace(dots3_serve.model_config(TINY_DOTS3),
                               **changes)


def kinds_with(mixer, **changes):
    """``latent_kinds`` with one kind's declaration changed."""
    return tuple((name, dataclasses.replace(kind, **changes)
                  if name == mixer else kind)
                 for name, kind in model_config().latent_kinds)


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


def start(model, params, ids, **kw):
    """The pass that makes a cache, compiled (an eager pass runs the
    stack's scans an operation at a time)."""
    return jax.jit(lambda ids: model.apply(
        {"params": params}, ids, decode=True, **kw))(ids)


def init(cfg, seed=0):
    model = GPT(cfg)
    return model, model.init(jax.random.PRNGKey(seed),
                             jnp.zeros((1, 8), jnp.int32))["params"]


# ---------------------------------------------------------------------------
# the declaration, and what follows it
# ---------------------------------------------------------------------------
def test_a_kind_declares_its_own_mixer_in_one_place():
    cfg = model_config()
    assert cfg.layer_types == ("attention", "attention", "window", "window",
                               "window")
    full, window = (cfg.attention_kind(k) for k in ("attention", "window"))
    assert (full.window, full.ring, full.scope) == (
        None, None, scopes.SCOPE_SPARSE_LATENT_ATTN)
    assert (window.window, window.ring, window.scope) == (
        WINDOW, RING, scopes.SCOPE_WINDOW_LATENT_ATTN)
    assert (full.latent.n_head, full.latent.mla.kv_rank,
            full.latent.mla.nope_dim, full.latent.rope_theta) \
        == (4, 32, 16, 8e7)
    assert (window.latent.n_head, window.latent.mla.kv_rank,
            window.latent.mla.nope_dim, window.latent.rope_theta) \
        == (2, 48, 24, 5e4)
    assert full.latent.indexer.topk == TOPK and window.latent.indexer is None
    assert full.latent.indexer.rope_dim == 8
    # a latent ring takes the exact bound: slack + 1 tokens evict no row
    # that one of them still sees
    assert cfg.pass_tokens == window.pass_tokens == RING - WINDOW + 1
    assert full.pass_tokens is None
    leaves = {(leaf.held_by, leaf.name): leaf for leaf in cfg.cache_leaves}
    assert set(leaves) == {
        ("attention", n) for n in (
            "cached_latent", "cached_rope_key", "cached_index_key",
            "chosen_rows", "choice_query", "choice_weights")} | {
        ("window", n) for n in ("cached_latent", "cached_rope_key")}
    assert leaves["window", "cached_latent"].counted_as == ("latent",
                                                            "window")
    assert leaves["attention", "cached_latent"].counted_as == ("latent",)
    assert leaves["attention", "cached_index_key"].counted_as == ("index",)
    assert leaves["attention", "chosen_rows"].kind == "step"
    assert cfg.layers_holding(leaves["window", "cached_latent"]) == 3
    assert cfg.layers_holding(leaves["attention", "cached_latent"]) == 2
    # the decode step of the layers that keep everything chooses its rows:
    # no block of a dense read to report
    assert decode_attention_block(cfg) is None
    assert decode_attention_block(dataclasses.replace(
        cfg, latent_kinds=kinds_with("attention", indexer=None))) == 64
    got = [(r.stack, r.first_layer, r.length)
           for r in kind_stacks.layer_runs(cfg)]
    assert got == [("attention_dense", 0, 1), ("attention", 1, 1),
                   ("window", 2, 3)]


@pytest.mark.parametrize("fault, says", [
    (dict(latent_kinds=model_config().latent_kinds[:1]), "for each kind"),
    (dict(latent_kinds=model_config().latent_kinds
          + model_config().latent_kinds[:1]), "for each kind"),
    (dict(attn_output_gate=True), "attn_output_gate"),
    (dict(n_kv_head=2), "n_kv_head"),
    (dict(qk_norm="head"), "qk_norm"),
    (dict(rotary_kinds=("window",)), "rotary_kinds"),
    (dict(layer_types=("attention",) * 5), "belong to a stack"),
    (dict(latent_kinds=tuple(
        (name, dataclasses.replace(
            kind, indexer=model_config().latent_kinds[0][1].indexer))
        for name, kind in model_config().latent_kinds)), "ring holds"),
    (dict(mla=model_config().latent_kinds[0][1].mla), "mixes kinds"),
])
def test_a_declaration_that_cannot_be_run_is_refused(fault, says):
    with pytest.raises(ValueError, match=says):
        model_config(**fault)


def test_an_int8_store_is_refused_with_its_reason():
    with pytest.raises(MixedCacheError, match="ring"):
        model_config(kv_cache_dtype="int8")


@pytest.mark.parametrize("feature, error, says", [
    ("draft_engine", LatentCacheError, "one clock a lane"),
    ("prefix_cache", LatentCacheError, "absorbed form"),
])
def test_what_is_not_built_over_the_three_leaf_families_is_refused_at_build(
        fp32, feature, error, says):
    from deepspeed_tpu.serving.prefix_cache import PrefixCache

    eng, _ = fp32
    asked = {"draft_engine": dict(draft_engine=eng, spec_k=2),
             "prefix_cache": dict(prefix_cache=PrefixCache())}[feature]
    with pytest.raises(error, match=says) as err:
        serving.ContinuousBatchingScheduler(
            eng, slots=2, prompt_bucket=BUCKET, **asked)
    assert err.value.feature.startswith(feature)


def test_each_leaf_family_refuses_what_it_cannot_serve(fp32):
    """``refuse`` walks the declared leaves: the latent leaves answer first
    for speculation and the prefix cache; with them set aside the index key
    and the ring give their own reasons."""
    _, sched = fp32
    layout = sched.lane_cache
    with pytest.raises(LatentCacheError):
        layout.refuse(draft_engine=False, prefix_cache=True)
    kept = layout.leaves
    try:
        layout.leaves = tuple(
            dataclasses.replace(leaf, counted_as=tuple(
                c for c in leaf.counted_as if c != "latent"))
            for leaf in kept)
        with pytest.raises(IndexKeyError, match="index keys"):
            layout.refuse(draft_engine=False, prefix_cache=True)
        with pytest.raises(MixedCacheError, match="verify pass"):
            layout.refuse(draft_engine=True, prefix_cache=False)
    finally:
        layout.leaves = kept


def test_parameters_and_cache_are_stacked_per_kind_at_the_kinds_widths(fp32):
    eng, sched = fp32
    h = jax.tree.map(lambda a: a.shape, eng.params)["h"]
    assert set(h) == {"attention_dense", "attention", "window"}
    assert h["attention"]["attn"]["q_b"]["kernel"] == (1, 32, 4 * 24)
    assert h["window"]["attn"]["q_b"]["kernel"] == (3, 32, 2 * 32)
    assert h["attention"]["attn"]["kv_b"]["kernel"] == (1, 32, 4 * 32)
    assert h["window"]["attn"]["kv_b"]["kernel"] == (3, 48, 2 * 40)
    assert h["attention"]["attn"]["c_gate"]["kernel"] == (1, 64, 4)
    assert h["window"]["attn"]["c_gate"]["kernel"] == (3, 64, 2)
    assert h["attention"]["attn"]["indexer"]["wq"]["kernel"] == (1, 32, 32)
    assert "indexer" not in h["window"]["attn"]
    assert "experts" not in h["attention_dense"]["mlp"]
    assert h["window"]["mlp"]["experts"]["wg"] == (3, 4, 64, 32)
    shapes = jax.tree.map(lambda a: a.shape, sched.lane_cache.shapes)["h"]
    full, ring = shapes["attention"]["attn"], shapes["window"]["attn"]
    assert full["cached_latent"] == (2, 4, 64, 32)
    assert full["cached_rope_key"] == (2, 4, 64, 8)
    assert full["cached_index_key"] == (2, 4, 64, 16)
    assert full["chosen_rows"] == (2, 4, TOPK) and "slot_pos" not in full
    assert ring["cached_latent"] == (3, 4, RING, 48)
    assert ring["slot_pos"] == (3, 4, RING)
    assert "cached_index_key" not in ring
    empty = sched.lane_cache.empty()["h"]
    assert (np.asarray(empty["window"]["attn"]["slot_pos"]) == -1).all()
    assert (np.asarray(empty["attention"]["attn"]["chosen_rows"]) == -1).all()


def test_the_plan_and_the_stats_say_the_three_families(fp32):
    eng, _ = fp32
    seen = []
    telemetry_bus.subscribe(seen.append)
    try:
        sched = serving.build_serving(eng, {"slots": 3,
                                            "prompt_bucket": BUCKET})
        sched.submit(tokens(20).tolist(), max_new_tokens=3)
        sched.run()
    finally:
        telemetry_bus.unsubscribe(seen.append)
    geo = sched.kv_cache_stats()
    assert geo["window_bytes_per_lane"] == 3 * RING * (48 + 8) * 4
    assert geo["index_key_bytes_per_lane"] == 2 * 64 * 16 * 4
    assert geo["latent_bytes_per_lane"] == geo["window_bytes_per_lane"] \
        + 2 * 64 * (32 + 8) * 4
    assert geo["leaf_layers"]["cached_latent"] == 5
    assert not sched.lane_cache.streams
    plan, = [ev for ev in seen if ev.get("kind") == "serve.cache_plan"]
    for field in ("window_bytes_per_lane", "index_key_bytes_per_lane",
                  "latent_bytes_per_lane"):
        assert plan[field] == geo[field]
    assert plan["decode_attention"] == "einsum"     # chosen rows, no blocks
    stats = [ev for ev in seen if ev.get("kind") == "serve.stats"
             and ev.get("lanes_active")]
    assert stats and all(
        ev["live_window_positions"] <= ev["live_chosen_positions"]
        <= ev["live_positions"] for ev in stats)
    assert stats[-1]["live_window_positions"] == WINDOW
    assert stats[-1]["live_chosen_positions"] == TOPK


# ---------------------------------------------------------------------------
# the forward pass in each form, and the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("form", ["train", "passes", "steps", "chunk"])
def test_each_form_gives_the_references_logits(form):
    """Without a cache (``train``); a prefill in passes of ``pass_tokens``
    (``passes``); one token at a time from the fifth on, through the
    decode kernel and the sparse step (``steps``); passes of 3 tokens on a
    cache that exists (``chunk``). 40 positions pass the window of 5 eight
    times, wrap the ring of 8 five times and pass ``index_topk`` = 8."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    ids = jnp.asarray(np.stack([tokens(40, seed=s) for s in (0, 1)]))
    want = np.stack([reference.logits(params, np.asarray(row), SIZES)
                     for row in ids])
    if form == "train":
        got = jax.jit(model.apply)({"params": params}, ids)
    else:
        first, step = {"passes": (4, 4), "steps": (4, 1),
                       "chunk": (4, 3)}[form]
        got, state = start(model, params, ids[:, :first],
                                 mutable=["cache"])
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


def test_the_sparse_step_is_the_absorbed_sums_over_the_rows_it_names():
    """``latent_decode_step`` alone, on a stacked cache: the rows it hands
    back are ``lax.top_k``'s of the visible scores, and its output is the
    softmax over exactly those rows of the absorbed scores (NumPy,
    float64), whatever lies in the rows it did not choose."""
    rng = np.random.default_rng(11)
    B, S, H, r, dr, Hi, Di, K, L = 2, 64, 4, 32, 8, 2, 16, 8, 2

    def draw(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    q_lat, q_rope = draw(B, H, r), draw(B, H, dr)
    q_idx, w = draw(B, Hi, Di), np.abs(draw(B, Hi))
    latent, rope_key = draw(L, B, S, r), draw(L, B, S, dr)
    index_keys = draw(L, B, S, Di)
    seen = np.asarray([40, 5])                   # the second sees fewer
    visible = np.arange(S)[None, :] < seen[:, None]
    scale = 0.17
    o_lat, rows, ok = jax.jit(
        lambda *a: indexed_attention.latent_decode_step(
            *a, 1, jnp.asarray(visible), K, scale, jnp.float32))(
        *(jnp.asarray(a) for a in (q_lat, q_rope, q_idx, w, latent,
                                   rope_key, index_keys)))
    rows, ok = np.asarray(rows), np.asarray(ok)
    assert ok.sum(1).tolist() == [K, 5]
    for b in range(B):
        dots = np.maximum(np.einsum("jd,sd->js", q_idx[b].astype(np.float64),
                                    index_keys[1, b]), 0)
        scores = (dots * w[b][:, None]).sum(0)[:seen[b]]
        want = np.sort(np.argsort(-scores, kind="stable")[:K])
        assert rows[b][ok[b]].tolist() == want.tolist()
        lat = latent[1, b, want].astype(np.float64)
        att = (q_lat[b] @ lat.T + q_rope[b] @ rope_key[1, b, want].T) * scale
        p = np.exp(att - att.max(-1, keepdims=True))
        np.testing.assert_allclose(
            np.asarray(o_lat)[b], (p / p.sum(-1, keepdims=True)) @ lat,
            atol=2e-5, rtol=0)


def test_the_selection_is_the_references_and_it_bites():
    """The rows each decode query chose, as the step leaves them in the
    cache: the reference's sets to the row in both full layers, and from
    the ninth position on a strict subset of what the query sees."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    ids = jnp.asarray(tokens(24, seed=9)[None])
    _, _, sets, _ = reference.hidden_and_states(
        params, np.asarray(ids[0]), SIZES, with_chosen=True)
    want = np.stack([np.asarray(s) for s in sets[:2]])      # [2, T, T]
    assert (want.sum(-1)[:, TOPK:] == TOPK).all()
    assert (want.sum(-1)[:, :TOPK] == np.arange(1, TOPK + 1)).all()
    more = jax.jit(lambda cache, ids: model.apply(
        {"params": params, "cache": cache}, ids, decode=True,
        mutable=["cache"]))
    _, state = start(model, params, ids[:, :4], mutable=["cache"])
    for at in range(4, 24):
        _, state = more(state["cache"], ids[:, at:at + 1])
        rows = np.asarray(
            state["cache"]["h"]["attention"]["attn"]["chosen_rows"])[:, 0]
        for layer in range(2):
            assert sorted(rows[layer][rows[layer] >= 0]) \
                == np.nonzero(want[layer, at])[0].tolist(), (layer, at)


def test_every_form_of_a_full_layer_chooses_the_same_rows():
    """One ``KindLatentAttention`` layer of the full kind, the chosen rows
    sown where a test asks: without a cache, in passes of 4 over a cache
    (the tiled form over the key tiles up to the pass's last row) and one
    token at a time (the step) choose the same sets."""
    kind = model_config().attention_kind("attention")
    layer = latent_attention.KindLatentAttention(model_config(), kind)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    _, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    want = np.asarray(state["intermediates"]["chosen"][0])[0]   # [T, T]
    assert (want.sum(-1) == np.minimum(np.arange(24) + 1, TOPK)).all()
    for step in (4, 1):
        more = jax.jit(lambda cache, x: layer.apply(
            {"params": params, "cache": cache}, x, decode=True,
            mutable=["cache", "intermediates"]))
        _, state = start(layer, params, x[:, :step],
                         mutable=["cache", "intermediates"])
        got = [np.asarray(state["intermediates"]["chosen"][0])[0]]
        for at in range(step, 24, step):
            _, state = more(state["cache"], x[:, at:at + step])
            got.append(np.asarray(state["intermediates"]["chosen"][0])[0])
        got = np.concatenate(got)[:, :24]
        np.testing.assert_array_equal(got, want, err_msg=str(step))


def test_a_pass_longer_than_the_rings_slack_is_refused():
    model, params = init(model_config())
    ids = jnp.asarray(tokens(24)[None])
    most = RING - WINDOW + 1
    _, state = start(model, params, ids[:, :most], mutable=["cache"])
    with pytest.raises(ValueError, match="pass_tokens"):
        model.apply({"params": params, "cache": state["cache"]},
                    ids[:, :most + 1], decode=True, mutable=["cache"])
    cfg = model.config
    assert engine_mod.prefill_chunk_spans(cfg, 4) is None
    assert engine_mod.prefill_chunk_spans(cfg, 10) == [(0, 4), (4, 8),
                                                       (8, 10)]


def attention_of(kind, x, decode_from=None):
    """One ``KindLatentAttention`` layer over ``x [1, T, C]``: without a
    cache, or a cache made over the first ``decode_from`` rows and one
    token at a time from there (the decode kernel)."""
    layer = latent_attention.KindLatentAttention(model_config(), kind)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    if decode_from is None:
        return layer.apply({"params": params}, x)
    out, state = start(layer, params, x[:, :decode_from],
                       mutable=["cache"])
    more = jax.jit(lambda cache, x: layer.apply(
        {"params": params, "cache": cache}, x, decode=True,
        mutable=["cache"]))
    parts = [out]
    for t in range(decode_from, x.shape[1]):
        out, state = more(state["cache"], x[:, t:t + 1])
        parts.append(out)
    return jnp.concatenate(parts, axis=1)


@pytest.mark.parametrize("decode_from", [None, 3])
def test_the_window_is_exact_at_its_edge(decode_from):
    """A query at ``i`` sees ``i - j = window - 1`` and not ``i - j =
    window``: changing row ``j`` moves the outputs of rows ``j .. j + window
    - 1`` and leaves row ``j + window`` and every later one as it was, to
    the bit."""
    kind = model_config().attention_kind("window")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 20, 64), jnp.float32)
    j = 6
    moved = x.at[0, j].add(1.0)
    a, b = (np.asarray(attention_of(kind, t, decode_from))[0]
            for t in (x, moved))
    changed = np.abs(a - b).max(-1) > 0
    assert changed[j:j + WINDOW].all()
    assert not changed[:j].any() and not changed[j + WINDOW:].any()


@pytest.mark.parametrize("mixer, off", [
    ("attention", dict(rank_rescale=False)),
    ("window", dict(rank_rescale=False)),
    ("attention", dict(head_gate=False)),
    ("window", dict(head_gate=False)),
    ("attention", dict(indexer=dataclasses.replace(
        model_config().latent_kinds[0][1].indexer, rope_dim=None))),
    ("attention", dict(indexer=dataclasses.replace(
        model_config().latent_kinds[0][1].indexer, topk=64))),
])
def test_each_assumed_item_of_a_kind_switched_off_changes_its_output(
        mixer, off):
    """None of a kind's assumed conventions is dead code: the latents'
    rescale and the heads' gate by kind, the indexer's partial rotary, the
    selection itself; one layer of the kind, the same parameters (one the
    other declaration does not read stays in the tree)."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 30, 64), jnp.float32)
    cfg = model_config()
    layer = latent_attention.KindLatentAttention(
        cfg, cfg.attention_kind(mixer))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    other = dataclasses.replace(cfg, latent_kinds=kinds_with(mixer, **off))
    got = latent_attention.KindLatentAttention(
        other, other.attention_kind(mixer)).apply({"params": params}, x)
    want = layer.apply({"params": params}, x)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() > 1e-4


def test_the_routers_correction_bias_changes_the_logits():
    base = model_config(num_logits_to_keep=None)
    model, params = init(base)
    ids = jnp.asarray(tokens(30, seed=4)[None])
    want = np.asarray(jax.jit(model.apply)({"params": params}, ids))
    other = GPT(dataclasses.replace(base, moe_expert_bias=False))
    got = np.asarray(jax.jit(other.apply)({"params": params}, ids))
    assert np.abs(got - want).max() > 1e-4


# ---------------------------------------------------------------------------
# the expert layer: nothing new, and the shares add up
# ---------------------------------------------------------------------------
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """Four devices' shares of 16 experts (4 each): the routed parts of the
    four plus the shared expert once are the layer that holds all 16; the
    router's scores, its bias in the choice alone and the renormalised
    weights are ``moe/``'s own since PR 52 (nothing here is new)."""
    cfg = model_config()
    common = dict(
        d_model=64, d_hidden=32, num_experts=16, k=2, drop_tokens=False,
        gated_experts=True, norm_topk_prob=True, n_shared=1,
        routed_scale=1.0, scoring="sigmoid", expert_bias=True,
        expert_bias_init=0.1, renorm_eps=1e-20, dtype=jnp.float32,
        param_dtype=jnp.float32)
    assert (cfg.moe_scoring, cfg.moe_expert_bias, cfg.moe_norm_topk_prob,
            cfg.moe_n_shared, cfg.moe_experts_held, cfg.moe_renorm_eps) \
        == ("sigmoid", True, True, 1, (4, 4), 1e-20)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64), jnp.float32)
    whole = MoE(**common)
    params = whole.init(jax.random.PRNGKey(3), x)["params"]
    want, *_ = whole.apply({"params": params}, x)
    shared, *_ = MoE(**dict(common, experts_held=(0, 4))).apply(
        {"params": _held(params, 0, 4)}, x)
    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        part, *_ = MoE(**dict(common, experts_held=(first, 4))).apply(
            {"params": _held(params, first, 4)}, x)
        total = total + part
    # every share computed the shared expert: count it once
    only_shared = shared - _routed(common, params, x, 0)
    got = total - 3 * only_shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)


def _held(params, first, count):
    ex = {k: v[first:first + count] for k, v in params["experts"].items()}
    return dict(params, experts=ex)


def _routed(common, params, x, first):
    """One share's routed part alone (no shared expert)."""
    bare = dict(common, n_shared=0, experts_held=(first, 4))
    tree = {k: v for k, v in _held(params, first, 4).items()
            if k != "shared"}
    out, *_ = MoE(**bare).apply({"params": tree}, x)
    return out


# ---------------------------------------------------------------------------
# the scheduler: greedy tokens, lanes reused, what a run leaves, rewind
# ---------------------------------------------------------------------------
def reference_greedy(params, prompt, n):
    """(right-padded to the cache's length: one compiled shape; a causal
    model's real rows never read the padding)"""
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
    tokens so far; the longest context, 45 rows, passes the window nine
    times and ``index_topk`` five."""
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
    _, sched = fp32
    assert not sched.lane_cache.streams and not sched._streaming
    with pytest.raises(ValueError, match="exceeds the KV cache capacity"):
        sched.submit(tokens(30).tolist(), max_new_tokens=40)


class Stop(Exception):
    pass


def test_lanes_at_exit_hold_what_each_kind_keeps_of_every_token_taken_in(
        fp32):
    """What the benchmark's check reads: ``positions`` of a lane, a kind at
    a time, and ``last_step``. The full layers' rows are the request's
    prompt and tokens, exactly those ``valid`` marks; a ring holds the
    newest rows, each where ``slot_pos`` says; all are the reference's
    latents, rotary keys and index keys; the last step's rows are the
    reference's choice."""
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
        step = kept.last_step(lane)
        assert full["cached_latent"].shape == (2, 64, 32)
        assert full["cached_index_key"].shape == (2, 64, 16)
        assert ring["cached_latent"].shape == (3, RING, 48)
        assert ring["slot_pos"].shape == (3, RING) and "slot_pos" not in full
        assert step["chosen_rows"].shape == (2, TOPK)
        first = -(-n_prompt // BUCKET) * BUCKET - n_prompt
        n = n_prompt + len(comp.tokens)
        assert n > 2 * RING
        valid = np.asarray(full["valid"])[0]
        assert valid.sum() == n and valid[first:first + n].all()
        seq = tokens(n_prompt, seed=2).tolist() + list(comp.tokens)
        _, states, sets, _ = reference.hidden_and_states(
            eng.params, np.asarray(seq), SIZES, offset=first,
            with_chosen=True)
        for i, layer in enumerate((0, 1)):
            for name, ref in (("cached_latent", "latent"),
                              ("cached_rope_key", "rope_key"),
                              ("cached_index_key", "index_key")):
                np.testing.assert_allclose(
                    full[name][i, first:first + n], states[layer][ref],
                    atol=ATOL, rtol=0)
            rows = np.asarray(step["chosen_rows"])[i]
            assert sorted(rows - first) == np.nonzero(
                np.asarray(sets[layer])[n - 1])[0].tolist()
        for i, layer in enumerate((2, 3, 4)):
            at = np.asarray(ring["slot_pos"])[i]
            assert sorted(at) == list(range(first + n - RING, first + n))
            assert (at % RING == np.arange(RING)).all()
            for name, ref in (("cached_latent", "latent"),
                              ("cached_rope_key", "rope_key")):
                np.testing.assert_allclose(
                    np.asarray(ring[name])[i],
                    np.asarray(states[layer][ref])[at - first], atol=ATOL,
                    rtol=0)


def test_rewind_steps_all_three_leaf_families_back():
    """``LaneLayout.rewind`` over a ring that has wrapped beside a dense
    latent leaf and its index keys: three tokens are taken in on top of a
    snapshot and two of them rejected; the cache then continues as if it
    had taken in one, and its logits are the reference's."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    layout = LaneLayout(model, 1)
    ids = jnp.asarray(tokens(40, seed=7)[None])
    want = reference.logits(params, np.asarray(ids[0]), SIZES)
    more = jax.jit(lambda cache, ids: model.apply(
        {"params": params, "cache": cache}, ids, decode=True,
        mutable=["cache"]))
    _, state = start(model, params, ids[:, :4], mutable=["cache"])
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
    assert sorted(np.asarray(ring["slot_pos"])[0, 0]) == list(range(23, 31))
    assert int(cache["h"]["attention"]["attn"]["cache_index"][0, 0]) == 31
    got = []
    for at in range(31, 40):
        out, state = more(cache, ids[:, at:at + 1])
        cache = state["cache"]
        got.append(np.asarray(out)[0, 0])
    np.testing.assert_allclose(np.stack(got), want[31:40], atol=ATOL, rtol=0)


# ---------------------------------------------------------------------------
# scopes, and which path a decode step takes
# ---------------------------------------------------------------------------
def test_a_decode_steps_layers_of_both_kinds_go_through_the_latent_kernel(
        monkeypatch):
    """By a spy on the Pallas call (the interpreter runs it here): the
    window kind at ITS shape out of the kind's stacked ring, under a plan
    made of its mask; the full kind under the chosen mask, at its own
    shape."""
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda

    calls = []
    real = lda.latent_decode_attention

    def spy(q_lat, q_rope, latent, rope_key, plan, layer=None, **kw):
        calls.append((q_lat.shape, latent.shape, plan.block))
        return real(q_lat, q_rope, latent, rope_key, plan, layer, **kw)

    monkeypatch.setattr(lda, "latent_decode_attention", spy)
    eng, _ = served(seed=4)
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    sched.submit(tokens(5).tolist(), max_new_tokens=3)
    sched.run()
    stacked = {c for c in calls if len(c[1]) == 4 and c[1][1] == 2}
    assert stacked == {((2, 2, 48), (3, 2, RING, 48), RING),
                       ((2, 4, 32), (2, 2, 64, 32), 64)}
    table = sched.program_scopes()

    def scopes_of(program):
        return {c for path in table[program].values() if path
                for c in scopes.split_path(path)}

    for program in ("jit_decode_k", "jit_prefill", "jit_prefill_more"):
        assert {scopes.SCOPE_WINDOW_LATENT_ATTN,
                scopes.SCOPE_SPARSE_LATENT_ATTN, scopes.SCOPE_LATENT_INDEX,
                scopes.SCOPE_LATENT_SELECT, scopes.SCOPE_MLA_Q_PROJ,
                scopes.SCOPE_MOE_ROUTER, scopes.SCOPE_MOE_SHARED,
                scopes.SCOPE_KV_CACHE_WRITE} <= scopes_of(program), program
        assert not {scopes.SCOPE_ATTN_CORE, scopes.SCOPE_DSA_ATTN,
                    scopes.SCOPE_WINDOW_ATTN} & scopes_of(program)


# ---------------------------------------------------------------------------
# the cut at the published widths, without allocating
# ---------------------------------------------------------------------------
def test_the_cuts_geometry_and_parameter_count_at_the_published_widths():
    """``LaneLayout`` and the parameter tree over abstract values at the
    cell's real size: a lane keeps 1,024 rows of 1,024 + 64 in each window
    layer, 24,576 rows of 512 + 64 + 128 in each full one; 4,087 M
    parameters; the configuration file's ``bytes`` are these."""
    import json
    import os

    from perfbench import dots3_flops, stats

    body = stats.load_json(os.path.join(
        stats.repo_root(), "perfbench", "configs",
        "dots3-note-ep8-5layer.json"))
    cfg = dots3_serve.model_config(body)
    layout = LaneLayout(GPT(cfg), 48)
    shapes = jax.tree.map(lambda a: a.shape, layout.shapes)["h"]
    assert shapes["window"]["attn"]["cached_latent"] == (3, 48, 1024, 1024)
    assert shapes["window"]["attn"]["cached_rope_key"] == (3, 48, 1024, 64)
    assert shapes["attention"]["attn"]["cached_latent"] \
        == (2, 48, 24576, 512)
    assert shapes["attention"]["attn"]["cached_index_key"] \
        == (2, 48, 24576, 128)
    assert shapes["attention"]["attn"]["chosen_rows"] == (2, 48, 2048)
    assert cfg.pass_tokens == 512
    geo = layout.geometry()
    ring, dense, index = dots3_flops.lane_cache_bytes(
        3, 2, 1024, 24576, dots3_serve.head_sizes(body, "window"),
        dots3_serve.head_sizes(body, "attention"), 128)
    assert geo["window_bytes_per_lane"] == ring == 3 * 1024 * 2176
    assert geo["latent_bytes_per_lane"] == ring + dense
    assert geo["index_key_bytes_per_lane"] == index
    assert geo["bytes_per_lane"] == body["bytes"]["lane_bytes"]
    params = layout._abstract_params()
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert count == body["bytes"]["parameters"] == 4_087_154_176
    kinds = [dots3_serve.kind_sizes(body, dots3_serve.KINDS[k])
             for k in body["layer_types"]]
    by_hand = (
        dots3_flops.layer_params(5120, kinds[0], dense_width=13824)
        + sum(dots3_flops.layer_params(
            5120, k, expert_width=1536, held=32, n_shared=1, n_routed=256)
            for k in kinds[1:]) + 2 * 19008 * 5120 + 5120)
    assert by_hand == count
    assert json.dumps(body["bytes"]["parameters"]) == "4087154176"
