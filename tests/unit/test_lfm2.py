"""LFM2-MoE's stack (layers of two kinds, gated short convolutions and
grouped-query attention, each kind with its own stacked parameters and
cache leaves; a sigmoid router whose choice is corrected by a bias) on the
normal serving path, at a small size on the CPU with seeded weights,
against the plain reference the benchmark's cell uses
(``perfbench/reference/lfm2.py``). The tiny model keeps the shape of the
thing: two leading dense layers, the pattern ``c c A c c c A c``, 8 experts
of which 4 a token, a bias of a size that changes choices.

Tolerances. Program and reference are float32 with every matmul at
``highest`` (the fixture below), so they differ by the ORDER of float32
sums alone. Logits of these tiny models are ~0.8 in size and came out
1e-6 apart; 1e-5 leaves the sums an order of magnitude and is three orders
under what bfloat16 operands give (~2e-2 in the mean:
``test_bfloat16_is_held_to_its_own_tolerance``)."""
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import serving
from deepspeed_tpu.inference.lane_cache import LaneLayout, RecurrentStateError
from deepspeed_tpu.models import kind_stacks
from deepspeed_tpu.models.transformer_lm import (
    GPT,
    GPTConfig,
    MLAConfig,
    gpt_tp_rules,
    num_params,
)
from deepspeed_tpu.moe.layer import MOE_STATS
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.telemetry import scopes, telemetry_bus
from lfm2_tiny import TINY_LFM2
from perfbench.builders import lfm2_serve
from perfbench.reference import lfm2 as reference

SIZES = reference.sizes(TINY_LFM2)
VOCAB = TINY_LFM2["vocab_size"]
BUCKET = 16
ATOL = 1e-5
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def model_config(dtype="float32", **changes):
    section = dict(TINY_LFM2["serve"], param_dtype=dtype,
                   compute_dtype=dtype)
    return dataclasses.replace(
        lfm2_serve.model_config(TINY_LFM2, section), **changes)


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
def test_the_runs_of_the_cut_pattern_index_each_kinds_stacks():
    """``c c A c c c A c c c A c`` with two leading dense layers: seven
    runs; a layer's place in its parameter stack counts its block kind
    (dense convolutions apart), its place in the cache its mixer's."""
    cfg = model_config(n_layer=12, layer_types=tuple(
        "conv conv attention conv conv conv attention conv conv conv "
        "attention conv".split()))
    got = [(r.stack, r.first_layer, r.first_param, r.first_cache, r.length)
           for r in kind_stacks.layer_runs(cfg)]
    assert got == [("conv_dense", 0, 0, 0, 2), ("attention", 2, 0, 0, 1),
                   ("conv", 3, 0, 2, 3), ("attention", 6, 1, 1, 1),
                   ("conv", 7, 3, 5, 3), ("attention", 10, 2, 2, 1),
                   ("conv", 11, 6, 8, 1)]


def test_parameters_are_stacked_per_kind_of_block(fp32):
    eng, _ = fp32
    h = eng.params["h"]
    assert sorted(h) == ["attention", "conv", "conv_dense"]
    assert h["conv_dense"]["conv"]["in_proj"]["kernel"].shape == (2, 32, 96)
    assert h["conv_dense"]["mlp"]["c_fc"]["kernel"].shape == (2, 32, 48)
    assert h["conv"]["conv"]["conv_kernel"].shape == (4, 3, 32)
    assert h["conv"]["mlp"]["experts"]["wi"].shape == (4, 8, 32, 16)
    assert h["attention"]["attn"]["c_attn"]["kernel"].shape == (2, 32, 64)
    assert h["attention"]["mlp"]["expert_bias"].shape == (2, 8)
    assert h["attention"]["mlp"]["expert_bias"].dtype == jnp.float32
    assert "attn" not in h["conv"] and "conv" not in h["attention"]
    # layers of one stack are born from different keys
    w = np.asarray(h["conv"]["conv"]["in_proj"]["kernel"])
    assert not np.allclose(w[0], w[1])


@pytest.mark.parametrize("fault", [
    "length", "kind", "no_short_conv", "latent", "unrolled", "quantized"])
def test_a_declaration_that_cannot_be_run_is_refused(fault):
    changes = {
        "length": dict(layer_types=("conv", "attention")),
        "kind": dict(layer_types=("conv",) * 7 + ("window",)),
        "no_short_conv": dict(short_conv=None),
        "latent": dict(mla=MLAConfig(8, 8, 4, 4, 4)),
        "unrolled": dict(scan_layers=False),
        "quantized": dict(quantized_weights=True)}[fault]
    with pytest.raises(ValueError):
        model_config(**changes)


def test_a_configuration_that_declares_nothing_is_a_stack_of_one_kind():
    cfg = GPTConfig(n_layer=2, n_embd=32, n_head=4, vocab_size=128)
    assert cfg.layer_types is None
    assert [leaf.held_by for leaf in cfg.cache_leaves] == [None, None]
    assert all(cfg.layers_holding(leaf) == 2 for leaf in cfg.cache_leaves)


def test_num_params_and_tp_rules_follow_the_declaration():
    """Exact for a dense stack of two kinds without the per-head norms;
    the convolution's channels are split as columns and joined as rows."""
    cfg = model_config(moe_num_experts=0, first_k_dense=0, qk_norm=False,
                       n_kv_head=None)
    _, params = init(cfg)
    assert num_params(cfg) == sum(x.size for x in jax.tree.leaves(params))
    one_kind = dataclasses.replace(cfg, layer_types=None, short_conv=None)
    conv, attn = 4 * 32 * 32 + 3 * 32, 4 * 32 * 32
    assert num_params(cfg) - num_params(one_kind) == 6 * (conv - attn)
    spec = lambda path, shape: tuple(gpt_tp_rules(path, shape))  # noqa: E731
    assert spec("h/conv/conv/in_proj/kernel", (4, 32, 96)) == \
        (None, None, "tp")
    assert spec("h/conv/conv/conv_kernel", (4, 3, 32)) == (None, None, "tp")
    assert spec("h/conv/conv/out_proj/kernel", (4, 32, 32)) == \
        (None, "tp", None)


# ---------------------------------------------------------------------------
# the forward pass in each form, and the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("form", ["train", "prefill", "steps", "chunk"])
def test_each_form_gives_the_references_logits(form):
    """Without a cache (``train``), the pass that makes the per-kind cache
    (``prefill``), one token at a time on it (``steps``) and many query
    tokens on a cache that exists (``chunk``)."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    ids = jnp.asarray(np.stack([tokens(24, seed=s) for s in (0, 1)]))
    want = np.stack([reference.logits(params, np.asarray(row), SIZES)
                     for row in ids])
    # (each pass one compiled program, as test_dots3.py's: outside
    # ``jax.jit`` every operation of the model is compiled by itself)
    if form == "train":
        got = jax.jit(model.apply)({"params": params}, ids)
    else:
        first = {"prefill": 24, "steps": 9, "chunk": 16}[form]
        got, state = jax.jit(lambda ids: model.apply(
            {"params": params}, ids, decode=True, mutable=["cache"]))(
                ids[:, :first])
        parts, at = [got], first
        step = 8 if form == "chunk" else 1
        more = jax.jit(lambda cache, ids: model.apply(
            {"params": params, "cache": cache}, ids, decode=True,
            mutable=["cache"]))
        while at < 24:
            out, state = more(state["cache"], ids[:, at:at + step])
            parts.append(out)
            at += step
        got = jnp.concatenate(parts, axis=1)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("form", ["prefill", "steps", "chunk"])
def test_heads_narrower_than_a_lane_row_are_stored_side_by_side(form):
    """Heads of 64 (the published size), two KV heads: the cache keeps
    them side by side in one row of 128 (``kv_lane_pack``), the decode
    kernel reads that row with each query head in its own half of it, and
    the logits are the reference's in every form."""
    from deepspeed_tpu.models.transformer_lm import kv_lane_pack

    cfg = model_config(attn_head_dim=64, num_logits_to_keep=None)
    assert kv_lane_pack(cfg) == 2 and kv_lane_pack(model_config()) == 1
    assert kv_lane_pack(dataclasses.replace(cfg, n_kv_head=1)) == 1
    assert kv_lane_pack(dataclasses.replace(cfg, kv_cache_dtype="int8")) == 1
    model, params = init(cfg)
    ids = jnp.asarray(np.stack([tokens(24, seed=s) for s in (0, 1)]))
    sizes = dict(SIZES, head_dim=64)
    want = np.stack([reference.logits(params, np.asarray(row), sizes)
                     for row in ids])
    first = {"prefill": 24, "steps": 9, "chunk": 16}[form]
    got, state = model.apply({"params": params}, ids[:, :first],
                             decode=True, mutable=["cache"])
    kept = state["cache"]["h"]["attention"]["attn"]
    assert kept["cached_key"].shape == kept["cached_value"].shape \
        == (2, 2, 64, 1, 128)
    parts, at = [got], first
    step = 8 if form == "chunk" else 1
    more = jax.jit(lambda cache, ids: model.apply(
        {"params": params, "cache": cache}, ids, decode=True,
        mutable=["cache"]))
    while at < 24:
        out, state = more(state["cache"], ids[:, at:at + step])
        parts.append(out)
        at += step
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, axis=1)),
                               want, atol=ATOL, rtol=0)
    # the row holds KV head 0's 64 values, then KV head 1's
    _, k, v, _ = reference.hidden_and_states(params, np.asarray(ids[0]),
                                             sizes)
    np.testing.assert_allclose(
        np.asarray(state["cache"]["h"]["attention"]["attn"]["cached_key"])[
            :, 0, :24].reshape(2, 24, 2, 64), k, atol=ATOL, rtol=0)


def test_bfloat16_is_held_to_its_own_tolerance():
    """The model in bfloat16 (the cell's precision) against the float32
    reference of the same weights, logits ~0.8 in size: they came out
    0.019 apart in the mean (eight layers of bfloat16 products, norms and
    residual sums) and 0.15 at the worst entry (a token near a tie of its
    router takes another expert) on three seeds; held to 0.04 and 0.3, and
    three orders outside the float32 tolerance."""
    cfg = model_config("bfloat16", num_logits_to_keep=None)
    model, params = init(cfg)
    ids = tokens(24)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(ids[None])),
                     np.float32)[0]
    err = np.abs(got - reference.logits(params, ids, SIZES))
    assert 1000 * ATOL < err.mean() < 0.04 and err.max() < 0.3, \
        (err.mean(), err.max())


def test_the_gradients_are_the_references():
    """Training through the runs: the gradient of a fixed linear reading
    of the logits, by every parameter of every stack, is the gradient of
    the same reading of the plain reference's logits."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    ids = tokens(12, seed=5)
    reading = jnp.asarray(np.random.default_rng(9).normal(
        size=(12, VOCAB)), jnp.float32)

    def served_reading(p):
        return jnp.sum(model.apply({"params": p}, jnp.asarray(ids[None]))[0]
                       * reading)

    def reference_reading(p):
        hidden = reference.hidden(p, ids, SIZES)
        return jnp.sum(reference.mm(
            hidden, reference.head_of(p)["lm_head"]) * reading)

    got, want = jax.jit(jax.grad(served_reading))(params), \
        jax.grad(reference_reading)(params)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert np.asarray(b).any() or "expert_bias" in str(path), path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-4, err_msg=str(path))


def test_a_declared_stack_of_one_kind_is_the_undeclared_models():
    """``layer_types`` all attention through ``KindStackedBlocks`` against
    the same weights under ``ScannedBlocks``: the same logits."""
    plain = model_config(layer_types=None, short_conv=None, first_k_dense=0,
                         num_logits_to_keep=None)
    declared = dataclasses.replace(plain, layer_types=("attention",) * 8)
    model, params = init(plain)
    restacked = dict(params, h={"attention": params["h"]["block"]})
    ids = jnp.asarray(tokens(16)[None])
    want, kept = model.apply({"params": params}, ids, decode=True,
                             mutable=["cache"])
    got, mine = GPT(declared).apply({"params": restacked}, ids, decode=True,
                                    mutable=["cache"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL, rtol=0)
    np.testing.assert_allclose(
        np.asarray(mine["cache"]["h"]["attention"]["attn"]["cached_key"]),
        np.asarray(kept["cache"]["h"]["block"]["attn"]["cached_key"]),
        atol=ATOL, rtol=0)


# ---------------------------------------------------------------------------
# the router alone
# ---------------------------------------------------------------------------
def brute_force_route(scores, bias, k, scale, weigh_corrected=False):
    """The published rule as a loop: the k largest of ``s + b`` (ties to
    the lower index), weighed by ``s`` over the chosen ``s`` + 1e-6.
    ``weigh_corrected`` plants the fault: weights from ``s + b``."""
    out = np.zeros_like(scores, np.float64)
    for t, row in enumerate(np.asarray(scores, np.float64)):
        corrected = row + bias
        chosen = sorted(range(len(row)), key=lambda e: (-corrected[e], e))[:k]
        picked = (corrected if weigh_corrected else row)[chosen]
        out[t, chosen] = picked / (picked.sum() + 1e-6) * scale
    return out


def routing_logits(case):
    rng = np.random.default_rng(11)
    logits = rng.normal(size=(64, 8)).astype(np.float32)
    if case == "ties":
        logits = np.round(logits)
    elif case == "one_strong_expert":
        logits[:, 3] += 4.0
    return logits


def dense_weights(route, experts):
    out = np.zeros((route.weights.shape[0], experts), np.float64)
    np.put_along_axis(out, np.asarray(route.experts),
                      np.asarray(route.weights, np.float64), axis=1)
    return out


@pytest.mark.parametrize("case", ["random", "ties", "one_strong_expert"])
@pytest.mark.parametrize("bias_std", [0.0, 0.3, 3.0])
def test_corrected_sigmoid_routing_is_the_brute_force_loop(case, bias_std):
    logits = routing_logits(case)
    bias = np.random.default_rng(12).normal(size=8).astype(np.float32) \
        * bias_std
    scores = np.asarray(jax.nn.sigmoid(logits))
    route = topk_routing(jnp.asarray(logits), 4, True, scale=1.5,
                         scoring="sigmoid", bias=jnp.asarray(bias))
    want = brute_force_route(scores, bias, 4, 1.5)
    np.testing.assert_allclose(dense_weights(route, 8), want, atol=1e-6)
    assert np.asarray(route.exp_counts).sum() == 64 * 4
    # changed: a chosen expert scores under the fourth largest score (a
    # set that differs from the uncorrected choice by a tie alone is the
    # four largest scores all the same)
    fourth = np.sort(scores, axis=1)[:, -4]
    changed = int((np.where(want > 0, scores, 2.0).min(1) < fourth).sum())
    assert int(route.bias_changed) == changed
    assert (changed > 0) == (bias_std > 0)
    if case != "ties":
        uncorrected = brute_force_route(scores, 0 * bias, 4, 1.5)
        assert changed == ((want > 0) != (uncorrected > 0)).any(1).sum()


@pytest.mark.parametrize("case", ["random", "ties", "one_strong_expert"])
def test_the_reference_routes_as_the_brute_force_loop(case):
    """With a bias large enough to change most choices; and the planted
    fault, weights taken from the corrected scores, is far outside."""
    logits = routing_logits(case)
    bias = np.random.default_rng(12).normal(size=8).astype(np.float32)
    x = jnp.asarray(logits)
    p = {"gate": {"kernel": jnp.eye(8)}, "expert_bias": jnp.asarray(bias)}
    s = dict(SIZES, routed_scale=1.5)
    got = np.asarray(reference.route(x, p, s))
    scores = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_allclose(got, brute_force_route(scores, bias, 4, 1.5),
                               atol=1e-6)
    unbiased = np.asarray(reference.route(x, p, s, biased=False))
    assert ((got > 0) != (unbiased > 0)).any(1).mean() > 0.3
    fault = brute_force_route(scores, bias, 4, 1.5, weigh_corrected=True)
    assert np.abs(got - fault).max() > 0.05
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, fault, atol=1e-3)


def test_without_a_bias_or_sigmoid_routing_is_what_it_was():
    """The softmax path takes none of the new code: the same outputs with
    the new arguments at their defaults, and a bias or groups with
    sigmoid scores are told apart."""
    logits = jnp.asarray(routing_logits("random"))
    a = topk_routing(logits, 4, True)
    b = topk_routing(logits, 4, True, scoring="softmax", bias=None)
    assert a.bias_changed is None
    for x, y in zip(a[:5], b[:5]):
        assert (np.asarray(x) == np.asarray(y)).all()
    with pytest.raises(ValueError):
        topk_routing(logits, 2, n_group=2, topk_group=1, scoring="sigmoid")


def test_expert_load_says_the_share_of_choices_the_bias_changed(fp32):
    """``moe.load``'s ``bias_changed_share`` is the reference's count: the
    (token, layer) choices whose set differs without the bias."""
    from deepspeed_tpu.moe.utils import publish_expert_load

    eng, _ = fp32
    ids = np.stack([tokens(16, seed=s) for s in (7, 8)]).astype(np.int32)
    load = publish_expert_load(eng.module, eng.params, {"input_ids": ids})
    assert load["held"] == 8 and load["tokens_dropped"] == 0
    assert np.asarray(load["tokens_per_expert"]).shape == (6, 8)
    assert 0.05 < load["bias_changed_share"] < 0.95
    changed = total = 0
    for row in ids:
        h = eng.params["wte"]["embedding"][row].astype(jnp.float32)
        for kind, stacked, i in reference.layers_of(eng.params, SIZES):
            p = jax.tree.map(lambda a: a[i], stacked)
            mixed = reference.block(h, p, SIZES, kind, 0, 16)[0]
            if "experts" in p["mlp"]:
                u_mix = reference.rms_norm(h, p["ln_1"]["scale"],
                                           SIZES["eps"])
                if kind == reference.CONV:
                    a = reference.short_conv(u_mix, p["conv"], SIZES, 16)[0]
                else:
                    a = reference.attention(u_mix, p["attn"], SIZES)[0]
                u = reference.rms_norm(h + a, p["ln_2"]["scale"],
                                       SIZES["eps"])
                with_bias = np.asarray(reference.route(u, p["mlp"], SIZES))
                without = np.asarray(reference.route(u, p["mlp"], SIZES,
                                                     biased=False))
                changed += ((with_bias > 0) != (without > 0)).any(1).sum()
                total += 16
            h = mixed
    assert load["bias_changed_share"] == pytest.approx(changed / total)


# ---------------------------------------------------------------------------
# through the scheduler's lane cache
# ---------------------------------------------------------------------------
def reference_greedy(params, prompt, n):
    """(Padded on the right to one length: a causal model's rows never
    read the padding, and every length then shares one compile.)"""
    seq = list(prompt)
    for _ in range(n):
        ids = np.zeros((64,), np.int32)
        ids[:len(seq)] = seq
        row = reference.logits(params, ids, SIZES,
                               positions=[len(seq) - 1])[0]
        seq.append(int(row.argmax()))
    return seq[len(prompt):]


def test_the_scheduler_serves_the_references_greedy_tokens_with_lanes_reused(
        fp32):
    """Five ragged prompts (left-padded into buckets of 16, two of them
    two buckets long) over three lanes: every admission after the third
    is spliced into a lane of the per-kind stacks beside live lanes, and
    every token is the plain reference's argmax over prompt + tokens so
    far."""
    eng, _ = fp32
    sched = serving.build_serving(eng, {"slots": 3, "prompt_bucket": BUCKET})
    prompts = [tokens(n, seed=1).tolist() for n in (5, 16, 21, 3, 30)]
    got = {}
    rids = [sched.submit(p, max_new_tokens=5 + i,
                         stream_callback=lambda r, t, d: got.setdefault(
                             r, []).append(int(t)))
            for i, p in enumerate(prompts)]
    stats = sched.run()
    assert stats.decode_steps > 0
    for i, (rid, prompt) in enumerate(zip(rids, prompts)):
        assert got[rid] == reference_greedy(eng.params, prompt, 5 + i), i


def test_the_cache_holds_each_leaf_over_its_kinds_layers_alone(fp32):
    """Keys and values over the two attention layers, tails over the six
    convolution layers; the plan event and ``kv_cache_stats`` say so."""
    eng, _ = fp32
    events = []
    telemetry_bus.subscribe(events.append)
    try:
        sched = serving.build_serving(eng, {"slots": 3,
                                            "prompt_bucket": BUCKET})
        sched._ensure_compiled()
    finally:
        telemetry_bus.unsubscribe(events.append)
    shapes = {"/".join(str(k.key) for k in path): leaf.shape
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  sched.lane_cache.shapes)[0]}
    assert shapes == {
        "h/attention/attn/cache_index": (2, 3),
        "h/attention/attn/cached_key": (2, 3, 64, 2, 8),
        "h/attention/attn/cached_value": (2, 3, 64, 2, 8),
        "h/attention/attn/valid": (2, 3, 64),
        "h/conv/conv/conv_tail": (6, 3, 2, 32)}
    plan = sched.kv_cache_stats()
    layers = {"cached_key": 2, "cached_value": 2, "conv_tail": 6}
    assert plan["leaf_layers"] == layers
    cfg = eng.module.config
    assert {leaf.name: cfg.layers_holding(leaf)
            for leaf in cfg.cache_leaves} == layers
    assert plan["conv_bytes_per_lane"] == 6 * 2 * 32 * 4
    assert plan["kv_bytes_per_lane"] == 2 * (2 * 64 * 2 * 8 * 4 + 64 + 4)
    assert plan["state_bytes_per_lane"] == 0
    (event,) = [e for e in events if e.get("kind") == "serve.cache_plan"]
    assert event["leaf_layers"] == layers
    assert event["conv_bytes_per_lane"] == plan["conv_bytes_per_lane"]


def test_the_cuts_geometry_at_the_published_widths():
    """The cell's own configuration file, shapes alone: keys and values
    over 3 layers at 6,144 bytes a position, tails over 9."""
    with open(os.path.join(
            REPO, "perfbench/configs/lfm2-8b-a1b-12layer.json")) as f:
        config = json.load(f)
    geo = LaneLayout(GPT(lfm2_serve.model_config(config)), 2).geometry()
    assert geo["leaf_layers"] == {"cached_key": 3, "cached_value": 3,
                                  "conv_tail": 9}
    positions = config["serve"]["cache_positions"]
    # 3 layers x (keys and values, a valid byte a position, a clock)
    assert geo["kv_bytes_per_lane"] == positions * 6144 + 3 * (positions + 4)
    assert geo["conv_bytes_per_lane"] == 9 * 2 * 2048 * 2 == 73728
    assert geo["state_bytes_per_lane"] == 0


def test_lanes_at_exit_hold_what_each_kind_keeps_of_every_token_taken_in(
        fp32):
    """What the benchmark's check reads: ``positions`` and
    ``recurrent_state`` of a lane out of the per-kind stacks: its rows are
    its request's prompt and tokens, exactly those ``valid`` marks, their
    keys and values the reference's (rotary counting cache rows), and the
    tails those after the last token taken in."""
    eng, _ = fp32
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    sched.retain_lanes = True
    prompts = {sched.submit(tokens(n, seed=2).tolist(), max_new_tokens=30): n
               for n in (5, 21)}

    class Stop(Exception):
        pass

    def poll(state={"n": 0}):
        state["n"] += 1
        if state["n"] > 9:
            raise Stop

    with pytest.raises(Stop):
        sched.run(poll_fn=poll)
    kept = sched.lanes_at_exit
    assert sorted(kept.live) == [0, 1]
    for lane, comp in kept.live.items():
        n_prompt = prompts[comp.request_id]
        got = dict(kept.positions(lane), **kept.recurrent_state(lane))
        assert got["cached_key"].shape == got["cached_value"].shape \
            == (2, 64, 2, 8)
        assert got["conv_tail"].shape == (6, 2, 32)
        assert kept.last_step(lane) == {}
        first = -(-n_prompt // BUCKET) * BUCKET - n_prompt
        n = n_prompt + len(comp.tokens)
        valid = np.asarray(got["valid"])
        assert valid.shape == (2, 64) and (valid[0] == valid[1]).all()
        assert valid[0].sum() == n and valid[0, first:first + n].all()
        seq = tokens(n_prompt, seed=2).tolist() + list(comp.tokens)
        _, k, v, tails = reference.hidden_and_states(
            eng.params, np.asarray(seq), SIZES, offset=first)
        np.testing.assert_allclose(got["cached_key"][:, first:first + n], k,
                                   atol=ATOL, rtol=0)
        np.testing.assert_allclose(got["cached_value"][:, first:first + n],
                                   v, atol=ATOL, rtol=0)
        np.testing.assert_allclose(got["conv_tail"], tails, atol=ATOL,
                                   rtol=0)


@pytest.mark.parametrize("n", [1, 5, 16, 21, 30])
def test_left_padded_bucket_equals_the_unpadded_prompt(fp32, n):
    """A pad's input is zeroed before the convolution's projection and its
    key is masked: the bucket's last-position logits and the tails it
    leaves are the unpadded prompt's."""
    eng, sched = fp32
    prompt = tokens(n, seed=4)
    Lp = -(-n // BUCKET) * BUCKET
    ids = np.zeros((1, Lp), np.int32)
    mask = np.zeros((1, Lp), bool)
    ids[0, Lp - n:], mask[0, Lp - n:] = prompt, True
    got, cache = eng._prefill_fn(eng.params, jnp.asarray(ids),
                                 jnp.asarray(mask))
    want = reference.logits(eng.params, prompt, SIZES, positions=[n - 1])[0]
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=ATOL, rtol=0)
    tails = reference.hidden_and_states(eng.params, prompt, SIZES)[3]
    np.testing.assert_allclose(
        np.asarray(cache["h"]["conv"]["conv"]["conv_tail"])[:, 0], tails,
        atol=ATOL, rtol=0)
    assert np.asarray(cache["h"]["attention"]["attn"]["valid"])[
        0, 0, :Lp].tolist() == mask[0].tolist()


@pytest.mark.parametrize("feature", ["draft_engine", "prefix_cache"])
def test_the_tail_refuses_what_cuts_a_cache_at_a_prefix(fp32, feature):
    """By the rule that is there: the tail is declared recurrent."""
    from deepspeed_tpu.serving.prefix_cache import PrefixCache

    eng, _ = fp32
    asked = {"draft_engine": dict(draft_engine=eng, spec_k=2),
             "prefix_cache": dict(prefix_cache=PrefixCache())}[feature]
    with pytest.raises(RecurrentStateError, match="conv_tail") as err:
        serving.ContinuousBatchingScheduler(
            eng, slots=2, prompt_bucket=BUCKET, **asked)
    assert err.value.feature.startswith(feature)


# ---------------------------------------------------------------------------
# scopes, the carry tag, and where the experts' matrices are read
# ---------------------------------------------------------------------------
def test_the_serving_programs_carry_the_new_scopes(fp32):
    eng, _ = fp32
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    sched.submit(tokens(5).tolist(), max_new_tokens=3)
    sched.run()
    table = sched.program_scopes()

    def scopes_of(program):
        return {c for path in table[program].values() if path
                for c in scopes.split_path(path)}

    for program in ("jit_decode_k", "jit_prefill"):
        assert {scopes.SCOPE_CONV_IN_PROJ, scopes.SCOPE_CONV_GATE_CONV,
                scopes.SCOPE_CONV_OUT_PROJ, scopes.SCOPE_MOE_ROUTER,
                scopes.SCOPE_MOE_EXPERTS, scopes.SCOPE_KV_CACHE_WRITE,
                scopes.SCOPE_ATTN_CORE} <= scopes_of(program), program
    declared = {leaf.name: leaf.carry_tag
                for leaf in eng.module.config.cache_leaves}
    assert declared == {"cached_key": "kv_cache_carry",
                        "cached_value": "kv_cache_carry",
                        "conv_tail": "conv_state_carry"}


def test_a_whole_tail_leaf_that_no_scope_owns_gets_the_carry_tag():
    text = """HloModule jit_decode_k

ENTRY %main (p: f32[6,2,2,32]) -> f32[6,2,2,32] {
  %p = f32[6,2,2,32]{3,2,1,0} parameter(0)
  ROOT %copy.1 = f32[6,2,2,32]{3,2,1,0} copy(%p), metadata={op_name="jit(decode_k)/while/body/copy"}
}
"""
    _, table = scopes.instruction_scopes(
        text, {scopes.SCOPE_CONV_STATE_CARRY: {(6, 2, 2, 32)}})
    assert scopes.has_scope(table["copy.1"], "conv_state_carry")


def aligned(**changes):
    """The tiny model at widths of whole lanes (hidden and experts 128
    wide): the grouped-matmul kernel takes the experts' products."""
    return dict(n_embd=128, moe_intermediate_size=128, **changes)


def gmm_routes(monkeypatch):
    """What the traces to come hand ``gmm``: True for a stack and a layer,
    False for one layer's matrices."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm

    routes, real = [], gm.gmm

    def spy(*args, **kwargs):
        routes.append(kwargs.get("layer") is not None)
        return real(*args, **kwargs)

    monkeypatch.setattr(gm, "gmm", spy)
    return routes


def test_the_experts_matrices_are_read_in_place_in_each_kinds_stack(
        monkeypatch):
    """At widths of whole lanes a serving call's grouped matmuls take the
    kind's stacked leaves with the layer's index (never a slice), the plan
    and the counter say so, and every leaf the programs leave is bitwise
    what the slices give."""
    from flax.traverse_util import flatten_dict

    from deepspeed_tpu.moe import experts

    routes = gmm_routes(monkeypatch)
    events = []
    telemetry_bus.subscribe(events.append)
    try:
        eng, sched = served(slots=8, **aligned())
    finally:
        telemetry_bus.unsubscribe(events.append)
    (plan,) = [e for e in events if e.get("kind") == "serve.cache_plan"]
    assert plan["expert_matrices"] == "in_place"

    def step():
        """One token a lane on a cache that exists (making one traces a
        block for its leaves' shapes alone, through no stack)."""
        jax.clear_caches()
        _, made = eng.module.apply(
            {"params": eng.params}, jnp.ones((8, 1), jnp.int32), decode=True,
            mutable=["cache"])
        del routes[:]
        out, left = eng.module.apply(
            {"params": eng.params, "cache": made["cache"]},
            jnp.ones((8, 1), jnp.int32), decode=True,
            mutable=["cache", MOE_STATS])
        counted = {path[-1]: np.asarray(value) for path, (value,)
                   in flatten_dict(left[MOE_STATS]).items()}
        return jax.tree.map(np.asarray, (out, left["cache"])), counted

    got, counted = step()
    assert routes and all(routes)
    assert counted["in_place"].tolist() == [1] * 6
    assert counted["bias_changed"].shape == (6,)
    assert counted["computed"].shape == (6, 8)
    monkeypatch.setattr(experts, "expert_matrices",
                        lambda cfg, rows: "slice")
    want, counted = step()
    jax.clear_caches()
    assert routes and not any(routes)
    assert counted["in_place"].tolist() == [0] * 6
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and (a == b).all()


def test_training_through_the_runs_reads_the_stacks_in_place_too(
        monkeypatch):
    """A training step through the runs at widths of whole lanes, each
    turn under ``jax.checkpoint``: every
    grouped matmul reads its kind's stack with the layer's index and is
    differentiated with respect to the turn's own leaves; the loss and
    every parameter's gradient are bitwise what the rule answering
    "slice" gives."""
    from deepspeed_tpu.moe import experts

    routes = gmm_routes(monkeypatch)
    model, params = init(model_config(num_logits_to_keep=None, remat=True,
                                      **aligned()))
    ids = jnp.asarray(np.stack([tokens(16, seed=s) for s in range(4)]))

    def step():
        jax.clear_caches()
        del routes[:]
        return jax.jit(jax.value_and_grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)))(params)

    got = step()
    assert routes and all(routes)
    monkeypatch.setattr(experts, "expert_matrices",
                        lambda cfg, rows: "slice")
    want = step()
    jax.clear_caches()
    assert routes and not any(routes)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and (np.asarray(a) == np.asarray(b)).all()
    assert all(np.asarray(leaf).any() for stack in got[1]["h"].values()
               if "experts" in stack.get("mlp", {})
               for leaf in stack["mlp"]["experts"].values())


# ---------------------------------------------------------------------------
# a configuration of one kind lowers to the programs it lowered to before
# ---------------------------------------------------------------------------
# sha256 of ``jax.jit(f).lower(...).as_text()`` (no source locations, dead
# code dropped: equal exactly when the traced program is) of three tiny
# one-kind models' prefill, decode and train programs, recorded from a run
# of ``one_kind_programs`` on the parent commit (5d84d17); the latent model's
# three again on PR 55's tree, which holds the second query projection's 2-D
# product as a value before its per-head view (models/latent_attention.py)
PARENT_PROGRAMS = {
    "gpt": {
        "prefill": "541dbeb4424d1cdb6e1e906951eda15b9cd8236018240663ec697c"
                   "3fb1d725bc",
        "decode": "66ebe239a8fe3b1f780d1fa74446423c0d9554db95feafbcb095097"
                  "90cfc0bb5",
        "train": "88dba8e9720329a23f75065e7bcf648752c76faa6b1da42972571d29"
                 "2cc01dce"},
    "falcon_h1": {
        "prefill": "4e09e7c5d8a5e29769fb6d62ef034c06b3899f323aa761813475c0"
                   "97c9433655",
        "decode": "eb884f53120e1b01777976dd86fc3145d674e6c02a9b907ff5f69a0"
                  "0eeb9db09",
        "train": "4f351d52274160cacd8ebdc3b8da729622aa95fc5878414a21ad64cd"
                 "65c483f2"},
    "deepseek_v2": {
        "prefill": "bdabb1bae68f8364be67c622b98a57013ee61aeaf9d90a8157841b"
                   "374705acb1",
        "decode": "f8b98d8994dd0601a29cd61d24485b492ffea04bf31d1b2bdd2d4c9"
                  "59179c0fc",
        "train": "fbff2ee1f8aac5b604a3467b7cc329cf05a17e67c189fa6a675d784f"
                 "49b5586d"}}


def one_kind_config(name):
    from simple_model import tiny_gpt_config

    if name == "gpt":
        return tiny_gpt_config()
    if name == "falcon_h1":
        from falcon_h1_tiny import TINY_FALCON_H1
        from perfbench.builders import falcon_h1_serve

        cfg = falcon_h1_serve.model_config(TINY_FALCON_H1)
    else:
        from deepseek_v2_tiny import TINY_DEEPSEEK
        from perfbench.builders import deepseek_v2_serve

        cfg = deepseek_v2_serve.model_config(TINY_DEEPSEEK)
    return dataclasses.replace(cfg, num_logits_to_keep=None)


def one_kind_programs(cfg):
    """``{program: lowered}`` of a model's prefill of four 16-token
    buckets, its decode step of four lanes and its training step's loss
    and gradients, from shapes alone."""
    model = GPT(cfg)
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"])

    def prefill(p, ids, mask):
        return model.apply({"params": p}, ids, attention_mask=mask,
                           decode=True, mutable=["cache"])

    def decode(p, cache, tok):
        return model.apply({"params": p, "cache": cache}, tok, decode=True,
                           mutable=["cache"])

    def train(p, ids):
        return jax.value_and_grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids))(p)

    bucket = (sd((4, 16), jnp.int32), sd((4, 16), jnp.bool_))
    cache = jax.eval_shape(prefill, params, *bucket)[1]["cache"]
    return {"prefill": jax.jit(prefill).lower(params, *bucket),
            "decode": jax.jit(decode).lower(params, cache,
                                            sd((4, 1), jnp.int32)),
            "train": jax.jit(train).lower(params, sd((2, 16), jnp.int32))}


@pytest.fixture(scope="module")
def lowered_one_kind():
    memo = {}

    def get(name):
        if name not in memo:
            with jax.default_matmul_precision(None):
                memo[name] = one_kind_programs(one_kind_config(name))
        return memo[name]

    return get


@pytest.mark.parametrize("program", ["prefill", "decode", "train"])
@pytest.mark.parametrize("model", ["gpt", "falcon_h1", "deepseek_v2"])
def test_a_model_of_one_kind_lowers_to_the_parents_program(
        lowered_one_kind, model, program):
    text = lowered_one_kind(model)[program].as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_PROGRAMS[model][program]
