"""DeepSeek-V2's block (latent attention with its two forms, YaRN, a
leading dense layer, group-limited routing over experts of which the layer
holds a share, shared experts) on the normal serving path, at a small size
on the CPU with seeded weights, against the plain reference the
benchmark's cell uses (``perfbench/reference/deepseek_v2.py``).

Tolerances. Program and reference are float32 with every matmul at
``highest`` (the fixture below), so they differ by the ORDER of float32
sums alone: the absorbed form re-associates ``q (W_UK c)`` into ``(q W_UK)
c``, the router and softmax are the same operations. Logits of these tiny
models are ~0.5 in size and came out 1e-7..3e-7 apart; 5e-6 leaves the sums
an order of magnitude and is three orders under what bfloat16 operands
give (~4e-3: ``test_bfloat16_would_not_pass_the_float32_tolerance``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import flax.linen as nn
from deepseek_v2_tiny import TINY_DEEPSEEK
from deepspeed_tpu import serving
from deepspeed_tpu.inference.engine import carried_leaf_shapes
from deepspeed_tpu.models import latent_attention as la
from deepspeed_tpu.models import transformer_lm
from deepspeed_tpu.models.transformer_lm import (
    GPT,
    GPTConfig,
    LatentCacheError,
    MLAConfig,
)
from deepspeed_tpu.moe.layer import MOE_STATS, MoE
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.ops import rotary
from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
from deepspeed_tpu.telemetry import scopes, telemetry_bus
from perfbench.builders import deepseek_v2_serve
from perfbench.reference import deepseek_v2 as reference

SIZES = reference.sizes(TINY_DEEPSEEK)
VOCAB = TINY_DEEPSEEK["vocab_size"]
BUCKET = 16
ATOL = 5e-6


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def model_config(dtype="float32", **changes):
    section = dict(TINY_DEEPSEEK["serve"], param_dtype=dtype,
                   compute_dtype=dtype)
    return dataclasses.replace(
        deepseek_v2_serve.model_config(TINY_DEEPSEEK, section), **changes)


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


def as_scanned(params, cfg):
    """An unrolled model's parameters in the tree the reference reads."""
    k = cfg.first_k_dense
    blocks = [params[f"h_{i}"] for i in range(cfg.n_layer)]
    h = {f"dense_{i}": blocks[i] for i in range(k)}
    h["block"] = jax.tree.map(lambda *a: jnp.stack(a), *blocks[k:])
    return dict({n: v for n, v in params.items() if not n.startswith("h_")},
                h=h)


# ---------------------------------------------------------------------------
# the two forms of the layer and the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
@pytest.mark.parametrize("form", ["train", "prefill", "steps", "chunk"])
def test_each_form_gives_the_references_logits(scan, form):
    """The per-head form without a cache (``train``) and on the pass that
    makes the cache (``prefill``), the absorbed form one token at a time
    (``steps``) and with many query tokens on a cache that exists
    (``chunk``), under ``ScannedBlocks`` and unrolled."""
    cfg = model_config(scan_layers=scan, num_logits_to_keep=None)
    model, params = init(cfg)
    ids = jnp.asarray(tokens(24)[None])
    want = reference.logits(params if scan else as_scanned(params, cfg),
                            np.asarray(ids[0]), SIZES)

    # (each pass one compiled program, as test_dots3.py's: outside
    # ``jax.jit`` every operation of the model is compiled by itself)
    @jax.jit
    def apply(variables, ids):
        return model.apply(variables, ids, decode=True, mutable=["cache"])

    def decode(ids, cache=None):
        variables = {"params": params}
        if cache is not None:
            variables["cache"] = cache
        out, new = apply(variables, ids)
        return np.asarray(out[0]), new["cache"]

    if form == "train":
        got = np.asarray(jax.jit(model.apply)({"params": params}, ids)[0])
    elif form == "prefill":
        got, _ = decode(ids)
    elif form == "steps":
        first, cache = decode(ids[:, :16])
        rest = []
        for t in range(16, 24):
            row, cache = decode(ids[:, t:t + 1], cache)
            rest.append(row)
        got = np.concatenate([first] + rest)
    else:
        first, cache = decode(ids[:, :8])
        got = np.concatenate([first, decode(ids[:, 8:], cache)[0]])
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_bfloat16_would_not_pass_the_float32_tolerance():
    """What ``ATOL`` is for: the same weights computed in bfloat16 are three
    orders of magnitude outside it."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    ids = jnp.asarray(tokens(24)[None])
    want = reference.logits(params, np.asarray(ids[0]), SIZES)
    low = GPT(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(low.apply({"params": params}, ids)[0], np.float32)
    assert np.abs(got - want).max() > 100 * ATOL


def test_the_cache_is_one_latent_and_one_rotary_key_a_position(fp32):
    """Stacked over ALL layers, the leading dense one among them, with one
    ``valid`` and one clock a lane, and it holds the reference's ``c_kv``
    and ``k_rope``."""
    eng, _ = fp32
    cfg = eng.module.config
    ids = jnp.asarray(tokens(16)[None])
    _, new = eng.module.apply({"params": eng.params}, ids, decode=True,
                              mutable=["cache"])
    cache = new["cache"]["h"]
    assert {k: v.shape for k, v in cache.items()} == {
        "cached_latent": (3, 1, 64, 16), "cached_rope_key": (3, 1, 64, 4),
        "valid": (1, 64), "cache_index": (1,)}
    assert cfg.position_leaves == (("cached_latent", 3),
                                   ("cached_rope_key", 3))
    assert GPTConfig().position_leaves == (
        ("cached_key", 4), ("cached_value", 4)) \
        == GPTConfig(kv_cache_dtype="int8").position_leaves
    assert cfg.position_leaves and not cfg.recurrent_leaves
    _, latent, rope_key = reference.hidden_and_states(
        eng.params, np.asarray(ids[0]), SIZES)
    np.testing.assert_allclose(cache["cached_latent"][:, 0, :16], latent,
                               atol=ATOL, rtol=0)
    np.testing.assert_allclose(cache["cached_rope_key"][:, 0, :16],
                               rope_key, atol=ATOL, rtol=0)
    assert not np.asarray(cache["cached_latent"][:, 0, 16:]).any()
    assert np.asarray(cache["valid"])[0].tolist() == [True] * 16 + [False] * 48
    # 576 values a position and layer where 4 heads of keys and values
    # would be 4 x (12 + 8)
    assert carried_leaf_shapes(cache, cfg.cache_leaves) == {
        "kv_cache_carry": {(3, 1, 64, 16), (1, 64, 16),
                           (3, 1, 64, 4), (1, 64, 4)}}


def test_the_leading_block_is_dense_and_the_rest_hold_their_share(fp32):
    eng, _ = fp32
    h = eng.params["h"]
    assert set(h) == {"dense_0", "block"}
    assert set(h["dense_0"]["mlp"]) == {"c_fc", "c_gate", "c_proj"}
    assert h["dense_0"]["mlp"]["c_fc"]["kernel"].shape == (32, 48)
    mlp = h["block"]["mlp"]
    assert set(mlp) == {"gate", "experts", "shared"}
    assert mlp["gate"]["kernel"].shape == (2, 32, 16)      # all 16 scored
    assert mlp["gate"]["kernel"].dtype == jnp.float32
    assert mlp["experts"]["wi"].shape == (2, 2, 32, 16)    # 2 held
    assert mlp["shared"]["c_fc"]["kernel"].shape == (2, 32, 32)
    assert set(h["block"]["attn"]) == set(h["dense_0"]["attn"]) == {
        "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "c_proj"}


# ---------------------------------------------------------------------------
# YaRN and the softmax scale, against hand-computed values
# ---------------------------------------------------------------------------
def test_yarn_frequencies_and_the_scale_at_the_published_numbers():
    m = MLAConfig(q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
                  v_dim=128, yarn_factor=40.0, yarn_original_positions=4096,
                  yarn_mscale=0.707, yarn_mscale_all_dim=0.707)
    # m = 0.1 * 0.707 * ln 40 + 1
    assert rotary.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=5e-5)
    assert m.softmax_scale == pytest.approx(1.2608 ** 2 * 192 ** -0.5,
                                            rel=1e-4)
    assert m.rope_mscale == 1.0
    f = m.inv_freq(10000.0)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # the dimensions whose wavelengths make 32 and 1 turns in 4,096
    # positions: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 -> 10, and
    # 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-12)
    ramp = (16 - 10) / (23 - 10)
    assert f[16] == pytest.approx(
        plain[16] / 40 * ramp + plain[16] * (1 - ramp), rel=1e-12)
    np.testing.assert_allclose(f, reference.yarn_inv_freq(64, 10000.0, dict(
        factor=40, original_max_position_embeddings=4096, beta_fast=32,
        beta_slow=1)), rtol=0)
    assert MLAConfig(1, 1, 2, 2, 2).inv_freq(10000.0) is None
    assert MLAConfig(1, 1, 2, 2, 2).softmax_scale == 0.5


def test_rotary_with_given_frequencies_rotates_by_position_times_each():
    x = jnp.ones((1, 3, 1, 4))
    out = rotary.apply_rotary_pos_emb(x, jnp.asarray([[0, 1, 5]]),
                                      inv_freq=[0.5, 0.25])
    for row, pos in zip(np.asarray(out[0, :, 0]), (0, 1, 5)):
        a, b = 0.5 * pos, 0.25 * pos
        np.testing.assert_allclose(
            row, [np.cos(a) - np.sin(a), np.cos(b) - np.sin(b),
                  np.cos(a) + np.sin(a), np.cos(b) + np.sin(b)], atol=1e-6)


# ---------------------------------------------------------------------------
# group-limited routing against a brute-force loop
# ---------------------------------------------------------------------------
def brute_force_route(probs, k, n_group, topk_group, scale):
    """``[(expert, weight)]`` per token by the published rule, with Python
    loops; ties to the lower index."""
    out = []
    per = probs.shape[1] // n_group
    for p in probs:
        group_score = [max(p[g * per:(g + 1) * per]) for g in range(n_group)]
        best = sorted(range(n_group), key=lambda g: (-group_score[g], g))
        keep = set(best[:topk_group])
        left = [(p[e] if e // per in keep else 0.0, e)
                for e in range(len(p))]
        chosen = sorted(left, key=lambda pe: (-pe[0], pe[1]))[:k]
        out.append([(e, w * scale) for w, e in chosen])
    return out


def routing_logits(case):
    rng = np.random.default_rng(7)
    logits = rng.normal(size=(40, 16)).astype(np.float32)
    if case == "ties":
        # whole groups tie, and experts inside a group tie
        logits = np.round(logits)
        logits[:8] = 0.0
    elif case == "one_strong_expert":
        # a group with one strong expert and nothing else is kept for its
        # maximum, and then contributes that one expert alone
        logits[:, 6] = 6.0
        logits[:, 7] = -6.0
    return logits


@pytest.mark.parametrize("case", ["random", "ties", "one_strong_expert"])
@pytest.mark.parametrize("k,topk_group", [(6, 3), (2, 1), (4, 8)])
def test_group_limited_routing_is_the_brute_force_loop(case, k, topk_group):
    logits = routing_logits(case)
    route = topk_routing(jnp.asarray(logits), k, False, n_group=8,
                         topk_group=topk_group, scale=16.0)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    want = brute_force_route(probs, k, 8, topk_group, 16.0)
    for t, pairs in enumerate(want):
        assert np.asarray(route.experts[t]).tolist() == \
            [e for e, _ in pairs], t
        np.testing.assert_allclose(route.weights[t], [w for _, w in pairs],
                                   rtol=1e-6)
    # not renormalised: the weights are 16 x the softmax's own values
    assert float(route.weights.sum(-1).max()) < 16.0
    assert np.asarray(route.exp_counts).sum() == 40 * k


@pytest.mark.parametrize("case", ["random", "ties", "one_strong_expert"])
def test_the_reference_routes_as_the_brute_force_loop(case):
    logits = routing_logits(case)
    # x W_g with x the identity's rows: the logits themselves
    got = np.asarray(reference.route(
        jnp.asarray(logits), jnp.eye(16, dtype=jnp.float32), SIZES))
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    want = np.zeros_like(got)
    for t, pairs in enumerate(brute_force_route(probs, 6, 8, 3, 16.0)):
        for e, w in pairs:
            want[t, e] = w
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_without_groups_or_scale_routing_is_what_it_was():
    logits = jnp.asarray(routing_logits("random"))
    old = topk_routing(logits, 6)
    new = topk_routing(logits, 6, False, n_group=1, topk_group=1, scale=1.0)
    for a, b in zip(old, new):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the share test: what ties the cut to the model
# ---------------------------------------------------------------------------
def moe_layer(held):
    return MoE(d_model=32, d_hidden=16, num_experts=16, k=6,
               drop_tokens=False, gated_experts=True, n_shared=2, n_group=8,
               topk_group=3, routed_scale=16.0, experts_held=held,
               dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def shares():
    """The uncut layer (all 16 experts held) and its 8 shares of one group
    of 2, each with ITS slice of the uncut layer's expert matrices, on 48
    tokens: ``(x, whole, [share outputs], [share stats], shared part)``."""
    x = jax.random.normal(jax.random.PRNGKey(2), (48, 32))
    whole = moe_layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    with jax.default_matmul_precision("highest"):
        y_whole = whole.apply({"params": params}, x)[0]
        outs, stats = [], []
        for g in range(8):
            part = dict(params, experts=jax.tree.map(
                lambda a: a[2 * g:2 * g + 2], params["experts"]))
            (y, *_), st = moe_layer((2 * g, 2)).apply(
                {"params": part}, x, mutable=[MOE_STATS])
            outs.append(y)
            stats.append({k: v[0] for k, v in st[MOE_STATS].items()})
        zero = jax.tree.map(jnp.zeros_like, params["experts"])
        y_shared = whole.apply(
            {"params": dict(params, experts=zero)}, x)[0]
    return x, params, y_whole, outs, stats, y_shared


def test_the_eight_shares_sum_to_the_uncut_layer(shares):
    """Every share adds the shared experts' output (each device computes
    them for its own tokens), so the routed parts are the shares less
    that; summed, with the shared experts counted once, they are the uncut
    layer's output. Float32 sums in another order: 1e-5 of values ~1."""
    _, _, y_whole, outs, stats, y_shared = shares
    routed = sum(y - y_shared for y in outs)
    np.testing.assert_allclose(routed + y_shared, y_whole, atol=1e-5, rtol=0)
    assert float(jnp.abs(y_whole - y_shared).max()) > 0.1     # not vacuous
    # every pair is computed on exactly one share
    assert sum(int(s["routed_here"]) for s in stats) == 48 * 6
    assert all(int(s["held"]) == 2 for s in stats)
    assert all(int(s["computed"].sum()) == int(s["routed_here"])
               for s in stats)


def test_a_share_computes_nothing_for_a_token_whose_groups_exclude_it(
        shares):
    x, params, _, outs, stats, y_shared = shares
    chosen = np.asarray(stats[0]["chosen"])                # [tokens, 6]
    groups = chosen // 2
    assert all(len(set(row)) <= 3 for row in groups)       # at most 3
    seen = 0
    for g in range(8):
        absent = ~(groups == g).any(1)
        seen += absent.sum()
        # the share's output for such a token is the shared experts' alone,
        # to the bit: its rows lie past the last held group and weigh 0
        np.testing.assert_array_equal(np.asarray(outs[g])[absent],
                                      np.asarray(y_shared)[absent])
        assert not np.array_equal(np.asarray(outs[g])[~absent],
                                  np.asarray(y_shared)[~absent])
    assert seen >= 48 * 5          # every token is absent from >= 5 groups


def test_the_reference_leaves_out_what_absent_experts_would_add(shares):
    x, params, _, outs, _, _ = shares
    for g in (0, 3, 7):
        p = {"gate": params["gate"], "shared": params["shared"],
             "experts": jax.tree.map(lambda a: a[2 * g:2 * g + 2],
                                     params["experts"])}
        want = reference.moe(x, p, dict(SIZES, held=(2 * g, 2)))
        np.testing.assert_allclose(outs[g], want, atol=ATOL, rtol=0)


def test_expert_load_counts_the_held_experts_alone(fp32):
    from deepspeed_tpu.moe.utils import publish_expert_load

    eng, _ = fp32
    seen = []
    telemetry_bus.subscribe(seen.append)
    try:
        load = publish_expert_load(
            eng.module, eng.params,
            {"input_ids": np.asarray(tokens(64).reshape(4, 16), np.int32)})
    finally:
        telemetry_bus.unsubscribe(seen.append)
    assert [e["kind"] for e in seen].count("moe.load") == 1
    counts = np.asarray(load["tokens_per_expert"])
    assert counts.shape == (2, 2) and load["held"] == 2
    assert load["routed"] == 2 * 64 * 6
    assert load["routed_here"] == counts.sum() < load["routed"]
    assert load["tokens_dropped"] == 0
    assert load["max_over_mean"] >= 1.0


# ---------------------------------------------------------------------------
# through the scheduler's lane cache
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
    """Five ragged prompts (left-padded into buckets of 16, two of them
    two buckets long) over three lanes: every admission after the third
    is spliced into a lane beside live lanes, and every token is the
    plain reference's argmax over prompt + tokens so far."""
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
    plan = sched.kv_cache_stats()
    assert plan["latent_bytes_per_lane"] == 3 * 64 * (16 + 4) * 4
    assert plan["kv_bytes_per_lane"] == plan["bytes_per_lane"] \
        == plan["latent_bytes_per_lane"] + 64 + 4
    assert plan["state_bytes"] == 0 and plan["compression_ratio"] == 1.0


def test_lanes_at_exit_hold_the_latents_of_every_token_taken_in(fp32):
    """What the benchmark's check reads: a lane's rows are its request's
    prompt and tokens, exactly those ``valid`` marks, and their latents
    and rotary keys are the reference's (rotary counting cache rows)."""
    eng, _ = fp32
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    sched.retain_lanes = True
    prompts = {sched.submit(tokens(n, seed=2).tolist(), max_new_tokens=30): n
               for n in (5, 21)}
    events = []
    telemetry_bus.subscribe(events.append)

    class Stop(Exception):
        pass

    def poll(state={"n": 0}):
        state["n"] += 1
        if state["n"] > 9:
            raise Stop

    try:
        sched.run(poll_fn=poll)
    except Stop:
        pass
    finally:
        telemetry_bus.unsubscribe(events.append)
    kept = sched.lanes_at_exit
    assert sorted(kept.live) == [0, 1]
    live = [e["live_positions"] for e in events
            if e["kind"] == "serve.stats"]
    assert live and live[-1] <= sum(
        prompts[c.request_id] + len(c.tokens) for c in kept.live.values())
    for lane, comp in kept.live.items():
        n_prompt = prompts[comp.request_id]
        got = kept.positions(lane)
        assert got["cached_latent"].shape == (3, 64, 16)
        assert got["cached_rope_key"].shape == (3, 64, 4)
        first = -(-n_prompt // BUCKET) * BUCKET - n_prompt
        n = n_prompt + len(comp.tokens)
        valid = np.asarray(got["valid"][0])
        assert valid.sum() == n and valid[first:first + n].all()
        seq = tokens(n_prompt, seed=2).tolist() + list(comp.tokens)
        _, latent, rope_key = reference.hidden_and_states(
            eng.params, np.asarray(seq), SIZES, offset=first)
        np.testing.assert_allclose(got["cached_latent"][:, first:first + n],
                                   latent, atol=ATOL, rtol=0)
        np.testing.assert_allclose(
            got["cached_rope_key"][:, first:first + n], rope_key, atol=ATOL,
            rtol=0)


@pytest.mark.parametrize("n", [1, 5, 16, 21, 30])
def test_left_padded_bucket_equals_the_unpadded_prompt(fp32, n):
    """A pad's latent and rotary key are written and never read: the
    bucket's last-position logits are the unpadded prompt's."""
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
    assert np.asarray(cache["h"]["valid"])[0, :Lp].tolist() == \
        mask[0].tolist()


# ---------------------------------------------------------------------------
# what a latent cache refuses, by name of the reason
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("feature", ["int8_kv", "prefix_cache",
                                     "speculation", "tp"])
def test_a_latent_cache_refuses_what_assumes_keys_and_values(fp32, feature):
    from deepspeed_tpu.parallel.mesh import (
        MeshTopology,
        reset_default_topology,
    )
    from deepspeed_tpu.serving.prefix_cache import PrefixCache

    eng, _ = fp32
    try:
        with pytest.raises(LatentCacheError) as err:
            if feature == "int8_kv":
                deepspeed_tpu.init_inference(
                    GPT(model_config()), dtype="fp32",
                    config={"kv_cache": "int8"})
            elif feature == "prefix_cache":
                serving.ContinuousBatchingScheduler(
                    eng, slots=2, prompt_bucket=BUCKET,
                    prefix_cache=PrefixCache())
            elif feature == "speculation":
                serving.ContinuousBatchingScheduler(
                    eng, slots=2, prompt_bucket=BUCKET, draft_engine=eng,
                    spec_k=2)
            else:
                reset_default_topology()
                tp = deepspeed_tpu.init_inference(
                    GPT(model_config()), dtype="fp32", mp_size=2)
                serving.ContinuousBatchingScheduler(
                    tp, slots=2, prompt_bucket=BUCKET)
    finally:
        if feature == "tp":
            reset_default_topology()
            from deepspeed_tpu.parallel.mesh import set_default_topology

            set_default_topology(eng.topology)
    assert "latent cache" in str(err.value)
    assert err.value.feature.startswith(
        {"int8_kv": "kv_cache_dtype", "prefix_cache": "prefix_cache",
         "speculation": "draft_engine", "tp": "tp > 1"}[feature])


def test_a_latent_block_has_no_second_mixer_and_takes_rotary_alone():
    with pytest.raises(ValueError, match="latent-attention block"):
        model_config(learned_positions=True)
    with pytest.raises(ValueError, match="first_k_dense"):
        model_config(first_k_dense=9)
    with pytest.raises(ValueError, match="moe_experts_held"):
        model_config(moe_experts_held=(15, 2))


# ---------------------------------------------------------------------------
# scopes: where the readers find the layer in a lowering
# ---------------------------------------------------------------------------
def test_the_serving_programs_carry_the_new_scopes(fp32):
    eng, sched = fp32
    sched.submit(tokens(5).tolist(), max_new_tokens=3)
    sched.run()
    table = sched.program_scopes()

    def scopes_of(program):
        return {c for path in table[program].values() if path
                for c in scopes.split_path(path)}

    decode = scopes_of("jit_decode_k")
    assert {scopes.SCOPE_MLA_Q_PROJ, scopes.SCOPE_MLA_KV_PROJ,
            scopes.SCOPE_MLA_ABSORB, scopes.SCOPE_MLA_ATTN,
            scopes.SCOPE_MLA_OUT_PROJ, scopes.SCOPE_MOE_SHARED,
            scopes.SCOPE_MOE_ROUTER, scopes.SCOPE_MOE_EXPERTS,
            scopes.SCOPE_KV_CACHE_WRITE, scopes.SCOPE_KV_CACHE_READ} <= decode
    prefill = scopes_of("jit_prefill")
    # a prefill decompresses keys and values and absorbs nothing
    assert scopes.SCOPE_MLA_ABSORB not in prefill
    assert {scopes.SCOPE_MLA_KV_PROJ, scopes.SCOPE_MLA_ATTN,
            scopes.SCOPE_MOE_SHARED} <= prefill
    # a whole latent leaf is only ever handed on (an argument, an element
    # of the loop's carry), never produced outside the row update. Off the
    # chip the interpreter's emulation of the decode kernel copies the
    # leaves it is handed; Mosaic takes them where they lie
    # (``test_the_decode_program_hands_the_kernel_the_stacked_leaves``)
    carried = [name for name, p in table["jit_decode_k"].items()
               if p and scopes.SCOPE_KV_CACHE_CARRY in scopes.split_path(p)]
    assert lda._interpret()
    assert carried and all(
        n.startswith(("get-tuple-element", "cache__", "param", "copy"))
        for n in carried)


def test_latent_leaves_names_are_what_the_model_declares():
    assert (la.CACHED_LATENT, la.CACHED_ROPE_KEY) == tuple(
        n for n, _ in model_config().position_leaves)
    assert [(x.name, x.rank, x.kind, x.counted_as, x.carry_tag)
            for x in model_config().cache_leaves] == [
        ("cached_latent", 3, "position", ("latent",), "kv_cache_carry"),
        ("cached_rope_key", 3, "position", ("latent",), "kv_cache_carry")]


# ---------------------------------------------------------------------------
# the absorbed form's two routes: one query token through the kernel that
# reads each lane's live blocks once (ops/pallas/latent_decode_attention.py),
# everything else on the einsums
# ---------------------------------------------------------------------------
def padded_prompts(lengths, bucket=BUCKET):
    ids = np.zeros((len(lengths), bucket), np.int32)
    mask = np.zeros((len(lengths), bucket), bool)
    for row, n in enumerate(lengths):
        ids[row, bucket - n:] = tokens(n, seed=row + 5)
        mask[row, bucket - n:] = True
    return jnp.asarray(ids), jnp.asarray(mask)


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_decode_k_over_the_carried_leaves_matches_the_einsum_model(
        monkeypatch, scan):
    """Four decode steps in one program over the stacked leaves the layer
    loop carries, ragged left-padded prompts: the kernel's tokens and
    cache are the einsum route's (the same model with the choice turned
    off). The leading dense block hands the kernel a Python layer index,
    the scanned blocks a traced one."""
    eng = deepspeed_tpu.init_inference(
        GPT(model_config(scan_layers=scan)), dtype="fp32", seed=4)
    ids, mask = padded_prompts((16, 5, 1))
    eng._materialize(ids)
    eng._build_decode_fns()

    def decode():
        logits, cache = eng._prefill_fn(eng.params, ids, mask)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return eng._decode_k_fn.fn(eng.params, tok, cache,
                                   jax.random.PRNGKey(0), jnp.float32(0.0),
                                   4)[:3]

    layers = []
    real = lda.latent_decode_attention
    monkeypatch.setattr(
        lda, "latent_decode_attention",
        lambda *a, **k: layers.append(a[5]) or real(*a, **k))
    toks, tok, cache = decode()
    # traced once a layer, or for the dense block and then for the
    # scanned one (which flax traces more than once)
    if scan:
        assert layers[0] == 0 and len(layers) > 1
        assert all(isinstance(n, jax.core.Tracer) for n in layers[1:])
    else:
        assert layers == [0, 1, 2]
    del layers[:]
    monkeypatch.setattr(transformer_lm, "decode_attention_block",
                        lambda cfg, T=1: None)
    jax.clear_caches()
    want_toks, want_tok, want_cache = decode()
    jax.clear_caches()
    assert not layers
    assert np.asarray(toks).tolist() == np.asarray(want_toks).tolist()
    assert np.asarray(tok).tolist() == np.asarray(want_tok).tolist()
    for got, want in zip(jax.tree.leaves(cache),
                         jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=ATOL, rtol=ATOL)


@pytest.mark.parametrize("case", ["one_token", "several_tokens", "tp"])
def test_the_route_is_told_from_the_call_and_by_no_option(case):
    """One query token over a cache that exists takes the kernel, at the
    latent rule's block; a continuation of several tokens and heads
    sharded over ``tp`` keep the einsums; a pass that makes the cache
    plans nothing; no field of either configuration names the choice."""
    from deepspeed_tpu.parallel.mesh import (
        MeshTopology,
        reset_default_topology,
        set_default_topology,
    )

    reset_default_topology()
    cfg = model_config()
    m = cfg.mla
    try:
        if case == "tp":
            set_default_topology(MeshTopology(tp=2, dp=-1,
                                              devices=jax.devices()[:2]))
        T = 4 if case == "several_tokens" else 1
        block = transformer_lm.decode_attention_block(cfg, T)
        if case == "one_token":
            assert block == lda.block_positions(64, m.kv_rank, 4) == 64
            # the cell's shape: 512 positions of 512 in bf16, whatever
            # divides the cache; a float32 latent half as many
            assert lda.block_positions(2944, 512, 2) == 512
            assert lda.block_positions(2944, 512, 4) == 256
            assert lda.block_positions(200, 512, 2) == 200
        else:
            assert block is None

        def lane_of(variables, T):
            holder = {}

            class Probe(nn.Module):
                @nn.compact
                def __call__(self):
                    holder["lane"] = la.open_lane_cache(
                        self, cfg, 2, T, None).lane
                    return jnp.zeros(())

            _, out = Probe().apply(variables, mutable=["cache"])
            return holder["lane"], out

        made, variables = lane_of({}, 1)
        assert made.fresh and made.plan is None
        found, _ = lane_of(variables, T)
        assert not found.fresh
        assert (found.plan is not None) == (case == "one_token")
    finally:
        reset_default_topology()
    for config in (GPTConfig, MLAConfig):
        names = {f.name for f in dataclasses.fields(config)}
        assert not {n for n in names if "decode_attention" in n
                    or "kernel" in n or "live_block" in n}


def test_the_decode_program_hands_the_kernel_the_stacked_leaves(fp32):
    """In the traced decode program each of the two layer bodies (the
    leading dense block, the scanned block) holds one ``mla_decode_attn``
    call whose operands are the stacked latent leaf as it lies and the
    stacked rotary leaf as ``[layers, B, rope_dim, S]`` (a bitcast on the
    TPU, whose layout of that leaf has the positions along the lanes),
    and no slice of either leaf's layer is made; what the calls share
    (work items, mask) is made outside the layer loop."""
    eng, sched = fp32
    cfg = eng.module.config
    L, S, r, dr = cfg.n_layer, cfg.n_positions, cfg.mla.kv_rank, \
        cfg.mla.rope_dim
    jaxpr = eng._decode_k_fn.fn.trace(
        eng.params, jnp.zeros((4,), jnp.int32), sched.lane_cache.shapes,
        jax.random.PRNGKey(0), jnp.float32(0.0), 1).jaxpr.jaxpr

    def walk(jaxpr, depth=0):
        for e in jaxpr.eqns:
            yield e, depth
            inner = depth + (e.primitive.name == "scan")
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub, inner)

    eqns = list(walk(jaxpr))
    calls = [(e, d) for e, d in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e, _ in calls] == [lda.KERNEL_NAME] * 2
    # the token loop is one scan; the scanned blocks a second inside it
    assert sorted(d for _, d in calls) == [1, 2]
    for e, _ in calls:
        shapes = [v.aval.shape for v in e.invars]
        assert (L, 4, S, r) in shapes and (L, 4, dr, S) in shapes
        assert (4, S, r) not in shapes
    sliced = [e for e, _ in eqns
              if e.primitive.name in ("dynamic_slice", "gather", "slice")
              and e.outvars[0].aval.shape[-2:] in ((S, r), (S, dr))]
    assert not sliced
    # the running sum of work_items is the plan's: once a step
    assert all(d == 1 for e, d in eqns if e.primitive.name == "argmax"
               and e.invars[0].aval.shape == (4, S))
    assert sum(e.primitive.name == "argmax"
               and e.invars[0].aval.shape == (4, S) for e, _ in eqns) == 1


# ---------------------------------------------------------------------------
# the experts' grouped matmuls read the stacked parameters in place
# ---------------------------------------------------------------------------
def aligned_config(**changes):
    """The tiny model with its experts 128 wide over a hidden size of 128:
    one leading dense layer, two scanned expert layers, one group of two
    experts held of sixteen. Eight prompts of a 16-token bucket sort 768
    rows a layer, eight lanes' decode step 48."""
    return model_config(n_embd=128, moe_intermediate_size=128, **changes)


def serving_programs(cfg, seed=4):
    eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=seed)
    ids, mask = padded_prompts((16, 5, 1, 9, 16, 2, 12, 7))
    eng._materialize(ids)
    eng._build_decode_fns()
    host = lambda tree: jax.tree.map(np.asarray, tree)    # noqa: E731
    logits, cache = eng._prefill_fn(eng.params, ids, mask)
    out = {"prefill": host((logits, cache))}
    more_ids, more_mask = padded_prompts((16,) * 8)
    logits, cache = eng._prefill_more_fn(eng.params, more_ids, more_mask,
                                         cache)
    out["prefill_more"] = host((logits, cache))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out["decode_k"] = host(eng._decode_k_fn.fn(
        eng.params, tok, cache, jax.random.PRNGKey(0), jnp.float32(0.0),
        4)[:3])
    return out


def gmm_routes(monkeypatch):
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm

    routes, real = [], gm.gmm

    def spy(*args, **kwargs):
        routes.append(kwargs.get("layer") is not None)
        return real(*args, **kwargs)

    monkeypatch.setattr(gm, "gmm", spy)
    return routes


def test_the_serving_programs_over_the_stack_are_bitwise_the_slices(
        monkeypatch):
    """``jit_prefill``, ``jit_prefill_more`` and four steps of
    ``jit_decode_k`` of the aligned model, the experts' products over the
    stacked leaves with the scan's index: logits, tokens and every cache
    leaf are bitwise those of the same model on the scan's slices (the
    rule answered for it), because the kernel makes the same walk over
    the same blocks."""
    from deepspeed_tpu.moe import experts

    cfg = aligned_config()
    routes = gmm_routes(monkeypatch)
    got = serving_programs(cfg)
    assert routes and all(routes)
    del routes[:]
    monkeypatch.setattr(experts, "expert_matrices",
                        lambda cfg, rows: "slice")
    jax.clear_caches()
    want = serving_programs(cfg)
    jax.clear_caches()
    assert routes and not any(routes)
    for program in want:
        for a, b in zip(jax.tree.leaves(got[program]),
                        jax.tree.leaves(want[program])):
            assert a.dtype == b.dtype and (a == b).all(), program


def test_a_training_forwards_gradient_is_what_it_was(monkeypatch):
    """``jax.grad`` of the aligned model's training forward reads the
    stack too (after the leading dense block: the turn's index is the
    scan's less one) and is differentiated with respect to the scan's
    slices: with the rule answering "slice" for everything the gradients
    are bitwise the same, every product then through the custom VJP on one
    layer's matrices."""
    from deepspeed_tpu.moe import experts

    cfg = aligned_config(num_logits_to_keep=None)
    model, params = init(cfg)
    ids = jnp.asarray(np.stack([tokens(16, seed=s) for s in range(8)]))

    def grads():
        jax.clear_caches()
        return jax.jit(jax.grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)))(params)

    routes = gmm_routes(monkeypatch)
    got = grads()
    assert routes and all(routes)
    del routes[:]
    monkeypatch.setattr(experts, "expert_matrices",
                        lambda cfg, rows: "slice")
    want = grads()
    jax.clear_caches()
    assert routes and not any(routes)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert np.asarray(got["h"]["block"]["mlp"]["experts"]["wi"]).any()


@pytest.mark.parametrize("model", ["aligned_latent", "tiny_latent", "gpt"])
def test_the_cache_plan_and_the_counter_say_where_the_matrices_are_read(
        model, fp32):
    """``serve.cache_plan``'s ``expert_matrices`` and the layers'
    ``in_place`` counter read what the rule said: in place at widths of
    whole lanes, the scan's slice for the tiny model (16-wide experts:
    ``ragged_dot``), none for a model without experts."""
    from flax.traverse_util import flatten_dict
    from simple_model import tiny_gpt_config

    events = []
    telemetry_bus.subscribe(events.append)
    try:
        if model == "aligned_latent":
            eng, sched = served(slots=8, n_embd=128,
                                moe_intermediate_size=128)
        elif model == "tiny_latent":
            eng, sched = served(slots=8)
        else:
            eng = deepspeed_tpu.init_inference(GPT(tiny_gpt_config()),
                                               dtype="fp32", seed=0)
            sched = serving.build_serving(eng, {"slots": 8,
                                                "prompt_bucket": BUCKET})
            sched._ensure_compiled()
    finally:
        telemetry_bus.unsubscribe(events.append)
    want = {"aligned_latent": "in_place", "tiny_latent": "slice",
            "gpt": "none"}[model]
    (plan,) = [e for e in events if e.get("kind") == "serve.cache_plan"]
    assert plan["expert_matrices"] == want
    _, out = eng.module.apply(
        {"params": eng.params}, jnp.zeros((8, 1), jnp.int32), decode=True,
        mutable=["cache", MOE_STATS])
    counted = [np.asarray(value).reshape(-1).tolist()
               for path, (value,) in flatten_dict(
                   out.get(MOE_STATS, {})).items() if path[-1] == "in_place"]
    assert sum(counted, []) == {"in_place": [1, 1], "slice": [0, 0],
                                "none": []}[want]


# ---------------------------------------------------------------------------
# the second query projection's product is held before its per-head view
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def held_and_free():
    """The tiny model's three serving programs' results as the layer is,
    and with ``optimization_barrier`` an identity while they are traced;
    and the shapes the barrier was asked to hold."""
    cfg, held = model_config(), []
    real = jax.lax.optimization_barrier
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax.lax, "optimization_barrier",
                      lambda x: held.append(x.shape) or real(x))
        got = serving_programs(cfg)
        patch.setattr(jax.lax, "optimization_barrier", lambda x: x)
        jax.clear_caches()
        want = serving_programs(cfg)
    jax.clear_caches()
    return cfg, held, got, want


@pytest.mark.parametrize("program", ["prefill", "prefill_more", "decode_k"])
def test_holding_the_query_projections_product_changes_no_value(
        held_and_free, program):
    """``LatentAttention`` holds ``c_q W_qb`` as a 2-D value
    (``optimization_barrier``) so that the TPU compiler keeps the stacked
    ``q_b`` kernel where it lies (tests/unit/test_grouped_matmul.py compiles
    that for the chip). The barrier is an identity: the tiny model's
    prefill, sixteen-token continuation and four decode steps give, bit for
    bit, the logits, tokens and cache leaves they give with the barrier
    taken out, which is what they gave before it was there."""
    cfg, held, got, want = held_and_free
    # the product of the tokens at hand alone, whatever the form
    assert held and {shape[-1] for shape in held} \
        == {cfg.n_head * cfg.mla.qk_dim}
    for a, b in zip(jax.tree.leaves(got[program]),
                    jax.tree.leaves(want[program])):
        assert a.dtype == b.dtype and (a == b).all()


# ---------------------------------------------------------------------------
# the benchmark's check keeps its teeth with the kernel engaged (the body
# of ``test_perfbench_deepseek_v2.py::test_check_fails_a_swapped_token_
# and_a_perturbed_latent``, which pins ``decode_attention == "einsum"`` for
# the stand-in and is a benchmark file: PERF.md, section 7)
# ---------------------------------------------------------------------------
def test_the_check_refuses_swapped_tokens_and_perturbed_latents():
    """The tiny system, its decode attention on the kernel, serves two
    requests to their end and is stopped with two more in their lanes.
    ``check`` over that record is correct; a swapped token, a swapped
    first token, a live lane that took in other tokens than it streamed,
    first-layer latents or rotary keys off by a hundredth are each
    refused; without live lanes there is no verdict."""
    import copy
    import types

    from brumby_tiny import TINY_CLOSED_DECODED
    from perfbench.traffic_kinds import serve_closed, serve_closed_decoded

    env = types.SimpleNamespace(
        config=TINY_DEEPSEEK, traffic=TINY_CLOSED_DECODED, seed=11,
        t_open=0.0, t_close=float("inf"))
    system = deepseek_v2_serve.build(env, None)
    sched, by_rid, done, polls = system.scheduler, {}, [], []

    class WindowEnds(Exception):
        pass

    def on_token(rid, token, ended):
        req = by_rid[rid]
        req.times.append(2.0 + len(req.times))
        req.tokens.append(int(token))
        if ended:
            done.append(req)

    def poll():
        polls.append(1)
        if len(polls) == 12:
            raise WindowEnds

    rng = np.random.default_rng(0)
    try:
        for i, (n, want) in enumerate(zip((9, 20, 5, 30), (6, 30, 6, 30))):
            prompt = rng.integers(0, 128, size=n).tolist()
            rid = sched.submit(prompt, max_new_tokens=want,
                               stream_callback=on_token)
            by_rid[rid] = serve_closed.Req(client=i, prompt=prompt,
                                           want=want, ramp=False,
                                           t_submit=1.0)
        with pytest.raises(WindowEnds):
            sched.run(poll_fn=poll)
    finally:
        system.unsubscribe(system.on_bus)
    sched._pending.clear()
    record = {"done": done, "by_rid": by_rid,
              "in_flight": [r for r in by_rid.values() if r not in done]}
    assert system.cache_plan["decode_attention"] == "live_blocks"
    assert system.cache_plan["decode_attention_block"] == 64
    assert len(record["done"]) == 2 and len(record["in_flight"]) == 2
    kept = sched.lanes_at_exit
    assert len(kept.live) == 2
    real_lanes = system.live_lanes
    plan = types.SimpleNamespace(vocab=128)

    def checked(edit=None, lanes=None):
        rec = copy.deepcopy(record)
        rec["by_rid"] = {rid: next(
            x for x in rec["done"] + rec["in_flight"] if x.client == r.client)
            for rid, r in record["by_rid"].items()}
        if edit:
            edit(rec)
        sched.lanes_at_exit = kept              # ``check`` lets it go
        system.live_lanes = (lambda n, rng: lanes(real_lanes(n, rng))) \
            if lanes else real_lanes
        return serve_closed_decoded.check(env, system, plan, rec)

    def swap(where, k):
        def edit(rec):
            for i, r in enumerate(rec[where]):
                r.tokens[k] = (r.tokens[k] + 1 + i) % 128
        return edit

    def perturb(leaf):
        def lanes(found):
            for lane in found:                  # the first layer's rows
                lane[leaf] = lane[leaf].at[0].multiply(1.01)
            return found
        return lanes

    good = checked()
    assert good["correct"] is True and good["decode"]["lanes"] == 2
    # float32 against float32: the latents the kernel's steps left are
    # the reference's, to the order of the sums
    assert good["decode"]["mean_state_error"] < 2e-6
    assert good["decode"]["first_layer_head_state_error"] < 2e-6
    assert good["decode"]["mean_tail_error"] < 2e-6
    bad = checked(swap("done", 3))
    assert bad["correct"] is False and bad["failed"] == 0
    assert bad["decode"]["ok"] is False
    first = checked(swap("done", 0))
    assert first["correct"] is False
    assert any(f["margin"] > f["tolerance"] for f in first["reference"])
    other = checked(swap("in_flight", 2))
    assert other["correct"] is False
    assert other["live_lanes_streamed_their_tokens"] is False
    latent = checked(lanes=perturb("cached_latent"))
    assert latent["correct"] is False and latent["failed"] == 0
    assert latent["decode"]["first_layer_head_state_error"] \
        == pytest.approx(0.01, rel=1e-2)
    assert latent["decode"]["mean_margin"] == 0.0      # tokens cannot tell
    keys = checked(lanes=perturb("cached_rope_key"))
    assert keys["correct"] is False
    assert keys["decode"]["mean_tail_error"] > 1e-3
    system.live_lanes = real_lanes
    sched.lanes_at_exit = None
    none = serve_closed_decoded.check(env, system, plan, record)
    assert none["correct"] is False and none["decode"]["lanes"] == 0
