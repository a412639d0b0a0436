"""The attention-free block (power retention in place of attention, Brumby)
on the normal serving path, at a small size on the CPU with seeded
weights, against the plain reference the benchmark's cell uses
(``perfbench/reference/brumby.py``)."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from brumby_tiny import TINY_BRUMBY
from deepspeed_tpu import serving
from deepspeed_tpu.inference.engine import carried_leaf_shapes
from deepspeed_tpu.models.transformer_lm import GPT, num_params
from deepspeed_tpu.ops import power_retention as pr
from deepspeed_tpu.ops.pallas import retention_step as kernel
from deepspeed_tpu.telemetry import scopes, telemetry_bus
from perfbench.builders import brumby_serve
from perfbench.reference import brumby as reference

HERE = os.path.dirname(os.path.abspath(__file__))
SIZES = reference.sizes(TINY_BRUMBY)
VOCAB = TINY_BRUMBY["vocab_size"]
HEAD = TINY_BRUMBY["head_dim"]
BUCKET = 16
ORDER = brumby_serve.stored_order(HEAD)


def model_config(dtype="float32", **serve):
    section = dict(TINY_BRUMBY["serve"], param_dtype=dtype,
                   compute_dtype=dtype, **serve)
    return brumby_serve.model_config(TINY_BRUMBY, section)


def served(dtype="float32", slots=4, seed=3, **serve):
    eng = deepspeed_tpu.init_inference(
        GPT(model_config(dtype, **serve)),
        dtype={"float32": "fp32", "bfloat16": "bf16"}[dtype], seed=seed)
    sched = serving.build_serving(eng, {"slots": slots,
                                        "prompt_bucket": BUCKET})
    sched._ensure_compiled()
    return eng, sched


@pytest.fixture(scope="module")
def fp32():
    return served()


def prompt_of(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, VOCAB, size=n)


def left_padded(prompt, bucket=BUCKET):
    Lp = -(-len(prompt) // bucket) * bucket
    ids = np.zeros((1, Lp), np.int32)
    mask = np.zeros((1, Lp), bool)
    ids[0, Lp - len(prompt):] = prompt
    mask[0, Lp - len(prompt):] = True
    return jnp.asarray(ids), jnp.asarray(mask)


def mixer_leaves(cache):
    """``(S [layers, B, Hkv, d, D], z [layers, B, Hkv, D])`` as stored."""
    m = cache["h"]["block"]["attn"]
    return np.asarray(m["ret_state"], np.float32), \
        np.asarray(m["ret_norm"], np.float32)


def in_reference_order(S, z):
    """The lane's leaves ``[layers, Hkv, d, D]`` / ``[layers, Hkv, D]`` as
    the reference lists them: ``[layers, Hkv, pairs, d]`` / ``[layers,
    Hkv, pairs]``, pairs ``a <= b`` in lexicographic order."""
    return np.swapaxes(np.asarray(S), -1, -2)[:, :, ORDER], \
        np.asarray(z)[:, :, ORDER]


# ---------------------------------------------------------------------------
# (h) the equations, pinned
# ---------------------------------------------------------------------------
def test_the_symmetric_square_is_the_squared_dot_product():
    rng = np.random.default_rng(0)
    for d in (2, 8, 128):
        q, k = rng.normal(size=(2, 3, d)).astype(np.float32)
        want = np.sum(q.astype(np.float64) * k, -1) ** 2
        # float32 sums of ~d^2 / 2 terms that cancel where q . k is small
        tol = dict(rtol=2e-5, atol=2e-6 * float(
            (np.linalg.norm(q, axis=-1) * np.linalg.norm(k, axis=-1)
             ).max()) ** 2)
        got = np.sum(np.asarray(pr.sympow2(q)) * np.asarray(pr.sympow2(k)),
                     -1)
        np.testing.assert_allclose(got, want, **tol)
        ref = np.sum(np.asarray(reference.phi(jnp.asarray(q)))
                     * np.asarray(reference.phi(jnp.asarray(k))), -1)
        np.testing.assert_allclose(ref, want, **tol)
        # the program's stored order: every unordered pair once among the
        # live entries, the dead ones exactly zero
        a, b, live = pr.sympow2_pairs(d)
        assert live.sum() == d * (d + 1) // 2 == len(reference.pairs(d)[0])
        assert len(a) == pr.sympow2_width(d) == (d // 2 + 1) * d
        assert len({(min(i, j), max(i, j))
                    for i, j, c in zip(a, b, live) if c}) == live.sum()
        assert not np.asarray(pr.sympow2(q))[..., ~live].any()
        coef = np.where(a == b, 1.0, np.sqrt(2.0)) * live
        np.testing.assert_allclose(np.asarray(pr.sympow2(q)),
                                   coef * q[..., a] * q[..., b], rtol=1e-6)
    with pytest.raises(ValueError, match="even"):
        pr.sympow2_width(7)


def _random_pass(rng, B, T, H, Hkv, d):
    # a shared component keeps q . k away from zero: where a token's
    # normaliser sum_j (q_t . k_j)^2 is tiny, two orders of the same
    # float32 sums (phi(q) . phi(k) against (q . k)^2) differ by percents
    q = (1.5 + rng.normal(size=(B, T, H, d))).astype(np.float32)
    k = (1.5 + rng.normal(size=(B, T, Hkv, d))).astype(np.float32)
    v = rng.normal(size=(B, T, Hkv, d)).astype(np.float32)
    # gates from a half-life of two tokens to one of hundreds
    log_g = -np.log1p(np.exp(-rng.normal(size=(B, T, Hkv)) * 3 - 2)) \
        .astype(np.float32)
    return q, k, v, log_g


def test_the_references_recurrence_is_the_explicit_quadratic_sum():
    """``y_t = sum_j a[t, j] v_j / (sum_j a[t, j] + eps)`` with ``a[t, j] =
    exp(sum_{l = j+1..t} log g_l) (q_t . k_j)^2``, written here with
    loops and no ``phi``, against the reference's token recurrence."""
    rng = np.random.default_rng(1)
    T, H, Hkv, d = 13, 4, 2, 8
    q, k, v, log_g = (x[0] for x in _random_pass(rng, 1, T, H, Hkv, d))
    y, S, z = reference.retention_core(
        *map(jnp.asarray, (q, k, v, log_g)), SIZES, T)
    want = np.zeros((T, H, d))
    for t in range(T):
        for i in range(H):
            h = i // (H // Hkv)
            a = np.array([np.exp(log_g[j + 1:t + 1, h].sum())
                          * float(q[t, i] @ k[j, h]) ** 2
                          for j in range(t + 1)])
            want[t, i] = (a[:, None] * v[:t + 1, h]).sum(0) \
                / (a.sum() + SIZES["ret_eps"])
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-6)
    # and the state it hands back is the gated sum of phi(k) v^T
    left = np.exp(np.cumsum(log_g[::-1], 0)[::-1] - log_g)      # [T, Hkv]
    pk = np.asarray(reference.phi(jnp.asarray(k)))              # [T, Hkv, D]
    np.testing.assert_allclose(
        np.asarray(S), np.einsum("th,ths,thd->hsd", left, pk, v), rtol=2e-5,
        atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(z), np.einsum("th,ths->hs", left, pk), rtol=2e-5,
        atol=2e-6)
    # a sequence padded on the right hands back the state at ``length``
    _, S9, z9 = reference.retention_core(
        *map(jnp.asarray, (q, k, v, log_g)), SIZES, 9)
    _, S9w, _ = reference.retention_core(
        *map(jnp.asarray, (q[:9], k[:9], v[:9], log_g[:9])), SIZES, 9)
    np.testing.assert_allclose(np.asarray(S9), np.asarray(S9w), rtol=1e-6)


@pytest.mark.parametrize("chunk", [1, 4, 8, 21, 64])
@pytest.mark.parametrize("start", ["zero", "fresh", "nonzero"])
def test_the_chunked_pass_is_the_token_recurrence(chunk, start):
    """``ops/power_retention.py`` alone: the chunked pass equals
    ``retention_step`` token by token, for a length that is no multiple of
    the chunk, from a zero state (with and without ``fresh``) and from a
    nonzero one; and the program's step equals the reference's."""
    rng = np.random.default_rng(2)
    B, T, H, Hkv, d = 2, 21, 4, 2, 8
    D = pr.sympow2_width(d)
    q, k, v, log_g = _random_pass(rng, B, T, H, Hkv, d)
    live = pr.sympow2_pairs(d)[2]
    S0 = np.zeros((B, Hkv, d, D), np.float32)
    z0 = np.zeros((B, Hkv, D), np.float32)
    if start == "nonzero":
        S0 = (rng.normal(size=S0.shape) * live).astype(np.float32)
        z0 = (np.abs(rng.normal(size=z0.shape)) * live).astype(np.float32)
    y, S, z = pr.retention_chunked(S0, z0, q, k, v, log_g, 1e-6, chunk,
                                   fresh=start == "fresh")
    S_t, z_t, ys = jnp.asarray(S0), jnp.asarray(z0), []
    for t in range(T):
        y_t, S_t, z_t = pr.retention_step(
            S_t, z_t, q[:, t], k[:, t], v[:, t], log_g[:, t], 1e-6)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_t), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_t), rtol=2e-5,
                               atol=2e-5)
    assert not np.asarray(S)[..., ~live].any()
    if start != "nonzero":
        want, S_ref, z_ref = reference.retention_core(
            *(jnp.asarray(x[0]) for x in (q, k, v, log_g)), SIZES, T)
        np.testing.assert_allclose(np.asarray(y)[0], np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        got_S, got_z = in_reference_order(np.asarray(S)[None, 0],
                                          np.asarray(z)[None, 0])
        np.testing.assert_allclose(got_S[0], np.asarray(S_ref), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(got_z[0], np.asarray(z_ref), rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("stacked", [True, False])
@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_the_step_kernel_is_the_plain_step(stacked, state_dtype):
    """``ops/pallas/retention_step.py`` interpreted on the CPU: the kernel
    over the stacked leaf, layer a traced scalar, gives ``retention_step``'s
    ``y``, ``S`` and ``z`` for that layer and leaves the other layers as
    they were; two blocks a lane, so that the accumulator is carried."""
    rng = np.random.default_rng(3)
    L, B, H, Hkv, d = 3, 2, 4, 2, 8
    D = pr.sympow2_width(d)
    dt = jnp.dtype(state_dtype)
    q, k, v, log_g = (x[:, 0] for x in _random_pass(rng, B, 1, H, Hkv, d))
    S = jnp.asarray(rng.normal(size=(L, B, Hkv, d, D)), dt)
    z = jnp.asarray(np.abs(rng.normal(size=(L, B, Hkv, D))), dt)
    want_y, want_S, want_z = pr.retention_step(S[1], z[1], q, k, v, log_g,
                                               1e-6)
    if stacked:
        step = jax.jit(lambda S, z, layer: pr.retention_step_stacked(
            S, z, layer, q, k, v, log_g, 1e-6))
        y, S2, z2 = step(S, z, jnp.int32(1))
        for other in (0, 2):
            np.testing.assert_array_equal(np.asarray(S2[other], np.float32),
                                          np.asarray(S[other], np.float32))
        got_S, got_z = S2[1], z2[1]
    else:
        y, got_S, got_z = pr.retention_step_stacked(
            S[1], z[1], None, q, k, v, log_g, 1e-6)
    assert got_S.dtype == dt and got_z.dtype == dt
    tol = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got_S, np.float32),
        np.asarray(want_S.astype(dt), np.float32), **tol)
    np.testing.assert_allclose(
        np.asarray(got_z, np.float32),
        np.asarray(want_z.astype(dt), np.float32), **tol)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), **tol)
    # the kernel's own entry, its block chosen: two tiles of D
    g = jnp.exp(log_g)
    S3, num = kernel.retention_step_update(
        S.astype(jnp.float32), jnp.int32(1), g, v, pr.sympow2(k),
        pr.sympow2(q).reshape(B, Hkv, H // Hkv, D), block=D // 2)
    np.testing.assert_allclose(np.asarray(S3[1]), np.asarray(
        pr.retention_step(S[1].astype(jnp.float32), z[1], q, k, v, log_g,
                          1e-6)[1]), **tol)
    assert kernel.block_columns(128, pr.sympow2_width(128)) == 1664
    assert kernel.block_columns(8, D) == D
    with pytest.raises(ValueError, match="divide"):
        kernel.retention_step_update(
            S, 1, g, v, pr.sympow2(k),
            pr.sympow2(q).reshape(B, Hkv, H // Hkv, D), block=7)


def hf_to_program(sd, n_layer, cfg):
    """The program's parameter tree from a ``Qwen3ForCausalLM``
    ``state_dict`` (numpy); the gate, which Qwen3 lacks, is zeros."""
    def stack(fmt, f=lambda a: a):
        return jnp.asarray(np.stack(
            [f(sd["model.layers.%d.%s" % (i, fmt)]) for i in range(n_layer)]))

    def t(a):
        return a.T

    qkv = jnp.asarray(np.stack([np.concatenate(
        [sd["model.layers.%d.self_attn.%s_proj.weight" % (i, n)].T
         for n in "qkv"], 1) for i in range(n_layer)]))
    return {
        "wte": {"embedding": jnp.asarray(sd["model.embed_tokens.weight"])},
        "lm_head": jnp.asarray(sd["lm_head.weight"].T),
        "ln_f": {"scale": jnp.asarray(sd["model.norm.weight"])},
        "h": {"block": {
            "ln_1": {"scale": stack("input_layernorm.weight")},
            "ln_2": {"scale": stack("post_attention_layernorm.weight")},
            "attn": {
                "c_attn": {"kernel": qkv},
                "c_proj": {"kernel": stack("self_attn.o_proj.weight", t)},
                "q_norm": {"scale": stack("self_attn.q_norm.weight")},
                "k_norm": {"scale": stack("self_attn.k_norm.weight")},
                "gate": {"kernel": jnp.zeros((n_layer, cfg.n_embd,
                                              cfg.kv_heads)),
                         "bias": jnp.zeros((n_layer, cfg.kv_heads))}},
            "mlp": {
                "c_fc": {"kernel": stack("mlp.up_proj.weight", t)},
                "c_gate": {"kernel": stack("mlp.gate_proj.weight", t)},
                "c_proj": {"kernel": stack("mlp.down_proj.weight", t)}}}}}


def test_everything_around_the_core_is_the_published_qwen3_code():
    """A tiny ``Qwen3ForCausalLM`` on CPU torch, its weights copied into
    the program's tree: the plain reference run with a softmax core gives
    its logits, so projections, per-head q/k norm, rotary, grouping, MLP,
    norms and head are the published code's; and the program's parameter
    tree is that model's plus the gate. Skipped where ``transformers`` has
    no ``qwen3``."""
    torch = pytest.importorskip("torch")
    try:
        from transformers import Qwen3Config, Qwen3ForCausalLM
    except ImportError:
        pytest.skip("transformers has no qwen3")
    keys = {k: v for k, v in TINY_BRUMBY.items() if k not in (
        "name", "source", "builders", "model", "serve", "reduced",
        "retention", "model_type")}
    torch.manual_seed(0)
    hf = Qwen3ForCausalLM(Qwen3Config(**keys)).eval().float()
    with torch.no_grad():      # off their constant initial values
        for name, p in hf.named_parameters():
            if "norm" in name:
                p.add_(0.3 * torch.randn_like(p))
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    cfg = model_config()
    params = hf_to_program(sd, keys["num_hidden_layers"], cfg)
    ids = prompt_of(21, seed=8)
    with torch.no_grad():
        want = hf(torch.tensor(ids)[None]).logits[0].numpy()
    ref = reference.logits(params, ids, SIZES, core=reference.softmax_core)
    np.testing.assert_allclose(ref, want, rtol=0, atol=2e-6)
    assert np.abs(want).max() > 0.01          # not a comparison of zeros
    shapes = jax.eval_shape(
        lambda: GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                              jnp.zeros((1, 8), jnp.int32)))["params"]
    assert jax.tree.map(lambda x: x.shape, shapes) \
        == jax.tree.map(lambda x: x.shape, params)
    gate = cfg.n_layer * (cfg.n_embd + 1) * cfg.kv_heads
    assert sum(x.size for x in jax.tree.leaves(params)) == num_params(cfg) \
        == sum(p.numel() for p in hf.parameters()) + gate


# ---------------------------------------------------------------------------
# (a) prefill, then decode steps through the scheduler's lane cache
# ---------------------------------------------------------------------------
# float32: the program sums in another order than the reference (a chunked
# pass against a token-by-token one, fused q/k/v, the kernel's blocks), so
# logits of magnitude ~0.5 agree to a few 1e-6; 2e-5 leaves room.
# bfloat16: the projections' outputs, the MLP and the residual stream are
# rounded to 8 bits of mantissa in every layer (what touches the state is
# float32 from the per-head norm on); logits of std ~0.3 then differ from
# the float32 reference over the same (bf16) weights by up to ~0.03: 0.1 of
# a standard deviation at the largest, and 0.02 of one on average.
@pytest.mark.parametrize("dtype,atol,mean_tol", [
    ("float32", 2e-5, 2e-6), ("bfloat16", 4e-2, 6e-3)])
def test_prefill_then_decode_through_the_lane_cache_gives_the_reference_logits(
        dtype, atol, mean_tol):
    eng, sched = served(dtype)
    # every position's logits, not only the last: the test reads them all
    model = eng.module.clone(config=dataclasses.replace(
        eng.module.config, num_logits_to_keep=None))
    seq = prompt_of(37, seed=1)
    n_prompt, lane = 21, 2
    want = reference.logits(eng.params, seq, SIZES)           # [37, V]
    ids, mask = left_padded(seq[:n_prompt])
    logits, sub = model.apply(
        {"params": eng.params}, ids, attention_mask=mask,
        deterministic=True, decode=True, mutable=["cache"])
    got = [np.asarray(logits, np.float32)[0, -n_prompt:]]
    cache = sched._splice(sched._empty_cache(), sub["cache"], lane)
    # one compiled step, as the served decode program is, and not the
    # model's operations dispatched one by one at each of sixteen steps
    step = jax.jit(lambda params, cache, tok: model.apply(
        {"params": params, "cache": cache}, tok,
        deterministic=True, decode=True, mutable=["cache"]))
    for t in range(n_prompt, len(seq)):
        tok = np.zeros((sched.slots, 1), np.int32)
        tok[lane, 0] = seq[t]
        logits, out = step(eng.params, cache, jnp.asarray(tok))
        cache = out["cache"]
        got.append(np.asarray(logits, np.float32)[lane])
    got = np.concatenate(got, 0)
    assert got.shape == want.shape
    err = np.abs(got - want)
    assert err.max() <= atol, (err.max(), want.std())
    assert err.mean() <= mean_tol, err.mean()
    assert want.std() > 0.05


def test_the_scheduler_serves_the_references_greedy_tokens_with_lanes_reused(
        fp32):
    """(a) through ``submit`` / ``run`` and (d): five requests over two
    lanes, so a lane is reused after a finished request while the other
    decodes; every served token is the reference's argmax given its
    prefix, which it is not if a lane starts from what the request before
    left in its state. The plan event and the stats say what the cache
    holds: state, normaliser and clocks, no keys or values."""
    eng, _ = fp32
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    prompts = [prompt_of(n, seed=2).tolist() for n in (5, 30, 17, 9, 21)]
    wants = (6, 3, 9, 4, 7)
    plans = []
    telemetry_bus.subscribe(plans.append)
    try:
        rids = [sched.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, wants)]
        stats = sched.run()
    finally:
        telemetry_bus.unsubscribe(plans.append)
    done = {c.request_id: c for c in stats.completions}
    for rid, p in zip(rids, prompts):
        toks = list(done[rid].tokens)
        # (padded on the right to one length: a causal model's rows never
        # read the padding, and every request then shares one compile)
        seq = np.zeros((64,), np.int64)
        seq[:len(p) + len(toks) - 1] = p + toks[:-1]
        logits = reference.logits(
            eng.params, seq, SIZES,
            positions=range(len(p) - 1, len(p) + len(toks) - 1))
        assert logits.argmax(-1).tolist() == toks
    plan = [ev for ev in plans if ev["kind"] == "serve.cache_plan"]
    assert len(plan) == 1 and plan[0]["slots"] == 2
    assert plan[0]["decode_attention"] == "none"
    assert plan[0]["decode_attention_block"] == 0
    kv = sched.kv_cache_stats()
    cfg = eng.module.config
    layers, D = cfg.n_layer, pr.sympow2_width(HEAD)
    z_bytes = layers * cfg.kv_heads * D * 4
    assert plan[0]["norm_bytes_per_lane"] == kv["norm_bytes_per_lane"] \
        == z_bytes
    assert plan[0]["state_bytes_per_lane"] == kv["state_bytes_per_lane"] \
        == z_bytes * (HEAD + 1)
    assert kv["conv_bytes_per_lane"] == plan[0]["conv_bytes_per_lane"] == 0
    # the clocks only: one int32 a layer
    assert kv["kv_bytes_per_lane"] == plan[0]["kv_bytes_per_lane"] \
        == layers * 4
    assert kv["kv_bytes"] + kv["state_bytes"] == kv["resident_bytes"]
    assert plan[0]["bytes_per_lane"] == kv["bytes_per_lane"] \
        == kv["kv_bytes_per_lane"] + kv["state_bytes_per_lane"]
    # no keys and values, so no share of them read, summed or on the span
    assert stats.kv_blocks_read_share_sum == 0.0 and stats.decode_steps > 0
    assert stats.summary()["kv_blocks_read_share"] == 0.0
    assert sched._clocks.step() is None and sched._clocks.block == 0
    assert scopes.SCOPE_KV_CACHE_CARRY not in carried_leaf_shapes(
        sched.lane_cache.shapes, eng.module.config.cache_leaves)
    names = {str(p[-1].key) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 sched.lane_cache.shapes)[0]}
    assert names == {"ret_state", "ret_norm", "clock"}


def test_a_reused_lane_starts_from_the_spliced_state_not_the_old_one(fp32):
    """(d) on the leaves themselves: a lane that kept being stepped after
    its request ended holds garbage; the splice of the next admission
    overwrites state, normaliser and clock whole, and the other lanes' are
    untouched."""
    eng, sched = fp32
    _, sub_a = eng._chunked_prefill(*left_padded(prompt_of(19, seed=3)))
    _, sub_b = eng._chunked_prefill(*left_padded(prompt_of(11, seed=4)))
    cache = sched._splice(sched._empty_cache(), sub_a, 1)
    cache = sched._splice(cache, sub_b, 3)
    for _ in range(3):      # every lane is stepped, live or not
        _, _, cache, _ = eng._decode_k_fn(
            eng.params, jnp.zeros((sched.slots,), jnp.int32), cache,
            jax.random.PRNGKey(0), jnp.float32(0.0), 1)
    S, z = mixer_leaves(cache)
    want_S, want_z = mixer_leaves(sub_b)
    assert np.abs(S[:, 1] - want_S[:, 0]).max() \
        > 0.1 * np.abs(want_S).max()                     # lane 1 moved on
    clock = np.asarray(cache["h"]["block"]["attn"]["clock"])
    assert clock[:, 1].tolist() == [19 + 3] * 2 and clock[0, 0] == 3
    before3 = S[:, 3].copy()
    cache = sched._splice(cache, sub_b, 1)
    S, z = mixer_leaves(cache)
    np.testing.assert_array_equal(S[:, 1], want_S[:, 0])
    np.testing.assert_array_equal(z[:, 1], want_z[:, 0])
    np.testing.assert_array_equal(S[:, 3], before3)
    # the clock counts real tokens, not the bucket's pads
    assert np.asarray(cache["h"]["block"]["attn"]["clock"])[:, 1].tolist() \
        == [11, 11]


class _Stop(Exception):
    pass


def test_lanes_at_exit_hold_the_state_after_every_emitted_token(fp32):
    """``retain_lanes``: a run ended from ``poll_fn`` with a step in flight
    keeps the lane cache as that step left it; a live lane's state and
    normaliser are the reference's after the prompt and every token the
    lane emitted (the step in flight consumed the last), finished lanes
    are not listed, and a run that drains keeps nothing."""
    eng, sched = fp32
    prompts = [prompt_of(n, seed=4).tolist() for n in (9, 20, 5)]
    wants = (3, 30, 30)
    polls = []

    def poll():
        polls.append(1)
        if len(polls) == 9:
            raise _Stop

    sched.retain_lanes = True
    try:
        rids = [sched.submit(p, max_new_tokens=w)
                for p, w in zip(prompts, wants)]
        with pytest.raises(_Stop):
            sched.run(poll_fn=poll)
        kept = sched.lanes_at_exit
        assert sorted(c.request_id for c in kept.live.values()) == rids[1:]
        for lane, comp in kept.live.items():
            prompt = prompts[rids.index(comp.request_id)]
            assert 3 < len(comp.tokens) < 30
            seq = np.asarray(prompt + [int(t) for t in comp.tokens])
            _, S, z = reference.hidden_and_states(eng.params, seq, SIZES)
            got = kept.recurrent_state(lane)
            assert set(got) == {"ret_state", "ret_norm"}
            got_S, got_z = in_reference_order(got["ret_state"],
                                              got["ret_norm"])
            assert got_S.shape == S.shape and got_z.shape == z.shape
            np.testing.assert_allclose(got_S, np.asarray(S), rtol=2e-5,
                                       atol=2e-5)
            np.testing.assert_allclose(got_z, np.asarray(z), rtol=2e-5,
                                       atol=2e-5)
        # the rest of the queue drains: nothing in flight, nothing kept
        sched.run()
        assert sched.lanes_at_exit is None
    finally:
        sched.retain_lanes = False
        sched._pending.clear()


# ---------------------------------------------------------------------------
# (b) a left-padded bucket is the unpadded prompt
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 2, 5, 16, 21, 30])
def test_left_padded_bucket_equals_the_unpadded_prompt(fp32, n):
    eng, _ = fp32
    prompt = prompt_of(n, seed=5)
    ids, mask = left_padded(prompt)
    logits_p, cache_p = eng._prefill_fn(eng.params, ids, mask)
    logits_u, cache_u = eng._prefill_fn(
        eng.params, jnp.asarray(prompt[None], jnp.int32),
        jnp.ones((1, n), jnp.bool_))
    # pads leave S and z EXACTLY zero and rotary counts real tokens; what
    # differs afterwards is where the chunks' edges fall (float32 sums in
    # another order: 1e-7 of the state's ~10 for most lengths; a prompt of
    # ONE token divides by its single (q . k)^2, and where that is small
    # the second layer's state moves by 3e-5 of itself)
    for got, want in zip(mixer_leaves(cache_p), mixer_leaves(cache_u)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_u),
                               rtol=0, atol=2e-5)
    want = reference.logits(eng.params, prompt, SIZES, positions=[n - 1])
    np.testing.assert_allclose(np.asarray(logits_p), want, rtol=0, atol=2e-5)
    _, S, z = reference.hidden_and_states(eng.params, prompt, SIZES)
    got_S, got_z = in_reference_order(*(x[:, 0] for x in
                                        mixer_leaves(cache_p)))
    np.testing.assert_allclose(got_S, np.asarray(S), rtol=1e-4, atol=5e-4)
    np.testing.assert_allclose(got_z, np.asarray(z), rtol=1e-4, atol=5e-4)
    clock = np.asarray(cache_p["h"]["block"]["attn"]["clock"])
    assert clock.tolist() == [[n]] * eng.module.config.n_layer


def test_pads_leave_state_and_normaliser_exactly_zero(fp32):
    """An all-pad pass, with a gate far from 1 (a pad must not decay
    either) and nonzero norm weights."""
    eng, _ = fp32
    ids = jnp.asarray(prompt_of(16, seed=6)[None], jnp.int32)
    _, cache = eng._prefill_fn(eng.params, ids, jnp.zeros((1, 16), jnp.bool_))
    S, z = mixer_leaves(cache)
    assert not S.any() and not z.any()
    assert not np.asarray(cache["h"]["block"]["attn"]["clock"]).any()
    # then a real continuation starts a sequence from that zero state
    seq = prompt_of(5, seed=6)
    logits, _ = eng._prefill_more_fn(
        eng.params, jnp.asarray(seq[None], jnp.int32),
        jnp.ones((1, 5), jnp.bool_), cache)
    want = reference.logits(eng.params, seq, SIZES, positions=[4])
    np.testing.assert_allclose(np.asarray(logits), want, rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# (c) prefill + prefill_more = one pass
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cut", [3, 8, 11, 16, 29])
def test_prefill_then_prefill_more_equals_one_pass(fp32, cut):
    """The chunk is 8 here: cuts inside a chunk (3, 11, 29), on a chunk's
    edge (8, 16), continuations that span several chunks."""
    eng, _ = fp32
    seq = jnp.asarray(prompt_of(32, seed=7)[None], jnp.int32)
    ones = jnp.ones((1, 32), jnp.bool_)
    logits_1, cache_1 = eng._prefill_fn(eng.params, seq, ones)
    _, cache = eng._prefill_fn(eng.params, seq[:, :cut], ones[:, :cut])
    logits_2, cache_2 = eng._prefill_more_fn(
        eng.params, seq[:, cut:], ones[:, cut:], cache)
    for got, want in zip(mixer_leaves(cache_2), mixer_leaves(cache_1)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(logits_2), np.asarray(logits_1),
                               rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# (e) the decode program moves no whole state leaf
# ---------------------------------------------------------------------------
def test_layer_loop_carries_state_and_normaliser_in_place():
    """``jit_decode_k``: the mixer's leaves cross the layer loop and the
    loop over ``k`` in the carry only; the compiled program aliases every
    cache leaf to its output; and its scope table names the mixer's four
    scopes and tags nothing as a carried whole leaf."""
    eng, sched = served("bfloat16", slots=3)
    cache = sched.lane_cache.shapes
    cfg = eng.module.config
    n_layer, D = cfg.n_layer, pr.sympow2_width(HEAD)
    stacked = jax.tree.leaves(cache["h"])
    assert all(leaf.shape[0] == n_layer for leaf in stacked)
    declared = carried_leaf_shapes(cache, cfg.cache_leaves)
    assert set(declared) == {scopes.SCOPE_RET_STATE_CARRY}
    whole = declared[scopes.SCOPE_RET_STATE_CARRY]
    assert whole == {(n_layer, 3, cfg.kv_heads, HEAD, D),
                     (3, cfg.kv_heads, HEAD, D),
                     (n_layer, 3, cfg.kv_heads, D)}
    args = (eng.params, jnp.zeros((3,), jnp.int32), cache,
            jax.random.PRNGKey(0), jnp.float32(0.0), 2)
    decode_k = eng._decode_k_fn.fn

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    loops = [e for e in scans(decode_k.trace(*args).jaxpr.jaxpr)
             if e.params["length"] in (2, n_layer)
             and len(e.outvars) > 2]
    assert sorted(e.params["length"] for e in loops) == [2, n_layer]
    for eqn in loops:
        first_x = eqn.params["num_consts"] + eqn.params["num_carry"]
        carried = {v.aval.shape for v in eqn.invars[
            eqn.params["num_consts"]:first_x]}
        crossing = {v.aval.shape for v in eqn.invars[first_x:]} | {
            v.aval.shape for v in eqn.outvars[eqn.params["num_carry"]:]}
        assert not crossing & whole, crossing & whole
        assert {leaf.shape for leaf in stacked} <= carried

    text = decode_k.lower(*args).compile().as_text()
    header = text[:text.index("\n")]
    aliased = {int(n) for n in re.findall(
        r"\{[\d, ]*\}: \((\d+), ", header[header.index(
            "input_output_alias="):])}
    n_cache = len(jax.tree.leaves(cache))
    n_params = len(jax.tree.leaves(eng.params))
    assert aliased == set(range(n_params + 1, n_params + 1 + n_cache))
    _, table = scopes.instruction_scopes(text, declared)
    found = {c for path in table.values() for c in scopes.components(path)}
    assert {"ret_proj", "ret_qk_norm_rope", "ret_state",
            "ret_out_proj"} <= found
    assert "attn_core" not in found and "kv_cache_write" not in found


def test_carry_tags_tell_a_retention_leaf_from_a_kv_and_a_mamba_leaf():
    hlo = "\n".join([
        "HloModule jit_step", "",
        "ENTRY %main (p: f32[2,3,2,8,40]) -> f32[2,3,2,8,40] {",
        "  %p = f32[2,3,2,8,40]{4,3,2,1,0} parameter(0)",
        "  %copy.1 = f32[2,3,2,8,40]{4,3,2,1,0} copy(%p)",
        '  %fusion.2 = f32[2,3,2,8,40]{4,3,2,1,0} fusion(%copy.1), '
        'kind=kLoop, calls=%f, metadata={op_name="jit(step)/ret_state/mul"}',
        "  %copy.3 = bf16[2,3,64,2,8]{4,3,2,1,0} copy(%fusion.2)",
        "  ROOT %copy.4 = f32[2,3,4,8,16]{4,3,2,1,0} copy(%copy.3)",
        "}"])
    carry = {scopes.SCOPE_KV_CACHE_CARRY: {(2, 3, 64, 2, 8)},
             scopes.SCOPE_SSM_STATE_CARRY: {(2, 3, 4, 8, 16)},
             scopes.SCOPE_RET_STATE_CARRY: {(2, 3, 2, 8, 40)}}
    _, table = scopes.instruction_scopes(hlo, carry)
    assert scopes.has_scope(table["copy.1"], "ret_state_carry")
    assert scopes.has_scope(table["copy.3"], "kv_cache_carry")
    assert scopes.has_scope(table["copy.4"], "ssm_state_carry")
    assert scopes.has_scope(table["fusion.2"], "ret_state")
    assert not scopes.has_scope(table["fusion.2"], "ret_state_carry")


def test_a_model_declares_its_recurrent_leaves_once():
    """The declaration both mixers give, and what reads it."""
    from falcon_h1_tiny import TINY_FALCON_H1
    from perfbench.builders import falcon_h1_serve
    from unit.simple_model import tiny_gpt_config

    ret = model_config().recurrent_leaves
    assert [(x.name, x.rank, x.counted_as, x.carry_tag, x.slice_is_whole)
            for x in ret] == [
        ("ret_state", 4, ("state",), "ret_state_carry", True),
        ("ret_norm", 3, ("state", "norm"), "ret_state_carry", False)]
    assert all(x.dtype == jnp.float32 for x in ret)
    assert model_config(state_dtype="bfloat16").recurrent_leaves[0].dtype \
        == jnp.bfloat16
    ssm = falcon_h1_serve.model_config(TINY_FALCON_H1).recurrent_leaves
    assert [(x.name, x.rank, x.counted_as, x.carry_tag) for x in ssm] == [
        ("ssm_state", 4, ("state",), "ssm_state_carry"),
        ("conv_tail", 3, ("conv",), "ssm_state_carry")]
    plain = tiny_gpt_config(n_embd=32, n_layer=1, vocab_size=64)
    assert plain.recurrent_leaves == () and plain.position_leaves
    assert not model_config().position_leaves
    with pytest.raises(ValueError, match="chunk"):
        dataclasses.replace(model_config().retention, chunk=0)
    with pytest.raises(ValueError, match="rotary"):
        dataclasses.replace(model_config(), rotary=False)
    with pytest.raises(NotImplementedError, match="segment_ids"):
        GPT(model_config()).init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
            segment_ids=jnp.ones((1, 8), jnp.int32))


# ---------------------------------------------------------------------------
# (f) what cannot work refuses by name
# ---------------------------------------------------------------------------
def test_speculation_and_prefix_cache_refuse_a_model_with_state(fp32):
    eng, _ = fp32
    with pytest.raises(serving.RecurrentStateError,
                       match="prefix_cache.*ret_state, ret_norm"):
        serving.build_serving(eng, {"slots": 2, "prefix_cache": True})
    with pytest.raises(serving.RecurrentStateError,
                       match="draft_engine.*ret_state"):
        serving.build_serving(eng, {"slots": 2, "spec_k": 2},
                              draft_engine=eng)


# ---------------------------------------------------------------------------
# (g) the programs that exist do not move
# ---------------------------------------------------------------------------
def test_lowered_programs_hash_as_on_the_parent():
    """``lower(...).as_text()`` of the GPT serving programs and train
    steps and of the tiny hybrid configuration's ``jit_prefill``,
    ``jit_decode_k`` and ``splice``: byte for byte what the commits that
    recorded them lower (``tests/unit/data/gpt_program_hashes.json``; the
    hybrid entries were recorded on 2b32c9c, the parent of the PR that
    made a model declare its recurrent leaves, but ``hybrid_jit_decode_k``,
    recorded anew on PR 36's tree (on e55e290) with the kernel ``ssm_step``
    in it; this model's own two on e55e290, before the two mixers shared
    one ``step_kernel``). ``test_falcon_h1.py`` holds the whole table; this
    holds the entries that PR's plumbing runs."""
    from unit import gpt_program_hashes

    with open(os.path.join(HERE, "data", "gpt_program_hashes.json"),
              encoding="utf-8") as f:
        want = json.load(f)
    got = dict(gpt_program_hashes.serve_hashes(),
               **gpt_program_hashes.hybrid_hashes(),
               **gpt_program_hashes.retention_hashes())
    assert {"hybrid_jit_prefill[32]", "hybrid_jit_decode_k",
            "hybrid_jit_splice", "jit_decode_k", "jit_splice",
            "retention_jit_decode_k", "retention_jit_prefill[32]"} \
        <= set(got)
    assert got == {k: want[k] for k in got}
