"""The hybrid block (a Mamba-2 mixer beside attention, Falcon-H1) on the
normal serving path, at a small size on the CPU with seeded weights,
against the plain reference the benchmark's cell uses
(``perfbench/reference/falcon_h1.py``)."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import serving
from deepspeed_tpu.inference.engine import carried_leaf_shapes
from deepspeed_tpu.models.transformer_lm import GPT, num_params
from deepspeed_tpu.ops import ssd
from deepspeed_tpu.ops.pallas import ssd_step as ssd_kernel
from deepspeed_tpu.telemetry import scopes, telemetry_bus
from falcon_h1_tiny import TINY_FALCON_H1
from perfbench.builders import falcon_h1_serve
from perfbench.reference import falcon_h1 as reference

HERE = os.path.dirname(os.path.abspath(__file__))
SIZES = reference.sizes(TINY_FALCON_H1)
VOCAB = TINY_FALCON_H1["vocab_size"]
BUCKET = 16


def model_config(dtype="float32", **serve):
    section = dict(TINY_FALCON_H1["serve"], param_dtype=dtype,
                   compute_dtype=dtype, **serve)
    return falcon_h1_serve.model_config(TINY_FALCON_H1, section)


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
    m = cache["h"]["block"]["mamba"]
    return np.asarray(m["ssm_state"], np.float32), \
        np.asarray(m["conv_tail"], np.float32)


def all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from all_eqns(sub)


# ---------------------------------------------------------------------------
# (a) prefill, then decode steps through the scheduler's lane cache
# ---------------------------------------------------------------------------
# float32: the program sums in another order than the reference (a chunked
# scan against a token-by-token one, fused q/k/v, XLA's own reductions), so
# logits of magnitude ~0.05 agree to a few 1e-7; 2e-6 leaves room.
# bfloat16: activations and weights' products are rounded to 8 bits of
# mantissa in every layer; logits of std ~0.02 then differ from the float32
# reference over the same (bf16) weights by up to ~1e-3: 0.1 of a standard
# deviation at the largest, and 0.02 of one on average.
@pytest.mark.parametrize("dtype,atol,mean_tol", [
    ("float32", 2e-6, 2e-7), ("bfloat16", 4e-3, 6e-4)])
def test_prefill_then_decode_through_the_lane_cache_gives_the_reference_logits(
        dtype, atol, mean_tol):
    eng, sched = served(dtype)
    model = eng.module
    # every position's logits, not only the last: the test reads them all
    import dataclasses

    model = model.clone(config=dataclasses.replace(
        model.config, num_logits_to_keep=None))
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


def test_the_scheduler_serves_the_references_greedy_tokens_with_lanes_reused(
        fp32):
    """(a) through ``submit`` / ``run`` and (d): five requests over two
    lanes, so a lane is reused after a finished request while the other
    decodes; every served token is the reference's argmax given its
    prefix, which it is not if a lane starts from what the request before
    left in its state."""
    eng, _ = fp32
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": BUCKET})
    prompts = [prompt_of(n, seed=2).tolist() for n in (5, 30, 17, 9, 21)]
    wants = (6, 3, 9, 4, 7)
    plans = []
    telemetry_bus.subscribe(plans.append)
    try:
        rids = [sched.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, wants)]
        done = {c.request_id: c for c in sched.run().completions}
    finally:
        telemetry_bus.unsubscribe(plans.append)
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
    kv = sched.kv_cache_stats()
    m = model_config().ssm
    layers = TINY_FALCON_H1["num_hidden_layers"]
    assert plan[0]["state_bytes_per_lane"] == kv["state_bytes_per_lane"] \
        == layers * m.n_heads * m.d_head * m.d_state * 4
    assert kv["conv_bytes_per_lane"] == layers * 3 * m.conv_dim * 4
    assert kv["kv_bytes"] + kv["state_bytes"] + kv["conv_bytes"] \
        == kv["resident_bytes"]


def test_a_reused_lane_starts_from_the_spliced_state_not_the_old_one(fp32):
    """(d) on the leaves themselves: a lane that kept being stepped after
    its request ended holds garbage; the splice of the next admission
    overwrites state and tail whole, and the other lanes' are untouched."""
    eng, sched = fp32
    _, sub_a = eng._chunked_prefill(*left_padded(prompt_of(19, seed=3)))
    _, sub_b = eng._chunked_prefill(*left_padded(prompt_of(11, seed=4)))
    cache = sched._splice(sched._empty_cache(), sub_a, 1)
    cache = sched._splice(cache, sub_b, 3)
    for _ in range(3):      # every lane is stepped, live or not
        _, _, cache, _ = eng._decode_k_fn(
            eng.params, jnp.zeros((sched.slots,), jnp.int32), cache,
            jax.random.PRNGKey(0), jnp.float32(0.0), 1)
    state, tail = mixer_leaves(cache)
    want_state, want_tail = mixer_leaves(sub_b)
    assert np.abs(state[:, 1] - want_state[:, 0]).max() \
        > 0.1 * np.abs(want_state).max()                 # lane 1 moved on
    before3 = state[:, 3].copy()
    cache = sched._splice(cache, sub_b, 1)
    state, tail = mixer_leaves(cache)
    np.testing.assert_array_equal(state[:, 1], want_state[:, 0])
    np.testing.assert_array_equal(tail[:, 1], want_tail[:, 0])
    np.testing.assert_array_equal(state[:, 3], before3)


class _Stop(Exception):
    pass


def test_lanes_at_exit_hold_the_state_after_every_emitted_token(fp32):
    """``retain_lanes``: a run ended from ``poll_fn`` with a step in flight
    keeps the lane cache as that step left it; a live lane's recurrent
    state and convolution tail are the reference's after the prompt and
    every token the lane emitted (the step in flight consumed the last),
    finished lanes are not listed, and a run that drains keeps nothing."""
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
            _, state, tail = reference.hidden_and_states(
                eng.params, seq, SIZES)
            got = kept.recurrent_state(lane)
            assert got["ssm_state"].shape == state.shape
            np.testing.assert_allclose(np.asarray(got["ssm_state"]),
                                       np.asarray(state), atol=2e-6)
            np.testing.assert_allclose(np.asarray(got["conv_tail"]),
                                       np.asarray(tail), atol=2e-6)
        # the rest of the queue drains: nothing in flight, nothing kept
        sched.run()
        assert sched.lanes_at_exit is None
        sched.retain_lanes = False
        sched.submit(prompts[0], max_new_tokens=30)
        polls.clear()
        with pytest.raises(_Stop):
            sched.run(poll_fn=poll)
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
    # pads leave state and tail EXACTLY zero; what differs afterwards is
    # where the chunks' edges fall (float32 sums in another order)
    for got, want in zip(mixer_leaves(cache_p), mixer_leaves(cache_u)):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_u),
                               rtol=0, atol=1e-6)
    want = reference.logits(eng.params, prompt, SIZES, positions=[n - 1])
    np.testing.assert_allclose(np.asarray(logits_p), want, rtol=0, atol=2e-6)


def test_pads_leave_state_and_tail_exactly_zero(fp32):
    """An all-pad pass: ``silu(conv_bias)`` would otherwise reach the
    state (the conv's bias is nonzero here)."""
    eng, _ = fp32
    params = jax.tree.map(lambda x: x, eng.params)
    params["h"]["block"]["mamba"]["conv_bias"] = \
        params["h"]["block"]["mamba"]["conv_bias"] + 0.5
    ids = jnp.asarray(prompt_of(16, seed=6)[None], jnp.int32)
    _, cache = eng._prefill_fn(params, ids, jnp.zeros((1, 16), jnp.bool_))
    state, tail = mixer_leaves(cache)
    assert not state.any() and not tail.any()


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
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits_2), np.asarray(logits_1),
                               rtol=0, atol=1e-6)


def test_chunked_scan_is_the_token_recurrence():
    """``ops/ssd.py`` alone: the chunked scan from a given state equals
    ``ssd_step`` token by token, for a length that is no multiple of the
    chunk; the convolution continues from its tail."""
    rng = np.random.default_rng(0)
    B, T, H, P, N, G, chunk = 2, 21, 4, 8, 16, 2, 8
    x = rng.normal(size=(B, T, H, P)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(B, T, H)))).astype(np.float32)
    A = -np.exp(rng.normal(size=(H,))).astype(np.float32)
    Bm = rng.normal(size=(B, T, G, N)).astype(np.float32)
    Cm = rng.normal(size=(B, T, G, N)).astype(np.float32)
    D = rng.normal(size=(H,)).astype(np.float32)
    S0 = rng.normal(size=(B, H, P, N)).astype(np.float32)
    y, S = ssd.ssd_chunked_scan(S0, x, dt, A, Bm, Cm, D, chunk)
    S_t, ys = jnp.asarray(S0), []
    for t in range(T):
        y_t, S_t = ssd.ssd_step(S_t, x[:, t], dt[:, t], A, Bm[:, t],
                                Cm[:, t], D)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_t), rtol=1e-5,
                               atol=1e-5)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    u = rng.normal(size=(B, T, 6)).astype(np.float32)
    b = rng.normal(size=(6,)).astype(np.float32)
    whole, tail = ssd.causal_conv1d(u, w, b, jnp.zeros((B, 3, 6)))
    first, mid = ssd.causal_conv1d(u[:, :9], w, b, jnp.zeros((B, 3, 6)))
    second, end = ssd.causal_conv1d(u[:, 9:], w, b, mid)
    np.testing.assert_allclose(
        np.concatenate([first, second], 1), np.asarray(whole), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(end), np.asarray(tail))
    np.testing.assert_array_equal(np.asarray(tail), u[:, -3:])


def _one_token(rng, B, H, P, N, G):
    x = jnp.asarray(rng.normal(size=(B, H, P)), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(rng.normal(size=(B, H)))), jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(H,)), jnp.float32))
    Bm = jnp.asarray(rng.normal(size=(B, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, G, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("stacked", [True, False])
@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [None, 2])
def test_the_step_kernel_is_the_plain_step(stacked, state_dtype, heads):
    """``ops/pallas/ssd_step.py`` interpreted on the CPU: the kernel over
    the stacked leaf, layer a traced scalar, gives ``ssd_step``'s ``y`` and
    new state for that layer, stores it in the leaf's dtype and leaves the
    other layers bit for bit as they were; at the tile it chooses (a
    group's four heads: two grid steps a lane) and at one of two heads
    (four)."""
    rng = np.random.default_rng(5)
    L, B, H, P, N, G = 3, 2, 8, 8, 16, 2
    sdt = jnp.dtype(state_dtype)
    x, dt, A, Bm, Cm, D = tok = _one_token(rng, B, H, P, N, G)
    S = jnp.asarray(rng.normal(size=(L, B, H, P, N)), sdt)
    want_y, want_S = ssd.ssd_step(S[1].astype(jnp.float32), *tok)
    assert ssd_kernel.block_heads(H, G, P, N) == 4

    if heads is None:
        def step(S, layer):
            return ssd.ssd_step_stacked(S, layer, *tok)
    else:
        def step(S, layer):   # the kernel's own entry, its tile chosen
            S, y = ssd_kernel.ssm_step_update(
                S, layer, jnp.exp(dt * A), x * dt[..., None], Bm, Cm,
                heads=heads)
            return y + D[None, :, None] * x, S

    if stacked:
        y, S2 = jax.jit(step)(S, jnp.int32(1))
        assert S2.shape == S.shape
        for other in (0, 2):
            np.testing.assert_array_equal(np.asarray(S2[other], np.float32),
                                          np.asarray(S[other], np.float32))
        got_S = S2[1]
    else:
        y, got_S = step(S[1], None)
    assert got_S.dtype == sdt and y.dtype == jnp.float32
    tol = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), **tol)
    np.testing.assert_allclose(
        np.asarray(got_S, np.float32),
        np.asarray(want_S.astype(sdt), np.float32), **tol)


def test_the_step_kernels_tile_comes_from_the_shapes():
    """Sixteen heads of a group a tile at the 34B widths (2 MB), the whole
    group where ``N`` is small, one head where even one is over the limit;
    a tile that does not divide a group's heads is refused."""
    assert ssd_kernel.block_heads(32, 2, 128, 256) == 16
    assert ssd_kernel.block_heads(32, 1, 128, 256) == 16
    assert ssd_kernel.block_heads(4, 2, 8, 16) == 2
    assert ssd_kernel.block_heads(24, 2, 64, 128) == 12
    assert ssd_kernel.block_heads(4, 1, 1024, 1024) == 1
    rng = np.random.default_rng(6)
    B, H, P, N, G = 2, 8, 8, 16, 2
    x, dt, A, Bm, Cm, _ = _one_token(rng, B, H, P, N, G)
    S = jnp.zeros((2, B, H, P, N), jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        ssd_kernel.ssm_step_update(S, 1, jnp.exp(dt * A), x * dt[..., None],
                                   Bm, Cm, heads=3)


@pytest.mark.parametrize("case", ["tp2", "two_tokens"])
def test_sharded_heads_and_longer_passes_keep_the_plain_form(case):
    """The mixer alone, its cache made by ``init``: one token on an
    unsharded mesh traces exactly one ``ssm_step`` call; with heads sharded
    over ``tp`` (GSPMD cannot partition a Mosaic call) the same token takes
    ``ssd_step`` on the slice and gives the same output and state; a pass
    of two tokens over the cache is the chunked scan."""
    from deepspeed_tpu.models.mamba2 import Mamba2Mixer
    from deepspeed_tpu.parallel.mesh import (
        MeshTopology,
        set_default_topology,
    )

    cfg = model_config()
    mixer = Mamba2Mixer(cfg)
    T = 2 if case == "two_tokens" else 1
    u = jnp.asarray(np.random.default_rng(7).normal(
        size=(3, T, cfg.n_embd)), jnp.float32)
    variables = mixer.init(jax.random.PRNGKey(0), u, decode=True)
    state = jnp.asarray(np.random.default_rng(8).normal(
        size=variables["cache"]["ssm_state"].shape), jnp.float32)
    variables = {"params": variables["params"],
                 "cache": dict(variables["cache"], ssm_state=state)}

    def step(v, u):
        return mixer.apply(v, u, decode=True, mutable=["cache"])

    def kernel_calls():
        # a function of its own each time: a trace is cached by function,
        # and the choice is made from the topology while tracing
        return [e.params["name"] for e in all_eqns(
            jax.make_jaxpr(lambda v, u: step(v, u))(variables, u).jaxpr)
            if e.primitive.name == "pallas_call"]

    if case == "two_tokens":
        assert kernel_calls() == []
        return
    assert kernel_calls() == [ssd_kernel.KERNEL_NAME]
    out, upd = step(variables, u)
    set_default_topology(MeshTopology(tp=2, dp=-1,
                                      devices=jax.devices()[:2]))
    assert kernel_calls() == []
    plain_out, plain_upd = step(variables, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(upd["cache"]["ssm_state"]),
        np.asarray(plain_upd["cache"]["ssm_state"]), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# (e) the decode program moves no whole state leaf
# ---------------------------------------------------------------------------
def test_layer_loop_carries_state_and_tail_in_place():
    """``jit_decode_k``: the mixer's leaves cross the layer loop and the
    loop over ``k`` in the carry only, like keys and values; the layer
    loop's body holds exactly one ``ssm_step`` call, which takes the
    stacked state leaf whole, and no slice or slice-update of that leaf's
    shape (the traced jaxpr: on the CPU the kernel is interpreted, so the
    compiled text would not show it); the compiled program aliases every
    cache leaf to its output; and its scope table names the mixer's five
    scopes. ``hybrid_jit_decode_k`` of ``gpt_program_hashes.json`` was
    recorded anew on the tree that brought the kernel (PR 36, on e55e290)."""
    eng, sched = served("bfloat16", slots=3)
    cache = sched.lane_cache.shapes
    n_layer = eng.module.config.n_layer
    stacked = jax.tree.leaves(cache["h"])
    assert all(leaf.shape[0] == n_layer for leaf in stacked)
    declared = carried_leaf_shapes(cache, eng.module.config.cache_leaves)
    assert set(declared) == {scopes.SCOPE_KV_CACHE_CARRY,
                             scopes.SCOPE_SSM_STATE_CARRY}
    whole = declared[scopes.SCOPE_KV_CACHE_CARRY] \
        | declared[scopes.SCOPE_SSM_STATE_CARRY]
    m = eng.module.config.ssm
    assert (n_layer, 3, m.n_heads, m.d_head, m.d_state) in whole
    assert (3, m.n_heads, m.d_head, m.d_state) in whole
    assert (n_layer, 3, m.d_conv - 1, m.conv_dim) in whole
    args = (eng.params, jnp.zeros((3,), jnp.int32), cache,
            jax.random.PRNGKey(0), jnp.float32(0.0), 2)
    decode_k = eng._decode_k_fn.fn

    def scans(jaxpr):
        return [e for e in all_eqns(jaxpr) if e.primitive.name == "scan"]

    loops = scans(decode_k.trace(*args).jaxpr.jaxpr)
    assert sorted(e.params["length"] for e in loops) == [2, n_layer]
    for eqn in loops:
        first_x = eqn.params["num_consts"] + eqn.params["num_carry"]
        carried = {v.aval.shape for v in eqn.invars[
            eqn.params["num_consts"]:first_x]}
        crossing = {v.aval.shape for v in eqn.invars[first_x:]} | {
            v.aval.shape for v in eqn.outvars[eqn.params["num_carry"]:]}
        assert not crossing & whole, crossing & whole
        assert {leaf.shape for leaf in stacked} <= carried

    state_leaf = (n_layer, 3, m.n_heads, m.d_head, m.d_state)
    # the layer loop is the inner one (the loop over k holds it)
    layer_loop, = (e for e in loops
                   if not scans(e.params["jaxpr"].jaxpr))
    assert layer_loop.params["length"] == n_layer
    body = list(all_eqns(layer_loop.params["jaxpr"].jaxpr))
    kernels = [e for e in body if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in kernels].count(
        ssd_kernel.KERNEL_NAME) == 1
    step_call, = (e for e in kernels
                  if e.params["name"] == ssd_kernel.KERNEL_NAME)
    assert state_leaf in {v.aval.shape for v in step_call.invars}
    assert state_leaf in {v.aval.shape for v in step_call.outvars}
    for eqn in body:
        if eqn.primitive.name in ("dynamic_slice", "dynamic_update_slice"):
            assert eqn.invars[0].aval.shape != state_leaf, eqn

    text = decode_k.lower(*args).compile().as_text()
    header = text[:text.index("\n")]
    aliased = {int(n) for n in re.findall(
        r"\{[\d, ]*\}: \((\d+), ", header[header.index(
            "input_output_alias="):])}
    n_cache = len(jax.tree.leaves(cache))
    n_params = len(jax.tree.leaves(eng.params))
    assert aliased == set(range(n_params + 1, n_params + 1 + n_cache))
    _, table = scopes.instruction_scopes(text)
    found = {c for path in table.values() for c in scopes.components(path)}
    assert {"ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
            "ssm_out_proj"} <= found


def test_carry_tags_tell_a_state_leaf_from_a_kv_leaf():
    hlo = "\n".join([
        "HloModule jit_step", "",
        "ENTRY %main (p: f32[2,3,4,8,16]) -> f32[2,3,4,8,16] {",
        "  %p = f32[2,3,4,8,16]{4,3,2,1,0} parameter(0)",
        "  %copy.1 = f32[2,3,4,8,16]{4,3,2,1,0} copy(%p)",
        '  %fusion.2 = f32[2,3,4,8,16]{4,3,2,1,0} fusion(%copy.1), '
        'kind=kLoop, calls=%f, metadata={op_name="jit(step)/ssm_scan/mul"}',
        "  ROOT %copy.3 = bf16[2,3,64,2,8]{4,3,2,1,0} copy(%fusion.2)",
        "}"])
    carry = {scopes.SCOPE_KV_CACHE_CARRY: {(2, 3, 64, 2, 8)},
             scopes.SCOPE_SSM_STATE_CARRY: {(2, 3, 4, 8, 16)}}
    _, table = scopes.instruction_scopes(hlo, carry)
    assert scopes.has_scope(table["copy.1"], "ssm_state_carry")
    assert scopes.has_scope(table["copy.3"], "kv_cache_carry")
    assert scopes.has_scope(table["fusion.2"], "ssm_scan")
    assert not scopes.has_scope(table["fusion.2"], "ssm_state_carry")
    # shapes alone are KV leaves', as before
    _, table = scopes.instruction_scopes(hlo, {(2, 3, 4, 8, 16)})
    assert scopes.has_scope(table["copy.1"], "kv_cache_carry")


# ---------------------------------------------------------------------------
# (f) what cannot work refuses by name
# ---------------------------------------------------------------------------
def test_speculation_and_prefix_cache_refuse_a_model_with_state(fp32):
    eng, _ = fp32
    with pytest.raises(serving.RecurrentStateError, match="prefix_cache"):
        serving.build_serving(eng, {"slots": 2, "prefix_cache": True})
    with pytest.raises(serving.RecurrentStateError, match="draft_engine"):
        serving.build_serving(eng, {"slots": 2, "spec_k": 2},
                              draft_engine=eng)
    # and a model without state is served with both, as before
    from unit.simple_model import tiny_gpt_config

    plain = deepspeed_tpu.init_inference(
        GPT(tiny_gpt_config(n_embd=32, n_layer=1, vocab_size=64)),
        dtype="fp32")
    serving.build_serving(plain, {"slots": 2, "prefix_cache": True,
                                  "spec_k": 2}, draft_engine=plain)


# ---------------------------------------------------------------------------
# (g) the GPT programs do not move
# ---------------------------------------------------------------------------
def test_lowered_gpt_programs_hash_as_on_the_parent():
    """``lower(...).as_text()`` of the serving programs and the train steps
    of the configurations the benchmark had before the hybrid block, at a
    small size, and of the tiny hybrid configuration's own three serving
    programs, and the tiny attention-free configuration's two: byte for
    byte what the commits that recorded them lower
    (``tests/unit/data/gpt_program_hashes.json``; ``gpt_program_hashes.py``
    says which commit recorded which). ``hybrid_jit_decode_k`` alone was
    recorded anew on PR 36's tree (on e55e290), whose kernel ``ssm_step``
    it now holds; every other entry stands, ``hybrid_jit_prefill[32]`` and
    ``hybrid_jit_splice`` among them: nothing else moved."""
    from unit import gpt_program_hashes

    with open(os.path.join(HERE, "data", "gpt_program_hashes.json"),
              encoding="utf-8") as f:
        want = json.load(f)
    got = gpt_program_hashes.all_hashes()
    assert got == want, {k: (got.get(k), want[k]) for k in want
                         if got.get(k) != want[k]}


def test_the_hash_tells_a_hybrid_program_from_a_plain_one():
    """The control: one multiplier moves a program's text."""
    import dataclasses

    from unit.simple_model import tiny_gpt_config

    base = tiny_gpt_config(n_embd=32, n_layer=1, vocab_size=64)
    ids = jnp.zeros((1, 8), jnp.int32)

    def text(cfg):
        model = GPT(cfg)
        params = jax.eval_shape(
            lambda: model.init({"params": jax.random.PRNGKey(0)}, ids))
        return jax.jit(lambda p: model.apply(p, ids)).lower(params).as_text()

    assert text(base) == text(dataclasses.replace(base))
    assert text(base) != text(dataclasses.replace(base, key_multiplier=0.5))


# ---------------------------------------------------------------------------
# (h) the equations are the published code's
# ---------------------------------------------------------------------------
def hf_to_program(sd, n_layer):
    """The program's parameter tree from a ``FalconH1ForCausalLM``
    ``state_dict`` (numpy)."""
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
        "ln_f": {"scale": jnp.asarray(sd["model.final_layernorm.weight"])},
        "h": {"block": {
            "ln_1": {"scale": stack("input_layernorm.weight")},
            "ln_2": {"scale": stack("pre_ff_layernorm.weight")},
            "attn": {"c_attn": {"kernel": qkv},
                     "c_proj": {"kernel": stack("self_attn.o_proj.weight",
                                                t)}},
            "mlp": {
                "c_fc": {"kernel": stack("feed_forward.up_proj.weight", t)},
                "c_gate": {"kernel": stack("feed_forward.gate_proj.weight",
                                           t)},
                "c_proj": {"kernel": stack("feed_forward.down_proj.weight",
                                           t)}},
            "mamba": {
                "in_proj": {"kernel": stack("mamba.in_proj.weight", t)},
                "out_proj": {"kernel": stack("mamba.out_proj.weight", t)},
                "conv_kernel": stack("mamba.conv1d.weight",
                                     lambda a: a[:, 0, :].T),
                "conv_bias": stack("mamba.conv1d.bias"),
                "A_log": stack("mamba.A_log"), "D": stack("mamba.D"),
                "dt_bias": stack("mamba.dt_bias"),
                "norm_scale": stack("mamba.norm.weight")}}}}


def test_reference_and_program_agree_with_the_published_modeling_code():
    """A tiny ``FalconH1ForCausalLM`` on CPU torch, its weights copied into
    the program's tree: the plain reference, and the program's own
    forward, give its logits. Skipped where ``transformers`` has no
    ``falcon_h1``."""
    torch = pytest.importorskip("torch")
    try:
        from transformers import FalconH1Config, FalconH1ForCausalLM
    except ImportError:
        pytest.skip("transformers has no falcon_h1")
    keys = {k: v for k, v in TINY_FALCON_H1.items() if k not in (
        "name", "source", "builders", "model", "serve", "reduced")}
    torch.manual_seed(0)
    hf = FalconH1ForCausalLM(FalconH1Config(**keys)).eval().float()
    with torch.no_grad():      # off their constant initial values
        for name, p in hf.named_parameters():
            if any(k in name for k in ("A_log", "dt_bias", ".D",
                                       "norm.weight", "layernorm.weight",
                                       "conv1d.bias")):
                p.add_(0.3 * torch.randn_like(p))
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    params = hf_to_program(sd, keys["num_hidden_layers"])
    ids = prompt_of(21, seed=8)
    with torch.no_grad():
        want = hf(torch.tensor(ids)[None], logits_to_keep=0).logits[0].numpy()
    ref = reference.logits(params, ids, SIZES)
    np.testing.assert_allclose(ref, want, rtol=0, atol=1e-6)
    model = GPT(model_config())
    got = model.apply({"params": params}, jnp.asarray(ids[None], jnp.int32))
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=0, atol=1e-6)
    assert np.abs(want).max() > 0.01          # not a comparison of zeros
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == num_params(model.config) \
        == sum(p.numel() for p in hf.parameters())
