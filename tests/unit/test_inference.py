"""Inference engine + KV-cache decode tests
(reference tests/unit/inference/test_inference.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig


def _cfg(**kw):
    base = dict(vocab_size=128, n_positions=64, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32, scan_layers=True)
    base.update(kw)
    return GPTConfig(**base)


_WINDOW = {"mode": "local_sliding_window", "block": 16,
           "num_sliding_window_blocks": 3}

# name -> (GPTConfig overrides, sparse layout, prompt length, its bucket,
# decode steps)
_DECODE_VARIANTS = {
    "dense": ({}, None, 8, 8, 7),
    "gqa": (dict(n_kv_head=2), None, 8, 8, 7),
    "rotary": (dict(rotary=True, learned_positions=False), None, 8, 8, 7),
    "alibi": (dict(alibi=True, learned_positions=False), None, 8, 8, 7),
    # learned positions count VALID predecessors: 5 tokens left-padded to 8
    "wpe_ragged": ({}, None, 5, 8, 7),
    "int8_kv": (dict(kv_cache_dtype="int8", rotary=True,
                     learned_positions=False), None, 8, 8, 7),
    # a ring of 32 slots, filled by the prompt and wrapped by the decode;
    # 32 + 16 generated = 48 = 3 layout blocks, the least the training
    # sparse forward (the reference) takes
    "ring": (dict(n_positions=256), _WINDOW, 32, 32, 15),
    "unscanned": (dict(scan_layers=False), None, 8, 8, 7),
}


def _decode_model(variant, **more):
    kw, sparse, n_prompt, bucket, k = _DECODE_VARIANTS[variant]
    model = GPT(_cfg(**kw, **more))
    if sparse is not None:
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import apply_sparse_attention

        model = apply_sparse_attention(model, sparse)
    return model, n_prompt, bucket, k


class TestKVCacheDecode:
    @pytest.mark.parametrize("variant", sorted(_DECODE_VARIANTS))
    def test_decode_matches_full_forward(self, variant):
        """``k`` steps of ``jit_decode_k`` after a prefill give the tokens
        and the last-position logits of the cache-free forward of the
        growing sequence; and a ``[1, ...]`` prefilled cache spliced into
        lane 2 of a 4-lane cache decodes there as it does alone."""
        from deepspeed_tpu.inference.scheduler import \
            ContinuousBatchingScheduler

        model, n_prompt, bucket, k = _decode_model(variant)
        eng = deepspeed_tpu.init_inference(model, dtype="fp32", seed=0)
        sched = ContinuousBatchingScheduler(eng, slots=4,
                                            prompt_bucket=bucket)
        sched._ensure_compiled()
        rng = np.random.RandomState(0)
        prompt = rng.randint(1, 128, size=n_prompt).astype(np.int32)
        ids = np.zeros((1, bucket), np.int32)
        mask = np.zeros((1, bucket), bool)
        ids[0, bucket - n_prompt:] = prompt      # left-padded, as admitted
        mask[0, bucket - n_prompt:] = True
        logits, sub = eng._prefill_fn(eng.params, jnp.asarray(ids),
                                      jnp.asarray(mask))
        tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        key, temp = jax.random.PRNGKey(0), jnp.float32(0.0)

        lanes = sched._splice(sched._empty_cache(), sched._copy_tree(sub), 2)
        toks, last, cache, _ = eng._decode_k_fn(
            eng.params, tok0, sub, key, temp, k)
        got = np.concatenate([np.asarray(tok0), np.asarray(toks)[0]])

        # the cache-free forward of prompt + generated tokens: under
        # causal attention its position t is the forward of the sequence
        # grown to t + 1 tokens
        seq = np.concatenate([prompt, got])
        full = np.asarray(model.apply(
            {"params": eng.params}, jnp.asarray(seq[None]),
            deterministic=True))[0]
        ref = full[n_prompt - 1:]                # [k + 2, V]
        step, _ = model.apply(
            {"params": eng.params, "cache": cache}, last[:, None],
            deterministic=True, decode=True, mutable=["cache"])
        step = np.asarray(step)[0, -1]
        if variant == "int8_kv":
            # quantization error is real and bounded (the envelope of
            # test_serving_disagg's int8 parity): a token may differ only
            # where the reference's top-2 margin is inside it
            err = np.abs(step - ref[-1]).max()
            assert err < 0.05 * np.abs(ref).max()
            top2 = np.sort(ref[:-1], axis=-1)
            flips = ref[:-1].argmax(-1) != got
            assert ((top2[:, -1] - top2[:, -2])[flips] < 2 * err).all()
        else:
            np.testing.assert_array_equal(got, ref[:-1].argmax(-1))
            np.testing.assert_allclose(step, ref[-1], atol=2e-4, rtol=1e-3)

        tok4 = jnp.zeros((4,), jnp.int32).at[2].set(tok0[0])
        toks4, _, _, _ = eng._decode_k_fn(
            eng.params, tok4, lanes, key, temp, k)
        np.testing.assert_array_equal(np.asarray(toks4)[2], got[1:])

    @pytest.mark.parametrize("variant", ["dense_bf16", "int8_kv", "ring"])
    def test_layer_loop_carries_the_cache_in_place(self, variant):
        """``jit_decode_k`` of a scanned model: a cache leaf crosses the
        layer loop (and the loop over ``k``) in the carry only, never as a
        scanned input or a stacked output, which would slice each layer's
        whole leaf out and write it back; and the compiled program
        aliases every cache leaf to its output."""
        import re

        from deepspeed_tpu.inference.engine import carried_leaf_shapes
        from deepspeed_tpu.inference.scheduler import \
            ContinuousBatchingScheduler

        model, _, bucket, _ = _decode_model(
            "dense" if variant == "dense_bf16" else variant, n_layer=3)
        eng = deepspeed_tpu.init_inference(
            model, dtype="bf16" if variant == "dense_bf16" else "fp32")
        sched = ContinuousBatchingScheduler(eng, slots=3,
                                            prompt_bucket=bucket)
        sched._ensure_compiled()
        cache = sched.lane_cache.shapes
        n_layer = model.config.n_layer
        stacked = jax.tree.leaves(cache["h"])
        assert all(leaf.shape[0] == n_layer for leaf in stacked)
        whole = carried_leaf_shapes(
            cache, model.config.cache_leaves)["kv_cache_carry"]
        args = (eng.params, jnp.zeros((3,), jnp.int32), cache,
                jax.random.PRNGKey(0), jnp.float32(0.0), 2)
        decode_k = eng._decode_k_fn.fn

        def scans(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "scan":
                    yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from scans(sub)

        loops = list(scans(decode_k.trace(*args).jaxpr.jaxpr))
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
        params = re.findall(
            r"= (\w+\[[\d,]*\])\S* parameter\((\d+)\)",
            text[text.index("\nENTRY "):])
        aliased_shapes = sorted(s for s, n in params if int(n) in aliased)
        # every donated cache leaf is an aliased parameter, and nothing
        # else is
        want = sorted("%s[%s]" % (
            {"bfloat16": "bf16", "float32": "f32", "int32": "s32",
             "int8": "s8", "bool": "pred"}[leaf.dtype.name],
            ",".join(map(str, leaf.shape)))
            for leaf in jax.tree.leaves(cache))
        assert aliased_shapes == want


class TestInferenceEngine:
    def test_forward_logits(self):
        engine = deepspeed_tpu.init_inference(GPT(_cfg()), mp_size=1)
        ids = np.random.RandomState(0).randint(0, 128, size=(2, 8))
        out = engine(jnp.asarray(ids, jnp.int32))
        assert out.shape == (2, 8, 128)
        assert bool(jnp.isfinite(out).all())

    @pytest.mark.slow
    def test_greedy_generate_matches_argmax_rollout(self):
        cfg = _cfg()
        model = GPT(cfg)
        engine = deepspeed_tpu.init_inference(model, mp_size=1)
        rng = np.random.RandomState(1)
        ids = jnp.asarray(rng.randint(0, 128, size=(1, 5)), jnp.int32)
        toks = engine.generate(ids, max_new_tokens=4, temperature=0.0)
        assert toks.shape == (1, 4)

        # reference rollout: argmax over the full forward each step
        params = engine.params
        cur = ids
        expect = []
        for _ in range(4):
            logits = model.apply({"params": params}, cur, deterministic=True)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            expect.append(int(nxt[0]))
            cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
        assert [int(t) for t in np.asarray(toks)[0]] == expect

    def test_tensor_parallel_inference(self, eight_devices):
        engine = deepspeed_tpu.init_inference(
            GPT(_cfg(n_embd=64, n_head=4)), mp_size=4, dtype="bf16")
        ids = np.random.RandomState(2).randint(0, 128, size=(2, 8))
        out = engine(jnp.asarray(ids, jnp.int32))
        assert out.shape == (2, 8, 128)
        assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
        specs = [str(x.sharding.spec) for x in jax.tree.leaves(engine.params)]
        assert any("tp" in s for s in specs), specs

    @pytest.mark.slow
    def test_checkpoint_load(self, tmp_path):
        cfg = _cfg()
        model = GPT(cfg)
        rng = np.random.RandomState(3)
        ids = rng.randint(0, 128, size=(4, 16)).astype(np.int32)
        ds_config = {
            "train_micro_batch_size_per_gpu": 4,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "steps_per_print": 10 ** 9,
        }
        from deepspeed_tpu.parallel.mesh import MeshTopology

        tengine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=ds_config,
            topology=MeshTopology(dp=1, devices=jax.devices()[:1]))
        tengine.forward({"input_ids": ids, "labels": ids})
        tengine.backward()
        tengine.step()
        tengine.save_checkpoint(str(tmp_path), tag="t")

        ckpt = str(tmp_path / "t" / "mp_rank_00_model_states.msgpack")
        iengine = deepspeed_tpu.init_inference(model, checkpoint=ckpt)
        out_i = iengine(jnp.asarray(ids, jnp.int32))
        out_t = model.apply(
            {"params": jax.device_get(tengine.params)},
            jnp.asarray(ids, jnp.int32), deterministic=True)
        np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_t),
                                   atol=1e-5)


class TestRaggedGenerate:
    """Padding-mask-aware KV-cache decode (reference inference_context.h
    masked decode): a ragged batch generates exactly what each prompt
    generates alone."""

    @pytest.mark.slow
    @pytest.mark.parametrize("variant", ["wpe", "rotary", "alibi"])
    def test_ragged_matches_per_sequence(self, variant):
        kw = dict(wpe={},
                  rotary=dict(rotary=True, learned_positions=False),
                  alibi=dict(alibi=True, learned_positions=False))[variant]
        cfg = _cfg(**kw)
        model = GPT(cfg)
        rng = np.random.RandomState(4)
        lens = [5, 9, 3]
        prompts = [rng.randint(0, 128, size=(1, n)).astype(np.int32)
                   for n in lens]

        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        singles = [np.asarray(engine.generate(jnp.asarray(p),
                                              max_new_tokens=6))
                   for p in prompts]

        # right-padded ragged batch + mask (generate left-aligns itself)
        T = max(lens)
        ids = np.zeros((len(lens), T), np.int32)
        mask = np.zeros((len(lens), T), bool)
        for b, p in enumerate(prompts):
            ids[b, :lens[b]] = p[0]
            mask[b, :lens[b]] = True
        batched = np.asarray(engine.generate(
            jnp.asarray(ids), max_new_tokens=6,
            attention_mask=jnp.asarray(mask)))

        for b in range(len(lens)):
            np.testing.assert_array_equal(batched[b], singles[b][0],
                                          err_msg=f"seq {b} ({variant})")

    def test_equal_length_mask_is_noop(self):
        cfg = _cfg()
        model = GPT(cfg)
        rng = np.random.RandomState(5)
        ids = rng.randint(0, 128, size=(2, 8)).astype(np.int32)
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        plain = np.asarray(engine.generate(jnp.asarray(ids),
                                           max_new_tokens=5))
        masked = np.asarray(engine.generate(
            jnp.asarray(ids), max_new_tokens=5,
            attention_mask=jnp.ones_like(ids, dtype=bool)))
        np.testing.assert_array_equal(plain, masked)


class TestInt8Serving:
    """True weight-only int8 (reference int8 GEMM inference variants,
    csrc/transformer/inference/csrc/pt_binding.cpp:1535): kernels STORED
    int8 + per-column scales, dequantized inside the compiled step."""

    def test_params_stored_int8_and_quality(self):
        import jax.numpy as jnp

        cfg = _cfg()
        rng = np.random.RandomState(3)
        ids = rng.randint(0, 128, size=(2, 8)).astype(np.int32)

        ref = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=0)
        ref_logits = np.asarray(ref.forward(jnp.asarray(ids)),
                                dtype=np.float32)

        eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="int8", seed=0)
        q_logits = np.asarray(eng.forward(jnp.asarray(ids)),
                              dtype=np.float32)

        # the stored tree really holds int8 kernels in the {q, scale}
        # layout (model-level quantized_weights; dequant happens inside
        # the layer scan)
        from deepspeed_tpu.utils.tree import path_str
        flat, _ = jax.tree_util.tree_flatten_with_path(eng.params)
        q_dtypes = {path_str(p): x.dtype for p, x in flat
                    if path_str(p).endswith("kernel/q")}
        assert q_dtypes, "no quantized kernels found"
        assert all(dt == jnp.int8 for dt in q_dtypes.values()), q_dtypes
        assert not any(path_str(p).endswith("kernel") for p, _ in flat), \
            "dense kernel leaves remain alongside the quantized layout"
        assert eng._model_quantized

        # int8 quality: close to the fp32 logits, but not identical
        mse = float(np.mean((q_logits - ref_logits) ** 2))
        ref_var = float(np.var(ref_logits))
        assert mse < 0.01 * ref_var, (mse, ref_var)
        assert mse > 0.0

    def test_int8_generation_runs(self):
        cfg = _cfg()
        rng = np.random.RandomState(4)
        ids = rng.randint(0, 128, size=(2, 8)).astype(np.int32)
        eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="int8", seed=0)
        out = np.asarray(eng.generate(jnp.asarray(ids), max_new_tokens=6))
        assert out.shape == (2, 6)  # generate returns the NEW tokens

    @pytest.mark.xfail(strict=False, reason=(
        "int8 x tensor-parallel dequant drift under this jaxlib: tp=2 "
        "logits diverge from tp=1 (reproduces at seed HEAD)"))
    def test_int8_composes_with_tensor_parallel(self, eight_devices):
        """init_inference(dtype=int8, tp=2) — the reference's first-class
        path (inference/engine.py:506 _convert_to_dtype with mp_size>1,
        GroupQuantizer post-slice at replace_module.py:139). The {q, scale}
        leaves shard via the derived specs; logits match bf16 tp=2 within
        the committed int8 MSE bound and int8 tp=1 near-exactly."""
        cfg = _cfg(n_embd=64, n_head=4)
        rng = np.random.RandomState(6)
        ids = jnp.asarray(rng.randint(0, 128, size=(2, 8)), jnp.int32)

        ref = deepspeed_tpu.init_inference(GPT(cfg), mp_size=2,
                                           dtype="bf16", seed=0)
        ref_logits = np.asarray(ref.forward(ids), dtype=np.float32)

        from deepspeed_tpu.parallel import mesh as mesh_mod
        mesh_mod.reset_default_topology()
        one = deepspeed_tpu.init_inference(GPT(cfg), mp_size=1,
                                           dtype="int8", seed=0)
        one_logits = np.asarray(one.forward(ids), dtype=np.float32)

        mesh_mod.reset_default_topology()
        eng = deepspeed_tpu.init_inference(GPT(cfg), mp_size=2,
                                           dtype="int8", seed=0)
        assert eng._model_quantized
        q_logits = np.asarray(eng.forward(ids), dtype=np.float32)

        # the int8 storage is genuinely tensor-parallel: q leaves carry tp
        # specs, and scales of column-parallel kernels shard with them
        from deepspeed_tpu.utils.tree import path_str
        flat, _ = jax.tree_util.tree_flatten_with_path(eng.params)
        by_path = {path_str(p): x for p, x in flat}
        q_specs = {p: str(x.sharding.spec) for p, x in by_path.items()
                   if p.endswith("kernel/q")}
        assert q_specs and any("tp" in s for s in q_specs.values()), q_specs
        col_scales = {p: str(x.sharding.spec) for p, x in by_path.items()
                      if p.endswith("c_attn/kernel/scale")}
        assert col_scales and all("tp" in s for s in col_scales.values()), \
            col_scales
        row_scales = {p: str(x.sharding.spec) for p, x in by_path.items()
                      if p.endswith("c_proj/kernel/scale")}
        assert row_scales and not any("tp" in s
                                      for s in row_scales.values()), \
            row_scales

        # same quantized math as tp=1 (psum order is the only difference)
        np.testing.assert_allclose(q_logits, one_logits, atol=5e-2,
                                   rtol=1e-2)
        # and the committed quality bound vs the bf16 tp=2 logits
        mse = float(np.mean((q_logits - ref_logits) ** 2))
        ref_var = float(np.var(ref_logits))
        assert mse < 0.01 * ref_var, (mse, ref_var)

    def test_int8_tp_generation_runs(self, eight_devices):
        cfg = _cfg(n_embd=64, n_head=4)
        rng = np.random.RandomState(7)
        ids = rng.randint(0, 128, size=(2, 8)).astype(np.int32)
        eng = deepspeed_tpu.init_inference(GPT(cfg), mp_size=2,
                                           dtype="int8", seed=0)
        out = np.asarray(eng.generate(jnp.asarray(ids), max_new_tokens=6))
        assert out.shape == (2, 6)

    def test_small_model_int8_warns_once(self, caplog):
        """dtype=int8 below the measured win threshold says so once
        instead of silently serving slower."""
        import logging

        from deepspeed_tpu.utils.logging import _warn_once_cached

        _warn_once_cached.cache_clear()
        pkg_logger = logging.getLogger("deepspeed_tpu")
        pkg_logger.propagate = True  # caplog listens on root
        try:
            with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
                deepspeed_tpu.init_inference(GPT(_cfg()), dtype="int8",
                                             seed=0)
        finally:
            pkg_logger.propagate = False
        assert any("dispatch-bound" in r.message and "int8" in r.message
                   for r in caplog.records), caplog.records


class TestExpertParallelInference:
    """Expert-parallel serving (reference DeepSpeedMoEInference,
    moe_inference.py:206 + inference/engine.py:227 EP groups): expert
    stacks shard over the ep mesh axis instead of replicating."""

    def _moe_cfg(self):
        # Mixtral-shaped toy: top-2 gated (SwiGLU) experts, rmsnorm, rotary
        return _cfg(n_embd=64, n_head=4, norm="rmsnorm", rotary=True,
                    learned_positions=False, gated_mlp=True,
                    moe_num_experts=8, moe_top_k=2, moe_gated_experts=True,
                    moe_capacity_factor=4.0, moe_eval_capacity_factor=4.0)

    @pytest.mark.xfail(strict=False, reason=(
        "expert-parallel routing drift under this jaxlib: ep=4 logits "
        "diverge from ep=1 beyond tolerance (reproduces at seed HEAD)"))
    def test_ep_sharded_serving_matches_ep1(self, eight_devices):
        cfg = self._moe_cfg()
        rng = np.random.RandomState(9)
        ids = jnp.asarray(rng.randint(0, 128, size=(2, 8)), jnp.int32)

        ref = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=0)
        ref_logits = np.asarray(ref.forward(ids), dtype=np.float32)

        from deepspeed_tpu.parallel import mesh as mesh_mod
        mesh_mod.reset_default_topology()
        eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=0,
                                           ep_size=4)
        assert eng.topology.size("ep") == 4
        logits = np.asarray(eng.forward(ids), dtype=np.float32)
        np.testing.assert_allclose(logits, ref_logits, atol=2e-4, rtol=1e-3)

        # expert weights are genuinely sharded: each device holds 1/4 of
        # every expert stack (8 experts -> 2 per device at ep=4)
        from deepspeed_tpu.utils.tree import path_str
        flat, _ = jax.tree_util.tree_flatten_with_path(eng.params)
        expert_leaves = [(path_str(p), x) for p, x in flat
                         if "experts/" in path_str(p)]
        assert expert_leaves
        for p, x in expert_leaves:
            global_bytes = x.size * x.dtype.itemsize
            shard = x.sharding.shard_shape(x.shape)
            local_bytes = int(np.prod(shard)) * x.dtype.itemsize
            assert local_bytes * 4 == global_bytes, (p, x.shape, shard)

        # greedy parity vs the replicated engine
        mesh_mod.reset_default_topology()
        ref2 = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=0)
        ref_toks = np.asarray(ref2.generate(ids, max_new_tokens=5))
        mesh_mod.reset_default_topology()
        eng2 = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=0,
                                            ep_size=4)
        ep_toks = np.asarray(eng2.generate(ids, max_new_tokens=5))
        np.testing.assert_array_equal(ep_toks, ref_toks)

    def test_ep_hlo_has_expert_collectives(self, eight_devices):
        """With the serving batch sharded over the data axes (the engine's
        _place_batch) and experts sharded over ep, the compiled forward
        must move tokens across the ep axis — the reference's _AllToAll
        dispatch/combine (sharded_moe.py:89). Here GSPMD emits the
        collectives from the sharding constraints and is free to choose
        the implementation (a literal all-to-all, or the equivalent
        all-gather + reduce pair it prefers at small shapes); the
        architectural property is cross-ep replica groups."""
        import re

        cfg = self._moe_cfg()
        rng = np.random.RandomState(10)
        # batch 8 divides dp(2) x ep(4), so _place_batch shards it
        ids = jnp.asarray(rng.randint(0, 128, size=(8, 8)), jnp.int32)
        eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=0,
                                           ep_size=4)
        eng.forward(ids)  # materialize params on the ep mesh
        model = eng.module
        placed = eng._place_batch(ids)
        assert "ep" in str(placed.sharding.spec)

        def fwd(params, ids):
            return model.apply({"params": params}, ids, deterministic=True)

        hlo = jax.jit(fwd).lower(eng.params, placed).compile().as_text()
        colls = [l for l in hlo.splitlines()
                 if re.search(r"all-to-all|all-gather|all-reduce"
                              r"|reduce-scatter", l)
                 and "replica_groups" in l]
        assert colls, "no collectives in the EP serving HLO"
        # mesh axis order is (pp, dp, fsdp, ep, sp, tp): dp=2 x ep=4 gives
        # ep peer groups {0,1,2,3} / {4,5,6,7} — consecutive ids, i.e. the
        # iota form [2,4]<=[8] (a pure-dp group {0,4} would carry a
        # transpose, [4,2]<=[8]T(...) or an explicit strided list)
        def crosses_ep(line):
            if re.search(r"replica_groups=\[\d+,4\]<=\[8\](?!T)", line):
                return True
            m = re.search(r"replica_groups=\{\{([^}]+)\}", line)
            if m:
                ids_in = {int(t) for t in re.findall(r"\d+", m.group(1))}
                return any({b, b + 3} <= ids_in for b in (0, 4))
            return False

        assert any(crosses_ep(l) for l in colls), colls[:6]


def _cached_key_slot_dims(model, ids):
    """Slot-axis size of every ``cached_key`` decode buffer (shape probe
    via eval_shape; the slots axis is -3: [*, B, S, Hkv, D], with a
    leading layer axis under scan_layers)."""
    vs = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids,
                           deterministic=True, decode=True))
    dims = [v.shape[-3] for p, v in
            jax.tree_util.tree_flatten_with_path(vs["cache"])[0]
            if "cached_key" in "/".join(str(k) for k in p)]
    assert dims, "no cached_key buffers in the decode cache"
    return dims


class TestSparseRingKVCache:
    """Layout-aware KV cache: window(+leading-global) sparse layouts
    decode from a block-granular ring holding only the attendable slots,
    reproducing the TRAINING block-sparse math exactly (the dense cache
    cannot — it sees strictly more keys than a window-trained model)."""

    def _sparse_model(self, sparse, n_positions=256, **kw):
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import apply_sparse_attention

        return apply_sparse_attention(
            GPT(_cfg(n_positions=n_positions, **kw)), sparse)

    @pytest.mark.parametrize("layout", ["window", "longformer",
                                        "window_rotary", "window_gqa"])
    def test_decode_matches_training_sparse_forward(self, layout):
        """Prefill + stepwise ring decode must equal the TRAINING sparse
        forward at every position — across several ring wraparounds —
        including under rotary positions (baked at cache-write) and
        grouped-query attention (un-repeated KV ring)."""
        sparse = ({"mode": "bslongformer", "block": 16,
                   "num_sliding_window_blocks": 3,
                   "attention": "unidirectional"}
                  if layout == "longformer" else
                  {"mode": "local_sliding_window", "block": 16,
                   "num_sliding_window_blocks": 3})
        kw = {}
        if layout == "window_rotary":
            kw = dict(rotary=True, learned_positions=False)
        elif layout == "window_gqa":
            kw = dict(n_kv_head=2)
        model = self._sparse_model(sparse, **kw)
        rng = np.random.RandomState(11)
        T = 96  # block 16, w=1 -> ring 32 slots: several wraparounds
        ids = jnp.asarray(rng.randint(0, 128, size=(2, T)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids,
                            deterministic=True)["params"]

        full = model.apply({"params": params}, ids, deterministic=True)

        # ring is 32 (+16 globals for longformer) slots — prefill 24
        # tokens (< ring) so every prefill logit is exact, then decode
        # one-by-one deep past the window. ONE jitted step program
        # (replayed per position) — eager per-position applies build
        # enough compile-cache pressure to destabilize a full-suite run.
        pre_t = 24

        @jax.jit
        def prefill(params, chunk):
            return model.apply({"params": params}, chunk,
                               deterministic=True, decode=True,
                               mutable=["cache"])

        @jax.jit
        def step_fn(params, cache, tok):
            return model.apply({"params": params, "cache": cache}, tok,
                               deterministic=True, decode=True,
                               mutable=["cache"])

        pre, cache = prefill(params, ids[:, :pre_t])
        cache = cache["cache"]
        np.testing.assert_allclose(
            np.asarray(pre), np.asarray(full[:, :pre_t]),
            atol=2e-4, rtol=1e-3)
        for t in range(pre_t, T):
            step, cache = step_fn(params, cache, ids[:, t:t + 1])
            cache = cache["cache"]
            np.testing.assert_allclose(
                np.asarray(step[:, 0]), np.asarray(full[:, t]),
                atol=2e-4, rtol=1e-3, err_msg=f"position {t} ({layout})")

    def test_cache_is_ring_sized(self):
        model = self._sparse_model(
            {"mode": "local_sliding_window", "block": 16,
             "num_sliding_window_blocks": 3}, n_positions=1024)
        # ring = (w+1)*block = 32 slots, not n_positions=1024: 32x less
        # cache memory
        assert all(d == 32 for d in _cached_key_slot_dims(
            model, jnp.zeros((1, 8), jnp.int32)))

    @pytest.mark.slow
    def test_ragged_ring_decode_matches_solo(self):
        model = self._sparse_model(
            {"mode": "local_sliding_window", "block": 16,
             "num_sliding_window_blocks": 3})
        import deepspeed_tpu

        eng = deepspeed_tpu.init_inference(model, dtype="fp32", seed=0)
        rng = np.random.RandomState(12)
        # block-divisible prompt lengths of >= 3 blocks: the engine's
        # param-shape init traces the TRAINING sparse forward, whose
        # layout needs T % block == 0 and enough blocks for the window
        # (serving callers pad via pad_to_block_size)
        lens = [48, 64]
        prompts = [rng.randint(0, 128, size=(1, n)).astype(np.int32)
                   for n in lens]
        singles = [np.asarray(eng.generate(jnp.asarray(p),
                                           max_new_tokens=40))
                   for p in prompts]
        T = max(lens)
        ids = np.zeros((2, T), np.int32)
        mask = np.zeros((2, T), bool)
        for b, p in enumerate(prompts):
            ids[b, :lens[b]] = p[0]
            mask[b, :lens[b]] = True
        batched = np.asarray(eng.generate(
            jnp.asarray(ids), max_new_tokens=40,
            attention_mask=jnp.asarray(mask)))
        for b in range(2):
            np.testing.assert_array_equal(batched[b], singles[b][0],
                                          err_msg=f"seq {b}")

    def test_bigbird_falls_back_dense_with_warning(self, caplog):
        import logging

        import deepspeed_tpu
        from deepspeed_tpu.utils.logging import _warn_once_cached

        model = self._sparse_model(
            {"mode": "bigbird", "block": 16,
             "attention": "unidirectional"})
        eng = deepspeed_tpu.init_inference(model, dtype="fp32", seed=0)
        # 48 = 3 blocks: bigbird's window needs >= 3 layout blocks
        ids = jnp.asarray(
            np.random.RandomState(13).randint(0, 128, size=(1, 48)),
            jnp.int32)
        _warn_once_cached.cache_clear()
        pkg_logger = logging.getLogger("deepspeed_tpu")
        pkg_logger.propagate = True
        try:
            with caplog.at_level(logging.WARNING,
                                 logger="deepspeed_tpu"):
                out = eng.generate(ids, max_new_tokens=3)
        finally:
            pkg_logger.propagate = False
        assert out.shape == (1, 3)
        assert any("DENSE" in r.message for r in caplog.records)
        # and the dense cache really is full-length (no ring engaged)
        assert all(d == eng.module.config.n_positions
                   for d in _cached_key_slot_dims(eng.module, ids))

    @pytest.mark.xfail(strict=False, reason=(
        "intermittent int8 dequant drift under this jaxlib (same family "
        "as the int8 x tp divergence; passes on most runs)"))
    @pytest.mark.slow
    def test_int8_composes_with_ring_cache(self):
        """Weight-only int8 serving and the ring KV cache engage in one
        model: the quantized block's in-scan dequant runs inside the ring
        decode branch, and generation matches the fp32 ring engine's
        greedy tokens (int8 error is far below argmax flips on this toy)."""
        import deepspeed_tpu

        model = self._sparse_model(
            {"mode": "local_sliding_window", "block": 16,
             "num_sliding_window_blocks": 3})
        rng = np.random.RandomState(14)
        ids = jnp.asarray(rng.randint(0, 128, size=(1, 48)), jnp.int32)

        ref = deepspeed_tpu.init_inference(model, dtype="fp32", seed=0)
        ref_toks = np.asarray(ref.generate(ids, max_new_tokens=24))

        from deepspeed_tpu.parallel import mesh as mesh_mod
        mesh_mod.reset_default_topology()
        eng = deepspeed_tpu.init_inference(model, dtype="int8", seed=0)
        assert eng._model_quantized
        toks = np.asarray(eng.generate(ids, max_new_tokens=24))
        # int8 stored weights + ring cache really engaged
        from deepspeed_tpu.utils.tree import path_str
        flat, _ = jax.tree_util.tree_flatten_with_path(eng.params)
        assert any(path_str(p).endswith("kernel/q") for p, _ in flat)
        # cache shapes probed on the dense twin (a quantized model cannot
        # run init through its map_variables transform); the ring layout
        # is identical
        import dataclasses as _dc

        dense_twin = eng.module.clone(config=_dc.replace(
            eng.module.config, quantized_weights=False))
        assert all(d == 32 for d in _cached_key_slot_dims(dense_twin,
                                                          ids))
        np.testing.assert_array_equal(toks, ref_toks)

    @pytest.mark.slow
    def test_streaming_decode_past_n_positions(self):
        """Ring-cached rotary models stream: no wpe table saturates, the
        ring evicts old window blocks, globals persist (attention sinks)
        — so generation runs PAST n_positions at O(window) memory. Ground
        truth: a rotary model's params don't depend on n_positions, so a
        same-seed engine with a 64x larger cap must emit the identical
        stream."""
        import deepspeed_tpu
        from deepspeed_tpu.parallel import mesh as mesh_mod

        sparse = {"mode": "bslongformer", "block": 16,
                  "num_sliding_window_blocks": 3,
                  "attention": "unidirectional"}
        kw = dict(rotary=True, learned_positions=False)
        rng = np.random.RandomState(15)
        ids = jnp.asarray(rng.randint(0, 128, size=(1, 48)), jnp.int32)

        small = self._sparse_model(sparse, n_positions=64, **kw)
        eng_s = deepspeed_tpu.init_inference(small, dtype="fp32", seed=0)
        # 48 + 100 = 148 tokens >> n_positions=64
        toks_s = np.asarray(eng_s.generate(ids, max_new_tokens=100))
        assert toks_s.shape == (1, 100)

        mesh_mod.reset_default_topology()
        big = self._sparse_model(sparse, n_positions=4096, **kw)
        eng_b = deepspeed_tpu.init_inference(big, dtype="fp32", seed=0)
        toks_b = np.asarray(eng_b.generate(ids, max_new_tokens=100))
        np.testing.assert_array_equal(toks_s, toks_b)

        # a wpe model keeps the hard cap: its position table saturates
        mesh_mod.reset_default_topology()
        wpe = self._sparse_model(sparse, n_positions=64)
        eng_w = deepspeed_tpu.init_inference(wpe, dtype="fp32", seed=0)
        with pytest.raises(ValueError, match="exceeds the KV cache"):
            eng_w.generate(ids, max_new_tokens=100)

    def test_sparse_kv_cache_true_rejects_bigbird(self):
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import get_sparse_attention_config

        sc = get_sparse_attention_config(
            {"mode": "bigbird", "block": 16,
             "attention": "unidirectional"}, 4)
        with pytest.raises(ValueError, match="ring-expressible"):
            _cfg(sparse_attention=sc, sparse_kv_cache=True)


class TestDecodeDivergenceWarnings:
    def test_sparse_model_generate_warns_dense_decode(self, caplog):
        """A sparse_attention-trained model decodes dense (the KV-cache
        path has no sparse analogue) — generate says so once."""
        import logging

        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import apply_sparse_attention
        from deepspeed_tpu.utils.logging import _warn_once_cached

        model = apply_sparse_attention(
            GPT(_cfg(n_positions=64)),
            {"mode": "fixed", "block": 16, "num_local_blocks": 2})
        eng = deepspeed_tpu.init_inference(model, dtype="fp32", seed=0)
        ids = jnp.asarray(
            np.random.RandomState(8).randint(0, 128, size=(1, 32)),
            jnp.int32)
        _warn_once_cached.cache_clear()
        pkg_logger = logging.getLogger("deepspeed_tpu")
        pkg_logger.propagate = True  # caplog listens on root
        try:
            with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
                eng.generate(ids, max_new_tokens=2)
        finally:
            pkg_logger.propagate = False
        assert any("DENSE" in r.message for r in caplog.records), \
            caplog.records


class TestDemandedRingDeclines:
    """sparse_kv_cache=True is a DEMAND: when the ring cache cannot engage,
    ring_engaged must warn and record the reason instead of silently
    decoding dense (sparse_attention_utils._decline_demanded_ring)."""

    def _cfg_ns(self, sc, kv, n_positions):
        from types import SimpleNamespace

        return SimpleNamespace(sparse_attention=sc, sparse_kv_cache=kv,
                               n_positions=n_positions)

    def _longformer(self):
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import get_sparse_attention_config

        return get_sparse_attention_config(
            {"mode": "bslongformer", "block": 16,
             "num_sliding_window_blocks": 3,
             "attention": "unidirectional"}, 4)

    def test_demand_engages_oversized_ring(self):
        """sparse_kv_cache=True DEMANDS the ring: a ring no smaller than
        the dense cache still engages (the caller wants the exact
        training-sparse decode math and streaming semantics, not a memory
        win) — the size heuristic is reserved for "auto"."""
        import warnings as _warnings

        from deepspeed_tpu.ops.sparse_attention import (
            sparse_attention_utils as sau)

        sc = self._longformer()
        n0 = len(sau.RING_DECLINES)
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            # ring span 16 + (1+1)*16 = 48 >= n_positions 32: auto would
            # decline, True must engage — silently, it is not a fallback
            ring = sau.ring_engaged(self._cfg_ns(sc, True, 32))
        assert ring == (1, 16, 16)
        assert len(sau.RING_DECLINES) == n0

    def test_inexpressible_layout_warns_with_reason(self):
        from deepspeed_tpu.ops.sparse_attention import (
            sparse_attention_utils as sau)
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import get_sparse_attention_config

        # bidirectional window has no causal ring expression
        sc = get_sparse_attention_config(
            {"mode": "bslongformer", "block": 16,
             "num_sliding_window_blocks": 3,
             "attention": "bidirectional"}, 4)
        n0 = len(sau.RING_DECLINES)
        with pytest.warns(RuntimeWarning, match="no ring expression"):
            assert sau.ring_engaged(self._cfg_ns(sc, True, 4096)) is None
        assert len(sau.RING_DECLINES) == n0 + 1

    def test_auto_decline_stays_silent(self):
        import warnings as _warnings

        from deepspeed_tpu.ops.sparse_attention import (
            sparse_attention_utils as sau)

        sc = self._longformer()
        n0 = len(sau.RING_DECLINES)
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            assert sau.ring_engaged(self._cfg_ns(sc, "auto", 32)) is None
        assert len(sau.RING_DECLINES) == n0  # auto means "when it helps"

    def test_engaged_ring_does_not_warn(self):
        import warnings as _warnings

        from deepspeed_tpu.ops.sparse_attention import (
            sparse_attention_utils as sau)

        sc = self._longformer()
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            ring = sau.ring_engaged(self._cfg_ns(sc, True, 4096))
        assert ring is not None
