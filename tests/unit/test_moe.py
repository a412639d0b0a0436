"""MoE gating + layer + expert-parallel E2E tests
(reference tests/unit/moe/test_moe.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import (
    MoE,
    split_moe_params,
    static_capacity,
    top1_gating,
    top2_gating,
)


class TestGating:
    def test_static_capacity(self):
        assert static_capacity(64, 8, 1.0, 4) == 8
        assert static_capacity(64, 8, 1.0, 16) == 16
        assert static_capacity(8, 8, 1.0, 0) == 1
        # clamped to token count
        assert static_capacity(4, 2, 100.0, 4) == 4

    def test_top1_respects_capacity(self):
        rng = jax.random.PRNGKey(0)
        # all tokens prefer expert 0 -> capacity must truncate
        logits = jnp.zeros((32, 4)).at[:, 0].set(10.0)
        out = top1_gating(logits, capacity_factor=1.0, min_capacity=4, rng=rng)
        per_expert = jnp.sum(out.dispatch_mask.astype(jnp.int32), axis=(0, 2))
        cap = static_capacity(32, 4, 1.0, 4)
        assert int(per_expert[0]) == cap
        assert int(per_expert[1:].sum()) == 0
        # every capacity slot used at most once
        per_slot = jnp.sum(out.dispatch_mask.astype(jnp.int32), axis=0)
        assert int(per_slot.max()) <= 1

    def test_top1_balanced_aux_loss_is_lower(self):
        rng = jax.random.PRNGKey(1)
        T, E = 64, 8
        balanced = jax.nn.one_hot(jnp.arange(T) % E, E) * 8.0
        unbalanced = jnp.zeros((T, E)).at[:, 0].set(8.0)
        l_bal = top1_gating(balanced, rng=rng).l_aux
        l_unbal = top1_gating(unbalanced, rng=rng).l_aux
        assert float(l_bal) < float(l_unbal)
        # perfectly balanced -> l_aux ~ 1.0 (me*ce*E = E * E*(1/E * 1/E))
        assert float(l_bal) == pytest.approx(1.0, rel=0.2)

    def test_top1_deterministic_no_rng(self):
        logits = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
        a = top1_gating(logits, rng=None)
        b = top1_gating(logits, rng=None)
        np.testing.assert_array_equal(np.asarray(a.dispatch_mask),
                                      np.asarray(b.dispatch_mask))

    def test_top1_combine_weights_are_gate_probs(self):
        logits = jax.random.normal(jax.random.PRNGKey(3), (64, 8)) * 3
        out = top1_gating(logits, capacity_factor=8.0, rng=None)
        gates = jax.nn.softmax(logits, axis=-1)
        w = np.asarray(jnp.sum(out.combine_weights, axis=(1, 2)))
        expect = np.asarray(jnp.max(gates, axis=-1))
        np.testing.assert_allclose(w, expect, rtol=1e-5)

    def test_top2_weights_normalized(self):
        logits = jax.random.normal(jax.random.PRNGKey(4), (64, 8)) * 3
        out = top2_gating(logits, capacity_factor=8.0, rng=None)
        # with ample capacity every token keeps both experts: weights sum to 1
        w = np.asarray(jnp.sum(out.combine_weights, axis=(1, 2)))
        np.testing.assert_allclose(w, 1.0, rtol=1e-5)

    def test_top2_two_experts_per_token(self):
        logits = jax.random.normal(jax.random.PRNGKey(5), (64, 8))
        out = top2_gating(logits, capacity_factor=8.0, rng=None)
        n = np.asarray(jnp.sum(out.dispatch_mask.astype(jnp.int32), axis=(1, 2)))
        assert (n == 2).all()


class TestMoELayer:
    def _layer(self, E=4, M=16, H=32, **kw):
        return MoE(d_model=M, d_hidden=H, num_experts=E,
                   capacity_factor=8.0, eval_capacity_factor=8.0,
                   dtype=jnp.float32, **kw)

    def test_forward_shape_and_finite(self):
        layer = self._layer()
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        params = layer.init(jax.random.PRNGKey(1), x)
        y, l_aux, _, counts = layer.apply(params, x)
        assert y.shape == x.shape
        assert jnp.isfinite(y).all()
        assert counts.shape == (4,)
        assert int(counts.sum()) == 16  # every token routed (top-1)

    def test_identical_experts_match_dense(self):
        """With all experts holding the same weights and ample capacity, the
        MoE output equals a single dense FFN pass (dispatch/combine is exact)."""
        layer = self._layer(E=4)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        params = layer.init(jax.random.PRNGKey(1), x)
        p = jax.tree_util.tree_map(lambda v: v, params)  # copy
        ex = p["params"]["experts"]
        for k in ("wi", "wo", "bi", "bo"):
            ex[k] = jnp.broadcast_to(ex[k][:1], ex[k].shape)
        y, _, _, _ = layer.apply(p, x)

        # dense reference with expert-0 weights
        h = jnp.einsum("btm,mh->bth", x, ex["wi"][0]) + ex["bi"][0]
        h = jax.nn.gelu(h)
        dense = jnp.einsum("bth,hm->btm", h, ex["wo"][0]) + ex["bo"][0]
        # top-1: output is gate_prob * expert_out, gate prob <= 1
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(dense * np.asarray(
                _top1_probs(layer, p, x))[..., None]), atol=1e-4)

    def test_gated_experts_match_dense_swiglu(self):
        """gated_experts=True: each expert is a biasless SwiGLU FFN
        (Mixtral-style); with identical experts the MoE output equals the
        dense SwiGLU reference scaled by the gate prob."""
        layer = self._layer(E=4, gated_experts=True)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        params = layer.init(jax.random.PRNGKey(1), x)
        ex = params["params"]["experts"]
        assert set(ex) == {"wi", "wg", "wo"}  # biasless, with a gate tensor
        for k in ex:
            ex[k] = jnp.broadcast_to(ex[k][:1], ex[k].shape)
        y, _, _, _ = layer.apply(params, x)

        h = jnp.einsum("btm,mh->bth", x, ex["wi"][0])
        g = jnp.einsum("btm,mh->bth", x, ex["wg"][0])
        dense = jnp.einsum("bth,hm->btm", jax.nn.silu(g) * h, ex["wo"][0])
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(dense * np.asarray(
                _top1_probs(layer, params, x))[..., None]), atol=1e-4)

    def test_grads_flow_to_experts_and_gate(self):
        layer = self._layer()
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        params = layer.init(jax.random.PRNGKey(1), x)

        def loss_fn(p):
            y, l_aux, _, _ = layer.apply(p, x)
            return jnp.sum(y ** 2) + 0.01 * l_aux

        grads = jax.grad(loss_fn)(params)
        gnorms = jax.tree_util.tree_map(lambda g: float(jnp.abs(g).sum()), grads)
        flat = jax.tree_util.tree_leaves(gnorms)
        assert all(np.isfinite(v) for v in flat)
        assert float(jnp.abs(grads["params"]["gate"]["kernel"]).sum()) > 0
        assert float(jnp.abs(grads["params"]["experts"]["wi"]).sum()) > 0

    def test_split_moe_params(self):
        layer = self._layer()
        x = jnp.ones((1, 4, 16))
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        moe, dense = split_moe_params(params)
        assert moe["experts"]["wi"] is not None
        assert moe["gate"]["kernel"] is None
        assert dense["gate"]["kernel"] is not None
        assert dense["experts"]["wi"] is None


def _top1_probs(layer, params, x):
    """Gate top-1 probability per token, reshaped to x's leading dims."""
    logits = x.reshape(-1, x.shape[-1]).astype(jnp.float32) @ (
        params["params"]["gate"]["kernel"]
    )
    p = jax.nn.softmax(logits, axis=-1).max(axis=-1)
    return p.reshape(x.shape[:-1])


class TestMoEExpertParallel:
    def test_moe_gpt_trains_on_ep_mesh(self, eight_devices):
        import deepspeed_tpu
        from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
        from deepspeed_tpu.parallel.mesh import MeshTopology

        topo = MeshTopology(dp=2, ep=4, devices=jax.devices()[:8])
        cfg = GPTConfig(
            vocab_size=128, n_positions=32, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32, scan_layers=True,
            moe_num_experts=4, moe_capacity_factor=2.0,
        )
        ds_config = {
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "steps_per_print": 10 ** 9,
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT(cfg), config=ds_config, topology=topo)

        gb = engine.train_micro_batch_size_per_gpu * topo.data_parallel_size
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, size=(gb, 32)).astype(np.int32)
        batch = {"input_ids": ids, "labels": ids}
        losses = []
        for _ in range(3):
            loss = engine.forward(batch)
            engine.backward()
            engine.step()
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        # expert params must actually shard over ep
        specs = {
            p: str(leaf.sharding.spec)
            for p, leaf in _flat_params(engine.params).items()
        }
        expert_specs = [s for p, s in specs.items() if "experts" in p]
        assert expert_specs and any("ep" in s for s in expert_specs), specs


def _flat_params(params):
    from deepspeed_tpu.utils.tree import flatten_with_paths

    return flatten_with_paths(params)


class TestExpertShardedCheckpoint:
    def test_moe_roundtrip_per_expert_files(self, eight_devices, tmp_path):
        """MoE checkpoints write one file per global expert id (reference
        _save_moe_checkpoint, engine.py:2965) — the dense model-states file
        must NOT contain the expert leaves — and load back exactly."""
        import os

        import deepspeed_tpu
        from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
        from deepspeed_tpu.parallel.mesh import MeshTopology

        topo = MeshTopology(dp=2, ep=4, devices=jax.devices()[:8])
        cfg = GPTConfig(
            vocab_size=128, n_positions=32, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32, scan_layers=True,
            moe_num_experts=4, moe_capacity_factor=2.0,
        )
        ds_config = {
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "steps_per_print": 10 ** 9,
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT(cfg), config=ds_config, topology=topo)
        gb = engine.train_micro_batch_size_per_gpu * topo.data_parallel_size
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.randint(0, 128, size=(gb, 32)).astype(
            np.int32)}
        batch["labels"] = batch["input_ids"]
        for _ in range(3):
            engine.forward(batch)
            engine.backward()
            engine.step()
        engine.save_checkpoint(str(tmp_path), tag="moe")

        tag_dir = os.path.join(str(tmp_path), "moe")
        expert_files = sorted(
            f for f in os.listdir(tag_dir) if f.startswith("expert_"))
        # 4 experts x (model + optim) states
        assert len([f for f in expert_files if "model" in f]) == 4
        assert len([f for f in expert_files if "optim" in f]) == 4

        # the dense file must not carry expert leaves (that is the point:
        # no host gathers the full expert set)
        from flax import serialization as ser

        with open(os.path.join(tag_dir,
                               "mp_rank_00_model_states.msgpack"), "rb") as f:
            dense = ser.msgpack_restore(f.read())
        from deepspeed_tpu.utils.tree import flatten_dots

        dense_paths = flatten_dots(dense["module"])
        assert not any("experts" in p for p in dense_paths), \
            [p for p in dense_paths if "experts" in p]

        ref_params = [np.asarray(x) for x in jax.tree.leaves(engine.params)]
        ref_opt = [np.asarray(x) for x in jax.tree.leaves(engine._opt_state)]
        for _ in range(2):  # drift
            engine.forward(batch)
            engine.backward()
            engine.step()
        engine.load_checkpoint(str(tmp_path), tag="moe")
        for a, b in zip(ref_params, jax.tree.leaves(engine.params)):
            np.testing.assert_array_equal(a, np.asarray(b))
        for a, b in zip(ref_opt, jax.tree.leaves(engine._opt_state)):
            np.testing.assert_array_equal(a, np.asarray(b))
        # expert leaves still sharded over ep after the restore
        from deepspeed_tpu.utils.tree import flatten_with_paths

        specs = {p: str(x.sharding.spec)
                 for p, x in flatten_with_paths(engine.params).items()}
        assert any("ep" in s for p, s in specs.items() if "experts" in p)
