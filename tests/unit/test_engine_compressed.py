"""Engine-integrated compressed gradient exchange.

Reference parity: configuring ``"optimizer": {"type": "OnebitAdam"}``
changes the wire protocol (reference runtime/fp16/onebit/adam.py:10 +
runtime/comm/nccl.py:51 compressed_allreduce), and
``communication_data_type`` selects the gradient-allreduce format
(runtime/config.py get_communication_data_type). These tests assert both
(a) convergence near the uncompressed optimizer and (b) actual int8
payloads in the compiled step's collectives.
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.dataloader import RepeatingLoader


class LSQ(nn.Module):
    """13-feature least squares: odd sizes exercise the padding path."""

    @nn.compact
    def __call__(self, x=None, y=None, deterministic=True):
        pred = nn.Dense(1)(x)[:, 0]
        return jnp.mean((pred - y) ** 2)


def _data(n=64, d=13, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    Y = (X @ rng.randn(d)).astype(np.float32)
    return X, Y


def _engine(opt_block, extra=None, micro=8, gas=1):
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": opt_block,
        "steps_per_print": 10 ** 9,
    }
    cfg.update(extra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=LSQ(), config=cfg)
    return engine


def _lowered_step(engine, batch):
    return engine._train_step_fn.lower(
        engine._params, engine._opt_state, engine._ls_state,
        engine._put_batch(batch), engine._rng, engine.micro_steps,
        engine._lr_factor_now())


def _compiled_step_text(engine, batch):
    return _lowered_step(engine, batch).compile().as_text()


def _has_int8_collective(hlo_text):
    return bool(re.search(r"(all-to-all|all-gather)[^\n]*s8", hlo_text)) or \
        bool(re.search(r"s8[^\n]*(all-to-all|all-gather)", hlo_text))


class TestOnebitEngine:
    def test_converges_near_adamw(self, eight_devices):
        """Same data, same lr schedule: the compressed run must pass the
        same convergence bar as exact AdamW (<1% of initial loss). The
        1-bit run keeps a compression-noise floor proportional to lr, so
        a decaying schedule is part of the recipe — as in the reference's
        1-bit Adam tutorials."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        sched = {"type": "WarmupDecayLR",
                 "params": {"warmup_min_lr": 0, "warmup_max_lr": 5e-2,
                            "warmup_num_steps": 10,
                            "total_num_steps": 200}}

        losses = {}
        for name, block in [
            ("adamw", {"type": "AdamW", "params": {"lr": 5e-2}}),
            ("onebit", {"type": "OnebitAdam",
                        "params": {"lr": 5e-2, "freeze_step": 10}}),
        ]:
            from deepspeed_tpu.parallel import mesh
            mesh.reset_default_topology()
            eng = _engine(block, extra={"scheduler": sched})
            it = iter(RepeatingLoader([batch]))
            losses[name] = [float(eng.train_batch(it)) for _ in range(200)]

        assert losses["adamw"][-1] < 0.01 * losses["adamw"][0]
        # the 1-bit run's compression-noise floor sits a few x higher than
        # exact AdamW's — hold it to a 20x-reduction bar rather than
        # AdamW's 100x, and require it keeps descending through the tail
        assert losses["onebit"][-1] < 0.05 * losses["onebit"][0], \
            losses["onebit"][::40]
        assert losses["onebit"][-1] < losses["onebit"][-40], \
            losses["onebit"][::40]

    def test_int8_payload_on_the_wire(self, eight_devices):
        """The compiled train step must exchange int8 sign tensors (not
        fp32) — inspect the HLO for s8 collectives."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "OnebitAdam",
                       "params": {"lr": 1e-2, "freeze_step": 2}})
        it = iter(RepeatingLoader([batch]))
        eng.train_batch(it)
        assert _has_int8_collective(_compiled_step_text(eng, batch))

    def test_gas_path(self, eight_devices):
        """Gradient accumulation: the unfused forward/backward/step protocol
        accumulates per-worker grads and exchanges at the boundary."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "OnebitAdam",
                       "params": {"lr": 5e-2, "freeze_step": 5}}, gas=2)
        it = iter(RepeatingLoader([batch]))
        first = float(eng.train_batch(it))
        for _ in range(99):
            last = float(eng.train_batch(it))
        assert eng.global_steps == 100
        assert last < 0.2 * first

    def test_onebit_lamb_and_zoadam_run(self, eight_devices):
        X, Y = _data()
        batch = {"x": X, "y": Y}
        for opt in ("OnebitLamb", "ZeroOneAdam"):
            from deepspeed_tpu.parallel import mesh
            mesh.reset_default_topology()
            # sign-based steps on this ill-conditioned quadratic need a
            # cool lr (scales are undiluted since the pad-masking fix)
            eng = _engine({"type": opt,
                           "params": {"lr": 5e-3, "freeze_step": 5}})
            it = iter(RepeatingLoader([batch]))
            first = float(eng.train_batch(it))
            for _ in range(80):
                last = float(eng.train_batch(it))
            assert np.isfinite(last) and last < first, (opt, first, last)

    def test_checkpoint_roundtrip(self, eight_devices, tmp_path):
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "OnebitAdam",
                       "params": {"lr": 5e-2, "freeze_step": 3}})
        it = iter(RepeatingLoader([batch]))
        for _ in range(10):
            eng.train_batch(it)
        eng.save_checkpoint(str(tmp_path), tag="t")

        from deepspeed_tpu.parallel import mesh
        mesh.reset_default_topology()
        eng2 = _engine({"type": "OnebitAdam",
                        "params": {"lr": 5e-2, "freeze_step": 3}})
        it2 = iter(RepeatingLoader([batch]))
        eng2.train_batch(it2)  # materialize state templates
        eng2.load_checkpoint(str(tmp_path), tag="t")
        assert eng2.global_steps == 10
        # error-feedback buffers restored (non-zero after compression
        # steps; single-element leaves compress exactly, so check ALL)
        we = np.concatenate([
            np.abs(np.asarray(x)).ravel()
            for x in jax.tree.leaves(eng2._opt_state.worker_error)])
        assert we.max() > 0

    def test_rejects_zero2_and_tp(self, eight_devices):
        with pytest.raises(ValueError, match="ZeRO stage"):
            _engine({"type": "OnebitAdam", "params": {"lr": 1e-2}},
                    extra={"zero_optimization": {"stage": 2}})
        from deepspeed_tpu.parallel.mesh import MeshTopology
        topo = MeshTopology(tp=2, dp=-1, devices=jax.devices()[:8])
        with pytest.raises(ValueError, match="dp axis"):
            deepspeed_tpu.initialize(
                model=LSQ(), topology=topo,
                config={"train_micro_batch_size_per_gpu": 8,
                        "optimizer": {"type": "OnebitAdam",
                                      "params": {"lr": 1e-2}},
                        "steps_per_print": 10 ** 9})


class TestInt8GradComm:
    def test_converges_and_int8_wire(self, eight_devices):
        """communication_data_type=int8 routes grad averaging through the
        quantized allreduce with error feedback; must converge like exact
        AdamW (~1e-2 relative comm error) and show s8 collectives."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                      extra={"communication_data_type": "int8"})
        it = iter(RepeatingLoader([batch]))
        losses = [float(eng.train_batch(it)) for _ in range(100)]
        assert losses[-1] < 0.01 * losses[0], losses[::20]
        assert _has_int8_collective(_compiled_step_text(eng, batch))

    def test_fp32_value_is_inert(self, eight_devices):
        X, Y = _data()
        eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                      extra={"communication_data_type": "fp32"})
        assert eng._exchange is None

    def test_rejects_zero_stage1(self, eight_devices):
        with pytest.raises(ValueError, match="ZeRO stage"):
            _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                    extra={"communication_data_type": "int8",
                           "zero_optimization": {"stage": 1}})


class TestCompressedObservability:
    def test_int8_grad_norm_and_clipping(self, eight_devices):
        """The int8 path materializes the post-exchange mean anyway, so
        get_global_grad_norm() works and gradient_clipping clips exactly."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                      extra={"communication_data_type": "int8",
                             "gradient_clipping": 1.0})
        it = iter(RepeatingLoader([batch]))
        eng.train_batch(it)
        gn = eng.get_global_grad_norm()
        assert gn is not None and np.isfinite(gn) and gn > 0, gn

    def test_onebit_norm_gated(self, eight_devices):
        """1-bit optimizers: grad norm is None by default (the averaged
        gradient never exists) and real with tpu.compressed_grad_norm."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "OnebitAdam",
                       "params": {"lr": 1e-2, "freeze_step": 2}})
        it = iter(RepeatingLoader([batch]))
        eng.train_batch(it)
        assert eng.get_global_grad_norm() is None

        eng2 = _engine({"type": "OnebitAdam",
                        "params": {"lr": 1e-2, "freeze_step": 2}},
                       extra={"tpu": {"compressed_grad_norm": True}})
        it2 = iter(RepeatingLoader([batch]))
        eng2.train_batch(it2)
        gn = eng2.get_global_grad_norm()
        assert gn is not None and np.isfinite(gn) and gn > 0, gn


class TestFp16Onebit:
    def test_overflow_skips_and_keeps_error_feedback(self, eight_devices):
        """fp16 dynamic loss scaling composes with OnebitAdam (reference
        fp16/onebit/adam.py pairs them): an overflow step is skipped with
        params, optimizer count, AND error-feedback buffers untouched, and
        convergence resumes after the skip."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine(
            {"type": "OnebitAdam", "params": {"lr": 5e-2, "freeze_step": 5}},
            extra={"fp16": {"enabled": True, "initial_scale_power": 4,
                            "hysteresis": 1},
                   "scheduler": {"type": "WarmupDecayLR",
                                 "params": {"warmup_min_lr": 0,
                                            "warmup_max_lr": 5e-2,
                                            "warmup_num_steps": 10,
                                            "total_num_steps": 200}}})
        it = iter(RepeatingLoader([batch]))
        first = float(eng.train_batch(it))
        for _ in range(19):  # well into the compression stage
            eng.train_batch(it)
        assert eng.skipped_steps == 0
        params_before = [np.asarray(x) for x in jax.tree.leaves(eng.params)]
        we_before = [np.asarray(x) for x in
                     jax.tree.leaves(eng._opt_state.worker_error)]
        count_before = int(eng._opt_state.count)

        bad = {"x": np.full_like(X, np.inf), "y": Y}
        eng.train_batch(iter(RepeatingLoader([bad])))
        assert eng.skipped_steps == 1
        assert eng.loss_scale == 2.0 ** 3  # halved
        for b, a in zip(params_before, jax.tree.leaves(eng.params)):
            np.testing.assert_array_equal(b, np.asarray(a))
        for b, a in zip(we_before,
                        jax.tree.leaves(eng._opt_state.worker_error)):
            np.testing.assert_array_equal(b, np.asarray(a))
        assert int(eng._opt_state.count) == count_before

        for _ in range(160):
            last = float(eng.train_batch(it))
        assert last < 0.05 * first, (first, last)

    def test_fp16_int8_comm_overflow_skip(self, eight_devices):
        """fp16 also composes with communication_data_type=int8: overflow
        skips the exchange and the server/worker residuals are untouched."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine(
            {"type": "AdamW", "params": {"lr": 5e-2}},
            extra={"communication_data_type": "int8",
                   "fp16": {"enabled": True, "initial_scale_power": 4,
                            "hysteresis": 1}})
        it = iter(RepeatingLoader([batch]))
        for _ in range(5):
            eng.train_batch(it)
        err_before = [np.asarray(x) for x in jax.tree.leaves(
            eng._opt_state[1])]
        bad = {"x": np.full_like(X, np.inf), "y": Y}
        eng.train_batch(iter(RepeatingLoader([bad])))
        assert eng.skipped_steps == 1
        for b, a in zip(err_before, jax.tree.leaves(eng._opt_state[1])):
            np.testing.assert_array_equal(b, np.asarray(a))
        last = None
        for _ in range(60):
            last = float(eng.train_batch(it))
        assert np.isfinite(last)
