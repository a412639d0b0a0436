"""Engine-integrated bucketed/deferred gradient exchange
(``tpu.grad_exchange`` config block -> ``runtime/grad_exchange.py``).

``deferred: true`` keeps per-worker grads through the accumulation window
and exchanges once, bucketed, at the optimizer boundary — same protocol as
the int8 path but with an fp32/bf16 wire, so it must match the baseline
engine's math (exactly, for the fp32 wire). ``bucket_mb`` re-buckets the
int8 exchange; ``bucket_mb: 0`` keeps the legacy per-leaf layout
(checkpoint compatibility)."""

import re

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.config import (
    DeepSpeedConfigError,
    GradExchangeConfig,
)
from deepspeed_tpu.runtime.dataloader import RepeatingLoader

from tests.unit.test_engine_compressed import (
    LSQ,
    _compiled_step_text,
    _data,
    _engine,
    _has_int8_collective,
    _lowered_step,
)


def _params(eng):
    return [np.asarray(x) for x in jax.tree.leaves(eng.params)]


def _all_reduce_operands(eng, batch):
    """Element types of every ``all_reduce`` operand in the fused step as
    LOWERED: the CPU backend promotes bf16 all-reduces to f32 when it
    compiles (it has no bf16 collective), so the compiled text cannot say
    what a TPU puts on the wire. The loss's mean over groups is a plain
    ``jnp.mean`` outside the shard_map: the exchange's are the only ones."""
    found = re.findall(
        r'"stablehlo\.all_reduce".*?\}\) : \(tensor<(?:\d+x)*(\w+)>\)',
        _lowered_step(eng, batch).as_text(), flags=re.S)
    assert found, "no all_reduce in the lowered step"
    return set(found)


class TestGradExchangeConfig:
    def test_defaults(self):
        cfg = GradExchangeConfig.from_dict({})
        assert cfg.bucket_mb == 0.0 and not cfg.deferred
        assert cfg.wire_dtype == "bf16"

    def test_rejects_bad_wire_dtype(self):
        with pytest.raises(DeepSpeedConfigError, match="wire_dtype"):
            GradExchangeConfig.from_dict({"wire_dtype": "fp8"})

    def test_rejects_negative_bucket(self):
        with pytest.raises(DeepSpeedConfigError, match="bucket_mb"):
            GradExchangeConfig.from_dict({"bucket_mb": -1})

    def test_engine_surfaces_config_error(self, eight_devices):
        with pytest.raises(DeepSpeedConfigError, match="wire_dtype"):
            _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                    extra={"tpu": {"grad_exchange":
                                   {"wire_dtype": "int4"}}})


class TestDeferredExchange:
    def test_default_off(self, eight_devices):
        eng = _engine({"type": "AdamW", "params": {"lr": 1e-2}})
        assert eng._exchange is None

    def test_fp32_wire_matches_baseline_engine(self, eight_devices):
        """The deferred exchange is psum-of-sums instead of
        sum-of-psums — algebraically identical, and with the fp32 wire it
        must track the baseline engine's parameters to float rounding."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        runs = {}
        for name, extra in [
            ("baseline", {}),
            ("deferred", {"tpu": {"grad_exchange":
                                  {"deferred": True, "wire_dtype": "fp32",
                                   "bucket_mb": 1}}}),
        ]:
            from deepspeed_tpu.parallel import mesh
            mesh.reset_default_topology()
            eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                          extra=extra, gas=2)
            it = iter(RepeatingLoader([batch]))
            losses = [float(eng.train_batch(it)) for _ in range(12)]
            runs[name] = (losses, _params(eng), eng)
        assert runs["deferred"][2]._exchange.mode == "deferred"
        assert runs["deferred"][2]._exchange.plan is not None
        np.testing.assert_allclose(runs["baseline"][0], runs["deferred"][0],
                                   rtol=1e-4)
        for b, d in zip(runs["baseline"][1], runs["deferred"][1]):
            np.testing.assert_allclose(b, d, atol=1e-5)

    def test_bf16_wire_converges_and_on_the_wire(self, eight_devices):
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                      extra={"tpu": {"grad_exchange": {"deferred": True}}})
        it = iter(RepeatingLoader([batch]))
        losses = [float(eng.train_batch(it)) for _ in range(100)]
        assert losses[-1] < 0.01 * losses[0], losses[::20]
        # the collective payload is cast to bf16 (the halved wire)
        assert _all_reduce_operands(eng, batch) == {"bf16"}

    def test_fp32_wire_is_f32_on_the_wire(self, eight_devices):
        batch = dict(zip("xy", _data()))
        eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                      extra={"tpu": {"grad_exchange": {
                          "deferred": True, "wire_dtype": "fp32"}}})
        eng.train_batch(iter([batch]))
        assert _all_reduce_operands(eng, batch) == {"f32"}

    def test_grad_norm_available(self, eight_devices):
        """Deferred mode materializes the averaged gradient, so the norm
        (and clipping) work exactly as in the baseline engine."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                      extra={"tpu": {"grad_exchange": {"deferred": True}},
                             "gradient_clipping": 1.0})
        it = iter(RepeatingLoader([batch]))
        eng.train_batch(it)
        gn = eng.get_global_grad_norm()
        assert gn is not None and np.isfinite(gn) and gn > 0, gn


class TestBucketedInt8:
    def test_converges_and_int8_wire(self, eight_devices):
        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                      extra={"communication_data_type": "int8",
                             "tpu": {"grad_exchange":
                                     {"bucket_mb": 0.0001}}})
        assert eng._exchange.mode == "int8"
        it = iter(RepeatingLoader([batch]))
        losses = [float(eng.train_batch(it)) for _ in range(100)]
        assert losses[-1] < 0.01 * losses[0], losses[::20]
        assert eng._exchange.plan is not None
        hlo = _compiled_step_text(eng, batch)
        assert re.search(r"(all-to-all|all-gather)[^\n]*s8"
                         r"|s8[^\n]*(all-to-all|all-gather)", hlo)

    def test_bucket_mb_zero_keeps_legacy_layout(self, eight_devices):
        """No bucket budget -> the pre-bucketing per-leaf path and its
        per-leaf error-feedback state layout (existing int8 checkpoints
        keep loading)."""
        X, Y = _data()
        eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                      extra={"communication_data_type": "int8"})
        assert eng._exchange.mode == "int8"
        assert eng._exchange.plan is None
        it = iter(RepeatingLoader([{"x": X, "y": Y}]))
        eng.train_batch(it)
        # legacy state: worker-error tree mirrors the PARAM tree
        assert len(jax.tree.leaves(eng._opt_state[1])) == \
            len(jax.tree.leaves(eng.params))

    def test_bucketed_error_feedback_state_per_bucket(self, eight_devices):
        X, Y = _data()
        eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                      extra={"communication_data_type": "int8",
                             "tpu": {"grad_exchange":
                                     {"bucket_mb": 0.0001}}})
        it = iter(RepeatingLoader([{"x": X, "y": Y}]))
        for _ in range(3):
            eng.train_batch(it)
        plan = eng._exchange.plan
        we = eng._opt_state[1]
        assert isinstance(we, tuple) and len(we) == plan.num_buckets
        # residuals are live (non-zero) after compressed steps
        assert max(np.abs(np.asarray(e)).max() for e in we) > 0


class TestHierarchicalExchange:
    """Two-level ICI/DCN deferred exchange (``hierarchical`` +
    ``dcn_slices`` forcing the slice structure on the virtual CPU mesh)."""

    def test_config_rejects_unknown_mode(self):
        with pytest.raises(DeepSpeedConfigError, match="hierarchical"):
            GradExchangeConfig.from_dict({"hierarchical": "yes"})
        with pytest.raises(DeepSpeedConfigError, match="dcn_slices"):
            GradExchangeConfig.from_dict({"dcn_slices": -2})
        with pytest.raises(DeepSpeedConfigError, match="dcn_block"):
            GradExchangeConfig.from_dict({"dcn_block": 0})

    def test_on_requires_deferred(self, eight_devices):
        with pytest.raises(ValueError, match="deferred"):
            _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                    extra={"tpu": {"grad_exchange":
                                   {"hierarchical": "on"}}})

    def test_rejected_on_int8_wire(self, eight_devices):
        # the int8 path owns its wire format end to end
        with pytest.raises(ValueError, match="deferred"):
            _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                    extra={"communication_data_type": "int8",
                           "tpu": {"grad_exchange":
                                   {"hierarchical": "auto"}}})

    def test_on_without_slice_structure_raises(self, eight_devices):
        # single-slice CPU mesh, no dcn_slices override: "on" must fail
        # loudly instead of silently running the flat exchange. The
        # layout is resolved with the rest of the lazily-built state, so
        # the error surfaces on the first batch.
        X, Y = _data()
        eng = _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                      extra={"tpu": {"grad_exchange":
                                     {"deferred": True,
                                      "hierarchical": "on"}}})
        it = iter(RepeatingLoader([{"x": X, "y": Y}]))
        with pytest.raises(ValueError, match="slice structure"):
            eng.train_batch(it)

    def test_indivisible_slice_count_raises(self, eight_devices):
        X, Y = _data()
        eng = _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                      extra={"tpu": {"grad_exchange":
                                     {"deferred": True,
                                      "hierarchical": "on",
                                      "dcn_slices": 3}}})
        it = iter(RepeatingLoader([{"x": X, "y": Y}]))
        with pytest.raises(ValueError, match="do not divide"):
            eng.train_batch(it)

    def test_auto_without_slices_falls_back_flat(self, eight_devices):
        X, Y = _data()
        eng = _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                      extra={"tpu": {"grad_exchange":
                                     {"deferred": True,
                                      "hierarchical": "auto"}}})
        it = iter(RepeatingLoader([{"x": X, "y": Y}]))
        eng.train_batch(it)  # builds the (lazy) exchange state
        assert eng._exchange.mode == "deferred"
        assert eng._exchange.num_slices == 1

    @pytest.mark.slow
    def test_converges_publishes_plan_and_int8_dcn_wire(
            self, eight_devices):
        from deepspeed_tpu.telemetry.bus import (KIND_COMM_HIERARCHY,
                                                 telemetry_bus)

        X, Y = _data()
        batch = {"x": X, "y": Y}
        eng = _engine({"type": "AdamW", "params": {"lr": 5e-2}},
                      extra={"tpu": {"grad_exchange":
                                     {"deferred": True, "bucket_mb": 1,
                                      "hierarchical": "auto",
                                      "dcn_slices": 2,
                                      "dcn_block": 64}}})
        it = iter(RepeatingLoader([batch]))
        evs = []
        telemetry_bus.subscribe(evs.append)
        try:
            first = float(eng.train_batch(it))  # lazy state init publishes
        finally:
            telemetry_bus.unsubscribe(evs.append)
        assert eng._exchange.mode == "deferred"
        assert eng._exchange.num_slices == 2
        plans = [e for e in evs if e["kind"] == KIND_COMM_HIERARCHY]
        assert len(plans) == 1, [e["kind"] for e in evs]
        assert plans[0]["world"] == 8 and plans[0]["num_slices"] == 2
        assert plans[0]["per_slice"] == 4 and plans[0]["dcn_wire"] == "int8"
        # the inter-slice leg rides the EQuARX int8 wire format
        assert _has_int8_collective(_compiled_step_text(eng, batch))
        losses = [first] + [float(eng.train_batch(it)) for _ in range(99)]
        assert losses[-1] < 0.01 * losses[0], losses[::20]

    @pytest.mark.slow
    def test_tracks_flat_deferred_exchange(self, eight_devices):
        """The hierarchy changes WHERE the reduction happens (and puts the
        1/P DCN shard on an int8 wire); early-training trajectories must
        track the flat deferred exchange closely."""
        X, Y = _data()
        batch = {"x": X, "y": Y}
        runs = {}
        for name, gx in [
            ("flat", {"deferred": True, "bucket_mb": 1}),
            ("hier", {"deferred": True, "bucket_mb": 1,
                      "hierarchical": "on", "dcn_slices": 2,
                      "dcn_block": 64}),
        ]:
            from deepspeed_tpu.parallel import mesh
            mesh.reset_default_topology()
            eng = _engine({"type": "AdamW", "params": {"lr": 1e-2}},
                          extra={"tpu": {"grad_exchange": gx}})
            it = iter(RepeatingLoader([batch]))
            losses = [float(eng.train_batch(it)) for _ in range(12)]
            runs[name] = (losses, _params(eng))
        np.testing.assert_allclose(runs["flat"][0], runs["hier"][0],
                                   rtol=0.05)
        for f, h in zip(runs["flat"][1], runs["hier"][1]):
            np.testing.assert_allclose(f, h, atol=0.05)
