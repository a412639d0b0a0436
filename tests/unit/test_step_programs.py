"""The step programs of ``runtime/step.py``: the fused ``train_step`` and the
split ``fwd_bwd`` + ``apply`` are one step written once, with GSPMD's sum
and with an explicit exchange (``runtime/grad_exchange.py``), and an
overflowing batch goes through the one ``guarded_update`` leaving every
piece of state as it was."""

import ast
import pathlib

import jax
import numpy as np
import pytest

import deepspeed_tpu.runtime as runtime_pkg
from deepspeed_tpu.parallel import mesh
from deepspeed_tpu.runtime import config_utils

from tests.unit.test_engine_compressed import _data, _engine, _lowered_step

ADAMW = {"type": "AdamW", "params": {"lr": 5e-2}}
ONEBIT = {"type": "OnebitAdam", "params": {"lr": 5e-2, "freeze_step": 2}}
INT8 = {"communication_data_type": "int8"}
FP16 = {"fp16": {"enabled": True, "initial_scale_power": 4, "hysteresis": 1}}
# name -> (optimizer block, extra config, the exchange's mode)
FAMILIES = {
    "plain": (ADAMW, {}, None),
    "int8": (ADAMW, INT8, "int8"),
    "onebit": (ONEBIT, {}, "onebit"),
}


def _state(eng):
    return [np.asarray(x) for x in
            jax.tree.leaves((eng.params, eng._opt_state))]


def _split_step(eng, batch):
    loss = eng.forward(batch)
    eng.backward()
    eng.step()
    return float(loss)


@pytest.mark.parametrize("family", ["plain", "int8"])
def test_fused_step_is_fwd_bwd_then_apply(eight_devices, family):
    """At gas 1, two steps of ``train_step`` and two of ``fwd_bwd`` +
    ``apply`` leave the same parameters, optimizer state (error-feedback
    buffers included) and losses."""
    opt, extra, mode = FAMILIES[family]
    batch = dict(zip("xy", _data()))
    runs = {}
    for path in ("fused", "split"):
        mesh.reset_default_topology()
        eng = _engine(opt, extra=extra)
        assert getattr(eng._exchange, "mode", None) == mode
        if path == "fused":
            losses = [float(eng.train_batch(iter([batch])))
                      for _ in range(2)]
            assert eng._fwd_bwd_fn is None and eng._train_step_fn is not None
        else:
            losses = [_split_step(eng, batch) for _ in range(2)]
            assert eng._train_step_fn is None and eng._apply_fn is not None
        runs[path] = (losses, _state(eng))
    np.testing.assert_allclose(runs["fused"][0], runs["split"][0], rtol=1e-6)
    assert len(runs["fused"][1]) == len(runs["split"][1])
    for fused, split in zip(runs["fused"][1], runs["split"][1]):
        np.testing.assert_allclose(fused, split, rtol=1e-5, atol=1e-7)


# what reads the two-program split, each as a user's config turns it on
SPLIT_ARMS = {
    "accumulation": {"gradient_accumulation_steps": 2},
    "wall_clock_breakdown": {"wall_clock_breakdown": True},
    "flops_profiler": {"flops_profiler": {"enabled": True,
                                          "profile_step": 10 ** 9}},
    "offloaded_optimizer": {"zero_optimization": {
        "stage": 0, "offload_optimizer": {"device": "cpu"}}},
}


@pytest.mark.parametrize("arm", list(SPLIT_ARMS))
def test_train_batch_leaves_the_fused_step_to_what_reads_the_split(
        eight_devices, arm):
    """``train_batch``'s gate: the default arm (the test above) runs one
    fused program; accumulation, ``wall_clock_breakdown``, the FLOPs
    profiler and an offloaded optimizer each run ``fwd_bwd`` and the
    update apart."""
    mesh.reset_default_topology()
    eng = _engine(ADAMW, extra=SPLIT_ARMS[arm])
    batch = dict(zip("xy", _data()))
    loss = eng.train_batch(iter([batch] * eng.gradient_accumulation_steps))
    assert np.isfinite(float(loss))
    assert eng._train_step_fn is None and eng._fwd_bwd_fn is not None
    assert eng.global_steps == 1


def test_a_removed_block_is_an_unknown_key(eight_devices, monkeypatch):
    """``tpu.step_autotune`` went with its tuner (PR 61): a config that
    still carries it is warned once, as for any unknown key of a block,
    and gets the engine it got with the block off: the fused step, the
    same program."""
    warned = []
    monkeypatch.setattr(config_utils.logger, "warning",
                        lambda *a, **kw: warned.append(a))
    batch = dict(zip("xy", _data()))
    texts = []
    for extra in ({}, {"tpu": {"step_autotune": {
            "enabled": True, "fused_step": "off", "apply_micro_batch": True,
            "micro_batches": [4]}}}):
        mesh.reset_default_topology()
        eng = _engine(ADAMW, extra=extra)
        eng.train_batch(iter([batch]))
        assert eng._train_step_fn is not None and eng._fwd_bwd_fn is None
        assert eng.train_micro_batch_size_per_gpu == 8
        texts.append(_lowered_step(eng, batch).as_text())
    assert texts[0] == texts[1]
    assert [a[1:] for a in warned if "step_autotune" in a] == [
        ("TpuConfig", "step_autotune")]


@pytest.mark.parametrize("module", ["step", "grad_exchange"])
def test_the_arrow_points_one_way(module):
    """``runtime/engine.py`` builds its programs from these modules; neither
    imports it back (a step is a function of a ``StepSpec``, not of an
    engine)."""
    path = pathlib.Path(runtime_pkg.__file__).parent / f"{module}.py"
    imported = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
            imported.update(f"{node.module}.{a.name}" for a in node.names)
    assert not [m for m in imported
                if m.startswith("deepspeed_tpu.runtime.engine")
                or m.startswith("deepspeed_tpu.runtime.pipe")], imported


@pytest.mark.parametrize("path", ["fused", "split"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_overflow_leaves_all_state_untouched(eight_devices, family, path):
    """An overflowing batch cond-skips the update — and the exchange — in
    the one ``guarded_update``: parameters, the WHOLE optimizer state
    (count, moments, worker and server error feedback) are bit for bit what
    they were, the step is counted as skipped and the loss scale halves."""
    opt, extra, _ = FAMILIES[family]
    X, Y = _data()
    batch = {"x": X, "y": Y}
    eng = _engine(opt, extra=dict(extra, **FP16))

    def take(b):
        if path == "fused":
            eng.train_batch(iter([b]))
        else:
            _split_step(eng, b)

    for _ in range(4):  # past freeze_step: the 1-bit residuals are live
        take(batch)
    before = _state(eng)
    if family != "plain":
        residuals = jax.tree.leaves(
            eng._opt_state.worker_error if family == "onebit"
            else eng._opt_state[1])
        assert max(np.abs(np.asarray(e)).max() for e in residuals) > 0
    take({"x": np.full_like(X, np.inf), "y": Y})
    assert eng.skipped_steps == 1
    assert eng.loss_scale == 2.0 ** 3
    for b, a in zip(before, _state(eng)):
        np.testing.assert_array_equal(b, a)
    take(batch)
    assert eng.skipped_steps == 1
    assert any((b != a).any() for b, a in zip(before, _state(eng)))
