"""chip_smoke.py on the CPU surface: the strict run refuses to start, the
phase functions run at tiny shapes in Pallas interpret mode, and the
compile-cache helper resolves where it says it does."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.utils import compile_cache  # noqa: E402

TINY = dict(n_layer=1, n_embd=32, n_head=2, vocab_size=128)


def test_main_exits_nonzero_on_cpu_before_building_a_model(capfd):
    """JAX_PLATFORMS=cpu (the test environment): the first child prints
    the device line and refuses; no phase runs, no result is printed."""
    assert os.environ.get("JAX_PLATFORMS") == "cpu"
    assert chip_smoke.main() != 0
    out = capfd.readouterr().out
    assert '"platform": "cpu"' in out
    assert '"phase"' not in out and '"ok"' not in out


def test_device_report_refuses_cpu(capsys):
    with pytest.raises(SystemExit, match="not 'tpu'"):
        chip_smoke.device_report()
    assert '"compile_cache_dir": null' in capsys.readouterr().out


def test_phase_kernels_tiny_interpret():
    out = chip_smoke.phase_kernels(
        flash_shapes=((128, 32, 2, 1),), adam_shape=(64, 128),
        splash=(256, 32, 2, 16),
        decode_shapes=((2, 3, 256, 4, 4, 16), (1, 2, 256, 2, 10, 16)),
        retention_shape=(2, 3, 2, 4, 8), ssd_shape=(2, 3, 4, 2, 8, 16),
        gmm_shape=(256, 128, 256, 4), gmm_stack_shape=(2, 64, 128, 128, 4),
        latent_shape=(3, 64, 32, 4, 24, 16, 8, 4, 8),
        held_experts_shape=(32, 32, 16, 16, (2, 2)),
        latent_kernel_shape=(2, 4, 328, 4, 16, 4), dtype=jnp.float32,
        strict=False)
    assert out["ok"] and out["interpret"] and out["n_checks"] == 13


def test_phase_kernels_strict_refuses_interpret_mode():
    with pytest.raises(AssertionError, match="interpret"):
        chip_smoke.phase_kernels(strict=True)


def test_phase_train_tiny():
    out = chip_smoke.phase_train(model="gpt2-125m", seq=64, micro=2,
                                 steps=3, strict=False, **TINY)
    assert out["ok"] and out["last_loss"] < out["first_loss"]
    assert out["mesh"].startswith("MeshTopology(single-device")
    # the step compiles once: not again for the second train_batch, and
    # not again for the HLO / memory analysis the phase reads afterwards
    assert out["train_step_compiles"] == 1
    assert out["compiles_per_train_batch"][1:] == [0, 0]


def test_phase_serve_tiny():
    out = chip_smoke.phase_serve(model="gpt2-125m", seq=128, slots=2,
                                 prompt_range=(5, 20), new_tokens=3,
                                 strict=False, **TINY)
    assert out["ok"] and out["requests"] == 2 and out["matched_generate"]


def test_a_phase_prints_where_its_set_up_went(capsys):
    """After its result line: the build stages' seconds and a row per
    program built, from the program's own log (telemetry/builds.py)."""
    import json

    chip_smoke.run_phase("serve", lambda: chip_smoke.phase_serve(
        model="gpt2-125m", seq=128, slots=2, prompt_range=(5, 40),
        new_tokens=3, strict=False, **TINY))
    result, report = [json.loads(ln) for ln in
                      capsys.readouterr().out.splitlines()
                      if ln.startswith("{")]
    assert result["ok"] and report["phase"] == "serve"
    b = report["builds"]
    assert set(b["stage_seconds"]) == {"trace", "lower", "compile_or_load"}
    assert b["programs_built"] == result["n_compiles"] > 0
    assert b["programs_built"] \
        == len(b["programs"]) + b["quicker_programs"]["count"]
    rows = [dict(zip(b["columns"], row)) for row in b["programs"]]
    prefill = [r for r in rows if r["program"] == "jit(prefill)"]
    assert prefill and all(r["key"] for r in prefill)
    assert all(r["compile_or_load_s"] > 0 and r["since_entry_s"] > 0
               for r in rows)
    assert b["first_dispatch_s"] > 0


def test_phase_four_chip_tiny(eight_devices):
    out = chip_smoke.phase_four_chip(
        model="gpt2-125m", seq=128, micro=2, steps=2, slots=2,
        prompt_range=(5, 20), new_tokens=2, strict=False, **TINY)
    assert out["ok"] and out["zero_stage"] == 3
    assert out["mesh"] == "MeshTopology({'fsdp': 4}, devices=4)"
    assert out["global_batch"] == 8
    # optimizer state is sharded four ways, evenly
    assert len(set(out["opt_state"]["bytes_per_device"].values())) == 1
    assert len(out["opt_state"]["bytes_per_device"]) == 4
    # off the chip build_serving does not refuse, and the server answers
    assert out["server"]["build_serving_refused"] is None
    assert out["server"]["matched_generate"]


class TestCompileCache:
    def test_env_var_is_left_alone(self, monkeypatch, tmp_path):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
        # even on a (pretend) TPU nothing is set in code
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert compile_cache.ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_cpu_backend_gets_no_cache(self, monkeypatch):
        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.ensure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

    def test_tpu_backend_gets_the_in_checkout_dir(self, monkeypatch):
        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = jax.config.jax_compilation_cache_dir
        try:
            got = compile_cache.ensure_compile_cache()
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_same_path_from_another_cwd_and_process(self, tmp_path):
        code = ("from deepspeed_tpu.utils.compile_cache import "
                "default_cache_dir; print(default_cache_dir())")
        env = dict(os.environ, PYTHONPATH=REPO)
        env.pop(compile_cache.CACHE_DIR_ENV, None)
        other = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                               env=env, capture_output=True, text=True,
                               timeout=120, check=True).stdout.split()[-1]
        assert other == compile_cache.default_cache_dir() \
            == os.path.join(REPO, ".jax_cache")
