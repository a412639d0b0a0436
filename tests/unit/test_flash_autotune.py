"""Shape-tuned flash-attention block selection (ops/pallas/autotune.py):
cache hit/miss keyed by (device_kind, shape, dtype), corrupt-cache
fallback, pretuned-entry revalidation, and numerical parity between tuned
and default block sizes on the CPU-interpreted kernel."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import autotune
from deepspeed_tpu.ops.pallas.autotune import (
    PRETUNED,
    cache_key,
    cache_path,
    clear_memory_cache,
    default_candidates,
    get_flash_blocks,
)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune._CACHE_ENV, str(tmp_path / "blocks.json"))
    monkeypatch.delenv(autotune._AUTOTUNE_ENV, raising=False)
    clear_memory_cache()
    yield
    clear_memory_cache()


def _counting_bench(monkeypatch, winner=(64, 64)):
    calls = []

    def fake(t, d, dtype, causal, candidates, **kw):
        calls.append((t, d, jnp.dtype(dtype).name, causal))
        return winner

    monkeypatch.setattr(autotune, "benchmark_candidates", fake)
    return calls


class TestCacheResolution:
    def test_off_by_default_uses_heuristic(self):
        # no cache, no pretuned hit on CPU, autotune off -> the historical
        # largest-divisor default, no disk writes
        assert get_flash_blocks(1024, 128, jnp.float32, True) == (512, 512)
        assert not autotune._mem_cache

    def test_autotune_miss_then_memory_then_disk_hit(self, monkeypatch):
        calls = _counting_bench(monkeypatch)
        got = get_flash_blocks(128, 8, jnp.float32, True, autotune=True,
                               candidates=[(32, 32), (64, 64)])
        assert got == (64, 64) and len(calls) == 1
        # memory hit: no second benchmark
        assert get_flash_blocks(128, 8, jnp.float32, True,
                                autotune=True) == (64, 64)
        assert len(calls) == 1
        # disk hit after dropping the in-process memo
        clear_memory_cache()
        assert get_flash_blocks(128, 8, jnp.float32, True,
                                autotune=True) == (64, 64)
        assert len(calls) == 1
        kind = jax.devices()[0].device_kind
        disk = json.load(open(cache_path()))
        assert disk == {cache_key(kind, 128, 8, jnp.float32, True):
                        [64, 64]}

    def test_key_includes_shape_dtype_and_causal(self, monkeypatch):
        calls = _counting_bench(monkeypatch)
        get_flash_blocks(128, 8, jnp.float32, True, autotune=True)
        get_flash_blocks(256, 8, jnp.float32, True, autotune=True)   # seq
        get_flash_blocks(128, 16, jnp.float32, True, autotune=True)  # dim
        get_flash_blocks(128, 8, jnp.bfloat16, True, autotune=True)  # dtype
        get_flash_blocks(128, 8, jnp.float32, False, autotune=True)  # mask
        assert len(calls) == 5 and len(set(calls)) == 5
        get_flash_blocks(128, 8, jnp.float32, True, autotune=True)
        assert len(calls) == 5  # every repeat is a hit

    def test_corrupt_cache_warns_and_falls_back(self):
        with open(cache_path(), "w") as f:
            f.write("{not json")
        with pytest.warns(RuntimeWarning, match="corrupt"):
            got = get_flash_blocks(128, 8, jnp.float32, True)
        assert got == (128, 128)  # heuristic fallback, no crash

    def test_corrupt_entry_revalidated_against_shape(self):
        # a stale/hand-edited entry that does not divide the current seq
        # must be ignored, not launched
        kind = jax.devices()[0].device_kind
        with open(cache_path(), "w") as f:
            json.dump({cache_key(kind, 128, 8, jnp.float32, True):
                       [96, "x"]}, f)
        assert get_flash_blocks(128, 8, jnp.float32, True) == (128, 128)

    def test_env_flag_enables_autotune(self, monkeypatch):
        calls = _counting_bench(monkeypatch, winner=(32, 32))
        monkeypatch.setenv(autotune._AUTOTUNE_ENV, "1")
        assert get_flash_blocks(128, 8, jnp.float32, True) == (32, 32)
        assert len(calls) == 1


class TestPretuned:
    def test_shipped_entries_cover_the_13b_shapes(self):
        # what the v5e measured (PERF.md section 6, PR 45): a strip of the
        # whole sequence a kernel, in KERNELS' order; every other entry is
        # a seed never run on its chip, one pair for all three
        measured = {(kind, seq, "bfloat16"): (
                        (seq, 512, g), (seq, 512, g), (512, keys, g))
                    for kind in ("TPU v5e", "TPU v5 lite")
                    for seq, g, keys in ((1024, 256, 1024),
                                         (4096, 512, 2048))}
        for kind in ("TPU v4", "TPU v5 lite", "TPU v5e", "TPU v5p",
                     "TPU v6e"):
            for dt in ("bfloat16", "float32"):
                for seq in (1024, 2048):
                    # 1.3B: n_embd=2048 / 16 heads -> head_dim 128
                    assert PRETUNED[(kind, seq, 128, dt, True)] == \
                        measured.get((kind, seq, dt),
                                     ((512, 256, None),) * 3)
        assert PRETUNED[("TPU v5 lite", 4096, 128, "bfloat16", True)] == \
            measured[("TPU v5 lite", 4096, "bfloat16")]

    def test_measured_entries_name_each_kernels_own_blocks(self,
                                                           monkeypatch):
        """On the v5e the 1.3B shape resolves to the measured schedule,
        with ``pretuned`` as its source; another chip keeps the pair."""
        from deepspeed_tpu.ops.pallas.flash_attention import KERNELS

        class Device:
            device_kind = "TPU v5 lite"

        monkeypatch.setattr(autotune.jax, "devices", lambda: [Device()])
        wanted, source = autotune.get_flash_schedule(1024, 128,
                                                     jnp.bfloat16, True)
        assert source == "pretuned"
        assert wanted == dict(zip(KERNELS, ((1024, 512, 256),
                                            (1024, 512, 256),
                                            (512, 1024, 256))))
        assert get_flash_blocks(1024, 128, jnp.bfloat16, True) == (1024, 512)
        Device.device_kind = "TPU v4"
        wanted, source = autotune.get_flash_schedule(1024, 128,
                                                     jnp.bfloat16, True)
        assert source == "pretuned"
        assert set(wanted.values()) == {(512, 256, None)}

    def test_entries_are_valid_launches(self):
        for (kind, seq, d, dt, causal), kernels in PRETUNED.items():
            assert len(kernels) == 3
            for blocks in kernels:
                assert autotune._valid(blocks, seq) == blocks[:2], (kind,
                                                                    seq)

    def test_candidate_grid_is_divisor_filtered(self):
        for bq, bk in default_candidates(1024):
            assert 1024 % bq == 0 and 1024 % bk == 0
            assert bq * bk <= 512 * 1024
        assert default_candidates(96)  # short seq still has candidates


class TestNumericalParity:
    def test_tuned_blocks_match_default_blocks(self):
        """Block sizes change the schedule, not the math: the interpreted
        kernel must produce the same output and gradients for tuned vs
        default blocks (fp32, tight tolerance)."""
        rng = np.random.RandomState(0)
        t, d = 128, 8
        q, k, v = (jnp.asarray(rng.randn(1, t, 2, d), jnp.float32)
                   for _ in range(3))

        def loss(q, k, v, bq, bk):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=bq, block_k=bk) ** 2)

        ref = flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=128)
        gref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 128, 128)
        for bq, bk in [(32, 32), (64, 32), (32, 64)]:
            out = flash_attention(q, k, v, causal=True, block_q=bq,
                                  block_k=bk)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)
            g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, bq, bk)
            for a, b in zip(g, gref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-4)

    def test_live_benchmark_returns_runnable_winner(self):
        """The real benchmark path (no monkeypatch): tiny candidate grid on
        the interpreted kernel; the winner must come from the grid and be
        persisted."""
        got = get_flash_blocks(64, 4, jnp.float32, True, autotune=True,
                               candidates=[(32, 32), (64, 64)])
        assert got in ((32, 32), (64, 64))
        kind = jax.devices()[0].device_kind
        disk = json.load(open(cache_path()))
        assert disk[cache_key(kind, 64, 4, jnp.float32, True)] == list(got)

    def test_resolver_feeds_flash_attention_defaults(self, monkeypatch):
        """flash_attention with no explicit blocks consults the resolver;
        a cached winner changes the launch (observed via the resolver
        memo), while explicit blocks bypass it."""
        seen = []
        real = autotune.get_flash_schedule

        def spy(*a, **kw):
            seen.append(a)
            return real(*a, **kw)

        monkeypatch.setattr(
            "deepspeed_tpu.ops.pallas.autotune.get_flash_schedule", spy)
        rng = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rng.randn(1, 64, 2, 4), jnp.float32)
                   for _ in range(3))
        flash_attention(q, k, v, causal=True)
        assert len(seen) == 1 and seen[0][:2] == (64, 4)
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        assert len(seen) == 1  # explicit blocks bypass the resolver
