"""The one way into a flash schedule (ops/pallas/autotune.py): the table
``PRETUNED`` where it has the chip and the shape, else the divisor
heuristic; nothing a run left behind and no environment variable has a
say. Literal entries for the benchmark cells' shapes, the heuristic for
the shapes the table lacks, the memo, and numerical parity between block
sizes on the CPU-interpreted kernel."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.flash_sweep import default_candidates, kernel_candidates
from deepspeed_tpu.models.transformer_lm import GPTConfig
from deepspeed_tpu.ops.pallas import autotune
from deepspeed_tpu.ops.pallas.autotune import (
    PRETUNED,
    clear_memory_cache,
    get_flash_schedule,
)
from deepspeed_tpu.ops.pallas.common import largest_divisor_block
from deepspeed_tpu.ops.pallas.flash_attention import (
    KERNELS,
    fit_blocks,
    flash_attention,
    resolve_schedule,
)

V5E_KINDS = ("TPU v5 lite", "TPU v5e")


@pytest.fixture(autouse=True)
def _fresh_memo():
    clear_memory_cache()
    yield
    clear_memory_cache()


def _on_chip(monkeypatch, kind):
    """The table is keyed by ``device_kind``: answer as that chip would."""

    class Device:
        device_kind = kind

    monkeypatch.setattr(autotune.jax, "devices", lambda: [Device()])


def _assert_valid_launch(kernel, t, blocks):
    """What ``fit_blocks`` promises of a causal launch: blocks that divide
    the sequence, the loop's tile and the granule dividing the strip."""
    strip = blocks.strip(kernel)
    tile = blocks.block_q + blocks.block_k - strip
    assert t % blocks.block_q == 0 and t % blocks.block_k == 0
    assert strip % tile == 0 and strip % blocks.granule == 0


def _lowered_flash_text():
    q = jnp.zeros((1, 128, 2, 8), jnp.float32)
    return jax.jit(lambda q: flash_attention(q, q, q, causal=True)
                   ).lower(q).as_text()


# (seq, head_dim, dtype) -> forward, dQ, dK/dV as the table holds them: the
# two 1.3B training cells (bfloat16 compute at 1,024 positions), the OLMoE
# cell (4,096), and a seed no chip ran
CELL_SHAPES = {
    "gpt-1.3b": ((1024, 128, "bfloat16"),
                 ((1024, 512, 256), (1024, 512, 256), (512, 1024, 256))),
    "olmoe": ((4096, 128, "bfloat16"),
              ((4096, 512, 512), (4096, 512, 512), (512, 2048, 512))),
    "seed-f32": ((1024, 128, "float32"), ((512, 256, None),) * 3),
}


class TestTable:
    @pytest.mark.parametrize("kind", V5E_KINDS)
    @pytest.mark.parametrize("cell", list(CELL_SHAPES))
    def test_the_cells_shapes_resolve_to_the_tables_triples(
            self, monkeypatch, cell, kind):
        (t, d, dtype), triples = CELL_SHAPES[cell]
        _on_chip(monkeypatch, kind)
        wanted, source = get_flash_schedule(t, d, dtype, True)
        assert source == "pretuned"
        assert wanted == dict(zip(KERNELS, triples))
        for kernel, triple in wanted.items():
            fitted = fit_blocks(kernel, t, True, *triple)
            _assert_valid_launch(kernel, t, fitted)
            if triple[2] is not None:  # a measured entry runs as measured
                assert tuple(fitted) == triple

    def test_shipped_entries_cover_the_13b_shapes(self):
        # what the v5e measured (PERF.md section 6, PR 45): a strip of the
        # whole sequence a kernel, in KERNELS' order; every other entry is
        # a seed never run on its chip, one pair for all three
        measured = {(kind, seq, "bfloat16"): (
                        (seq, 512, g), (seq, 512, g), (512, keys, g))
                    for kind in V5E_KINDS
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

    def test_another_chip_keeps_the_seed_pair(self, monkeypatch):
        """The measured triples are the v5e's alone: on a v4 the same
        shape resolves, ``pretuned`` too, to the seed."""
        _on_chip(monkeypatch, "TPU v4")
        wanted, source = get_flash_schedule(1024, 128, jnp.bfloat16, True)
        assert source == "pretuned"
        assert set(wanted.values()) == {(512, 256, None)}

    def test_entries_are_valid_launches(self):
        for (kind, seq, d, dt, causal, *window), kernels in PRETUNED.items():
            assert len(kernels) == 3 and len(window) <= 1
            for blocks in kernels:
                assert autotune._valid(blocks, seq) == blocks[:2], (kind,
                                                                    seq)
            for kernel, blocks in zip(KERNELS, kernels):
                # a row under a window is launched as it is written
                assert not window or tuple(fit_blocks(
                    kernel, seq, True, *blocks, window=window[0])) == blocks

    def test_an_entry_that_does_not_divide_the_shape_is_not_launched(
            self, monkeypatch):
        """A hand-edited entry is held to the shape like any other: the
        heuristic answers, and nothing is kept of the bad one."""
        _on_chip(monkeypatch, "TPU v5 lite")
        key = ("TPU v5 lite", 1024, 128, "bfloat16", True)
        monkeypatch.setitem(PRETUNED, key, ((96, "x", None),) * 3)
        wanted, source = get_flash_schedule(1024, 128, jnp.bfloat16, True)
        assert source == "heuristic"
        assert set(wanted.values()) == {(512, 512, None)}
        assert not autotune._mem_cache

    def test_one_explicit_block_keeps_the_tables_other(self, monkeypatch):
        _on_chip(monkeypatch, "TPU v5 lite")
        schedule, source = resolve_schedule(1024, 128, jnp.bfloat16, True,
                                            block_q=256)
        assert source == "pretuned"
        assert [tuple(b) for b in schedule] == [
            (256, 256, 256), (256, 256, 256), (256, 1024, 256)]


# 1,024 at a head of 64 and the unmasked 1,024 are on no chip's rows either
LACKING = [(128, 128, True), (384, 128, True), (640, 128, True),
           (1536, 128, True), (32768, 128, True), (777, 128, True),
           (1024, 64, True), (1024, 128, False)]


class TestHeuristic:
    @pytest.mark.parametrize("t,d,causal", LACKING,
                             ids=[f"{t}x{d}{'' if c else '-unmasked'}"
                                  for t, d, c in LACKING])
    def test_shapes_the_table_lacks(self, monkeypatch, t, d, causal):
        """On the chip whose rows are measured, a shape with no row gets
        the ``largest_divisor_block`` pair for all three kernels."""
        _on_chip(monkeypatch, "TPU v5 lite")
        wanted, source = get_flash_schedule(t, d, jnp.bfloat16, causal)
        assert source == "heuristic"
        block = largest_divisor_block(t, 512)
        assert wanted == dict.fromkeys(KERNELS, (block, block, None))
        for kernel, triple in wanted.items():
            _assert_valid_launch(kernel, t,
                                 fit_blocks(kernel, t, True, *triple))

    def test_off_the_tables_chips_the_heuristic_answers(self):
        # no row for the CPU: the historical largest-divisor default, and
        # nothing kept
        wanted, source = get_flash_schedule(1024, 128, jnp.float32, True)
        assert source == "heuristic"
        assert set(wanted.values()) == {(512, 512, None)}
        assert not autotune._mem_cache


class TestNothingElseHasASay:
    # the four variables the tuners read before PR 61, each set as a user
    # of theirs would have set it
    @pytest.mark.parametrize("name,value", [
        ("DS_TPU_FLASH_AUTOTUNE", "1"),
        ("DS_TPU_PALLAS_CACHE", "blocks.json"),
        ("DS_TPU_STEP_AUTOTUNE", "1"),
        ("DS_TPU_STEP_AUTOTUNE_CACHE", "step_configs.json"),
    ])
    def test_the_environment(self, monkeypatch, tmp_path, name, value):
        without = get_flash_schedule(128, 8, jnp.float32, True), \
            _lowered_flash_text()
        clear_memory_cache()
        if value.endswith(".json"):
            # a file that names this very shape's key, as a tuner's run
            # left it
            path = tmp_path / value
            kind = jax.devices()[0].device_kind
            path.write_text('{"%s|128|8|float32|True": [32, 32]}' % kind)
            value = str(path)
        monkeypatch.setenv(name, value)
        assert (get_flash_schedule(128, 8, jnp.float32, True),
                _lowered_flash_text()) == without
        assert without[0] == (dict.fromkeys(KERNELS, (128, 128, None)),
                              "heuristic")

    def test_the_shape_is_all_the_resolver_takes(self):
        assert list(inspect.signature(get_flash_schedule).parameters) == [
            "t", "d", "dtype", "causal", "window"]
        for removed in ("get_flash_blocks", "benchmark_candidates",
                        "cache_path", "cache_key"):
            assert not hasattr(autotune, removed)

    def test_removed_names_are_type_errors(self):
        q = jnp.zeros((1, 64, 2, 4), jnp.float32)
        with pytest.raises(TypeError, match="autotune"):
            flash_attention(q, q, q, causal=True, autotune=True)
        with pytest.raises(TypeError, match="flash_autotune"):
            GPTConfig(flash_autotune=True)


class TestMemo:
    @pytest.fixture
    def reads(self, monkeypatch):
        """The table's lookups, counted."""
        _on_chip(monkeypatch, "TPU v5 lite")
        seen = []

        class Counted(dict):
            def get(self, key, default=None):
                seen.append(key)
                return super().get(key, default)

        monkeypatch.setattr(autotune, "PRETUNED", Counted(PRETUNED))
        return seen

    def test_a_second_resolution_reads_the_table_no_more(self, reads):
        first = get_flash_schedule(1024, 128, jnp.bfloat16, True)
        assert get_flash_schedule(1024, 128, "bfloat16", True) == first
        assert reads == [("TPU v5 lite", 1024, 128, "bfloat16", True)]
        get_flash_schedule(4096, 128, jnp.bfloat16, True)  # another key
        assert len(reads) == 2

    def test_cleared_the_next_one_reads_it_again(self, reads):
        first = get_flash_schedule(1024, 128, jnp.bfloat16, True)
        clear_memory_cache()
        assert get_flash_schedule(1024, 128, jnp.bfloat16, True) == first
        assert len(reads) == 2


class TestSweepCandidates:
    """``benchmarks/flash_sweep.py --kernels`` is how an entry is made."""

    def test_candidate_grid_is_divisor_filtered(self):
        for bq, bk in default_candidates(1024):
            assert 1024 % bq == 0 and 1024 % bk == 0
            assert bq * bk <= 512 * 1024
        assert default_candidates(96)  # short seq still has candidates

    @pytest.mark.parametrize("t", [1024, 4096])
    def test_the_sweep_holds_what_the_table_chose(self, t):
        """Strips of the whole sequence are among the candidates, each
        with every granule, once."""
        grid = kernel_candidates(t)
        assert len(grid) == len(set(grid))
        for triples in (PRETUNED[(kind, t, 128, "bfloat16", True)]
                        for kind in V5E_KINDS):
            for bq, bk, granule in triples:
                assert (bq, bk, granule) in grid or (
                    (t, 512, granule) in grid and (512, t, granule) in grid)


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

    def test_resolver_feeds_flash_attention_defaults(self, monkeypatch):
        """flash_attention with no explicit blocks consults the resolver,
        while explicit blocks bypass it."""
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
