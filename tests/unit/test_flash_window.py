"""The flash kernels under a window (ops/pallas/flash_attention.py
``window_flash_*``): key j for query i where ``0 <= i - j < window``, the
window's edge square cut in granule slices as the diagonal's is, K and V
(dK/dV: q, do, lse, delta) held as the band a strip reads, grouped queries
reading their KV head where it lies. Interpret mode, small shapes whose
band is not the whole sequence, windows smaller than, equal to and larger
than a block and not a multiple of the wanted granule; the tile counts
against a count of the scores a schedule computes; and without a window
the kernels that were there, to the letter of their jaxpr."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry.bus import KIND_FLASH_PLAN, telemetry_bus

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
FWD, DQ, DKV = fa.KERNELS


def _reference(q, k, v, window):
    """Float32 attention over ``[b, t, h, d]`` / ``[b, t, h_kv, d]``."""
    t, h, d = q.shape[1:]
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (ahead >= 0) if window is None \
        else (ahead >= 0) & (ahead < window)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v)


# (t, heads, kv heads, window, block_q, block_k): the window under, at and
# over a block; no multiple of the granule the blocks would take (12 under
# blocks of 16: the fitting cuts strips of 4); one position; one strip short
# of the sequence; four query heads to a KV head, and seven
CASES = {
    "window_under_block": (64, 4, 2, 8, 16, 16),
    "window_is_block": (64, 4, 2, 16, 16, 8),
    "window_over_block": (96, 4, 1, 48, 16, 8),
    "window_no_granule_multiple": (64, 6, 2, 12, 16, 16),
    "window_of_one": (64, 2, 1, 1, 32, 16),
    "window_a_strip_short": (64, 2, 2, 48, 32, 16),
    "seven_heads_a_group": (64, 7, 1, 32, 16, 16),
    "no_window_grouped": (64, 4, 2, None, 16, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_gradients_match_float32_attention(case):
    t, h, hkv, window, bq, bk = CASES[case]
    rng = np.random.RandomState(len(case))
    q, w = (jnp.asarray(rng.randn(2, t, h, 8), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, t, hkv, 8), jnp.float32)
            for _ in range(2))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, window=window, block_q=bq,
                                  block_k=bk)

    np.testing.assert_allclose(flash(q, k, v), _reference(q, k, v, window),
                               atol=2e-6)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_reference(*a, window) * w).sum(),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-6, err_msg="d" + name)


def test_the_windows_edge_to_the_position():
    """One key with a value apart: the queries that see it are exactly
    those at ``0 <= i - j < window``."""
    t, window, j = 64, 16, 20
    q = jnp.zeros((1, t, 1, 8), jnp.float32)
    v = jnp.zeros((1, t, 1, 8), jnp.float32).at[0, j].set(1.0)
    o = fa.flash_attention(q, q, v, window=window, block_q=16, block_k=8)
    seen = np.asarray(o[0, :, 0, 0]) > 0
    assert seen.nonzero()[0].tolist() == list(range(j, j + window))
    # uniform scores: the key's weight is one over the keys a query sees
    np.testing.assert_allclose(
        o[0, j:j + window, 0, 0],
        1.0 / np.minimum(np.arange(j, j + window) + 1, window), rtol=1e-6)


def _computed(kernel, t, blocks, window):
    """Scores a schedule computes, counted strip by strip as the kernels
    walk them: the squares' granule slices and the loop's tiles."""
    strip, g = blocks.strip(kernel), blocks.granule
    tile = blocks.block_q + blocks.block_k - strip
    square = sum(g * g * (r + 1) for r in range(strip // g))
    total = 0
    for start in range(0, t, strip):
        first, n = fa.interior_tiles(kernel, t, True, blocks, start, window)
        total += square + n * tile * strip
        edge = start + window + strip <= t if kernel == DKV \
            else start >= window
        if window is not None and edge:
            total += square
        # the loop's tiles lie between the squares, inside the sequence
        if kernel == DKV:
            assert first == start + strip and first + n * tile <= min(
                t, start + (window or t))
        elif n:
            assert first >= 0 and first + n * tile == start
            assert window is None or first == max(0, start + strip - window)
    return total


@pytest.mark.parametrize("kernel", fa.KERNELS)
@pytest.mark.parametrize("t,window,wanted", [
    (256, 64, (64, 32, 32)), (256, 128, (64, 64, 16)),
    (512, 128, (128, 64, 64)), (384, 96, (64, 32, 32)),
    (256, 48, (64, 64, 32))])
def test_tile_counts_against_a_count_of_the_walk(kernel, t, window, wanted):
    bq, bk = (wanted[1], wanted[0]) if kernel == DKV else wanted[:2]
    blocks = fa.fit_blocks(kernel, t, True, bq, bk, wanted[2], window=window)
    assert window % blocks.strip(kernel) == 0
    counts = fa.tile_counts(kernel, t, True, blocks, window)
    tile = blocks.block_q * blocks.block_k
    assert counts["tiles_computed"] * tile == _computed(
        kernel, t, blocks, window)
    # the band, by brute force over every pair, less half the band's
    # last diagonal (as the causal count t * t / 2 leaves half of its own)
    ahead = np.arange(t)[:, None] - np.arange(t)[None, :]
    pairs = ((ahead >= 0) & (ahead < window)).sum()
    assert counts["tiles_needed"] * tile == pytest.approx(
        pairs - window / 2)
    assert counts["tiles_needed"] < counts["tiles_computed"] \
        < fa.tile_counts(kernel, t, True, blocks)["tiles_computed"] + \
        2 * t * blocks.granule / tile
    squares = t // blocks.strip(kernel) * 2 - window // blocks.strip(kernel)
    assert counts["tiles_masked"] * tile == squares * blocks.strip(
        kernel) * blocks.granule


def test_a_window_of_the_whole_sequence_is_the_causal_mask():
    q = jnp.zeros((1, 64, 2, 8), jnp.float32)
    plain = jax.make_jaxpr(lambda q: fa.flash_attention(
        q, q, q, block_q=16, block_k=16))(q)
    for window in (64, 100):
        assert str(jax.make_jaxpr(lambda q: fa.flash_attention(
            q, q, q, block_q=16, block_k=16, window=window))(q)) == str(plain)


def test_refusals():
    q = jnp.zeros((1, 64, 4, 8), jnp.float32)
    kv = jnp.zeros((1, 64, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="segment_ids"):
        fa.flash_attention(q, kv, kv,
                           segment_ids=jnp.ones((1, 64), jnp.int32))
    with pytest.raises(ValueError, match="heads"):
        fa.flash_attention(q[:, :, :3], kv, kv)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, window=0)


def test_names_and_plan_carry_the_window():
    events = []

    def on(ev):
        if ev["kind"] == KIND_FLASH_PLAN:
            events.append(ev)

    telemetry_bus.subscribe(on)
    try:
        q = jnp.zeros((1, 128, 2, 8), jnp.float32)
        text = jax.jit(jax.grad(lambda q: fa.flash_attention(
            q, q, q, window=32, block_q=32, block_k=16).sum())
        ).lower(q).as_text()
    finally:
        telemetry_bus.unsubscribe(on)
    assert [fa.kernel_name(k, 32) for k in fa.KERNELS] == [
        "window_flash_fwd", "window_flash_bwd_dq", "window_flash_bwd_dkv"]
    assert [fa.kernel_name(k) for k in fa.KERNELS] == list(fa.KERNELS)
    (ev,) = events
    assert ev["window"] == 32 and ev["t"] == 128
    fwd = ev["kernels"][FWD]
    assert (fwd["block_q"], fwd["block_k"], fwd["granule"]) == (32, 16, 32)
    # a strip of 32 under a window of 32: one edge square and one diagonal
    # square each, no tile between; the first strip has no edge
    assert fwd["tiles_computed"] * 32 * 16 == 7 * 32 * 32
    assert "tpu_custom_call" not in text    # interpreted here; the names
    # reach the HLO on the chip (tests/unit/test_grouped_matmul.py compiles)


# --- without a window: the kernels that were there -------------------------
PARENT_JAXPRS = "tests/unit/data/flash_jaxpr_hashes.json"


@pytest.mark.parametrize("t,bq,bk", [(128, 64, 32), (256, 256, 64)])
def test_without_a_window_the_kernels_are_the_parents(t, bq, bk):
    """The forward and backward jaxprs of a call without a window, hashed,
    against what the parent commit's module gave at the same two shapes
    (recorded by this test's ``__main__`` run from the parent's
    checkout)."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(here, PARENT_JAXPRS), encoding="utf-8") as f:
        recorded = json.load(f)
    assert recorded["%d,%d,%d" % (t, bq, bk)] == _jaxpr_hash(t, bq, bk)


def _jaxpr_hash(t, bq, bk):
    import hashlib

    q = jnp.zeros((2, t, 2, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda q, k, v: (
        fa.flash_attention(q, k, v, block_q=bq, block_k=bk) ** 2).sum(),
        (0, 1, 2)))(q, q, q)
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()


if __name__ == "__main__":      # run from a checkout's root to record it
    import json

    print(json.dumps({"%d,%d,%d" % c: _jaxpr_hash(*c)
                      for c in ((128, 64, 32), (256, 256, 64))}, indent=1))
