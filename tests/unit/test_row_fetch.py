"""The row-fetch kernels (``ops/pallas/row_fetch.py``) in interpret mode on
the CPU, against ``jnp.take`` and the MoE layer's XLA forms
(``moe/sharded_moe.py`` without ``n_live``): the same rows, the same
float32 sum over k, the same single rounding, and nothing read from a row
at or past ``n_live`` (those rows are NaN here)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import experts as experts_mod
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.ops.pallas import row_fetch as rf

TOKENS, WIDTH = 64, 256
KS = [4, 6, 8]
DTYPES = [jnp.bfloat16, jnp.float32]


def live_counts(k):
    """0, 1, a tile boundary - 1, on it, + 1, and every row."""
    pairs = TOKENS * k
    tile = rf._row_tile(pairs if pairs > rf._ROW_TILE else pairs // 2)
    return [0, 1, tile - 1, tile, tile + 1, pairs]


CASES = [(k, n) for k in KS for n in live_counts(k)]
# the gradients and the scaled rows at one k: the same kernels, and a case
# is a second of compiling for the interpreter
FEWER = [(k, n) for k, n in CASES if k == 6]


def row_tile(k):
    """The tile the cases' boundaries are of: k = 4's 256 pairs would be
    one tile, so they are moved in two."""
    return live_counts(k)[3]


def routed(k, n_live, dtype, seed=0):
    """A layer's sorted pairs with ``n_live`` of them routed here: token
    rows, the sort's two permutations, the pairs' weights (0 elsewhere),
    expert outputs (zeros from ``n_live`` on as the grouped matmuls write
    them, and the same with NaN there)."""
    rng = np.random.default_rng(seed)
    pairs = TOKENS * k
    here = np.zeros(pairs, bool)
    here[rng.permutation(pairs)[:n_live]] = True
    order, inverse = sharded_moe.sort_by_expert(
        jnp.asarray(np.where(here, 0, 1).reshape(TOKENS, k)))
    tokens = jnp.asarray(rng.standard_normal((TOKENS, WIDTH)), dtype)
    rows = jnp.asarray(rng.standard_normal((pairs, WIDTH)), dtype)
    past = jnp.arange(pairs)[:, None] >= n_live
    weights = jnp.asarray(
        np.where(here, rng.random(pairs), 0.0).reshape(TOKENS, k),
        jnp.float32)
    return dict(tokens=tokens, order=order, inverse=inverse, weights=weights,
                rows=jnp.where(past, 0, rows),
                poisoned=jnp.where(past, jnp.nan, rows),
                n_live=jnp.int32(n_live), past=past)


def zeroed_to(n_live, block, rows):
    """The end of the block that holds row ``n_live``: a whole block of
    zeros where ``n_live`` is a block's edge (a trailing group of no rows
    has its one visit in that block, and the matrices' gradient multiplies
    what it finds there by zero)."""
    return min(rows, (n_live // block + 1) * block)


def f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("k,n_live", CASES)
def test_fetch_rows_is_take_on_the_live_rows_and_zeros_to_the_tiles_end(
        k, n_live, dtype):
    r = routed(k, n_live, dtype)
    tile = row_tile(k)
    out = rf.fetch_rows(r["tokens"], r["order"] // k, r["n_live"], tile=tile)
    want = jnp.take(r["tokens"], r["order"] // k, axis=0)
    assert np.array_equal(f32(out[:n_live]), f32(want[:n_live]))
    assert not f32(out[n_live:zeroed_to(n_live, tile, TOKENS * k)]).any()
    # every row live: the same call without the count
    if n_live == TOKENS * k:
        assert np.array_equal(f32(rf.fetch_rows(
            r["tokens"], r["order"] // k)), f32(want))


@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("k,n_live", FEWER)
def test_scaled_rows_are_rounded_once_and_zeroed_to_the_consumers_tile(
        k, n_live, dtype):
    r = routed(k, n_live, dtype)
    pairs = TOKENS * k
    tile = row_tile(k)
    zero_to = 2 * tile if pairs % (2 * tile) == 0 else tile
    scale = jnp.take(r["weights"].reshape(-1), r["order"])
    out = rf.fetch_rows(r["tokens"], r["order"] // k, r["n_live"], scale,
                        zero_to=zero_to, tile=tile)
    want = (jnp.take(r["tokens"], r["order"] // k, axis=0).astype(
        jnp.float32) * scale[:, None]).astype(dtype)
    assert np.array_equal(f32(out[:n_live]), f32(want[:n_live]))
    assert not f32(out[n_live:zeroed_to(n_live, zero_to, pairs)]).any()


@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("k,n_live", CASES)
def test_fetch_sum_rows_is_the_parents_combine_within_a_float32_rounding(
        k, n_live, dtype):
    """Against ``combine_rows`` without ``n_live`` (XLA's gather and its sum
    over a ``[T, k, M]`` view), both before the final cast: the terms are
    the same float32 products, so the sums differ by the order of k
    additions at most. The kernel's source holds NaN from ``n_live`` on."""
    r = routed(k, n_live, dtype)
    got = sharded_moe.combine_rows(
        r["poisoned"], r["weights"], r["order"], r["inverse"],
        dtype=jnp.float32, n_live=r["n_live"])
    want = sharded_moe.combine_rows(
        r["rows"], r["weights"], r["order"], r["inverse"], dtype=jnp.float32)
    terms = jnp.abs(jnp.take(r["rows"], r["inverse"], axis=0).astype(
        jnp.float32).reshape(TOKENS, k, WIDTH)) * r["weights"][..., None]
    room = np.asarray(jnp.sum(terms, axis=1)) * k * 2.0 ** -24
    assert np.isfinite(f32(got)).all()
    assert (np.abs(f32(got) - f32(want)) <= room).all()
    # and with the final cast, one rounding of that sum
    cast = sharded_moe.combine_rows(
        r["poisoned"], r["weights"], r["order"], r["inverse"], dtype=dtype,
        n_live=r["n_live"])
    assert np.array_equal(f32(cast), f32(got.astype(dtype)))


def agree(got, want, rtol):
    got, want = f32(got), f32(want)
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) <= rtol * max(np.linalg.norm(want),
                                                    1e-30)


@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("k,n_live", FEWER)
def test_the_dispatchs_gradient_is_the_parents(k, n_live, dtype):
    """``dispatch_rows``' transpose (``fetch_sum_rows`` without weights)
    against ``jax.grad`` of the XLA form; the cotangent's rows from
    ``n_live`` on are NaN and are not read."""
    r = routed(k, n_live, dtype)
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (TOKENS * k, WIDTH)), dtype)

    def loss(tokens, cot, **live):
        out = sharded_moe.dispatch_rows(tokens, r["order"], r["inverse"], k,
                                        **live)
        # (the rows past the live ones carry no loss on either side)
        return jnp.sum(jnp.where(r["past"], 0.0,
                                 out.astype(jnp.float32) * cot))

    want = jax.grad(loss)(r["tokens"], jnp.where(r["past"], 0, cot))
    got = jax.grad(loss)(r["tokens"], jnp.where(r["past"], jnp.nan, cot),
                         n_live=r["n_live"])
    agree(got, want, 1e-6 if dtype == jnp.float32 else 4e-3)


@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("k,n_live", FEWER)
def test_the_combines_gradients_are_the_parents(k, n_live, dtype):
    """The rows' gradient (``fetch_rows`` scaled by the weights) on the live
    rows, bit for bit, and the weights' (``fetch_dot_rows``) within the
    order of a float32 sum over the width."""
    r = routed(k, n_live, dtype)
    cot = jnp.asarray(np.random.default_rng(2).standard_normal(
        (TOKENS, WIDTH)), jnp.float32)

    def loss(rows, weights, **live):
        return jnp.sum(sharded_moe.combine_rows(
            rows, weights, r["order"], r["inverse"], dtype=dtype,
            **live).astype(jnp.float32) * cot)

    want = jax.grad(loss, argnums=(0, 1))(r["rows"], r["weights"])
    got = jax.grad(loss, argnums=(0, 1))(r["poisoned"], r["weights"],
                                         n_live=r["n_live"])
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    assert np.array_equal(f32(got[0][:n_live]), f32(want[0][:n_live]))
    agree(got[1], want[1], 1e-5)


@pytest.mark.parametrize("n_live,steps", [
    (0, 2), (1, 2), (511, 2), (512, 4), (513, 4), (1023, 4), (1024, 6),
    (98304 - 1, 384), (98304, 384)])
def test_the_grid_ends_with_the_consumers_block_that_holds_row_n_live(
        n_live, steps):
    """Tiles of 256 under a consumer's blocks of 512, 98,304 rows: where
    the live rows end on a block's edge the NEXT block is written too (as
    zeros). The interpreter hands back zeros for rows no grid step wrote,
    so the tests of values above cannot tell; the chip's memory can, and
    the first chip run of PR 64 trained into NaN on it (a trailing expert
    of no rows has its one visit in that block)."""
    assert int(rf.tiles_written(jnp.int32(n_live), 256, 512, 384)) == steps


def test_the_words_of_a_row_are_its_columns_and_its_upper_halfs():
    src = jnp.arange(16 * 256, dtype=jnp.float32).reshape(16, 256) / 7
    words = rf.as_words(src.astype(jnp.bfloat16), tile=16)
    assert words.shape == (16, 128) and words.dtype == jnp.uint32
    low = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    high = jax.lax.bitcast_convert_type(
        words & jnp.uint32(0xFFFF0000), jnp.float32)
    assert np.array_equal(
        np.concatenate([low, high], axis=1),
        f32(src.astype(jnp.bfloat16)))
    assert np.array_equal(rf.as_words(src, tile=16).reshape(16, 256), src)


@pytest.mark.parametrize("tokens,k,width,dtype,takes", [
    (TOKENS, 6, 256, jnp.bfloat16, True),
    (TOKENS, 6, 128, jnp.float32, True),
    (TOKENS, 6, 128, jnp.bfloat16, False),    # half a row is not whole lines
    (TOKENS, 6, 256, jnp.float16, False),
    (TOKENS + 8, 6, 256, jnp.bfloat16, False),  # no tile divides the tokens
    (16384, 6, 2560, jnp.bfloat16, True),     # the window-and-full cell
    (8192, 8, 2048, jnp.bfloat16, True)])     # OLMoE's
def test_the_shapes_the_kernels_take(tokens, k, width, dtype, takes):
    assert rf.supported(tokens, k, width, dtype) is takes


def test_the_rule_reads_the_pairs_the_shapes_and_whether_the_call_serves(
        monkeypatch):
    cell = (16384, 6, 2560, jnp.bfloat16)
    assert sharded_moe.fetches_live_rows(*cell)
    # a decode step's and a prompt pass's pairs stay on XLA's gather
    assert not sharded_moe.fetches_live_rows(256, 6, 2560, jnp.bfloat16)
    assert not sharded_moe.fetches_live_rows(
        sharded_moe.ROW_FETCH_MIN_PAIRS // 8 - 1, 8, 2048, jnp.bfloat16)
    assert not sharded_moe.fetches_live_rows(16384, 6, 2560 + 64,
                                             jnp.bfloat16)
    # ... and so does a serving call of any size (a 16k prompt's pass
    # sorts more pairs than the training step)
    with experts_mod.matrices_in_place({}, 0, serving=True):
        assert not sharded_moe.fetches_live_rows(*cell)
    with experts_mod.matrices_in_place({}, 0, serving=False):
        assert sharded_moe.fetches_live_rows(*cell)
