"""The flash kernels' schedule (ops/pallas/flash_attention.py): tiles wholly
under the diagonal walked with no mask arithmetic, the diagonal square cut
into granule slices, ``scale`` folded into an operand, each kernel its own
blocks. Interpret mode, small shapes that still have interior tiles,
diagonal slices, a ragged divisor and strips of the whole sequence."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import autotune
from deepspeed_tpu.telemetry.bus import KIND_FLASH_PLAN, telemetry_bus

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
FWD, DQ, DKV = fa.KERNELS


def _reference(q, k, v, causal, seg):
    """Plain ``jax.numpy`` attention over ``[bh, t, d]``."""
    t, d = q.shape[1:]
    s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(d)
    keep = jnp.tril(jnp.ones((t, t), bool)) if causal \
        else jnp.ones((t, t), bool)
    keep = keep[None]
    if seg is not None:
        keep = keep & (seg[:, :, None] == seg[:, None, :])
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v)


def _inputs(bh, t, d, seed, with_seg):
    rng = np.random.RandomState(seed)
    q, k, v, w = (jnp.asarray(rng.randn(bh, t, d), jnp.float32)
                  for _ in range(4))
    seg = None
    if with_seg:
        # a few documents a row and a padded tail (segment 0)
        ids = np.sort(rng.randint(1, 4, (bh, t)), axis=1)
        ids[:, -t // 8:] = 0
        seg = jnp.asarray(ids, jnp.int32)
    return q, k, v, w, seg


def _seg_layouts(seg):
    if seg is None:
        return None
    bh, t = seg.shape
    return (jnp.broadcast_to(seg[:, :, None], (bh, t, fa.LSE_LANES)),
            jnp.broadcast_to(seg[:, None, :], (bh, fa.LSE_LANES, t)))


def _schedule(t, causal, wanted, **kw):
    return tuple(fa.fit_blocks(kernel, t, causal, *w, **kw)
                 for kernel, w in zip(fa.KERNELS, wanted))


# (bh, t, d, per kernel wanted (block_q, block_k, granule)): interior tiles
# and four granule slices a square; each kernel its own blocks, one square
# a strip; a ragged divisor (448 of 896) with its one masked square; blocks
# that do not divide one another; a strip of the whole sequence (the v5e's
# table: no loop at all when causal)
SHAPES = {
    "granules": (4, 256, 16, [(128, 64, 32), (128, 32, 32), (64, 128, 32)]),
    "own_blocks": (4, 128, 8, [(32, 32, None), (64, 32, 16), (32, 64, 64)]),
    "ragged_448": (2, 896, 8, [(512, 512, None)] * 3),
    "coprime_blocks": (2, 96, 8, [(48, 32, None)] * 3),
    "whole_strips": (2, 256, 8, [(256, 64, 64), (256, 128, 32),
                                 (64, 256, 128)]),
}


@pytest.mark.parametrize("with_seg", [False, True], ids=["noseg", "seg"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_forward_and_gradients_match_plain_attention(shape, causal, with_seg):
    bh, t, d, wanted = SHAPES[shape]
    q, k, v, w, seg = _inputs(bh, t, d, 0, with_seg)
    schedule = _schedule(t, causal, wanted)
    if shape == "ragged_448":
        assert schedule[0][:2] == (448, 448)
    scale = 1.0 / np.sqrt(d)
    segs = _seg_layouts(seg)

    def flash(q, k, v):
        o, lse = fa._call_fwd(q, k, v, segs, scale, causal, schedule[0])
        return o, lse

    def loss_ref(q, k, v):
        return jnp.sum(_reference(q, k, v, causal, seg) * w)

    o, lse = flash(q, k, v)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(_reference(q, k, v, causal, seg)),
                               atol=2e-5, rtol=1e-4)
    operands = (q, k, v, w, lse, fa.row_delta(o, w))
    dq = fa._call_dq(operands, segs, scale, causal, schedule[1])
    dk, dv = fa._call_dkv(operands, segs, scale, causal, schedule[2])
    for got, want, name in zip((dq, dk, dv),
                               jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v),
                               "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("with_seg", [False, True], ids=["noseg", "seg"])
def test_public_op_differentiates_through_the_schedule(with_seg):
    """``flash_attention`` itself (custom_vjp, layouts, segment layouts)."""
    b, t, h, d = 2, 128, 2, 16
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
               for _ in range(3))
    seg = jnp.asarray(np.sort(rng.randint(1, 3, (b, t)), axis=1)) \
        if with_seg else None

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          segment_ids=seg, block_q=64,
                                          block_k=32) ** 2)

    def loss_ref(q, k, v):
        segf = None if seg is None else jnp.repeat(seg, h, axis=0)
        return jnp.sum(_reference(flat(q), flat(k), flat(v), True,
                                  segf) ** 2)

    for got, want in zip(jax.grad(loss, argnums=(0, 1, 2))(q, k, v),
                         jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=1e-3)


# (t, wanted blocks): the parent's pair at 1,024, OLMoE's at 4,096, the
# prefill bucket's ragged 448, blocks that do not divide one another, small
# tiles, and the v5e's whole strips (no interior tile at all)
INTERIOR_CASES = [(1024, (512, 256, None)), (4096, (512, 512, 128)),
                  (896, (512, 512, None)), (96, (48, 32, None)),
                  (1024, (128, 128, None)), (1024, (1024, 1024, 256))]


@pytest.mark.parametrize("t,wanted", INTERIOR_CASES,
                         ids=[f"{t}-{w[0]}x{w[1]}"
                              for t, w in INTERIOR_CASES])
@pytest.mark.parametrize("kernel", fa.KERNELS)
def test_interior_tiles_lie_wholly_under_the_diagonal(kernel, t, wanted):
    """The causal mask of every tile the loops walk unmasked is all True
    (``where(True, s, NEG_INF)`` is ``s``: leaving the mask's arithmetic
    out of them changes no bit), and with the strip's own square they are
    all of the strip's causal half: the keys before the square (forward,
    dQ), the rows after it (dK/dV)."""
    blocks = fa.fit_blocks(kernel, t, True, *wanted)
    strip = blocks.strip(kernel)
    tile = blocks.block_q + blocks.block_k - strip
    for start in range(0, t, strip):
        first, n = fa.interior_tiles(kernel, t, True, blocks, start)
        if strip == t:
            assert n is None
            continue
        for i in range(n):
            lo = first + i * tile
            if kernel == DKV:  # rows lo.. see the strip's keys
                rows, cols = np.arange(lo, lo + tile), \
                    np.arange(start, start + strip)
            else:  # the strip's rows see keys lo..
                rows, cols = np.arange(start, start + strip), \
                    np.arange(lo, lo + tile)
            assert (rows[:, None] >= cols[None, :]).all()
        assert (first, first + n * tile) == (
            (start + strip, t) if kernel == DKV else (0, start))
    full = fa.interior_tiles(kernel, t, False, blocks, 0)
    assert full == (0, t // tile)


def test_scale_goes_into_the_operand_in_float32():
    """A bf16 ``scale`` would be off by up to 0.4% for every score."""
    x = jnp.asarray(np.random.RandomState(3).randn(16, 128), jnp.bfloat16)
    scale = 1.0 / np.sqrt(128)
    want = (x.astype(jnp.float32) * np.float32(scale)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(fa._scaled(x, scale)),
                                  np.asarray(want))


# (kernel, t, blocks) -> computed, needed, masked, in tiles of the blocks:
# the schedule before PR 45 computed 6 tiles of 512 x 256 a head at 1,024
# where 4 are needed (a whole tile wherever the diagonal passed: 1.50);
# granule slices leave 1.125 at any blocks, and the mask's arithmetic runs
# on t / granule corners of granule x granule alone
TILE_CASES = [
    # the v5e's table at 1,024 and 4,096: whole strips, no interior tile
    (FWD, 1024, (1024, 512, 256), 1.25, 1.0, 0.5),
    (DQ, 1024, (1024, 512, 256), 1.25, 1.0, 0.5),
    (DKV, 1024, (512, 1024, 256), 1.25, 1.0, 0.5),
    (FWD, 4096, (4096, 512, 512), 4.5, 4.0, 1.0),
    (DKV, 4096, (512, 2048, 512), 9.0, 8.0, 2.0),
    (DQ, 1024, (1024, 512, 128), 1.125, 1.0, 0.25),
    (FWD, 1024, (512, 256, 128), 4.5, 4.0, 1.0),
    (FWD, 1024, (512, 256, 512), 6.0, 4.0, 4.0),
    (DQ, 1024, (256, 256, 128), 9.0, 8.0, 2.0),
    (DKV, 1024, (256, 512, 128), 4.5, 4.0, 1.0),
    (DKV, 1024, (512, 512, 256), 2.5, 2.0, 1.0),
    (FWD, 4096, (512, 512, 128), 33.0, 32.0, 2.0),
    (FWD, 4096, (512, 512, 512), 36.0, 32.0, 8.0),
    (FWD, 896, (448, 448, 448), 3.0, 2.0, 2.0),
]


@pytest.mark.parametrize("kernel,t,blocks,computed,needed,masked",
                         TILE_CASES)
def test_tile_counts(kernel, t, blocks, computed, needed, masked):
    got = fa.tile_counts(kernel, t, True, fa.KernelBlocks(*blocks))
    assert got == {"tiles_computed": computed, "tiles_needed": needed,
                   "tiles_masked": masked}
    full = fa.tile_counts(kernel, t, False, fa.KernelBlocks(*blocks))
    assert full["tiles_computed"] == full["tiles_needed"] \
        == t * t / (blocks[0] * blocks[1])
    assert full["tiles_masked"] == 0


@pytest.mark.parametrize("kernel,wanted,fitted", [
    # the loop's tile divides the strip under the causal mask
    (FWD, (256, 512, None), (256, 256, 256)),
    (DKV, (512, 256, None), (256, 256, 256)),
    # a granule that does not divide the strip falls to 256, then 128
    (DQ, (512, 256, 384), (512, 256, 256)),
    (DQ, (128, 128, 256), (128, 128, 128)),
    # blocks that do not divide the sequence fall to its divisors
    (FWD, (768, 512, 128), (512, 512, 128)),
    (DKV, (512, 1024, 256), (512, 1024, 256)),
])
def test_fit_blocks_makes_a_valid_launch(kernel, wanted, fitted):
    assert fa.fit_blocks(kernel, 1024, True, *wanted) == fitted


def test_long_strips_ask_for_vmem_and_short_ones_do_not():
    """The limit is asked only where blocks and tiles pass what a kernel
    gets unasked: the 1.3B shape's entries run as they were measured."""
    for kernel, blocks in zip(fa.KERNELS, ((1024, 512, 256),) * 2
                              + ((512, 1024, 256),)):
        assert fa._compiler_params(kernel, 1024, 128, 2,
                                   fa.KernelBlocks(*blocks)) is None
    for kernel, blocks in zip(fa.KERNELS, ((4096, 512, 512),) * 2
                              + ((512, 2048, 512),)):
        params = fa._compiler_params(kernel, 4096, 128, 2,
                                     fa.KernelBlocks(*blocks))
        # no TPU here: the v5e's 128 MiB stand in, 96 MiB as measured
        assert params.vmem_limit_bytes == fa._vmem_asked() == 96 << 20


def test_segment_ids_keep_blocks_lane_aligned():
    """The key side's segment row is sliced along lanes in the kernel."""
    got = fa.fit_blocks(FWD, 896, True, 512, 512, lane_aligned=True)
    assert got.block_q % 128 == 0 and got.block_k % 128 == 0
    assert fa.fit_blocks(FWD, 896, True, 512, 512).block_q == 448


@pytest.fixture
def plans():
    events = []

    def on_event(ev):
        if ev["kind"] == KIND_FLASH_PLAN:
            events.append(ev)

    telemetry_bus.subscribe(on_event)
    yield events
    telemetry_bus.unsubscribe(on_event)


@pytest.mark.parametrize("explicit", [True, False],
                         ids=["explicit", "heuristic"])
def test_flash_plan_event(plans, explicit):
    autotune.clear_memory_cache()
    q = jnp.zeros((1, 128, 2, 8), jnp.float32)
    kw = {"block_q": 64, "block_k": 32} if explicit else {}
    jax.jit(lambda q: fa.flash_attention(q, q, q, causal=True, **kw)
            ).lower(q)
    (ev,) = plans
    assert (ev["t"], ev["d"], ev["causal"]) == (128, 8, True)
    assert ev["source"] == ("explicit" if explicit else "heuristic")
    assert set(ev["kernels"]) == set(fa.KERNELS)
    fwd = ev["kernels"][FWD]
    if explicit:
        assert (fwd["block_q"], fwd["block_k"], fwd["heads"],
                fwd["granule"]) == (64, 32, 1, 64)
        # 2 interior tiles of 64 x 32 and two whole squares of two tiles
        assert (fwd["tiles_computed"], fwd["tiles_needed"],
                fwd["tiles_masked"]) == (6.0, 4.0, 4.0)
        assert ev["kernels"][DKV]["block_q"] == 32  # q tile divides strip
    else:
        assert (fwd["block_q"], fwd["block_k"]) == (128, 128)
    autotune.clear_memory_cache()
