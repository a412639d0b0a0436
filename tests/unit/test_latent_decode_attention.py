"""Latent attention's decode kernel (``ops/pallas/latent_decode_attention
.py``), in interpret mode on the CPU: the kernel against the absorbed
einsums of ``models/latent_attention.py`` that it replaces, and its grid
against the mask (each live block named once, no dead block named)."""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
from deepspeed_tpu.ops.pallas.decode_attention import live_blocks

LAYERS, B, H, R, DR = 3, 4, 4, 32, 8
SCALE = 0.173
# float32 differs from the einsums by the order of its sums; bfloat16 by
# the rounding of the probabilities, which the einsum path rounds once
# over the whole row and the kernel block by block under a running maximum
DTYPES = {"float32": (jnp.float32, 1e-5), "bfloat16": (jnp.bfloat16, 2e-2)}
# cache length and block: one block, several, and a length that no
# multiple of 128 divides (its last block ragged)
GEOMETRIES = {"one_block": (64, 64), "several_blocks": (512, 128),
              "ragged": (328, 128)}


def einsum_path(q_lat, q_rope, lat_all, rk_all, valid, clock):
    """``LatentAttention``'s absorbed branch between its two products with
    ``W_kvb``, for one query token, as the parent has it."""
    S = lat_all.shape[1]
    visible = (jnp.arange(S)[None, None, :] <= clock[:, None, None]) \
        & valid[:, None, :]
    att = (jnp.einsum("bqhr,bkr->bhqk", q_lat[:, None], lat_all,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bqhd,bkd->bhqk", q_rope[:, None], rk_all,
                        preferred_element_type=jnp.float32)) * SCALE
    att = jnp.where(visible[:, None], att, jnp.finfo(jnp.float32).min)
    att = jax.nn.softmax(att, axis=-1).astype(lat_all.dtype)
    return jnp.einsum("bhqk,bkr->bqhr", att, lat_all)[:, 0]


def lanes(case, S, block):
    """``(valid [B, S], clock [B])`` of four lanes for a case."""
    pos = np.arange(S)[None, :]
    first = np.zeros(B, int)
    clock = np.array([0, block - 1, min(block, S - 1), S // 2])
    if case == "left_padding":
        first = np.array([1, 3, block // 2, block + 1]) % S
        clock = np.maximum(clock, first + np.array([0, 2, 1, 5]))
    elif case == "last_position":
        clock = np.array([S - 1, S - 1, S - 2, S - 1])
        first = np.array([0, S - 1, 5, S // 2])
    valid = pos >= first[:, None]
    if case == "all_invalid_lane":
        valid[1] = False
        clock[1] = S - 1
    elif case == "holes":
        # no caller makes a hole or leaves valid rows past a clock; the
        # cache allows both
        clock = np.array([S - 1, S // 2, S - 3, 7])
        valid[0, 3:min(S - 2, 2 * block + 9)] = False
        valid[2, ::2] = False
    return valid, np.minimum(clock, S - 1)


@functools.lru_cache(maxsize=None)
def arrays(dtype, geometry):
    S, block = GEOMETRIES[geometry]
    dt = DTYPES[dtype][0]
    ks = jax.random.split(jax.random.PRNGKey(len(dtype) + S), 4)
    q_lat = jax.random.normal(ks[0], (B, H, R), dt)
    q_rope = jax.random.normal(ks[1], (B, H, DR), dt)
    latent = jax.random.normal(ks[2], (LAYERS, B, S, R), dt)
    rope_key = jax.random.normal(ks[3], (LAYERS, B, S, DR), dt)

    def kernel(layer, valid, clock):
        plan = lda.step_plan(valid, clock, block)
        return lda.latent_decode_attention(q_lat, q_rope, latent, rope_key,
                                           plan, layer, scale=SCALE), plan

    def plain(layer, valid, clock):
        return einsum_path(q_lat, q_rope, latent[layer], rope_key[layer],
                           valid, clock)

    return {"static": jax.jit(functools.partial(kernel, 0)),
            "traced": jax.jit(kernel),
            "plain": jax.jit(plain),
            "arrays": (q_lat, q_rope, latent, rope_key)}


@pytest.mark.parametrize("layer", ["static", "traced"])
@pytest.mark.parametrize("geometry", GEOMETRIES)
@pytest.mark.parametrize("case", ["no_padding", "left_padding",
                                  "all_invalid_lane", "last_position",
                                  "holes"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_kernel_gives_what_the_absorbed_einsums_give(dtype, case, geometry,
                                                     layer):
    """``static``: the leading dense block's layer index, a Python int;
    ``traced``: a scanned layer's. Every lane is finite; every lane with a
    visible row agrees with the einsums; the grid names each live block
    once, lanes in order, and no dead block."""
    S, block = GEOMETRIES[geometry]
    fns = arrays(dtype, geometry)
    valid, clock = lanes(case, S, block)
    args = (jnp.asarray(valid), jnp.asarray(clock, jnp.int32))
    number = 0 if layer == "static" else 2
    got, plan = fns[layer](*args) if layer == "static" \
        else fns[layer](jnp.int32(number), *args)
    want = fns["plain"](number, *args)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == (B, H, R) and np.isfinite(got).all()
    visible = valid & (np.arange(S)[None, :] <= clock[:, None])
    seen = visible.any(axis=1)
    assert seen.sum() >= 3
    tol = DTYPES[dtype][1]
    np.testing.assert_allclose(got[seen], want[seen], atol=tol, rtol=tol)

    count = int(plan.count)
    items = list(zip(np.asarray(plan.item_lane)[:count].tolist(),
                     np.asarray(plan.item_block)[:count].tolist()))
    assert len(set(items)) == count and items == sorted(items)
    lo, hi = live_blocks(np.argmax(valid, axis=1), clock, block)
    assert items == [(b, k) for b in range(B)
                     for k in range(lo[b], hi[b] + 1)]
    held = {(b, int(p) // block) for b, p in zip(*np.nonzero(visible))}
    assert held <= set(items)
    # a block outside a lane's range holds no visible row, and a block
    # inside it lies between the lane's first visible row and its clock
    for b, k in items:
        if seen[b]:
            rows = np.nonzero(visible[b])[0]
            assert rows[0] // block <= k <= clock[b] // block
    np.testing.assert_array_equal(np.asarray(plan.visible)[:, 0] > 0,
                                  visible)


def test_one_layers_own_leaf_needs_no_layer_index():
    S, block = GEOMETRIES["several_blocks"]
    fns = arrays("float32", "several_blocks")
    valid, clock = lanes("left_padding", S, block)
    args = (jnp.asarray(valid), jnp.asarray(clock, jnp.int32))
    q_lat, q_rope, latent, rope_key = fns["arrays"]
    got = lda.latent_decode_attention(
        q_lat, q_rope, latent[1], rope_key[1],
        lda.step_plan(*args, block), scale=SCALE)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(fns["plain"](1, *args)),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the programs that exist do not move
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", ["serve", "hybrid", "retention", "latent",
                                    "speculative"])
def test_lowered_programs_hash_as_recorded(family):
    """``lower(...).as_text()`` of each family's serving programs, byte
    for byte what the commits that recorded them lower
    (``tests/unit/data/gpt_program_hashes.json``; ``gpt_program_hashes.py``
    says which commit recorded which): the GPT, hybrid and retention
    models ask ``decode_attention_block`` and ``live_blocks`` as before;
    the latent model's prefill, splice and sixteen-token continuation
    were recorded on the parent of the PR that brought its decode kernel
    (the pass that makes the cache and the einsum route lower as they
    did), its ``jit_decode_k`` on that PR's tree, and the three that run
    the layer again on PR 55's, which holds the query projection's 2-D
    product before its per-head view; the copy and rewind of
    a speculative scheduler and the plain loop's ``set_token`` on the
    parent of the PR that moved the lane cache's programs behind
    inference/lane_cache.py."""
    from unit import gpt_program_hashes

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "gpt_program_hashes.json"),
              encoding="utf-8") as f:
        want = json.load(f)
    got = getattr(gpt_program_hashes, family + "_hashes")()
    assert len(got) >= 2 and got == {k: want[k] for k in got}
    if family == "latent":
        assert set(got) == {k for k in want if k.startswith("latent_")}
