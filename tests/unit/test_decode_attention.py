"""Decode attention that reads each lane's live blocks only
(``ops/pallas/decode_attention.py``), in interpret mode on the CPU: the
kernel against the einsum path it replaces, ``live_blocks`` against the
mask, the model's choice between the two, and the calls that keep the
einsums lowering to what the parent lowered."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import transformer_lm
from deepspeed_tpu.models.transformer_lm import (GPT, GPTConfig,
                                                 decode_attention_block)
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.parallel.mesh import reset_default_topology

HERE = os.path.dirname(__file__)
S, BLOCK, D = 64, 16, 128
HEADS = {"full": (4, 4), "grouped": (10, 2)}        # (H, Hkv): G = 1, 5
DTYPES = {"float32": (jnp.float32, 2e-6), "bfloat16": (jnp.bfloat16, 2e-2)}
# left padding of nothing, one position, a whole block
PADS = (0, 1, BLOCK)
# the query at the cache's first row, a block's last and first, the last
CLOCKS = (0, BLOCK - 1, 2 * BLOCK, S - 1)


def einsum_path(q, k_all, v_all, valid, clock):
    """``CausalSelfAttention``'s decode branch for one query token, as the
    parent has it: scores in the cache's dtype, masked, softmax in
    float32, probabilities back in the cache's dtype."""
    B, H, _ = q.shape
    Hkv = k_all.shape[2]
    qg = q.reshape(B, 1, Hkv, H // Hkv, D)
    visible = (jnp.arange(S)[None, None] <= clock[:, None, None])
    visible = visible[:, None, None] & valid[:, None, None, None, :]
    att = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all) * (1.0 / np.sqrt(D))
    att = jnp.where(visible, att, jnp.finfo(att.dtype).min)
    att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", att, v_all).reshape(B, H, D)


@pytest.fixture(scope="module")
def arrays():
    """``{(heads, dtype): (q, stacked keys, stacked values, kernel, einsum
    path)}``, three layers of two lanes; both callables jitted once and
    run with the clocks as values."""
    out = {}
    for heads, (H, Hkv) in HEADS.items():
        for name, (dtype, _) in DTYPES.items():
            ks = jax.random.split(jax.random.PRNGKey(len(out)), 3)
            q = jax.random.normal(ks[0], (2, H, D), dtype)
            kc = jax.random.normal(ks[1], (3, 2, S, Hkv, D), dtype)
            vc = jax.random.normal(ks[2], (3, 2, S, Hkv, D), dtype)
            kernel = jax.jit(lambda q, kc, vc, valid, clock, layer:
                             da.decode_attention(q, kc, vc, valid, clock,
                                                 layer, block=BLOCK))
            plain = jax.jit(lambda q, kc, vc, valid, clock, layer:
                            einsum_path(q, kc[layer], vc[layer], valid,
                                        clock))
            out[heads, name] = (q, kc, vc, kernel, plain)
    return out


@pytest.mark.parametrize("clock", CLOCKS)
@pytest.mark.parametrize("pad", PADS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("heads", HEADS)
def test_kernel_gives_what_the_einsum_path_gives(arrays, heads, dtype, pad,
                                                 clock):
    """Lane 0: ``pad`` invalid rows, then valid ones, the query at
    ``clock``. Lane 1 the same one position on, so that the two lanes'
    ranges differ. A lane whose padding reaches past its clock has nothing
    visible: finite there, whatever the numbers."""
    q, kc, vc, kernel, plain = arrays[heads, dtype]
    first = np.array([pad, min(pad + 1, S - 1)])
    clocks = np.array([clock, min(clock + 1, S - 1)])
    valid = jnp.asarray(np.arange(S)[None, :] >= first[:, None])
    got = np.asarray(kernel(q, kc, vc, valid, jnp.asarray(clocks), 1),
                     np.float32)
    want = np.asarray(plain(q, kc, vc, valid, jnp.asarray(clocks), 1),
                      np.float32)
    assert np.isfinite(got).all()
    seen = first <= clocks
    assert seen.any() or pad > clock
    np.testing.assert_allclose(got[seen], want[seen],
                               atol=DTYPES[dtype][1], rtol=DTYPES[dtype][1])


@pytest.mark.parametrize("heads", HEADS)
def test_a_lane_with_no_valid_row_is_finite_and_harms_no_other(arrays,
                                                               heads):
    q, kc, vc, kernel, plain = arrays[heads, "float32"]
    valid = jnp.asarray(np.stack([np.zeros(S, bool), np.ones(S, bool)]))
    clocks = jnp.asarray([S - 1, 40])
    got = np.asarray(kernel(q, kc, vc, valid, clocks, 2))
    want = np.asarray(plain(q, kc, vc, valid, clocks, 2))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[1], want[1], atol=2e-6, rtol=2e-6)


def test_holes_in_valid_and_rows_past_the_clock_are_masked(arrays):
    """The range comes from the first valid row and the clock; inside it
    the mask is still ``valid & (position <= clock)``: a hole in ``valid``
    (no caller makes one; the cache allows it) and valid rows past the
    clock (a rewound lane) count for nothing."""
    q, kc, vc, kernel, plain = arrays["grouped", "float32"]
    valid = np.ones((2, S), bool)
    valid[0, 3:20] = False
    valid[1, :5] = False
    clocks = jnp.asarray([50, 17])
    got = kernel(q, kc, vc, jnp.asarray(valid), clocks, 0)
    want = plain(q, kc, vc, jnp.asarray(valid), clocks, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


def test_one_layers_own_leaf_needs_no_layer_index(arrays):
    q, kc, vc, _, plain = arrays["full", "float32"]
    valid = jnp.ones((2, S), bool)
    clocks = jnp.asarray([9, 33])
    got = da.decode_attention(q, kc[2], vc[2], valid, clocks, block=BLOCK)
    want = plain(q, kc, vc, valid, clocks, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


def test_kv_heads_that_do_not_divide_128_take_the_plain_repeat():
    """The ``valid`` flags are spread over the heads by a 0/1 product
    where ``Hkv`` divides 128, by ``repeat`` where it does not."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 6, D), jnp.float32)
    kc = jax.random.normal(ks[1], (1, 2, S, 3, D), jnp.float32)
    vc = jax.random.normal(ks[2], (1, 2, S, 3, D), jnp.float32)
    valid = jnp.asarray(np.arange(S)[None, :] >= np.array([[5], [0]]))
    clocks = jnp.asarray([40, 17])
    got = da.decode_attention(q, kc, vc, valid, clocks, 0, block=BLOCK)
    want = einsum_path(q, kc[0], vc[0], valid, clocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    flags = np.asarray(da._valid_rows(valid, 3))
    assert flags.shape == (2, 1, S * 3)
    assert (flags[:, 0].reshape(2, S, 3) == np.asarray(valid)[..., None]).all()
    spread = np.asarray(da._valid_rows(valid, 4))       # 4 divides 128
    assert (spread[:, 0].reshape(2, S, 4)
            == np.asarray(valid)[..., None]).all()


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("block", (1, 16, 64))
def test_live_blocks_names_every_visible_position_and_no_dead_block(block):
    """Over every (first valid row, clock) of a 64-position lane: every
    visible position lies in a named block, and no named block is wholly
    invisible, except the one block a lane with nothing visible names."""
    first, clock = np.meshgrid(np.arange(S + 1), np.arange(S),
                               indexing="ij")
    lo, hi = da.live_blocks(first, clock, block)
    pos = np.arange(S)[None, None, :]
    visible = (pos >= first[..., None]) & (pos <= clock[..., None])
    named = (pos // block >= lo[..., None]) & (pos // block <= hi[..., None])
    assert not (visible & ~named).any()
    assert (lo <= hi).all() and (lo >= 0).all() and (hi < S // block).all()
    blocks = np.arange(S // block)[None, None, :]
    in_range = (blocks >= lo[..., None]) & (blocks <= hi[..., None])
    live = visible.reshape(S + 1, S, S // block, block).any(-1)
    nothing = ~visible.any(-1)
    assert (live[~nothing] == in_range[~nothing]).all()
    assert (in_range[nothing].sum(-1) == 1).all()


def test_live_blocks_is_the_same_rule_on_device_values():
    first, clock = np.array([0, 5, 40, 63]), np.array([0, 63, 41, 2])
    host = da.live_blocks(first, clock, 16)
    dev = da.live_blocks(jnp.asarray(first), jnp.asarray(clock), 16)
    assert all(isinstance(x, np.ndarray) for x in host)
    for h, d in zip(host, dev):
        assert (h == np.asarray(d)).all()


def test_work_items_walk_each_lanes_live_blocks_in_order():
    first, clock = np.array([0, 20, 63, 17]), np.array([40, 20, 5, 63])
    count, lane, blk = da.work_items(jnp.asarray(first), jnp.asarray(clock),
                                     16, S // 16)
    lo, hi = da.live_blocks(first, clock, 16)
    want = [(n, b) for n in range(4) for b in range(lo[n], hi[n] + 1)]
    assert int(count) == len(want)
    assert list(zip(np.asarray(lane)[:len(want)].tolist(),
                    np.asarray(blk)[:len(want)].tolist())) == want
    assert lane.shape == blk.shape == (4 * S // 16,)


@pytest.mark.parametrize("shape,want", [
    ((1024, 16, 128, 2), 128),      # GPT-2 1.3B in bf16: 512 KiB of keys
    ((1408, 4, 128, 2), 128),       # Falcon-H1: 1,408 = 11 x 128
    ((2048, 8, 128, 2), 256),
    ((256, 4, 8, 4), 256),
    ((64, 4, 8, 4), 64),            # no multiple of 128 divides it
])
def test_block_comes_from_the_shapes(shape, want):
    assert da.block_positions(*shape) == want
    assert shape[0] % want == 0


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _cfg(**kw):
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32, scan_layers=True)
    base.update(kw)
    return GPTConfig(**base)


@pytest.mark.parametrize("name,kw,T,taken", [
    ("one token, dense", {}, 1, True),
    ("grouped heads", {"n_kv_head": 2}, 1, True),
    ("rotary", {"rotary": True, "learned_positions": False}, 1, True),
    ("layers not scanned", {"scan_layers": False}, 1, True),
    ("several tokens", {}, 3, False),
    ("int8 storage", {"kv_cache_dtype": "int8"}, 1, False),
    ("ALiBi", {"alibi": True, "learned_positions": False}, 1, False),
])
def test_the_path_is_told_from_the_call_and_the_layout(name, kw, T, taken):
    reset_default_topology()
    assert (decode_attention_block(_cfg(**kw), T) is not None) == taken


def test_a_ring_cache_keeps_the_einsums():
    from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import \
        apply_sparse_attention

    reset_default_topology()
    model = apply_sparse_attention(
        GPT(_cfg(rotary=True, learned_positions=False)),
        {"mode": "local_sliding_window", "block": 16,
         "num_sliding_window_blocks": 3})
    assert decode_attention_block(model.config) is None


@pytest.mark.parametrize("kw", [
    {}, {"n_kv_head": 2, "rotary": True, "learned_positions": False},
    {"scan_layers": False}], ids=["full", "grouped-rotary", "unscanned"])
def test_decode_k_over_a_carried_leaf_matches_the_einsum_model(monkeypatch,
                                                               kw):
    """Four decode steps in one program over the stacked leaf the layer
    loop carries, ragged left-padded prompts: the kernel's tokens and
    cache are the einsum path's (the same model with the choice turned
    off)."""
    reset_default_topology()
    eng = InferenceEngine(GPT(_cfg(**kw)), {"dtype": "fp32"}, seed=0)
    ids = np.zeros((3, 16), np.int32)
    mask = np.zeros((3, 16), bool)
    rng = np.random.default_rng(0)
    for row, n in enumerate((16, 5, 1)):
        ids[row, 16 - n:] = rng.integers(1, 128, n)
        mask[row, 16 - n:] = True
    eng._materialize(jnp.asarray(ids))
    eng._build_decode_fns()

    def decode():
        logits, cache = eng._prefill_fn(eng.params, jnp.asarray(ids),
                                        jnp.asarray(mask))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return eng._decode_k_fn.fn(eng.params, tok, cache,
                                   jax.random.PRNGKey(0), jnp.float32(0.0),
                                   4)[:3]

    calls = []
    real = da.decode_attention
    monkeypatch.setattr(da, "decode_attention",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    toks, tok, cache = decode()
    assert calls    # traced once a layer, or once for the scanned block
    del calls[:]
    monkeypatch.setattr(transformer_lm, "decode_attention_block",
                        lambda cfg, T=1: None)
    jax.clear_caches()
    want_toks, want_tok, want_cache = decode()
    jax.clear_caches()
    assert not calls
    assert np.asarray(toks).tolist() == np.asarray(want_toks).tolist()
    for got, want in zip(jax.tree.leaves(cache),
                         jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# what keeps the einsums lowers to the parent's text
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def fallbacks():
    from unit import gpt_program_hashes

    with open(os.path.join(HERE, "data", "gpt_program_hashes.json"),
              encoding="utf-8") as f:
        return gpt_program_hashes.fallback_hashes(), json.load(f)


@pytest.mark.parametrize("program", [
    "jit_verify_greedy[T=3]", "jit_prefill_more[16]", "jit_decode_k[ring]",
    "jit_decode_k[int8]", "jit_decode_k[alibi]"])
def test_fallbacks_lower_to_the_parents_text(fallbacks, program):
    got, want = fallbacks
    assert got[program] == want[program]


def test_the_config_has_no_field_for_the_choice():
    names = {f.name for f in dataclasses.fields(GPTConfig)}
    assert not {n for n in names if "decode_attention" in n
                or "live_block" in n}
