"""The grouped-matmul kernels (``ops/pallas/grouped_matmul.py``) against
``jax.lax.ragged_dot`` and its autodiff on the same inputs, in interpret
mode on the CPU; the rule that chooses them (``moe/experts.py``); and, for
a described v5e, Mosaic's own compile at the OLMoE cell's widths.

Tolerance: both sides accumulate in float32 and round once, in another
order of the sums, so a bf16 result differs by one bf16 ulp at most (2^-7
of its value) and a sum that cancels by float32's own error on its terms
(1e-3 of the largest value covers it; float32 results: 1e-4 and 1e-5). A
row given to the wrong group, a tile skipped or computed from another
group's rows is wrong by the size of the values themselves.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.moe import experts as experts_mod
from deepspeed_tpu.moe.experts import StackedExperts
from deepspeed_tpu.ops.pallas import autotune
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.parallel.mesh import (
    MeshTopology,
    reset_default_topology,
    set_default_topology,
)

ROWS, GROUPS = 256, 4
LAYOUTS = {
    "equal": [64, 64, 64, 64],
    "ending_inside_a_tile": [100, 60, 50, 46],
    "some_empty": [0, 200, 0, 56],
    "one_group_has_every_row": [0, 0, 256, 0],
    "rows_no_group_covers": [40, 50, 0, 60],
}
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}
# row tiles of 32 and 128 (two and eight tiles), column tiles of 128 and all
TILES = {"small": (32, 128), "default": None}
TGMM_TILES = {"small": (32, 128, 128), "default": None}


def operands(dtype, k=256, n=128, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((ROWS, k)), dtype),
            jnp.asarray(rng.standard_normal((GROUPS, k, n)) / np.sqrt(k),
                        dtype),
            jnp.asarray(rng.standard_normal((ROWS, n)), dtype))


def close(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    bf16 = want.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(
        got, want, rtol=2.0 ** -7 if bf16 else 1e-4,
        atol=(1e-3 if bf16 else 1e-5) * np.abs(want).max())


def reference(lhs, rhs, sizes, cot):
    out, vjp = jax.vjp(lambda a, w: jax.lax.ragged_dot(a, w, sizes), lhs, rhs)
    return (out,) + vjp(cot)


@pytest.fixture(scope="module", params=[
    (layout, dtype) for layout in LAYOUTS for dtype in DTYPES],
    ids=lambda p: "-".join(p))
def case(request):
    layout, dtype = request.param
    lhs, rhs, cot = operands(DTYPES[dtype])
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    return dict(lhs=lhs, rhs=rhs, cot=cot, sizes=sizes, layout=layout,
                ref=reference(lhs, rhs, sizes, cot))


@pytest.mark.parametrize("tiles", TILES)
def test_forward_is_ragged_dots(case, tiles):
    close(gm.gmm(case["lhs"], case["rhs"], case["sizes"],
                 tiles=TILES[tiles]), case["ref"][0])


@pytest.mark.parametrize("tiles", TILES)
def test_rows_gradient_is_ragged_dots_with_the_matrices_as_they_lie(
        case, tiles):
    close(gm.gmm(case["cot"], case["rhs"], case["sizes"],
                 transpose_rhs=True, tiles=TILES[tiles]), case["ref"][1])


@pytest.mark.parametrize("tiles", TGMM_TILES)
def test_matrices_gradient_is_ragged_dots(case, tiles):
    close(gm.tgmm(case["lhs"], case["cot"], case["sizes"],
                  tiles=TGMM_TILES[tiles]), case["ref"][2])


def test_the_custom_vjp_gives_all_three(case):
    out, vjp = jax.vjp(
        lambda a, w: gm.grouped_matmul(a, w, case["sizes"]),
        case["lhs"], case["rhs"])
    for got, want in zip((out,) + vjp(case["cot"]), case["ref"]):
        close(got, want)


@pytest.mark.parametrize("dtype", DTYPES)
def test_rows_no_group_covers_are_zeros_and_so_are_their_gradients(dtype):
    lhs, rhs, cot = operands(DTYPES[dtype])
    sizes = jnp.asarray(LAYOUTS["rows_no_group_covers"], jnp.int32)
    covered = int(sizes.sum())
    out, vjp = jax.vjp(lambda a, w: gm.grouped_matmul(a, w, sizes), lhs, rhs)
    d_lhs, d_rhs = vjp(cot)
    assert not np.asarray(out[covered:], np.float32).any()
    assert np.asarray(out[:covered], np.float32).any(axis=1).all()
    assert not np.asarray(d_lhs[covered:], np.float32).any()
    assert not np.asarray(d_rhs[2], np.float32).any()     # the empty group
    # what the uncovered rows hold changes nothing
    other = lhs.at[covered:].set(7.0)
    close(gm.tgmm(other, cot, sizes), d_rhs)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_visits_are_what_the_host_counts(layout):
    sizes = LAYOUTS[layout]
    for tm in (16, 32, 128, 256):
        offsets, group, tile, count = gm.row_walk(
            jnp.asarray(sizes, jnp.int32), ROWS, tm)
        count = int(count[0])
        assert count == gm.row_tile_visits(sizes, ROWS, tm)
        assert group.shape == tile.shape == (ROWS // tm + GROUPS,)
        assert ROWS // tm <= count <= ROWS // tm + GROUPS
        offsets, group, tile = (np.asarray(a) for a in (offsets, group, tile))
        assert offsets.tolist() == [0, *np.cumsum(sizes), ROWS]
        # every row is stored by exactly one visit, in the order of the
        # rows; a group of no rows has one visit (and the rest none)
        rows = np.concatenate([
            np.arange(max(offsets[g], t * tm), min(offsets[g + 1],
                                                   (t + 1) * tm))
            for g, t in zip(group[:count], tile[:count])])
        assert rows.tolist() == list(range(ROWS))
        assert np.bincount(group[:count], minlength=GROUPS)[:GROUPS].min() \
            >= 1
        assert (group[count:] == group[count - 1]).all()
        assert (tile[count:] == tile[count - 1]).all()


def test_one_walk_serves_all_three_products(case):
    """A layer lays the walk out once; its row tile then holds for every
    call, whatever the table would say."""
    walk = gm.row_walk(case["sizes"], ROWS, 32)
    lhs, rhs, cot = case["lhs"], case["rhs"], case["cot"]
    close(gm.gmm(lhs, rhs, None, walk=walk), case["ref"][0])
    close(gm.gmm(cot, rhs, None, transpose_rhs=True, walk=walk),
          case["ref"][1])
    close(gm.tgmm(lhs, cot, None, walk=walk), case["ref"][2])
    out, vjp = jax.vjp(
        lambda a, w: gm.grouped_matmul(a, w, None, walk), lhs, rhs)
    for got, want in zip((out,) + vjp(cot), case["ref"]):
        close(got, want)


def stack(matmul, sizes):
    """Three layers under ``jax.checkpoint`` inside a ``scan``, as the
    model's scanned blocks under full remat have them."""
    def layer(x, w):
        return jnp.tanh(matmul(x, w, sizes)), None

    def loss(x, ws):
        y, _ = jax.lax.scan(jax.checkpoint(layer), x, ws)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))


@pytest.mark.parametrize("layout", ["ending_inside_a_tile",
                                    "rows_no_group_covers"])
def test_under_checkpoint_inside_the_layer_scan(layout):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((ROWS, 128)), jnp.bfloat16)
    ws = jnp.asarray(rng.standard_normal((3, GROUPS, 128, 128)) / 11.3,
                     jnp.bfloat16)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    got = stack(gm.grouped_matmul, sizes)(x, ws)
    want = stack(jax.lax.ragged_dot, sizes)(x, ws)
    assert abs(float(got[0]) - float(want[0])) < 2e-2 * float(want[0])
    for g, w in zip(got[1], want[1]):
        assert g.dtype == w.dtype
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(g - w) < 2e-2 * np.linalg.norm(w)


# --- the rule that chooses the kernel --------------------------------------
def experts_program(monkeypatch, d_model, d_hidden, dtype, rows):
    """The sorted-rows path of the experts, lowered for the TPU (no chip is
    needed to lower): there the kernel is a ``tpu_custom_call`` under its
    name and the compiler's ragged dot a ``chlo.ragged_dot``."""
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    ffn = StackedExperts(num_experts=GROUPS, d_model=d_model,
                         d_hidden=d_hidden, dtype=dtype, gated=True,
                         use_bias=False)
    x = jnp.zeros((rows, d_model), dtype)
    sizes = jnp.full((GROUPS,), rows // GROUPS, jnp.int32)
    params = jax.eval_shape(ffn.init, jax.random.PRNGKey(0), x, sizes)
    return jax.jit(lambda p, x, s: ffn.apply(p, x, s)).trace(
        params, x, sizes).lower(lowering_platforms=("tpu",)).as_text()


@pytest.fixture
def topology():
    def use(**axes):
        n = int(np.prod(list(axes.values()) or [1]))
        set_default_topology(MeshTopology(devices=jax.devices()[:n], **axes))
    yield use
    reset_default_topology()


RULE = {
    "aligned_on_one_device": (dict(), 128, 256, jnp.bfloat16, 64, True),
    "aligned_float32": (dict(), 256, 128, jnp.float32, 64, True),
    "aligned_under_dp": (dict(dp=2), 128, 128, jnp.bfloat16, 64, True),
    "k_off_128": (dict(), 64, 128, jnp.bfloat16, 64, False),
    "n_off_128": (dict(), 128, 96, jnp.bfloat16, 64, False),
    "rows_off_16": (dict(), 128, 128, jnp.bfloat16, 24, False),
    "float16": (dict(), 128, 128, jnp.float16, 64, False),
    "under_ep": (dict(ep=2), 128, 128, jnp.bfloat16, 64, False),
    "under_tp": (dict(tp=2), 128, 128, jnp.bfloat16, 64, False),
}


@pytest.mark.parametrize("name", RULE)
def test_the_kernel_is_chosen_from_what_the_call_shows(
        topology, monkeypatch, name):
    axes, d_model, d_hidden, dtype, rows, kernel = RULE[name]
    topology(**axes)
    tiles = experts_mod.grouped_matmul_tiles(rows, d_model, d_hidden, GROUPS,
                                             dtype)
    text = experts_program(monkeypatch, d_model, d_hidden, dtype, rows)
    # one jitted function per signature (up and gate share theirs, and
    # down too where the two widths are equal), one kernel in each
    calls = len(re.findall(r"call @_gmm\w*\(", text))
    kernels = text.count(f'kernel_name = "{gm.GMM_NAME}"')
    assert text.count("tpu_custom_call") == kernels
    assert kernels == (calls and 1 + (d_model != d_hidden))
    if kernel:
        assert tiles == autotune.grouped_matmul_tiles(
            "gmm", rows, d_model, d_hidden, GROUPS, dtype)
        assert tiles[1] == d_model                    # all of K is one tile
        assert calls == 3 and "ragged_dot" not in text
    else:
        assert tiles is None
        assert calls == 0 and len(re.findall(
            r'= "?chlo\.ragged_dot"?[ (]', text)) == 3


def test_no_field_or_variable_chooses_it():
    import dataclasses
    import inspect

    fields = {f.name for f in dataclasses.fields(StackedExperts)}
    assert fields == {"num_experts", "d_model", "d_hidden", "dtype",
                      "param_dtype", "activation", "gated", "use_bias",
                      "parent", "name"}
    for module in (experts_mod, gm):
        assert "environ" not in inspect.getsource(module)


# --- the tiles --------------------------------------------------------------
CELL = dict(rows=65536, d_model=2048, d_hidden=1024, groups=64)
# (kind, K, N of the call) of the cell's nine grouped matmuls a layer
CELL_CALLS = {
    "up_and_gate": ("gmm", 2048, 1024), "down": ("gmm", 1024, 2048),
    "up_rows_gradient": ("gmm_t", 1024, 2048),
    "down_rows_gradient": ("gmm_t", 2048, 1024),
    "up_matrices_gradient": ("tgmm", 2048, 1024),
    "down_matrices_gradient": ("tgmm", 1024, 2048)}


@pytest.mark.parametrize("kind", ["gmm", "gmm_t", "tgmm"])
def test_default_tiles_divide_the_shapes_and_fit(kind):
    for rows, k, n in ((64, 128, 384), (4096, 2048, 1024), (48, 256, 128),
                       (65536, 8192, 8192)):
        tm, tk, tn = autotune.grouped_matmul_tiles(kind, rows, k, n, 8,
                                                   jnp.bfloat16)
        assert rows % tm == 0 and tm % 16 == 0 and tm <= 128
        assert k % tk == 0 and n % tn == 0 and tn % 128 == 0
        if kind != "tgmm":
            assert tk == k
        assert autotune.grouped_matmul_vmem_bytes(kind, tm, tk, tn, 2) \
            <= max(autotune._GMM_VMEM_BUDGET,
                   autotune.grouped_matmul_vmem_bytes(
                       kind, tm, 128 if kind == "tgmm" else k, 128, 2))


@pytest.mark.parametrize("call", CELL_CALLS)
def test_the_cells_shapes_have_an_entry_found_on_the_chip(call):
    kind, k, n = CELL_CALLS[call]
    tm, tk, tn = autotune.GMM_PRETUNED[
        kind, CELL["rows"], k, n, CELL["groups"], "bfloat16", "TPU v5 lite"]
    assert CELL["rows"] % tm == 0 and k % tk == 0 and n % tn == 0
    if kind != "tgmm":
        assert tk == k


# --- Mosaic's own compile, for a described v5e -------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("call", CELL_CALLS)
def test_the_cells_calls_compile_for_the_chip_and_keep_their_name(
        one_chip, call, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    kind, k, n = CELL_CALLS[call]
    rows, groups = CELL["rows"], CELL["groups"]
    tiles = autotune.GMM_PRETUNED[kind, rows, k, n, groups, "bfloat16",
                                  "TPU v5 lite"]
    monkeypatch.setattr(gm, "_interpret", lambda: False)

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if kind == "tgmm":
        def run(lhs, cot, sizes):
            return gm.tgmm(lhs, cot, sizes, tiles=tiles)
        args = (spec(rows, k), spec(rows, n), spec(groups, dtype=jnp.int32))
    else:
        def run(lhs, rhs, sizes):
            return gm.gmm(lhs, rhs, sizes, transpose_rhs=kind == "gmm_t",
                          tiles=tiles[::2])
        args = (spec(rows, k),
                spec(groups, n, k) if kind == "gmm_t" else spec(groups, k, n),
                spec(groups, dtype=jnp.int32))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(run).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    name = gm.TGMM_NAME if kind == "tgmm" else gm.GMM_NAME
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert len(calls) == 1 and f"%{name}" in calls[0]
    # no copy of an operand: neither a transposed expert tensor nor rows
    assert not [line for line in text.splitlines() if "bf16[" in line
                and (" transpose(" in line or " copy(" in line)]
