"""The grouped-matmul kernels (``ops/pallas/grouped_matmul.py``) against
``jax.lax.ragged_dot`` and its autodiff on the same inputs, in interpret
mode on the CPU; the rule that chooses them (``moe/experts.py``); and, for
a described v5e, Mosaic's own compile at the OLMoE cell's widths; and the
forward product over a STACK of layers' matrices with the layer as an
index (``gmm(..., layer=)``), which a call inside the layer scan takes so
that no slice of the stack is written out for the custom call: a serving
call the forward product itself, a training step the custom VJP that reads
the stack and is differentiated with respect to the scan's slice
(``grouped_matmul(..., stack=, layer=)``).

Tolerance: both sides accumulate in float32 and round once, in another
order of the sums, so a bf16 result differs by one bf16 ulp at most (2^-7
of its value) and a sum that cancels by float32's own error on its terms
(1e-3 of the largest value covers it; float32 results: 1e-4 and 1e-5). A
row given to the wrong group, a tile skipped or computed from another
group's rows is wrong by the size of the values themselves.
"""
import dataclasses
import hashlib
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


# --- the matrices read where they lie in a stack of layers ------------------
LAYERS = 3


def stacked_operands(dtype, rows, seed=2):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((rows, 256)), dtype),
            jnp.asarray(rng.standard_normal((LAYERS, GROUPS, 256, 128)) / 16,
                        dtype))


def bitwise(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()


@pytest.mark.parametrize("rows", [ROWS, 2 * ROWS])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_over_the_stack_with_a_layer_is_the_slices_product_bitwise(
        layout, dtype, rows):
    """Every layer, groups of no rows and rows past ``sum(group_sizes)``
    (all of the second half at 512 rows) included; the layer a Python int
    or a traced scalar."""
    lhs, stack = stacked_operands(DTYPES[dtype], rows)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    traced = jax.jit(lambda a, w, n: gm.gmm(a, w, sizes, layer=n))
    for n in range(LAYERS):
        want = gm.gmm(lhs, stack[n], sizes)
        bitwise(gm.gmm(lhs, stack, sizes, layer=n), want)
        bitwise(traced(lhs, stack, jnp.int32(n)), want)
    assert not np.asarray(want[int(sizes.sum()):], np.float32).any()


def test_the_rows_gradients_product_takes_a_stack_too():
    lhs, stack = stacked_operands(jnp.bfloat16, ROWS)
    sizes = jnp.asarray(LAYOUTS["ending_inside_a_tile"], jnp.int32)
    cot = lhs[:, :128]
    for n in range(LAYERS):
        bitwise(gm.gmm(cot, stack, sizes, transpose_rhs=True, layer=n),
                gm.gmm(cot, stack[n], sizes, transpose_rhs=True))


def scanned_layers(in_place, walk_rows=None):
    """One product a layer inside a ``lax.scan`` over the layers: over the
    scan's slice of the stack, or over the stack (closed over, not a
    carry) with the turn's index."""
    def run(x, stack, sizes):
        walk = gm.row_walk(sizes, x.shape[0], walk_rows or 32)

        def turn(x, xs):
            if in_place:
                y = gm.gmm(x, stack, None, walk=walk, layer=xs)
            else:
                y = gm.gmm(x, xs, None, walk=walk)
            return x + jnp.tanh(jnp.concatenate([y, y], axis=1)), None

        return jax.lax.scan(
            turn, x, jnp.arange(stack.shape[0]) if in_place else stack)[0]

    return jax.jit(run)


@pytest.mark.parametrize("layout", ["ending_inside_a_tile", "some_empty",
                                    "rows_no_group_covers"])
def test_inside_a_scan_over_the_layers_likewise(layout):
    lhs, stack = stacked_operands(jnp.bfloat16, ROWS)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    bitwise(scanned_layers(True)(lhs, stack, sizes),
            scanned_layers(False)(lhs, stack, sizes))


def differentiated_layers(in_place, remat):
    """Loss and gradients (rows, stack) of one differentiable product a
    layer inside a ``lax.scan`` over the layers, the turn under
    ``jax.checkpoint`` or not: over the scan's slice alone, or reading the
    stack (closed over under ``stop_gradient``) and differentiated with
    respect to the slice."""
    def loss(x, stack, sizes):
        walk = gm.row_walk(sizes, x.shape[0], 32)
        held = jax.lax.stop_gradient(stack)

        def turn(x, xs):
            w, n = xs
            y = gm.grouped_matmul(x, w, None, walk, stack=held, layer=n) \
                if in_place else gm.grouped_matmul(x, w, None, walk)
            return x + jnp.tanh(jnp.concatenate([y, y], axis=1)), None

        if remat:
            turn = jax.checkpoint(turn, prevent_cse=False)
        out = jax.lax.scan(turn, x, (stack, jnp.arange(stack.shape[0])))[0]
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return jax.value_and_grad(loss, argnums=(0, 1))


def shapes_made(jaxpr, shape):
    """Names of the primitives, at any depth, with a result of ``shape``."""
    for eqn in jaxpr.eqns:
        if any(getattr(v.aval, "shape", None) == shape for v in eqn.outvars):
            yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from shapes_made(sub, shape)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "saved"])
@pytest.mark.parametrize("layout", ["ending_inside_a_tile", "some_empty",
                                    "rows_no_group_covers"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_differentiated_over_the_stack_gives_the_slices_gradients(
        dtype, layout, remat):
    """Read from the stack, differentiated with respect to the slice: the
    loss, the rows' gradient and every layer's matrices' gradient are the
    slice route's to the bit, and the backward pass makes nothing of the
    stack's shape but the scan's own stacking of the layers' gradients."""
    lhs, stack = stacked_operands(DTYPES[dtype], ROWS)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    got = jax.jit(differentiated_layers(True, remat))(lhs, stack, sizes)
    want = jax.jit(differentiated_layers(False, remat))(lhs, stack, sizes)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        bitwise(a, b)
    assert all(np.asarray(layer, np.float32).any() for layer in got[1][1])
    made = list(shapes_made(jax.make_jaxpr(differentiated_layers(
        True, remat))(lhs, stack, sizes).jaxpr, stack.shape))
    assert sorted(made) == ["scan", "stop_gradient"]


def pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from pallas_calls(sub)


# sha256 of the traced call (the jaxpr with the kernel's body, block
# shapes, cost estimate and compiler parameters, then the three index
# maps), recorded on f3cc1e2, PR 44's parent. (The text LOWERED for the TPU
# carries the kernel's source path and line numbers inside the serialised
# Mosaic module, so it moves with any edit of the file.)
AS_ON_THE_PARENT = {
    False: "cff83dfac2b27dbd086cb024d35e74d388ec70cb9ecb6341eff25736df3664ee",
    True: "9436909b13be0de84b581dc076619d2d7c027e51c8434fa6072c071e643e3bcf",
}


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["forward", "rows_gradient"])
def test_without_a_layer_the_call_is_the_parents(monkeypatch, transpose_rhs):
    """OLMoE's training step calls ``gmm`` nine times a layer and must
    trace as it did: four prefetched scalars, the same blocks and index
    maps, the same cost estimate."""
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    closed = jax.make_jaxpr(
        lambda a, w, s: gm.gmm(a, w, s, transpose_rhs=transpose_rhs))(
        jax.ShapeDtypeStruct((256, 256), jnp.bfloat16),
        jax.ShapeDtypeStruct((4, 128, 256) if transpose_rhs
                             else (4, 256, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((4,), jnp.int32))
    (call,) = pallas_calls(closed.jaxpr)
    assert call.params["grid_mapping"].num_index_operands == 4
    text = "\n".join([str(closed)] + [
        str(m.index_map_jaxpr)
        for m in call.params["grid_mapping"].block_mappings])
    assert hashlib.sha256(text.encode()).hexdigest() \
        == AS_ON_THE_PARENT[transpose_rhs]


def test_the_stacked_calls_cost_counts_one_layers_matrices(monkeypatch):
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    lhs = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((4,), jnp.int32)

    def cost(rhs_shape, **layer):
        closed = jax.make_jaxpr(lambda a, w, s: gm.gmm(a, w, s, **layer))(
            lhs, jax.ShapeDtypeStruct(rhs_shape, jnp.bfloat16), sizes)
        (call,) = pallas_calls(closed.jaxpr)
        return (call.params["cost_estimate"],
                call.params["grid_mapping"].num_index_operands)

    one, scalars = cost((4, 256, 128))
    stacked, stacked_scalars = cost((LAYERS, 4, 256, 128), layer=1)
    assert stacked == one
    assert (scalars, stacked_scalars) == (4, 5)


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


# --- the rule that says where the kernel reads the matrices ------------------
def moe_gpt(**changes):
    """Two scanned expert layers at widths of whole lanes; a call over
    ``[2, 8]`` tokens sorts 64 rows a layer."""
    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig

    base = dict(
        vocab_size=64, n_positions=16, n_embd=128, n_layer=2, n_head=4,
        norm="rmsnorm", activation="silu", use_bias=False, rotary=True,
        learned_positions=False, dtype=jnp.float32, param_dtype=jnp.float32,
        scan_layers=True, use_flash_attention=False, moe_num_experts=4,
        moe_top_k=4, moe_drop_tokens=False, moe_gated_experts=True,
        moe_intermediate_size=128)
    base.update(changes)
    return GPT(GPTConfig(**base))


IDS = np.arange(16, dtype=np.int32).reshape(2, 8) % 64
# name: (mesh axes, changes to the configuration, a serving call? (else a
# training step, differentiated), the rule's answer)
MATRICES = {
    "serving_under_the_scan": (dict(), dict(), True, "in_place"),
    "serving_bf16": (dict(), dict(dtype=jnp.bfloat16,
                                  param_dtype=jnp.bfloat16), True,
                     "in_place"),
    "training_forward": (dict(), dict(), False, "in_place"),
    "training_bf16": (dict(), dict(dtype=jnp.bfloat16,
                                   param_dtype=jnp.bfloat16, remat=True),
                      False, "in_place"),
    "under_ep": (dict(ep=2), dict(), True, "slice"),
    "under_tp": (dict(tp=2), dict(), True, "slice"),
    "widths_off_128": (dict(), dict(n_embd=64), True, "slice"),
    "stored_wider_than_computed": (dict(), dict(dtype=jnp.bfloat16), True,
                                   "slice"),
    "layers_looped_over": (dict(), dict(scan_layers=False), True, "slice"),
    "with_a_capacity": (dict(), dict(moe_top_k=1, moe_drop_tokens=True),
                        True, "slice"),
    "no_experts": (dict(), dict(moe_num_experts=0), True, "none"),
    "training_under_ep": (dict(ep=2), dict(), False, "slice"),
    "training_stored_wider_than_computed": (
        dict(), dict(dtype=jnp.bfloat16), False, "slice"),
    "training_layers_looped_over": (dict(), dict(scan_layers=False), False,
                                    "slice"),
}


def counted_in_place(stats):
    """The layers' ``in_place`` counters of a ``moe_stats`` collection."""
    from flax.traverse_util import flatten_dict

    return sum((np.asarray(value).reshape(-1).tolist()
                for path, (value,) in flatten_dict(stats).items()
                if path[-1] == "in_place"), [])


def gmm_routes(monkeypatch):
    """What the traces to come hand ``gmm``: True for a stack and a layer,
    False for one layer's matrices."""
    routes, real = [], gm.gmm

    def spy(lhs, rhs, *args, **kwargs):
        routes.append(kwargs.get("layer") is not None)
        assert rhs.ndim == 3 + routes[-1]
        return real(lhs, rhs, *args, **kwargs)

    monkeypatch.setattr(gm, "gmm", spy)
    return routes


@pytest.mark.parametrize("name", MATRICES)
def test_where_the_matrices_are_read_is_told_from_what_the_call_shows(
        topology, monkeypatch, name):
    """The rule's answer, the route the model then traces (a training
    step under ``jax.grad``), and the layers' ``in_place`` counter
    agree."""
    from deepspeed_tpu.moe.layer import MOE_STATS

    axes, changes, decode, want = MATRICES[name]
    topology(**axes)
    model = moe_gpt(**changes)
    cfg = model.config
    rows = IDS.size * cfg.moe_top_k
    assert experts_mod.expert_matrices(cfg, rows) == want
    params = model.init(jax.random.PRNGKey(0), IDS)["params"]
    routes = gmm_routes(monkeypatch)
    # (each one compiled program, as a step is: outside ``jax.jit`` every
    # operation of the model is compiled by itself)
    if decode:
        _, out = jax.jit(lambda p: model.apply(
            {"params": p}, IDS, decode=True,
            mutable=["cache", MOE_STATS]))(params)
    else:
        grads, out = jax.jit(jax.grad(lambda p: model.apply(
            {"params": p}, IDS, labels=IDS, mutable=[MOE_STATS]),
            has_aux=True))(params)
        assert all(np.isfinite(np.asarray(g, np.float32)).all()
                   and np.asarray(g, np.float32).any()
                   for g in jax.tree.leaves(grads))
    tiles = experts_mod.grouped_matmul_tiles(
        rows, cfg.n_embd, cfg.moe_ffn_dim, max(cfg.moe_num_experts, 1),
        cfg.dtype)
    dropless = cfg.is_moe and not cfg.moe_drop_tokens
    assert bool(routes) == bool(dropless and tiles)
    assert set(routes) <= {want == "in_place"}
    assert counted_in_place(out.get(MOE_STATS, {})) == (
        [int(want == "in_place")] * 2 if dropless else [])


def test_a_differentiated_forward_reads_the_stack_and_init_nothing(
        monkeypatch):
    """``jax.grad`` of a training forward goes through the custom VJP on
    the stack with the layer as an index, forward and rows' gradient; the
    stacked leaves get their gradient all the same (it went to the scan's
    slices); ``init`` traces no kernel at all, serving call or not."""
    model = moe_gpt()
    routes = gmm_routes(monkeypatch)
    params = model.init(jax.random.PRNGKey(0), IDS, decode=True)["params"]
    assert not routes
    grads = jax.grad(lambda p: model.apply(
        {"params": p}, IDS, labels=IDS))(params)
    assert routes and all(routes)
    stacked = grads["h"]["block"]["mlp"]["experts"]
    assert all(np.isfinite(np.asarray(g)).all()
               and all(np.asarray(layer).any() for layer in g)
               for g in stacked.values())


TRAINED = {
    "float32_remat": dict(remat=True),
    "float32_saved": dict(),
    "bf16_remat": dict(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                       remat=True),
}


def loss_and_grads(model, params):
    """Loss, gradients and the layers' ``in_place`` counters of one
    training forward, traced anew."""
    from deepspeed_tpu.moe.layer import MOE_STATS

    jax.clear_caches()
    (loss, out), grads = jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, IDS, labels=IDS,
                              mutable=[MOE_STATS]), has_aux=True))(params)
    return (loss, grads), counted_in_place(out[MOE_STATS])


@pytest.mark.parametrize("name", TRAINED)
def test_a_training_steps_gradients_are_the_slice_routes_to_the_bit(
        monkeypatch, name):
    """A tiny scanned MoE model, under ``remat`` and without: read in
    place, the loss and EVERY parameter's gradient (``wi`` / ``wg`` /
    ``wo`` among them) are bitwise what the slices give, and the layers'
    counter says which route each program took. (After a leading dense
    block, where the turn's index is the scan's less one:
    ``test_deepseek_v2.py``; through the runs of kinds:
    ``test_lfm2.py``.)"""
    model = moe_gpt(**TRAINED[name])
    params = model.init(jax.random.PRNGKey(0), IDS)["params"]
    layers = model.config.n_layer - model.config.first_k_dense
    routes = gmm_routes(monkeypatch)
    got, counted = loss_and_grads(model, params)
    assert routes and all(routes) and counted == [1] * layers
    del routes[:]
    monkeypatch.setattr(experts_mod, "expert_matrices",
                        lambda cfg, rows: "slice")
    want, counted = loss_and_grads(model, params)
    jax.clear_caches()
    assert routes and not any(routes) and counted == [0] * layers
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        bitwise(a, b)
    stacked = got[1]["h"]["block"]["mlp"]["experts"]
    assert sorted(stacked) == ["wg", "wi", "wo"]
    assert all(np.asarray(layer, np.float32).any()
               for g in stacked.values() for layer in g)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "saved"])
def test_the_stack_gets_no_gradient_of_its_own(remat):
    """The backward pass of the model read in place: the only results of
    a stacked leaf's shape ``[layers, E, K, N]`` are the three
    ``stop_gradient`` the stack is closed over under and the backward
    scan's own stacking of the layers' ``tgmm`` results (no sum into a
    cotangent the size of the stack a turn, no zeros of its shape)."""
    model = moe_gpt(remat=remat)
    params = model.init(jax.random.PRNGKey(0), IDS)["params"]
    shape = params["h"]["block"]["mlp"]["experts"]["wi"].shape
    assert shape == (2, 4, 128, 128)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.apply(
        {"params": p}, IDS, labels=IDS)))(params).jaxpr
    assert sorted(shapes_made(jaxpr, shape)) \
        == ["scan"] + ["stop_gradient"] * 3


@pytest.mark.parametrize("stored", ["int8_at_rest", "offloaded", "gathered"])
def test_a_stack_that_is_not_what_the_layer_multiplies_by_keeps_the_slice(
        stored, monkeypatch):
    from deepspeed_tpu.runtime.zero import gather

    changes = {"int8_at_rest": dict(quantized_weights=True),
               "offloaded": dict(param_offload=True), "gathered": dict()}
    cfg = moe_gpt(**changes[stored]).config
    assert experts_mod.expert_matrices(moe_gpt().config, 64) == "in_place"
    if stored == "gathered":
        # every program under a ZeRO-3 plan, the step's among them
        monkeypatch.setattr(gather, "current_plan", lambda: object())
    assert experts_mod.expert_matrices(cfg, 64) == "slice"


@pytest.mark.parametrize("call", ["serving", "training"])
def test_a_tree_in_another_dtype_than_declared_keeps_the_slice(
        monkeypatch, call):
    """The configuration says bf16 parameters, the caller hands float32
    ones: the layer casts its slice, as it did."""
    model = moe_gpt(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), model.init(
        jax.random.PRNGKey(0), IDS)["params"])
    routes = gmm_routes(monkeypatch)
    if call == "serving":
        model.apply({"params": params}, IDS, decode=True, mutable=["cache"])
    else:
        jax.grad(lambda p: model.apply({"params": p}, IDS, labels=IDS))(
            params)
    assert routes and not any(routes)


def test_no_field_or_variable_chooses_it():
    import inspect

    from deepspeed_tpu.models.transformer_lm import GPTConfig

    fields = {f.name for f in dataclasses.fields(StackedExperts)}
    assert fields == {"num_experts", "d_model", "d_hidden", "dtype",
                      "param_dtype", "activation", "gated", "use_bias",
                      "parent", "name"}
    for module in (experts_mod, gm):
        assert "environ" not in inspect.getsource(module)
    # where the matrices are read is asked with the model's configuration
    # and the rows, of a serving call and a training step alike, and
    # nothing of the configuration names the answer
    assert list(inspect.signature(experts_mod.expert_matrices).parameters) \
        == ["cfg", "rows"]
    assert not {f.name for f in dataclasses.fields(GPTConfig)
                if "in_place" in f.name or "stacked" in f.name
                or "expert_matrices" in f.name}


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


def compiled_for_the_chip(fn, *args):
    """The compiled program's text, with JAX's persistent cache off (an
    executable for a described chip can be written to it and not read)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("call", CELL_CALLS)
def test_the_cells_calls_compile_for_the_chip_and_keep_their_name(
        one_chip, call, monkeypatch):
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
    text = compiled_for_the_chip(jax.jit(run), *args)
    name = gm.TGMM_NAME if kind == "tgmm" else gm.GMM_NAME
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert len(calls) == 1 and f"%{name}" in calls[0]
    # no copy of an operand: neither a transposed expert tensor nor rows
    assert not [line for line in text.splitlines() if "bf16[" in line
                and (" transpose(" in line or " copy(" in line)]


@pytest.mark.parametrize("route", ["in_place", "slice"])
def test_the_scan_over_the_stack_compiles_to_no_copy_of_a_layers_matrices(
        one_chip, route, monkeypatch):
    """Scanned over slices, the loop's body writes the turn's ``[E, K, N]``
    out before the custom call may read it (the control: the test sees the
    copy, under the name the v5e's trace gave it); handed the stack and the
    turn's index it holds the one call and no such result."""
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    layers, groups, k, n, rows = 4, 20, 512, 256, 256

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = compiled_for_the_chip(
        scanned_layers(route == "in_place", walk_rows=128),
        spec(rows, k), spec(layers, groups, k, n),
        spec(groups, dtype=jnp.int32))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert len(calls) == 1 and f"%{gm.GMM_NAME}" in calls[0]
    whole_layer = [line.split("=")[0].strip() for line in text.splitlines()
                   if f"= bf16[{groups},{k},{n}]" in line
                   and " parameter(" not in line
                   and " get-tuple-element(" not in line]
    if route == "in_place":
        assert not whole_layer
        # the stack rides the loop as it came in
        assert f"bf16[{layers},{groups},{k},{n}]" in calls[0]
    else:
        assert any(name.startswith("%dynamic-slice") and "fusion" in name
                   for name in whole_layer)


# the flash kernels live in this file's compile tests too: one file holds
# every test that describes the chip (a second could go to another worker)
FLASH_CELLS = {"gpt-1.3b-train": (96, 1024),
               "olmoe-1b-7b-train-4k": (32, 4096)}


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
@pytest.mark.parametrize("cell", sorted(FLASH_CELLS))
def test_the_flash_kernels_compile_for_the_chip_under_the_tables_schedule(
        one_chip, cell, kernel, monkeypatch):
    """The v5e's measured entries at the two training cells' shapes: whole
    strips (at 4,096 with the VMEM limit asked) compile, and each call
    keeps the name and the result signature the benchmark's reader tells
    the three apart by."""
    import importlib

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    bh, t = FLASH_CELLS[cell]
    d = 128
    blocks = fa.fit_blocks(kernel, t, True, *autotune.PRETUNED[
        "TPU v5 lite", t, d, "bfloat16", True][fa.KERNELS.index(kernel)])
    assert blocks[kernel == fa.KERNEL_BWD_DKV] in (t, t // 2)

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, rows = spec(bh, t, d), spec(bh, t, fa.LSE_LANES, dtype=jnp.float32)
    scale = d ** -0.5
    run = {fa.KERNEL_FWD: lambda q, k, v, do, lse, delta: fa._call_fwd(
               q, k, v, None, scale, True, blocks),
           fa.KERNEL_BWD_DQ: lambda *ops: fa._call_dq(
               ops, None, scale, True, blocks),
           fa.KERNEL_BWD_DKV: lambda *ops: fa._call_dkv(
               ops, None, scale, True, blocks)}[kernel]
    text = compiled_for_the_chip(jax.jit(run), x, x, x, x, rows, rows)
    (call,) = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "custom-call(" in line]
    assert f"%{kernel}" in call
    results = call.split(" custom-call(")[0].split(" = ", 1)[1]
    big, lse = f"bf16[{bh},{t},{d}]", f"f32[{bh},{t},{fa.LSE_LANES}]"
    assert (results.count(big), results.count(lse)) == {
        fa.KERNEL_FWD: (1, 1), fa.KERNEL_BWD_DQ: (1, 0),
        fa.KERNEL_BWD_DKV: (2, 0)}[kernel]


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
@pytest.mark.parametrize("window", [4096, None], ids=["window", "full"])
def test_the_flash_kernels_compile_at_16k_over_grouped_queries(
        one_chip, window, kernel, monkeypatch):
    """The window-and-full training cell's shape (28 query heads over 4 KV
    heads of 128, 16,384 positions): with and without the window each
    kernel compiles for the chip under the schedule the resolver gives,
    keeps the name a reader tells the two families apart by, and holds K
    and V at their own head count; under the window the loop's operands
    are bands of ``window + strip`` positions, not the sequence."""
    import importlib

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(autotune.jax, "devices", lambda: [
        type("D", (), {"device_kind": "TPU v5 lite"})()])
    autotune.clear_memory_cache()
    h, kv, t, d = 28, 4, 16384, 128
    wanted, _ = autotune.get_flash_schedule(t, d, jnp.bfloat16, True, window)
    blocks = fa.fit_blocks(kernel, t, True, *wanted[kernel], window=window)
    autotune.clear_memory_cache()

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, k = spec(h, t, d), spec(kv, t, d)
    rows = spec(h, t, fa.LSE_LANES, dtype=jnp.float32)
    scale = d ** -0.5
    if window is None:
        run = {fa.KERNEL_FWD: lambda q, k, v, do, lse, delta: fa._call_fwd(
                   q, k, v, None, scale, True, blocks),
               fa.KERNEL_BWD_DQ: lambda *ops: fa._call_dq(
                   ops, None, scale, True, blocks),
               fa.KERNEL_BWD_DKV: lambda *ops: fa._call_dkv(
                   ops, None, scale, True, blocks)}[kernel]
    else:
        run = {fa.KERNEL_FWD: lambda q, k, v, do, lse, delta:
               fa._call_fwd_window(q, k, v, scale, window, blocks, False),
               fa.KERNEL_BWD_DQ: lambda *ops: fa._call_dq_window(
                   ops, scale, window, blocks, False),
               fa.KERNEL_BWD_DKV: lambda *ops: fa._call_dkv_window(
                   ops, scale, window, blocks, False)}[kernel]
        assert fa.band_rows(kernel, blocks, window) \
            == window + blocks.strip(kernel) < t
    text = compiled_for_the_chip(jax.jit(run), q, k, k, q, rows, rows)
    (call,) = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "custom-call(" in line]
    assert f"%{fa.kernel_name(kernel, window)}" in call
    assert ("%window_" in call) == (window is not None)
    operands = call.split(" custom-call(")[1]
    assert operands.count(f"bf16[{kv},{t},{d}]") == 2       # K and V
    results = call.split(" custom-call(")[0].split(" = ", 1)[1]
    assert results.count(f"bf16[{h},{t},{d}]") == {
        fa.KERNEL_FWD: 1, fa.KERNEL_BWD_DQ: 1, fa.KERNEL_BWD_DKV: 2}[kernel]


def test_the_selected_decode_step_compiles_for_the_chip_at_the_cells_shape(
        one_chip, monkeypatch):
    """``ops/indexed_attention.py`` ``decode_step`` inside a scan over the
    stacked leaves, at the selected-attention cell's shape (32 lanes of
    24,576 positions, 4 KV heads of 128 under 32, ``topk`` 2,048): one
    conditional, the ``decode_attn`` call in one branch and the two gathers
    in the other; nothing is sorted, and the selection's searches are two
    loops beside the scan's (over the scores' bits and over the ties'
    positions: ``decode_step`` asks for the set as a mask and, through
    ``choose``, as rows, and the compiler merges the two into one), each
    pass over ``[lanes, positions]``, eight lanes a tile (with a ``[lanes,
    1, positions]`` operand of the conditional the sort that stood here
    was laid out a row a tile and ran eight times as long on the chip:
    PERF.md, PR 48); and no stacked key or value leaf is copied on its way
    into either form."""
    from deepspeed_tpu.ops import indexed_attention as ia
    from deepspeed_tpu.ops.pallas import decode_attention as da

    monkeypatch.setattr(da, "_interpret", lambda: False)
    layers, lanes, positions, kv, heads, d = 2, 32, 24576, 4, 32, 128
    ix_heads, ix_dim, topk = 16, 64, 2048

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(q, q_idx, w, keys, values, index_keys, visible, clock):
        def layer(carry, i):
            keys, values, index_keys, y = carry
            out, rows, ok = ia.decode_step(
                q + y, q_idx, w, keys, values, index_keys, i, visible, clock,
                topk, jnp.bfloat16)
            return (keys, values, index_keys, out), rows
        return jax.lax.scan(layer, (keys, values, index_keys, q),
                            jnp.arange(layers))

    text = compiled_for_the_chip(
        jax.jit(run, donate_argnums=(3, 4, 5)),
        spec(lanes, heads, d), spec(lanes, ix_heads, ix_dim),
        spec(lanes, ix_heads, dtype=jnp.float32),
        spec(layers, lanes, positions, kv, d),
        spec(layers, lanes, positions, kv, d),
        spec(layers, lanes, positions, ix_dim),
        spec(lanes, positions, dtype=jnp.bool_),
        spec(lanes, dtype=jnp.int32))
    lines = text.splitlines()
    calls = [line for line in lines
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert len(calls) == 1 and f"%{da.KERNEL_NAME}" in calls[0]
    assert len([line for line in lines if " conditional(" in line]) == 1
    assert sum(" gather(" in line for line in lines) == 2
    assert not [line[:160] for line in lines if re.search(r" sort\(", line)]
    loops = [line for line in lines if " while(" in line]
    assert len(loops) == 3
    searches = [line for line in loops if f" u32[{lanes},1]" in line]
    assert len(searches) == 2
    assert f"u32[{lanes},{positions}]{{1,0:T(8,128)" in searches[0]
    assert f"pred[{lanes},{positions}]{{1,0:T(8,128)" in searches[1]
    leaf = f"bf16[{layers},{lanes},{positions},{kv},{d}]"
    assert not [line[:160] for line in lines
                if re.search(r"= " + re.escape(leaf) + r"\S* (copy|fusion)\(",
                             line)]


def materialised(text):
    """``(name, shape)`` of the compiled program's instructions that write
    a result of their own: those of the entry and the loops' bodies, not
    the ones fused into another (a fused computation is named by a
    ``calls=``), and neither parameters nor views, nor the compiler's own
    asynchronous prefetch of an operand into the chip's fast memory
    (``slice-done``, ``copy-done``)."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    computation = None
    for line in text.splitlines():
        opened = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$", line)
        if opened:
            computation = opened.group(1)
        elif line.startswith("}"):
            computation = None
        elif computation is not None and computation not in fused:
            made = re.match(
                r"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                line)
            if made and made.group(3) not in (
                    "parameter", "get-tuple-element", "bitcast", "tuple",
                    "slice-done", "copy-done"):
                yield made.group(1), tuple(
                    int(d) for d in made.group(2).split(",") if d)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_a_latent_models_serving_programs_write_no_copy_of_q_b(
        one_chip, program, monkeypatch):
    """The decode step and a prefill of a small latent-attention model
    under ``ScannedBlocks``, compiled for the chip: no instruction's result
    has the elements of a whole layer's ``q_b`` kernel, whatever its
    layout: no slice of the stack written out, no transposed copy (on the
    parent of PR 55 the decode step had both, under the names the v5e's
    trace gave them: ``constant_dynamic-slice_fusion.6``, ``copy.28``).
    This is the test that fails when an edit to ``LatentAttention`` brings
    back the convolution that XLA makes of the second query projection and
    the 192-wide per-head view after it. ``kv_b`` is not held to the same:
    the absorbed form's two einsums over it viewed per head still copy it
    (a kernel over the leaf as stored was measured and lost: PERF.md,
    section 6, PR 55)."""
    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig, MLAConfig
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda

    monkeypatch.setattr(lda, "_interpret", lambda: False)
    heads, q_rank, kv_rank, nope, rope, lanes = 8, 384, 256, 128, 64, 32
    cfg = GPTConfig(
        vocab_size=512, n_positions=256, n_embd=128, n_layer=3, n_head=heads,
        norm="rmsnorm", use_bias=False, rotary=True, learned_positions=False,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, scan_layers=True,
        use_flash_attention=False, num_logits_to_keep=1,
        mla=MLAConfig(q_rank=q_rank, kv_rank=kv_rank, nope_dim=nope,
                      rope_dim=rope, v_dim=nope))
    model = GPT(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    def prefill(params, ids):
        logits, out = model.apply({"params": params}, ids,
                                  deterministic=True, decode=True,
                                  mutable=["cache"])
        return logits[:, -1], out["cache"]

    def decode(params, token, cache):
        logits, out = model.apply({"params": params, "cache": cache},
                                  token[:, None], deterministic=True,
                                  decode=True, mutable=["cache"])
        return logits[:, -1], out["cache"]

    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert params["h"]["block"]["attn"]["q_b"]["kernel"].shape \
        == (3, q_rank, heads * (nope + rope))
    if program == "decode":
        cache = jax.eval_shape(
            prefill, params, jnp.zeros((lanes, 1), jnp.int32))[1]
        text = compiled_for_the_chip(
            jax.jit(decode, donate_argnums=(2,)), on_chip(params),
            on_chip(jax.ShapeDtypeStruct((lanes,), jnp.int32)),
            on_chip(cache))
        assert f"%{lda.KERNEL_NAME}" in text
    else:
        text = compiled_for_the_chip(
            jax.jit(prefill), on_chip(params),
            on_chip(jax.ShapeDtypeStruct((1, 128), jnp.int32)))
    whole = q_rank * heads * (nope + rope)
    assert not [(name, shape) for name, shape in materialised(text)
                if int(np.prod(shape)) == whole]


@pytest.mark.parametrize("route", ["in_place", "slice"])
def test_the_olmoe_train_step_slices_no_expert_tensor_out_of_the_stack(
        one_chip, route, monkeypatch):
    """The differentiated step of ``olmoe-1b-7b-train-4k`` (the published
    widths, 3 layers, 2 x 4,096 tokens: 65,536 sorted rows a layer, full
    remat, the flash and grouped-matmul kernels), compiled for the chip.
    The instructions that write a whole expert tensor of one layer
    (``[64, 2048, 1024]`` or ``[64, 1024, 2048]``, 268 MB), each run once a
    layer: on the slice route (the control, and every tree before PR 58)
    three ``dynamic-slice`` in the forward loop's body, three more in the
    backward's (one serves the recomputed forward call and the rows'
    gradient), and three ``dynamic-update-slice`` that put the ``tgmm``
    results into the stacked gradient: nine a layer, 27 a step. Read in
    place the six slices are gone and the three writes stay (ROADMAP S17):
    9 a step."""
    import importlib

    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    if route == "slice":
        monkeypatch.setattr(experts_mod, "expert_matrices",
                            lambda cfg, rows: "slice")
    layers, experts, d_model, d_hidden = 3, 64, 2048, 1024
    model = GPT(GPTConfig(
        vocab_size=50304, n_positions=4096, n_embd=d_model, n_layer=layers,
        n_head=16, intermediate_size=d_hidden, norm="rmsnorm",
        layer_norm_epsilon=1e-5, activation="silu", use_bias=False,
        rotary=True, learned_positions=False, tie_word_embeddings=False,
        qk_norm=True, moe_num_experts=experts, moe_top_k=8,
        moe_drop_tokens=False, moe_gated_experts=True,
        moe_norm_topk_prob=False, moe_aux_loss_coef=0.01,
        moe_z_loss_coef=0.001, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        scan_layers=True, remat=True, remat_policy="full",
        use_flash_attention=True))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    def step(params, ids):
        return jax.grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids))(params)

    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    text = compiled_for_the_chip(
        jax.jit(step), on_chip(params),
        on_chip(jax.ShapeDtypeStruct((2, 4096), jnp.int32)))
    up, down = (experts, d_model, d_hidden), (experts, d_hidden, d_model)
    made = list(materialised(text))
    # a layer's tensor made outside the kernel that computes it
    sliced = [name for name, shape in made if shape in (up, down)
              and not name.startswith(f"%{gm.TGMM_NAME}")]
    written = [name for name, shape in made
               if shape in ((layers,) + up, (layers,) + down)
               and "dynamic-update-slice" in name]
    assert len(written) == 3
    if route == "in_place":
        assert not sliced
    else:
        assert len(sliced) == 6 and all(
            name.startswith("%dynamic-slice") for name in sliced)
    assert (len(sliced) + len(written)) * layers \
        == {"in_place": 9, "slice": 27}[route]
    # the kernels are the cell's: nine grouped matmuls a layer
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert sum(f"%{gm.GMM_NAME}" in line for line in calls) == 6 + 3
    assert sum(f"%{gm.TGMM_NAME}" in line for line in calls) == 3


# --- the row-fetch kernels at the window-and-full cell's shapes -------------
ROW_FETCH_CALLS = ["rows", "rows_scaled", "words", "sum", "dot"]


@pytest.mark.parametrize("call", ROW_FETCH_CALLS)
def test_the_row_fetch_kernels_compile_for_the_chip_at_the_cells_shape(
        one_chip, call, monkeypatch):
    """98,304 sorted rows of 2,560 bf16 from 16,384 tokens, the live count
    a run-time scalar: Mosaic takes the row copies out of the row-major
    words, the strided loads and stores and the dynamic grid (interpret
    mode refuses none of them), and XLA copies no ``[rows, width]``
    operand on the way in or out."""
    from deepspeed_tpu.ops.pallas import row_fetch as rf

    monkeypatch.setattr(rf, "_interpret", lambda: False)
    tokens, k, width = 16384, 6, 2560
    rows = tokens * k

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    live = spec(dtype=jnp.int32)
    run, args, name = {
        "rows": (lambda s, i, n: rf.fetch_rows(s, i, n, zero_to=512),
                 (spec(tokens, width), spec(rows, dtype=jnp.int32), live),
                 rf.FETCH_NAME),
        "rows_scaled": (
            lambda s, i, n, w: rf.fetch_rows(s, i, n, w, zero_to=512),
            (spec(tokens, width), spec(rows, dtype=jnp.int32), live,
             spec(rows, dtype=jnp.float32)), rf.FETCH_NAME),
        "words": (rf.as_words, (spec(rows, width), live), rf.WORDS_NAME),
        "sum": (lambda w, i, p, n: rf.fetch_sum_rows(
            w, i, p, n, dtype=jnp.bfloat16),
            (spec(rows * width // 256, 128, dtype=jnp.uint32),
             spec(tokens, k, dtype=jnp.int32),
             spec(tokens, k, dtype=jnp.float32), live), rf.FETCH_SUM_NAME),
        "dot": (rf.fetch_dot_rows,
                (spec(rows * width // 256, 128, dtype=jnp.uint32),
                 spec(tokens, k, dtype=jnp.int32), spec(tokens, width),
                 live), rf.FETCH_DOT_NAME)}[call]
    text = compiled_for_the_chip(jax.jit(run), *args)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert [c for c in calls if f"%{name}." in c or f"%{name} " in c]
    assert not [line for line in text.splitlines()
                if ("bf16[" in line or "u32[" in line)
                and (" transpose(" in line or " copy(" in line)]
