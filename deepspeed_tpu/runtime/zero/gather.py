"""ZeRO-3's gather at the point of use.

A parameter stored ``fsdp``-sharded (``ZeroShardingRules.param_spec``) is
cast to the compute dtype and constrained, where a layer reads it, to its
stored spec WITHOUT ``fsdp``: the partitioner has to all-gather the weight
there. The cotangent is constrained back to the stored spec, so the sum
over chips is a reduce-scatter into the shard each chip keeps (reference
``partitioned_param_coordinator.py:237`` fetch_sub_module and
``stage3.py:1089`` reduce-scatter of the layer's gradients).

Why the stored sharding alone did not do it (PERF.md, PR 29): the shard
sits on a leaf's largest dimension, for an MLP kernel its feature
dimension, and nothing in a plain GSPMD step says that the weight is to be
gathered. The partitioner read the shard as tensor parallelism and paid by
resharding the batch-sharded activations: five ``all-to-all`` a layer on
the chip, 178 ms of a 782 ms step exposed.

How a model learns of it: the engine enters :func:`gather_context` around
``model.apply`` while it traces a step program; a model's layer loop asks
:func:`gathered_on_use` for its layer class and gets it back wrapped
(``nn.map_variables`` over ``params``, inside the loop body, so that one
layer's weights are whole at a time and ``nn.remat`` gathers them again in
the backward pass). Outside such a context, and in one whose rules are not
stage 3 over ``fsdp > 1``, the class comes back as it went in and the
traced program is what it was without this module.

What a gather inside a loop body cannot have is a start before its turn:
the compiler schedules no collective across a ``while`` turn, so the first
weight a layer reads, and the first its recomputation reads, arrive with
nothing of that turn running (15.1 of 27.7 ms exposed a step at 1.3B on
four v5e chips, PERF.md, PR 29). The reference prefetches the coming
submodules' parameters under the current one
(``partitioned_param_coordinator.py`` fetch_sub_module, bounded by
``stage3_prefetch_bucket_size`` and ``stage3_max_live_parameters``). Here
a layer loop asks :func:`layers_per_turn`, which reads the same two keys
(:func:`turn_length`), and where a turn may gather for more than its own
layer it runs as :func:`layers_ahead`: turn i issues the gather of layer
i + 1's first weight and hands the result on through the loop's carry, in
the forward loop and, mirrored, in the backward loop that recomputes; the
vectors of all layers (a few hundred kilobytes) are gathered once ahead of
each loop, so that no turn opens on a collective. The gathered weight lives
for two turns and is never among what the forward loop saves for the
backward one. (The scan's own ``unroll`` was tried first and measured
slower on the chip at 2, 3 and 4 layers a turn: PERF.md, PR 60.)
"""

import contextlib
import contextvars
import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.telemetry.bus import KIND_ZERO3_GATHER_PLAN, publish
from deepspeed_tpu.telemetry.scopes import SCOPE_ZERO3_GATHER
from deepspeed_tpu.utils.tree import path_str

FSDP = "fsdp"


class GatherPlan:
    """What one step program gathers, filled while the program is traced
    and published when the trace ends. Bytes are what arrives at (gather)
    or leaves (reduce-scatter) one chip in a step: (fsdp - 1) / fsdp of
    the leaf in the dtype it travels in, per use."""

    def __init__(self, rules, program: str):
        self.rules = rules
        self.program = program
        # (path, use site) -> bytes gathered, bytes reduce-scattered per
        # step. A body traced twice writes the same entries twice.
        self.gathered: Dict[Tuple[str, str], Tuple[int, int]] = {}
        self.persistent = set()
        # layer loop -> (layers a turn gathers for, gathers a step that
        # nothing of their loop runs under): what :func:`layers_per_turn`
        # answered
        self.loops: Dict[str, Tuple[int, int]] = {}

    def event(self) -> Dict[str, Any]:
        return {
            "program": self.program,
            "fsdp": self.rules.topo.size(FSDP),
            "leaves_gathered": len({path for path, _ in self.gathered}),
            "leaves_persistent": len(self.persistent),
            "bytes_gathered_per_step": sum(
                g for g, _ in self.gathered.values()),
            "bytes_reduce_scattered_per_step": sum(
                s for _, s in self.gathered.values()),
            "layers_per_turn": max(
                [k for k, _ in self.loops.values()], default=1),
            "gathers_at_turn_head_per_step": sum(
                heads for _, heads in self.loops.values()),
        }


_PLAN: contextvars.ContextVar = contextvars.ContextVar(
    "zero3_gather_plan", default=None)


def current_plan() -> Optional[GatherPlan]:
    """The plan of the step program being traced, or None."""
    return _PLAN.get()


@contextlib.contextmanager
def _entered(plan: GatherPlan):
    token = _PLAN.set(plan)
    try:
        yield plan
    finally:
        _PLAN.reset(token)


@contextlib.contextmanager
def gather_context(rules, program: str):
    """Entered by the engine around ``model.apply`` in a step program's
    loss function. Carries ``rules`` to the models' layer loops at trace
    time; under any rules but stage 3 over ``fsdp > 1`` it carries nothing.
    On the way out it publishes the ``zero3.gather_plan`` event: once per
    trace of a program, never per step."""
    if rules.stage < 3 or rules.topo.size(FSDP) <= 1:
        yield None
        return
    plan = GatherPlan(rules, program)
    with _entered(plan):
        yield plan
    if plan.gathered or plan.persistent:
        publish(KIND_ZERO3_GATHER_PLAN, **plan.event())


def _without_fsdp(spec: Sequence) -> Tuple:
    out = []
    for entry in spec:
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a != FSDP)
            out.append(kept[0] if len(kept) == 1 else (kept or None))
        else:
            out.append(None if entry == FSDP else entry)
    return tuple(out)


def _stored_and_use(rules, path: str, shape, ndim: int):
    """A leaf's stored spec and its spec in use (``fsdp`` taken out), both
    over its last ``ndim`` dimensions: inside a loop's body the stored
    ``shape`` has a leading layer axis that the slice lacks."""
    spec = tuple(rules.param_spec(path, shape))
    spec = (spec + (None,) * (len(shape) - len(spec)))[len(shape) - ndim:]
    return spec, _without_fsdp(spec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _gather(x, use, stored, dtype, param_dtype):
    with jax.named_scope(SCOPE_ZERO3_GATHER):
        # cast first: half the bytes on the wire for float32 parameters,
        # and exact, because the leaf's consumer casts it anyway and a cast
        # commutes with a gather
        return jax.lax.with_sharding_constraint(x.astype(dtype), use)


def _gather_fwd(x, use, stored, dtype, param_dtype):
    return _gather(x, use, stored, dtype, param_dtype), None


def _gather_bwd(use, stored, dtype, param_dtype, _, ct):
    with jax.named_scope(SCOPE_ZERO3_GATHER):
        # the plain transpose of a sharding constraint constrains the
        # cotangent to the same (gathered) sharding: an all-reduce that
        # leaves every chip the whole gradient. Constrained to the STORED
        # sharding the sum is a reduce-scatter; it runs in the cotangent's
        # dtype (the compute dtype), as the sum of the plain GSPMD step did
        ct = jax.lax.with_sharding_constraint(ct, stored)
        return (ct.astype(param_dtype),)


_gather.defvjp(_gather_fwd, _gather_bwd)


def _gather_leaf(plan, path: str, x, stacked, dtype, keep_dtype):
    """One leaf gathered for use, and the bytes one gather of it brings to
    a chip; ``None`` for a leaf that is stored whole (``gather_tree`` says
    which leaves travel in ``dtype``)."""
    rules = plan.rules
    shape = tuple(x.shape) if stacked is None else (stacked,) + tuple(x.shape)
    spec, use = _stored_and_use(rules, path, shape, x.ndim)
    if use == spec:
        return None
    n = rules.topo.size(FSDP)
    floating = jnp.issubdtype(x.dtype, jnp.floating)
    to = dtype if (floating and x.ndim >= 2
                   and not path.endswith(tuple(keep_dtype))) else x.dtype
    size = int(np.prod(x.shape)) * (stacked or 1)
    wire = size * jnp.dtype(to).itemsize * (n - 1) // n
    use = NamedSharding(rules.topo.mesh, PartitionSpec(*use))
    if not floating:  # int8-at-rest weights: no cotangent to scatter
        with jax.named_scope(SCOPE_ZERO3_GATHER):
            return jax.lax.with_sharding_constraint(x, use), wire, False
    stored = NamedSharding(rules.topo.mesh, PartitionSpec(*spec))
    return _gather(x, use, stored, jnp.dtype(to), jnp.dtype(x.dtype)), \
        wire, True


def gather_tree(tree, prefix: Sequence[str], dtype, *,
                stacked: Optional[int] = None, uses: int = 1,
                keep_dtype: Sequence[str] = (), site: str = ""):
    """One module's ``params`` subtree (or one leaf), gathered for use.

    ``prefix`` is the subtree's path in the engine's parameter tree.
    ``stacked`` is the length of the leading layer axis that the stored
    leaf has and this slice of it lacks (inside a scanned loop's body).
    ``uses`` is how often a step gathers the leaf: 2 under ``nn.remat``,
    whose backward pass gathers again; ``site`` tells a leaf's second use
    site from its first in the plan (a tied table: lookup and head).

    Leaves whose spec has no ``fsdp`` (``param_persistence_threshold``
    kept them whole, or the shard is on the layer axis itself) pass through
    untouched. Of the others, floating leaves with two or more dimensions
    are cast to ``dtype`` before the gather: their consumers (``nn.Dense``,
    ``nn.Embed``, the experts' matmuls) cast them anyway. Vectors keep
    their dtype (norms multiply in float32; they are a few kilobytes), and
    so does a leaf whose path ends with one of ``keep_dtype``, which the
    caller knows is read as stored.

    A leaf that :func:`layers_ahead` gathered a turn early arrives here
    whole and in ``dtype``: cast and constraint are then no operation, the
    cotangent is still reduce-scattered here, and the plan counts the
    leaf's one gather a use as it always did.
    """
    plan = current_plan()
    if plan is None:
        return tree
    prefix = "/".join(prefix)

    def leaf(path, x):
        path = "/".join(p for p in (prefix, path_str(path)) if p)
        out = _gather_leaf(plan, path, x, stacked, dtype, keep_dtype)
        if out is None:
            plan.persistent.add(path)
            return x
        value, wire, floating = out
        plan.gathered[path, site] = (wire * uses, wire if floating else 0)
        return value

    return jax.tree_util.tree_map_with_path(leaf, tree)


def turn_length(n_layers: int, layer_elements: int, ahead_elements: int,
                prefetch_bucket_size: int, max_live_parameters: int) -> int:
    """How many layers' gathers one turn of a layer loop may hold.

    A turn that holds k gathers ahead of use the first weight of each of
    the k - 1 layers after its own (``ahead_elements`` each), which
    ``stage3_prefetch_bucket_size`` bounds, and has up to k layers'
    gathered weights (``layer_elements`` each) whole at once, which
    ``stage3_max_live_parameters`` bounds. Never more than the loop has
    layers, never less than one."""
    if min(layer_elements, ahead_elements) <= 0:
        return 1                # nothing is gathered: nothing to prefetch
    ahead = 1 + prefetch_bucket_size // ahead_elements
    whole = max_live_parameters // layer_elements
    return max(1, min(n_layers, ahead, whole))


def layers_per_turn(stack, prefix: Sequence[str], n_layers: int, dtype, *,
                    uses: int = 1, keep_dtype: Sequence[str] = (),
                    may_hand_on: bool = True):
    """For a layer loop over ``stack`` (its parameters as stored,
    ``n_layers`` on their leading axis; the other arguments as
    :func:`gather_tree` takes them): how many layers a turn gathers for,
    from what a layer gathers and the rules' two bounds, told to the plan
    (its event's ``layers_per_turn``); :func:`layers_ahead` hands a weight
    on by one turn, so 2 is the most. Returned, where it is 2, is the
    argument that function wants: which leaf of the
    flattened stack is gathered a turn early, the first matrix of the
    layer's tree that is gathered at all (its mixer's input projection:
    ``attn`` sorts before the norms and the MLP), with its gather, and the
    gathered vector leaves, each with the gather of its whole stack. What
    is gathered ahead of use, and has to fit, is that matrix and all layers'
    vectors. ``None`` where that may not be, where the caller's loop
    cannot run as :func:`layers_ahead` (``may_hand_on``), and when no step
    program with something to gather is being traced."""
    plan = current_plan()
    if plan is None:
        return None
    prefix = "/".join(prefix)
    head, vectors, layer = None, [], 0
    for at, (path, x) in enumerate(
            jax.tree_util.tree_flatten_with_path(stack)[0]):
        path = "/".join(p for p in (prefix, path_str(path)) if p)
        spec, use = _stored_and_use(plan.rules, path, tuple(x.shape),
                                    x.ndim - 1)
        if use == spec:
            continue
        size = int(np.prod(x.shape[1:]))
        layer += size
        if not jnp.issubdtype(x.dtype, jnp.floating):
            continue
        if x.ndim < 3:
            vectors.append((at, path, size))
        elif head is None:
            head = (at, path, size)
    ahead = 0 if head is None else head[2] + n_layers * sum(
        size for _, _, size in vectors)
    k = 1 if not may_hand_on else min(2, turn_length(
        n_layers, layer, ahead, plan.rules.prefetch_bucket_size,
        plan.rules.max_live_parameters))
    # the gathers a step makes with nothing of their loop to run under:
    # every layer's first, or the one ahead of each loop
    plan.loops[prefix] = (k, uses * (n_layers if k == 1 else 1))
    if k == 1:
        return None

    def whole(path, stacked, keep):
        return lambda x: _gather_leaf(plan, path, x, stacked, dtype, keep)[0]

    return (head[0], whole(head[1], n_layers, keep_dtype),
            # a vector travels as it is stored, whatever its stack's rank
            [(at, whole(path, None, (path,))) for at, path, _ in vectors])


def layers_ahead(layer, stack, x, consts, n_layers: int, ahead):
    """``x`` through ``n_layers`` layers, ``layer(params_i, x, i, consts)
    -> (x, aux_i)`` over the slices of ``stack``, with one leaf of layer
    i + 1 gathered while layer i runs: returns ``(x, [aux_0, ...])``.

    ``ahead`` is :func:`layers_per_turn`'s. Ahead of the loop every layer's
    vectors are gathered, one gather a leaf, and layer 0's first matrix.
    Turn i slices layer i's parameters, puts what arrived whole in the
    place of its shards, issues the gather of layer i + 1's first matrix
    and hands that on, tied to the turn's output so that the compiler lets
    it run to the turn's end (left free it was gathered in the open). The
    last layer runs after the loop, where nothing is left to gather, so a
    step makes the gathers it made and no more. What the forward loop
    saves is each layer's input, as ``nn.remat`` around a layer does. The
    backward pass is written out, because the one JAX derives gathers
    where a turn recomputes: it walks the layers downwards, turn i
    recomputing layer i under ``jax.checkpoint`` (so the recomputed
    operations keep JAX's ``rematted_computation`` name) from its saved
    input and the carried leaf, and gathering layer i - 1's. Gradients of
    the slices go into the stacked gradient in place; those of the leaves
    that arrived whole come back from :func:`gather_tree` reduce-scattered
    like any other.
    """
    leaves, treedef = jax.tree_util.tree_flatten(stack)
    at, gather, vectors = ahead
    last = n_layers - 1
    plan = current_plan()

    def sliced(leaf, i):
        if isinstance(i, int):  # outside the loops: a static slice
            return leaf[i]
        return jax.lax.dynamic_index_in_dim(leaf, i, keepdims=False)

    def with_vectors(leaves):
        """The stack with every layer's vectors whole: one gather a leaf
        ahead of a loop, so that no turn opens on a collective."""
        leaves = list(leaves)
        for place, gather_all in vectors:
            leaves[place] = gather_all(leaves[place])
        return leaves

    def params(leaves, i, whole):
        slices = [sliced(leaf, i) for leaf in leaves]
        slices[at] = whole
        return treedef.unflatten(slices)

    def shard(leaves, i):
        return sliced(leaves[at], i)

    # The layer is traced ONCE (``i`` an int32 array at every call site):
    # both loops' turns and the layers outside them share that trace,
    # inlined where it is called, so the program is what it would be and
    # the step's set-up pays for one trace of a layer, as the scan's did.
    apply = jax.jit(layer, inline=True)

    @functools.partial(jax.jit, inline=True)
    def back(p, x, i, consts, dy, daux):
        recomputed = jax.checkpoint(
            lambda p, x: apply(p, x, i, consts), prevent_cse=False)
        _, vjp = jax.vjp(recomputed, p, x)
        dp, dx = vjp((dy, daux))
        return jax.tree_util.tree_leaves(dp), dx

    def forward(leaves, x, consts):
        leaves = with_vectors(leaves)

        def turn(carry, i):
            x, whole = carry
            y, aux = apply(params(leaves, i, whole), x, i, consts)
            coming = gather(shard(leaves, i + 1))
            y, coming = jax.lax.optimization_barrier((y, coming))
            return (y, coming), (y, aux)

        (y, whole), (inputs, auxs) = jax.lax.scan(
            turn, (x, gather(shard(leaves, 0))), jnp.arange(last))
        y, aux = apply(params(leaves, last, whole), y, jnp.int32(last),
                       consts)
        # inputs[i] is what layer i + 1 read
        return (y, jnp.append(auxs, aux)), (x, inputs)

    @jax.custom_vjp
    def run(leaves, x, consts):
        return forward(leaves, x, consts)[0]

    def run_fwd(leaves, x, consts):
        out, saved = forward(leaves, x, consts)
        return out, (leaves, saved, consts)

    def run_bwd(residuals, cts):
        # JAX traces this after the engine has left ``gather_context``:
        # the layers it recomputes gather under the plan of the forward
        with _entered(plan):
            return backward(residuals, cts)

    def backward(residuals, cts):
        leaves, (x, inputs), consts = residuals
        dy, dauxs = cts
        grads = [jnp.zeros_like(leaf) for leaf in leaves]
        leaves = with_vectors(leaves)

        def turn(carry, step):
            dy, whole, grads = carry
            i, x, daux = step
            dp, dx = back(params(leaves, i, whole), x, i, consts, dy, daux)
            coming = gather(shard(leaves, i - 1))
            dx, coming = jax.lax.optimization_barrier((dx, coming))
            grads = [jax.lax.dynamic_update_index_in_dim(
                g, d.astype(g.dtype), i, 0) for g, d in zip(grads, dp)]
            return (dx, coming, grads), None

        (dy, whole, grads), _ = jax.lax.scan(
            turn, (dy, gather(shard(leaves, last)), grads),
            (jnp.arange(1, n_layers), inputs, dauxs[1:]), reverse=True)
        dp, dx = back(params(leaves, 0, whole), x, jnp.int32(0), consts, dy,
                      dauxs[0])
        grads = [g.at[0].set(d.astype(g.dtype)) for g, d in zip(grads, dp)]
        return grads, dx, None

    run.defvjp(run_fwd, run_bwd)
    return run(leaves, x, consts)


def gathered_on_use(module_cls, prefix: Sequence[str], dtype, **kwargs):
    """``module_cls`` with its ``params`` gathered where it reads them
    (:func:`gather_tree`'s arguments), or ``module_cls`` itself when no
    step program with something to gather is being traced."""
    if current_plan() is None:
        return module_cls
    import flax.linen as nn

    def gather_params(collections):  # {"params": the module's subtree}
        return {name: gather_tree(tree, prefix, dtype, **kwargs)
                for name, tree in collections.items()}

    return nn.map_variables(module_cls, "params", trans_in_fn=gather_params)
