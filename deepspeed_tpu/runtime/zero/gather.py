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
        }


_PLAN: contextvars.ContextVar = contextvars.ContextVar(
    "zero3_gather_plan", default=None)


def current_plan() -> Optional[GatherPlan]:
    """The plan of the step program being traced, or None."""
    return _PLAN.get()


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
    token = _PLAN.set(plan)
    try:
        yield plan
    finally:
        _PLAN.reset(token)
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
    """
    plan = current_plan()
    if plan is None:
        return tree
    rules, mesh = plan.rules, plan.rules.topo.mesh
    n = rules.topo.size(FSDP)
    prefix = "/".join(prefix)

    def leaf(path, x):
        path = "/".join(p for p in (prefix, path_str(path)) if p)
        shape = tuple(x.shape) if stacked is None \
            else (stacked,) + tuple(x.shape)
        spec = tuple(rules.param_spec(path, shape))
        spec = (spec + (None,) * (len(shape) - len(spec)))[len(shape) - x.ndim:]
        use = _without_fsdp(spec)
        if use == spec:
            plan.persistent.add(path)
            return x
        floating = jnp.issubdtype(x.dtype, jnp.floating)
        to = dtype if (floating and x.ndim >= 2
                       and not path.endswith(tuple(keep_dtype))) else x.dtype
        size = int(np.prod(x.shape)) * (stacked or 1)
        wire = size * jnp.dtype(to).itemsize * (n - 1) // n
        plan.gathered[path, site] = (wire * uses, wire if floating else 0)
        use = NamedSharding(mesh, PartitionSpec(*use))
        if not floating:  # int8-at-rest weights: no cotangent to scatter
            with jax.named_scope(SCOPE_ZERO3_GATHER):
                return jax.lax.with_sharding_constraint(x, use)
        return _gather(x, use, NamedSharding(mesh, PartitionSpec(*spec)),
                       jnp.dtype(to), jnp.dtype(x.dtype))

    return jax.tree_util.tree_map_with_path(leaf, tree)


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
