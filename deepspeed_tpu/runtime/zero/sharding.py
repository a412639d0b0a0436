"""ZeRO as sharding rules.

This module IS the TPU-native ZeRO (reference ``deepspeed/runtime/zero/``,
~8k LoC of hooks/buckets/streams): each stage is a set of PartitionSpecs
over the ``fsdp`` mesh axis, applied to the param / grad-accumulation /
optimizer-state pytrees of the compiled train step. XLA then emits exactly
the collectives the reference implements by hand:

=========  =======================================  =============================
stage      reference mechanism                       sharding expression
=========  =======================================  =============================
0 (DDP)    bucketed grad allreduce                   grads replicated -> psum
           (engine.py:2180-2298)
1          optimizer-state partitions + allgather    opt state sharded over fsdp
           of updated fp16 (stage_1_and_2.py:1744)   (XLA: reduce-scatter grads
                                                     into the update, all-gather
                                                     new params out)
2          + gradient partitions via bucketed        + grad-accum buffer sharded
           reduce-scatter (stage_1_and_2.py:938)     over fsdp
3          + param partitions, allgather-on-use,     + params sharded over fsdp,
           prefetch coordinator                      and a constraint where a
           (partition_parameters.py:806,             layer reads them (gather.py:
           partitioned_param_coordinator.py:237)     all-gather in the loop body,
                                                     reduce-scatter of the
                                                     cotangent)
=========  =======================================  =============================

Stage 3 needs the second half. The annotation alone does not make XLA
gather a weight: the shard is on a leaf's largest dimension, for an MLP
kernel the feature dimension, and the partitioner of a plain GSPMD step
read that as tensor parallelism and resharded the batch-sharded
activations instead (five ``all-to-all`` a layer, 178 ms of a 782 ms step
exposed on four v5e chips: PERF.md, PR 24). ``gather.py`` says at the use
site what the stored sharding means; XLA then schedules the gathers and
reduce-scatters under the layer's matmuls (27.7 ms exposed of 497 ms,
PERF.md, PR 29). What stayed exposed was the gather at the head of a loop
turn, which nothing can be scheduled across; since PR 60, where
``stage3_prefetch_bucket_size`` and ``stage3_max_live_parameters`` allow
(gather.py ``turn_length``), turn i of the layer loop issues the gather of
layer i+1's first weight and hands it on (gather.py ``layers_ahead``).

``param_persistence_threshold`` (stage3, zero/config.py) maps to ``min_size``:
small params stay replicated.
"""

from typing import Any, Callable, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.parallel.mesh import MeshTopology, shard_largest_dim_spec
from deepspeed_tpu.utils.tree import path_str as _path_str


def _spec_for_shape(shape, topo: MeshTopology, min_size: int = 0,
                    tp_spec: Optional[PartitionSpec] = None) -> PartitionSpec:
    """FSDP sharding for one array shape, composed with an optional TP spec
    (TP dims win; fsdp takes the largest remaining divisible dim)."""
    fsdp_size = topo.size("fsdp")
    if tp_spec is not None and any(a is not None for a in tp_spec):
        if fsdp_size <= 1:
            return tp_spec
        # shard largest dim not already taken by tp
        taken = {i for i, a in enumerate(tp_spec) if a is not None}
        candidates = [
            i for i, d in enumerate(shape)
            if i not in taken and d % fsdp_size == 0
        ]
        if not candidates or int(np.prod(shape)) < max(min_size, fsdp_size):
            return tp_spec
        best = max(candidates, key=lambda i: shape[i])
        spec = list(tp_spec) + [None] * (len(shape) - len(tp_spec))
        spec[best] = "fsdp"
        return PartitionSpec(*spec)
    return shard_largest_dim_spec(shape, "fsdp", fsdp_size, min_size=min_size)


class ZeroShardingRules:
    """Builds NamedSharding trees for params / grads / optimizer state given a
    ZeRO stage and mesh, optionally composed with tensor-parallel rules
    (a ``path, shape -> PartitionSpec`` callable, see parallel/tensor_parallel)."""

    def __init__(self, topo: MeshTopology, stage: int,
                 param_persistence_threshold: int = 0,
                 tp_rules: Optional[Callable] = None,
                 prefetch_bucket_size: int = 0,
                 max_live_parameters: int = 0):
        self.topo = topo
        self.stage = stage
        self.persistence_threshold = param_persistence_threshold
        self.tp_rules = tp_rules
        # stage 3's ``stage3_prefetch_bucket_size`` and
        # ``stage3_max_live_parameters``, in elements: whether a turn of a
        # layer loop gathers for the next layer too (gather.py
        # ``turn_length``). Rules built without a ZeRO configuration
        # gather nothing ahead.
        self.prefetch_bucket_size = prefetch_bucket_size
        self.max_live_parameters = max_live_parameters

    # -- per-leaf specs ----------------------------------------------------
    def _tp_spec(self, path, shape) -> Optional[PartitionSpec]:
        if self.tp_rules is None:
            return None
        spec = self.tp_rules(path, shape)
        if spec is None:
            spec = self._quantized_leaf_spec(path, shape)
        if spec is None:
            return None
        # validate: strip axes whose dim is not divisible by the mesh axis size
        cleaned = []
        for i, axis in enumerate(spec):
            if axis is None:
                cleaned.append(None)
                continue
            size = self.topo.size(axis) if isinstance(axis, str) else int(
                np.prod([self.topo.size(a) for a in axis])
            )
            # size-1 axes collapse to replicated; indivisible dims cannot shard
            cleaned.append(axis if size > 1 and shape[i] % size == 0 else None)
        if all(a is None for a in cleaned):
            return None
        return PartitionSpec(*cleaned)

    def _quantized_leaf_spec(self, path, shape) -> Optional[PartitionSpec]:
        """TP specs for int8 weight-only ``{q, scale}`` leaves, derived from
        the dense kernel rule they replace (reference composes int8 with MP
        the same way: GroupQuantizer quantizes the already-sliced weight,
        replace_module.py:139 after slicing at :18). ``q`` has the kernel's
        shape, so it inherits the kernel's spec verbatim; ``scale`` is
        per-output-column (the kernel shape minus the contraction dim), so
        its spec is the kernel spec with dim -2 dropped — column-parallel
        kernels shard their scales on the same output axis, row-parallel
        kernels keep scales replicated. Both are exact: dequant is an
        elementwise per-column product, so sharded q × broadcast scale
        equals the sharded dense kernel."""
        if path.endswith("/q"):
            return self.tp_rules(path[:-len("/q")], shape)
        if path.endswith("/scale"):
            kshape = tuple(shape[:-1]) + (1,) + (shape[-1],)
            kspec = self.tp_rules(path[:-len("/scale")], kshape)
            if kspec is None:
                return None
            ks = list(kspec) + [None] * (len(kshape) - len(kspec))
            del ks[-2]  # the contraction dim the scale does not carry
            return PartitionSpec(*ks)
        return None

    def param_spec(self, path, shape) -> PartitionSpec:
        tp = self._tp_spec(path, shape)
        if self.stage >= 3:
            return _spec_for_shape(
                shape, self.topo, min_size=self.persistence_threshold, tp_spec=tp
            )
        return tp if tp is not None else PartitionSpec()

    def grad_accum_spec(self, path, shape) -> PartitionSpec:
        tp = self._tp_spec(path, shape)
        if self.stage >= 2:
            return _spec_for_shape(shape, self.topo, tp_spec=tp)
        return tp if tp is not None else PartitionSpec()

    def opt_state_spec(self, param_path: Optional[str], shape) -> PartitionSpec:
        """Spec for an optimizer-state leaf. ``param_path`` is the path of the
        param this leaf mirrors (mu/nu), or None for non-param-shaped state.
        Stage >= 1 shards param-shaped state over fsdp (the reference's
        optimizer-state partitioning, stage_1_and_2.py:634) composed with the
        param's TP spec; stage 0 mirrors the param spec exactly."""
        if not shape:
            return PartitionSpec()
        tp = self._tp_spec(param_path, shape) if param_path is not None else None
        if self.stage >= 1:
            return _spec_for_shape(shape, self.topo, tp_spec=tp)
        return tp if tp is not None else PartitionSpec()

    # -- pytree builders ---------------------------------------------------
    def param_sharding_tree(self, params_shapes) -> Any:
        """``params_shapes``: pytree of ShapeDtypeStruct (from eval_shape)."""
        mesh = self.topo.mesh

        def leaf(path, leaf_shape):
            spec = self.param_spec(path, leaf_shape.shape)
            return NamedSharding(mesh, spec)

        return _tree_map_with_path(leaf, params_shapes)

    def grad_sharding_tree(self, params_shapes) -> Any:
        mesh = self.topo.mesh

        def leaf(path, leaf_shape):
            spec = self.grad_accum_spec(path, leaf_shape.shape)
            return NamedSharding(mesh, spec)

        return _tree_map_with_path(leaf, params_shapes)

    def opt_sharding_tree(self, opt_state_shapes, params_shapes=None) -> Any:
        """Optimizer-state leaves that mirror a parameter (optax mu/nu subtrees
        carry the param pytree, so their paths END with the param's path) get
        that param's rule; everything else (counts, scalars) follows the plain
        shape rule."""
        mesh = self.topo.mesh
        param_paths = []
        if params_shapes is not None:
            flat = jax.tree_util.tree_flatten_with_path(params_shapes)[0]
            param_paths = [
                (_path_str(path), leaf.shape) for path, leaf in flat
            ]

        def leaf(path_s, leaf_shape):
            # path_s is already stringified by _tree_map_with_path
            matched = None
            for ppath, pshape in param_paths:
                if path_s.endswith(ppath) and tuple(pshape) == tuple(leaf_shape.shape):
                    matched = ppath
                    break
            spec = self.opt_state_spec(matched, leaf_shape.shape)
            return NamedSharding(mesh, spec)

        return _tree_map_with_path(leaf, opt_state_shapes)


def _tree_map_with_path(fn, tree):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(_path_str(path), leaf), tree
    )
