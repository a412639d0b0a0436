"""Mesh construction + state layout as a reusable module.

Historically ``DeepSpeedEngine.__init__`` inlined three layout decisions:
resolve a :class:`MeshTopology` from config, move the data-parallel axis to
``fsdp`` when a ZeRO stage shards over it, and build the
:class:`ZeroShardingRules` that turn the stage into per-leaf PartitionSpecs.
Elastic topology resume (``runtime/reshard.py``) needs the SAME decisions
outside any engine — a checkpoint saved at N devices must be re-laid-out
for N' before an engine on the new mesh exists — so they live here and the
engine calls in.

Also home to the manifest-facing serialization of a layout: a topology
metadata block (world size, zero stage, axis sizes) and JSON-safe
PartitionSpec encoding, written at save time and compared at load time to
*detect* a topology change instead of discovering it as a shape error deep
inside a compiled step.
"""

from typing import Any, Callable, Dict, List, Optional, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.parallel.mesh import (
    AXIS_ORDER,
    MeshTopology,
    topology_from_config,
)
from deepspeed_tpu.runtime.zero.sharding import ZeroShardingRules
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.tree import path_str as _path_str


# ---------------------------------------------------------------------------
# mesh construction (extracted from DeepSpeedEngine.__init__)
# ---------------------------------------------------------------------------

def build_topology(config, topology: Optional[MeshTopology] = None
                   ) -> MeshTopology:
    """The engine's initial topology: an explicit one wins, otherwise the
    mesh config resolves against the visible devices."""
    if topology is not None:
        return topology
    return topology_from_config(config.tpu.mesh_config)


def apply_zero_fsdp_move(topology: MeshTopology, zero_stage: int,
                         compressed: bool = False) -> MeshTopology:
    """ZeRO shards over the fsdp axis: when the user asked for a ZeRO stage
    but left all data parallelism on ``dp``, move it to ``fsdp`` (the mesh
    expression of "partition across the DP world", reference
    stage_1_and_2.py partitioning over the DP group). Compressed modes keep
    the axis on ``dp``: the exchange needs the full momentum/gradient
    materialized per worker (reference 1-bit optimizers are likewise
    limited to ZeRO stages 0-1, onebit/adam.py)."""
    if (zero_stage >= 1 and topology.size("fsdp") == 1
            and topology.size("dp") > 1 and not compressed):
        sizes = dict(topology.axis_sizes)
        sizes["fsdp"] = sizes.pop("dp")
        sizes["dp"] = 1
        topology = MeshTopology(
            **sizes, devices=list(topology.mesh.devices.flat)
        )
        log_dist(
            f"zero stage {zero_stage}: data-parallel axis "
            f"moved to fsdp ({topology})", ranks=[0],
        )
    return topology


def build_sharding_rules(topology: MeshTopology, zero_stage: int,
                         param_persistence_threshold: int = 0,
                         tp_rules: Optional[Callable] = None,
                         prefetch_bucket_size: int = 0,
                         max_live_parameters: int = 0
                         ) -> ZeroShardingRules:
    """The per-leaf layout policy for this (topology, stage) pair."""
    return ZeroShardingRules(
        topology,
        stage=zero_stage,
        param_persistence_threshold=(
            param_persistence_threshold if zero_stage >= 3 else 0),
        tp_rules=tp_rules,
        prefetch_bucket_size=prefetch_bucket_size,
        max_live_parameters=max_live_parameters,
    )


# ---------------------------------------------------------------------------
# manifest-facing layout serialization
# ---------------------------------------------------------------------------

def spec_to_json(spec: PartitionSpec) -> List[Any]:
    """JSON-safe PartitionSpec: each entry is None, an axis name, or a list
    of axis names (multi-axis sharding of one dim)."""
    out: List[Any] = []
    for entry in spec:
        if entry is None or isinstance(entry, str):
            out.append(entry)
        else:  # tuple of axis names
            out.append(list(entry))
    return out


def spec_from_json(entries: Optional[List[Any]]) -> PartitionSpec:
    if not entries:
        return PartitionSpec()
    parts = []
    for entry in entries:
        if entry is None or isinstance(entry, str):
            parts.append(entry)
        else:
            parts.append(tuple(entry))
    return PartitionSpec(*parts)


def describe_shardings(shardings_tree: Any, shapes_tree: Any = None
                       ) -> Dict[str, Dict[str, Any]]:
    """Flatten a pytree of NamedShardings into ``{dotted-path: {"spec":
    [...], "shape": [...]}}`` — the per-leaf layout record the manifest
    carries so a resharding load can verify the gathered (logical) shapes
    against what was saved."""
    out: Dict[str, Dict[str, Any]] = {}
    flat = jax.tree_util.tree_flatten_with_path(
        shardings_tree, is_leaf=lambda x: isinstance(x, NamedSharding))[0]
    shapes: Dict[str, Any] = {}
    if shapes_tree is not None:
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes_tree)[0]:
            shapes[_path_str(path)] = list(getattr(leaf, "shape", ()))
    for path, sharding in flat:
        key = _path_str(path)
        entry: Dict[str, Any] = {"spec": spec_to_json(sharding.spec)}
        if key in shapes:
            entry["shape"] = shapes[key]
        out[key] = entry
    return out


def topology_metadata(topology: MeshTopology, zero_stage: int,
                      partition_specs: Optional[Dict[str, Dict[str, Any]]]
                      = None) -> Dict[str, Any]:
    """The manifest ``topology`` block: enough to detect a mismatched load
    (world size + axis sizes), re-derive the saved layout (zero stage +
    per-leaf specs), and re-stride data (world size)."""
    meta: Dict[str, Any] = {
        "world_size": int(topology.num_devices),
        "zero_stage": int(zero_stage),
        "axis_sizes": {a: int(topology.axis_sizes[a]) for a in AXIS_ORDER},
    }
    if partition_specs:
        meta["partition_specs"] = partition_specs
    return meta


def topology_matches(saved: Dict[str, Any], topology: MeshTopology,
                     zero_stage: Optional[int] = None) -> List[str]:
    """Compare a saved topology block against a live topology; returns a
    list of human-readable mismatch descriptions (empty = same layout)."""
    mismatches: List[str] = []
    saved_world = saved.get("world_size")
    if saved_world is not None and int(saved_world) != topology.num_devices:
        mismatches.append(
            f"world_size {saved_world} -> {topology.num_devices}")
    saved_axes = saved.get("axis_sizes") or {}
    for axis in AXIS_ORDER:
        if axis not in saved_axes:
            continue
        cur = topology.axis_sizes[axis]
        if int(saved_axes[axis]) != cur:
            mismatches.append(f"{axis} {saved_axes[axis]} -> {cur}")
    if (zero_stage is not None and saved.get("zero_stage") is not None
            and int(saved["zero_stage"]) != int(zero_stage)):
        mismatches.append(
            f"zero_stage {saved['zero_stage']} -> {zero_stage}")
    return mismatches
