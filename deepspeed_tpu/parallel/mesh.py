"""Device mesh / topology management.

TPU-native replacement for the reference's process-group plumbing
(``deepspeed/utils/groups.py``, ``deepspeed/runtime/pipe/topology.py:9-453``):
instead of materialising torch.distributed groups per parallel dimension, we
build ONE ``jax.sharding.Mesh`` with named axes and express every parallel
strategy as a PartitionSpec over those axes.

Axis semantics (order = mesh layout; ``tp`` innermost so tensor-parallel
collectives ride the shortest ICI hops):

* ``pp``   — pipeline stages (reference runtime/pipe/)
* ``dp``   — pure data parallel (replicated params; reference engine.py DDP path)
* ``fsdp`` — sharded data parallel; ZeRO-1/2/3 shard optimizer/grads/params here
             (reference runtime/zero/)
* ``ep``   — expert parallel for MoE all-to-all (reference deepspeed/moe/)
* ``sp``   — sequence/context parallel (absent in the reference snapshot;
             first-class here, see SURVEY.md §2.2)
* ``tp``   — Megatron-style tensor parallel (reference mpu protocol /
             module_inject tensor slicing)

The global batch is sharded over (dp, fsdp, ep): fsdp is *sharded* data
parallelism and each expert-parallel group sees distinct data, matching the
reference's expert-data-parallel group construction (utils/groups.py:109-265).
"""

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_ORDER: Tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")
BATCH_AXES: Tuple[str, ...] = ("dp", "fsdp", "ep")


class MeshTopology:
    """Named-axis device mesh with ProcessTopology-parity queries
    (reference pipe/topology.py: get_coord, axis sizes, rank mapping)."""

    def __init__(
        self,
        dp: int = -1,
        fsdp: int = 1,
        tp: int = 1,
        pp: int = 1,
        ep: int = 1,
        sp: int = 1,
        devices: Optional[Sequence] = None,
    ):
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        n = len(devices)

        sizes: Dict[str, int] = {
            "pp": pp, "dp": dp, "fsdp": fsdp, "ep": ep, "sp": sp, "tp": tp
        }
        bad = {a: s for a, s in sizes.items() if s != -1 and s < 1}
        if bad:
            raise ValueError(f"Mesh axis sizes must be >= 1 (or -1 to infer): {bad}")
        unknown = [a for a, s in sizes.items() if s == -1]
        if len(unknown) > 1:
            raise ValueError(f"At most one mesh axis may be -1, got {unknown}")
        fixed = int(np.prod([s for s in sizes.values() if s != -1]))
        if unknown:
            if n % fixed != 0:
                raise ValueError(
                    f"{n} devices not divisible by fixed axes product {fixed}"
                )
            sizes[unknown[0]] = n // fixed
        total = int(np.prod(list(sizes.values())))
        if total != n:
            raise ValueError(
                f"Mesh axes {sizes} require {total} devices but {n} are available"
            )

        self.axis_sizes = sizes
        shape = tuple(sizes[a] for a in AXIS_ORDER)
        # slice structure (multi-slice TPU pods): how many DCN-connected
        # slices the devices span and how the slice count factors into the
        # outer mesh axes. On a single slice / CPU backend every factor is
        # 1 — consumers (the hierarchical gradient exchange) read
        # dcn_size("dp") and fall back to the flat exchange at 1.
        is_tpu = bool(devices) and getattr(
            devices[0], "platform", "cpu") == "tpu"
        self.num_slices = (len({getattr(d, "slice_index", None) or 0
                                for d in devices}) if is_tpu else 1)
        self.dcn_shape = (self._derive_dcn_shape(shape, self.num_slices)
                          if self.num_slices > 1
                          else tuple(1 for _ in shape))
        device_array = self._arrange(devices, shape)
        self.mesh = Mesh(device_array, AXIS_ORDER)

    @staticmethod
    def _derive_dcn_shape(shape: Tuple[int, ...], n_slices: int
                          ) -> Tuple[int, ...]:
        """Factor the slice count into the OUTERMOST axes (AXIS_ORDER:
        pp, dp, fsdp, ...), so collectives of the inner axes (tp/sp/ep)
        never cross the data-center network: each element of the result
        divides the global axis size; their product is n_slices."""
        import math

        # only pp/dp/fsdp may absorb the slice dimension; a DCN hop inside
        # an ep all-to-all, sp ring, or tp matmul psum defeats the layout
        n_dcn_eligible = 3  # AXIS_ORDER prefix (pp, dp, fsdp)
        remaining = n_slices
        dcn = []
        for i, size in enumerate(shape):
            g = math.gcd(size, remaining) if i < n_dcn_eligible else 1
            dcn.append(g)
            remaining //= g
        if remaining != 1:
            raise ValueError(
                f"cannot distribute {n_slices} slices over mesh axes "
                f"{dict(zip(AXIS_ORDER, shape))}: the outer axes "
                f"(pp/dp/fsdp) must jointly absorb a factor of {n_slices} "
                f"so no tp/sp/ep collective crosses DCN"
            )
        return tuple(dcn)

    @staticmethod
    def _arrange(devices: List, shape: Tuple[int, ...]) -> np.ndarray:
        """Physical device layout. On one real TPU slice use mesh_utils so
        the innermost axes land on adjacent ICI neighbours; on a MULTI-SLICE
        job (device.slice_index varies) build a hybrid ICI x DCN mesh where
        the slice dimension is absorbed by the outermost parallel axes —
        the 'collectives ride ICI, not DCN' layout. Plain reshape off-TPU."""
        is_tpu = bool(devices) and getattr(
            devices[0], "platform", "cpu") == "tpu"
        if not is_tpu:
            return np.array(devices).reshape(shape)
        from jax.experimental import mesh_utils

        slice_ids = {getattr(d, "slice_index", None) or 0 for d in devices}
        if len(slice_ids) > 1:
            # a plain reshape would route tp/sp collectives over DCN
            dcn_shape = MeshTopology._derive_dcn_shape(shape, len(slice_ids))
            per_slice = tuple(s // d for s, d in zip(shape, dcn_shape))
            return mesh_utils.create_hybrid_device_mesh(
                per_slice, dcn_shape, devices=devices)
        # no reshape fallback on the chip: a mesh_utils failure is a layout
        # the ICI topology cannot host, and it must be seen
        return mesh_utils.create_device_mesh(shape, devices=devices)

    # -- size queries (parity: groups.get_data_parallel_world_size etc.) ---
    def size(self, axis: str) -> int:
        return self.axis_sizes[axis]

    def dcn_size(self, axis: str) -> int:
        """How many DCN-connected slice groups the axis spans (1 on a
        single slice): the factor of ``num_slices`` that
        :meth:`_derive_dcn_shape` assigned to this axis. An axis with
        ``dcn_size > 1`` has its slice dimension as the SLOW (outer)
        dimension — rank = slice_idx * per_slice + ici_idx (the
        ``create_hybrid_device_mesh`` layout ``comm.bucketed.
        hierarchy_groups`` assumes)."""
        return self.dcn_shape[AXIS_ORDER.index(axis)]

    @property
    def num_devices(self) -> int:
        return int(np.prod(list(self.axis_sizes.values())))

    @property
    def data_parallel_size(self) -> int:
        """Number of distinct data shards = dp * fsdp * ep."""
        return int(np.prod([self.axis_sizes[a] for a in BATCH_AXES]))

    @property
    def model_parallel_size(self) -> int:
        return self.axis_sizes["tp"]

    @property
    def pipe_parallel_size(self) -> int:
        return self.axis_sizes["pp"]

    @property
    def expert_parallel_size(self) -> int:
        return self.axis_sizes["ep"]

    @property
    def sequence_parallel_size(self) -> int:
        return self.axis_sizes["sp"]

    def active_axes(self) -> List[str]:
        return [a for a in AXIS_ORDER if self.axis_sizes[a] > 1]

    # -- coordinate queries (parity: ProcessTopology.get_coord) ------------
    def coord_of(self, flat_rank: int) -> Dict[str, int]:
        """Coordinates of a LOGICAL mesh position (row-major index into the
        mesh array). On real TPU slices ``_arrange`` permutes devices for ICI
        locality, so a logical position is generally NOT the device's index in
        ``jax.devices()`` — use :meth:`coord_of_device` to query by device."""
        shape = tuple(self.axis_sizes[a] for a in AXIS_ORDER)
        coords = np.unravel_index(flat_rank, shape)
        return dict(zip(AXIS_ORDER, (int(c) for c in coords)))

    def coord_of_device(self, device) -> Dict[str, int]:
        """Mesh coordinates of a physical jax device."""
        for idx, dev in np.ndenumerate(self.mesh.devices):
            if dev == device:
                return dict(zip(AXIS_ORDER, (int(c) for c in idx)))
        raise ValueError(f"device {device} is not in this mesh")

    def filter_ranks(self, **axis_values) -> List[int]:
        """All LOGICAL mesh positions (row-major, see coord_of) whose
        coordinates match the given axis values
        (parity: ProcessTopology.filter_match, pipe/topology.py)."""
        out = []
        for r in range(self.num_devices):
            c = self.coord_of(r)
            if all(c[a] == v for a, v in axis_values.items()):
                out.append(r)
        return out

    # -- sharding helpers --------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def batch_spec(self) -> PartitionSpec:
        axes = [a for a in BATCH_AXES if self.axis_sizes[a] > 1]
        return PartitionSpec(tuple(axes) if axes else None)

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec())

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def __repr__(self):
        active = {a: s for a, s in self.axis_sizes.items() if s > 1}
        return f"MeshTopology({active or 'single-device'}, devices={self.num_devices})"


# ---------------------------------------------------------------------------
# Default-mesh registry (parity with groups.initialize global state,
# reference utils/groups.py:45)
# ---------------------------------------------------------------------------
_DEFAULT_TOPOLOGY: Optional[MeshTopology] = None


def set_default_topology(topo: MeshTopology) -> None:
    global _DEFAULT_TOPOLOGY
    _DEFAULT_TOPOLOGY = topo


def get_default_topology() -> MeshTopology:
    global _DEFAULT_TOPOLOGY
    if _DEFAULT_TOPOLOGY is None:
        _DEFAULT_TOPOLOGY = MeshTopology()
    return _DEFAULT_TOPOLOGY


def reset_default_topology() -> None:
    global _DEFAULT_TOPOLOGY
    _DEFAULT_TOPOLOGY = None


def topology_from_config(mesh_config, devices=None) -> MeshTopology:
    """Build a MeshTopology from a config MeshConfig/dict."""
    if hasattr(mesh_config, "to_dict"):
        mesh_config = mesh_config.to_dict()
    mesh_config = dict(mesh_config or {})
    return MeshTopology(
        dp=mesh_config.get("dp", -1),
        fsdp=mesh_config.get("fsdp", 1),
        tp=mesh_config.get("tp", 1),
        pp=mesh_config.get("pp", 1),
        ep=mesh_config.get("ep", 1),
        sp=mesh_config.get("sp", 1),
        devices=devices,
    )


# ---------------------------------------------------------------------------
# Parameter sharding rules (FSDP-style "shard the largest divisible dim")
# ---------------------------------------------------------------------------
def shard_largest_dim_spec(
    shape: Tuple[int, ...], axis_name: str, axis_size: int, min_size: int = 0
) -> PartitionSpec:
    """PartitionSpec that shards the largest dim divisible by ``axis_size``.

    This is the TPU-native analogue of ZeRO-3 flat-buffer partitioning
    (reference zero/partition_parameters.py:882): instead of flattening and
    slicing bytes, we annotate a whole dimension (and skip params below
    the persistence threshold, mirroring stage3
    param_persistence_threshold). The annotation says where a shard lives,
    not that the weight is gathered at its use: for stage-3 parameters
    ``runtime/zero/gather.py`` constrains the use site.
    """
    if axis_size <= 1 or not shape:
        return PartitionSpec()
    numel = int(np.prod(shape))
    if numel < max(min_size, axis_size):
        return PartitionSpec()
    candidates = [i for i, d in enumerate(shape) if d % axis_size == 0]
    if not candidates:
        return PartitionSpec()
    best = max(candidates, key=lambda i: shape[i])
    spec = [None] * len(shape)
    spec[best] = axis_name
    return PartitionSpec(*spec)
