"""``deepspeed_tpu.comm`` — the communication facade.

Parity with reference ``deepspeed/comm/comm.py:223-760`` (torch.distributed-
compatible verb surface + init_distributed + env discovery), re-expressed for
XLA SPMD. Two layers:

1. **In-program collectives** (this module's functional API) — used inside
   ``shard_map``/``jit`` with a named mesh axis. Each verb lowers to the
   corresponding ``jax.lax`` collective and records itself with the
   CommsLogger at trace time:

   =====================  ==============================
   reference verb          XLA lowering
   =====================  ==============================
   all_reduce              lax.psum / pmax / pmin
   all_gather(_base)       lax.all_gather(tiled=True)
   reduce_scatter(_base)   lax.psum_scatter
   all_to_all_single       lax.all_to_all
   send/recv (pipeline)    lax.ppermute
   broadcast               psum of masked value
   =====================  ==============================

2. **Host-level process management** — ``init_distributed`` wraps
   ``jax.distributed.initialize`` (multi-host rendezvous ≈ the reference's
   torch.distributed.init_process_group at comm/torch.py:32), and
   rank/world-size queries map to ``jax.process_index``/device counts.

The 1-bit compressed-allreduce path (reference runtime/comm/nccl.py:51) is
provided by :mod:`deepspeed_tpu.comm.compressed`.
"""

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.comm.logging import comms_logger
from deepspeed_tpu.utils.logging import log_dist, logger


class ReduceOp:
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


# ---------------------------------------------------------------------------
# In-program collectives (use inside shard_map / jit with named axes)
# ---------------------------------------------------------------------------
def _axis_world(axis: str):
    """Static size of a bound mesh axis at trace time (``psum(1, axis)`` is
    constant-folded to the axis size), or None when called with the axis
    unbound — the comms logger then falls back to payload-only accounting."""
    try:
        return int(lax.psum(1, axis))
    except Exception:
        return None


def all_reduce(x, axis: str, op: str = ReduceOp.SUM):
    """reference comm/comm.py:503 all_reduce."""
    comms_logger.append("all_reduce", x, axis, world=_axis_world(axis))
    if op == ReduceOp.SUM:
        return lax.psum(x, axis)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axis)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis)
    if op == ReduceOp.PROD:
        # Exact, dtype-preserving product: gather then reduce (no log/exp trick,
        # which is inexact and NaNs on negatives).
        gathered = lax.all_gather(x, axis)
        return jnp.prod(gathered, axis=0)
    raise ValueError(f"unsupported reduce op {op}")


def all_gather(x, axis: str, gather_dim: int = 0, tiled: bool = True):
    """reference comm/comm.py all_gather/_base; tiled=True concatenates along
    ``gather_dim`` (the _base flat-buffer form)."""
    comms_logger.append("all_gather", x, axis, world=_axis_world(axis))
    return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


def reduce_scatter(x, axis: str, scatter_dim: int = 0):
    """reference comm/comm.py reduce_scatter(_base) → psum_scatter."""
    comms_logger.append("reduce_scatter", x, axis, world=_axis_world(axis))
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dim, tiled=True)


def all_to_all_single(x, axis: str, split_dim: int = 0, concat_dim: int = 0):
    """reference comm/comm.py:392 all_to_all_single (MoE dispatch path)."""
    comms_logger.append("all_to_all", x, axis, world=_axis_world(axis))
    return lax.all_to_all(x, axis, split_axis=split_dim, concat_axis=concat_dim,
                          tiled=True)


def ppermute(x, axis: str, perm):
    """Point-to-point ring/pipeline transfer (reference pipe/p2p.py send/recv
    :48-161 collapses to one collective-permute on TPU)."""
    comms_logger.append("ppermute", x, axis, world=_axis_world(axis))
    return lax.ppermute(x, axis, perm)


def send_recv_next(x, axis: str, axis_size: int):
    """Send to rank+1 on ``axis`` (pipeline forward activations)."""
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    return ppermute(x, axis, perm)


def send_recv_prev(x, axis: str, axis_size: int):
    """Send to rank-1 on ``axis`` (pipeline backward grads)."""
    perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]
    return ppermute(x, axis, perm)


def broadcast(x, axis: str, root: int = 0):
    """reference comm/comm.py:223 broadcast: every rank gets root's value."""
    comms_logger.append("broadcast", x, axis, world=_axis_world(axis))
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)


def axis_index(axis: str):
    return lax.axis_index(axis)


# ---------------------------------------------------------------------------
# Host-level process management
# ---------------------------------------------------------------------------
_initialized = False


def init_distributed(
    dist_backend: str = "xla",
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    auto_mpi_discovery: bool = True,
    **kwargs,
):
    """Multi-host rendezvous (reference comm/comm.py:577 init_distributed).

    Single-host (or already-initialised) is a no-op. Env discovery mirrors the
    reference's MPI/launcher env probing (comm/comm.py:640-760): honours
    COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID, the OMPI_* rank vars,
    and the JAX-native auto-detection on TPU pods.
    """
    global _initialized
    if _initialized:
        return
    # DS_TPU_* is the deepspeed_tpu launcher's protocol (launcher/runner.py)
    coordinator_address = (coordinator_address
                           or os.environ.get("DS_TPU_COORDINATOR")
                           or os.environ.get("COORDINATOR_ADDRESS"))
    num_processes = (num_processes or _env_int("DS_TPU_NUM_PROCS")
                     or _env_int("NUM_PROCESSES"))
    if process_id is None:
        process_id = _env_int("DS_TPU_PROC_ID")
    process_id = process_id if process_id is not None else _env_int("PROCESS_ID")
    if auto_mpi_discovery and process_id is None:
        # scheduler-provided rank identity: OpenMPI, then Slurm (reference
        # probes MPI/AzureML/SageMaker env the same way, comm/comm.py:640)
        ompi_rank = _env_int("OMPI_COMM_WORLD_RANK")
        if ompi_rank is not None:
            process_id = ompi_rank
            num_processes = num_processes or _env_int("OMPI_COMM_WORLD_SIZE")
        elif (_env_int("SLURM_PROCID") is not None
              and os.environ.get("SLURM_STEP_ID") is not None
              and (_env_int("SLURM_STEP_NUM_TASKS")
                   or _env_int("SLURM_NTASKS") or 1) > 1):
            # only inside an actual srun step (SLURM_STEP_ID) with more
            # than one task: a bare `python train.py` in an sbatch/salloc
            # shell carries SLURM_PROCID=0 + SLURM_NTASKS but must stay a
            # single-host no-op, not hang in rendezvous (jax's Slurm
            # cluster detection supplies the coordinator when none given)
            process_id = _env_int("SLURM_PROCID")
            num_processes = num_processes or _env_int(
                "SLURM_STEP_NUM_TASKS") or _env_int("SLURM_NTASKS")
    multi_host = coordinator_address is not None or (
        num_processes is not None and num_processes > 1
    )
    if not multi_host:
        # single-host no-op; do NOT latch _initialized so a later call with
        # real coordinator args still performs the rendezvous
        return
    # enable jax's cross-host device-transfer server (PjRt DCN path) so
    # host-level cross-mesh device_puts — the pipeline engine's inter-stage
    # transfers — work across hosts. Must be configured BEFORE the backend
    # initialises. DS_TPU_TRANSFER_ADDR overrides the advertised address
    # (set it empty to disable).
    addr = os.environ.get("DS_TPU_TRANSFER_ADDR")
    if addr is None:
        # the reachable local IP is the one that routes to the coordinator
        # (gethostbyname(gethostname()) is a loopback trap on hosts whose
        # /etc/hosts maps the hostname to 127.0.x.1): a connected UDP
        # socket picks the right interface without sending anything
        import socket

        addr = ""
        host = (coordinator_address or "").strip()
        if host.startswith("["):          # [v6]:port or [v6]
            host = host[1:].split("]", 1)[0]
        elif host.count(":") == 1:        # host:port
            host = host.rpartition(":")[0]
        # else: port-less hostname/IPv4, or bare IPv6 — use as-is
        if host:
            try:
                family = (socket.AF_INET6 if ":" in host
                          else socket.AF_INET)
                probe = socket.socket(family, socket.SOCK_DGRAM)
                try:
                    probe.connect((host, 9))
                    ip = probe.getsockname()[0]
                    # bracket IPv6 or the host:port split is ambiguous
                    addr = f"[{ip}]:0" if ":" in ip else f"{ip}:0"
                finally:
                    probe.close()
            except OSError:
                addr = ""
    if addr:
        jax.config.update("jax_cross_host_transfer_socket_address", addr)
    elif os.environ.get("DS_TPU_TRANSFER_ADDR") is None:
        # not explicitly disabled, yet no address could be derived (e.g.
        # pod auto-detection with no coordinator given, or probe failure)
        logger.warning(
            "could not derive a cross-host transfer address; pipeline "
            "inter-stage transfers across hosts will be unavailable — "
            "set DS_TPU_TRANSFER_ADDR=<this_host_ip>:0 to enable them")

    # the CPU backend compiles cross-process programs only when a CPU
    # collectives implementation is configured (gloo); without it every
    # multi-process jit — including the virtual-mesh tests — aborts with
    # "Multiprocess computations aren't implemented on the CPU backend".
    # Must be set BEFORE backend init; harmless for TPU/GPU platforms.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    # log_dist is unusable before the rendezvous: it queries
    # jax.process_index(), which initialises the XLA backend and makes
    # jax.distributed.initialize fail — use the raw logger here so a
    # hanging rendezvous still records what it attempted
    logger.info(
        f"Initializing distributed JAX: coordinator={coordinator_address} "
        f"procs={num_processes} id={process_id}"
    )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
    _initialized = True
    log_dist(
        f"Distributed JAX ready: {jax.process_count()} processes, "
        f"{jax.device_count()} devices",
        ranks=[-1],
    )


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def is_initialized() -> bool:
    return _initialized


def get_rank() -> int:
    """Host process rank (reference get_rank; device-level rank is a mesh
    coordinate, see MeshTopology.coord_of)."""
    return jax.process_index()


def get_world_size() -> int:
    """Number of devices (reference world_size counts GPUs, one per process;
    on TPU one process drives many chips so this counts chips)."""
    return jax.device_count()


def get_local_device_count() -> int:
    return jax.local_device_count()


def barrier():
    """reference comm/comm.py barrier; on JAX: a tiny global psum, blocked on."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("deepspeed_tpu_barrier")


def log_summary():
    return comms_logger.log_summary()
