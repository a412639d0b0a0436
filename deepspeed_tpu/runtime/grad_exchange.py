"""How gradients cross chips when GSPMD's implicit sum does not do it.

A plain engine leaves the gradient average to the partitioner. An explicit
exchange replaces XLA's implicit sum with a ``shard_map``ped program over the
data-parallel axis, so the step keeps PER-WORKER gradients (a leading
``dp``-sized group axis) until the exchange consumes them:

* ``onebit``: a 1-bit optimizer type (reference runtime/fp16/onebit +
  runtime/comm/nccl.py:51) — sign-compressed momentum, the compression IS
  the allreduce;
* ``int8``: ``communication_data_type: int8`` — a quantized gradient
  allreduce with two rounds of error feedback, per leaf or (``bucket_mb`` >
  0) per bucket, in front of any optax optimizer;
* ``deferred``: ``tpu.grad_exchange.deferred`` on a dp > 1 mesh — the same
  machinery at a bf16/fp32 wire: per-worker gradients through the
  accumulation window and ONE bucketed exchange at the GAS boundary instead
  of an implicit psum every micro step; over several slices
  (``hierarchical``) a two-level ICI/DCN exchange.

:func:`select` decides once, from configuration. The engine keeps its answer
(``None`` or a :class:`GradExchange`), which lays out the optimizer state;
the step programs (``runtime/step.py``) ask it for ``k`` and for the update
that runs after the exchange. The collectives live in ``comm/bucketed.py``,
``comm/compressed.py`` and ``runtime/fp16/onebit/``.
"""

import jax
import jax.numpy as jnp
import optax
from flax import serialization
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.bucketed import (
    bucketed_all_reduce,
    bucketed_quantized_all_reduce,
    hierarchical_all_reduce,
    plan_for_tree,
)
from deepspeed_tpu.comm.compressed import (
    quantized_all_reduce,
    server_shard_length,
)
from deepspeed_tpu.runtime.optimizer import (
    apply_optimizer,
    is_compressed_optimizer,
    norm_and_clip,
)
from deepspeed_tpu.telemetry.bus import KIND_COMM_HIERARCHY, publish
from deepspeed_tpu.utils.logging import log_dist, logger

AXIS = "dp"


def _map_errors(state, fn):
    """A 1-bit optimizer state with ``fn`` over its per-worker buffers
    (outside the shard_map they carry the leading group axis)."""
    return state._replace(
        worker_error=jax.tree.map(fn, state.worker_error),
        server_error=jax.tree.map(fn, state.server_error))


def select(config, topology, client_optimizer):
    """The exchange this configuration asks for on this mesh, or None for
    GSPMD's implicit sum."""
    gx = config.tpu.grad_exchange_config
    if (client_optimizer is None
            and is_compressed_optimizer(config.optimizer.type)):
        mode = "onebit"
    elif config.communication_data_type == "int8":
        mode = "int8"
    elif gx.deferred and topology.size(AXIS) > 1:
        mode = "deferred"
    else:
        if gx.hierarchical == "on":
            # "on" demands the two-level exchange; with no deferred
            # exchange engaged that is a config contradiction, not a
            # fallback case ("auto" is the degrade-quietly spelling)
            raise ValueError(
                "tpu.grad_exchange.hierarchical: on requires the deferred "
                "exchange (tpu.grad_exchange.deferred: true on a dp>1 "
                "mesh)")
        return None
    return GradExchange(mode, config, topology)


class GradExchange:
    """One explicit exchange: its mode, the ``dp`` world ``k``, its wire
    dtype and (once :meth:`init_state` has seen the parameter shapes) its
    bucket plan, slice count and the specs of the state it lays out."""

    def __init__(self, mode, config, topology):
        self.mode = mode
        self.topology = topology
        self.k = topology.size(AXIS)
        self._gx = config.tpu.grad_exchange_config
        self._debug_norm = config.tpu.compressed_grad_norm
        # comm/bucketed.py plan; deferred always buckets, int8 when asked
        self.plan = None
        self.wire_dtype = (jnp.float32
                           if self._gx.wire_dtype in ("fp32", "float32")
                           else jnp.bfloat16)
        self.num_slices = 1  # >1 = two-level ICI/DCN exchange
        self._validate(config)

    @property
    def norm_available(self):
        """Whether the step materializes a real averaged-gradient norm (int8
        / deferred: free from the post-exchange mean; onebit: debug-gated)."""
        return self.mode != "onebit" or self._debug_norm

    def optimizer_kwargs(self):
        """What ``build_optimizer`` has to know of the exchange: the 1-bit
        optimizers run their own collective over the axis."""
        if self.mode != "onebit":
            return {}
        return dict(compression_axis=AXIS, compression_axis_size=self.k)

    def _validate(self, config):
        """Constraints shared by the 1-bit optimizers and int8 grad comm.
        fp16 dynamic loss scaling composes (reference fp16/onebit/adam.py:10
        pairs OnebitAdam with the FP16 wrapper): the step cond-skips the
        exchange+update on overflow with error-feedback state carried
        through untouched."""
        mode, topology = self.mode, self.topology
        max_stage = 1 if mode == "onebit" else 0
        if config.zero_config.stage > max_stage:
            raise ValueError(
                f"{mode} compressed gradient exchange requires ZeRO stage "
                f"<= {max_stage} (got {config.zero_config.stage}); the "
                "exchange needs the full gradient/momentum per worker — "
                "same limitation as the reference 1-bit optimizers")
        for ax in ("fsdp", "tp", "pp", "sp", "ep"):
            if topology.size(ax) > 1:
                raise ValueError(
                    f"compressed gradient exchange runs over the dp axis "
                    f"only; mesh axis {ax!r} has size {topology.size(ax)}")
        off = (config.zero_config.offload_optimizer or {}).get("device", "none")
        if off != "none":
            raise ValueError(
                f"{mode} compressed gradient exchange cannot combine with "
                "offload_optimizer (the host step bypasses the exchange)")
        if self._gx.hierarchical != "off" and mode != "deferred":
            raise ValueError(
                "tpu.grad_exchange.hierarchical requires the deferred "
                "bf16/fp32 exchange (grad_exchange.deferred: true); the "
                "onebit/int8 paths own their wire format end to end and "
                "carry error-feedback state the two-level exchange does "
                "not")
        if config.gradient_clipping and mode == "onebit":
            logger.warning(
                "gradient_clipping is ignored with the 1-bit optimizers: "
                "they exchange sign-compressed MOMENTUM, so the averaged "
                "gradient the clip would apply to never exists (divergence "
                "documented in docs/DIVERGENCES.md). The int8 "
                "communication_data_type path clips exactly.")
        if mode == "onebit" and config.zero_config.stage == 1:
            log_dist(
                "OnebitAdam with ZeRO stage 1: optimizer state stays "
                "replicated (the compressed exchange materializes the full "
                "momentum per worker)", ranks=[0])

    def resolve_dcn_slices(self):
        """Inter-slice group count for the hierarchical deferred exchange
        (1 = flat single-level). ``dcn_slices`` overrides detection so the
        virtual CPU mesh can exercise the DCN leg; otherwise the slice
        factor the mesh derived for the dp axis
        (``MeshTopology.dcn_size``) is used."""
        gx = self._gx
        if gx.hierarchical == "off":  # anything else is deferred
            return 1
        n = gx.dcn_slices or self.topology.dcn_size(AXIS)
        if n <= 1:
            if gx.hierarchical == "on":
                raise ValueError(
                    "tpu.grad_exchange.hierarchical: on, but the dp axis "
                    "has no slice structure (single-slice mesh and "
                    "dcn_slices unset) — use hierarchical: auto to fall "
                    "back to the flat exchange, or set dcn_slices")
            return 1
        if self.k % n:
            raise ValueError(
                f"hierarchical exchange: {n} DCN slices do not divide the "
                f"dp axis of {self.k} ranks")
        return n

    def init_state(self, tx, params, param_shapes):
        """State for the shard_mapped step: ``(opt_state, opt_shardings,
        grad_shardings)``.

        Gradients (and their accumulation buffer) carry a leading
        ``dp``-sized group axis — each worker's UNAVERAGED gradient, which
        the exchange consumes (the compression IS the allreduce; reference
        runtime/comm/nccl.py:51). Per-worker error-feedback buffers shard
        over dp; everything else is replicated.
        """
        k = self.k
        pw = self.topology.sharding(AXIS)
        grad_shardings = jax.tree.map(lambda _: pw, param_shapes)

        # bucket plan for the explicit exchange (comm/bucketed.py):
        # deferred always buckets (bucket_mb=0 -> one leaf per bucket);
        # int8 buckets only when asked — its error-feedback buffers change
        # shape with the plan, and the legacy per-leaf layout must stay the
        # default for existing checkpoints
        gx = self._gx
        if (self.mode == "deferred"
                or (self.mode == "int8" and gx.bucket_mb > 0)):
            self.plan = plan_for_tree(param_shapes, gx.bucket_mb)
        self.num_slices = self.resolve_dcn_slices()
        if self.num_slices > 1:
            # discrete layout decision -> telemetry (docs/observability.md):
            # the flight recorder sees which ranks pay DCN and in what wire
            publish(KIND_COMM_HIERARCHY,
                    world=int(k),
                    num_slices=int(self.num_slices),
                    per_slice=int(k // self.num_slices),
                    ici_wire=str(jnp.dtype(self.wire_dtype)),
                    dcn_wire="int8",
                    dcn_block=int(gx.dcn_block),
                    num_buckets=int(self.plan.num_buckets
                                    if self.plan else 0))

        if self.mode == "onebit":
            self._opt_specs = _map_errors(
                jax.tree.map(lambda _: P(),
                             jax.eval_shape(tx.init, param_shapes)),
                lambda _: P(AXIS))
            opt_state = jax.jit(jax.shard_map(
                lambda p: _map_errors(tx.init(p), lambda x: x[None]),
                mesh=self.topology.mesh, in_specs=(P(),),
                out_specs=self._opt_specs, check_vma=False))(params)
        else:
            # (inner optimizer, worker residuals, server residuals): the
            # error feedback of the two quantization rounds shards over dp
            inner = jax.jit(tx.init)(params)
            if self.mode == "deferred":
                # bf16/fp32 wire: no quantization, no error feedback — the
                # 1-tuple keeps the (inner, ...) shape of the
                # explicit-exchange family for checkpoints
                residuals = ()
            else:
                # int8 quantized grad allreduce, any optax optimizer. The
                # compensation spans exactly what each exchange quantizes:
                # a leaf, or bucketed the flat concatenated payload of one
                # BUCKET. Phase-2 (server) buffers hold one reduced-shard
                # residual per worker (reference compressed_allreduce
                # compensates both quantization rounds,
                # runtime/comm/nccl.py:51)
                payloads = param_shapes if self.plan is None else tuple(
                    jax.ShapeDtypeStruct((n,), jnp.float32)
                    for n in self.plan.bucket_sizes())

                def zeros(shape_of):
                    return jax.jit(lambda: jax.tree.map(
                        lambda x: jnp.zeros((k,) + shape_of(x), jnp.float32),
                        payloads), out_shardings=pw)()

                residuals = (
                    zeros(lambda x: x.shape),
                    zeros(lambda x: (server_shard_length(x.size, k),)))
            opt_state = (inner,) + residuals
            self._opt_specs = (P(),) + (P(AXIS),) * len(residuals)
        opt_shardings = jax.tree.map(lambda x: x.sharding, opt_state)
        return opt_state, opt_shardings, grad_shardings

    def update_core(self, tx, clip):
        """shard_map program: per-worker grads -> compressed exchange ->
        optimizer update -> replicated new params. ``runtime/step.py``
        ``guarded_update`` runs it as the taken branch of its overflow
        ``cond``; norm and clip happen in here, after the exchange, on the
        mean (the 1-bit optimizers never form one: their norm is 0.0 unless
        ``tpu.compressed_grad_norm``, and they do not clip)."""
        k, mode, plan = self.k, self.mode, self.plan

        def apply_step(params, opt_state, grads_pw, lr_factor):
            local_g = jax.tree.map(lambda g: g[0], grads_pw)  # [1,*s]->[*s]
            if mode == "onebit":
                if self._debug_norm:
                    # debug-only exact pmean: a full fp32 allreduce beside
                    # the compressed exchange (tpu.compressed_grad_norm)
                    g_avg = jax.tree.map(
                        lambda g: jax.lax.pmean(g, AXIS), local_g)
                    grad_norm = optax.global_norm(g_avg)
                else:
                    grad_norm = jnp.float32(0.0)
                new_params, new_st = apply_optimizer(
                    tx, params, _map_errors(opt_state, lambda x: x[0]),
                    local_g, lr_factor, cast=False)
                return (new_params, _map_errors(new_st, lambda x: x[None]),
                        grad_norm)
            if mode == "deferred":
                (inner,) = opt_state
                if self.num_slices > 1:
                    # two-level ICI/DCN exchange: wire_dtype psum_scatter /
                    # all_gather inside each slice, bucketed int8 EQuARX
                    # exchange of the 1/P shard across slices
                    mean_g = hierarchical_all_reduce(
                        local_g, AXIS, self.num_slices, plan,
                        block=self._gx.dcn_block,
                        wire_dtype=self.wire_dtype, mean=True,
                        log_name="hierarchical_grad_exchange")
                else:
                    # ONE bucketed explicit exchange at the GAS boundary:
                    # each bucket is an independent collective XLA may
                    # overlap with the others' cast/unpack compute
                    # (T3-style)
                    mean_g = bucketed_all_reduce(
                        local_g, AXIS, plan, wire_dtype=self.wire_dtype,
                        mean=True, log_name="bucketed_grad_exchange")
                new_opt_tail = ()
            elif plan is not None:
                inner, err, serr = opt_state
                # per-BUCKET int8 exchange: independent collective chains
                # (vs the serial per-leaf loop) with residuals carried on
                # the flat bucket payloads
                summed, e2s, se2s = bucketed_quantized_all_reduce(
                    local_g, AXIS, plan,
                    worker_errors=[e[0] for e in err],
                    server_errors=[se[0] for se in serr])
                mean_g = jax.tree.map(lambda r: r / k, summed)
                new_opt_tail = (tuple(e[None] for e in e2s),
                                tuple(se[None] for se in se2s))
            else:
                inner, err, serr = opt_state
                reduced, new_err, new_serr = [], [], []
                flat_g, treedef = jax.tree.flatten(local_g)
                for g, e, se in zip(flat_g, jax.tree.leaves(err),
                                    jax.tree.leaves(serr)):
                    r, e2, se2 = quantized_all_reduce(
                        g + e[0], AXIS, return_error=True,
                        server_error=se[0])
                    reduced.append(r / k)
                    new_err.append(e2[None])
                    new_serr.append(se2[None])
                mean_g = jax.tree.unflatten(treedef, reduced)
                new_opt_tail = (jax.tree.unflatten(treedef, new_err),
                                jax.tree.unflatten(treedef, new_serr))
            # the post-exchange mean is materialized anyway: its norm is
            # free, and gradient_clipping gets exact semantics
            mean_g, grad_norm = norm_and_clip(mean_g, clip)
            new_params, new_inner = apply_optimizer(
                tx, params, inner, mean_g, lr_factor)
            return new_params, (new_inner,) + new_opt_tail, grad_norm

        return jax.shard_map(
            apply_step, mesh=self.topology.mesh,
            in_specs=(P(), self._opt_specs, P(AXIS), P()),
            out_specs=(P(), self._opt_specs, P()),
            check_vma=False)

    def migrate_state_dict(self, opt_sd, opt_state):
        """A checkpoint's optimizer state dict in today's layout:
        pre-server-error int8 checkpoints held (inner, worker_err); "2" =
        the phase-2 residuals, and fresh zeros are the correct cold start
        for error-feedback buffers."""
        if (self.mode == "int8" and isinstance(opt_sd, dict)
                and "2" not in opt_sd and "1" in opt_sd):
            opt_sd = dict(opt_sd)
            opt_sd["2"] = serialization.to_state_dict(opt_state[2])
        return opt_sd
