"""The MoE layer.

Parity with reference ``deepspeed/moe/layer.py:15`` (MoE = TopKGate +
MOELayer + Experts with expert-parallel all-to-all) re-designed for SPMD:

* gate: small fp32 Dense (reference TopKGate wg, sharded_moe.py:351)
* dispatch: einsum to ``[experts, capacity, model]`` + a PartitionSpec("ep")
  sharding constraint — GSPMD emits the all-to-all the reference implements
  as the ``_AllToAll`` autograd function (sharded_moe.py:89)
* experts: one stacked tensor sharded over ``ep`` (moe/experts.py)
* expert vs non-expert gradient groups (reference engine.py:2225-2287) need
  no special handling: the global-view jit program reduces each param over
  exactly the axes it is replicated on.

The layer returns ``(y, l_aux, l_z, exp_counts)``; the model adds
``aux_coef * l_aux`` (and ``z_coef * l_z``, the router z-loss) to its loss
(reference stores l_aux on the module and the engine collects it).

Two ways from tokens to experts. With a capacity (``k <= 2`` and
``drop_tokens``): the one-hot ``[tokens, experts, capacity]`` dispatch
above, which drops what overflows. Dropless (``drop_tokens=False`` or
``k > 2``): scores (``scoring``: a softmax over the experts, or the
sigmoid of each expert's own logit), top-k (by the scores alone, or by the
scores plus the layer's ``expert_bias``, which enters the choice and
nothing else), a sort of the tokens * k (token, expert) pairs
by expert, a gather, one grouped matmul per projection over the ragged
groups, and the weighted sum back (moe/sharded_moe.py); no token is left
out under any load, and no tensor grows with experts * capacity. The
grouped matmul is the repo's Pallas kernel
(``ops/pallas/grouped_matmul.py``: an expert's matrix stays on chip while
its rows stream past) where the widths are multiples of 128, the operands
bf16 or float32 and no ``ep`` or ``tp`` axis spans the expert tensors
(``moe/experts.py`` ``grouped_matmul_tiles`` decides from what the call
shows; no option), and ``jax.lax.ragged_dot`` otherwise: on an ``ep`` mesh
the expert tensors keep their ``ep`` sharding and the compiler partitions
its own call; the sorted rows are not annotated for it.

One share of an expert-parallel dropless layer runs on its own
(``experts_held = (first, count)``): the router scores ALL
``num_experts``, the layer holds the matrices of ``count`` of them, sorts
the pairs routed to the others past its last group, so that the grouped
matmuls' ``group_sizes`` cover the held experts alone, and returns its
part of the layer's output, the weighted sum over the pairs routed to
experts it holds. The shares of all devices summed are the layer's routed
output. The exchange that would bring other devices' rows here and take
the parts back is not built: a share serves the tokens it is given. The
choice can be limited to the best groups of experts (``n_group``,
``topk_group``: DeepSeek-V2's device-limited routing, one group a device),
the weights scaled (``routed_scale``), and ``n_shared`` shared experts, one
SwiGLU of their summed width, run on every token beside the routed ones.
"""

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.moe.experts import (
    StackedExperts,
    grouped_matmul_tiles,
    reading_in_place,
)
from deepspeed_tpu.moe.sharded_moe import (
    combine_rows,
    combine_tokens,
    dispatch_rows,
    dispatch_tokens,
    fetches_live_rows,
    router_z_loss,
    rows_computed,
    sort_by_expert,
    topk_gating,
    topk_routing,
)
from deepspeed_tpu.telemetry.scopes import (
    SCOPE_MOE_COMBINE,
    SCOPE_MOE_DISPATCH,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTER,
    SCOPE_MOE_SHARED,
)

# the collection the layer sows its routing counts into; nothing is written
# unless a caller makes it mutable (moe/utils.py publish_expert_load)
MOE_STATS = "moe_stats"


def _ep_constraint(x, ndim_spec):
    """Sharding constraint over the ep axis; a no-op when no ep axis is
    active in the default topology."""
    from deepspeed_tpu.parallel.mesh import get_default_topology

    topo = get_default_topology()
    if topo.size("ep") <= 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(topo.mesh, PartitionSpec(*ndim_spec))
    )


class SharedExperts(nn.Module):
    """The experts every token passes through beside its routed ones, as
    one gated FFN of their summed width (DeepSeek-V2's ``shared_experts``:
    ``down(act(gate x) * up x)``, no bias)."""

    d_model: int
    width: int
    activation: Any
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        h = self.activation(dense(self.width, "c_gate")(x)) \
            * dense(self.width, "c_fc")(x)
        return dense(self.d_model, "c_proj")(h)


class MoE(nn.Module):
    """Drop-in FFN replacement (reference moe/layer.py:15 wraps an `expert`
    module; here the expert FFN is built from d_model/d_hidden)."""

    d_model: int
    d_hidden: int
    num_experts: int = 1
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    gated_experts: bool = False      # SwiGLU experts (Mixtral-style)
    expert_activation: Any = None    # defaults: gelu, or silu when gated
    # dropless path only: divide the k weights by their sum
    norm_topk_prob: bool = False
    # dropless path only (the module's docstring): shared experts on every
    # token; the choice limited to the best ``topk_group`` of ``n_group``
    # consecutive groups; a factor on the weights; the ``(first, count)``
    # of the ``num_experts`` whose matrices this layer holds (None = all)
    n_shared: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None
    # dropless path only (``topk_routing``): "softmax" | "sigmoid" scores;
    # a float32 parameter ``expert_bias [num_experts]`` added to the scores
    # for the choice alone, drawn from a normal of ``expert_bias_init``
    # (0.0: zeros)
    scoring: str = "softmax"
    expert_bias: bool = False
    expert_bias_init: float = 0.0
    # sigmoid scoring's renormaliser (``topk_routing``); None = its own
    renorm_eps: Optional[float] = None

    @property
    def dropless(self) -> bool:
        return self.k > 2 or not self.drop_tokens

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    def _activation(self):
        return self.expert_activation or (
            nn.silu if self.gated_experts else nn.gelu)

    def _experts(self):
        act = self._activation()
        return StackedExperts(
            num_experts=self.held[1],
            d_model=self.d_model,
            d_hidden=self.d_hidden,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            activation=act,
            gated=self.gated_experts,
            use_bias=not self.gated_experts,
            name="experts",
        )

    def _count(self, **counters):
        """Sow the routing counters (moe/utils.py reads them): ``routed``,
        the (token, expert) pairs the router asked for; ``computed``
        [experts], those whose expert output exists, counted from the
        dispatch (one-hot path) or the grouped matmuls' output (dropless)
        and not from the routing; on the dropless path ``chosen`` [tokens,
        k], ``gmm_tiles`` [3], the grouped-matmul kernel's ``(tm, tk,
        tn)`` as this trace chose them, zeros where it chose
        ``ragged_dot``, ``in_place``, 1 where this trace's grouped matmuls
        read the matrices in the stacked parameters and 0 where they took
        a layer's own tensor (moe/experts.py ``expert_matrices``),
        ``held``, how many experts' matrices the layer
        holds (``computed`` is of those), ``routed_here``, the pairs
        routed to them, ``rows_moved``, the sorted rows the dispatch
        fetched (the live ones under the row-fetch kernels, every pair
        under XLA's gather), and, of a layer with an ``expert_bias``,
        ``bias_changed``, the tokens whose chosen experts are not their k
        largest uncorrected scores. (``init`` makes every collection
        mutable and would return them beside the parameters.)"""
        if not self.is_initializing():
            for name, value in counters.items():
                self.sow(MOE_STATS, name, value)

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True, router_input=None):
        """``router_input`` (``x``'s shape; None = ``x``): what the gate's
        logits are computed from where that is not what the experts read
        (``GPTConfig.moe_router_input``)."""
        orig_shape = x.shape
        d_model = orig_shape[-1]
        tokens = x.reshape(-1, d_model)
        scored = tokens if router_input is None \
            else router_input.reshape(-1, d_model)

        # gate in fp32 (reference TopKGate casts input to float, wg fp32),
        # and a true float32 product: the TPU's default rounds float32
        # operands to bfloat16, which would undo both. It matters little
        # (the bfloat16 activations flip more choices than the product
        # does: PERF.md, section 6, PR 27) and costs nothing at this width
        gate = nn.Dense(
            self.num_experts, use_bias=False, dtype=jnp.float32,
            param_dtype=jnp.float32, name="gate",
            precision=jax.lax.Precision.HIGHEST)
        routed = jnp.int32(tokens.shape[0] * self.k)

        if self.dropless:
            if self.noisy_gate_policy is not None:
                raise ValueError(
                    "the dropless path routes deterministically; "
                    f"noisy_gate_policy={self.noisy_gate_policy!r} exists "
                    "only with a capacity (k <= 2, drop_tokens=True)")
            first, held = self.held
            corrected = {}
            if self.scoring != "softmax" or self.expert_bias:
                corrected["scoring"] = self.scoring
            if self.renorm_eps is not None:
                corrected["renorm_eps"] = self.renorm_eps
            if self.expert_bias:
                corrected["bias"] = self.param(
                    "expert_bias",
                    nn.initializers.normal(self.expert_bias_init)
                    if self.expert_bias_init else nn.initializers.zeros,
                    (self.num_experts,), jnp.float32)
            with jax.named_scope(SCOPE_MOE_ROUTER):
                route = topk_routing(gate(scored.astype(jnp.float32)),
                                     self.k, self.norm_topk_prob,
                                     self.n_group, self.topk_group,
                                     self.routed_scale, **corrected)
                groups, weights, sizes = (route.experts, route.weights,
                                          route.exp_counts)
                routed_here = routed
                if self.experts_held is not None:
                    # a pair routed to an expert held elsewhere sorts past
                    # the last held group and weighs nothing here
                    here = (groups >= first) & (groups < first + held)
                    groups = jnp.where(here, groups - first, held)
                    weights = jnp.where(here, weights, 0.0)
                    sizes = jax.lax.dynamic_slice_in_dim(sizes, first, held)
                    routed_here = jnp.sum(here, dtype=jnp.int32)
            tiles = grouped_matmul_tiles(tokens.shape[0] * self.k, d_model,
                                         self.d_hidden, held, self.dtype)
            # a share moves the rows routed here alone where the row-fetch
            # kernels take the call (the grouped-matmul kernel reads no
            # row past them; ``ragged_dot``'s gradient is not known to). A
            # layer that holds all its experts keeps XLA's gather: every
            # row is live there, and what the kernels gain it (5% of
            # OLMoE's step) they cost its set-up in tracing (PERF.md,
            # section 6, PR 64)
            live = {}
            if self.experts_held is not None and tiles \
                    and not self.is_initializing() and fetches_live_rows(
                        tokens.shape[0], self.k, d_model, self.dtype):
                live = {"n_live": jnp.sum(sizes, dtype=jnp.int32),
                        "zero_to": tiles[0]}
            with jax.named_scope(SCOPE_MOE_DISPATCH):
                order, inverse = sort_by_expert(groups)
                rows = dispatch_rows(tokens, order, inverse, self.k, **live)
            with jax.named_scope(SCOPE_MOE_EXPERTS):
                rows = self._experts()(rows, sizes)
                if self.experts_held is not None and not tiles:
                    # the kernel writes zeros past ``sum(group_sizes)``;
                    # ``ragged_dot`` leaves those rows unwritten on the TPU
                    rows = jnp.where(
                        jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes),
                        rows, jnp.zeros((), rows.dtype))
            with jax.named_scope(SCOPE_MOE_COMBINE):
                y = combine_rows(rows, weights, order, inverse,
                                 dtype=x.dtype, **live)
            if self.n_shared:
                with jax.named_scope(SCOPE_MOE_SHARED):
                    y = y + SharedExperts(
                        d_model, self.n_shared * self.d_hidden,
                        self._activation(), self.dtype, self.param_dtype,
                        name="shared")(tokens).astype(y.dtype)
            self._count(routed=routed, chosen=route.experts,
                        computed=rows_computed(rows, groups, order,
                                               held + 1)[:held],
                        gmm_tiles=jnp.asarray(tiles or (0, 0, 0), jnp.int32),
                        in_place=jnp.int32(bool(tiles) and reading_in_place()),
                        held=jnp.int32(held), routed_here=routed_here,
                        rows_moved=live.get("n_live", routed),
                        **({} if route.bias_changed is None
                           else {"bias_changed": route.bias_changed}))
            return (y.reshape(orig_shape), route.l_aux, route.l_z,
                    route.exp_counts)

        gate_logits = gate(scored.astype(jnp.float32))

        rng = None
        if not deterministic and self.has_rng("gating"):
            rng = self.make_rng("gating")

        gout = topk_gating(
            gate_logits,
            k=self.k,
            capacity_factor=(self.capacity_factor if not deterministic
                             else self.eval_capacity_factor),
            min_capacity=self.min_capacity,
            rng=rng,
            noisy_gate_policy=self.noisy_gate_policy,
            drop_tokens=self.drop_tokens,
            use_rts=self.use_rts,
        )

        dispatched = dispatch_tokens(gout.dispatch_mask, tokens)  # [E,C,M]
        dispatched = _ep_constraint(dispatched, ("ep", None, None))
        expert_out = self._experts()(dispatched)
        expert_out = _ep_constraint(expert_out, ("ep", None, None))
        y = combine_tokens(gout.combine_weights, expert_out, dtype=x.dtype)
        # pairs that found a slot, per expert
        self._count(routed=routed, computed=jnp.sum(
            gout.dispatch_mask, axis=(0, 2), dtype=jnp.int32))
        return (y.reshape(orig_shape), gout.l_aux,
                router_z_loss(gate_logits), gout.exp_counts)


def expert_axis(path: str, ndim: int) -> Optional[int]:
    """Index of the expert axis in a :class:`StackedExperts` param (the same
    layout convention :func:`moe_param_spec` encodes: 3rd-from-last for
    wi/wg/wo, 2nd-from-last for bi/bo — robust to a leading scan-layer
    axis), or None for non-expert leaves / shapes too small to carry one
    (e.g. flattened error-feedback buffers)."""
    if "experts/" not in path:
        return None
    if path.endswith(("experts/wi", "experts/wg", "experts/wo")):
        ax = ndim - 3
    elif path.endswith(("experts/bi", "experts/bo")):
        ax = ndim - 2
    else:
        return None
    return ax if ax >= 0 else None


def moe_param_spec(path: str, shape) -> Optional[PartitionSpec]:
    """Expert-parallel PartitionSpec for MoE params, composable with TP rules.

    Expert tensors carry the expert axis 3rd-from-last (wi/wo) or 2nd-from-
    last (bi/bo) — robust to a leading scan-layer axis. Column-parallel tp on
    wi's hidden dim, row-parallel on wo's hidden dim (Megatron FFN pattern).
    """
    ndim = len(shape)

    def spec(**axis_by_dim):
        s = [None] * ndim
        for d, a in axis_by_dim.items():
            s[int(d)] = a
        return PartitionSpec(*s)

    ep_ax = expert_axis(path, ndim)  # single source of the layout rule
    if ep_ax is None:
        return None
    if path.endswith(("experts/wi", "experts/wg")):
        return spec(**{str(ep_ax): "ep", str(ndim - 1): "tp"})
    if path.endswith("experts/wo"):
        return spec(**{str(ep_ax): "ep", str(ndim - 2): "tp"})
    return spec(**{str(ep_ax): "ep"})  # bi/bo
