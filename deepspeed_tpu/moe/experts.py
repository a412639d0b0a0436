"""Stacked expert FFNs.

Parity with reference ``deepspeed/moe/experts.py:9`` (Experts = ModuleList of
cloned FFNs, each rank holding ``num_local_experts``). TPU re-design: ONE
parameter tensor with a leading ``experts`` axis, sharded over the ``ep`` mesh
axis — "local experts" are the shard XLA assigns this device; the per-expert
loop becomes a batched einsum on the MXU.

Over rows sorted by expert (the dropless path) each projection is one
grouped matmul. Where the layout allows it that is the repo's own Pallas
kernel (``ops/pallas/grouped_matmul.py``: an expert's matrix stays on chip
while its rows stream past; forward and both gradients), chosen by
:func:`grouped_matmul_tiles` from what the call shows and by no option;
otherwise ``jax.lax.ragged_dot``, which the compiler can partition.

Inside a layer scan a layer's matrices are the scan's slice of the stacked
parameters, and a slice handed to a custom call is written out whole
first (3 x 314 MB a layer at DeepSeek-V2's widths, two fifths of its
decode step: PERF.md, section 6, PR 44; 3 x 268 MB a layer at OLMoE's,
forward and again backward: PR 58). The kernel therefore reads the STACK
where it lies, the layer one more index (:func:`expert_matrices` is the
rule, :func:`matrices_in_place` how the scan's owner hands stack and index
over), serving and training alike. A serving call differentiates nothing
and calls the forward product. A training call is differentiated WITH
RESPECT TO THE SLICE and reads the stack
(``grouped_matmul.grouped_matmul(..., stack=, layer=)``): forward turn,
recomputed turn and the rows' gradient multiply by the stack in place;
the matrices' gradient (``tgmm``) is the cotangent of the slice, which
the scan stacks into the parameter's gradient as it does every leaf's (one
``[E, K, N]`` write a matrix a layer: those stay); the stack itself
carries no gradient, so nothing the size of the stack is summed a turn.
"""

import contextlib
import contextvars
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

def grouped_matmul_tiles(rows: int, d_model: int, d_hidden: int,
                         num_experts: int,
                         dtype) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` of the kernel's forward call for the up projection
    (its ``tm`` is the layer's: one walk over the row tiles serves all its
    calls) where the experts' grouped matmuls over ``rows`` sorted rows run
    in the Pallas kernel, None where they are ``jax.lax.ragged_dot``. The
    kernel
    wants both widths whole lanes (multiples of 128), the rows whole row
    tiles, bf16 or float32 operands (``grouped_matmul.supported``), and
    the expert tensors whole on the device: a Pallas call is opaque to the
    partitioner, so under an ``ep`` or ``tp`` axis of more than one device
    the compiler's ragged dot, which it can partition, stays."""
    # imported where it is needed: importing Pallas and Mosaic is a second
    # of a process's start, and most models have no experts
    from deepspeed_tpu.ops.pallas import autotune, grouped_matmul
    from deepspeed_tpu.parallel.mesh import get_default_topology

    topo = get_default_topology()
    if topo.size("ep") > 1 or topo.size("tp") > 1 \
            or not grouped_matmul.supported(rows, d_model, d_hidden, dtype):
        return None
    return autotune.grouped_matmul_tiles(
        "gmm", rows, d_model, d_hidden, num_experts, dtype)


def expert_matrices(cfg, rows: int) -> str:
    """How the scanned expert layers of a model (``cfg``: its
    ``GPTConfig``) read their matrices in a call that sorts ``rows`` (token,
    expert) pairs a layer: ``"in_place"``, the grouped-matmul kernel over
    the stacked parameter leaf ``[layers, E, K, N]`` as it lies in memory
    with the layer as an index; ``"slice"``, each layer multiplies by the
    ``[E, K, N]`` tensor it is handed (the scan's slice, or its own leaf
    without a scan); ``"none"``, no layer has experts. Told from what the
    call shows, by no option; model and scheduler ask alike, of a serving
    call and of a training step (differentiated or not: the gradient of a
    call in place goes to the scan's slice, the module's docstring). In
    place where all hold:

    * :func:`grouped_matmul_tiles` chose the kernel (the sorted-rows path
      at widths of whole lanes, rows of whole tiles, bf16 or float32, no
      ``ep`` or ``tp`` axis): the compiler fuses a slice into a ragged dot
      of its own and has nothing to copy;
    * the layers run in ``ScannedBlocks``' scan: a layer looped over reads
      a leaf of its own;
    * the layer multiplies by what is stored: not wider parameters cast
      on use, nor a stack that is dequantised, gathered over ``fsdp``
      (every program under a ZeRO-3 plan) or streamed from the host where
      the layer reads it."""
    from deepspeed_tpu.runtime.zero.gather import current_plan

    if not cfg.is_moe or cfg.n_layer <= cfg.first_k_dense:
        return "none"
    dropless = cfg.moe_top_k > 2 or not cfg.moe_drop_tokens
    held = (cfg.moe_experts_held or (0, cfg.moe_num_experts))[1]
    if (dropless and cfg.scan_layers
            and jnp.dtype(cfg.param_dtype) == jnp.dtype(cfg.dtype)
            and not (cfg.quantized_weights or cfg.param_offload)
            and current_plan() is None
            and grouped_matmul_tiles(rows, cfg.n_embd, cfg.moe_ffn_dim, held,
                                     cfg.dtype)):
        return "in_place"
    return "slice"


# What a layer scan's owner offers the experts traced inside one turn: the
# stacked ``{"wi", "wg", "wo"}`` leaves, the turn's index into them, and
# whether the call serves.
_STACKED: contextvars.ContextVar = contextvars.ContextVar(
    "stacked_expert_matrices", default=None)


def stack_in_place(stacked, dtype, serving):
    """``stacked`` (the ``experts`` subtree of a layer scan's parameters,
    leading layer axis) as :func:`matrices_in_place` takes it, made by the
    scan's owner OUTSIDE the scan: None for a tree handed over in another
    dtype than the configuration declares (the layer casts its slice, as
    it did); under ``lax.stop_gradient`` unless the call serves (a
    training step's gradient belongs to the turn's slice: a cotangent the
    size of the stack would otherwise be summed every turn)."""
    if any(leaf.dtype != dtype for leaf in stacked.values()):
        return None
    return stacked if serving else jax.lax.stop_gradient(stacked)


@contextlib.contextmanager
def matrices_in_place(stacked, layer, serving):
    """Entered around one turn of a layer scan whose expert matrices are
    read where they lie (:func:`expert_matrices`): ``stacked`` what
    :func:`stack_in_place` made of the scanned blocks' ``experts``
    subtree, ``layer`` this turn's index into it (traced), ``serving``
    whether the call is one that nothing differentiates (its programs
    then hold the kernel's forward call alone, as they always did). Read
    at trace time by :class:`StackedExperts`; as ZeRO-3's
    ``gather_context`` tells the layer loop of its rules, without an
    argument through every ``__call__`` between."""
    token = _STACKED.set((stacked, layer, serving))
    try:
        yield
    finally:
        _STACKED.reset(token)


def reading_in_place() -> bool:
    """Whether the experts traced now read the stack (a counter's source)."""
    return _STACKED.get() is not None


def serving_call() -> bool:
    """Whether the layer traced now is one turn of a serving call's layer
    scan, as the scan's owner said (:func:`matrices_in_place`)."""
    stacked = _STACKED.get()
    return stacked is not None and bool(stacked[2])


class StackedExperts(nn.Module):
    """[E, C, M] -> [E, C, M] two-layer FFN, vectorized over experts; or,
    with ``group_sizes`` ([E], summing to R), [R, M] -> [R, M] over rows
    sorted by expert: each einsum becomes one grouped matmul over the
    ragged groups, so no row is padding and none is dropped: the Pallas
    kernel ``ops/pallas/grouped_matmul.py`` where
    :func:`grouped_matmul_tiles` says the layout allows it (widths
    multiples of 128, bf16 or float32, no ``ep`` or ``tp`` axis over the
    expert tensors), ``jax.lax.ragged_dot`` otherwise; the same arithmetic
    either way (float32 accumulation over all of K, one rounding, zeros
    for rows past ``sum(group_sizes)``).

    Param shapes carry the expert axis first (``wi: [E, M, H]``,
    ``wo: [E, H, M]``) so expert-parallel sharding rules can address it
    (see moe/layer.py moe_sharding_rules).

    ``gated=True`` makes each expert a SwiGLU FFN (Mixtral-style:
    ``wo @ (act(wg x) * (wi x))``, biasless), with a ``wg`` gate tensor
    alongside ``wi`` — same expert-parallel layout.

    Under :func:`matrices_in_place` the three products take the stacked
    leaves and the layer's index: the kernel's own calls on the same
    blocks, bitwise the slice's results. A serving call is the forward
    product (``gmm(..., layer=)``); any other is differentiable with
    respect to the layer's own ``wi`` / ``wg`` / ``wo``, whose values it
    does not read (``grouped_matmul(..., stack=, layer=)``).
    """

    num_experts: int
    d_model: int
    d_hidden: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    activation: Callable = nn.gelu
    gated: bool = False
    use_bias: bool = True

    @nn.compact
    def __call__(self, x, group_sizes=None):
        E, M, H = self.num_experts, self.d_model, self.d_hidden
        if group_sizes is None:
            def matmul(a, w, name):
                return jnp.einsum("ecm,emh->ech", a, w)

            def per_expert(b):
                return b[:, None, :]
        else:
            # (``init`` wants the parameters' shapes alone: tracing and
            # lowering three kernels for it is set-up time for nothing)
            tiles = None if self.is_initializing() else \
                grouped_matmul_tiles(x.shape[0], M, H, E, self.dtype)
            stacked, layer, serving = _STACKED.get() or (None, None, False)
            if tiles:
                from deepspeed_tpu.ops.pallas import grouped_matmul as gm

                # one walk over the row tiles for all three projections,
                # forward and backward
                walk = gm.row_walk(group_sizes, x.shape[0], tiles[0])

            def matmul(a, w, name):
                if tiles and serving:
                    return gm.gmm(a, stacked[name], None, walk=walk,
                                  layer=layer)
                if tiles:
                    return gm.grouped_matmul(
                        a, w, group_sizes, walk, layer=layer,
                        stack=None if stacked is None else stacked[name])
                return jax.lax.ragged_dot(a, w, group_sizes)

            def per_expert(b):
                return jnp.repeat(b, group_sizes, axis=0,
                                  total_repeat_length=x.shape[0])

        wi = self.param("wi", nn.initializers.lecun_normal(),
                        (E, M, H), self.param_dtype)
        wo = self.param("wo", nn.initializers.lecun_normal(),
                        (E, H, M), self.param_dtype)
        x = x.astype(self.dtype)
        h = matmul(x, wi.astype(self.dtype), "wi")
        if self.use_bias:
            bi = self.param("bi", nn.initializers.zeros, (E, H),
                            self.param_dtype)
            h = h + per_expert(bi).astype(self.dtype)
        if self.gated:
            wg = self.param("wg", nn.initializers.lecun_normal(),
                            (E, M, H), self.param_dtype)
            g = matmul(x, wg.astype(self.dtype), "wg")
            h = self.activation(g) * h
        else:
            h = self.activation(h)
        y = matmul(h, wo.astype(self.dtype), "wo")
        if self.use_bias:
            bo = self.param("bo", nn.initializers.zeros, (E, M),
                            self.param_dtype)
            y = y + per_expert(bo).astype(self.dtype)
        return y
