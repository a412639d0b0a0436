"""Layers of several kinds in one stack (``GPTConfig.layer_types``).

``ScannedBlocks`` scans ONE ``Block`` over a leading layer axis of its
parameters and of its cache. A model whose layers differ in kind (LFM2:
gated short convolutions with an attention layer every few of them) has
no such axis: the convolution's ``[C, 3C]`` input projection and
attention's q/k/v/o cannot be stacked, and a cache that gave every layer
every leaf would hold the keys and values of layers that never attend.
Here each kind has stacks of its own:

* parameters, one stack a kind of BLOCK: ``params/h/<kind>`` ``[n, ...]``
  over the kind's layers in order and, for the leading ``first_k_dense``
  layers (a dense MLP where the others hold experts, so another tree),
  ``params/h/<kind>_dense``;
* cache leaves, one stack a kind of MIXER: ``cache/h/<kind>/...`` over ALL
  the kind's layers, dense or not (``attention``: keys, values, ``valid``
  and the clocks, ``[attention layers, B, ...]``; ``conv``: the tails,
  ``[convolution layers, B, K - 1, C]``).

The declared sequence is cut into runs of equal block kind and each run is
one ``lax.scan`` whose turn takes its layer's parameters out of the kind's
stack by index (the stack is closed over, loop-invariant: what a scan does
with its own ``xs``, so no run's part of a stack is ever sliced out whole),
so program size grows with the number of runs, not with depth. The cache is
carried through every run and each layer updates its own index of its
kind's stack in place (``cache_layer``: the layer's place among the layers
of its kind), on the call that makes the cache as on the calls that find
it; the experts' matrices are read where they lie in their stack where
moe/experts.py ``expert_matrices`` says so, as under ``ScannedBlocks``.

Not built for such a stack, and refused (``GPTConfig.__post_init__``, and
here for ZeRO-3's gather): weights that are not read as stored
(``quantized_weights``, ``param_offload``, a gather over ``fsdp``),
progressive layer drop, and mixers other than attention (grouped-query, or
latent attention where the kinds declare it: ``GPTConfig.latent_kinds``;
then two kinds' leaves of one name differ in WIDTH as well as length) and
the short convolution: the state-space mixer, retention and the whole-model
``mla`` / ``indexer`` fields stay with stacks of one kind.
"""
import contextlib
import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.traverse_util import flatten_dict

from deepspeed_tpu.moe.layer import MOE_STATS


@dataclasses.dataclass(frozen=True)
class Run:
    """Consecutive layers of one kind of block."""
    stack: str          # the parameter stack's name
    mixer: str          # the layers' entry of ``layer_types``
    dense: bool         # among the leading ``first_k_dense``
    first_layer: int
    first_param: int    # the first layer's index in its parameter stack
    first_cache: int    # and among the layers of its mixer's kind
    length: int


def layer_runs(cfg) -> Tuple[Run, ...]:
    """``cfg.layer_types`` as runs of equal block kind, in order."""
    runs, params, caches = [], {}, {}
    for layer, mixer in enumerate(cfg.layer_types):
        dense = layer < cfg.first_k_dense
        stack = mixer + "_dense" if dense else mixer
        if runs and runs[-1].stack == stack:
            runs[-1] = dataclasses.replace(runs[-1],
                                           length=runs[-1].length + 1)
        else:
            runs.append(Run(stack, mixer, dense, layer, params.get(stack, 0),
                            caches.get(mixer, 0), 1))
        params[stack] = params.get(stack, 0) + 1
        caches[mixer] = caches.get(mixer, 0) + 1
    return tuple(runs)


def _take(stack, index):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False),
        stack)


class KindStackedBlocks(nn.Module):
    """All blocks of a model that mixes kinds of layer (the module's
    docstring). Returns ``(x, l_aux)`` as ``ScannedBlocks`` does."""

    config: "GPTConfig"  # noqa: F821  (models/transformer_lm.py)

    @nn.compact
    def __call__(self, x, *, mask=None, segment_ids=None, positions=None,
                 deterministic=True, decode=False):
        from deepspeed_tpu.models.transformer_lm import Block, _remat_policy
        from deepspeed_tpu.moe import experts
        from deepspeed_tpu.runtime.zero.gather import current_plan

        cfg = self.config
        if current_plan() is not None:
            raise NotImplementedError(
                "ZeRO-3's gather on use wraps the one Block that "
                "ScannedBlocks scans; a stack that mixes kinds of layer "
                "reads its weights as stored")
        runs = layer_runs(cfg)
        blocks, sizes = {}, {}
        for run in runs:
            blocks.setdefault(run.stack, Block(
                cfg, dense_mlp=run.dense, mixer=run.mixer, parent=None))
            sizes[run.stack] = sizes.get(run.stack, 0) + run.length

        # ---- parameters: one stack a kind of block -----------------------
        def born(stack):
            def init():
                keys = jax.random.split(self.make_rng("params"),
                                        sizes[stack])
                # a layer at a time (the same values as all at once: each
                # layer's own key): the temporaries of ONE layer's random
                # draws, not of the stack's
                return jax.lax.map(lambda key: blocks[stack].init(
                    {"params": key}, x, deterministic=True)["params"], keys)

            return init

        stacks = {stack: self.variable("params", stack, born(stack)).value
                  for stack in blocks}

        # ---- the cache: one stack a kind of mixer, carried ---------------
        def empty(run):
            def init():
                shapes = jax.eval_shape(
                    lambda p: blocks[run.stack].apply(
                        {"params": p}, x, mask=mask, deterministic=True,
                        decode=True, mutable=["cache"])[1]["cache"],
                    _take(stacks[run.stack], 0))
                n = cfg.layer_types.count(run.mixer)
                return jax.tree.map(
                    lambda sd: jnp.zeros((n,) + sd.shape, sd.dtype), shapes)

            return init

        kept = {}
        if decode:
            for run in runs:
                if run.mixer not in kept:
                    kept[run.mixer] = self.variable("cache", run.mixer,
                                                    empty(run))
        caches = {mixer: var.value for mixer, var in kept.items()}

        counting = self.is_mutable_collection(MOE_STATS) \
            and not self.is_initializing()
        mutable = (["cache"] if decode else []) \
            + ([MOE_STATS] if counting else [])
        in_place = experts.expert_matrices(
            cfg, x.shape[0] * x.shape[1] * cfg.moe_top_k) == "in_place"

        def turn(run):
            block, stack = blocks[run.stack], stacks[run.stack]
            matrices = experts.stack_in_place(
                stack["mlp"]["experts"], cfg.dtype, serving=decode) \
                if in_place and not run.dense \
                and not self.is_initializing() else None

            def call(x, cache, at):
                at_param, at_cache, layer = at
                variables = {"params": _take(stack, at_param)}
                if decode:
                    variables["cache"] = cache
                rngs = {} if deterministic else {
                    name: jax.random.fold_in(self.make_rng(name), layer)
                    for name in ("dropout", "gating") if self.has_rng(name)}
                out = block.apply(
                    variables, x, mask=mask, segment_ids=segment_ids,
                    positions=positions, deterministic=deterministic,
                    decode=decode, cache_layer=at_cache if decode else None,
                    mutable=mutable or False, rngs=rngs)
                (x, l_aux), left = out if mutable else (out, {})
                return x, left.get("cache", cache), \
                    (l_aux, left.get(MOE_STATS, {}))

            if cfg.remat:
                call = jax.checkpoint(call, prevent_cse=False,
                                      policy=_remat_policy(cfg.remat_policy))

            def body(carry, at):
                with jax.named_scope(run.stack), experts.matrices_in_place(
                        matrices, at[0], serving=decode) \
                        if matrices is not None else contextlib.nullcontext():
                    x, cache, out = call(*carry, at)
                return (x, cache), out

            return body

        l_aux, counted = jnp.float32(0.0), {}
        for run in runs:
            (x, cache), (aux, stats) = jax.lax.scan(
                turn(run), (x, caches.get(run.mixer)),
                tuple(jnp.arange(first, first + run.length) for first in (
                    run.first_param, run.first_cache, run.first_layer)))
            if decode:
                caches[run.mixer] = cache
            l_aux = l_aux + jnp.sum(aux)
            for path, (value,) in flatten_dict(stats).items():
                counted.setdefault(path[-1], []).append(value)
        for mixer, var in kept.items():
            var.value = caches[mixer]
        # the expert layers' counters (moe/utils.py), all layers in order
        for name, parts in counted.items():
            self.sow(MOE_STATS, name, jnp.concatenate(parts))
        return x, l_aux
