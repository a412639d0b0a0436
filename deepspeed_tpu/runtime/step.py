"""The training step, written once.

The engine's compiled programs as plain functions of what they need (a
:class:`StepSpec`; nothing here reads an engine):

* :func:`loss_and_grads`: value_and_grad of the (scaled) loss of one micro
  batch, into gradients whose sharding encodes the ZeRO stage (replicated ->
  psum at use; sharded over fsdp -> reduce-scatter), replacing the per-param
  backward hooks and bucketed reducers of stage_1_and_2.py:832-1038 — or
  per ``dp`` group under ``vmap``, where an explicit exchange
  (``runtime/grad_exchange.py``) consumes them unaveraged;
* :func:`guarded_update`: unscale -> overflow check -> global-norm clip ->
  overflow-gated optimizer update -> loss-scale update, all under
  ``lax.cond`` (reference does this host-side in fused_optimizer.py:147 /
  stage_1_and_2.py:1744);
* ``build_fwd_bwd`` / ``build_apply`` / ``build_train_step`` /
  ``build_eval`` compose the two and own ``jax.jit``, donation and
  ``out_shardings``. The jitted callables keep the Python names
  ``fwd_bwd``, ``apply_step``, ``train_step`` and ``eval_fn``: a profiler
  trace names the modules ``jit_<name>`` and the scope table's paths start
  with it.

Where the GSPMD family and the explicit-exchange family differ in more than
spelling, ``spec.exchange`` is asked: every program lowers to the text it
lowered to when the two were written apart (PERF.md, PR 30).
"""

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import optax

from deepspeed_tpu.runtime.loss_scaler import has_overflow, update_loss_scale
from deepspeed_tpu.runtime.optimizer import apply_optimizer, norm_and_clip
from deepspeed_tpu.runtime.zero.gather import gather_context
from deepspeed_tpu.telemetry.scopes import (
    SCOPE_GRAD_CAST,
    SCOPE_GRAD_NORM_CLIP,
    SCOPE_OPTIMIZER,
    SCOPE_OVERFLOW_CHECK,
)


@dataclasses.dataclass(frozen=True)
class StepSpec:
    """What a step program is a function of."""

    model: Any  # flax Module whose __call__(**batch) returns the loss
    tx: optax.GradientTransformation
    rules: Any  # ZeroShardingRules: what gather_context carries to the model
    exchange: Any  # grad_exchange.GradExchange, or None for GSPMD's sum
    clip: float  # gradient_clipping (0/None = off)
    ls_config: Any  # update_loss_scale is a no-op without fp16 scaling
    # fp16 loss-scale gating, or the sentinel's any-dtype non-finite guard:
    # a NaN/Inf grad tree cond-skips the update either way
    check_overflow: bool
    gas: int
    pld: Any  # the ProgressiveLayerDrop schedule (theta, gamma), or None
    # offload_param: grads of streamed layers land in HOST memory
    # (per-layer, from the streaming bwd); elementwise accumulation on
    # host tensors is not a device op, so the buffer is REPLACED each
    # micro step — with gas > 1 forward() accumulates host-side numpy
    # (the grads are host-resident anyway; the host optimizer consumes
    # them there)
    replace_acc: bool
    param_shardings: Any
    opt_shardings: Any
    grad_shardings: Any


def loss_and_grads(spec, program, params, batch, rng, step, loss_scale,
                   pld_step):
    """``(grads, loss)`` of one micro batch. With an explicit exchange the
    gradients are taken per worker, by a vmap over dp-sized batch groups:
    each group's gradient only depends on its batch shard, so the [k, ...]
    output shards over dp with NO collective — the exchange in the update
    is the only cross-worker traffic.

    ``loss_scale`` and ``pld_step`` are thunks: each traces a few scalar
    operations, and where they land is part of the lowered text. The GSPMD
    family has always formed them inside the loss (the scale after the
    model's loss), the per-group family ahead of the vmap (the scale ahead
    of the rng fold), and each keeps its place.
    """
    model, exchange, pld = spec.model, spec.exchange, spec.pld
    per_group = exchange is not None

    def model_kwargs():
        """Extra model kwargs for stochastic-mode models under a PLD
        schedule: ``pld_theta`` computed IN-GRAPH from the (traced) step
        counter — theta(t) = (1 - theta)e^{-gamma t} + theta, exactly the
        host-side ProgressiveLayerDrop schedule — so the compiled step
        needs no per-step host transfer or recompile."""
        if pld is None or not getattr(getattr(model, "config", None),
                                      "stochastic_mode", False):
            return {}
        theta = pld.theta + (1.0 - pld.theta) * jnp.exp(
            -pld.gamma * jnp.asarray(pld_step(), jnp.float32))
        return {"pld_theta": theta}

    if per_group:
        scale_now = loss_scale()
    # fold the step counter in HERE: a host-side jax.random.split per
    # micro step costs a full small-op dispatch round-trip
    rng = jax.random.fold_in(rng, step)
    if per_group:
        rngs = jax.random.split(rng, exchange.k)
        kwargs_now = model_kwargs()
        loss_scale, model_kwargs = (lambda: scale_now), (lambda: kwargs_now)

    def loss_fn(p, local_batch, r):
        # carries nothing under any rules but stage 3 over fsdp > 1, and
        # an explicit exchange admits no such mesh
        with gather_context(spec.rules, program):
            loss = model.apply(
                {"params": p}, **local_batch, deterministic=False,
                rngs={"dropout": r, "gating": jax.random.fold_in(r, 7)},
                **model_kwargs(),
            )
        return loss * loss_scale(), loss

    if not per_group:
        return jax.grad(loss_fn, has_aux=True)(params, batch, rng)
    k = exchange.k
    grouped = jax.tree.map(
        lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)
    grads, losses = jax.vmap(
        jax.grad(loss_fn, has_aux=True), in_axes=(None, 0, 0)
    )(params, grouped, rngs)
    return grads, jnp.mean(losses)


def guarded_update(spec):
    """The update tail, for tracing: ``(params, opt_state, grads, ls_state,
    lr_factor) -> (new_params, new_opt, new_ls, overflow, grad_norm)`` with
    ``grads`` still scaled by the loss scale. On overflow the update — and
    with an explicit exchange the exchange itself — is cond-skipped with
    the optimizer count and the error-feedback buffers untouched (reference
    fp16+onebit skip semantics, fp16/onebit/adam.py:10).

    With GSPMD the norm and clip run ahead of the ``cond``, on the summed
    gradients. With an explicit exchange they are still per worker there:
    the taken branch is the exchange's shard_mapped core, which norms and
    clips the post-exchange mean, and the skip branch returns a norm too.
    """
    explicit = spec.exchange is not None
    update = (spec.exchange.update_core(spec.tx, spec.clip) if explicit
              else functools.partial(apply_optimizer, spec.tx))

    def tail(params, opt_state, grads, ls_state, lr_factor):
        with jax.named_scope(SCOPE_GRAD_CAST):
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / ls_state.scale, grads)
        with jax.named_scope(SCOPE_OVERFLOW_CHECK):
            overflow = (has_overflow(grads) if spec.check_overflow
                        else jnp.bool_(False))
        if not explicit:
            with jax.named_scope(SCOPE_GRAD_NORM_CLIP):
                grads, grad_norm = norm_and_clip(grads, spec.clip)

        @jax.named_scope(SCOPE_OPTIMIZER)
        def do_update(operand):
            params, opt_state, grads = operand
            return update(params, opt_state, grads, lr_factor)

        def skip_update(operand):
            params, opt_state, _ = operand
            if explicit:
                return params, opt_state, jnp.float32(0.0)
            return params, opt_state

        out = jax.lax.cond(
            overflow, skip_update, do_update, (params, opt_state, grads))
        new_params, new_opt = out[:2]
        if explicit:
            grad_norm = out[2]
        new_ls = update_loss_scale(ls_state, overflow, spec.ls_config)
        return new_params, new_opt, new_ls, overflow, grad_norm

    return tail


def build_fwd_bwd(spec):
    gas, replace_acc = spec.gas, spec.replace_acc

    def fwd_bwd(params, acc_grads, batch, rng, step, scale):
        # loss scaled by 1/gas (reference engine.py:1789 -> :1596)
        # and by the fp16 loss scale (loss_scaler.py)
        grads, loss = loss_and_grads(
            spec, "fwd_bwd", params, batch, rng, step,
            loss_scale=lambda: scale / gas, pld_step=lambda: step // gas)
        if replace_acc:
            return grads, loss
        new_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), acc_grads, grads)
        return new_acc, loss

    # replace_acc with gas > 1: the previous micro step's grad leaves
    # stay alive until their in-flight host copies are drained
    # (double-buffered host accumulation), so the acc_grads argument
    # must NOT be donated out from under them. At gas == 1 the offload
    # step consumes the grads before the next dispatch — keep donating
    # so peak grad allocation stays at one tree.
    no_donate = replace_acc and gas > 1
    return jax.jit(
        fwd_bwd,
        donate_argnums=() if no_donate else (1,),
        out_shardings=(spec.grad_shardings, None),
    )


def build_apply(spec):
    update = guarded_update(spec)

    def apply_step(params, opt_state, acc_grads, ls_state, lr_factor):
        new_params, new_opt, new_ls, overflow, grad_norm = update(
            params, opt_state, acc_grads, ls_state, lr_factor)
        zero_acc = jax.tree.map(jnp.zeros_like, acc_grads)
        return new_params, new_opt, zero_acc, new_ls, overflow, grad_norm

    # an explicit exchange's outputs leave its shard_map already placed by
    # out_specs, and its programs have never named them again
    return jax.jit(
        apply_step,
        donate_argnums=(0, 1, 2),
        out_shardings=None if spec.exchange is not None else (
            spec.param_shardings, spec.opt_shardings, spec.grad_shardings,
            None, None, None),
    )


def build_train_step(spec):
    """Fused fwd+bwd+optimizer in ONE compiled program (used by
    train_batch when gas == 1): one dispatch instead of two, and XLA
    overlaps the optimizer update with the tail of the backward."""
    update = guarded_update(spec)
    per_group = spec.exchange is not None

    def train_step(params, opt_state, ls_state, batch, rng, step,
                   lr_factor):
        # at gas == 1 the quotient is the step; the per-group family has
        # always spelled it out, and with PLD on it is in the lowered text
        grads, loss = loss_and_grads(
            spec, "train_step", params, batch, rng, step,
            loss_scale=lambda: ls_state.scale,
            pld_step=lambda: step // spec.gas if per_group else step)
        new_params, new_opt, new_ls, overflow, grad_norm = update(
            params, opt_state, grads, ls_state, lr_factor)
        return new_params, new_opt, new_ls, loss, overflow, grad_norm

    return jax.jit(
        train_step,
        donate_argnums=(0, 1),
        # see build_apply
        out_shardings=None if spec.exchange is not None else (
            spec.param_shardings, spec.opt_shardings,
            None, None, None, None),
    )


def build_eval(model, rules):
    def eval_fn(params, batch):
        with gather_context(rules, "eval"):
            return model.apply({"params": params}, **batch,
                               deterministic=True)

    return jax.jit(eval_fn)
