"""Top-k gating + dispatch/combine math for Mixture-of-Experts.

Capability parity with reference ``deepspeed/moe/sharded_moe.py`` (top1gating
:177, top2gating :278, MOELayer :439): softmax gating with static capacity,
load-balancing auxiliary loss, random token selection (RTS), gumbel-noise
second-expert choice, einsum dispatch/combine.

TPU re-design notes:

* Capacity is computed at TRACE time from the static token count — XLA needs
  static shapes, and the reference's ``drop_tokens=False`` dynamic capacity
  (all-reduced max) becomes "capacity = all tokens" here (worst case, static).
* The reference's ``_AllToAll`` autograd function + expert process groups
  collapse into a sharding constraint: dispatched tensors are laid out
  ``[experts, capacity, model]`` and annotated with PartitionSpec("ep", ...);
  GSPMD inserts the all-to-all (and its transpose in the backward) itself.
* Everything is differentiable exactly where the reference is: gradients flow
  through the gate probabilities in combine_weights and through l_aux; the
  argmax/top-k index paths are non-differentiable in both.
"""

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class GatingOutput(NamedTuple):
    l_aux: jnp.ndarray           # scalar load-balance loss
    combine_weights: jnp.ndarray  # [tokens, experts, capacity] float
    dispatch_mask: jnp.ndarray    # [tokens, experts, capacity] bool
    exp_counts: jnp.ndarray       # [experts] int32 — tokens routed per expert


def static_capacity(num_tokens: int, num_experts: int, capacity_factor: float,
                    min_capacity: int) -> int:
    """Static per-expert capacity (reference sharded_moe.py:155 _capacity).

    Python math on static shapes so the jitted program has fixed buffers.
    """
    capacity = int(np.ceil((num_tokens / num_experts) * capacity_factor))
    capacity = max(capacity, min_capacity)
    return min(capacity, num_tokens)


def _gumbel(rng, shape):
    return jax.random.gumbel(rng, shape, dtype=jnp.float32)


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.int32)


def top1_gating(
    logits: jnp.ndarray,
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    rng: Optional[jax.Array] = None,
    noisy_gate_policy: Optional[str] = None,
    drop_tokens: bool = True,
    use_rts: bool = True,
    used_token: Optional[jnp.ndarray] = None,
) -> GatingOutput:
    """Top-1 (Switch) gating (reference sharded_moe.py:177).

    ``rng`` drives RSample noise and random-token-selection; pass None for
    deterministic eval (noise and RTS are skipped, matching the reference's
    behaviour when no stochastic path is active).
    """
    logits = logits.astype(jnp.float32)
    num_tokens, num_experts = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)

    if drop_tokens:
        capacity = static_capacity(num_tokens, num_experts, capacity_factor,
                                   min_capacity)
    else:
        capacity = num_tokens  # static worst case (reference all-reduces a max)

    if noisy_gate_policy == "RSample" and rng is not None:
        rng, sub = jax.random.split(rng)
        indices1 = jnp.argmax(logits + _gumbel(sub, logits.shape), axis=-1)
    else:
        indices1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(indices1, num_experts)
    if used_token is not None:
        mask1 = mask1 * used_token[:, None].astype(mask1.dtype)

    exp_counts = jnp.sum(mask1, axis=0).astype(jnp.int32)

    # load-balance loss (reference :218): mean(gate_prob) . mean(assignment)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1.astype(jnp.float32), axis=0)
    l_aux = jnp.sum(me * ce) * num_experts

    if use_rts and rng is not None:
        # Random Token Selection (reference :227): random priority per routed
        # token, keep the top-`capacity` per expert
        rng, sub = jax.random.split(rng)
        priority = mask1.astype(jnp.float32) * jax.random.uniform(
            sub, mask1.shape, dtype=jnp.float32
        )
        _, top_idx = jax.lax.top_k(priority.T, capacity)  # [E, C] token ids
        keep = jnp.zeros((num_experts, num_tokens), jnp.int32)
        keep = jax.vmap(lambda row, idx: row.at[idx].set(1))(keep, top_idx)
        mask1 = mask1 * keep.T
        locations1 = jnp.cumsum(mask1, axis=0) - 1
    else:
        # deterministic: first-come-first-served by position (stable top-k)
        locations1 = jnp.cumsum(mask1, axis=0) - 1
        mask1 = mask1 * (locations1 < capacity).astype(mask1.dtype)

    locations1_s = jnp.sum(locations1 * mask1, axis=-1)

    gates = gates * mask1.astype(jnp.float32)
    locations1_sc = jax.nn.one_hot(locations1_s, capacity, dtype=jnp.float32)
    combine = jnp.einsum("te,tc->tec", gates, locations1_sc)
    # zero out dropped tokens' capacity rows (one_hot(0) would alias slot 0)
    combine = combine * mask1[..., None].astype(jnp.float32)
    dispatch = combine > 0
    return GatingOutput(l_aux, combine, dispatch, exp_counts)


def top2_gating(
    logits: jnp.ndarray,
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    rng: Optional[jax.Array] = None,
) -> GatingOutput:
    """Top-2 (GShard) gating (reference sharded_moe.py:278): second expert via
    gumbel-max over the non-top logits, combined weights renormalized over the
    two selected experts."""
    logits = logits.astype(jnp.float32)
    num_tokens, num_experts = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)
    capacity = static_capacity(num_tokens, num_experts, 2.0 * capacity_factor,
                               min_capacity)

    indices1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(indices1, num_experts)

    if rng is not None:
        logits_w_noise = logits + _gumbel(rng, logits.shape)
    else:
        logits_w_noise = logits
    logits_except1 = jnp.where(mask1.astype(bool), -jnp.inf, logits_w_noise)
    indices2 = jnp.argmax(logits_except1, axis=-1)
    mask2 = _one_hot(indices2, num_experts)

    locations1 = jnp.cumsum(mask1, axis=0) - 1
    locations2 = jnp.cumsum(mask2, axis=0) - 1
    locations2 = locations2 + jnp.sum(mask1, axis=0, keepdims=True)

    exp_counts = jnp.sum(mask1, axis=0).astype(jnp.int32)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1.astype(jnp.float32), axis=0)
    l_aux = jnp.mean(me * ce) * num_experts * num_experts

    mask1 = mask1 * (locations1 < capacity).astype(mask1.dtype)
    mask2 = mask2 * (locations2 < capacity).astype(mask2.dtype)

    locations1_s = jnp.sum(locations1 * mask1, axis=-1)
    locations2_s = jnp.sum(locations2 * mask2, axis=-1)

    mask1_f = mask1.astype(jnp.float32)
    mask2_f = mask2.astype(jnp.float32)
    gates1_s = jnp.einsum("te,te->t", gates, mask1_f)
    gates2_s = jnp.einsum("te,te->t", gates, mask2_f)
    denom = jnp.maximum(gates1_s + gates2_s, jnp.finfo(jnp.float32).eps)
    gates1_s = gates1_s / denom
    gates2_s = gates2_s / denom

    gates1 = jnp.einsum("t,te->te", gates1_s, mask1_f)
    gates2 = jnp.einsum("t,te->te", gates2_s, mask2_f)
    loc1_sc = jax.nn.one_hot(locations1_s, capacity, dtype=jnp.float32)
    loc2_sc = jax.nn.one_hot(locations2_s, capacity, dtype=jnp.float32)
    combine = (
        jnp.einsum("te,tc->tec", gates1, loc1_sc) * mask1_f[..., None]
        + jnp.einsum("te,tc->tec", gates2, loc2_sc) * mask2_f[..., None]
    )
    dispatch = combine > 0
    return GatingOutput(l_aux, combine, dispatch, exp_counts)


def topk_gating(logits, k: int, **kwargs) -> GatingOutput:
    if k == 1:
        return top1_gating(logits, **kwargs)
    if k == 2:
        # these knobs only exist on the top-1 path (as in the reference, where
        # top2gating takes no noise/RTS/drop arguments) — reject non-defaults
        # rather than silently changing routing behaviour
        unsupported = {
            "noisy_gate_policy": None, "drop_tokens": True,
            "use_rts": True, "used_token": None,
        }
        for name, default in unsupported.items():
            if name in kwargs and kwargs.pop(name) != default:
                raise ValueError(
                    f"top-2 gating does not support {name} "
                    "(top-1-only option, see reference sharded_moe.py:278)"
                )
        return top2_gating(logits, **kwargs)
    raise ValueError(
        f"capacity gating supports only top-1 and top-2, got k={k}; "
        "any k routes droplessly through topk_routing + sort_by_expert")


def dispatch_tokens(dispatch_mask: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """[T,E,C] bool x [T,M] -> [E,C,M] (reference MOELayer einsum "sec,sm->ecm",
    sharded_moe.py:439 forward). MXU-friendly: a single batched matmul."""
    return jnp.einsum("tec,tm->ecm", dispatch_mask.astype(x.dtype), x)


def combine_tokens(combine_weights: jnp.ndarray, expert_out: jnp.ndarray,
                   dtype=None) -> jnp.ndarray:
    """[T,E,C] x [E,C,M] -> [T,M] (reference einsum "sec,ecm->sm")."""
    y = jnp.einsum(
        "tec,ecm->tm", combine_weights,
        expert_out.astype(combine_weights.dtype),
    )
    return y.astype(dtype) if dtype is not None else y


# ---------------------------------------------------------------------------
# dropless routing: sort the (token, expert) pairs by expert, gather their
# rows, one grouped matmul per projection over the ragged groups
# (moe/experts.py), weighted sum back. No capacity: every pair is computed,
# whatever the load per expert, and every shape is static (tokens * k rows).
# ---------------------------------------------------------------------------
class RoutingOutput(NamedTuple):
    l_aux: jnp.ndarray       # scalar load-balancing loss
    l_z: jnp.ndarray         # scalar router z-loss (router_z_loss)
    weights: jnp.ndarray     # [tokens, k] float32 combine weights
    experts: jnp.ndarray     # [tokens, k] int32 chosen experts
    exp_counts: jnp.ndarray  # [experts] int32 pairs routed per expert
    # with a correction bias: scalar int32, the tokens whose chosen set is
    # not the k largest uncorrected scores
    bias_changed: Optional[jnp.ndarray] = None


def router_z_loss(logits: jnp.ndarray) -> jnp.ndarray:
    """The mean squared log-partition of the router logits (ST-MoE's
    z-loss): it keeps the logits small, whatever the path that routes."""
    return jnp.mean(jnp.square(
        jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)))


def topk_routing(logits: jnp.ndarray, k: int, renormalize: bool = False,
                 n_group: int = 1, topk_group: int = 1,
                 scale: float = 1.0, scoring: str = "softmax",
                 bias: Optional[jnp.ndarray] = None,
                 renorm_eps: Optional[float] = None) -> RoutingOutput:
    """Softmax over all experts, then the k largest probabilities per token
    (any k). ``renormalize`` divides the k weights by their sum
    (``norm_topk_prob``); without it they are the softmax's own values and
    sum to less than one. ``scale`` multiplies them last
    (``routed_scaling_factor``).

    ``scoring="sigmoid"`` scores each expert by the sigmoid of its own
    logit (LFM2, DeepSeek-V3); the k weights, renormalised, are then
    ``s_i / (sum of the chosen s + 1e-6)`` as those models publish it
    (``renorm_eps`` where a model publishes another: AFMoE's 1e-20).
    ``bias`` ``[experts]`` (a parameter of the layer that a balancing rule
    moves apart from the weights' training) is added to the scores for
    the CHOICE and for nothing else: the k weights are the uncorrected
    scores of the experts chosen. ``bias_changed`` then counts the tokens
    whose chosen set is not the k largest uncorrected scores.

    With ``n_group > 1`` the choice is group-limited (DeepSeek-V2's
    ``group_limited_greedy``, its device-limited routing): the experts are
    ``n_group`` consecutive runs, a group's score is its largest
    probability, and only the experts of the ``topk_group`` best groups
    can be chosen (the others' probabilities count as 0 in the top-k; the
    chosen weights are still the softmax's over all experts). Ties go to
    the lower index, in the groups and in the experts (``lax.top_k``'s
    rule).

    ``l_aux`` is the load-balancing loss ``E * sum_i f_i * P_i`` with
    ``f_i`` the share of the tokens * k pairs routed to expert i and ``P_i``
    the mean probability of expert i (1 when perfectly balanced, and
    ``top1_gating``'s at k = 1); ``l_z`` is :func:`router_z_loss`.
    Gradients flow through the probabilities; the choice of experts is not
    differentiable."""
    logits = logits.astype(jnp.float32)
    num_tokens, num_experts = logits.shape
    sigmoid = scoring == "sigmoid"
    if n_group > 1 and (sigmoid or bias is not None):
        raise ValueError("group-limited routing is written for "
                         "uncorrected softmax scores")
    probs = _scores(logits, sigmoid)
    eligible = probs
    if n_group > 1:
        per_group = num_experts // n_group
        _, best = jax.lax.top_k(
            probs.reshape(num_tokens, n_group, per_group).max(-1),
            topk_group)
        keep = jnp.sum(jax.nn.one_hot(best, n_group, dtype=jnp.int32),
                       axis=1) > 0                       # [tokens, groups]
        eligible = jnp.where(jnp.repeat(keep, per_group, axis=1), probs, 0.0)
    changed = None
    if bias is None:
        weights, experts = jax.lax.top_k(eligible, k)
    else:
        _, experts = jax.lax.top_k(eligible + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
        # the chosen set is the uncorrected one exactly when it holds the
        # k largest scores, that is when its least is their k-th
        changed = jnp.sum(jnp.min(weights, axis=-1)
                          < jax.lax.top_k(probs, k)[0][:, -1],
                          dtype=jnp.int32)
    if renormalize:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (
            total + (1e-6 if renorm_eps is None else renorm_eps) if sigmoid
            else jnp.maximum(total, jnp.finfo(jnp.float32).eps))
    if scale != 1.0:
        weights = weights * scale
    exp_counts = jnp.bincount(experts.reshape(-1), length=num_experts)
    f = exp_counts.astype(jnp.float32) / (num_tokens * k)
    # (of sigmoid scores, a token's shares of their sum)
    shares = probs / jnp.sum(probs, axis=-1, keepdims=True) if sigmoid \
        else probs
    l_aux = num_experts * jnp.sum(f * jnp.mean(shares, axis=0))
    return RoutingOutput(l_aux, router_z_loss(logits), weights,
                         experts.astype(jnp.int32),
                         exp_counts.astype(jnp.int32), changed)


def _scores(logits, sigmoid: bool):
    """Each expert's score for each token, float32: the softmax over the
    experts, or the sigmoid of the expert's own logit."""
    return jax.nn.sigmoid(logits) if sigmoid \
        else jax.nn.softmax(logits, axis=-1)


def sort_by_expert(experts: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(order, inverse)`` of the tokens * k (token, expert) pairs: row r
    of the sorted layout holds pair ``order[r]`` (token ``order[r] // k``),
    pair p sits at row ``inverse[p]``. Stable, so one expert's rows keep
    their token order."""
    order = jnp.argsort(experts.reshape(-1), stable=True).astype(jnp.int32)
    return order, jnp.argsort(order).astype(jnp.int32)


# The crossover of the row-fetch kernels (ops/pallas/row_fetch.py): a layer
# that holds a share of its experts moves its live rows through them where
# it sorts at least this many pairs. Measured INSIDE the step program of
# ``smallthinker-21b-train-16k`` on the v5e (98,304 pairs of 2,560 bf16,
# 42-50% live: `moe_dispatch_share_of_step` 27.2 -> 12.2, chiprun_out/p64d
# to p64f, PERF.md section 6, PR 64) and alone at that shape and at 65,536
# pairs of 2,048 (chiprun_out/p64b); no program under it was measured with
# the kernels, and the serving programs' passes stay on XLA's gather
# whatever they sort (:func:`fetches_live_rows`)
ROW_FETCH_MIN_PAIRS = 32768


def fetches_live_rows(tokens: int, k: int, width: int, dtype) -> bool:
    """Whether a dropless layer that holds a share of its experts, over
    ``tokens`` tokens of ``width`` and ``k`` experts a token, moves its
    live rows alone (those routed to the experts it holds), through the
    row-fetch kernels (``n_live`` given to
    :func:`dispatch_rows` and :func:`combine_rows`): at least
    :data:`ROW_FETCH_MIN_PAIRS` pairs, shapes the kernels take
    (``row_fetch.supported``), the rows whole on one device (a Pallas call
    is opaque to the partitioner), and a call that something may
    differentiate: a serving call keeps the gather it was lowered with
    (moe/experts.py ``serving_call``: a long prompt's pass sorts more
    pairs than a training step, and its programs are held to their text).
    Static shapes and what the scan's owner says of the call, no option."""
    if tokens * k < ROW_FETCH_MIN_PAIRS:
        return False
    from deepspeed_tpu.moe.experts import serving_call
    from deepspeed_tpu.ops.pallas import row_fetch
    from deepspeed_tpu.parallel.mesh import get_default_topology

    topo = get_default_topology()
    return (not serving_call() and topo.size("ep") == 1
            and topo.size("tp") == 1
            and row_fetch.supported(tokens, k, width, dtype))


def dispatch_rows(tokens, order, inverse, k, n_live=None, zero_to=None):
    """[T, M] -> [T * k, M]: the rows of the pairs in sorted order (a gather
    by ``order // k``). Its transpose is a scatter-add of k rows into each
    token; ``inverse`` turns that into a gather and a sum over k, which the
    TPU does at memory speed where it serialises a scatter.

    With ``n_live`` (an int32 scalar: the sorted rows before it are the
    live ones, ``sum(group_sizes)``) the rows are moved by the row-fetch
    kernels, which issue a copy for a live row alone: the rows from
    ``n_live`` to the end of the block of ``zero_to`` rows (the consumer's
    row tile) that holds row ``n_live`` are zeros, those past it are NOT
    WRITTEN, and the transpose sums the live pairs of a token alone."""
    if n_live is None:
        return _take_rows(tokens, order, inverse, k)
    return _fetch_dispatch(tokens, order, inverse, n_live, k, zero_to)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(tokens, order, inverse, k):
    return jnp.take(tokens, order // k, axis=0)


def _take_rows_fwd(tokens, order, inverse, k):
    return _take_rows(tokens, order, inverse, k), inverse


def _take_rows_bwd(k, inverse, g):
    pairs = jnp.take(g, inverse, axis=0).reshape(-1, k, g.shape[-1])
    return (jnp.sum(pairs.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fetch_dispatch(tokens, order, inverse, n_live, k, zero_to):
    from deepspeed_tpu.ops.pallas import row_fetch

    return row_fetch.fetch_rows(tokens, order // k, n_live, zero_to=zero_to)


def _fetch_dispatch_fwd(tokens, order, inverse, n_live, k, zero_to):
    return (_fetch_dispatch(tokens, order, inverse, n_live, k, zero_to),
            (inverse, n_live))


def _fetch_dispatch_bwd(k, zero_to, residuals, g):
    from deepspeed_tpu.ops.pallas import row_fetch

    inverse, n_live = residuals
    return (row_fetch.fetch_sum_rows(
        row_fetch.as_words(g, n_live), inverse.reshape(-1, k), None, n_live,
        dtype=g.dtype), None, None, None)


_fetch_dispatch.defvjp(_fetch_dispatch_fwd, _fetch_dispatch_bwd)


@jax.custom_vjp
def unsort_rows(rows, order, inverse):
    """[T * k, M] sorted rows -> pair order (row t * k + j is token t's
    j-th expert): a permutation, so its transpose is the gather by
    ``order``."""
    return jnp.take(rows, inverse, axis=0)


def _unsort_fwd(rows, order, inverse):
    return unsort_rows(rows, order, inverse), order


def _unsort_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


unsort_rows.defvjp(_unsort_fwd, _unsort_bwd)


def combine_rows(rows, weights, order, inverse, dtype=None, n_live=None,
                 zero_to=None):
    """The weighted scatter-add of the expert outputs back onto their
    tokens, ``out[t] = sum_j weights[t, j] * rows[inverse[t * k + j]]``,
    as a gather and a sum over k in float32.

    With ``n_live`` (:func:`dispatch_rows`) through the row-fetch kernels:
    the sum is over the pairs whose row lies before ``n_live``, in the
    order j = 0 .. k-1, and no row from ``n_live`` on is read; the rows'
    gradient is written as :func:`dispatch_rows` writes its rows (zeros to
    ``zero_to``, nothing past it), a pair elsewhere gets no weight
    gradient."""
    num_tokens, k = weights.shape
    dtype = jnp.dtype(dtype or rows.dtype)
    if n_live is not None:
        return _fetch_combine(rows, weights, order, inverse, n_live, dtype,
                              zero_to)
    pairs = unsort_rows(rows, order, inverse).reshape(num_tokens, k, -1)
    out = jnp.sum(pairs.astype(jnp.float32) * weights[..., None], axis=1)
    return out.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fetch_combine(rows, weights, order, inverse, n_live, dtype, zero_to):
    return _fetch_combine_fwd(rows, weights, order, inverse, n_live, dtype,
                              zero_to)[0]


def _fetch_combine_fwd(rows, weights, order, inverse, n_live, dtype, zero_to):
    from deepspeed_tpu.ops.pallas import row_fetch

    # the rows as the copies read them are the residual, in the rows' place
    words = row_fetch.as_words(rows, n_live)
    out = row_fetch.fetch_sum_rows(words, inverse.reshape(weights.shape),
                                   weights, n_live, dtype=dtype)
    return out, (words, weights, order, inverse, n_live)


def _fetch_combine_bwd(dtype, zero_to, residuals, g):
    from deepspeed_tpu.ops.pallas import row_fetch

    words, weights, order, inverse, n_live = residuals
    k = weights.shape[1]
    # (bf16 rows are read as uint32 words, float32 rows as themselves)
    rows_dtype = jnp.bfloat16 if words.dtype == jnp.uint32 else words.dtype
    d_rows = row_fetch.fetch_rows(
        g, order // k, n_live, scale=jnp.take(weights.reshape(-1), order),
        zero_to=zero_to).astype(rows_dtype)
    d_weights = row_fetch.fetch_dot_rows(
        words, inverse.reshape(weights.shape), g, n_live)
    return d_rows, d_weights.astype(weights.dtype), None, None, None


_fetch_combine.defvjp(_fetch_combine_fwd, _fetch_combine_bwd)


def rows_computed(rows, experts, order, num_experts):
    """[experts] int32: per expert, the sorted rows that came out of the
    grouped matmuls with an output. A row they skip (``group_sizes`` that
    do not cover it) is zeros, which an expert's output for a real row is
    not; so this counts what was computed from the output itself and not
    from the routing that asked for it."""
    written = jnp.any(rows != 0, axis=-1).astype(jnp.int32)
    return jnp.bincount(experts.reshape(-1)[order], weights=written,
                        length=num_experts)

