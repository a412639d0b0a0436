"""Power retention (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239) at degree 2, in plain ``jax.numpy``.

Gated linear attention whose kernel is the even power ``(q . k)^2``. With
``phi(u)`` the symmetric square of ``u`` (``phi(q) . phi(k) = (q . k)^2``),
``g_t`` in (0, 1] the gate and, per KV head, ``S`` a ``[D, d]`` state and
``z`` a ``[D]`` normaliser (``D = d (d + 1) / 2`` entries that count):

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t) . z_t + eps)

which is, written over the tokens of one sequence (causal, ``j = t``
included),

    a[t, j] = exp(sum_{l=j+1..t} log g_l) (q_t . k_j)^2
    y_t = sum_j a[t, j] v_j / (sum_j a[t, j] + eps)

One set of equations, three entry shapes:

* :func:`retention_step`: the recurrence itself, one token;
* :func:`retention_chunked` from a given ``(S, z)``: a pass over many
  tokens in chunks (inside a chunk the masked ``a[t, j]`` matrix, which
  needs no ``phi``; between chunks the state is handed on);
* the same with ``fresh=True``: a pass that starts a sequence. Its first
  chunk has nothing to read from the state, so ``phi(q)`` is never formed
  there: a prompt that fits one chunk costs the quadratic form and one
  state build.

All arithmetic is float32 and the matmuls run at precision ``highest``:
``S`` and ``z`` are accumulators over the whole sequence. Query head ``i``
reads KV head ``i // (H // Hkv)``. A token whose ``k``, ``v`` are zero and
whose ``log g`` is zero (a pad) leaves ``S`` and ``z`` exactly as they are.

**How ``phi`` is stored** is the program's own (:func:`sympow2`,
:func:`sympow2_pairs`): ``d / 2 + 1`` rows of ``d`` entries, row ``r``
holding ``u_a u_{(a + r) mod d}`` for every ``a``, so that each row is ``u``
times a rotation of itself (a whole vector of lanes at ``d = 128``, no
gather) and the width ``(d / 2 + 1) d`` is a multiple of ``d``. Every
unordered pair appears once, off-diagonal ones times ``sqrt 2``; the last
row would hold its pairs twice, so its second half is dead: always zero,
``d / 2`` of the ``(d / 2 + 1) d`` stored entries (0.8% at ``d = 128``).
``S`` is stored ``[d, D]``, with ``phi``'s axis minor: ``phi(k)`` and
``phi(q)`` are then rows of the tiles the one-token kernel walks
(ops/pallas/retention_step.py).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_SQRT2 = math.sqrt(2.0)


def sympow2_width(d: int) -> int:
    """Stored entries of the symmetric square of a ``d``-vector, the dead
    ones included; ``d (d + 1) / 2`` of them are live."""
    if d % 2:
        raise ValueError(f"the symmetric square is laid out in rotations of "
                         f"an even head size; got {d}")
    return (d // 2 + 1) * d


def sympow2_pairs(d: int):
    """``(a, b, live)`` arrays ``[D]``: stored entry ``i`` of
    :func:`sympow2` is ``u[a[i]] * u[b[i]]`` (times ``sqrt 2`` where ``a !=
    b``) where ``live[i]``, and zero where not."""
    rows = sympow2_width(d) // d
    a = np.tile(np.arange(d), rows)
    shift = np.repeat(np.arange(rows), d)
    return a, (a + shift) % d, (shift < d // 2) | (a < d // 2)


def sympow2(u):
    """``phi(u)``: ``[..., d]`` float32 -> ``[..., (d / 2 + 1) d]``. Every
    rotation of ``u`` at once, as one product with a 0/1 matrix on the
    matrix unit (each entry is a single term, so it is exact at precision
    ``highest``): ``d / 2`` separate rotations are as many small kernels a
    layer on the chip, and a second of tracing a program on its host."""
    d = u.shape[-1]
    D = sympow2_width(d)
    u = u.astype(jnp.float32)
    i = jnp.arange(D)
    a, r = i % d, i // d
    pick = (jnp.arange(d)[:, None] == ((a + r) % d)[None, :])
    rotated = jnp.dot(u, pick.astype(jnp.float32), precision=_HIGHEST)
    coef = jnp.where(r == 0, 1.0, _SQRT2) \
        * ((r < d // 2) | (a < d // 2)).astype(jnp.float32)
    return jnp.tile(u, D // d) * rotated * coef


def _step_inputs(q, k, v, log_g):
    """``(g [B, Hkv], v, phi(k) [B, Hkv, D], phi(q) [B, Hkv, G, D])``, all
    float32."""
    f32 = jnp.float32
    B, H, _ = q.shape
    Hkv = k.shape[1]
    phik = sympow2(k)
    phiq = sympow2(q).reshape(B, Hkv, H // Hkv, -1)
    return jnp.exp(log_g.astype(f32)), v.astype(f32), phik, phiq


def retention_step(S, z, q, k, v, log_g, eps):
    """One token. ``S`` ``[B, Hkv, d, D]``, ``z`` ``[B, Hkv, D]``, ``q``
    ``[B, H, d]``, ``k`` / ``v`` ``[B, Hkv, d]``, ``log_g`` ``[B, Hkv]``.
    Returns ``(y [B, H, d] float32, S, z)``."""
    B, d = S.shape[0], S.shape[2]
    f32 = jnp.float32
    g, v, phik, phiq = _step_inputs(q, k, v, log_g)
    S = S.astype(f32) * g[..., None, None] \
        + v[..., :, None] * phik[..., None, :]
    z = z.astype(f32) * g[..., None] + phik
    num = jnp.einsum("bhgs,bhds->bhgd", phiq, S, precision=_HIGHEST)
    den = jnp.sum(phiq * z[:, :, None, :], axis=-1)          # [B, Hkv, G]
    y = num / (den[..., None] + eps)
    return y.reshape(B, -1, d), S, z


def retention_step_stacked(S, z, layer, q, k, v, log_g, eps):
    """:func:`retention_step` on layer ``layer`` of the stacked ``[n_layer,
    B, Hkv, d, D]`` / ``[n_layer, B, Hkv, D]`` leaves (or on one layer's
    with ``layer`` None), through the kernel that reads and writes each
    lane's ``S`` once, where it lies (ops/pallas/retention_step.py). ``S``
    and ``z`` keep their dtypes. Returns ``(y, S, z)`` with the WHOLE
    leaves, this layer replaced."""
    from deepspeed_tpu.ops.pallas.retention_step import retention_step_update

    B, _, d = q.shape
    g, v, phik, phiq = _step_inputs(q, k, v, log_g)
    z_old = z if layer is None else jax.lax.dynamic_index_in_dim(
        z, layer, 0, keepdims=False)
    z_new = z_old.astype(jnp.float32) * g[..., None] + phik
    den = jnp.sum(phiq * z_new[:, :, None, :], axis=-1)
    S, num = retention_step_update(S, layer, g, v, phik, phiq)
    z_new = z_new.astype(z.dtype)
    z = z_new if layer is None else \
        jax.lax.dynamic_update_index_in_dim(z, z_new, layer, 0)
    return (num / (den[..., None] + eps)).reshape(B, -1, d), S, z


def _one_chunk(S, z, q, k, v, lg, eps, cross):
    """A chunk of ``Q`` tokens from ``(S, z)``: ``q`` ``[B, Q, Hkv, G, d]``,
    ``k`` / ``v`` ``[B, Q, Hkv, d]``, ``lg`` ``[B, Q, Hkv]``. ``cross``
    False says that ``S`` and ``z`` are zero."""
    Q = q.shape[1]
    cum = jnp.cumsum(lg, axis=1)                             # [B, Q, Hkv]
    # what is left at t of what entered at j <= t; masked BEFORE the
    # exponential: above the diagonal the difference is positive
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    diff = cum[:, :, None, :] - cum[:, None, :, :]           # [B, t, j, Hkv]
    decay = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
    qk = jnp.einsum("bthgd,bjhd->bhgtj", q, k, precision=_HIGHEST)
    a = qk * qk * jnp.moveaxis(decay, 3, 1)[:, :, None]      # [B,Hkv,G,t,j]
    num = jnp.einsum("bhgtj,bjhd->bthgd", a, v, precision=_HIGHEST)
    den = jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)            # [B,t,Hkv,G]
    phik = sympow2(k)                                        # [B, Q, Hkv, D]
    left = jnp.exp(cum[:, -1:, :] - cum)                     # [B, Q, Hkv]
    own_S = jnp.einsum("bjhs,bjhd->bhds", phik * left[..., None], v,
                       precision=_HIGHEST)
    own_z = jnp.sum(phik * left[..., None], axis=1)          # [B, Hkv, D]
    if cross:
        phiq = sympow2(q)                                    # [B,Q,Hkv,G,D]
        since = jnp.exp(cum)[..., None]                      # [B, Q, Hkv, 1]
        num = num + since[..., None] * jnp.einsum(
            "bthgs,bhds->bthgd", phiq, S, precision=_HIGHEST)
        den = den + since * jnp.einsum(
            "bthgs,bhs->bthg", phiq, z, precision=_HIGHEST)
        end = jnp.exp(cum[:, -1, :])                         # [B, Hkv]
        own_S = own_S + end[..., None, None] * S
        own_z = own_z + end[..., None] * z
    return num / (den[..., None] + eps), own_S, own_z


def retention_chunked(S, z, q, k, v, log_g, eps, chunk, fresh=False):
    """A pass over ``T`` tokens from ``(S, z)``.

    ``S`` ``[B, Hkv, d, D]``, ``z`` ``[B, Hkv, D]``, ``q`` ``[B, T, H, d]``,
    ``k`` / ``v`` ``[B, T, Hkv, d]``, ``log_g`` ``[B, T, Hkv]``. ``T`` is
    padded up to a multiple of ``chunk`` with tokens of zero ``k``, ``v``
    and ``log g``, which neither decay nor feed the state. ``fresh`` says
    that ``S`` and ``z`` are zero (a pass that starts a sequence): the
    first chunk then reads nothing from them. Returns ``(y [B, T, H, d]
    float32, S, z)`` after token ``T - 1``."""
    B, T, H, d = q.shape
    Hkv = k.shape[2]
    f32 = jnp.float32
    chunk = min(chunk, T)
    pad = (-T) % chunk

    def grow(t):
        t = t.astype(f32)
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        n = (T + pad) // chunk
        # [B, T', ...] -> [n, B, Q, ...]
        return jnp.moveaxis(t.reshape((B, n, chunk) + t.shape[2:]), 1, 0)

    qs = grow(q.reshape(B, T, Hkv, H // Hkv, d))
    ks, vs, ls = grow(k), grow(v), grow(log_g)
    S, z = S.astype(f32), z.astype(f32)
    ys = []
    if fresh:
        y0, S, z = _one_chunk(S, z, qs[0], ks[0], vs[0], ls[0], eps, False)
        ys.append(y0[None])
        qs, ks, vs, ls = qs[1:], ks[1:], vs[1:], ls[1:]
    if qs.shape[0]:
        def body(carry, c):
            y, S, z = _one_chunk(*carry, *c, eps, True)
            return (S, z), y

        (S, z), rest = jax.lax.scan(body, (S, z), (qs, ks, vs, ls))
        ys.append(rest)
    y = jnp.moveaxis(jnp.concatenate(ys, 0), 0, 1)           # [B, n, Q, ...]
    return y.reshape(B, T + pad, H, d)[:, :T], S, z
