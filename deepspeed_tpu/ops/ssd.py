"""The state-space duality (Mamba-2) operations: plain ``jax.numpy`` but for
the served decode step's pass over the state, which is a Pallas kernel.

One set of equations, four entry shapes. With ``S`` a head's ``[P, N]``
state, ``a_t = dt_t * A`` (``A < 0``):

    S_t = exp(a_t) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D * x_t

* :func:`causal_conv1d`: the depthwise causal convolution in front of the
  recurrence, continued from the ``width - 1`` inputs before the pass
  (zeros for a pass that starts a sequence), returning those of the next;
* :func:`ssd_chunked_scan`: a pass over many tokens from a given state, in
  chunks (inside a chunk the recurrence is a masked matmul, between chunks
  a state is handed on), returning every ``y_t`` and the state after the
  last token;
* :func:`ssd_step`: the recurrence itself for one token, the plain form;
* :func:`ssd_step_stacked`: the same on one layer of the stacked state leaf
  where it lies, each lane's state read once and written once by the kernel
  ``ssm_step`` (ops/pallas/ssd_step.py). A served decode step of one token
  takes this one; it falls back to :func:`ssd_step` on a slice where heads
  are sharded over ``tp`` (GSPMD cannot partition a Mosaic call), and a
  pass of more than one token over a cache (verification, a chunked
  prefill's continuation) is the chunked scan
  (models/transformer_lm.py ``step_kernel``, models/mamba2.py).

All arithmetic is float32; the matmuls of the chunked form run at
precision ``highest`` (the state is an accumulator over the whole
sequence, and the chunked form's work is small beside the projections').
Heads are grouped: head ``h`` reads ``B`` and ``C`` of group
``h // (H // G)``.
"""
import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def causal_conv1d(x, weight, bias, tail):
    """Depthwise causal convolution over time.

    ``x`` ``[B, T, C]``, ``weight`` ``[K, C]`` (``weight[K - 1]`` multiplies
    the current token), ``bias`` ``[C]`` (None: no bias, the gated short
    convolution's form, models/short_conv.py), ``tail`` ``[B, K - 1, C]``
    the inputs before the pass. Returns ``(y [B, T, C] float32, the next
    tail [B, K - 1, C] in tail's dtype)``."""
    K = weight.shape[0]
    T = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, T+K-1, C]
    w = weight.astype(jnp.float32)
    y = sum(ext[:, k:k + T].astype(jnp.float32) * w[k] for k in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y, ext[:, T:].astype(tail.dtype)


def ssd_step(state, x, dt, A, Bm, Cm, D):
    """One token. ``state`` ``[B, H, P, N]`` float32, ``x`` ``[B, H, P]``,
    ``dt`` ``[B, H]`` (after softplus), ``A`` ``[H]``, ``Bm`` / ``Cm``
    ``[B, G, N]``, ``D`` ``[H]``. Returns ``(y [B, H, P], new state)``."""
    B_, H, P, N = state.shape
    G = Bm.shape[1]
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))                     # [B, H]
    st = state.reshape(B_, G, H // G, P, N)
    xdt = (x * dt[..., None]).reshape(B_, G, H // G, P)
    new = (st * decay.reshape(B_, G, H // G, 1, 1)
           + xdt[..., None] * Bm.astype(f32)[:, :, None, None, :])
    y = jnp.sum(new * Cm.astype(f32)[:, :, None, None, :], axis=-1)
    y = y.reshape(B_, H, P) + D.astype(f32)[None, :, None] * x
    return y, new.reshape(B_, H, P, N)


def ssd_step_stacked(state, layer, x, dt, A, Bm, Cm, D):
    """:func:`ssd_step` on layer ``layer`` of the stacked ``[n_layer, B, H,
    P, N]`` leaf (or on one layer's with ``layer`` None), through the
    kernel that reads and writes each lane's state once, where it lies
    (ops/pallas/ssd_step.py). The state keeps its dtype; the arithmetic is
    :func:`ssd_step`'s in float32, but for the order of the sum over ``N``.
    Returns ``(y [B, H, P], the WHOLE leaf with this layer replaced)``."""
    from deepspeed_tpu.ops.pallas.ssd_step import ssm_step_update

    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))                     # [B, H]
    state, y = ssm_step_update(state, layer, decay, x * dt[..., None],
                               Bm, Cm)
    return y + D.astype(f32)[None, :, None] * x, state


def ssd_chunked_scan(state, x, dt, A, Bm, Cm, D, chunk):
    """A pass over ``T`` tokens from ``state``.

    ``state`` ``[B, H, P, N]`` float32, ``x`` ``[B, T, H, P]``, ``dt``
    ``[B, T, H]`` (after softplus), ``A`` ``[H]``, ``Bm`` / ``Cm``
    ``[B, T, G, N]``, ``D`` ``[H]``. ``T`` is padded up to a multiple of
    ``chunk`` with ``dt = 0`` tokens, which neither decay nor feed the
    state. Returns ``(y [B, T, H, P] float32, the state after token
    T - 1)``."""
    B_, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R = H // G
    f32 = jnp.float32
    pad = (-T) % chunk
    if pad:
        def grow(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

        x, dt, Bm, Cm = grow(x), grow(dt), grow(Bm), grow(Cm)
    n_chunks = (T + pad) // chunk
    x, dt = x.astype(f32), dt.astype(f32)
    a = dt * A.astype(f32)                                   # [B, T', H]

    def chunks(t):      # [B, T', ...] -> [nC, B, Q, ...]
        return jnp.moveaxis(
            t.reshape((B_, n_chunks, chunk) + t.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(S, c):
        xc, dtc, ac, Bc, Cc = c        # [B,Q,H,P] [B,Q,H] [B,Q,H] [B,Q,G,N]x2
        cum = jnp.cumsum(ac, axis=1)                         # [B, Q, H]
        # L[l, s] = exp(cum[l] - cum[s]) for s <= l: what is left at l of
        # what entered at s. Masked before the exponential: above the
        # diagonal the difference is positive and may overflow.
        diff = cum[:, :, None, :] - cum[:, None, :, :]       # [B, l, s, H]
        L = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
        CB = jnp.einsum("blgn,bsgn->blsg", Cc.astype(f32), Bc.astype(f32),
                        precision=_HIGHEST)                  # [B, l, s, G]
        xdt = xc * dtc[..., None]                            # [B, Q, H, P]
        M = jnp.moveaxis(L, 3, 1).reshape(B_, G, R, chunk, chunk) \
            * jnp.moveaxis(CB, 3, 1)[:, :, None]             # [B, G, R, l, s]
        y_diag = jnp.einsum("bgrls,bsgrp->blgrp", M,
                            xdt.reshape(B_, chunk, G, R, P),
                            precision=_HIGHEST)
        # what the chunk's own tokens leave in the state at its end
        left = jnp.exp(cum[:, -1:, :] - cum)                 # [B, Q, H]
        own = jnp.einsum("bsgn,bsgrp->bgrpn", Bc.astype(f32),
                         (xdt * left[..., None]).reshape(
                             B_, chunk, G, R, P), precision=_HIGHEST)
        Sg = S.reshape(B_, G, R, P, N)
        y_off = jnp.einsum("blgn,bgrpn->blgrp", Cc.astype(f32), Sg,
                           precision=_HIGHEST) \
            * jnp.exp(cum).reshape(B_, chunk, G, R)[..., None]
        S_next = Sg * jnp.exp(cum[:, -1, :]).reshape(B_, G, R, 1, 1) + own
        y = (y_diag + y_off).reshape(B_, chunk, H, P)
        return S_next.reshape(B_, H, P, N), y

    S, ys = jax.lax.scan(
        body, state.astype(f32),
        (chunks(x), chunks(dt), chunks(a), chunks(Bm), chunks(Cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B_, T + pad, H, P)
    y = y + D.astype(f32)[None, None, :, None] * x
    return y[:, :T], S
