"""Rotary position embeddings.

Parity with reference ``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu``
(exposed as ``apply_rotary_pos_emb`` in pt_binding.cpp): rotate q/k pairs by
position-dependent angles. Pure jnp — XLA fuses the sin/cos/interleave into
the surrounding attention matmuls; the CUDA kernel exists because torch
eager could not.
"""

import math
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature term ``0.1 * mscale * ln(factor) + 1``
    (1 without scaling). A model multiplies its softmax scale by the
    square of it (``mscale_all_dim``) and its cos / sin tables by the ratio
    of two of them."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's blended inverse frequencies ``[dim / 2]`` (float64, a
    trace-time constant): a rotary dimension that makes more than
    ``beta_fast`` turns within ``original_max_position`` positions keeps
    its frequency, one that makes fewer than ``beta_slow`` is interpolated
    (divided by ``factor``), and a linear ramp over the dimensions between
    blends the two (Peng et al., arXiv:2309.00071; the published
    DeepSeek-V2 ``DeepseekV2YarnRotaryEmbedding``)."""
    def correction_dim(turns):
        return dim * math.log(original_max_position
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001       # the published code's guard against 0 / 0
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary_angles(positions: jnp.ndarray, dim: int, base: float = 10000.0,
                  dtype=jnp.float32,
                  inv_freq: Optional[Sequence[float]] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables of shape [..., dim/2] for integer positions;
    ``inv_freq`` ([dim / 2]) in place of the plain ``base`` ladder where a
    model scales its frequencies (:func:`yarn_inv_freq`)."""
    if inv_freq is None:
        inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                   / dim))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary_pos_emb(
    x: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    base: float = 10000.0,
    rotary_dim: Optional[int] = None,
    interleaved: bool = False,
    inv_freq: Optional[Sequence[float]] = None,
) -> jnp.ndarray:
    """Rotate ``x: [batch, seq, heads, head_dim]``.

    ``interleaved=False``: pairwise half-dim split — the GPT-NeoX/LLaMA
    convention the reference's kernel implements with rotate_half.
    ``interleaved=True``: even/odd pairing — the GPT-J convention (the
    reference kernel's ``rotate_every_two`` variant).
    """
    b, t, h, d = x.shape
    rd = rotary_dim or d
    if positions is None:
        positions = jnp.arange(t)[None, :]
    cos, sin = rotary_angles(positions, rd, base, dtype=x.dtype,
                             inv_freq=inv_freq)
    cos = cos[:, :, None, :]  # [b, t, 1, rd/2]
    sin = sin[:, :, None, :]

    x_rot, x_pass = x[..., :rd], x[..., rd:]
    if interleaved:
        x1, x2 = x_rot[..., ::2], x_rot[..., 1::2]
        rotated = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(x_rot.shape)
    else:
        x1, x2 = x_rot[..., : rd // 2], x_rot[..., rd // 2:]
        rotated = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if rd < d:
        return jnp.concatenate([rotated, x_pass], axis=-1)
    return rotated
