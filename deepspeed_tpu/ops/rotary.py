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


def section_streams(sections: Sequence[int], dim: int) -> np.ndarray:
    """Which position stream each of the ``dim / 2`` frequencies takes its
    angle from under a sectioned rotary (``mrope_section``): the first
    ``sections[0]`` frequencies from stream 0 (the temporal position), the
    next ``sections[1]`` from stream 1 (the height), and so on. The
    sections must add up to ``dim / 2``."""
    if sum(sections) != dim // 2:
        raise ValueError(
            f"rotary sections {tuple(sections)} do not add up to the "
            f"{dim // 2} frequencies of a rotary dimension of {dim}")
    return np.repeat(np.arange(len(sections)), sections)


def rotary_angles(positions: jnp.ndarray, dim: int, base: float = 10000.0,
                  dtype=jnp.float32,
                  inv_freq: Optional[Sequence[float]] = None,
                  sections: Optional[Sequence[int]] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables of shape [..., dim/2] for integer positions;
    ``inv_freq`` ([dim / 2]) in place of the plain ``base`` ladder where a
    model scales its frequencies (:func:`yarn_inv_freq`). With
    ``sections`` and positions that carry a leading axis of
    ``len(sections)`` streams (``[3, ...]``: temporal, height, width),
    each frequency takes its angle from its section's stream
    (:func:`section_streams`); positions without that axis are every
    stream's, as for text, and the sections then change nothing."""
    if inv_freq is None:
        inv_freq = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                   / dim))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    if sections is not None and positions.ndim == 3:
        stream = section_streams(sections, dim)             # [dim / 2]
        angles = jnp.take_along_axis(
            angles, jnp.asarray(stream)[None, None, None, :], axis=0)[0]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary_pos_emb(
    x: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    base: float = 10000.0,
    rotary_dim: Optional[int] = None,
    interleaved: bool = False,
    inv_freq: Optional[Sequence[float]] = None,
    sections: Optional[Sequence[int]] = None,
) -> jnp.ndarray:
    """Rotate ``x: [batch, seq, heads, head_dim]``.

    ``sections`` with ``positions [streams, batch, seq]``: the sectioned
    rotary of :func:`rotary_angles`.

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
                             inv_freq=inv_freq, sections=sections)
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
