"""Shared constants/helpers for the Pallas kernel library."""

import jax

from deepspeed_tpu.utils.logging import warning_once

NEG_INF = -1e30
# logsumexp rows carry 8 broadcast sublane copies to satisfy TPU tiling
LSE_LANES = 8


# bytes of one tile of a recurrent state that a one-token kernel walks
# (retention_step.py, ssd_step.py): in and out, double-buffered, four of
# them are live (under the 16 MB a kernel may use unasked). On the v5e a
# retention tile of all 8,320 columns (with the limit raised) ran no faster
# than one of 1,664, and a Mamba-2 tile of 32 heads (4 MB) 1.3% faster than
# one of 16: the walk is bound by the bytes, not by its steps
STATE_TILE_BYTES = 2 << 20


def interpret() -> bool:
    """Whether Pallas kernels run in interpreter mode: on every backend but
    TPU, so the CPU test mesh exercises the same kernel bodies. Logged once
    when it engages — a job meant for the chip whose backend came up as
    CPU would otherwise interpret every kernel without a word."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    warning_once(
        f"Pallas kernels run in INTERPRET mode: backend is {backend!r}, not "
        "'tpu' (correct results, none of the chip's speed)")
    return True


def largest_divisor_block(t: int, want: int = 128) -> int:
    """Largest block size <= want dividing t.

    Shape-blind FALLBACK: kernels that care about the (seq, head_dim,
    device) trade-off — flash attention's causal block pruning above all —
    resolve blocks through ``ops/pallas/autotune.get_flash_schedule``
    (the pretuned table) and only land here when nothing better is known
    for the shape."""
    b = min(want, t)
    while t % b:
        b -= 1
    return b
