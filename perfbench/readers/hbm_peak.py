"""The allocator's high-water on the fullest chip, in GB (10^9 bytes):
``peak_bytes_in_use`` (buffers) plus ``peak_bytes_reserved`` (the compiled
programs' temporaries, a pool of its own); the run's log line gives both."""


def read(ctx):
    peaks = [m["peak_bytes"] for m in ctx.memory if m.get("peak_bytes")]
    if not peaks:
        return None
    return max(peaks) / 1e9
