"""Model FLOP/s utilization: tokens/s/chip times the FLOPs the forward and
backward passes need per token (recompute not counted) over the chip's
bf16 peak, in percent. It is the throughput times a constant."""


def read(ctx):
    rate = ctx.series.get("tokens_per_s_per_chip")
    fpt = ctx.system.info.get("flops_per_token")
    if not rate or not fpt:
        return None
    return 100.0 * rate * fpt / (ctx.env.peak["bf16_tflops"] * 1e12)
