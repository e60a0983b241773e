"""Model FLOP utilisation of the whole DiLoCo round, in percent: the model
FLOPs of the trained tokens (``bench/flops``: forward + backward, no
recomputation, no optimizer) over the window, the chips and the chip's
bf16 peak (``bench/peaks.json``)."""


def read(ctx):
    if ctx.window_s <= 0 or ctx.tokens <= 0:
        return None
    return 100.0 * ctx.flops_per_token * ctx.tokens / (
        ctx.window_s * ctx.chips * ctx.peak["bf16_flops_per_s"])
