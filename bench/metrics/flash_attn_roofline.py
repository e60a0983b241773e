"""Share of its roofline that the flash-attention kernels (forward, dq, dk/dv)
reached in the traced window, in percent: over the calls that
``bench/kernels/flash_attn.py`` recognises, their least time on the chip
(``bench/peaks.json``) over their device time."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    from bench.harness import load_module
    from bench.trace import roofline_share

    lo, hi = ctx.trace_window_ns
    return roofline_share(ctx.trace, lo, hi, load_module("kernels", "flash_attn").cost, ctx.peak)
