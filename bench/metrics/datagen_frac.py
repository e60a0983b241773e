"""Device time of the input pipeline's programs (``MarkovStream.batch_stack``,
compiled as ``jit_stacked``, train and eval streams) over the window."""

MODULE = "jit_stacked"


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.trace_window_ns
    from bench.trace import module_ns

    found = [module_ns(d, lo, hi, MODULE) for d in ctx.trace.devices.values()]
    if not any(found):
        return None
    return sum(found) / len(found) / 1e9 / ctx.trace_window_s
