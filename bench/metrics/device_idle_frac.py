"""Share of the measured window in which no operation ran on the device,
averaged over the cell's chips (1 - busy / window), from the trace."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 1.0 - ctx.busy_s / ctx.trace_window_s
