"""The device: the share of the traced window in which no operation ran on
it, in % (1 minus the union of device-op intervals over the window)."""
from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or ctx.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_ns(ctx.trace) / ctx.trace.window_ns)
