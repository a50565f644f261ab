"""device_idle_frac: 1 - (union of the device's op intervals over the
traced window), the highest over the cell's devices, in %."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * max(1.0 - b / t.window_s for b in t.busy_s)
