"""Device: the share of the traced window in which no op ran on the chip."""


def read(ctx, split=None):
    if ctx.trace is None:
        return None
    return 1.0 - ctx.trace.busy_s / ctx.trace.window_s
