"""Device step: device busy time in the traced window divided by the
frames delivered in it. The ``.live`` and ``.backlog`` splits read the
same quantity in cells that report different end-to-end metrics."""


def read(ctx, split=None):
    frames = ctx.delivered_in_window()
    if ctx.trace is None or frames == 0:
        return None
    return ctx.trace.busy_s * 1e3 / frames
