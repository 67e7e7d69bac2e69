"""D2H and monitor: the program's ``ServeReport.d2h_bytes`` per frame
stepped (a count)."""


def read(ctx, split=None):
    frames = ctx.report.frames
    if not frames:
        return None
    return ctx.report.d2h_bytes / frames
