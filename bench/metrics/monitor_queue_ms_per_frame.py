"""Monitor: the program's ``ServeReport.phases["monitor_queue_s"]`` (a
counter: per frame, the monitor's write time minus its ``put`` time) per
frame stepped."""


def read(ctx, split=None):
    frames = ctx.report.frames
    if not frames or "monitor_queue_s" not in ctx.report.phases:
        return None
    return ctx.report.phases["monitor_queue_s"] * 1e3 / frames
