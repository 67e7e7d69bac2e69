"""H2D staging: the program's ``ServeReport.phases["host_stage_s"]`` (host
time dispatching the lane uploads and splices) per frame stepped."""


def read(ctx, split=None):
    frames = ctx.report.frames
    if not frames or "host_stage_s" not in ctx.report.phases:
        return None
    return ctx.report.phases["host_stage_s"] * 1e3 / frames
