"""In-flight window: the program's ``ServeReport.phases["inflight_wait_s"]``
(the ``repro.inflight_wait`` span: the serve thread blocked on the
``max_in_flight`` semaphore until a completion frees a slot) per frame
stepped."""


def read(ctx, split=None):
    frames = ctx.report.frames
    if not frames or "inflight_wait_s" not in ctx.report.phases:
        return None
    return ctx.report.phases["inflight_wait_s"] * 1e3 / frames
