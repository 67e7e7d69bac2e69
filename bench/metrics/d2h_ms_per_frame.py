"""D2H: the program's ``ServeReport.phases["fetch_s"]`` (the ``repro.fetch``
span: ``np.asarray`` of a lane's ready slice, the D2H copy and the host
delinearize, summed over completion threads) per frame stepped. Thread
time: divided by the wall time per frame it gives the fetch concurrency.
The ``.backlog`` and ``.live`` splits read the same quantity in cells that
report different end-to-end metrics."""


def read(ctx, split=None):
    frames = ctx.report.frames
    if not frames or "fetch_s" not in ctx.report.phases:
        return None
    return ctx.report.phases["fetch_s"] * 1e3 / frames
