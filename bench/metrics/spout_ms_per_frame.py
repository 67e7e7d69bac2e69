"""Spout: the program's ``ServeReport.phases["spout_s"]`` (the ``repro.spout``
span: ``Spout._emit``'s padding, ``np.stack`` and ids on the serve thread)
per frame stepped. The ``.backlog`` and ``.live`` splits read the same
quantity in cells that report different end-to-end metrics."""


def read(ctx, split=None):
    frames = ctx.report.frames
    if not frames or "spout_s" not in ctx.report.phases:
        return None
    return ctx.report.phases["spout_s"] * 1e3 / frames
