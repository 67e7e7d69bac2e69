"""Frames delivered to the sink inside the window, per window second."""


def read(ctx, split=None):
    return ctx.delivered_in_window() / ctx.seconds
