"""Process start to the opening of the measured window: imports, frame
pool, compile or compile-cache load, and the warm tick."""


def read(ctx, split=None):
    return ctx.setup_s
