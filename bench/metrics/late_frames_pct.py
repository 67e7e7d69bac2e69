"""Share of the frames due in the window whose sink time minus due time
passed the mix's ``latency_limit_ms`` (a frame never delivered counts with
the time the run waited); nothing where the mix states no limit."""


def read(ctx, split=None):
    if ctx.latency_limit_ms is None:
        return None
    lat = ctx.latencies_ms()
    if not lat:
        return None
    return 100.0 * sum(x > ctx.latency_limit_ms for x in lat) / len(lat)
