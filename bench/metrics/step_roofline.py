"""Whole device step against the HBM roofline: the least time the chip
could take for a frame (the service bytes of ``bench.costs`` over the peak
HBM bandwidth of ``peaks.json``) over the device busy time per frame."""
from bench import costs


def read(ctx, split=None):
    frames = ctx.delivered_in_window()
    if ctx.trace is None or frames == 0:
        return None
    least_s = ctx.service_bytes / costs.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (ctx.trace.busy_s / frames)
