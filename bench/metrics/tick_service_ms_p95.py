"""Tick (stage, step, fetch, monitor): sink time minus pull time of each
batch's last frame, 95th percentile over every batch delivered."""
from bench.stats import percentile


def read(ctx, split=None):
    return percentile(ctx.tick_service_ms(), 95)
