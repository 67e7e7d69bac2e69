"""Median of sink time minus due time, over every frame due in the window."""
from bench.stats import percentile


def read(ctx, split=None):
    return percentile(ctx.latencies_ms(), 50)
