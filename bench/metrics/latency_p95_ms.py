"""95th percentile of sink time minus due time, over every frame due in
the window (a frame never delivered counts with the time the run waited)."""
from bench.stats import percentile


def read(ctx, split=None):
    return percentile(ctx.latencies_ms(), 95)
