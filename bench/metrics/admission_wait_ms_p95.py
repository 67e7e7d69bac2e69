"""Serving tier: the time the tick loop pulled a frame from the camera
minus the frame's due time, 95th percentile over every frame due in the
window (the harness's own clock readings around the camera iterator)."""
from bench.stats import percentile


def read(ctx, split=None):
    return percentile(ctx.admission_waits_ms(), 95)
