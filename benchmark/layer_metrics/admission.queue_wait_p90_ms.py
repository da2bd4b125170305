"""admission (cake_tpu/serve/admission/): how long requests waited for a slot.

90th percentile of `queue_wait_ms` on the judged requests' `admit` events
(enqueue -> slot assigned).
"""
from statistics import quantiles


def read(ctx):
    waits = [e["queue_wait_ms"] for e in ctx.timeline_events("admit")]
    if len(waits) < 2:
        return None
    return quantiles(waits, n=10, method="inclusive")[-1]
