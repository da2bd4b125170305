"""engine (cake_tpu/serve/engine.py): the host's own part of an iteration.

Median, over the `serve.step` spans inside the window, of the span's
duration minus its `serve.fetch` child (tied by `args.parent`): sweeps,
admission, planning, both dispatches and the fan-out, without the time the
scheduler stood blocked on the device.
"""
from statistics import median

import phases


def read(ctx):
    ms = phases.step_host_ms(ctx)
    return median(ms) if ms else None
