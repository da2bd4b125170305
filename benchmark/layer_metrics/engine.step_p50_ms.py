"""engine (cake_tpu/serve/engine.py): one scheduler iteration, host clock.

Median duration of the `serve.step` spans inside the window: sweeps,
admission, the decode dispatch, one prefill chunk's dispatch, the fetch of
the sampled ids and the fan-out to the streams.
"""
from statistics import median


def read(ctx):
    durs = [e["dur"] / 1e3 for e in ctx.spans_named("serve.step")]
    return median(durs) if durs else None
