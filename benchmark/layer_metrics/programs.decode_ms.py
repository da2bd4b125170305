"""programs (models/common/text_model.py): device time of one batched decode.

Median duration of the `_decode_slots` executions on the device, from the
profiler trace's module line.
"""
from statistics import median

PROGRAM = "_decode_slots"


def read(ctx):
    durs = [d / 1e6 for _, _, d in ctx.trace.events("modules", PROGRAM)]
    return median(durs) if durs else None
