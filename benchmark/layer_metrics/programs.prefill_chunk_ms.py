"""programs: device time of one prefill chunk.

Median duration of the `_prefill_slot` executions on the device, from the
profiler trace's module line (all chunk buckets and flash modes together).
"""
from statistics import median

PROGRAM = "_prefill_slot"


def read(ctx):
    durs = [d / 1e6 for _, _, d in ctx.trace.events("modules", PROGRAM)]
    return median(durs) if durs else None
