"""programs: device time of one prefill chunk, in a closed loop.

The same reading as `programs.prefill_chunk_ms` (median duration of the
`_prefill_slot` executions on the device, from the profiler trace's module
line), under a name of its own because a closed loop at full slots judges
no TTFT: there a chunk is what stretches the step it shares with every
stream's decode, so it moves `itl_p95_ms`.
"""
from statistics import median

PROGRAM = "_prefill_slot"


def read(ctx):
    durs = [d / 1e6 for _, _, d in ctx.trace.events("modules", PROGRAM)]
    return median(durs) if durs else None
