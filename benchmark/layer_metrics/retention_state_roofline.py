"""kernel (cake_tpu/ops/retention_state.py `cake_retention_state`, called
under the scope `cake.attn.retention.scan` of cake_tpu/models/brumby.py):
the passes over the retention state in a decode step against their
roofline, whatever makes them (the kernel, or XLA's fusions where it is
off: the scope holds both, with the normaliser's pass and the division).

For every `_decode_slots` execution in the trace, the rows it advanced are
read from the `serve.decode_dispatch` span that dispatched it (the last
one that began before the execution did; the program runs the whole pool
in place, the span's `slots` says how many rows were live).
benchmark/kernels/<kernel>.py gives the operations and bytes those rows
NEED in all layers (state read and written once, at its minimal width, in
float32); the least time is the larger of operations over peak FLOP/s and
bytes over peak bytes/s. The share is the summed least time over the summed
device time of the ops traced under `cake.attn.retention.scan` inside those
executions. Needed work only, so it cannot pass 100 %. None where no
execution holds such an op (a parent commit, a model without retention).
"""
import bisect
import os
import re

KERNEL = os.path.basename(__file__)[:-len("_roofline.py")]
PROGRAM = "_decode_slots"
SCOPE = re.compile(r"[/(]cake\.attn\.retention\.scan[/)]")


def read(ctx):
    tr = ctx.trace
    counts = ctx.kernel(KERNEL).counts
    spans = sorted((tr.perf_to_prof(e["ts"] * 1000), e["args"]["slots"])
                   for e in ctx.spans if e["name"] == "serve.decode_dispatch")
    starts = [s for s, _ in spans]
    # executions() walks the module events sorted by start: the same order
    runs = sorted(s for _, s, _ in tr.events("modules", PROGRAM))
    least = spent = 0.0
    for start, ops in zip(runs, tr.executions(PROGRAM)):
        scan = sum(d for scope, d in ops if SCOPE.search(scope))
        i = bisect.bisect_right(starts, start) - 1
        if not scan or i < 0:
            continue
        flops, nbytes = counts(ctx.cell.hf, spans[i][1])
        least += max(flops / ctx.peaks["bf16_flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
        spent += scan / 1e9
    return 100.0 * least / spent if spent else None
