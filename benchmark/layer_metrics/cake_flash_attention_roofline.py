"""kernel (cake_tpu/ops/flash.py): the flash kernel against its roofline.

For every `_prefill_slot` execution in the trace that ran the kernel, the
chunk it computed is read from the `serve.prefill_chunk` span that
dispatched it (the last one that began before the execution did: the engine
has at most one chunk in flight). benchmark/kernels/<kernel>.py gives the
operations and bytes that chunk NEEDS in every layer; the least time is the
larger of operations over peak FLOP/s and bytes over peak bytes/s. The share
is the summed least time over the summed device time of the kernel's events
inside those executions. Needed work only (masked and beyond-frontier tiles
do not count), so it cannot pass 100 %.
"""
import bisect
import os

KERNEL = os.path.basename(__file__)[:-len("_roofline.py")]
PROGRAM = "_prefill_slot"


def read(ctx):
    tr = ctx.trace
    counts = ctx.kernel(KERNEL).counts
    spans = sorted((tr.perf_to_prof(e["ts"] * 1000), e["args"])
                   for e in ctx.spans if e["name"] == "serve.prefill_chunk")
    starts = [s for s, _ in spans]
    least = spent = 0.0
    for _, start, dur in tr.events("modules", PROGRAM):
        kernels = tr.inside("ops", KERNEL, start, start + dur)
        i = bisect.bisect_right(starts, start) - 1
        if not kernels or i < 0:
            continue
        flops, nbytes = counts(ctx.cell.hf, spans[i][1]["pos0"],
                               spans[i][1]["tokens"])
        # the heads are what a cell of several chips divides: the device
        # plane read here computed its share of them
        flops, nbytes = flops / ctx.cell.chips, nbytes / ctx.cell.chips
        least += len(kernels) * max(
            flops / ctx.peaks["bf16_flops_per_s"],
            nbytes / ctx.peaks["hbm_bytes_per_s"])
        spent += sum(d for _, _, d in kernels) / 1e9
    return 100.0 * least / spent if spent else None
