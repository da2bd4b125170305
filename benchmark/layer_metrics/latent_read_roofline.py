"""kernel (cake_tpu/ops/latent_attention.py `cake_latent_decode_attention`,
called under the scope `cake.attn.latent.read` of
cake_tpu/models/deepseek_v2.py): the absorbed read of the rows' latents in a
decode step against its roofline, whatever makes it (the kernel, or XLA's
masked scores, softmax and weighted sum where it is off: the scope holds
either).

For every `_decode_slots` execution in the trace, the tokens its rows held
are read from the `serve.decode_dispatch` span that dispatched it (the last
one that began before the execution did; the span's `kv_tokens` is the sum
of the live rows' frontiers). benchmark/kernels/<kernel>.py gives the
operations and bytes those tokens NEED in all layers (every held token's
latent read once a layer at its minimal width; scores and weighted sum of
every head); the least time is the larger of operations over peak FLOP/s
and bytes over peak bytes/s. The share is the summed least time over the
summed device time of the ops traced under `cake.attn.latent.read` inside
those executions. Needed work only, so it cannot pass 100 %. None where no
execution holds such an op (a parent commit, a model without latent
layers).
"""
import bisect
import os
import re

KERNEL = os.path.basename(__file__)[:-len("_roofline.py")]
PROGRAM = "_decode_slots"
SCOPE = re.compile(r"[/(]cake\.attn\.latent\.read[/)]")


def read(ctx):
    tr = ctx.trace
    counts = ctx.kernel(KERNEL).counts
    spans = sorted((tr.perf_to_prof(e["ts"] * 1000), e["args"]["kv_tokens"])
                   for e in ctx.spans if e["name"] == "serve.decode_dispatch")
    starts = [s for s, _ in spans]
    # executions() walks the module events sorted by start: the same order
    runs = sorted(s for _, s, _ in tr.events("modules", PROGRAM))
    least = spent = 0.0
    for start, ops in zip(runs, tr.executions(PROGRAM)):
        took = sum(d for scope, d in ops if SCOPE.search(scope))
        i = bisect.bisect_right(starts, start) - 1
        if not took or i < 0:
            continue
        flops, nbytes = counts(ctx.cell.hf, spans[i][1])
        least += max(flops / ctx.peaks["bf16_flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
        spent += took / 1e9
    return 100.0 * least / spent if spent else None
