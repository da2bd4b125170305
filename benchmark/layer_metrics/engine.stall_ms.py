"""engine (cake_tpu/serve/flight.py): how long the scheduler stood still.

Sum, over the flight records inside the window, of `stall_ms`: a record's
excess of `wall_ms + gap_ms` (the iteration and the `_run` loop's time
before it) over the stall threshold, max(10 x the ring's median step,
500 ms); 0 on every record that was not flagged. So a window without a
stall reads 0, and a program whose records carry no `wall_ms` (the parent
of the PR that brought it) reads nothing. What stood still, and whether
the program, the collector, a compile or the machine did it, is in the
report's `engine.stalls` (`phase`, `gc_ms`, `compiles`, `loop_lag_ms`).

Per-layer metrics are read in traced runs, and a traced run has one pause
that is the harness's own: `jax.profiler.stop_trace()` holds the GIL for
0.7-5 s, from 0.3-0.5 s after launch_server.py stamps `trace_stop_ns`
(chip runs, PR 41: every traced run of every cell, on the parent too).
The flagged iteration that ran into it is left out: one that ended after
that stamp and began before STOP_SHADOW_S past it. (`ctx.trace.t1` is the
stamp on the profiler's clock, `offset_ns` what ties it to the records'.)
"""

# how long after its stamp the profiler's stop may take the GIL: four times
# the latest seen
STOP_SHADOW_S = 2.0


def read(ctx):
    recs = [r for r in ctx.flight if "wall_ms" in r]
    if not recs:
        return None
    stop = (ctx.trace.t1 - ctx.trace.offset_ns) / 1e9

    def ours(r):
        began = r["t"] - (r["wall_ms"] + r.get("gap_ms", 0.0)) / 1e3
        return r["t"] < stop or began > stop + STOP_SHADOW_S

    return sum(r.get("stall_ms", 0.0) for r in recs if ours(r))
