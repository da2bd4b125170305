"""engine: how long the scheduler stood still, where the loop is open.

The same reading as `engine.stall_ms` (the sum over the window's flight
records of `stall_ms`, without the one that ran into the profiler's stop),
under a name of its own because a per-layer metric moves ONE end-to-end
metric. In a closed loop a scheduler that stands still gives its callers
fewer tokens: `out_tok_s`. In an open loop the arrivals do not wait for it:
every request due meanwhile waits, and what it moves is the time to the
first token, `ttft_p50_ms`. Tokens per second there are the schedule's own
(`qwen3-4b.longprompt`: 35 requests of 64 tokens due in 40 s, 56.0 tokens/s)
plus what the window's edges carry, which FALLS as the server gets faster
(PERF.md §2, PR 58).
"""
import os

import manifest

read = manifest.metric_reader(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "engine.stall_ms")
