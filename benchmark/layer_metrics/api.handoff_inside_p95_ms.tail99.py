"""api: a token's hand-off inside the program, where the ITL tail that is
judged is the 99th percentile.

The same reading as `api.handoff_inside_p95_ms` under a name of its own,
as `api.handoff_p95_ms.tail99` is to `api.handoff_p95_ms`: a per-layer
metric moves ONE end-to-end metric, and `qwen3-4b.chat` judges
`itl_p99_ms` (PERF.md §2, PR 30).
"""
import os

import manifest

read = manifest.metric_reader(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "api.handoff_inside_p95_ms")
