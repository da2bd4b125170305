"""engine (cake_tpu/serve/engine.py): the `_run` loop between two iterations.

Median `gap_ms` over the flight records inside the window whose previous
iteration left work behind (busy rows, a queue or a step in flight: the
others carry 0): from that iteration's last stamp to this one's first,
the watchdog's disarm, the bookkeeping and the GIL handed to the event
loop. Nothing to read on a program whose records carry no `gap_ms`.
"""
from statistics import median


def read(ctx):
    gaps = [r["gap_ms"] for r in ctx.flight if r.get("gap_ms", 0) > 0]
    return median(gaps) if gaps else None
