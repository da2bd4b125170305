"""api (cake_tpu/api/text.py): a token's hand-off, measured inside the program.

95th percentile, over the window's `api.sse_write` spans (one a streamed
content token, recorder on), of `wait_us` + the span's duration: from the
scheduler's `call_soon_threadsafe` to the event loop handing the token to
the SSE writer (the GIL and the loop's queue), plus `json.dumps` and
aiohttp's `resp.write`. What `api.handoff_p95_ms` reads beyond this is the
socket and the benchmark's own client. Nothing to read on a program
without the span.
"""
from statistics import quantiles


def read(ctx):
    ms = [(e["args"]["wait_us"] + e["dur"]) / 1e3
          for e in ctx.spans_named("api.sse_write")
          if "wait_us" in e.get("args", {})]
    # under 20 tokens the 95th percentile is the largest sample
    if len(ms) < 20:
        return None
    return quantiles(ms, n=20, method="inclusive")[-1]
