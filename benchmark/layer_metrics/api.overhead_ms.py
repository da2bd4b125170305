"""api (cake_tpu/api/): what the HTTP layer adds to a first token.

Median, over the judged requests, of the client's send -> first streamed
token minus the engine's own enqueue -> first-token-fetched (`ttft_ms` on
the request's `finish` event). Templating, tokenizing, admission, the SSE
writer and the socket are in it; queueing and prefill are not.
"""
from statistics import median


def read(ctx):
    engine_ttft = {e["rid"]: e["ttft_ms"]
                   for e in ctx.timeline_events("finish")
                   if e.get("ttft_ms")}
    diffs = [(r.tokens[0] - r.sent) * 1e3 - engine_ttft[r.rid]
             for r in ctx.records if r.rid in engine_ttft and r.tokens]
    return median(diffs) if diffs else None
