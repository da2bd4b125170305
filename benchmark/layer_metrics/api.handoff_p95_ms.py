"""api (cake_tpu/api/): from the engine's stamp of a token to the client.

95th percentile, over every token of the judged requests, of the client's
arrival instant minus the engine's stamp for that token (the request's
timeline on the recorder's clock: `t0_us` + `t_ms`; `first_token`, then the
`decode` / `spec_verify` events in order). The hand-off to the event loop,
the SSE writer and the socket are in it. On Linux `time.monotonic` (the
client) and `perf_counter` (the engine) are one clock; a negative
difference says they are not, and the metric is left out.
"""
from statistics import quantiles

import phases


def read(ctx):
    t_end = ctx.t0 + ctx.seconds
    diffs = []
    for r in ctx.records:
        tl = ctx.timelines.get(r.rid)
        if not tl or "t0_us" not in tl or r.origin is None \
                or not ctx.t0 <= r.origin < t_end:
            continue
        diffs += [(got - sent) * 1e3
                  for got, sent in zip(r.tokens, phases.token_stamps(tl))]
    # under 20 tokens the 95th percentile is the largest sample
    if len(diffs) < 20 or min(diffs) < 0:
        return None
    return quantiles(diffs, n=20, method="inclusive")[-1]
