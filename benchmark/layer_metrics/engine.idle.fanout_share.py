"""engine: the device idle while the host fanned the ids out.

That part of `device.idle_share`: seconds of the first device plane's idle
gaps in the traced window that lie under
`serve.fanout` (ids to the streams, finished rows released),
children of a `serve.step` span, over the window; the spans are first laid
on the device plane's clock (`phases.device_lead_ns`). The five
`engine.idle.*` shares sum to `device.idle_share` of the same run.
"""
import phases


def read(ctx):
    return phases.idle_share(ctx, "fanout")
