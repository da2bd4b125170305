"""engine: the device idle under no named host phase.

That part of `device.idle_share`: seconds of the first device plane's idle
gaps in the traced window that lie under
no leaf span at all (between steps, or a hole in the cover of `serve.step`),
children of a `serve.step` span, over the window; the spans are first laid
on the device plane's clock (`phases.device_lead_ns`). The five
`engine.idle.*` shares sum to `device.idle_share` of the same run.
"""
import phases


def read(ctx):
    return phases.idle_share(ctx, "unnamed")
