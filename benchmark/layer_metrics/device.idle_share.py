"""device (TPU v5e): the share of the traced window with no op on the chip.

1 - (union of the device's op intervals) / window, first device plane.
"""


def read(ctx):
    tr = ctx.trace
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
