"""engine: how many slots decoded in an iteration, on average.

Mean `occupancy` of the flight recorder's iteration records inside the
window (the launcher raises the ring's size for the traced run).
"""


def read(ctx):
    occ = [r["occupancy"] for r in ctx.flight]
    return sum(occ) / len(occ) if occ else None
