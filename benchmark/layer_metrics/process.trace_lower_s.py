"""process (cake_tpu/obs/process.py): Python's time to build the programs.

Sum, over the `process.compile` spans that ended before the window, of the
stages `trace` (a program's function to a jaxpr: the program's own trace,
the functions traced inside it are in its time) and `lower` (the jaxpr to
an MLIR module), every program the process built since it began. Paid with
a warm persistent cache as with a cold one.
"""
import boot_account


def read(ctx):
    return boot_account.build_s(ctx, ("trace", "lower"))
