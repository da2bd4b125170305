"""process (cake_tpu/obs/process.py): XLA's compilations.

Sum, over the `process.compile` spans that ended before the window, of the
`backend` stage where the program was compiled: the persistent cache had
no entry (`cache` = miss) or was not asked (off). 0 in a warm run.
"""
import boot_account


def read(ctx):
    return boot_account.build_s(ctx, ("backend",), ("miss", "off"))
