"""process (cake_tpu/obs/process.py): executables loaded from the cache.

Sum, over the `process.compile` spans that ended before the window, of the
`backend` stage where the persistent compilation cache held the program
(`cache` = hit): the retrieval and the executable's load. 0 in a cold run.
"""
import boot_account


def read(ctx):
    return boot_account.build_s(ctx, ("backend",), ("hit",))
