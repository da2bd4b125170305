"""engine (cake_tpu/serve/engine.py): the engine's constructor.

The `boot.engine` span: `ServeEngine.__init__` whole, the slot pool's and
the prefix cache's allocation (waited for: its child `boot.engine.pool`)
in it. The programs it builds inside carry `phase` = boot.engine in
`process.compile`, and are in the three `process.*_s` as well.
"""
import boot_account


def read(ctx):
    return boot_account.phase_s(ctx, "boot.engine")
