"""programs (cake_tpu/models/common/text_model.py): the model's constructor.

The `boot.model` span: `TextModel.__init__` whole (the parameters placed,
the rope tables cut to the caches' length, the jitted programs defined).
The programs it builds inside carry `phase` = boot.model in
`process.compile`, and are in the three `process.*_s` as well.
"""
import boot_account


def read(ctx):
    return boot_account.phase_s(ctx, "boot.model")
