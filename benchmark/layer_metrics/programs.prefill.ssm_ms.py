r"""programs: op time of one prefill chunk under the scope `cake.ssm`: the
state-space mixer of every Mamba layer over the chunk's tokens, the scan
along the tokens included.

The same reading as `programs.decode.ssm_ms`, over the window's
`_prefill_slot` executions (all chunk buckets together): the MEAN of the
summed device time of the ops traced under `cake.ssm`. None where the
program has no such scope.
"""
PROGRAM = "_prefill_slot"
SCOPE = "ssm"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
