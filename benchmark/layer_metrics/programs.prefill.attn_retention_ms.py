r"""programs: op time of one prefill chunk under the scope
`cake.attn.retention`: the mixer of every power-retention layer over the
chunk's tokens in its chunk form: two masked products inside the chunk, two
against the state the row carried in, and the state behind the chunk
(models/brumby.py: retention_chunk).

The same reading as `programs.decode.attn_retention_ms`, over the window's
`_prefill_slot` executions (all chunk buckets together), as
`programs.prefill.attn_linear_ms` reads `cake.attn.linear`: the MEAN of the
summed device time of the ops traced under `cake.attn.retention`. None
where the program has no such scope.
"""
PROGRAM = "_prefill_slot"
SCOPE = "attn.retention"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
