r"""programs: op time of one prefill chunk under the scope
`cake.attn.latent`: the mixer of every latent-attention layer over the
chunk's tokens: the projections, the chunk's rows written into the buffer,
and the ABSORBED read of the row's latents up to each token by all its
heads (models/deepseek_v2.py: a chunk absorbs too, through the same kernel
as a decode step).

The same reading as `programs.decode.attn_latent_ms`, over the window's
`_prefill_slot` executions (all chunk buckets together), as
`programs.prefill.attn_retention_ms` reads `cake.attn.retention`: the MEAN
of the summed device time of the ops traced under `cake.attn.latent`. None
where the program has no such scope.
"""
PROGRAM = "_prefill_slot"
SCOPE = "attn.latent"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
