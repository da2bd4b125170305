r"""programs: op time of one batched decode under the scope
`cake.attn.latent`: every latent-attention layer's whole mixer
(models/deepseek_v2.py): its projections, norms and rope (`.proj`: q_a, q_b,
kv_a, o), W_uk folded into the queries and W_uv out of the latents
(`.absorb`), and the absorbed read of every row's latents to its frontier
(`.read`: the kernel `cake_latent_decode_attention`, or XLA's masked scores
over the whole buffer where it is off); the scatter of the new row lies
beside them, inside the scope.

A PART of `programs.decode.attn_ms` (the scope is nested in `cake.attn`),
as `programs.decode.attn_retention_ms` is: the top-level readers still add
up to the mean op time of an execution without this one.

The MEAN over the window's `_decode_slots` executions of the summed device
time of the ops traced under `cake.attn.latent`, nested scopes included.
A scope is read from the op's `tf_op` by `[/(]cake\.<scope>[/)]`
(`trace_reduce.Trace.scope_ms`). A program with no such scope (a model
without latent layers, a parent commit) gives None: the metric is left
out of the line.
"""

PROGRAM = "_decode_slots"
SCOPE = "attn.latent"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
