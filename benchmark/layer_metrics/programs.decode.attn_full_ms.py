r"""programs: op time of one batched decode under the scope `cake.attn.full`:
every full-attention layer's read of its cache, the call of the Pallas
decode kernel (`cake_decode_attention`, a row walked to its frontier) or,
where a layer decodes masked, the scores, softmax and weighted values over
the whole buffer (layers.attention_forward). Projections, norms, rope, the
gate and the cache's scatter stay outside it.

A PART of `programs.decode.attn_ms` (the scope is nested in `cake.attn`),
the counterpart of `programs.decode.attn_window_ms`, not a sibling of
`attn_ms`: the top-level readers still add up to the mean op time of an
execution without this one.

The MEAN over the window's `_decode_slots` executions of the summed device
time of the ops traced under `cake.attn.full`, nested scopes included. A
scope is read from the op's `tf_op` by `[/(]cake\.<scope>[/)]`
(`trace_reduce.Trace.scope_ms`). A program with no such scope (a parent
commit) gives None: the metric is left out of the line.
"""

PROGRAM = "_decode_slots"
SCOPE = "attn.full"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
