r"""programs: op time of one batched decode under the scope `cake.ffn.dense`:
the dense FFNs of a model whose layers are shortcut-connected pairs
(layers.shortcut_forward; LongCat-Flash: two dense SwiGLUs of
`ffn_hidden_size` a layer), each with its sum into the stream and, in the
sub-layer that closes a pair, the sum of the sparse layer's held-back
output. It is the part of `programs.decode.ffn_ms` that is NOT the sparse
layer: with it `ffn_ms` = dense + route + experts + zero in such a model.
Older families' dense FFNs enter no such scope (their programs lower to the
text they lowered to before).

A PART of `programs.decode.ffn_ms` (the scope is nested in `cake.ffn`), not
a sibling of it: the top-level readers still add up to the mean op time of
an execution without this one.

The MEAN over the window's `_decode_slots` executions of the summed device
time of the ops traced under `cake.ffn.dense`, nested scopes included. A
scope is read from the op's `tf_op` by `[/(]cake\.<scope>[/)]`
(`trace_reduce.Trace.scope_ms`). A program with no such scope gives None:
the metric is left out of the line.
"""

PROGRAM = "_decode_slots"
SCOPE = "ffn.dense"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
