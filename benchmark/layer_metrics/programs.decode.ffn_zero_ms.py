r"""programs: op time of one batched decode under the scope `cake.ffn.zero`:
the identity experts' term of every sparse layer whose router scores them
(ops.moe.moe_ffn, `zero_experts`; LongCat-Flash's `zero_expert_num`): the
summed weights of a token's picks among the router's last outputs, their
product with the layer's input and its sum into the held experts' result.
No bank backs an identity expert and no share holds one: the term costs a
reduction over the picks and one pass over [tokens, hidden].

A PART of `programs.decode.ffn_ms` (the scope is nested in `cake.ffn`),
beside `programs.decode.ffn_dense_ms`, `programs.decode.ffn_route_ms` and
`programs.decode.ffn_experts_ms`, not a sibling of `ffn_ms`: the top-level
readers still add up to the mean op time of an execution without this one.

The MEAN over the window's `_decode_slots` executions of the summed device
time of the ops traced under `cake.ffn.zero`, nested scopes included. A
scope is read from the op's `tf_op` by `[/(]cake\.<scope>[/)]`
(`trace_reduce.Trace.scope_ms`). A program with no such scope (a model
without identity experts, or a program that lacks the scope, as every
program before PR 64) gives None: the metric is left out of the line.
"""

PROGRAM = "_decode_slots"
SCOPE = "ffn.zero"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
