r"""programs: op time of one batched decode under the scope `cake.ffn.shared`:
the shared expert every token passes in every sparse layer (its three
GEMMs, its gate where the family has one) and its sum into the routed
result (layers.moe_forward). In one chip's share of an expert-parallel
group it is what every chip computes alike.

A PART of `programs.decode.ffn_ms` (the scope is nested in `cake.ffn`),
beside `programs.decode.ffn_experts_ms`, not a sibling of `ffn_ms`: the
top-level readers still add up to the mean op time of an execution without
this one.

The MEAN over the window's `_decode_slots` executions of the summed device
time of the ops traced under `cake.ffn.shared`, nested scopes included. A
scope is read from the op's `tf_op` by `[/(]cake\.<scope>[/)]`
(`trace_reduce.Trace.scope_ms`). A program with no such scope (a model
without a shared expert, a parent commit) gives None: the metric is left
out of the line.
"""

PROGRAM = "_decode_slots"
SCOPE = "ffn.shared"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
