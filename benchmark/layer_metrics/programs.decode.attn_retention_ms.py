r"""programs: op time of one batched decode under the scope
`cake.attn.retention`: every power-retention layer's whole mixer
(models/brumby.py): its projections, norms, rope and gate (`.proj`), the
symmetric squares of q and k (`.expand`), and every pass over the row's
[kv heads, 128, 8320] float32 state and its normaliser (`.scan`): the
read-out against the state the row carried in and ONE decayed update of
every row, whatever the rows hold (the kernel `cake_retention_state`), and
the division.

A PART of `programs.decode.attn_ms` (the scope is nested in `cake.attn`),
as `programs.decode.attn_linear_ms` is: the top-level readers still add up
to the mean op time of an execution without this one.

The MEAN over the window's `_decode_slots` executions of the summed device
time of the ops traced under `cake.attn.retention`, nested scopes included.
A scope is read from the op's `tf_op` by `[/(]cake\.<scope>[/)]`
(`trace_reduce.Trace.scope_ms`). A program with no such scope (a model
without retention layers, a parent commit) gives None: the metric is left
out of the line.
"""

PROGRAM = "_decode_slots"
SCOPE = "attn.retention"


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
