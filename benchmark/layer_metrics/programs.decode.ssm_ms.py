r"""programs: op time of one batched decode under the scope `cake.ssm`:
the state-space mixer of every Mamba layer (models/jamba.py), its
projections, conv and state update included.

The MEAN over the window's `_decode_slots` executions (every slot bucket
the window ran) of the summed device time of the ops traced under
`cake.ssm`, nested scopes included. With its siblings
`programs.decode.*_ms` it adds up to the mean op time of an execution:
each op counts in exactly one of them (medians would not add). A scope is
read from the op's `tf_op` by `[/(]cake\.<scope>[/)]`
(`trace_reduce.Trace.scope_ms`). A program with no such scope (a model
without state-space layers, a parent commit) gives None: the metric is
left out of the line.
"""
import os

PROGRAM = "_decode_slots"
SCOPE = os.path.basename(__file__)[len("programs.decode."):-len("_ms.py")]


def read(ctx):
    return ctx.trace.scope_ms(PROGRAM, SCOPE)
