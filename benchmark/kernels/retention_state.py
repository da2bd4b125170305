"""Operations and bytes that the state pass of ONE decode step NEEDS,
whatever implements it (the Pallas kernel `cake_retention_state`, or XLA's
fusions where it is off: both are judged by the same counts).

One step advances `rows` rows: in every layer, every key/value head's state
S [D, d] and normaliser z [D] (D = d (d + 1) / 2, the minimal width of the
symmetric square; float32, the configuration's precision) are read once
and written once, every key/value head's S takes the decayed update (a
multiply and an add an element), and every query head reads S out (a
multiply and an add an element). Rows the step masks out need nothing and
are not counted; a layout wider than D, a second read of S for the
read-out, and the expansion of q and k are the implementation's, not the
need.
"""
BYTES = 4       # float32 state


def counts(hf: dict, rows: int) -> tuple[float, float]:
    layers = hf["num_hidden_layers"]
    hq, hkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    width = d * (d + 1) // 2
    nbytes = rows * layers * hkv * (width * d + width) * BYTES * 2
    flops = rows * layers * 2.0 * width * d * (hkv + hq)
    return flops, float(nbytes)
