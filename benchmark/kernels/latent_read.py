"""Operations and bytes that the absorbed read of ONE decode step NEEDS,
whatever implements it (the Pallas kernel `cake_latent_decode_attention`,
or XLA's masked read where it is off: both are judged by the same counts).

The rows of a step hold `kv_tokens` tokens between them (the sum of their
frontiers). In every layer each held token's latent, `kv_lora_rank` +
`qk_rope_head_dim` numbers in bfloat16 (the configuration's precision), is
read ONCE, and every head takes its score against it (a multiply and an add
a number of the latent) and its weighted sum of the latent's value part (a
multiply and an add a number of `kv_lora_rank`). Positions beyond a row's
frontier, rows the step masks out, lanes of padding behind a latent, a
second read of a block for the values, the queries, the output and the
softmax's own arithmetic are the implementation's, not the need.
"""
BYTES = 2       # bfloat16 latents


def counts(hf: dict, kv_tokens: int) -> tuple[float, float]:
    layers, heads = hf["num_hidden_layers"], hf["num_attention_heads"]
    width = hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    nbytes = kv_tokens * layers * width * BYTES
    flops = kv_tokens * layers * heads * 2.0 * (width + hf["kv_lora_rank"])
    return flops, float(nbytes)
