"""Operations and bytes that one call of `cake_flash_attention` NEEDS.

One call is one layer's attention for one prefill chunk: `tokens` valid
queries at absolute positions pos0 .. pos0+tokens-1, each attending to the
keys at or before it (causal, valid-length-limited). Work on masked pairs,
on padding rows of the chunk bucket and on the buffer beyond the frontier is
not needed and is not counted.
"""
BYTES = 2       # bfloat16


def counts(hf: dict, pos0: int, tokens: int) -> tuple[float, float]:
    hq, hkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    pairs = tokens * pos0 + tokens * (tokens + 1) // 2    # (query, key)
    flops = 4.0 * d * hq * pairs            # q.k and p.v, 2 flops a MAC
    kv_len = pos0 + tokens
    nbytes = BYTES * d * (2 * tokens * hq     # read q, write out
                          + 2 * kv_len * hkv)  # read k and v once
    return flops, float(nbytes)
