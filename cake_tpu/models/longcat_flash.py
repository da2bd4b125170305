"""LongCat-Flash (meituan-longcat LongCat-Flash-Chat, `model_type:
longcat_flash`): a SHORTCUT-CONNECTED block. One of the `num_layers`
published layers holds two latent-attention sub-layers, two dense FFNs and
ONE sparse layer whose output skips a whole sub-layer. Pre-norm, `n` =
RMSNorm at `rms_norm_eps`; i = 0, 1 are the layer's two sub-layers, each
with its own `input_layernorm[i]`, `self_attn[i]`,
`post_attention_layernorm[i]` and dense `mlps[i]`; `mlp` is the one sparse
layer:

    1.  x  = x + MLA_0(n_in0(x))
    2.  h0 = n_post0(x);  m = MoE(h0)  (held back);  x = x + FFN_0(h0)
    3.  x  = x + MLA_1(n_in1(x))
    4.  x  = x + FFN_1(n_post1(x)) + m

The second attention and the second dense FFN never see `m`: in a
deployment the experts' exchange runs while they compute. (The order is the
family's published decoder layer as this repo knows it, unconfirmed
offline: `assumed` in the benchmark's configuration.)

  FFN_i  a SwiGLU of `ffn_hidden_size`.
  MLA_i  models/deepseek_v2.py's one rule (absorbed wherever a cache is
         read) with both latents scaled: c_q = n(W_qa h) (hidden /
         q_lora_rank)^1/2, c_kv = n(c) (hidden / kv_lora_rank)^1/2 where
         `mla_scale_q_lora` / `mla_scale_kv_lora` are true (the form is the
         family's convention, in no key); the shared rope key part is not
         scaled; plain rope at `rope_theta` on the `qk_rope_head_dim` dims;
         softmax scale (nope + rope)^-1/2. A row of the pool holds
         [n(c) ; rope(k_pe)] UNSCALED: the kv scale is folded where W_uk
         and W_uv are absorbed (LatentAttnConfig.kv_scale).
  MoE    s = softmax(W_r h0) in float32 over `n_routed_experts` +
         `zero_expert_num` outputs; the top `moe_topk` of s +
         `e_score_correction_bias`; weights s at the chosen, NOT
         normalised, times `routed_scaling_factor`. The first
         `n_routed_experts` outputs are SwiGLU experts of
         `expert_ffn_hidden_size`, the last `zero_expert_num` identity
         experts: m = sum_{real picks} w_e E_e(h0) + (sum_{zero picks} w_z)
         h0 (ops/moe.py: `zero_experts`). A share of an expert-parallel
         group computes its held experts' part and the WHOLE identity term
         (it belongs to the chip a token lives on; counted once when shares
         add up).

HOW THE BLOCK MEETS THE LAYER LIST. The program's layer list is the
2 x `num_layers` SUB-layers (`ModelConfig.shortcut_pairs`): each a latent
mixer and a dense FFN with a cache entry of its own, so cache.py, both
pools, the row operations and the prefix cache go by the leaves untouched.
`LayerSpec.shortcut` says of an even entry that it OPENS a pair (its tree
also holds the sparse layer, `moe`, which reads its post-attention norm)
and of an odd one that it CLOSES it (it adds `m` last);
`layers.forward_layers` carries `m` between the two and refuses a
`layer_range` that separates them.

The published checkpoint's tensor names are not known offline, so nothing
is loaded or exported under this family's name yet (`refuse_checkpoint`):
weights are made in place (`init_params`, the benchmark's from a seed).
"""
from __future__ import annotations


def refuse_checkpoint(what: str):
    raise NotImplementedError(
        f"longcat_flash: {what} a checkpoint is not implemented: a layer "
        "of the published file is two entries of this program's layer list "
        "and its tensor names (`self_attn.{0,1}`, `mlps.{0,1}`, "
        "`mlp.experts`, `mlp.router.classifier` are guesses) are "
        "unconfirmed offline")
