"""Qwen3-MoE forward pass, plain (see qwen3.py for the shared parts and the
rules). The MLP of every layer is a sparse mixture (`Qwen3MoeSparseMoeBlock`,
`decoder_sparse_step` 1, no shared expert): router logits over all experts,
softmax in float32, the top `num_experts_per_tok`, their probabilities
renormalised when `norm_topk_prob`, and the weighted sum of those experts'
SwiGLU outputs.

Plain means: every expert is applied to every token, one expert at a time,
and a dense [tokens, experts] weight matrix (zero where not routed) does the
selection. No sorting, grouping or capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import qwen3
from .qwen3 import mm, swiglu


# The router's init, four times that of the other matrices: a trained router
# separates its top experts; at 0.02 the 8th and 9th expert of nearly every
# token tie and bf16 rounding flips them, which no deployment sees.
ROUTER_STD = 0.08


def layer_leaves(hf: dict) -> dict:
    e, i, h = (hf["num_experts"], hf["moe_intermediate_size"],
               hf["hidden_size"])
    return qwen3.layer_leaves(hf, {
        "gate": {"weight": ((e, h), ROUTER_STD)},
        "experts": {"gate_proj": ((e, i, h), qwen3.STD),
                    "up_proj": ((e, i, h), qwen3.STD),
                    "down_proj": ((e, h, i), qwen3.STD)}})


def route(x, gate_w, c, quant=None):
    """Dense routing weights [S, E] and the chosen experts [S, k]."""
    probs = jax.nn.softmax(mm(x, gate_w, quant), axis=-1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c.get("norm_topk_prob"):
        top = top / jnp.sum(top, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top), idx


def moe(x, p, c, quant=None):
    dense, _ = route(x, p["gate"]["weight"], c, quant)
    ex = p["experts"]

    def one(acc, args):
        g, u, d, w = args
        return acc + w[:, None] * swiglu(x, g, u, d, quant), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (ex["gate_proj"], ex["up_proj"], ex["down_proj"], dense.T))
    return acc


_layer = qwen3.make_layer_fn(moe)


def forward_logits(hf: dict, weights: dict, ids, positions, quant=None
                   ) -> np.ndarray:
    return qwen3.forward_logits(hf, weights, ids, positions, quant,
                                layer_fn=_layer)


def experts_used(hf: dict, weights: dict, ids) -> int:
    """Distinct experts the first layer's router picks over `ids` (the
    check prints it: the prompt has to reach many experts)."""
    c = dict(qwen3._static(hf))
    x = jnp.take(weights["embed_tokens"]["weight"],
                 jnp.asarray(np.asarray(ids, np.int32)), axis=0
                 ).astype(jnp.float32)
    p = weights["layers"][0]
    x = qwen3.rms_norm(x, p["post_attention_layernorm"]["weight"],
                       hf["rms_norm_eps"])
    _, idx = route(x, p["mlp"]["gate"]["weight"], c)
    return int(np.unique(np.asarray(idx)).size)
