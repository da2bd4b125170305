"""Qwen3 (dense) forward pass, plain: float32, highest matmul precision,
no cache, no kernels, no batching. Written from the published description
(Qwen3 technical report; `Qwen3ForCausalLM`): pre-norm RMSNorm, grouped-query
attention with a per-head RMSNorm on q and k before rotary embedding
(half-rotation, theta from the config), causal softmax at 1/sqrt(head_dim),
SwiGLU MLP, final RMSNorm, output head (the embedding when tied).

Imports nothing from the program. Takes the benchmark's own weights
(benchmark/weights.py, published `[out, in]` layout) and token ids, and
returns logits at the positions asked for. Each layer's weights are cast up
as the walk reaches them, so the model never sits in float32 at once.

`quant="int8"` (or "fp8") is the CONTROL, not a reference: the same walk with
the operands of every matrix product rounded to int8 (a scale per output
channel of the weights and per token of the activations) or to float8 e4m3 —
the precision below bfloat16 that a later PR might be tempted by. The output
check must call it not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# rows of the output head multiplied at a time (f32 cast of the whole table
# would be 1.5 GB at the published vocabulary)
HEAD_ROWS = 16384
# init std of every projection (the family's initializer_range)
STD = 0.02


def _fake_int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _fake_fp8(x, axis):
    """float8 e4m3 with a scale per row: 3 bits of mantissa."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant=None):
    """x [..., in] @ w[out, in]^T in float32."""
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, -1)
    elif quant == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, -1)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.einsum("...i,oi->...o", x, w, precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x [S, H, D], pos [S]; rotate-half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(x, p, c, quant=None):
    s = x.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, pos = c["rms_norm_eps"], jnp.arange(s)
    q = mm(x, p["q_proj"]["weight"], quant).reshape(s, hq, d)
    k = mm(x, p["k_proj"]["weight"], quant).reshape(s, hkv, d)
    v = mm(x, p["v_proj"]["weight"], quant).reshape(s, hkv, d)
    q = rope(rms_norm(q, p["q_norm"]["weight"], eps), pos, c["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"]["weight"], eps), pos, c["rope_theta"])
    g = hq // hkv
    qg = q.reshape(s, hkv, g, d).transpose(1, 2, 0, 3)      # [hkv, g, s, d]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # [hkv, s, d]
    causal = pos[None, :] <= pos[:, None]

    def one_group(args):
        qh, kh, vh = args
        sc = jnp.einsum("gqd,kd->gqk", qh, kh, precision=HI) / np.sqrt(d)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(sc, -1), vh,
                          precision=HI)

    o = jax.lax.map(one_group, (qg, kg, vg))                # [hkv, g, s, d]
    o = o.transpose(2, 0, 1, 3).reshape(s, hq * d)
    return mm(o, p["o_proj"]["weight"], quant)


def swiglu(x, gate, up, down, quant=None):
    return mm(jax.nn.silu(mm(x, gate, quant)) * mm(x, up, quant), down,
              quant)


def mlp(x, p, c, quant=None):
    return swiglu(x, p["gate_proj"]["weight"], p["up_proj"]["weight"],
                  p["down_proj"]["weight"], quant)


def make_layer_fn(mlp_fn):
    @functools.partial(jax.jit, static_argnames=("c", "quant"))
    def layer(x, p, c, quant=None):
        c = dict(c)
        eps = c["rms_norm_eps"]
        x = x + attention(rms_norm(x, p["input_layernorm"]["weight"], eps),
                          p["self_attn"], c, quant)
        return x + mlp_fn(
            rms_norm(x, p["post_attention_layernorm"]["weight"], eps),
            p["mlp"], c, quant)
    return layer


_layer = make_layer_fn(mlp)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_w, table, eps, quant=None):
    x = rms_norm(x, norm_w, eps)
    v = table.shape[0]
    out = []
    for r0 in range(0, v, HEAD_ROWS):
        out.append(mm(x, table[r0:r0 + HEAD_ROWS], quant))
    return jnp.concatenate(out, -1)


def attention_leaves(hf: dict) -> dict:
    h, d = hf["hidden_size"], hf["head_dim"]
    sq, skv = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    return {"q_proj": {"weight": ((sq, h), STD)},
            "k_proj": {"weight": ((skv, h), STD)},
            "v_proj": {"weight": ((skv, h), STD)},
            "o_proj": {"weight": ((h, sq), STD)},
            "q_norm": {"weight": ((d,), None)},
            "k_norm": {"weight": ((d,), None)}}


def layer_leaves(hf: dict, mlp_leaves=None) -> dict:
    """One layer of the published checkpoint as a tree of (shape, init std;
    None = a norm weight, spread around 1): benchmark/weights.py fills it."""
    h, i = hf["hidden_size"], hf["intermediate_size"]
    return {"self_attn": attention_leaves(hf),
            "input_layernorm": {"weight": ((h,), None)},
            "post_attention_layernorm": {"weight": ((h,), None)},
            "mlp": mlp_leaves or {
                "gate_proj": {"weight": ((i, h), STD)},
                "up_proj": {"weight": ((i, h), STD)},
                "down_proj": {"weight": ((h, i), STD)}}}


def _static(hf: dict) -> tuple:
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "num_experts",
            "num_experts_per_tok", "norm_topk_prob")
    return tuple(sorted((k, hf[k]) for k in keep if k in hf))


def forward_logits(hf: dict, weights: dict, ids, positions, quant=None,
                   layer_fn=None) -> np.ndarray:
    """Logits [len(positions), vocab] (float32, on the host) of the full
    forward pass over `ids` at the given positions."""
    layer_fn = layer_fn or _layer
    c = _static(hf)
    x = jnp.take(weights["embed_tokens"]["weight"],
                 jnp.asarray(np.asarray(ids, np.int32)), axis=0
                 ).astype(jnp.float32)
    for p in weights["layers"]:
        x = layer_fn(x, p, c, quant)
    table = (weights["embed_tokens"] if hf.get("tie_word_embeddings")
             else weights["lm_head"])["weight"]
    rows = x[jnp.asarray(np.asarray(positions, np.int32))]
    return np.asarray(_head(rows, weights["norm"]["weight"], table,
                            hf["rms_norm_eps"], quant))
