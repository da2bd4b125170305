"""Jamba forward pass, plain (see qwen3.py for the shared parts and the
rules): float32, highest matmul precision, no cache, no kernels. Written
from the published `JambaForCausalLM` (AI21 Jamba / Jamba2; the slow path of
its `JambaMambaMixer`) and the Mamba paper's selective state space.

Every layer is pre-norm `x + Mixer(RMSNorm(x))`, `x + SwiGLU(RMSNorm(x))`.
Layer `i` mixes by attention iff `i % attn_layer_period ==
attn_layer_offset`, else by Mamba-1. `num_experts` 1: every FFN is dense.

Attention: no rotary embedding, no q/k norm, no bias, grouped queries over
`num_key_value_heads` heads, causal softmax at 1/sqrt(head_dim).

Mamba, for token t of the normed input u (d_inner = mamba_expand x hidden):
    [xr_t ; z_t]      = W_in u_t
    xc_t              = silu(sum_j w_conv[:, j] xr_{t-3+j} + b_conv)
    [dt_t; B_t; C_t]  = W_x xc_t
    dt_t              = softplus(W_dt RMSNorm_dt(dt_t) + b_dt)
    B_t, C_t          = RMSNorm_b(B_t), RMSNorm_c(C_t)
    h_t               = exp(dt_t A) h_{t-1} + (dt_t xc_t) B_t^T,  A = -exp(A_log)
    out_t             = W_out ((h_t C_t + D xc_t) silu(z_t))
one token at a time under `lax.scan` (the state is [d_inner, d_state]; no
[tokens, d_inner, d_state] array is ever made, so thousands of tokens at the
published widths fit).

The tree is named as the program's: `mamba`, `self_attn`, `mlp`,
`input_layernorm`, `post_attention_layernorm` (the checkpoint's
`feed_forward` and `pre_ff_layernorm`).

`quant` is the precision control of qwen3.py. `drop_state_at` is a SECOND
control, for this family's own mechanism: token indices before which the
Mamba state and the conv tail are zeroed, as a program would leave them that
lost a row's recurrent state between two dispatches. The output check must
call that not correct either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import qwen3
from .qwen3 import HI, STD, mm, rms_norm

# log-normal |A| over seven decades: a quarter of the states keep half their
# content for more than 8 tokens, about 2 % for more than 400, so a state
# lost at a chunk boundary shows at a check point hundreds of tokens later
A_LOG_STD = 3.0
CONV_STD = 0.3


def is_attention(hf: dict, i: int) -> bool:
    return i % hf["attn_layer_period"] == hf["attn_layer_offset"]


def _dims(hf: dict) -> tuple[int, int, int, int]:
    return (hf["mamba_expand"] * hf["hidden_size"], hf["mamba_d_state"],
            hf["mamba_dt_rank"], hf["mamba_d_conv"])


def layer_trees(hf: dict) -> list:
    if hf.get("num_experts", 1) != 1:
        raise ValueError("reference/jamba.py: dense FFNs only (num_experts 1)")
    h, i = hf["hidden_size"], hf["intermediate_size"]
    di, n, r, k = _dims(hf)
    d = hf.get("head_dim") or h // hf["num_attention_heads"]
    sq, skv = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    rest = {"input_layernorm": {"weight": ((h,), None)},
            "post_attention_layernorm": {"weight": ((h,), None)},
            "mlp": {"gate_proj": {"weight": ((i, h), STD)},
                    "up_proj": {"weight": ((i, h), STD)},
                    "down_proj": {"weight": ((h, i), STD)}}}
    mamba = {**rest, "mamba": {
        "in_proj": {"weight": ((2 * di, h), STD)},
        "conv1d": {"weight": ((di, 1, k), CONV_STD), "bias": ((di,), STD)},
        "x_proj": {"weight": ((r + 2 * n, di), STD)},
        "dt_proj": {"weight": ((di, r), r ** -0.5), "bias": ((di,), STD)},
        "A_log": ((di, n), A_LOG_STD),
        "D": ((di,), None),
        "out_proj": {"weight": ((h, di), STD)},
        "dt_layernorm": {"weight": ((r,), None)},
        "b_layernorm": {"weight": ((n,), None)},
        "c_layernorm": {"weight": ((n,), None)}}}
    attn = {**rest, "self_attn": {
        "q_proj": {"weight": ((sq, h), STD)},
        "k_proj": {"weight": ((skv, h), STD)},
        "v_proj": {"weight": ((skv, h), STD)},
        "o_proj": {"weight": ((h, sq), STD)}}}
    return [attn if is_attention(hf, j) else mamba
            for j in range(hf["num_hidden_layers"])]


def attention(x, p, c, quant=None):
    s = x.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    pos = jnp.arange(s)
    q = mm(x, p["q_proj"]["weight"], quant).reshape(s, hkv, hq // hkv, d)
    k = mm(x, p["k_proj"]["weight"], quant).reshape(s, hkv, d)
    v = mm(x, p["v_proj"]["weight"], quant).reshape(s, hkv, d)
    causal = pos[None, :] <= pos[:, None]

    def one_head(qh):                                   # [s, hkv, d]
        sc = jnp.einsum("qhd,khd->hqk", qh, k, precision=HI) / np.sqrt(d)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    o = jax.lax.map(one_head, q.transpose(2, 0, 1, 3))  # [g, s, hkv, d]
    o = o.transpose(1, 2, 0, 3).reshape(s, hq * d)
    return mm(o, p["o_proj"]["weight"], quant)


def mamba(x, p, c, quant=None, drop=None):
    """x [S, hidden]; drop [S] bool or None: True where the state and the
    conv tail are zeroed before the token."""
    s = x.shape[0]
    di, n, r, k = c["d_inner"], c["d_state"], c["dt_rank"], c["d_conv"]
    eps = c["rms_norm_eps"]
    f32 = jnp.float32
    drop = jnp.zeros((s,), bool) if drop is None else drop
    xz = mm(x, p["in_proj"]["weight"], quant)
    xr, z = xz[:, :di], xz[:, di:]
    w = p["conv1d"]["weight"].astype(f32)[:, 0, :]              # [di, k]
    b_conv = p["conv1d"]["bias"].astype(f32)

    def conv_step(tail, inp):                                   # [k-1, di]
        xr_t, drop_t = inp
        tail = jnp.where(drop_t, 0.0, tail)
        win = jnp.concatenate([tail, xr_t[None]])               # [k, di]
        return win[1:], jnp.sum(win * w.T, axis=0) + b_conv

    _, pre = jax.lax.scan(conv_step, jnp.zeros((k - 1, di), f32), (xr, drop))
    xc = jax.nn.silu(pre)                                       # [S, di]
    par = mm(xc, p["x_proj"]["weight"], quant)
    dt = rms_norm(par[:, :r], p["dt_layernorm"]["weight"], eps)
    bm = rms_norm(par[:, r:r + n], p["b_layernorm"]["weight"], eps)
    cm = rms_norm(par[:, r + n:], p["c_layernorm"]["weight"], eps)
    dt = jax.nn.softplus(mm(dt, p["dt_proj"]["weight"], quant)
                         + p["dt_proj"]["bias"].astype(f32))    # [S, di]
    a = -jnp.exp(p["A_log"].astype(f32))                        # [di, n]

    def step(h, inp):
        dt_t, xc_t, b_t, c_t, drop_t = inp
        h = jnp.where(drop_t, 0.0, h)
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * xc_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((di, n), f32),
                        (dt, xc, bm, cm, drop))
    y = (y + p["D"].astype(f32) * xc) * jax.nn.silu(z)
    return mm(y, p["out_proj"]["weight"], quant)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _layer(x, p, c, quant=None, drop=None):
    c = dict(c)
    eps = c["rms_norm_eps"]
    h = rms_norm(x, p["input_layernorm"]["weight"], eps)
    x = x + (mamba(h, p["mamba"], c, quant, drop) if "mamba" in p
             else attention(h, p["self_attn"], c, quant))
    return x + qwen3.mlp(
        rms_norm(x, p["post_attention_layernorm"]["weight"], eps),
        p["mlp"], c, quant)


def forward_logits(hf: dict, weights: dict, ids, positions, quant=None,
                   drop_state_at=None) -> np.ndarray:
    """Logits [len(positions), vocab] (float32, on the host) of the full
    forward pass over `ids` at the given positions."""
    di, n, r, k = _dims(hf)
    h = hf["hidden_size"]
    c = tuple(sorted({
        "num_attention_heads": hf["num_attention_heads"],
        "num_key_value_heads": hf["num_key_value_heads"],
        "head_dim": hf.get("head_dim") or h // hf["num_attention_heads"],
        "rms_norm_eps": hf["rms_norm_eps"], "d_inner": di, "d_state": n,
        "dt_rank": r, "d_conv": k}.items()))
    ids = np.asarray(ids, np.int32)
    drop = None
    if drop_state_at is not None:
        drop = np.zeros(len(ids), bool)
        drop[[p for p in drop_state_at if 0 < p < len(ids)]] = True
        drop = jnp.asarray(drop)
    x = jnp.take(weights["embed_tokens"]["weight"], jnp.asarray(ids), axis=0
                 ).astype(jnp.float32)
    for p in weights["layers"]:
        x = _layer(x, p, c, quant, drop)
    table = (weights["embed_tokens"] if hf.get("tie_word_embeddings")
             else weights["lm_head"])["weight"]
    rows = x[jnp.asarray(np.asarray(positions, np.int32))]
    return np.asarray(qwen3._head(rows, weights["norm"]["weight"], table,
                                  hf["rms_norm_eps"], quant))
