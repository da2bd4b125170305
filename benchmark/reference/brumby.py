"""Brumby forward pass, plain (see qwen3.py for the shared parts and the
rules): float32, highest matmul precision, no cache, no state, no expansion
of keys. Written from the published configuration (`model_type: brumby`,
manifestai Brumby-14B-Base: Qwen3-14B's config key for key) and, for the
mixer, from power retention (arXiv:2507.04239 "Scaling Context Requires
Rethinking Attention") and the Brumby-14B-Base release note. What no key of
the config settles is marked (+) here and is listed, with its ground, under
`assumed` in the configuration's file.

Every layer, pre-norm, `n` = RMSNorm with `rms_norm_eps`:
    h = n(x)
    q = rope(n_head(W_q h)) as [Hq, d];  k = rope(n_head(W_k h)),
        v = W_v h as [Hkv, d]: Qwen3's projections, per-head RMS norms
        (`q_norm`, `k_norm`) and rope (theta `rope_theta`, all d dims), no
        bias ((+) kept from Qwen3: the config keeps `rope_theta` and has no
        key that turns either off)
    log g_t = logsigmoid(W_g h_t + b_g), one number a key/value head and
        token ((+) the gate's form, `W_g` [Hkv, hidden], the bias)
    B_t = sum_{j <= t} log g_j;  for s <= t, query head a on key/value head
        floor(a / (Hq / Hkv)):
        A_ts = exp(B_t - B_s) (q_t . k_s)^p,  p = 2 ((+) the degree)
        y_t = sum_s A_ts v_s / (sum_s A_ts + eps)  ((+) the normaliser and
        eps 1e-6; with an even p every weight is >= 0, and the 1 / sqrt(d)
        scale cancels between the two sums and is left out of both)
    x <- x + W_o y
    x <- x + SwiGLU(n(x))
then the final norm and the untied head.

This is the ATTENTION form: a row of weights over all earlier keys, made
from q . k directly. The program holds the recurrent form (a state of the
keys' symmetric squares against the values) and runs its chunk form; the
two agree because phi(q) . phi(k) = (q . k)^2. A key/value group's rows of
weights are [Hq / Hkv, S, S]: 26 MB at the check's 1,136 tokens, one group
at a time (`lax.map`), so the walk fits beside the weights.

`quant` is the precision control of qwen3.py. Three further controls, for
this family's own mechanisms, each what a program would serve that lacked
it: `gate="off"` (log g = 0: nothing fades), `power=1` (plain linear
attention), `state="dropped"` (keys before the last `state_block`-token
boundary at or before the query masked out: a chunk that failed to carry
the state in). The output check must call each not correct
(benchmark/tests/brumby_controls.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import qwen3
from .qwen3 import HI, STD, mm, rms_norm, rope

EPS = 1e-6
# Initialisers (benchmark/weights.py draws N(0, std), or 1 + 0.1 N(0, 1)
# for std None, and nothing else). PERF.md section 6 (PR 53) has the
# readings each was set on, and those of what was tried and taken back.
#
# The gate's bias, one a key/value head of a layer, N(0, 8): g = sigmoid(b)
# keeps half a key's weight for more than ~280 tokens in 23 % of the 64
# head-layers (b > 6) and for more than ~11 in 37 %, while 23 % forget
# within a token (b < -6). With a zero bias and a W_g of 0.02 every head
# would halve each token, and a program that lost a row's state at a chunk
# boundary would read correct 256 tokens later (jamba2-3b's A_log, solar's
# dt_bias). ISSUE 53 asked for N(0, 4): weights.py draws no mean, so as many
# heads forget at once as remember, and at N(0, 4) half of them lie between,
# where one to three keys share a query's weight and a rounding of q . k
# moves the output most: the dropped-state control then read 0.025 beside a
# sound 0.013 (6 layers, CPU), at N(0, 8) 0.044 beside 0.009. The token's
# part of the logit has std ~1: W_g N(0, 1 / sqrt(hidden)) under a normed
# input.
GATE_BIAS_STD = 8.0
GATE_LOGIT_STD = 1.0
# The projections that write to the stream (o_proj, down_proj) are drawn at
# STD / sqrt(2 x 40): the scaled initialiser of GPT-2 and Megatron-LM for
# residual projections, at the PUBLISHED depth of 40 layers, so that a
# sublayer adds 0.16-0.3 a channel to the stream; the embedding's rows at
# EMBED_SCALE x STD x sqrt(hidden) = 1.07 are then the largest part of the
# stream through all 8 layers, as a trained model's are (solar_open2.py's
# EMBED_SCALE, for its reason). With every leaf at 0.02 a sublayer adds 2-3
# a channel to an embedding of 0.02: each layer's input is then the layers'
# own output before it, power retention's weights (q . k)^2 turn sharply on
# that input, and a rounding grows ~1.35 x a layer: 0.031 at 3 layers, 0.059
# at 6 on the CPU, 0.09-0.20 at 8 on the chip beside an int8 control of
# 0.23-0.28 and a dropped state of 0.10-0.17: no limit fits between.
RESIDUAL_STD = STD / (2 * 40) ** 0.5
EMBED_SCALE = 0.75


def layer_leaves(hf: dict) -> dict:
    """One layer (all alike): Qwen3's leaves and the gate's projection."""
    h, hkv = hf["hidden_size"], hf["num_key_value_heads"]
    leaves = qwen3.layer_leaves(hf)
    for part, proj in (("self_attn", "o_proj"), ("mlp", "down_proj")):
        shape, _ = leaves[part][proj]["weight"]
        leaves[part][proj]["weight"] = (shape, RESIDUAL_STD)
    leaves["self_attn"]["g_proj"] = {
        "weight": ((hkv, h), GATE_LOGIT_STD / h ** 0.5),
        "bias": ((hkv,), GATE_BIAS_STD)}
    return leaves


def top_leaves(hf: dict) -> dict:
    """What lies outside the layers: the embedding (EMBED_SCALE above), the
    final norm, the untied head."""
    v, h = hf["vocab_size"], hf["hidden_size"]
    return {"embed_tokens": {"weight": ((v, h),
                                        EMBED_SCALE * STD * h ** 0.5)},
            "norm": {"weight": ((h,), None)},
            "lm_head": {"weight": ((v, h), STD)}}


def retention(x, p, c, quant=None, gate="on", power=2, drop=None):
    """x [S, hidden] (normed) -> [S, hidden]; `drop`: None, or the block
    whose boundaries the state does not cross."""
    s = x.shape[0]
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, pos = c["rms_norm_eps"], jnp.arange(s)
    q = mm(x, p["q_proj"]["weight"], quant).reshape(s, hq, d)
    k = mm(x, p["k_proj"]["weight"], quant).reshape(s, hkv, d)
    v = mm(x, p["v_proj"]["weight"], quant).reshape(s, hkv, d)
    q = rope(rms_norm(q, p["q_norm"]["weight"], eps), pos, c["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"]["weight"], eps), pos, c["rope_theta"])
    log_g = jax.nn.log_sigmoid(
        mm(x, p["g_proj"]["weight"], quant)
        + p["g_proj"]["bias"].astype(jnp.float32))          # [S, Hkv]
    if gate == "off":
        log_g = jnp.zeros_like(log_g)
    run = jnp.cumsum(log_g, axis=0).T                       # B_t  [Hkv, S]
    seen = pos[None, :] <= pos[:, None]                     # [t, s]
    if drop is not None:
        seen = seen & (pos[None, :] >= (pos[:, None] // drop) * drop)
    g = hq // hkv
    qg = q.reshape(s, hkv, g, d).transpose(1, 2, 0, 3)      # [Hkv, g, S, d]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # [Hkv, S, d]

    def one_group(args):
        qh, kh, vh, bh = args
        sc = jnp.einsum("gqd,kd->gqk", qh, kh, precision=HI) ** power
        fade = jnp.where(seen, bh[:, None] - bh[None, :], -jnp.inf)
        a = jnp.exp(fade)[None] * sc                        # [g, t, s]
        num = jnp.einsum("gqk,kd->gqd", a, vh, precision=HI)
        return num / (jnp.sum(a, axis=-1, keepdims=True) + EPS)

    o = jax.lax.map(one_group, (qg, kg, vg, run))           # [Hkv, g, S, d]
    o = o.transpose(2, 0, 1, 3).reshape(s, hq * d)
    return mm(o, p["o_proj"]["weight"], quant)


@functools.partial(jax.jit, static_argnames=("c", "quant", "gate", "power",
                                             "drop"))
def _layer(x, p, c, quant=None, gate="on", power=2, drop=None):
    c = dict(c)
    eps = c["rms_norm_eps"]
    x = x + retention(rms_norm(x, p["input_layernorm"]["weight"], eps),
                      p["self_attn"], c, quant, gate, power, drop)
    return x + qwen3.mlp(
        rms_norm(x, p["post_attention_layernorm"]["weight"], eps),
        p["mlp"], c, quant)


def forward_logits(hf: dict, weights: dict, ids, positions, quant=None,
                   gate="on", power=2, state="kept",
                   state_block=256) -> np.ndarray:
    """Logits [len(positions), vocab] (float32, on the host) of the full
    forward pass over `ids` at the given positions."""
    if state not in ("kept", "dropped"):
        raise ValueError(f"unknown state control {state!r}")
    drop = state_block if state == "dropped" else None
    return qwen3.forward_logits(
        hf, weights, ids, positions, quant,
        layer_fn=lambda x, p, c, quant: _layer(x, p, c, quant, gate, power,
                                               drop))
