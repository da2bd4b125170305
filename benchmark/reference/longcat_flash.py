"""LongCat-Flash forward pass, plain (see qwen3.py for the shared parts and
the rules): float32, highest matmul precision, no cache, no kernels, the
EXPANDED form of latent attention only. Written from the published
configuration (meituan-longcat/LongCat-Flash-Chat, config.json) and the
family's published decoder layer as this repository knows it offline. What
no key of the configuration settles is marked (+) and listed under
`assumed` in the configuration's file.

ONE of the `num_layers` layers is a shortcut-connected block. `n` = RMSNorm
with `rms_norm_eps` (x / sqrt(mean x^2 + eps) . w); i = 0, 1 are its two
sub-layers, each with its own `input_layernorm`, `self_attn`,
`post_attention_layernorm` and dense `mlp`; `moe` is the layer's one sparse
layer (+ the order):

    1.  x  = x + MLA_0(n_in0(x))
    2.  h0 = n_post0(x);  m = MoE(h0)  (held back);  x = x + FFN_0(h0)
    3.  x  = x + MLA_1(n_in1(x))
    4.  x  = x + FFN_1(n_post1(x)) + m

    FFN_i   SwiGLU of width `ffn_hidden_size`
    MLA_i   H = `num_attention_heads`, widths `qk_nope_head_dim` (N),
            `qk_rope_head_dim` (R), `v_head_dim` (V), ranks `q_lora_rank`
            (Q), `kv_lora_rank` (C), h the normed input:
        c_q = n_q(W_qa h) . (hidden / Q)^1/2    (`mla_scale_q_lora`, + form)
        q = W_qb c_q as [H, N + R], a head [q_nope ; q_pe]
        [c ; k_pe] = W_kva h as [C ; R];  k_pe ONE key part for all heads
        c_kv = n_kv(c) . (hidden / C)^1/2       (`mla_scale_kv_lora`, + form)
        [k_nope^a ; v^a] = W_kvb^a c_kv as [N ; V] a head
        rope on the R dims of q_pe^a and of k_pe (NOT scaled), base
            `rope_theta`, no scaling; pairs interleaved, (2i, 2i + 1), as
            the lineage's checkpoints have them (+)
        causal softmax attention at (N + R)^-1/2;  W_o [o^1 ; .. ; o^H]
    MoE     s = softmax(W_r h0) in float32 over `n_routed_experts` (of the
            model) + `zero_expert_num` outputs; sel = the top `moe_topk` of
            s + `e_score_correction_bias`; w_e = s_e at the chosen, NOT
            renormalised (+), times `routed_scaling_factor`. Outputs
            0 .. n_routed - 1 are SwiGLU experts of width
            `expert_ffn_hidden_size`; the last `zero_expert_num` are
            identity experts (`zero_expert_type: identity`):
            m = sum_{e in sel, e real and held} w_e E_e(h0)
                + (sum_{z in sel, z identity} w_z) h0

then the final norm and the untied head.

GIVEN THE SAME SHARE as the program: with `expert_parallel: {size, rank}`
the weights hold `n_routed_experts` experts, numbers rank x held .. of the
size x held REAL experts the router scores beside its identity outputs;
what the absent experts would add is left out, here as there; the identity
term is whole (it belongs to the chip a token lives on), and that partial
result is what joins the stream. The vocabulary is the slice.

THE SAME ARRAYS as the program, whose layer list is the 2 x `num_layers`
SUB-layers: `layer_trees` gives one tree a sub-layer, an even one with the
pair's `moe` beside its dense `mlp`. As in deepseek_v2.py the rope rows of
`q_b_proj` and `kv_a_proj_with_mqa` lie de-interleaved for the program's
rope in halves; `deepseek_v2.to_checkpoint` puts them back and everything
here runs the interleaved rope.

`quant` is the precision control of qwen3.py. Eight further controls, each
what a program would serve that got one mechanism wrong
(benchmark/tests/longcat_flash_controls.py): `zero="off"` (the identity
term dropped), `shortcut="early"` (m added behind FFN_0: the unshortcut
block) and `shortcut="post1"` (m computed from n_post1's output),
`q_scale=1.0`, `kv_scale=1.0`, `select_bias="off"`, `router="real"` (the
softmax over the real outputs alone, an identity output scoring 0),
`routed_scale=1.0`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import deepseek_v2, qwen3
from .deepseek_v2 import HEAD_GROUP, rope, to_checkpoint
from .qwen3 import HI, STD, mm, rms_norm, swiglu

# Initialisers (benchmark/weights.py draws N(0, std), or 1 + 0.1 N(0, 1) for
# std None, and nothing else); the arithmetic is for the published widths
# (hidden 6144) under a normed input of unit RMS. The configuration's file
# (`assumed.weights`, `correct.readings`) has the chip's readings.
#
# Router: logits of std 0.8 over the 768 outputs (laguna.py's
# ROUTER_LOGIT_STD, for its reason). The top 12 of 768 then score 0.005 to
# 0.02 (weights 0.03-0.12 after the 6), about 4 of them identity experts
# and one pick in four tokens an expert of this share's 16.
ROUTER_LOGIT_STD = 0.8
# The selection bias: N(0, 0.001). The 12th and 13th best scores lie
# 0.00014 apart, so a bias of this size changes 3-4 of a token's 12 picks
# (a selection without it differs), and an expert whose bias is 4 sigma
# down is still picked ~5 times by the 2,400-token check prompt: every held
# expert is reached on any seed (experts_used needs 17 of 17).
SELECT_BIAS_STD = 0.001
# Latent attention: with both latents scaled the published initialiser
# gives q 1.57 a number (0.02 x 2 x sqrt(1536)) and k_nope, v 1.57 (0.02 x
# sqrt(12) x sqrt(512)), k_pe 1.57: scores of std ~2.5 under 192^-1/2, a
# softmax that picks tens of a few thousand keys, so position and both
# scales matter (DeepSeek-V2, whose latents carry no scale, needed 4 x STD
# on kv_b_proj for the same).
Q_B_STD = STD
KV_A_STD = STD
KV_B_STD = STD
# What writes to the stream (deepseek_v2.py has the reasons, found on the
# chip: the embedding the largest part, attention small because its
# rounding error sets how often a router near-tie flips in bfloat16, the
# dense FFNs large enough that int8 reads well over such a flip). The
# embedding 1.5 x STD x sqrt(hidden) = 2.35 a channel; o_proj STD / 8 (a
# sub-layer's attention adds ~0.1, eight of them ~0.25); a dense FFN's
# down_proj STD / 6 (each adds ~0.6, eight ~1.7); a routed expert's
# down_proj 1.5 x STD (a picked expert adds ~0.09 under a weight of ~0.045);
# the identity term is (sum of ~4 weights ~0.18) x h0 a layer. The first
# set (dense STD / 9, experts 2 x STD) read sound 0.0044-0.0130 of 12 seeds
# beside int8 0.0198-0.0250 on the chip: every reading over the floor of
# 0.0044 was ONE check point of 17 at 3-5 % where bfloat16 flips a held
# expert in or out at the selection's edge (weight ~0.03 x an expert's
# output), so two such points in one seed would have read 0.018. A flip's
# size goes with the held experts' part, what int8 reads with the dense
# FFNs': hence the one down by a quarter and the other up by half.
O_PROJ_STD = STD / 8
FFN_IN_STD = STD
DENSE_DOWN_STD = STD / 6
EXPERT_DOWN_STD = 1.5 * STD
EMBED_SCALE = 1.5


def share(hf: dict) -> tuple[int, int, int, int]:
    """(real experts the router scores, first held, held, identity
    experts): the router is the first + the last wide."""
    held = hf["n_routed_experts"]
    ep = hf.get("expert_parallel") or {"size": 1, "rank": 0}
    return (held * ep["size"], held * ep["rank"], held,
            int(hf.get("zero_expert_num") or 0))


def layer_trees(hf: dict) -> list:
    """One tree a SUB-layer, 2 x `num_layers` of them, as the program's
    layer list has them: an even one also holds the pair's sparse layer."""
    h, heads = hf["hidden_size"], hf["num_attention_heads"]
    n, r, v = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
               hf["v_head_dim"])
    ql, c = hf["q_lora_rank"], hf["kv_lora_rank"]
    real, _, held, zeros = share(hf)
    w, im = hf["ffn_hidden_size"], hf["expert_ffn_hidden_size"]
    attn = {"q_a_proj": {"weight": ((ql, h), STD)},
            "q_a_layernorm": {"weight": ((ql,), None)},
            "q_b_proj": {"weight": ((heads * (n + r), ql), Q_B_STD)},
            "kv_a_proj_with_mqa": {"weight": ((c + r, h), KV_A_STD)},
            "kv_a_layernorm": {"weight": ((c,), None)},
            "kv_b_proj": {"weight": ((heads * (n + v), c), KV_B_STD)},
            "o_proj": {"weight": ((h, heads * v), O_PROJ_STD)}}
    sub = {"self_attn": attn,
           "input_layernorm": {"weight": ((h,), None)},
           "post_attention_layernorm": {"weight": ((h,), None)},
           "mlp": {"gate_proj": {"weight": ((w, h), FFN_IN_STD)},
                   "up_proj": {"weight": ((w, h), FFN_IN_STD)},
                   "down_proj": {"weight": ((h, w), DENSE_DOWN_STD)}}}
    moe = {"gate": {"weight": ((real + zeros, h),
                               ROUTER_LOGIT_STD / h ** 0.5),
                    "e_score_correction_bias": ((real + zeros,),
                                                SELECT_BIAS_STD)},
           "experts": {"gate_proj": ((held, im, h), FFN_IN_STD),
                       "up_proj": ((held, im, h), FFN_IN_STD),
                       "down_proj": ((held, h, im), EXPERT_DOWN_STD)}}
    return [{**sub, "moe": moe} if j % 2 == 0 else sub
            for j in range(2 * hf["num_layers"])]


def top_leaves(hf: dict) -> dict:
    """What lies outside the layers: the embedding (EMBED_SCALE above), the
    final norm, the untied head."""
    v, h = hf["vocab_size"], hf["hidden_size"]
    return {"embed_tokens": {"weight": ((v, h),
                                        EMBED_SCALE * STD * h ** 0.5)},
            "norm": {"weight": ((h,), None)},
            "lm_head": {"weight": ((v, h), STD)}}


def rope_tables(s: int, hf: dict) -> tuple:
    """(cos, sin) [S, R/2] float32 of positions 0..S-1: plain rope."""
    if hf.get("rope_scaling"):
        raise ValueError(f"rope_scaling {hf['rope_scaling']}")
    return deepseek_v2.rope_tables(s, {**hf, "rope_scaling": None})


def attention(x, p, cos, sin, c, quant=None, q_scale=None, kv_scale=None):
    s = x.shape[0]
    heads, n, r, v, eps = c["heads"], c["nope"], c["rope"], c["v"], c["eps"]
    p = to_checkpoint(p, c)
    c_q = rms_norm(mm(x, p["q_a_proj"]["weight"], quant),
                   p["q_a_layernorm"]["weight"], eps)
    c_q = c_q * (c["q_scale"] if q_scale is None else q_scale)
    q = mm(c_q, p["q_b_proj"]["weight"], quant).reshape(s, heads, n + r)
    ckv = mm(x, p["kv_a_proj_with_mqa"]["weight"], quant)
    lat, k_pe = ckv[:, :c["kv_lora"]], ckv[:, None, c["kv_lora"]:]
    lat = rms_norm(lat, p["kv_a_layernorm"]["weight"], eps)
    lat = lat * (c["kv_scale"] if kv_scale is None else kv_scale)
    kv = mm(lat, p["kv_b_proj"]["weight"], quant).reshape(s, heads, n + v)
    q_pe, k_pe = rope(q[..., n:], cos, sin), rope(k_pe, cos, sin)
    qf = jnp.concatenate([q[..., :n], q_pe], -1)
    kf = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_pe, (s, heads, r))], -1)
    scale = (n + r) ** -0.5
    pos = jnp.arange(s)
    seen = pos[None, :] <= pos[:, None]
    g = HEAD_GROUP if heads % HEAD_GROUP == 0 else 1

    def group(args):
        qh, kh, vh = args                                   # [g, s, .]
        sc = jnp.einsum("gqd,gkd->gqk", qh, kh, precision=HI) * scale
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return jnp.einsum("gqk,gkd->gqd", jax.nn.softmax(sc, -1), vh,
                          precision=HI)

    def by_group(a):
        return a.transpose(1, 0, 2).reshape(heads // g, g, s, a.shape[-1])

    o = jax.lax.map(group, (by_group(qf), by_group(kf),
                            by_group(kv[..., n:])))
    o = o.reshape(heads, s, v).transpose(1, 0, 2).reshape(s, heads * v)
    return mm(o, p["o_proj"]["weight"], quant)


def route(x, gate, c, quant=None, select_bias="on", router="all",
          routed_scale=None):
    """Dense routing weights [S, real + identity outputs] and the chosen
    [S, k]."""
    logits = mm(x, gate["weight"], quant)
    if router == "all":
        probs = jax.nn.softmax(logits, axis=-1)
    elif router == "real":
        probs = jnp.concatenate(
            [jax.nn.softmax(logits[:, :c["real"]], axis=-1),
             jnp.zeros_like(logits[:, c["real"]:])], -1)
    else:
        raise ValueError(f"router {router!r}")
    pick = probs
    if select_bias == "on":
        pick = probs + gate["e_score_correction_bias"].astype(jnp.float32)
    elif select_bias != "off":
        raise ValueError(f"select_bias {select_bias!r}")
    _, idx = jax.lax.top_k(pick, c["k"])
    top = jnp.take_along_axis(probs, idx, axis=-1)
    top = top * (c["routed_scale"] if routed_scale is None else routed_scale)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top), idx


def sparse_ffn(x, p, c, quant=None, zero="on", select_bias="on",
               router="all", routed_scale=None):
    """(the sparse layer's output for normed tokens x, the choices): the
    held experts' part and the identity term."""
    dense, idx = route(x, p["gate"], c, quant, select_bias, router,
                       routed_scale)
    ex, first, held = p["experts"], c["first"], c["held"]

    def one(acc, args):
        g, u, d, w = args
        return acc + w[:, None] * swiglu(x, g, u, d, quant), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (ex["gate_proj"], ex["up_proj"], ex["down_proj"],
         dense[:, first:first + held].T))
    if zero == "on":
        acc = acc + jnp.sum(dense[:, c["real"]:], -1, keepdims=True) * x
    elif zero != "off":
        raise ValueError(f"zero {zero!r}")
    return acc, idx


CONTROLS = ("zero", "shortcut", "q_scale", "kv_scale", "select_bias",
            "router", "routed_scale")


@functools.partial(jax.jit, static_argnames=("c", "quant") + CONTROLS)
def _pair(x, p0, p1, cos, sin, c, quant=None, zero="on", shortcut="on",
          q_scale=None, kv_scale=None, select_bias="on", router="all",
          routed_scale=None):
    """(x after ONE published layer = two sub-layers, the router's choices
    [S, k])."""
    c = dict(c)
    eps = c["eps"]
    if shortcut not in ("on", "early", "post1"):
        raise ValueError(f"shortcut {shortcut!r}")

    def moe(h):
        return sparse_ffn(h, p0["moe"], c, quant, zero, select_bias, router,
                          routed_scale)

    def mla(x, p):
        return attention(rms_norm(x, p["input_layernorm"]["weight"], eps),
                         p["self_attn"], cos, sin, c, quant, q_scale,
                         kv_scale)

    x = x + mla(x, p0)
    h0 = rms_norm(x, p0["post_attention_layernorm"]["weight"], eps)
    m, idx = moe(h0)
    x = x + qwen3.mlp(h0, p0["mlp"], c, quant)
    if shortcut == "early":
        x, m = x + m, 0.0
    x = x + mla(x, p1)
    h1 = rms_norm(x, p1["post_attention_layernorm"]["weight"], eps)
    if shortcut == "post1":
        m, idx = moe(h1)
    return x + qwen3.mlp(h1, p1["mlp"], c, quant) + m, idx


def static(hf: dict) -> tuple:
    real, first, held, zeros = share(hf)
    h = hf["hidden_size"]
    c = {"heads": hf["num_attention_heads"], "nope": hf["qk_nope_head_dim"],
         "rope": hf["qk_rope_head_dim"], "v": hf["v_head_dim"],
         "kv_lora": hf["kv_lora_rank"], "eps": hf["rms_norm_eps"],
         "q_scale": ((h / hf["q_lora_rank"]) ** 0.5
                     if hf.get("mla_scale_q_lora") else 1.0),
         "kv_scale": ((h / hf["kv_lora_rank"]) ** 0.5
                      if hf.get("mla_scale_kv_lora") else 1.0),
         "real": real, "zeros": zeros, "first": first, "held": held,
         "k": hf["moe_topk"],
         "routed_scale": float(hf.get("routed_scaling_factor") or 1.0)}
    return tuple(sorted(c.items()))


def _walk(hf: dict, weights: dict, ids, quant=None, **controls):
    """(hidden states after the last layer, each sparse layer's choices)."""
    ids = np.asarray(ids, np.int32)
    x = jnp.take(weights["embed_tokens"]["weight"], jnp.asarray(ids), axis=0
                 ).astype(jnp.float32)
    cos, sin = rope_tables(len(ids), hf)
    c, chosen, subs = static(hf), [], weights["layers"]
    for p0, p1 in zip(subs[0::2], subs[1::2]):
        x, idx = _pair(x, p0, p1, cos, sin, c, quant, **controls)
        chosen.append(idx)
    return x, chosen


def forward_logits(hf: dict, weights: dict, ids, positions, quant=None,
                   **controls) -> np.ndarray:
    """Logits [len(positions), vocab] (float32, on the host) of the full
    forward pass over `ids` at the given positions; `controls`: the
    keywords named in CONTROLS (see the head)."""
    x, _ = _walk(hf, weights, ids, quant, **controls)
    rows = x[jnp.asarray(np.asarray(positions, np.int32))]
    return np.asarray(qwen3._head(rows, weights["norm"]["weight"],
                                  weights["lm_head"]["weight"],
                                  hf["rms_norm_eps"], quant))


def experts_used(hf: dict, weights: dict, ids) -> tuple[int, int]:
    """(used, needed): the fewest distinct HELD experts that any sparse
    layer's router reached over `ids`, plus 1 where that layer also picked
    an identity expert; needed is all of them and the identity path, in
    every sparse layer: a run that never took an identity expert, or missed
    a held one, has not checked it."""
    real, first, held, zeros = share(hf)
    _, chosen = _walk(hf, weights, ids)
    used = [np.unique(np.asarray(idx)) for idx in chosen]
    return (min(int(np.sum((u >= first) & (u < first + held)))
                + int(bool(zeros) and bool(np.any(u >= real)))
                for u in used), held + int(bool(zeros)))
