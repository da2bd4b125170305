"""DeepSeek-V2 forward pass, plain (see qwen3.py for the shared parts and the
rules): float32, highest matmul precision, no cache, no kernels, the
EXPANDED form of latent attention only. Written from the published
configuration (`model_type: deepseek_v2`, deepseek-ai/DeepSeek-V2), its
`modeling_deepseek.py` and arXiv:2405.04434. What no key of the
configuration settles is marked (+) and listed under `assumed` in the
configuration's file.

Every layer is pre-norm, `n` = RMSNorm with `rms_norm_eps` (x / sqrt(mean
x^2 + eps) . w), H = `num_attention_heads`, widths `qk_nope_head_dim` (N),
`qk_rope_head_dim` (R), `v_head_dim` (V), ranks `q_lora_rank`,
`kv_lora_rank` (C):

    h = n(x)
    c_q = n_q(W_qa h);  q = W_qb c_q as [H, N + R], a head [q_nope ; q_pe]
    [c ; k_pe] = W_kva h as [C ; R];  c_kv = n_kv(c)
        k_pe is ONE key part, shared by all heads
    [k_nope^a ; v^a] = W_kvb^a c_kv as [N ; V] a head
    rope on the R dims of q_pe^a and of k_pe, pairs INTERLEAVED as the
        checkpoint has them, (2i, 2i + 1): base `rope_theta`, YaRN by
        `rope_scaling` (laguna.py's inv_i: factor, original positions,
        beta_fast / beta_slow, truncated ramp); cos and sin times
        m(mscale) / m(mscale_all_dim), m(a) = 0.1 a ln(factor) + 1
    k^a = [k_nope^a ; rope(k_pe)],  q^a = [q_nope^a ; rope(q_pe^a)]
    causal softmax attention at s = (N + R)^-1/2 . m(mscale_all_dim)^2
    x <- x + W_o [o^1 ; .. ; o^H]

    h2 = n(x)
    dense (layer < `first_k_dense_replace`): x <- x + SwiGLU(h2), width
        `intermediate_size`
    sparse: p = softmax(W_g h2) over all `n_routed_experts` of the model, in
        float32; `group_limited_greedy`: a group's score is the largest p
        among its members (`n_group` contiguous groups), the `topk_group`
        best groups stay, every other p counts as 0; sel = top
        `num_experts_per_tok` of what is left; w_e = p_e, NOT renormalised
        (`norm_topk_prob` false), times `routed_scaling_factor`;
        y = sum_{e in sel, e held} w_e E_e(h2) + S(h2), E_e SwiGLU of width
        `moe_intermediate_size`, S ONE SwiGLU of `n_shared_experts` x that
        width, added ungated. x <- x + y

then the final norm and the untied head.

GIVEN THE SAME SHARE as the program: with `expert_parallel: {size, rank}`
the weights hold `n_routed_experts` experts, numbers rank x held .. of the
size x held the router scores (DeepSeek-V2 at size 8: exactly one of the
router's 8 groups); what the absent experts would add is left out, here as
there, the shared experts are whole, and that partial result goes on to the
next layer. The vocabulary is the slice.

THE SAME ARRAYS as the program, whose tree is the checkpoint's with ONE
change (+): the rope rows of `q_b_proj` (the last R of every head) and of
`kv_a_proj_with_mqa` (the last R) lie de-interleaved, x_0 x_1 .. y_0 y_1 ..,
as the program's loader leaves them for its rope in halves. `to_checkpoint`
puts them back in the published order, (x_0 y_0 x_1 y_1 ..), and everything
here then runs on the published layout with the published, interleaved rope.

Plain means: keys and values of every position expanded for all heads,
heads walked HEAD_GROUP at a time (128 heads x 2,400^2 scores never stand
at once), every held expert on every token under a dense weight matrix.

`quant` is the precision control of qwen3.py. Five further controls, one a
mechanism, each what a program would serve that lacked it: `rope_pe="off"`
(the shared key part stored unrotated), `kv_norm="off"` (the latent stored
without its norm), `mscale=1.0` (the softmax scale without YaRN's factor:
(N + R)^-1/2), `groups="off"` (plain top-k over all the router's experts),
`routed_scale=1.0`. The output check must call each not correct
(benchmark/tests/deepseek_v2_controls.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import laguna, qwen3
from .qwen3 import HI, STD, mm, rms_norm, swiglu

# heads whose scores stand at once
HEAD_GROUP = 8

# Initialisers (benchmark/weights.py draws N(0, std), or 1 + 0.1 N(0, 1) for
# std None, and nothing else); the arithmetic is for the published widths
# under a normed input of unit RMS. PERF.md section 6 (PR 56) has the
# readings each was set on, and those of what was tried and taken back.
#
# Router: logits of std 0.8 (laguna.py's ROUTER_LOGIT_STD, for its reason).
ROUTER_LOGIT_STD = 0.8
# kv_b_proj at 4 x STD: k_nope and v have std 1.8 a number, so a score's
# content part (128 terms, q 0.78 a number) has std 1.8 and its position
# part (64 terms against a k_pe of 1.43) std 1.0 under s = 0.1147: scores
# of std ~2.1, a softmax that picks tens of a few thousand keys. At STD the
# scores have std 1.1, a near-flat average whose output is 0.02 a number:
# a shared key part left unrotated, a latent left unnormed or a scale
# without YaRN's factor would then move nothing the check can see.
KV_B_STD = 4 * STD
# What writes to the stream. The first set (o_proj STD / 3, a routed
# expert's down_proj STD / 2, dense and shared down_proj STD / sqrt(120),
# the embedding at 0.75) did not separate on the chip: sound 0.026-0.066
# beside int8 0.077. Most points read ~0.018, but one token in six met a
# router near-tie that bfloat16 flips in some layer, and with unnormalised
# weights of 16 p a flip moves a whole expert (0.09 at that point), a
# group's flip every selected expert of this share (0.22). A flip's size
# goes with the routed experts' part of the stream, as the plain-top-k and
# routed-scale controls do; how often one happens goes with the stream's
# rounding error, which was the attention's (o_proj / 3: the points' floor
# 0.012 -> 0.0053); and what int8 reads goes with the dense and shared
# FFNs' part (their down_proj x 2: int8 0.0155 -> 0.021, the floor 0.0036
# -> 0.0039). So: the embedding 1.5 x STD x sqrt(hidden) = 2.15 a channel;
# o_proj STD / 9 (attention adds ~0.1: its three controls still read 3-7 x
# the limit); dense and shared down_proj 4 x STD / sqrt(2 x 60) (layer 0's
# FFN adds 0.8, a shared pair 0.4); a routed expert's down_proj STD / 5 (a
# token that keeps this share's group takes ~2 of its experts at ~0.5 each:
# ~0.1 a channel there, nothing where the group is dropped).
RESIDUAL_STD = 4 * STD / (2 * 60) ** 0.5
O_PROJ_STD = STD / 9
EXPERT_DOWN_STD = STD / 5
EMBED_SCALE = 1.5
# What the arithmetic above takes as given at the published widths, named
# so that a preset of other widths can keep the numbers it leads to (q 0.78
# a number, k_pe and an FFN's gate and up 1.43: benchmark/tests/
# test_deepseek_v2.py, tests/test_deepseek_v2.py: TINY_INIT).
Q_B_STD = STD
KV_A_STD = STD
FFN_IN_STD = STD


def share(hf: dict) -> tuple[int, int, int]:
    """(router width, first held expert, experts held)."""
    held = hf["n_routed_experts"]
    ep = hf.get("expert_parallel") or {"size": 1, "rank": 0}
    return held * ep["size"], held * ep["rank"], held


def is_sparse(hf: dict, i: int) -> bool:
    return i >= hf.get("first_k_dense_replace", 0)


def layer_trees(hf: dict) -> list:
    h, heads = hf["hidden_size"], hf["num_attention_heads"]
    n, r, v = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
               hf["v_head_dim"])
    ql, c = hf["q_lora_rank"], hf["kv_lora_rank"]
    width, _, held = share(hf)
    im = hf["moe_intermediate_size"]

    def ffn(w):
        return {"gate_proj": {"weight": ((w, h), FFN_IN_STD)},
                "up_proj": {"weight": ((w, h), FFN_IN_STD)},
                "down_proj": {"weight": ((h, w), RESIDUAL_STD)}}

    attn = {"q_a_proj": {"weight": ((ql, h), STD)},
            "q_a_layernorm": {"weight": ((ql,), None)},
            "q_b_proj": {"weight": ((heads * (n + r), ql), Q_B_STD)},
            "kv_a_proj_with_mqa": {"weight": ((c + r, h), KV_A_STD)},
            "kv_a_layernorm": {"weight": ((c,), None)},
            "kv_b_proj": {"weight": ((heads * (n + v), c), KV_B_STD)},
            "o_proj": {"weight": ((h, heads * v), O_PROJ_STD)}}
    sparse = {"gate": {"weight": ((width, h), ROUTER_LOGIT_STD / h ** 0.5)},
              "experts": {"gate_proj": ((held, im, h), FFN_IN_STD),
                          "up_proj": ((held, im, h), FFN_IN_STD),
                          "down_proj": ((held, h, im), EXPERT_DOWN_STD)},
              "shared_expert": ffn(hf["n_shared_experts"] * im)}
    return [{"self_attn": attn,
             "input_layernorm": {"weight": ((h,), None)},
             "post_attention_layernorm": {"weight": ((h,), None)},
             "mlp": sparse if is_sparse(hf, j)
             else ffn(hf["intermediate_size"])}
            for j in range(hf["num_hidden_layers"])]


def top_leaves(hf: dict) -> dict:
    """What lies outside the layers: the embedding (EMBED_SCALE above), the
    final norm, the untied head."""
    v, h = hf["vocab_size"], hf["hidden_size"]
    return {"embed_tokens": {"weight": ((v, h),
                                        EMBED_SCALE * STD * h ** 0.5)},
            "norm": {"weight": ((h,), None)},
            "lm_head": {"weight": ((v, h), STD)}}


def _interleave_rows(w, heads: int, r: int):
    """w [heads * per, in]: the last r rows of every head from halves
    (x_0 .. y_0 ..) to the published pairs (x_0 y_0 x_1 y_1 ..)."""
    per = w.shape[0] // heads
    w3 = w.reshape(heads, per, -1)
    rope = w3[:, per - r:]
    pairs = jnp.stack([rope[:, :r // 2], rope[:, r // 2:]], axis=2
                      ).reshape(heads, r, -1)
    return jnp.concatenate([w3[:, :per - r], pairs], axis=1).reshape(w.shape)


def to_checkpoint(p: dict, c: dict) -> dict:
    """An attention layer's leaves in the published order (see the head)."""
    out = dict(p)
    out["q_b_proj"] = {"weight": _interleave_rows(
        p["q_b_proj"]["weight"], c["heads"], c["rope"])}
    out["kv_a_proj_with_mqa"] = {"weight": _interleave_rows(
        p["kv_a_proj_with_mqa"]["weight"], 1, c["rope"])}
    return out


def m_of(factor: float, a: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * a * float(np.log(factor)) + 1.0


def rope_tables(s: int, hf: dict) -> tuple:
    """(cos, sin) [S, R/2] float32 of positions 0..S-1, angles in float64."""
    r, rs = hf["qk_rope_head_dim"], hf.get("rope_scaling")
    if rs is None:
        inv, m = laguna.inv_freq(r, {"rope_theta": hf["rope_theta"]}), 1.0
    else:
        if rs.get("type", rs.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling {rs}")
        inv = laguna.inv_freq(r, {**rs, "rope_type": "yarn",
                                  "rope_theta": hf["rope_theta"]})
        m = m_of(rs["factor"], rs.get("mscale", 1)) \
            / m_of(rs["factor"], rs.get("mscale_all_dim", 0))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def rope(x, cos, sin):
    """x [S, H, R], pairs interleaved: (x_2i, x_2i+1) turns by angle i."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1
                     ).reshape(x.shape)


def attention(x, p, cos, sin, c, quant=None, rope_pe="on", kv_norm="on",
              mscale=None):
    s = x.shape[0]
    heads, n, r, v, eps = c["heads"], c["nope"], c["rope"], c["v"], c["eps"]
    p = to_checkpoint(p, c)
    c_q = rms_norm(mm(x, p["q_a_proj"]["weight"], quant),
                   p["q_a_layernorm"]["weight"], eps)
    q = mm(c_q, p["q_b_proj"]["weight"], quant).reshape(s, heads, n + r)
    ckv = mm(x, p["kv_a_proj_with_mqa"]["weight"], quant)
    lat, k_pe = ckv[:, :c["kv_lora"]], ckv[:, None, c["kv_lora"]:]
    if kv_norm == "on":
        lat = rms_norm(lat, p["kv_a_layernorm"]["weight"], eps)
    elif kv_norm != "off":
        raise ValueError(f"kv_norm {kv_norm!r}")
    kv = mm(lat, p["kv_b_proj"]["weight"], quant).reshape(s, heads, n + v)
    q_pe = rope(q[..., n:], cos, sin)
    if rope_pe == "on":
        k_pe = rope(k_pe, cos, sin)
    elif rope_pe != "off":
        raise ValueError(f"rope_pe {rope_pe!r}")
    qf = jnp.concatenate([q[..., :n], q_pe], -1)
    kf = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_pe, (s, heads, r))], -1)
    m = c["m"] if mscale is None else mscale
    scale = (n + r) ** -0.5 * m * m
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


def route(x, gate_w, c, quant=None, groups="on", routed_scale=None):
    """Dense routing weights [S, router width] and the chosen [S, k]."""
    probs = jax.nn.softmax(mm(x, gate_w, quant), axis=-1)
    pick = probs
    if groups == "on" and c["n_group"] > 1:
        s, e = probs.shape
        best = probs.reshape(s, c["n_group"], -1).max(-1)
        _, kept = jax.lax.top_k(best, c["topk_group"])
        keep = jnp.zeros_like(best, bool).at[
            jnp.arange(s)[:, None], kept].set(True)
        pick = jnp.where(jnp.repeat(keep, e // c["n_group"], axis=1),
                         probs, 0.0)
    elif groups not in ("on", "off"):
        raise ValueError(f"groups {groups!r}")
    top, idx = jax.lax.top_k(pick, c["k"])
    if c["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    else:
        top = top * (c["routed_scale"] if routed_scale is None
                     else routed_scale)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top), idx


def sparse_ffn(x, p, c, quant=None, groups="on", routed_scale=None):
    """(a sparse layer's FFN output for normed tokens x, the choices)."""
    dense, idx = route(x, p["gate"]["weight"], c, quant, groups,
                       routed_scale)
    ex, first, held = p["experts"], c["first"], c["held"]

    def one(acc, args):
        g, u, d, w = args
        return acc + w[:, None] * swiglu(x, g, u, d, quant), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (ex["gate_proj"], ex["up_proj"], ex["down_proj"],
         dense[:, first:first + held].T))
    return acc + qwen3.mlp(x, p["shared_expert"], c, quant), idx


@functools.partial(jax.jit, static_argnames=(
    "c", "quant", "rope_pe", "kv_norm", "mscale", "groups", "routed_scale"))
def _layer(x, p, cos, sin, c, quant=None, rope_pe="on", kv_norm="on",
           mscale=None, groups="on", routed_scale=None):
    """(x after the layer, the router's choices [S, k] or None)."""
    c = dict(c)
    eps = c["eps"]
    x = x + attention(rms_norm(x, p["input_layernorm"]["weight"], eps),
                      p["self_attn"], cos, sin, c, quant, rope_pe, kv_norm,
                      mscale)
    h = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
    if "experts" in p["mlp"]:
        y, idx = sparse_ffn(h, p["mlp"], c, quant, groups, routed_scale)
        return x + y, idx
    return x + qwen3.mlp(h, p["mlp"], c, quant), None


def static(hf: dict) -> tuple:
    width, first, held = share(hf)
    rs = hf.get("rope_scaling")
    grouped = hf.get("topk_method", "greedy") == "group_limited_greedy"
    c = {"heads": hf["num_attention_heads"], "nope": hf["qk_nope_head_dim"],
         "rope": hf["qk_rope_head_dim"], "v": hf["v_head_dim"],
         "kv_lora": hf["kv_lora_rank"], "eps": hf["rms_norm_eps"],
         "m": (m_of(rs["factor"], rs.get("mscale_all_dim", 0))
               if rs and rs.get("mscale_all_dim") else 1.0),
         "first": first, "held": held, "k": hf["num_experts_per_tok"],
         "n_group": hf.get("n_group", 1) if grouped else 1,
         "topk_group": hf.get("topk_group", 1) if grouped else 1,
         "norm_topk_prob": bool(hf.get("norm_topk_prob", False)),
         "routed_scale": float(hf.get("routed_scaling_factor") or 1.0)}
    return tuple(sorted(c.items()))


def _walk(hf: dict, weights: dict, ids, quant=None, **controls):
    """(hidden states after the last layer, each sparse layer's choices)."""
    ids = np.asarray(ids, np.int32)
    x = jnp.take(weights["embed_tokens"]["weight"], jnp.asarray(ids), axis=0
                 ).astype(jnp.float32)
    cos, sin = rope_tables(len(ids), hf)
    c, chosen = static(hf), []
    for p in weights["layers"]:
        x, idx = _layer(x, p, cos, sin, c, quant, **controls)
        if idx is not None:
            chosen.append(idx)
    return x, chosen


def forward_logits(hf: dict, weights: dict, ids, positions, quant=None,
                   rope_pe="on", kv_norm="on", mscale=None, groups="on",
                   routed_scale=None) -> np.ndarray:
    """Logits [len(positions), vocab] (float32, on the host) of the full
    forward pass over `ids` at the given positions."""
    x, _ = _walk(hf, weights, ids, quant, rope_pe=rope_pe, kv_norm=kv_norm,
                 mscale=mscale, groups=groups, routed_scale=routed_scale)
    rows = x[jnp.asarray(np.asarray(positions, np.int32))]
    return np.asarray(qwen3._head(rows, weights["norm"]["weight"],
                                  weights["lm_head"]["weight"],
                                  hf["rms_norm_eps"], quant))


def experts_used(hf: dict, weights: dict, ids) -> tuple[int, int]:
    """(used, needed): the fewest distinct HELD experts that any sparse
    layer's router reached over `ids`, and how many it has to: all of
    them, in every sparse layer."""
    _, first, held = share(hf)
    _, chosen = _walk(hf, weights, ids)
    used = [np.unique(np.asarray(idx)) for idx in chosen]
    return (min(int(np.sum((u >= first) & (u < first + held)))
                for u in used), held)
