"""Laguna forward pass, plain (see qwen3.py for the shared parts and the
rules): float32, highest matmul precision, no cache, no kernels. Written
from the published configuration (`model_type: laguna`, poolside
Laguna-S-2.1). What no key of that file settles is marked (+) here and is
listed, with its ground, under `assumed` in the configuration's file.

Every layer is pre-norm, `n` = RMSNorm with `rms_norm_eps`:

    h = n(x)
    q = W_q h as [H, D]      k, v = W_k h, W_v h as [Hkv, D]
        H = `num_attention_heads_per_layer[i]`: one count on the full
        layers, another on the sliding ones, on the same Hkv K/V heads
    (+) q and k each RMS-normed over the D of a head, with a weight, before
        rope (the Qwen3-MoE lineage whose key names this config keeps:
        `decoder_sparse_step`, `mlp_only_layers`, `norm_topk_prob`)
    rope, rotate-half, by `rope_parameters[kind]`, on the first
        R = D x partial_rotary_factor dims, the rest untouched:
        default:  inv_i = theta^(-2i/R)
        yarn:     ext_i = theta^(-2i/R), int_i = ext_i / factor,
                  c(r) = R ln(original / (2 pi r)) / (2 ln theta),
                  low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
                  both clamped to [0, R - 1],
                  ramp_i = clip((i - low) / (high - low), 0, 1),
                  inv_i = int_i ramp_i + ext_i (1 - ramp_i),
                  cos and sin both multiplied by `attention_factor`
    causal softmax attention at D^-0.5, query head a on K/V head
        floor(a / (H / Hkv)); a sliding layer sees the last
        `sliding_window` positions
    g = sigmoid(W_g h), W_g [H, hidden]: head a's output times g_a before
        W_o ((+) sigmoid, of the layer's normed input: the published
        headwise form of gated attention)
    x <- x + W_o [g_1 y_1; ...; g_H y_H]

    h2 = n(x)
    dense (`mlp_layer_types[i] == "dense"`): x <- x + SwiGLU(h2), width
        `intermediate_size`
    sparse: p = softmax(W_r h2) over all `num_experts` of the model
        ((+) softmax: no `scoring_func`, no selection bias key;
        `moe_router_logit_softcapping` 0 is off), sel = top
        `num_experts_per_tok`, w_e = `moe_routed_scaling_factor` x p_e /
        sum_{e' in sel} p_e' (`norm_topk_prob`),
        y = sum_{e in sel, e held} w_e E_e(h2) + S(h2), E_e and S SwiGLU of
        width `moe_intermediate_size` / `shared_expert_intermediate_size`
        ((+) the shared expert is added ungated: no key for a gate);
        weights on the experts' OUTPUTS (`moe_apply_router_weight_on_input`
        false). x <- x + y

then the final norm and the untied head.

GIVEN THE SAME SHARE as the program: with `expert_parallel: {size, rank}`
the weights hold `num_experts` experts, numbers rank x held .. of the size x
held the router scores; what the absent experts would add is left out, here
as there, the shared expert is whole (every chip computes it alike), and
that partial result goes on to the next layer. The vocabulary is the slice.

Plain means: every held expert is applied to every token, one at a time,
under a dense [tokens, held] weight matrix; attention one K/V group at a
time, so 2,400 tokens at the published widths fit.

`quant` is the precision control of qwen3.py. Four further controls, for
this family's own mechanisms, each what a program would serve that lacked
it: `gate="off"` (every gate 1), `rope_full="unscaled"` (the branch
cake_tpu/ops/rope.py took before it knew YaRN: the full layers' base over
all D dims, no blend, no attention factor), `routed_scale=1.0`,
`shared="off"`. The output check must call the first, second and fourth not
correct; the third moves what the held experts give alone and is read beside
them (benchmark/tests/laguna_controls.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import qwen3
from .qwen3 import HI, STD, mm, rms_norm, swiglu

# The router's init is ROUTER_LOGIT_STD / sqrt(hidden): logits of that
# spread at any width. A trained router separates its top experts; at 0.02
# the 10th and 11th of 256 tie for nearly every token and bf16 rounding
# flips them (PR 25, PR 39).
ROUTER_LOGIT_STD = 0.8
# The gate projection's init is GATE_LOGIT_STD / sqrt(hidden): gate logits
# of std 1.5, so the gates spread over (0.1, 0.9). At 0.02 every gate is
# 0.5 +- 0.005 and a program without the gate would differ by a factor a
# norm weight could hide.
GATE_LOGIT_STD = 1.5


def is_full(hf: dict, i: int) -> bool:
    return hf["layer_types"][i] == "full_attention"


def is_sparse(hf: dict, i: int) -> bool:
    return (hf["mlp_layer_types"][i] != "dense"
            and i not in hf.get("mlp_only_layers", ()))


def share(hf: dict) -> tuple[int, int, int]:
    """(router width, first held expert, experts held)."""
    held = hf["num_experts"]
    ep = hf.get("expert_parallel") or {"size": 1, "rank": 0}
    return held * ep["size"], held * ep["rank"], held


def heads_of(hf: dict, full: bool) -> int:
    """Query heads of a layer kind: the first layer of it says."""
    per = hf["num_attention_heads_per_layer"]
    return next(per[i] for i in range(hf["num_hidden_layers"])
                if is_full(hf, i) == full)


def layer_trees(hf: dict) -> list:
    h, d = hf["hidden_size"], hf["head_dim"]
    width, _, held = share(hf)
    i, im = hf["intermediate_size"], hf["moe_intermediate_size"]
    ish = hf["shared_expert_intermediate_size"]

    def ffn(n):
        return {"gate_proj": {"weight": ((n, h), STD)},
                "up_proj": {"weight": ((n, h), STD)},
                "down_proj": {"weight": ((h, n), STD)}}

    sparse = {"gate": {"weight": ((width, h), ROUTER_LOGIT_STD / h ** 0.5)},
              "experts": {"gate_proj": ((held, im, h), STD),
                          "up_proj": ((held, im, h), STD),
                          "down_proj": ((held, h, im), STD)},
              "shared_expert": ffn(ish)}

    def attn(full: bool) -> dict:
        hq, hkv = heads_of(hf, full), hf["num_key_value_heads"]
        return {"q_proj": {"weight": ((hq * d, h), STD)},
                "k_proj": {"weight": ((hkv * d, h), STD)},
                "v_proj": {"weight": ((hkv * d, h), STD)},
                "o_proj": {"weight": ((h, hq * d), STD)},
                "q_norm": {"weight": ((d,), None)},
                "k_norm": {"weight": ((d,), None)},
                "g_proj": {"weight": ((hq, h), GATE_LOGIT_STD / h ** 0.5)}}

    return [{"self_attn": attn(is_full(hf, j)),
             "input_layernorm": {"weight": ((h,), None)},
             "post_attention_layernorm": {"weight": ((h,), None)},
             "mlp": sparse if is_sparse(hf, j) else ffn(i)}
            for j in range(hf["num_hidden_layers"])]


def inv_freq(rotary_dim: int, r: dict) -> np.ndarray:
    """The inverse frequencies of one `rope_parameters` entry, float64."""
    theta = float(r["rope_theta"])
    i = np.arange(rotary_dim // 2, dtype=np.float64)
    ext = theta ** (-2.0 * i / rotary_dim)
    kind = r.get("rope_type", "default")
    if kind == "default":
        return ext

    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")

    def c(turns):
        return (rotary_dim * np.log(r["original_max_position_embeddings"]
                                    / (2.0 * np.pi * turns))
                / (2.0 * np.log(theta)))

    low = max(np.floor(c(r.get("beta_fast", 32))), 0.0)
    high = min(np.ceil(c(r.get("beta_slow", 1))), rotary_dim - 1.0)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return ext / r["factor"] * ramp + ext * (1.0 - ramp)


def attention_factor(r: dict) -> float:
    if r.get("rope_type", "default") != "yarn":
        return 1.0
    af = r.get("attention_factor")
    return float(af) if af is not None else 0.1 * np.log(r["factor"]) + 1.0


def rope_tables(s: int, head_dim: int, r: dict) -> tuple:
    """(cos, sin) [S, R/2] float32 of positions 0..S-1 (angles in float64,
    as a table made once would be), and R."""
    rd = int(head_dim * r.get("partial_rotary_factor", 1.0))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv_freq(rd, r)[None, :]
    m = attention_factor(r)
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32), rd)


def rope(x, cos, sin, rd):
    """x [S, H, D]: rotate-half on dims [0, rd), the rest untouched."""
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c, x[..., rd:]],
                           -1)


def attention(x, p, cos, sin, c, quant=None, gate="on"):
    s = x.shape[0]
    hq, hkv, d, eps = c["heads"], c["kv_heads"], c["head_dim"], c["eps"]
    g, pos = hq // hkv, jnp.arange(s)
    q = mm(x, p["q_proj"]["weight"], quant).reshape(s, hq, d)
    k = mm(x, p["k_proj"]["weight"], quant).reshape(s, hkv, d)
    v = mm(x, p["v_proj"]["weight"], quant).reshape(s, hkv, d)
    q = rope(rms_norm(q, p["q_norm"]["weight"], eps), cos, sin, c["rd"])
    k = rope(rms_norm(k, p["k_norm"]["weight"], eps), cos, sin, c["rd"])
    seen = pos[None, :] <= pos[:, None]
    if c["window"]:
        seen &= pos[None, :] > pos[:, None] - c["window"]
    qg = q.reshape(s, hkv, g, d).transpose(1, 2, 0, 3)      # [hkv, g, s, d]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def one_group(args):
        qh, kh, vh = args
        sc = jnp.einsum("gqd,kd->gqk", qh, kh, precision=HI) / np.sqrt(d)
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(sc, -1), vh,
                          precision=HI)

    o = jax.lax.map(one_group, (qg, kg, vg))                # [hkv, g, s, d]
    o = o.transpose(2, 0, 1, 3).reshape(s, hq, d)
    if gate == "on":
        o = o * jax.nn.sigmoid(mm(x, p["g_proj"]["weight"], quant)
                               )[:, :, None]
    elif gate != "off":
        raise ValueError(f"gate {gate!r}")
    return mm(o.reshape(s, hq * d), p["o_proj"]["weight"], quant)


def route(x, gate_w, c, quant=None, routed_scale=None):
    """Dense routing weights [S, router width] and the chosen [S, k]."""
    probs = jax.nn.softmax(mm(x, gate_w, quant), axis=-1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * (c["routed_scale"] if routed_scale is None else routed_scale)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top), idx


def routed(x, p, c, quant=None, routed_scale=None):
    """The part of a sparse layer's result that the HELD experts give."""
    dense, idx = route(x, p["gate"]["weight"], c, quant, routed_scale)
    ex, first, held = p["experts"], c["first"], c["held"]

    def one(acc, args):
        g, u, d, w = args
        return acc + w[:, None] * swiglu(x, g, u, d, quant), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (ex["gate_proj"], ex["up_proj"], ex["down_proj"],
         dense[:, first:first + held].T))
    return acc, idx


def sparse_ffn(x, p, c, quant=None, routed_scale=None, shared="on"):
    """(a sparse layer's FFN output for normed tokens x, the choices)."""
    y, idx = routed(x, p, c, quant, routed_scale)
    if shared == "on":
        y = y + qwen3.mlp(x, p["shared_expert"], c, quant)
    elif shared != "off":
        raise ValueError(f"shared {shared!r}")
    return y, idx


@functools.partial(jax.jit, static_argnames=(
    "c", "quant", "gate", "routed_scale", "shared"))
def _layer(x, p, cos, sin, c, quant=None, gate="on", routed_scale=None,
           shared="on"):
    """(x after the layer, the router's choices [S, k] or None)."""
    c = dict(c)
    eps = c["eps"]
    x = x + attention(rms_norm(x, p["input_layernorm"]["weight"], eps),
                      p["self_attn"], cos, sin, c, quant, gate)
    h = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
    if "experts" in p["mlp"]:
        y, idx = sparse_ffn(h, p["mlp"], c, quant, routed_scale, shared)
        return x + y, idx
    return x + qwen3.mlp(h, p["mlp"], c, quant), None


def rope_of(hf: dict, full: bool, rope_full: str = "yarn") -> dict:
    """The `rope_parameters` entry a layer kind rotates by; the control
    `rope_full="unscaled"` gives the full layers their base alone, over
    all of a head."""
    r = hf["rope_parameters"]["full_attention" if full
                              else "sliding_attention"]
    if full and rope_full == "unscaled":
        return {"rope_theta": r["rope_theta"], "rope_type": "default",
                "partial_rotary_factor": 1.0}
    if rope_full not in ("yarn", "unscaled"):
        raise ValueError(f"rope_full {rope_full!r}")
    return r


def static(hf: dict, full: bool, rd: int) -> tuple:
    width, first, held = share(hf)
    c = {"heads": heads_of(hf, full), "kv_heads": hf["num_key_value_heads"],
         "head_dim": hf["head_dim"], "eps": hf["rms_norm_eps"], "rd": rd,
         "window": 0 if full else hf["sliding_window"],
         "first": first, "held": held,
         "num_experts_per_tok": hf["num_experts_per_tok"],
         "norm_topk_prob": bool(hf["norm_topk_prob"]),
         "routed_scale": float(hf.get("moe_routed_scaling_factor") or 1.0)}
    return tuple(sorted(c.items()))


def _walk(hf: dict, weights: dict, ids, quant=None, rope_full="yarn",
          **controls):
    """(hidden states after the last layer, each sparse layer's choices)."""
    ids = np.asarray(ids, np.int32)
    x = jnp.take(weights["embed_tokens"]["weight"], jnp.asarray(ids), axis=0
                 ).astype(jnp.float32)
    tables = {full: rope_tables(len(ids), hf["head_dim"],
                                rope_of(hf, full, rope_full))
              for full in (True, False)}
    chosen = []
    for j, p in enumerate(weights["layers"]):
        full = is_full(hf, j)
        cos, sin, rd = tables[full]
        x, idx = _layer(x, p, cos, sin, static(hf, full, rd), quant,
                        **controls)
        if idx is not None:
            chosen.append(idx)
    return x, chosen


def forward_logits(hf: dict, weights: dict, ids, positions, quant=None,
                   gate="on", rope_full="yarn", routed_scale=None,
                   shared="on") -> np.ndarray:
    """Logits [len(positions), vocab] (float32, on the host) of the full
    forward pass over `ids` at the given positions."""
    x, _ = _walk(hf, weights, ids, quant, rope_full, gate=gate,
                 routed_scale=routed_scale, shared=shared)
    table = (weights["embed_tokens"] if hf.get("tie_word_embeddings")
             else weights["lm_head"])["weight"]
    rows = x[jnp.asarray(np.asarray(positions, np.int32))]
    return np.asarray(qwen3._head(rows, weights["norm"]["weight"], table,
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
