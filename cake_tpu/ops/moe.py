"""Mixture-of-Experts routing and the experts' combine.

Reference semantics (ref: models/qwen3_moe/moe.rs, qwen3_5_moe/moe.rs):
softmax (or sigmoid) router -> top-k experts -> optional weight
renormalization -> weighted sum of expert FFNs (+ always-active shared
expert gated by sigmoid for Qwen3.5 MoE).

TPU formulation: experts are stacked [E, ...] tensors and ONE dispatch, the
dense combine: every held expert runs on every token and a [T, E] combine
matrix (zero outside the top-k) selects. It streams the banks once and has
no gather, sort or scatter; above EXPERT_BLOCK_TOKENS it walks the tokens
in blocks so its [T, E, *] temporaries stay bounded. There is no
sort-based dispatch over `lax.ragged_dot_general` (FLOPs in proportion to
k/E on paper): on the chip it took 4.4 x the dense combine's time at 32
tokens, 7.5 x at 256, 1.5 x at 2048 and 1.05 x at 4096 (PERF.md section
5), and gave zeros for most rows of two share shapes (section 6, PR 48).

tests/test_moe_ragged.py pins the combine against a hand-written
reference and tests/test_hf_parity.py pins the router+combine semantics to
transformers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Tokens the dense combine takes in one pass; more are walked in blocks of
# this many. The chip's sweep (TPU v5e, PR 55: moe_ffn alone, bf16, E 128,
# top 8, H 2048, I 768; ms a call; PERF.md section 5):
#   T       32    256    512    1024    2048    4096
#   whole   1.65  1.97   3.71   7.58    16.34   32.65
#   walked  -     -      -      7.38    14.73   29.43  (blocks of 512)
# Up to 256 the combine streams the 1.2 GB of banks (75 % of 819 GB/s); at
# 512 it is bound by the MXU (85 % of 197 TFLOP/s) and a walk holds it
# there (256: 30.05 at 4096, 1024: 30.45, 2048: 35.04; whole: 77 %) with
# 0.1 GB of temporaries where the whole pass takes 0.8 GB at 4096.
EXPERT_BLOCK_TOKENS = 512


def group_limited(probs, n_group: int, topk_group: int, score_top: int = 1,
                  fill: float = 0.0):
    """probs [T, E] with every expert outside a token's `topk_group` best
    groups set to `fill`: the E experts lie in `n_group` contiguous groups,
    a group's score is its best expert's (DeepSeek-V2's
    `group_limited_greedy`; the paper's device-limited routing, a group a
    device) or, at `score_top` 2, the SUM of its two best (DeepSeek-V3's
    `noaux_tc`, Ling-3.0)."""
    t, e = probs.shape
    by_group = probs.reshape(t, n_group, e // n_group)
    if score_top == 1:
        best = jnp.max(by_group, axis=-1)
    else:
        best = jnp.sum(jax.lax.top_k(by_group, score_top)[0], axis=-1)
    _, kept = jax.lax.top_k(best, topk_group)                  # [T, M]
    keep = jnp.any(kept[:, :, None]
                   == jnp.arange(n_group, dtype=kept.dtype), axis=1)
    return jnp.where(jnp.repeat(keep, e // n_group, axis=1), probs, fill)


def router_topk(logits, k: int, norm_topk_prob: bool, gate_act: str = "softmax",
                select_bias=None, n_group: int = 1, topk_group: int = 1,
                group_score_top: int = 1):
    """logits: [T, E] -> (weights [T, k] f32, idx [T, k] int32).

    softmax gate: probabilities over experts then top-k (Qwen3 MoE).
    sigmoid gate: per-expert sigmoid scores then top-k (Qwen3.5 MoE).
    select_bias [E]: the top-k is taken of score + bias, the weights are
    the scores alone (DeepSeek-V3 `noaux_tc`, MiMo-V2's
    `e_score_correction_bias`), normalised over the selected as
    DeepseekV3TopkRouter does (+ 1e-20).
    n_group > 1: the top-k is taken within each token's `topk_group` best
    groups (group_limited); 1 is plain top-k, and traces nothing more.
    Beside a bias the groups are chosen, as the experts are, on score +
    bias, a group's score the sum of its `group_score_top` best, and what
    lies outside them is out of the top-k whatever its sign (-inf).
    """
    lf = logits.astype(jnp.float32)
    if gate_act == "softmax":
        probs = jax.nn.softmax(lf, axis=-1)
    elif gate_act == "sigmoid":
        probs = jax.nn.sigmoid(lf)
    else:
        raise ValueError(f"unknown gate activation {gate_act}")
    if select_bias is not None:
        pick = probs + select_bias.astype(jnp.float32)
        if n_group > 1:
            pick = group_limited(pick, n_group, topk_group, group_score_top,
                                 -jnp.inf)
        _, idx = jax.lax.top_k(pick, k)
        weights = jnp.take_along_axis(probs, idx, axis=-1)
        if norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        return weights, idx.astype(jnp.int32)
    if n_group > 1:
        probs = group_limited(probs, n_group, topk_group, group_score_top)
    weights, idx = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx.astype(jnp.int32)


def combine_weights(weights, idx, num_experts: int):
    """Scatter top-k (weight, index) into a dense [T, E] combine matrix;
    an index of num_experts or more (an expert held elsewhere) is
    dropped."""
    t, k = weights.shape
    w_te = jnp.zeros((t, num_experts), weights.dtype)
    rows = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None], (t, k))
    return w_te.at[rows, idx].add(weights, mode="drop")


def _expert_act(g, u, act: str):
    if act == "silu":
        return jax.nn.silu(g) * u
    return jax.nn.gelu(g, approximate=True) * u


def moe_ffn(x, router_weight, gate_proj, up_proj, down_proj, k: int,
            norm_topk_prob: bool, gate_act: str = "softmax", act: str = "silu",
            select_bias=None, first: int = 0, routed_scale: float = 1.0,
            n_group: int = 1, topk_group: int = 1, group_score_top: int = 1,
            zero_experts: int = 0):
    """x: [T, H]; router_weight: [R, H]; gate/up_proj: [E, I, H];
    down_proj: [E, H, I]. Returns [T, H] in x.dtype.

    routed_scale multiplies the selected experts' weights after their
    normalisation (Laguna's `moe_routed_scaling_factor`, DeepSeek-V3's
    `routed_scaling_factor`); n_group / topk_group / group_score_top limit
    the top-k to a token's best groups of the R experts (router_topk).

    R > E is one share of an expert-parallel group: the banks hold experts
    first .. first + E - 1 of the R the router scores. Routing and the
    normalisation run over all R; the result is the part the held experts
    give, and an assignment to an expert held elsewhere adds nothing (its
    index is moved to E, which the dense combine drops). Nothing stands in
    for the other shares.

    zero_experts > 0: the router's LAST that many outputs are identity
    experts (LongCat-Flash's `zero_expert_type: identity`). No bank backs
    them and no share holds them: a pick of one gives the token itself times
    its weight, so the result gains (sum of a token's identity picks'
    weights) x. That term belongs to the chip a token lives on, for every
    row it serves: when shares add up it counts ONCE, as a shared expert
    does. 0 traces nothing.

    One dispatch at every T, for a share as for a whole model: the dense
    combine, walked in blocks of EXPERT_BLOCK_TOKENS where T (a
    compile-time shape) is larger; a program of at most that many tokens
    is the four einsums and nothing else.
    """
    e = gate_proj.shape[0]
    share = router_weight.shape[0] != e
    with jax.named_scope("cake.ffn.route"):
        logits = jnp.einsum("th,eh->te", x, router_weight,
                            preferred_element_type=jnp.float32)
        weights, idx = router_topk(logits, k, norm_topk_prob, gate_act,
                                   select_bias, n_group, topk_group,
                                   group_score_top)
        if routed_scale != 1.0:
            weights = weights * routed_scale
        picked, picked_w = idx, weights
        if share:
            held = (idx >= first) & (idx < first + e)
            idx = jnp.where(held, idx - first, e)
            weights = jnp.where(held, weights, 0.0)

    def combine(xb, wb):
        """Every held expert on each token of xb [B, H]; wb [B, E] picks.
        On the chip XLA keeps one [B, E, I] of temporaries."""
        g = jnp.einsum("th,eih->tei", xb, gate_proj)        # [B, E, I]
        u = jnp.einsum("th,eih->tei", xb, up_proj)
        a = _expert_act(g, u, act)
        y_e = jnp.einsum("tei,ehi->teh", a, down_proj)      # [B, E, H]
        return jnp.einsum("te,teh->th", wb, y_e).astype(xb.dtype)

    with jax.named_scope("cake.ffn.experts"):
        w_te = combine_weights(weights, idx, e).astype(x.dtype)
        t, block = x.shape[0], EXPERT_BLOCK_TOKENS
        if t <= block:
            out = combine(x, w_te)
        else:
            cut = t - t % block
            out = jax.lax.map(lambda xw: combine(*xw),
                              (x[:cut].reshape(-1, block, x.shape[1]),
                               w_te[:cut].reshape(-1, block, e))
                              ).reshape(cut, -1)
            if cut < t:
                out = jnp.concatenate([out, combine(x[cut:], w_te[cut:])])
    if zero_experts:
        with jax.named_scope("cake.ffn.zero"):
            zero_w = jnp.sum(jnp.where(
                picked >= router_weight.shape[0] - zero_experts, picked_w,
                0.0), axis=-1)                                  # [T] f32
            out = (out.astype(jnp.float32)
                   + zero_w[:, None] * x.astype(jnp.float32)).astype(x.dtype)
    return out
