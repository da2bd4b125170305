"""Mixture-of-Experts routing and dispatch.

Reference semantics (ref: models/qwen3_moe/moe.rs, qwen3_5_moe/moe.rs):
softmax (or sigmoid) router -> top-k experts -> optional weight
renormalization -> weighted sum of expert FFNs (+ always-active shared
expert gated by sigmoid for Qwen3.5 MoE).

TPU formulation: experts are stacked [E, ...] tensors with two dispatch
strategies sharing one router:

  * dense combine (decode, T < RAGGED_MIN_TOKENS): every expert runs on
    every token and a [T, E] combine matrix (zero outside top-k) selects —
    for T of 1-8 this is a batched matvec with zero gather/scatter
    overhead, cheaper than any routing machinery.
  * sort-based ragged dispatch (prefill): the T*k (token, expert)
    assignments are sorted by expert and each expert multiplies only its
    contiguous slice via `lax.ragged_dot_general` (TPU ragged segment-GEMM
    over the stored [E, I, H] banks, no transpose/relayout) — FLOPs scale
    with k/E instead of E/E (ref: qwen3_moe/moe.rs top-8 over 128 experts
    = 16x prefill FLOP reduction; SURVEY hard-part #4).

Both paths compute identical expert math; tests/test_moe_ragged.py pins
them against each other and tests/test_hf_parity.py pins the
router+combine semantics to transformers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# below this many tokens the dense combine wins (decode / tiny chunks):
# the ragged path's sort/gather/scatter overhead only pays off once the
# per-expert GEMMs are big enough to tile the MXU
RAGGED_MIN_TOKENS = 32


def _ragged_enabled() -> bool:
    """CAKE_MOE_RAGGED=0 pins every shape to the dense combine (escape
    hatch if a backend mishandles ragged_dot_general)."""
    from .. import knobs
    return knobs.get("CAKE_MOE_RAGGED")


def router_topk(logits, k: int, norm_topk_prob: bool, gate_act: str = "softmax",
                select_bias=None):
    """logits: [T, E] -> (weights [T, k] f32, idx [T, k] int32).

    softmax gate: probabilities over experts then top-k (Qwen3 MoE).
    sigmoid gate: per-expert sigmoid scores then top-k (Qwen3.5 MoE).
    select_bias [E]: the top-k is taken of score + bias, the weights are
    the scores alone (DeepSeek-V3 `noaux_tc`, MiMo-V2's
    `e_score_correction_bias`), normalised over the selected as
    DeepseekV3TopkRouter does (+ 1e-20).
    """
    lf = logits.astype(jnp.float32)
    if gate_act == "softmax":
        probs = jax.nn.softmax(lf, axis=-1)
    elif gate_act == "sigmoid":
        probs = jax.nn.sigmoid(lf)
    else:
        raise ValueError(f"unknown gate activation {gate_act}")
    if select_bias is not None:
        _, idx = jax.lax.top_k(probs + select_bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(probs, idx, axis=-1)
        if norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        return weights, idx.astype(jnp.int32)
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
            select_bias=None, first: int = 0, routed_scale: float = 1.0):
    """x: [T, H]; router_weight: [R, H]; gate/up_proj: [E, I, H];
    down_proj: [E, H, I]. Returns [T, H] in x.dtype.

    routed_scale multiplies the selected experts' weights after their
    normalisation (Laguna's `moe_routed_scaling_factor`, DeepSeek-V3's
    `routed_scaling_factor`), on both dispatch paths.

    R > E is one share of an expert-parallel group: the banks hold experts
    first .. first + E - 1 of the R the router scores. Routing and the
    normalisation run over all R; the result is the part the held experts
    give, and an assignment to an expert held elsewhere adds nothing (its
    index is moved to E, which the dense combine drops and the ragged path
    sorts behind every group). Nothing stands in for the other shares.

    Static dispatch on T (a compile-time shape): ragged segment-GEMM for
    prefill-sized batches, dense combine for decode.
    """
    e = gate_proj.shape[0]
    share = router_weight.shape[0] != e
    with jax.named_scope("cake.ffn.route"):
        logits = jnp.einsum("th,eh->te", x, router_weight,
                            preferred_element_type=jnp.float32)
        weights, idx = router_topk(logits, k, norm_topk_prob, gate_act,
                                   select_bias)
        if routed_scale != 1.0:
            weights = weights * routed_scale
        if share:
            held = (idx >= first) & (idx < first + e)
            idx = jnp.where(held, idx - first, e)
            weights = jnp.where(held, weights, 0.0)

    with jax.named_scope("cake.ffn.experts"):
        if x.shape[0] >= RAGGED_MIN_TOKENS and _ragged_enabled():
            return _moe_ragged(x, weights, idx, gate_proj, up_proj,
                               down_proj, act, share)
        w_te = combine_weights(weights, idx, e).astype(x.dtype)
        g = jnp.einsum("th,eih->tei", x, gate_proj)         # [T, E, I]
        u = jnp.einsum("th,eih->tei", x, up_proj)
        a = _expert_act(g, u, act)
        y_e = jnp.einsum("tei,ehi->teh", a, down_proj)      # [T, E, H]
        return jnp.einsum("te,teh->th", w_te, y_e).astype(x.dtype)


def _ragged_dn(lhs_contract: int, rhs_contract: int):
    from jax.lax import RaggedDotDimensionNumbers
    return RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((lhs_contract,), (rhs_contract,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])


def _moe_ragged(x, weights, idx, gate_proj, up_proj, down_proj, act: str,
                share: bool = False):
    """Sort the T*k assignments by expert; each expert GEMMs only its own
    contiguous token slice. Exact — group sizes come from the real
    assignment counts, so nothing is dropped or padded (no capacity
    factor), and the FLOPs are (k/E) * dense.

    For a share (moe_ffn), assignments to experts held elsewhere carry
    index E: they sort behind every group, `bincount(length=E)` leaves
    them out of the group sizes, and the rows past the groups' sum are
    zeroed by their weight (0) under a `where`, so nothing is read of what
    ragged_dot_general leaves there. All T*k rows are gathered: how many
    of them the held experts take is known only on the device (up to all
    of them), so a smaller gather would need a capacity and could drop."""
    from jax.lax import ragged_dot_general
    t, h = x.shape
    k = idx.shape[1]
    e = gate_proj.shape[0]

    flat_expert = idx.reshape(t * k)
    order = jnp.argsort(flat_expert)                    # stable
    # a share: the sorted rows that fall in some held expert's group
    in_group = (flat_expert[order] < e) if share else None
    tok_of = order // k                                 # [T*k]
    xs = x[tok_of]                                      # [T*k, H]
    group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)

    g = ragged_dot_general(xs, gate_proj, group_sizes, _ragged_dn(1, 2))
    u = ragged_dot_general(xs, up_proj, group_sizes, _ragged_dn(1, 2))
    a = _expert_act(g, u, act).astype(x.dtype)          # [T*k, I]
    y = ragged_dot_general(a, down_proj, group_sizes, _ragged_dn(1, 2))
    # combine in f32: the dense path's einsum accumulates on the MXU in
    # f32, so the bf16 scatter-add here must not be the lower-precision one
    w_flat = weights.reshape(t * k)[order]                 # f32 from router
    out = jnp.zeros((t, h), jnp.float32)
    contrib = y.astype(jnp.float32) * w_flat[:, None]
    if in_group is not None:
        contrib = jnp.where(in_group[:, None], contrib, 0.0)
    out = out.at[tok_of].add(contrib)
    return out.astype(x.dtype)
