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


def router_topk(logits, k: int, norm_topk_prob: bool, gate_act: str = "softmax"):
    """logits: [T, E] -> (weights [T, k] f32, idx [T, k] int32).

    softmax gate: probabilities over experts then top-k (Qwen3 MoE).
    sigmoid gate: per-expert sigmoid scores then top-k (Qwen3.5 MoE).
    """
    lf = logits.astype(jnp.float32)
    if gate_act == "softmax":
        probs = jax.nn.softmax(lf, axis=-1)
    elif gate_act == "sigmoid":
        probs = jax.nn.sigmoid(lf)
    else:
        raise ValueError(f"unknown gate activation {gate_act}")
    weights, idx = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx.astype(jnp.int32)


def combine_weights(weights, idx, num_experts: int):
    """Scatter top-k (weight, index) into a dense [T, E] combine matrix."""
    t, k = weights.shape
    w_te = jnp.zeros((t, num_experts), weights.dtype)
    rows = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None], (t, k))
    return w_te.at[rows, idx].add(weights)


def _expert_act(g, u, act: str):
    if act == "silu":
        return jax.nn.silu(g) * u
    return jax.nn.gelu(g, approximate=True) * u


def moe_ffn(x, router_weight, gate_proj, up_proj, down_proj, k: int,
            norm_topk_prob: bool, gate_act: str = "softmax", act: str = "silu"):
    """x: [T, H]; router_weight: [E, H]; gate/up_proj: [E, I, H];
    down_proj: [E, H, I]. Returns [T, H] in x.dtype.

    Static dispatch on T (a compile-time shape): ragged segment-GEMM for
    prefill-sized batches, dense combine for decode.
    """
    e = gate_proj.shape[0]
    with jax.named_scope("cake.ffn.route"):
        logits = jnp.einsum("th,eh->te", x, router_weight,
                            preferred_element_type=jnp.float32)
        weights, idx = router_topk(logits, k, norm_topk_prob, gate_act)

    with jax.named_scope("cake.ffn.experts"):
        if x.shape[0] >= RAGGED_MIN_TOKENS and _ragged_enabled():
            return _moe_ragged(x, weights, idx, gate_proj, up_proj,
                               down_proj, act)
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


def _moe_ragged(x, weights, idx, gate_proj, up_proj, down_proj, act: str):
    """Sort the T*k assignments by expert; each expert GEMMs only its own
    contiguous token slice. Exact — group sizes come from the real
    assignment counts, so nothing is dropped or padded (no capacity
    factor), and the FLOPs are (k/E) * dense."""
    from jax.lax import ragged_dot_general
    t, h = x.shape
    k = idx.shape[1]
    e = gate_proj.shape[0]

    flat_expert = idx.reshape(t * k)
    order = jnp.argsort(flat_expert)                    # stable
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
    out = out.at[tok_of].add(y.astype(jnp.float32) * w_flat[:, None])
    return out.astype(x.dtype)
