"""Scaled-dot-product attention with GQA, causal masking and sliding windows.

TPU-first design notes (vs ref: cake-core/src/models/common/attention.rs):
  * Activations stay in [B, S, H, D] layout end-to-end; GQA is expressed as a
    grouped einsum so no repeat_kv materialization and no transposes — the
    reference's seq_len==1 transpose-avoidance hack is unnecessary under XLA.
  * Masking is position-based: the KV cache carries an absolute-position array
    (-1 = empty slot), so one code path serves prefill, chunked prefill into an
    existing cache, decode, and sliding-window ring buffers. The reference
    instead trims/concats the KV tensors dynamically (cache.rs:163-210), which
    would recompile under XLA's static shapes.
  * Softmax/accumulation in f32 (matches the reference's F32 attention path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free for all-masked rows


def make_attention_mask(q_positions, kv_positions, window: int | None = None,
                        causal: bool = True):
    """Boolean attend-mask [B, Sq, Skv].

    q_positions:  [B, Sq] absolute positions of the queries.
    kv_positions: [B, Skv] absolute positions in the KV cache, -1 for empty.
    window: sliding-window size W — key visible iff q_pos - W < k_pos.
    """
    q = q_positions[:, :, None]
    k = kv_positions[:, None, :]
    mask = k >= 0
    if causal:
        mask &= k <= q
    if window is not None:
        mask &= k > q - window
    return mask


def _spread_queries(qf):
    """qf [B, Sq, Hkv, G, D] -> [B, Sq, Hkv, G, Hkv * D]: a head's D query
    values in the D columns of its own K/V head and exact zeros in the
    others, so that contracting with joined keys [B, Skv, Hkv * D] over the
    whole width gives the head's scores against its own K/V head. The zeros
    add nothing to a sum, and the keys are never reshaped: splitting Hkv * D
    lanes at a width that is no multiple of 128 would copy the buffer."""
    b, sq, hkv, g, d = qf.shape
    own = jnp.eye(hkv, dtype=jnp.bool_)[None, None, :, None, :, None]
    return jnp.where(own, qf[:, :, :, :, None, :], 0).reshape(
        b, sq, hkv, g, hkv * d)


def multi_head_attention(q, k, v, mask=None, scale: float | None = None,
                         sink=None):
    """Grouped-query attention.

    q: [B, Sq, Hq, D], k: [B, Skv, Hkv, D], v: [B, Skv, Hkv, Dv] with Hq a
    multiple of Hkv (Dv may differ from D). k may also be JOINED, [B, Skv,
    Hkv * D], as a cache holds keys whose width is no multiple of the lanes
    (cache.key_row_shape). Where the queries are the smaller side (a
    decode or verify step against a pool's rows: Sq * Hq < Skv) it is read
    as it lies, against queries spread over the joined width
    (_spread_queries: Hkv times the products, no copy of the keys); else (a
    chunk against a ring and itself) its heads are split, which copies it.
    mask: bool [B, Sq, Skv] (True = attend) or None for full attention.
    sink: [Hq] or None — a learned logit a head that joins the softmax's
    denominator and carries no value (gpt-oss's sinks, MiMo-V2's
    attention_sink_bias): p_s = exp(s_s - m) / (sum exp(s - m) +
    exp(sink - m)), m the maximum over the scores and the sink.
    Returns [B, Sq, Hq, Dv] in q.dtype.
    """
    b, sq, hq, d = q.shape
    hkv = v.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b, sq, hkv, g, d)
    # scores: [B, Hkv, G, Sq, Skv]
    if k.ndim == 3 and sq * hq < k.shape[1]:
        qf, dims = _spread_queries(qf), "bskgc,btc->bkgst"
    else:
        k, dims = k.reshape(b, -1, hkv, d), "bskgd,btkd->bkgst"
    scores = jnp.einsum(dims, qf, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        sk = sink.astype(jnp.float32).reshape(1, hkv, g, 1, 1)
        m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sk)
        e = jnp.exp(scores - m)
        probs = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sk - m))
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, v.shape[-1]).astype(q.dtype)


def causal_sdpa(q, k, v, scale: float | None = None):
    """Plain causal attention for prefill without a cache (B,S,H,D)."""
    b, s = q.shape[0], q.shape[1]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    mask = make_attention_mask(pos, pos)
    return multi_head_attention(q, k, v, mask, scale)
