"""Pallas flash attention for TPU (prefill path).

The reference reaches flash-attention through candle-flash-attn on CUDA
(ref: utils/flash_attn.rs, attention.rs:270-277). On TPU the equivalent is
a Pallas kernel: blockwise Q x K^T with the online-softmax accumulator so
the [S, S] score matrix never leaves VMEM tiles (same algebra as
parallel/ring_attention.py, scheduled on one chip).

Layout: q/k/v in [B, S, H, D] (the framework-wide activation layout); the
kernel grid is (batch*q_heads, q_blocks, k_blocks) with the K axis innermost
("arbitrary") and the softmax accumulators in VMEM scratch, so VMEM use is
independent of the key length; GQA via q_head -> kv_head integer division.
Causal / window / limit block-skips clamp the K/V index maps (no DMA for a
skipped tile) and gate the compute; optional valid_len clamps padded
prefill tails. Under a mesh the call is a shard_map over the `tp` axis.

Dispatched from the serving prefill via the host-static `flash_mode`
threaded through forward_layers: "fresh" (pos0 == 0; SWA layers included
via the kernel's window mask) and "append" (continued prefill — the chunk
is scattered into the cache first, then the kernel runs over the unwrapped
buffer with a q_offset scalar), for seq_len >= FLASH_MIN_SEQ on TPU. The
XLA einsum path remains the fallback (and the CPU/test path — interpret
mode validates the kernel without hardware). Inference-only: no custom VJP
is defined, so the differentiable training path never dispatches here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
FLASH_MIN_SEQ = 256
NEG_INF = -1e30

# q @ k^T without materialising a transpose (contract the last dims)
_NT_DIMS = (((1,), (1,)), ((), ()))


def _k_block_range(q_start, limit, *, block_q, block_k, n_k, causal, window):
    """Inclusive [lo, hi] range of K blocks a q block can see.

    q_start: absolute position of the q block's first row (traced);
    limit: absolute key-position limit. Blocks above the causal diagonal,
    past the limit or entirely below the sliding window are outside the
    range; hi < lo means no block is visible. Shared by the kernel body
    (which skips the compute) and the K/V index maps (which clamp to the
    range, so a skipped grid step re-names the block already in VMEM and
    Pallas issues no DMA for it).
    """
    hi = jnp.minimum((limit - 1) // block_k, n_k - 1)
    if causal:
        hi = jnp.minimum(hi, (q_start + block_q - 1) // block_k)
    lo = 0
    if window is not None:
        lo = jnp.maximum((q_start - window + 1) // block_k, 0)
    return lo, hi


def _flash_kernel(vl_ref, off_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, causal, window):
    """One (batch*head, q_block, k_block) grid step of the online softmax.

    vl_ref:  (1,) SMEM scalar-prefetch — absolute key-position limit (valid
             keys occupy positions [0, limit); padded prefill tails
             excluded).
    off_ref: (1,) SMEM scalar-prefetch — absolute position of query row 0
             (continued prefill appends at pos0 > 0; keys' positions are
             their buffer indices, valid because append mode requires an
             unwrapped cache).
    q_ref/o_ref: [block_q, D]; k_ref/v_ref: [block_k, D] — ONE K/V tile,
             so VMEM use does not depend on the key length.
    m_ref/l_ref: [block_q, 1] f32 running max / sum; acc_ref: [block_q, D]
             f32 — VMEM scratch carried across the (innermost,
             "arbitrary") K axis of the grid.
    window: sliding-window size (None = full attention) — key visible iff
             q_pos - window < k_pos.
    """
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)
    limit = vl_ref[0]
    q_start = off_ref[0] + qi * block_q
    lo, hi = _k_block_range(q_start, limit, block_q=block_q, block_k=block_k,
                            n_k=n_k, causal=causal, window=window)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((ki >= lo) & (ki <= hi))
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, _NT_DIMS,
                                preferred_element_type=jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < limit
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _store():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _pad_seq(x, mult: int):
    s = x.shape[1]
    pad = (-s) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x


def _flash_local(q, k, v, vl, off, *, scale, causal, window, block_q,
                 block_k, interpret):
    """The pallas_call over this device's heads. vl/off: int32 scalars."""
    b, s, hq, d = q.shape
    skv = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    # blocks stay multiples of 16 (bf16 TPU tile); _pad_seq covers the rest
    block_q = min(block_q, max(-(-s // 16) * 16, 16))
    block_k = min(block_k, max(-(-skv // 16) * 16, 16))

    q = _pad_seq(q, block_q)
    k = _pad_seq(k, block_k)
    v = _pad_seq(v, block_k)
    s_p, skv_p = q.shape[1], k.shape[1]
    n_k = skv_p // block_k

    # [B, S, H, D] -> [B*H, S, D] with GQA expansion folded into indexing
    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, s_p, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv_p, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv_p, d)

    def kv_index(h, i, j, vl_ref, off_ref):
        lo, hi = _k_block_range(off_ref[0] + i * block_q, vl_ref[0],
                                block_q=block_q, block_k=block_k, n_k=n_k,
                                causal=causal, window=window)
        j = jnp.clip(jnp.minimum(jnp.maximum(j, lo), hi), 0, n_k - 1)
        return (h // g, j, 0)

    def q_index(h, i, j, vl_ref, off_ref):
        return (h, i, 0)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * hq, s_p // block_q, n_k),
            in_specs=[
                pl.BlockSpec((None, block_q, d), q_index),
                pl.BlockSpec((None, block_k, d), kv_index),
                pl.BlockSpec((None, block_k, d), kv_index),
            ],
            out_specs=pl.BlockSpec((None, block_q, d), q_index),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hq, s_p, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="cake_flash_attention",
        interpret=interpret,
    )(vl.reshape(1), off.reshape(1), qt, kt, vt)
    out = out.reshape(b, hq, s_p, d).transpose(0, 2, 1, 3)
    return out[:, :s]


def flash_attention(q, k, v, scale: float | None = None, causal: bool = True,
                    valid_len=None, q_offset=None, window: int | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K, interpret: bool = False,
                    mesh=None):
    """q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D] (Hq multiple of Hkv).

    Returns [B, Sq, Hq, D]. Non-multiple-of-block lengths are padded here
    (pad keys are masked via the limit, pad query rows sliced off). K and V
    are tiled through the grid, so there is no key-length limit from VMEM.
    valid_len: int or traced scalar — number of valid NEW keys; the
       absolute limit becomes q_offset + valid_len.
    q_offset: absolute position of query row 0 (continued prefill over an
       unwrapped cache buffer whose index == position); None/0 = fresh.
    window: sliding-window size for SWA layers.
    mesh: the mesh the enclosing program is partitioned over, or None. A
       Mosaic kernel cannot be partitioned by GSPMD, so under a mesh the
       call is a shard_map: heads split over the mesh's `tp` axis
       (check_tp_divisibility guarantees Hkv % tp == 0, so every device
       keeps whole GQA groups) and every other axis (sp, dp, ep) sees
       replicated operands. That is decided from the mesh alone: with an
       `sp` axis a length-sharded cache is all-gathered over sp by XLA on
       the way in and each sp device runs the same kernel — exact, linear
       in memory, redundant in compute; the sp-efficient prefill is the
       "ring" mode, which never reaches this function.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    off = jnp.asarray(0 if q_offset is None else q_offset, jnp.int32)
    vl = off + jnp.asarray(q.shape[1] if valid_len is None else valid_len,
                           jnp.int32)
    local = functools.partial(_flash_local, scale=scale, causal=causal,
                              window=window, block_q=block_q, block_k=block_k,
                              interpret=interpret)
    if mesh is None:
        return local(q, k, v, vl, off)
    tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
    heads = P(None, None, tp, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(heads, heads, heads, P(), P()),
                         out_specs=heads, check_vma=False)(q, k, v, vl, off)


def flash_enabled() -> bool:
    """The Pallas attention kernels (prefill here, decode in
    ops/decode_attention.py): on for TPU backends unless CAKE_TPU_FLASH=0."""
    from .. import knobs
    return bool(knobs.get("CAKE_TPU_FLASH")) and jax.default_backend() == "tpu"
