"""Pallas latent attention for TPU: the ABSORBED read of a latent layer's
cache (models/deepseek_v2.py), a decode step and a prompt chunk alike.

A position of a latent row is ONE vector [c ; k_pe] of D = kv_lora_rank +
qk_rope_head_dim numbers (DeepSeek-V2: 576). With W_uk folded into the
queries every head's query is D wide too, all H heads read the SAME key,
and the value is the key's first Dv = kv_lora_rank columns. So the read is
multi-query attention with H x tokens query rows on one K/V head:

    s = scale * q_abs @ kv^T        [rows, D] x [D, block]
    o_lat = softmax(s) @ kv[:, :Dv] [rows, block] x [block, Dv]

The kernel walks a row's latents in blocks of block_k up to the frontier
of the queries it holds, reads each block ONCE for scores and for values
(one DMA, the value is a lane-aligned slice of it), keeps the heads as the
MXU's rows, and skips what a step masks out: the grid is (pool rows, query
blocks, key blocks) with the key axis innermost, and a key block past the
frontier re-names the last visible one, so Pallas issues no DMA for it and
the body is gated off. A row whose `limit` is 0 (inactive in this step)
reads nothing and gives zeros.

A query block is `tq` consecutive tokens x all H heads (row r is token
r // H, head r % H): one token a decode step, Q_ROWS / H of a chunk, so a
chunk's queries re-read the row's latents once a block of tq tokens and
not once a head. Inside a walked block a key is visible iff its `pos` leaf
is >= 0 and <= its query's position: the masked path's rule
(ops.attention.make_attention_mask), valid where buffer index == position
(an unwrapped buffer: a latent layer has no window). Precision is the
masked path's: operands in the cache dtype into the MXU with float32
accumulation, softmax in float32, probabilities cast to the cache dtype
for the values.

Inference-only (no VJP); under `vmap` the batch-1 call batches into the
kernel's own row axis (custom_vmap) instead of a loop over rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF

DEFAULT_BLOCK_K = 512
MIN_BLOCK_K = 128
# query rows (tokens x heads) of one grid step of a chunk
Q_ROWS = 512

# q @ k^T without materialising a transpose (contract the last dims)
_NT_DIMS = (((1,), (1,)), ((), ()))


def latent_block_k(t: int, block_k: int = DEFAULT_BLOCK_K) -> int | None:
    """The block length the kernel walks a buffer of length t in: block_k
    or its largest power-of-two fraction that divides t, down to
    MIN_BLOCK_K; None when none does."""
    bk = block_k
    while bk >= MIN_BLOCK_K:
        if t % bk == 0:
            return bk
        bk //= 2
    return None


def query_tokens(s: int, heads: int) -> int:
    """tq: how many of a step's s tokens share a query block: the largest
    power of two that divides s and keeps tq x heads within Q_ROWS."""
    tq = 1
    while tq * 2 * heads <= Q_ROWS and s % (tq * 2) == 0:
        tq *= 2
    return tq


def _visible_blocks(pos0, limit, i, *, tq, block_k):
    """How many key blocks query block i of a row walks: up to the last
    key its last token sees, inside the row's limit."""
    end = jnp.minimum(limit, pos0 + (i + 1) * tq)
    return (jnp.maximum(end, 0) + block_k - 1) // block_k


def _latent_kernel(pos0_ref, limit_ref, q_ref, kv_ref, pos_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, heads, tq, block_k, dv,
                   n_k):
    """One (row, query block, key block) grid step of the online softmax.
    pos0_ref/limit_ref: [B] SMEM: the absolute position of the row's first
    query, and the position limit of its keys. q_ref: [tq * H, D];
    kv_ref: [block_k, D]; pos_ref: [1, block_k]; o_ref: [tq * H, Dv];
    m/l: [tq * H, 1], acc: [tq * H, Dv], float32."""
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows = q_ref.shape[0]
    first = pos0_ref[b] + i * tq
    n_vis = _visible_blocks(pos0_ref[b], limit_ref[b], i, tq=tq,
                            block_k=block_k)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j < n_vis)
    def _step():
        kv = kv_ref[...]
        s = jax.lax.dot_general(q_ref[...], kv, _NT_DIMS,
                                preferred_element_type=jnp.float32) * scale
        kv_pos = pos_ref[...]                                 # [1, block_k]
        if tq == 1:
            q_pos = first
        else:
            q_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) // heads
        visible = (kv_pos >= 0) & (kv_pos <= q_pos)
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(kv.dtype), kv[:, :dv],
            preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _store():
        l = l_ref[...]
        # a query that saw nothing (an inactive row) reads as zeros
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def _latent_local(q, kv, kv_pos, pos0, limit, *, scale, dv, block_k,
                  interpret):
    """q: [B, S, H, D]; kv: [B, T, D]; kv_pos: [B, T]; pos0, limit: [B]
    int32. Returns [B, S, H, Dv]."""
    b, s, h, d = q.shape
    t = kv.shape[1]
    tq = query_tokens(s, h)
    n_q, n_k, rows = s // tq, t // block_k, tq * h
    walk = functools.partial(_visible_blocks, tq=tq, block_k=block_k)

    def block(bi, i, j, pos0_ref, limit_ref):
        """The key block grid step j reads: past the last visible one it
        re-names that one (no DMA)."""
        last = jnp.maximum(walk(pos0_ref[bi], limit_ref[bi], i) - 1, 0)
        return jnp.minimum(j, last)

    def kv_index(bi, i, j, *refs):
        return (bi, block(bi, i, j, *refs), 0)

    def pos_index(bi, i, j, *refs):
        return (bi, 0, block(bi, i, j, *refs))

    def q_index(bi, i, j, pos0_ref, limit_ref):
        return (bi, i, 0, 0)

    kernel = functools.partial(_latent_kernel, scale=scale, heads=h, tq=tq,
                               block_k=block_k, dv=dv, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_q, n_k),
            in_specs=[
                pl.BlockSpec((None, None, rows, d), q_index),
                pl.BlockSpec((None, block_k, d), kv_index),
                pl.BlockSpec((None, 1, block_k), pos_index),
            ],
            out_specs=pl.BlockSpec((None, None, rows, dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_q, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="cake_latent_decode_attention",
        interpret=interpret,
    )(pos0, limit, q.reshape(b, n_q, rows, d), kv,
      kv_pos.reshape(b, 1, t))
    return out.reshape(b, s, h, dv)


@functools.lru_cache(maxsize=None)
def _entry(scale: float, dv: int, block_k: int, interpret: bool):
    """The batched call for one static configuration, with the rule that
    keeps `vmap` from looping over rows: a mapped axis is merged into the
    kernel's own row axis (a reshape of leading dims, no copy of the
    latents), as ops.decode_attention does."""
    # jitted so that the layers of a model, which call it on the same
    # shapes, trace the kernel and lower it for Mosaic once between them
    local = jax.jit(functools.partial(
        _latent_local, scale=scale, dv=dv, block_k=block_k,
        interpret=interpret))

    @jax.custom_batching.custom_vmap
    def call(q, kv, kv_pos, pos0, limit):
        return local(q, kv, kv_pos, pos0, limit)

    @call.def_vmap
    def _merge(axis_size, in_batched, *args):
        merged = []
        for a, batched in zip(args, in_batched):
            if not batched:
                a = jnp.broadcast_to(a[None], (axis_size,) + a.shape)
            merged.append(a.reshape((axis_size * a.shape[1],) + a.shape[2:]))
        out = call(*merged)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return call


def latent_attention(q, kv, kv_pos, pos0, limit, dv: int,
                     scale: float, block_k: int = DEFAULT_BLOCK_K,
                     interpret: bool = False):
    """q: [B, S, H, D], the absorbed queries of S consecutive tokens a row
    (S = 1: a decode step); kv: [B, T, D] the rows' latents with the new
    entries already written (buffer index == position); kv_pos: [B, T]
    their `pos` leaf (-1 = empty). pos0: int32 scalar or [B], the absolute
    position of each row's first query; limit: int32 scalar or [B], keys at
    positions >= limit are not read (0: the row is masked out of the step
    and its output is zeros). dv: the leading columns of a latent that are
    its value. Returns [B, S, H, dv].

    T must be a multiple of latent_block_k(T, block_k)."""
    b = q.shape[0]
    bk = latent_block_k(kv.shape[1], block_k)
    assert bk is not None, "cache buffer no multiple of a block"
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (b,))
    limit = jnp.broadcast_to(jnp.asarray(limit, jnp.int32), (b,))
    return _entry(float(scale), int(dv), bk, bool(interpret))(
        q, kv, kv_pos, pos0, limit)


def latent_read(q, kv, kv_pos, q_pos, dv: int, scale: float):
    """The same read on XLA's ops (the CPU's path, and a buffer no block
    divides): scores of every query against the whole buffer under the
    position mask. q: [B, S, H, D]; kv: [B, T, D]; kv_pos: [B, T]; q_pos:
    [B, S]. Returns [B, S, H, dv]."""
    from .attention import make_attention_mask
    scores = jnp.einsum("bshc,btc->bhst", q, kv,
                        preferred_element_type=jnp.float32) * scale
    mask = make_attention_mask(q_pos, kv_pos)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,btc->bshc", probs.astype(kv.dtype),
                      kv[..., :dv]).astype(q.dtype)
