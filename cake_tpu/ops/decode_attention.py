"""Pallas decode attention for TPU: one query token a row, walked to the
row's frontier over the cache buffers as they lie.

The masked XLA path (ops/attention.py) attends a decode step over the
whole [rows, T, Hkv, D] buffer under a position mask, so the bytes it reads
scale with the buffer and not with what the rows hold. This kernel reads
the K and V buffers in place (no transpose, no slice of the pool), one
block of block_k tokens at a time, and only the blocks below each row's
frontier: cdiv(q_pos + 1, block_k) blocks for an active row, none for a row
whose `act` is false. Valid where buffer index == position (an unwrapped
full-attention buffer; layers.decode_kernel_block is the rule).

Schedule: the walk of every row is flattened, outside the kernel, into one
list of (row, block) steps (a handful of integer ops on [rows] vectors, the
same for every layer, so XLA computes it once a program). The kernel is ONE
invocation that runs a fori_loop over that list with the K, V and `pos`
blocks fetched by hand (pltpu.make_async_copy, N_BUF deep), so the block of
the next row is in flight while the last block of this one is computed and
a row that holds nothing costs no step at all.

Inside a walked block a key is visible iff its `pos` leaf is >= 0 and
<= q_pos — the masked path's rule, so a hole or a rolled-back entry below
the frontier stays invisible. Mathematics and precision are
multi_head_attention's: operands in the cache dtype into the MXU with f32
accumulation for q.k^T, softmax in f32, probabilities cast to the values'
dtype for p.v.

Heads are sliced inside the kernel. A block lands in VMEM as
[block_k * Hkv, D] rows (token-major, as in HBM); head h is every Hkv-th
row. A 16-bit dtype packs two rows into one 32-bit sublane word, so a pair
of heads is read with ONE strided load of the uint32 view and split with a
shift and a mask (the idiom of jax's ragged_paged_attention kernel). The
q heads of a pair share one [rows_p, block_k] score tile.

Inference-only (no VJP); under `vmap` the batch-1 call batches into the
kernel's own row axis (custom_vmap) instead of a loop over rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .attention import NEG_INF

DEFAULT_BLOCK_K = 256
# one lane tile of scores
MIN_BLOCK_K = 128
# K/V blocks in VMEM: one being computed, the others in flight
N_BUF = 3

# q @ k^T without materialising a transpose (contract the last dims)
_NT_DIMS = (((1,), (1,)), ((), ()))


def decode_block_k(t: int, block_k: int = DEFAULT_BLOCK_K) -> int | None:
    """The block length the kernel walks a buffer of length t in: block_k
    or, where that does not divide t, its largest power-of-two fraction
    that does, down to MIN_BLOCK_K; None when none divides it (a buffer
    shorter than one block among them)."""
    bk = block_k
    while bk >= min(block_k, MIN_BLOCK_K):
        if t % bk == 0:
            return bk
        bk //= 2
    return None


def _split_heads(ref, slot, j, hkv, n_tok):
    """Heads (2j, 2j+1) — or head j alone for a 32-bit dtype — of the block
    in ref[slot] ([block_k * Hkv, D], token-major): a list of [block_k, D]
    arrays in the buffer's dtype."""
    if ref.dtype.itemsize == 4:
        return [ref[slot, pl.ds(j, n_tok, stride=hkv), :]]
    # two heads a 32-bit word: even head in the low half, odd in the high
    w = ref.bitcast(jnp.uint32)[slot, pl.ds(j, n_tok, stride=hkv // 2), :]
    lo = pltpu.bitcast(w << 16, jnp.float32)
    hi = pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)
    return [lo.astype(ref.dtype), hi.astype(ref.dtype)]


def _decode_kernel(row_ref, blk_ref, nb_ref, qpos_ref, total_ref,
                   q_ref, pos_hbm, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, posbuf, sem, m_ref, l_ref, acc_ref,
                   *, scale, block_k, hkv, group, n_buf):
    """row_ref/blk_ref: [S] the flattened walk; nb_ref/qpos_ref: [B] blocks
    to walk and query position a row; total_ref: [1] steps in the walk.
    q_ref/o_ref: [B, n_pairs, rows_p, D] (the q heads of a K/V head pair,
    padded to rows_p); pos_hbm: [B up to 8s, T] and k_hbm/v_hbm:
    [B, T * Hkv, D], left in HBM. kbuf/vbuf: [n_buf, block_k * Hkv, D];
    posbuf: [n_buf, 8, block_k]; m/l: [n_pairs, rows_p, 1], acc:
    [n_pairs, rows_p, D], f32."""
    per = 1 if kbuf.dtype.itemsize == 4 else 2     # K/V heads a load
    n_pairs = hkv // per
    rows_p = q_ref.shape[2]
    total = total_ref[0]

    def copies(step, slot):
        r = row_ref[step]
        start = pl.multiple_of(blk_ref[step] * (block_k * hkv),
                               block_k * hkv)
        src = pl.ds(start, block_k * hkv)
        tok = pl.ds(pl.multiple_of(blk_ref[step] * block_k, block_k),
                    block_k)
        return (pltpu.make_async_copy(k_hbm.at[r, src], kbuf.at[slot],
                                      sem.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[r, src], vbuf.at[slot],
                                      sem.at[slot, 1]),
                # a DMA moves whole sublane tiles: the row's group of 8
                pltpu.make_async_copy(
                    pos_hbm.at[pl.ds(pl.multiple_of(r // 8 * 8, 8), 8), tok],
                    posbuf.at[slot], sem.at[slot, 2]))

    # rows that walk nothing (inactive, or empty) read as zeros
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def start(step):
        @pl.when(step < total)
        def _():
            for c in copies(step, step % n_buf):
                c.start()

    for i in range(n_buf - 1):
        start(i)

    def body(step, carry):
        slot = step % n_buf
        r, b = row_ref[step], blk_ref[step]
        # into the buffer the last step has finished with
        start(step + n_buf - 1)

        @pl.when(b == 0)
        def _init():
            m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        # which K/V head of the pair a score row belongs to
        first = jax.lax.broadcasted_iota(jnp.int32, (rows_p, 1), 0) < group

        for c in copies(step, slot):
            c.wait()
        kv_pos = posbuf[slot, pl.ds(r % 8, 1), :]
        visible = (kv_pos >= 0) & (kv_pos <= qpos_ref[r])     # [1, block_k]
        for j in range(n_pairs):
            q = q_ref[r, j]                                   # [rows_p, D]
            ks = _split_heads(kbuf, slot, j, hkv, block_k)
            s = [jax.lax.dot_general(q, kh, _NT_DIMS,
                                     preferred_element_type=jnp.float32)
                 for kh in ks]
            s = (s[0] if per == 1 else jnp.where(first, s[0], s[1])) * scale
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[j] = m_new
            vs = _split_heads(vbuf, slot, j, hkv, block_k)
            pv = [jnp.dot(p.astype(vh.dtype), vh,
                          preferred_element_type=jnp.float32) for vh in vs]
            pv = pv[0] if per == 1 else jnp.where(first, pv[0], pv[1])
            acc_ref[j] = acc_ref[j] * alpha + pv

        @pl.when(b == nb_ref[r] - 1)
        def _store():
            o_ref[r] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, total, body, 0)


def _walk(q_pos, act, n_blk: int, block_k: int):
    """The flattened walk: (row [S], block [S], blocks a row [B], steps
    [1]), S = B * n_blk. Steps past the total name the last row's last
    block and are never run."""
    b = q_pos.shape[0]
    nb = jnp.where(act, jnp.clip((q_pos + block_k) // block_k, 0, n_blk), 0)
    ends = jnp.cumsum(nb)
    steps = jnp.arange(b * n_blk, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(steps[:, None] >= ends[None, :], axis=1),
                      b - 1)
    blk = jnp.clip(steps - (ends - nb)[row], 0, n_blk - 1)
    return (row.astype(jnp.int32), blk.astype(jnp.int32),
            nb.astype(jnp.int32), ends[-1:].astype(jnp.int32))


def _decode_local(q, k, v, kv_pos, q_pos, act, *, scale, block_k,
                  interpret):
    """The pallas_call over this device's heads. q: [B, Hq, D]; k/v:
    [B, T, Hkv, D]; kv_pos: [B, T]; q_pos: [B] int32; act: [B] bool."""
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    per = 1 if k.dtype.itemsize == 4 else 2
    n_pairs = hkv // per
    # the q heads of one load's K/V heads share a score tile, padded to
    # whole sublane tiles of the operand dtype
    tile = 8 * (4 // q.dtype.itemsize)
    rows = per * group
    rows_p = -(-rows // tile) * tile
    qp = q.reshape(b, n_pairs, rows, d)
    if rows_p != rows:
        qp = jnp.pad(qp, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))
    row, blk, nb, total = _walk(q_pos, act, t // block_k, block_k)
    if b % 8:       # the kernel fetches positions by groups of 8 rows
        kv_pos = jnp.pad(kv_pos, ((0, -b % 8), (0, 0)), constant_values=-1)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               hkv=hkv, group=group, n_buf=N_BUF)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(),
            in_specs=[vmem, hbm, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((N_BUF, block_k * hkv, d), k.dtype),
                pltpu.VMEM((N_BUF, block_k * hkv, d), v.dtype),
                pltpu.VMEM((N_BUF, 8, block_k), jnp.int32),
                pltpu.SemaphoreType.DMA((N_BUF, 3)),
                pltpu.VMEM((n_pairs, rows_p, 1), jnp.float32),
                pltpu.VMEM((n_pairs, rows_p, 1), jnp.float32),
                pltpu.VMEM((n_pairs, rows_p, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        name="cake_decode_attention",
        interpret=interpret,
    )(row, blk, nb, q_pos, total, qp, kv_pos,
      k.reshape(b, t * hkv, d), v.reshape(b, t * hkv, d))
    return out[:, :, :rows].reshape(b, hq, d)


@functools.lru_cache(maxsize=None)
def _entry(scale: float, block_k: int, interpret: bool, mesh):
    """The batched call for one static configuration, with the rule that
    keeps `vmap` from looping over rows: a mapped axis is merged into the
    kernel's own row axis (a reshape of leading dims, no copy of K or V)."""
    def decode_rows(q, k, v, kv_pos, q_pos, act):
        return _decode_local(q, k, v, kv_pos, q_pos, act, scale=scale,
                             block_k=block_k, interpret=interpret)

    local = decode_rows
    if mesh is not None:
        # a Mosaic kernel cannot be partitioned by GSPMD: heads split over
        # `tp` as flash_attention does, every other axis replicated
        tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
        heads, kv = P(None, tp, None), P(None, None, tp, None)
        local = jax.shard_map(decode_rows, mesh=mesh,
                              in_specs=(heads, kv, kv, P(), P(), P()),
                              out_specs=heads, check_vma=False)
    # jitted so that the layers of a model, which call it on the same
    # shapes, trace the kernel and lower it for Mosaic once between them
    local = jax.jit(local)

    @jax.custom_batching.custom_vmap
    def call(q, k, v, kv_pos, q_pos, act):
        return local(q, k, v, kv_pos, q_pos, act)

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


def decode_attention(q, k, v, kv_pos, q_pos, act=None,
                     scale: float | None = None,
                     block_k: int = DEFAULT_BLOCK_K, interpret: bool = False,
                     mesh=None):
    """q: [B, 1, Hq, D], the query of one new token a row; k/v:
    [B, T, Hkv, D] cache buffers with the new entry already scattered in
    (buffer index == position); kv_pos: [B, T] their `pos` leaf (-1 =
    empty). q_pos: int32 scalar or [B], the query's absolute position;
    act: bool scalar or [B] (None = all true), false = the row is masked
    out of the step: no block of it is read and its output is zeros.
    Returns [B, 1, Hq, D].

    T must be a multiple of decode_block_k(T, block_k). mesh: as for
    flash_attention — heads split over `tp`, other axes replicated.
    """
    b, s, hq, d = q.shape
    assert s == 1, "decode attention takes one query token a row"
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bk = decode_block_k(k.shape[1], block_k)
    assert bk is not None, "cache buffer shorter than one block"
    q_pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (b,))
    act = jnp.broadcast_to(jnp.asarray(True if act is None else act,
                                       jnp.bool_), (b,))
    call = _entry(float(scale), bk, bool(interpret), mesh)
    return call(q[:, 0], k, v, kv_pos, q_pos, act)[:, None]
