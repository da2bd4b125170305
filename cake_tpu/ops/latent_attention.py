"""Pallas latent attention for TPU: the ABSORBED read of a latent layer's
cache (models/deepseek_v2.py), a decode step and a prompt chunk.

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
MXU's rows, and reads nothing a step masks out. A row whose `limit` is 0
(inactive in this step) reads nothing and gives zeros. Inside a walked
block a key is visible iff its `pos` leaf is >= 0 and <= its query's
position: the masked path's rule (ops.attention.make_attention_mask), valid
where buffer index == position (an unwrapped buffer: a latent layer has no
window). Precision is the masked path's: operands in the cache dtype into
the MXU with float32 accumulation, softmax in float32, probabilities cast
to the cache dtype for the values.

One algorithm, two bodies, chosen at trace time by the call's SHAPE alone
(the device op's shape in a trace says which ran: bf16[B, 1, H, Dv] against
bf16[B, n_q, tq * H, Dv]):

  * S > 1, a chunk or a verify step (`_latent_kernel`): a query block is
    `tq` consecutive tokens x all H heads (row r is token r // H, head
    r % H; Q_ROWS / H tokens of a chunk), so a chunk's queries re-read the
    row's latents once a block of tq tokens and not once a head. The grid
    is (pool rows, query blocks, key blocks), the key axis innermost; a key
    block past the frontier re-names the last visible one, so Pallas issues
    no DMA for it and the body is gated off. 512 rows a block keep the MXU
    at 57 % of its peak: bound by arithmetic.
  * S == 1, a decode step (`_decode_kernel`, PR 63): the query block is ONE
    token's H heads (128 rows at most), too few to hide a grid step's fixed
    cost (~0.35 us, paid 1,344 times a layer at 32 rows x 21 k latents) or
    one block's softmax. The grid is the pool rows alone; a row's key steps
    (DECODE_STEP_K latents = 4 blocks where the buffer divides) are a loop
    INSIDE the body over two buffers the body fills itself, the next key
    step's copy (at a row's end the next row's first) queued before the
    current one is waited for; within a key step every block is a softmax
    chain of its own, all score products first, merged once. Same products,
    same mask on every block, same precision. What was tried and did not
    pay (the latents as the MXU's streamed operand, the mask on a row's
    last block alone, the scale on the queries) is kept in
    scripts/latent_read_sweep.py with its readings (PERF.md section 5).

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
# latents a key step of a decode step holds (where the buffer divides)
DECODE_STEP_K = 2048
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


def _decode_kernel(pos0_ref, limit_ref, q_ref, kv_hbm, pos_hbm, o_ref,
                   kv_buf, pos_buf, sem, seen_ref, m_ref, l_ref, acc_ref, *,
                   scale, step, block_k, dv, n_rows, n_steps):
    """One ROW of a DECODE step's read a grid step: the query block is one
    token's H heads, and the row's key steps are a loop inside the body
    over two buffers the body fills itself. q_ref: [H, D]; kv_hbm: [B, T,
    D] and pos_hbm: [B, 1, T] where they lie; o_ref: [H, Dv]; kv_buf: [2,
    step, D]; pos_buf: [2, 1, step]; sem: [2, 2] DMA; seen_ref: [1] SMEM,
    the key steps read so far in the call (its parity is the buffer the
    next one lands in); m/l: [H, 1], acc: [H, Dv], float32.

    A key step is step // block_k blocks. Each block is a chain of its own
    (scores, mask, softmax against its OWN maximum, weighted sum) and the
    chains meet at the online softmax's merge, in one basic block with
    every block's score product first: no product waits for another
    block's softmax. The copy of the next key step (at a row's end: of the
    next row's first) is queued BEFORE the current one is waited for, so
    the DMA engine goes from one to the next without a gap and no grid step
    is spent on a key step, none at all on what a row does not hold. Of a
    row's LAST key step only the blocks below the frontier are computed."""
    b = pl.program_id(0)
    parts = step // block_k

    def key_steps(row):           # never past the buffer's end
        return jnp.minimum(n_steps, _visible_blocks(
            pos0_ref[row], limit_ref[row], 0, tq=1, block_k=step))

    def copies(row, j, slot):
        at = pl.ds(pl.multiple_of(j * step, step), step)
        return (pltpu.make_async_copy(kv_hbm.at[row, at, :], kv_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(pos_hbm.at[row, :, at],
                                      pos_buf.at[slot], sem.at[1, slot]))

    def start(row, j, slot):
        for copy in copies(row, j, slot):
            copy.start()

    @pl.when(b == 0)
    def _first():
        seen_ref[0] = 0

        @pl.when(key_steps(0) > 0)
        def _():
            start(0, 0, 0)

    n_vis, q_pos, seen = key_steps(b), pos0_ref[b], seen_ref[0]
    nxt = jnp.minimum(b + 1, n_rows - 1)
    hand_on = (b + 1 < n_rows) & (key_steps(nxt) > 0)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((n_vis == 0) & hand_on)
    def _idle():                 # a row the step masks out reads nothing
        start(nxt, 0, seen % 2)

    def fetch(j):
        """Queue the copy after key step j, wait for j's; its buffer."""
        slot = (seen + j) % 2

        @pl.when(j + 1 < n_vis)
        def _():
            start(b, j + 1, 1 - slot)

        @pl.when((j + 1 == n_vis) & hand_on)
        def _():
            start(nxt, 0, 1 - slot)

        for copy in copies(b, j, slot):
            copy.wait()
        return slot

    def walk(slot, blocks):
        q = q_ref[...]
        chains = []
        for c in blocks:
            at = slice(c * block_k, (c + 1) * block_k)
            s = jax.lax.dot_general(q, kv_buf[slot, at, :], _NT_DIMS,
                                    preferred_element_type=jnp.float32) * scale
            kv_pos = pos_buf[slot, :, at]                     # [1, block_k]
            visible = (kv_pos >= 0) & (kv_pos <= q_pos)
            s = jnp.where(visible, s, NEG_INF)
            chains.append((at, s, visible,
                           jnp.max(s, axis=-1, keepdims=True)))
        m_prev = m_ref[...]
        m_new = functools.reduce(jnp.maximum, [c[3] for c in chains], m_prev)
        alpha = jnp.exp(m_prev - m_new)
        l_new, acc = l_ref[...] * alpha, acc_ref[...] * alpha
        for at, s, visible, m_c in chains:
            p = jnp.where(visible, jnp.exp(s - m_c), 0.0)
            w = jnp.exp(m_c - m_new)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True) * w
            acc = acc + w * jnp.dot(p.astype(kv_buf.dtype),
                                    kv_buf[slot, at, :dv],
                                    preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc

    def whole(j, carry):
        walk(fetch(j), range(parts))
        return carry

    jax.lax.fori_loop(0, n_vis - 1, whole, 0)

    @pl.when(n_vis > 0)
    def _last():
        j = n_vis - 1
        slot = fetch(j)
        end = jnp.minimum(limit_ref[b], q_pos + 1) - j * step
        # one body a count of visible blocks, each ONE basic block (a
        # `when` a block, one chain after the other, read 8 % slower at 32
        # heads: scripts/latent_read_sweep.py)
        for n in range(1, parts + 1):
            pl.when((end + block_k - 1) // block_k == n)(
                functools.partial(walk, slot, range(n)))

    seen_ref[0] = seen + n_vis
    l = l_ref[...]
    # a query that saw nothing (an inactive row) reads as zeros
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                  ).astype(o_ref.dtype)


def _decode_local(q, kv, kv_pos, pos0, limit, *, scale, dv, block_k,
                  interpret):
    """A decode step: q [B, 1, H, D], one token a row. The grid is the pool
    rows; a key step is DECODE_STEP_K latents where the buffer divides, else
    the longest run of whole blocks that does (down to one)."""
    b, _, h, d = q.shape
    t = kv.shape[1]
    step = max(latent_block_k(t, DECODE_STEP_K), block_k)

    def row(bi, pos0_ref, limit_ref):
        return (bi, 0, 0, 0)

    kernel = functools.partial(_decode_kernel, scale=scale, step=step,
                               block_k=block_k, dv=dv, n_rows=b,
                               n_steps=t // step)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, None, h, d), row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, None, h, dv), row),
            scratch_shapes=[
                pltpu.VMEM((2, step, d), kv.dtype),
                pltpu.VMEM((2, 1, step), kv_pos.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, h, dv), q.dtype),
        # the rows in order: a row's last key step starts the next row's
        # first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="cake_latent_decode_attention",
        interpret=interpret,
    )(pos0, limit, q, kv, kv_pos.reshape(b, 1, t))


def _latent_local(q, kv, kv_pos, pos0, limit, *, scale, dv, block_k,
                  interpret):
    """q: [B, S, H, D]; kv: [B, T, D]; kv_pos: [B, T]; pos0, limit: [B]
    int32. Returns [B, S, H, Dv]. The form is chosen by shape: S == 1 is a
    decode step, anything else a chunk."""
    b, s, h, d = q.shape
    t = kv.shape[1]
    if s == 1:
        return _decode_local(q, kv, kv_pos, pos0, limit, scale=scale, dv=dv,
                             block_k=block_k, interpret=interpret)
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
