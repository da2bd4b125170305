"""Static-shape KV cache.

The reference cache concatenates/trims KV tensors per token
(ref: models/common/cache.rs:163-210) — dynamic shapes that would force an
XLA recompile every step. The TPU design preallocates fixed buffers and
scatters new entries in, carrying an absolute-position array per layer
(-1 = empty) that drives position-based masking (ops/attention.py):

  * full-attention layers: buffer of max_seq_len, slot i holds position i;
  * sliding-window layers: ring buffer of window size W, slot p%W holds
    position p (ref cache.rs:173-182 trims instead — same visibility);
  * linear-attention layers: O(1) recurrent + conv state instead of KV
    (ref cache.rs:18-23,221-238 GDN states);
  * Mamba layers: a conv tail and a diagonal state per channel
    (models/jamba.py), recurrent rows like the above.

The cache is a plain pytree (list of per-layer dicts + scalar pos) so it
flows through jit/donate/shard unchanged. Each connection gets a fresh
cache (ref worker.rs get_client_context / cache.as_new()).
"""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp

from .config import AttnShape, LayerSpec, ModelConfig
from .mixers import mixer_of

LANES = 128     # the TPU's minor tile: a buffer's last dim is padded to it


def key_row_shape(a: AttnShape) -> tuple[int, ...]:
    """What one position of a K leaf is, past [rows, length]. Keys whose
    width is a multiple of the 128 lanes lie as [Hkv, D]. Keys of another
    width (MiMo-V2's 192) lie JOINED, [Hkv * D], where that is a multiple:
    nothing is padded, so the runtime keeps the buffer D-minor, a token's
    keys are one contiguous run, and the one-token scatter, the chunk's
    scatter and the row operations write the leaf as it lies. At [Hkv, 192]
    the runtime stores the buffer length-minor (D-minor would pad 192 to 256
    lanes) and every program that scatters into it first copies it whole to
    a D-minor layout and back (PERF.md, PR 40). The masked read takes a
    joined leaf in place (ops.attention.multi_head_attention); the flash
    kernel is handed the one row it reads with its heads split, which
    copies that row (layers.attention_forward)."""
    if a.head_dim % LANES and a.size_k % LANES == 0:
        return (a.size_k,)
    return (a.kv_heads, a.head_dim)


def latent_row_width(width: int) -> int:
    """The lanes a latent layer's entry takes: its width (DeepSeek-V2: 576)
    rounded up to whole 128-lane tiles (640), the pad zeros. At [rows, T,
    576] the runtime stores the leaf length-minor (576-minor would pad it
    to 640 all the same) and every program that reads it 576-minor (the
    Pallas read, XLA's scores) first copies the WHOLE buffer, 1.0 GB a
    layer at 32 x 24,576 (the described-v5e compile, PR 56): PR 40's lesson
    with a leaf that has no heads to join. Padded by hand the leaf lies as
    the kernel reads it and nothing copies it."""
    return -(-width // LANES) * LANES


def keys_joined(lc: dict) -> bool:
    """Does this positional layer hold its keys joined (key_row_shape); a
    layer without keys by head (a latent one) does not."""
    return "k" in lc and lc["k"].ndim == 3


def joined_key_widths(layers: list[dict]) -> dict[int, int]:
    """Joined width -> how many of these layers hold their keys so; empty
    for a model whose key widths are all multiples of the lanes."""
    return dict(Counter(lc["k"].shape[2] for lc in layers
                        if is_positional(lc) and keys_joined(lc)))


def positional_leaves(lead: tuple[int, int], a: AttnShape, dtype) -> dict:
    """The leaves of a layer whose entries are addressed by position, given
    the two axes in front of a position: (batch, size) for a row's buffer
    or ring, (num_blocks, block_tokens) for the paged pool. The one place
    that says what they are; `pos` is what cache.is_positional reads. A
    latent layer (AttnShape.latent) holds ONE vector a position, the normed
    latent and the roped shared key part side by side, and no K or V by
    head, padded with zeros to whole lane tiles (latent_row_width). Everything
    below goes by the leaves it finds: whatever is not `pos` is an entry's
    bytes, [.., .., *entry]."""
    if a.latent:
        return {"kv": jnp.zeros(lead + (latent_row_width(a.head_dim),),
                                dtype),
                "pos": jnp.full(lead, -1, jnp.int32)}
    return {"k": jnp.zeros(lead + key_row_shape(a), dtype),
            "v": jnp.zeros(lead + (a.kv_heads, a.v_head_dim), dtype),
            "pos": jnp.full(lead, -1, jnp.int32)}


def init_attention_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                         max_seq_len: int, dtype) -> dict:
    """An attention layer's rows: a buffer of max_seq_len, or a ring of its
    window, at this layer kind's head count and widths."""
    size = max_seq_len if spec.window is None else min(spec.window, max_seq_len)
    return positional_leaves((batch, size), cfg.attn_shape(spec), dtype)


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_seq_len: int, dtype=jnp.bfloat16) -> dict:
    return mixer_of(cfg, spec).init_cache(cfg, spec, batch, max_seq_len,
                                          dtype)


def init_cache(cfg: ModelConfig, batch: int, max_seq_len: int,
               dtype=jnp.bfloat16, layer_range: tuple[int, int] | None = None) -> dict:
    """Cache for a contiguous layer range (workers hold only their range —
    ref: partial VarBuilder loading, utils/mod.rs:251-333)."""
    lo, hi = layer_range or (0, cfg.num_hidden_layers)
    return {
        "layers": [init_layer_cache(cfg, cfg.layer_spec(i), batch, max_seq_len, dtype)
                   for i in range(lo, hi)],
        "pos": jnp.zeros((), jnp.int32),
    }


def is_positional(lc: dict) -> bool:
    """What a layer's row of state is, read off its leaves: with a `pos`
    leaf it holds entries addressed by position (a full buffer or a ring,
    remapped at position % size and rolled back by marking pos -1);
    without one it is recurrent state of fixed size, copied whole. The
    row operations below go by this and by nothing else, so a layer kind
    with new state leaves needs no arm in any of them."""
    return "pos" in lc


def row_state_bytes(layers: list[dict]) -> int:
    """Bytes ONE row of a pool holds in leaves that are not addressed by
    position (recurrent state: read and written whole by every step the
    row takes, whatever its length); 0 for a model with none."""
    return sum(leaf.nbytes // leaf.shape[0] for lc in layers
               if not is_positional(lc) for leaf in lc.values())


def update_kv_cache(layer_cache: dict, k_new, v_new, pos, valid_len=None):
    """write_entries for a layer of keys and values by head. k_new/v_new:
    [B, S, Hkv, D]; where the layer holds its keys joined (key_row_shape),
    k_new is written so."""
    if keys_joined(layer_cache):
        k_new = k_new.reshape(k_new.shape[:2] + (-1,))
    return write_entries(layer_cache, {"k": k_new, "v": v_new}, pos,
                         valid_len)


def write_entries(layer_cache: dict, new: dict, pos, valid_len=None):
    """Write S new entries at absolute positions pos..pos+S-1: `new` holds
    [B, S, *entry] for every leaf of the layer but `pos`; pos: traced
    scalar int32.
    Ring semantics: slot = position % size. When S > size only the last
    `size` entries are written (the earlier ones would be overwritten anyway),
    keeping scatter indices unique.

    valid_len (traced scalar, bucketed prefill): entries with index >=
    valid_len are padding — their slots are remapped out-of-bounds so the
    scatter drops them (jax default scatter mode drops OOB writes).
    """
    size = layer_cache["pos"].shape[1]
    s = next(iter(new.values())).shape[1]
    if s > size:
        # Keep the last `size` VALID entries: with bucketed-prefill padding
        # the tail of k_new is garbage, so the slice starts at
        # valid_len - size (clamped), not at s - size.
        if valid_len is None:
            start = jnp.asarray(s - size, jnp.int32)
        else:
            start = jnp.clip(valid_len - size, 0, s - size).astype(jnp.int32)
        new = {n: jax.lax.dynamic_slice_in_dim(a, start, size, axis=1)
               for n, a in new.items()}
        offset = start
        s = size
    else:
        offset = jnp.asarray(0, jnp.int32)
    idx = offset + jnp.arange(s, dtype=jnp.int32)          # [S] source indices
    positions = pos + idx
    slots = positions % size
    if valid_len is not None:
        slots = jnp.where(idx < valid_len, slots, size)    # OOB -> dropped
    out = {n: layer_cache[n].at[:, slots].set(a, mode="drop")
           for n, a in new.items()}
    out["pos"] = layer_cache["pos"].at[:, slots].set(positions[None, :],
                                                     mode="drop")
    return out


def kv_capacity(cfg: ModelConfig, cache: dict,
                layer_range: tuple[int, int] | None = None) -> int | None:
    """Smallest full-attention buffer length in the cache — positions past
    it would silently wrap. None when the range has only ring (SWA) or
    recurrent-state layers, which wrap/forget by design."""
    lo, hi = layer_range or (0, cfg.num_hidden_layers)
    caps = [lc["pos"].shape[1]
            for i, lc in zip(range(lo, hi), cache["layers"])
            if is_positional(lc) and cfg.layer_spec(i).window is None]
    return min(caps) if caps else None


def _entries_then_pos(lc: dict) -> list[str]:
    """A positional layer's leaf names, `pos` last: the order the row
    operations have always written them in (K, V, then positions), so a
    layer of keys and values lowers to the program it lowered to before
    the operations went by the leaves."""
    return sorted(lc, key=lambda name: name == "pos")


def _empty(lead: tuple, like, name: str):
    """An empty buffer of `like`'s entries behind the axes `lead`: zeros,
    and -1 for the `pos` leaf."""
    shape = lead + like.shape[2:]
    return jnp.full(shape, -1, like.dtype) if name == "pos" \
        else jnp.zeros(shape, like.dtype)


def grow_layer_kv(lc: dict, new_size: int) -> dict:
    """Re-home a KV layer cache into a larger buffer.

    Entries are re-scattered at slot = pos % new_size, so this is correct
    for both full-attention buffers (identity prefix copy) and
    sliding-window rings (remap). Empty slots (pos == -1) are dropped via
    the OOB-scatter trick used by update_kv_cache.
    """
    b, old_size = lc["pos"].shape
    if new_size <= old_size:
        return lc
    pos = lc["pos"]                                        # [B, old]
    slots = jnp.where(pos >= 0, pos % new_size, new_size)  # OOB -> dropped
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
    return {name: _empty((b, new_size), lc[name], name)
            .at[bidx, slots].set(lc[name], mode="drop")
            for name in _entries_then_pos(lc)}


def grow_cache(cfg: ModelConfig, cache: dict, new_len: int,
               layer_range: tuple[int, int] | None = None) -> dict:
    """Grow every KV buffer to min(new_len, its window) slots.

    Cache-length bucketing (the single-chip decode perf lever): decode
    attends over the allocated buffer, so short generations keep a small
    buffer and grow it bucket-by-bucket instead of always paying
    max_cache_len worth of attention bandwidth per token (the reference
    trims to actual length per step instead — cache.rs:163-210; under XLA
    we recompile per bucket, which happens O(log max_len) times).
    Recurrent state is O(1) and passes through untouched.
    """
    lo, hi = layer_range or (0, cfg.num_hidden_layers)
    new_layers = []
    for i, lc in zip(range(lo, hi), cache["layers"]):
        if not is_positional(lc):
            new_layers.append(lc)
            continue
        window = cfg.layer_spec(i).window
        target = new_len if window is None else min(window, new_len)
        new_layers.append(grow_layer_kv(lc, target))
    return {"layers": new_layers, "pos": cache["pos"]}


def slot_reset_layers(layers: list[dict], slot) -> list[dict]:
    """Clear row `slot` of a batched cache pool (positions -> -1, state ->
    zeros) without touching the other rows or reallocating — the
    continuous-batching engine's per-request release. `slot` may be a
    traced scalar, so one jitted program serves every slot index."""
    out = []
    for lc in layers:
        o = {}
        for name, buf in lc.items():
            if name == "pos":
                o[name] = buf.at[slot].set(jnp.full(buf.shape[1:], -1,
                                                    buf.dtype))
            else:
                o[name] = buf.at[slot].set(jnp.zeros(buf.shape[1:], buf.dtype))
        out.append(o)
    return out


def slot_assign_layers(pool_layers: list[dict], src_layers: list[dict],
                       slot) -> list[dict]:
    """Write a batch-1 cache (a fresh request's bucketed prefill) into row
    `slot` of the batched pool, replacing whatever the row held.

    Entries are re-homed at position % row_size — the same remap
    grow_layer_kv uses — so a prompt prefilled into a small-bucket cache
    lands correctly in the pool's larger full-attention buffers and
    sliding-window rings (the pool ring is never smaller than the source
    ring, so the scatter stays injective). Recurrent state copies through
    row-wise, leaf by leaf. `slot` may be a traced scalar.
    """
    out = []
    for pl, sl in zip(pool_layers, src_layers):
        if not is_positional(pl):
            out.append({n: pl[n].at[slot].set(sl[n][0]) for n in pl})
            continue
        size = pl["pos"].shape[1]
        pos = sl["pos"][0]                                 # [src_size]
        slots = jnp.where(pos >= 0, pos % size, size)      # OOB -> dropped
        empty = {name: _empty((size,), pl[name], name)
                 for name in _entries_then_pos(pl)}
        out.append({name: pl[name].at[slot].set(
            row.at[slots].set(sl[name][0], mode="drop"))
            for name, row in empty.items()})
    return out


def slot_extract_block_layers(pool_layers: list[dict], slot, start,
                              width: int) -> list[dict]:
    """Copy one prefix BLOCK (absolute positions start .. start+width-1) out
    of pool row `slot` into a batch-1 pytree — the shared-prefix cache's
    insert path. Must be called right after prefill has advanced the row to
    exactly start+width:

      * positional layers: gather the block's entries through the ring map
        (index = position % buffer). A ring smaller than the block gives
        the block's LAST `size` positions, all it holds of it: the row's
        state at the boundary, as recurrent state is. Spliced back by
        position % size, a later block of a chain overwrites the ring
        whole, so the chain's end is exact;
      * recurrent layers: the state IS the prefix summary at this
        boundary, so the snapshot (every leaf) is exact only at the current
        position — the reason blocks are captured at chunk boundaries
        during prefill instead of after the fact.

    `slot`/`start` may be traced scalars; `width` is static (one program
    per block size)."""
    out = []
    for pl in pool_layers:
        if not is_positional(pl):
            out.append({n: pl[n][slot][None] for n in pl})
            continue
        size = pl["pos"].shape[1]
        n = min(width, size)
        idx = (start + width - n + jnp.arange(n, dtype=jnp.int32)) % size
        out.append({name: pl[name][slot][idx][None]
                    for name in _entries_then_pos(pl)})
    return out


def restore_runs(size: int, block: int, n: int) -> list[list[int]] | None:
    """What a positional buffer of `size` entries keeps of a piece of `n`
    chain blocks of `block` tokens each, from shapes alone: the blocks it
    reads (by their index in the piece), grouped into the runs they lie in
    the row as. A block is a run wherever buffer and block divide one
    another: a buffer of q blocks keeps the piece's last min(n, q), a ring
    narrower than a block the last block's own tail, which is the ring
    whole. They join into ONE run where the piece is ALIGNED (its first
    block a multiple of n: slot_restore_chain_layers' contract) and n and q
    divide one another, since a run that starts on a multiple of its own
    length cannot wrap; else each is a run of its own. None: no run rule
    covers the shape (a ring that is no multiple of the block, nor the
    block of it), every block is read and scattered entry by entry."""
    if block % size == 0:
        return [[n - 1]]
    if size % block:
        return None
    q = size // block
    k = min(n, q)
    kept = list(range(n - k, n))
    return [kept] if max(n, q) % k == 0 else [[b] for b in kept]


def _write_run(lc: dict, src: dict, slot, start) -> dict:
    """Write `src` (leaves [L, ...], no batch axis) over the L entries of
    row `slot` from index `start` on, as one slab a leaf. Where a source
    `pos` is -1 the row keeps what it had, bytes and position: the
    scatter's drop rule, as a select over the slab."""
    length = src["pos"].shape[0]
    keep = src["pos"] >= 0
    out = {}
    for name, buf in lc.items():
        at = (slot, start) + (jnp.int32(0),) * (buf.ndim - 2)
        old = jax.lax.dynamic_slice(buf, at, (1, length) + buf.shape[2:])
        new = jnp.where(keep.reshape((1, length) + (1,) * (buf.ndim - 2)),
                        src[name][None].astype(buf.dtype), old)
        out[name] = jax.lax.dynamic_update_slice(buf, new, at)
    return out


def _scatter_block(lc: dict, src: dict, slot) -> dict:
    """One block's entries into row `slot` at position % size, entry by
    entry (pos -1: dropped): what a run cannot express."""
    size = lc["pos"].shape[1]
    pos = src["pos"][0]                                    # [width]
    slots = jnp.where(pos >= 0, pos % size, size)          # OOB -> dropped
    return {name: lc[name].at[slot, slots].set(src[name][0], mode="drop")
            for name in _entries_then_pos(lc)}


def restore_reads(pool_layers: list[dict], chain: list[list[dict]],
                  block: int) -> list[list[dict]]:
    """The chain with every layer of a block that slot_restore_chain_layers
    does not read replaced by {}: a ring's overwritten blocks, every
    recurrent snapshot but the last. What the jitted program is handed, so
    what is not read is not an argument either."""
    n = len(chain)
    reads = []
    for pl in pool_layers:
        runs = restore_runs(pl["pos"].shape[1], block, n) \
            if is_positional(pl) else [[n - 1]]
        reads.append(range(n) if runs is None else sum(runs, []))
    return [[lc if b in reads[i] else {} for i, lc in enumerate(blk)]
            for b, blk in enumerate(chain)]


def slot_restore_chain_layers(pool_layers: list[dict],
                              chain: list[list[dict]], slot, first_block,
                              block: int, final) -> list[dict]:
    """Restore a piece of a matched prefix chain (`chain`: consecutive
    blocks, slot_extract_block_layers output each, the first one block
    `first_block` of the prompt) into pool row `slot` WITHOUT resetting the
    rest of the row, so the pieces of a chain merge and admission prefills
    the suffix alone. The row holds afterwards what block-by-block scatters
    at position % row_size leave, and each byte of it is written once:

      * a positional layer (restore_runs): the blocks the buffer keeps,
        concatenated into one slab where they lie as one run (a full
        buffer: the whole piece at first_block * block; a ring: its last
        blocks, or the last block's tail), else a slab a block. A ring's
        earlier blocks are not read: a block captured at a chunk boundary
        holds every position it spans, so the later ones overwrite them
        whole (a `pos` of -1 in a block that IS read keeps what the row
        had). A shape no run rule covers takes the scatter, block by
        block;
      * a recurrent layer: its state is a block-END snapshot, so the
        piece's LAST block installs it, once, and only where `final`
        (traced bool: the chain's last piece).

    `slot`, `first_block`, `final` may be traced; `block` and the piece's
    length are static (one program per length). The piece must be ALIGNED,
    first_block a multiple of its length (TextModel.slot_restore checks
    it): pieces of powers of two, largest first, always are. The row must
    have been wiped at release, so everything outside the restored prefix
    is still empty."""
    n = len(chain)
    out = []
    for i, pl in enumerate(pool_layers):
        if not is_positional(pl):
            snap = chain[-1][i]
            out.append({name: pl[name].at[slot].set(
                jnp.where(final, snap[name][0], pl[name][slot]))
                for name in pl})
            continue
        size = pl["pos"].shape[1]
        runs = restore_runs(size, block, n)
        if runs is None:
            for blk in chain:
                pl = _scatter_block(pl, blk[i], slot)
            out.append(pl)
            continue
        m = min(block, size)            # entries a block holds of this layer
        for run in runs:
            src = {name: jnp.concatenate([chain[b][i][name][0] for b in run])
                   for name in pl}
            # the run's first stored position, at its place in the row
            start = ((first_block + run[0] + 1) * block - m) % size
            pl = _write_run(pl, src, slot, start)
        out.append(pl)
    return out


def truncate_layers(layers: list[dict], new_end) -> list[dict]:
    """Mark every KV entry at absolute position >= new_end empty (pos -1)
    across the whole batch — the speculative-decoding rejected-suffix
    rollback, traceable (new_end may be a traced scalar) so the verify
    program can truncate in the same compiled step that discovered the
    rejection. K/V bytes are left in place: position-based masking makes
    a pos==-1 slot invisible, and the next write re-scatters over it.

    Recurrent layers pass through UNCHANGED: their state cannot be
    truncated after the fact. Callers with such layers must instead
    rebuild the state with a valid_len-masked commit forward
    (text_model.slot_verify does exactly that — the same machinery that
    keeps bucketed-prefill padding out of the state).
    """
    return [{**lc, "pos": jnp.where(lc["pos"] >= new_end, -1, lc["pos"])}
            if is_positional(lc) else lc for lc in layers]


def truncate_cache(cache: dict, new_end: int) -> dict:
    """Host-level cache rollback to positions < new_end (pos scalar
    clamped too) — the draft-model drafter discards its own speculative
    suffix with this between proposals. Raises for recurrent layers:
    their state cannot roll back, and a silent pass-through here would
    hand the caller a cache that CLAIMS new_end tokens but carries state
    from more (truncate_layers documents pass-through instead because its
    in-trace callers — the verify programs — handle the recurrent commit
    themselves via a valid_len-masked re-forward)."""
    if not all(is_positional(lc) for lc in cache["layers"]):
        raise ValueError(
            "truncate_cache cannot roll back linear-attention state; "
            "use a valid_len-masked re-forward instead")
    return {"layers": truncate_layers(cache["layers"], new_end),
            "pos": jnp.minimum(cache["pos"], new_end)}


# -- paged KV: block-pool storage behind a per-slot indirection table -------
#
# The slot pool above provisions every row for the worst-case context
# (B x ctx of KV per layer). Paged mode (vLLM/PagedAttention) splits
# full-attention KV into fixed BLOCK_TOKENS-sized physical blocks in one
# shared pool per layer; a slot owns only the blocks its sequence has
# actually reached, addressed through a [max_blocks] block TABLE whose
# entry j maps logical positions [j*bt, (j+1)*bt) to a physical block id
# (the sentinel id == num_blocks means unmapped). Only full-attention
# layers page: a sliding-window ring is already O(window) per slot and a
# linear-attention state is O(1), so both stay per-slot "row" state —
# paging them would add indirection without saving a byte.
#
# The gather below materializes a slot's logical row from the pool with
# EXACTLY the contiguous row's shape and layout (entry for position p at
# row index p % L): the forward over a paged view is the same computation
# on the same bytes, which is what makes paged decode bit-identical to
# the contiguous path and lets forward_layers run unchanged.


def layer_is_pooled(spec: LayerSpec) -> bool:
    """Does this layer's KV live in the shared block pool: positional and
    without a window (see the note above)."""
    return not spec.recurrent and spec.window is None


def init_paged_layers(cfg: ModelConfig, num_blocks: int, block_tokens: int,
                      batch: int, ctx: int, dtype=jnp.bfloat16,
                      layer_range: tuple[int, int] | None = None
                      ) -> tuple[list[dict], list[dict]]:
    """(pool_layers, row_layers) for a paged slot pool.

    pool_layers[i] holds the physical block pool for full-attention layer
    i ({k,v: [num_blocks, block_tokens, H, D] (k joined where
    key_row_shape says so), pos: [num_blocks, block_tokens]}) and an
    EMPTY dict elsewhere; row_layers[i] holds the per-slot state for
    sliding-window rings and recurrent layers (leading batch axis) and an
    empty dict at pooled positions. Empty
    dicts keep both lists layer-aligned pytrees with zero leaves at the
    other list's positions, so they vmap/donate cleanly side by side.
    """
    lo, hi = layer_range or (0, cfg.num_hidden_layers)
    pool, rows = [], []
    for i in range(lo, hi):
        spec = cfg.layer_spec(i)
        if not layer_is_pooled(spec):
            pool.append({})
            rows.append(init_layer_cache(cfg, spec, batch, ctx, dtype))
        else:
            pool.append(positional_leaves((num_blocks, block_tokens),
                                          cfg.attn_shape(spec), dtype))
            rows.append({})
    return pool, rows


def paged_gather_layer(pl: dict, table_row, frontier) -> dict:
    """Materialize one slot's logical KV row from a layer's block pool
    through its block table (`table_row`: [M] physical ids; id ==
    num_blocks = unmapped). Returns {k, v, pos} WITHOUT a batch axis
    (leaves [M*bt, ...]) — callers add [None] to feed forward_layers.

    Stale-tenant guard: a freed block is never wiped on the device, so
    a gathered entry is real iff BOTH hold:

      * it lands in its table entry's own logical range
        (pos // bt == table index j) — a recycled block still carrying
        a previous tenant's positions from a DIFFERENT range is masked;
      * pos < `frontier`, the slot's write frontier (prefill: pos0;
        decode: the step's write position). The row's contract is
        "holds exactly positions 0 .. frontier-1" — precisely what a
        wiped contiguous row guarantees — which kills the same-index
        recycling case: a stale entry claiming a position the sequence
        has not reached yet would otherwise be VISIBLE to the
        [cache ; in-pass chunk] prefill concat as a duplicate key.

    Masked entries get pos = -1; attention weights for pos == -1 are
    exactly zero, so the masking is bit-exact. The k/v garbage under a
    masked pos is finite bytes, never read into the output."""
    nblocks, bt = pl["pos"].shape
    mapped = table_row < nblocks                           # [M]
    safe = jnp.where(mapped, table_row, 0)
    out = {name: buf[safe].reshape((-1,) + buf.shape[2:])
           for name, buf in pl.items() if name != "pos"}
    pos = pl["pos"][safe]                                  # [M, bt]
    blk = jnp.arange(table_row.shape[0], dtype=jnp.int32)[:, None]
    own = jnp.logical_and(mapped[:, None], pos // bt == blk)
    own = jnp.logical_and(own, pos < frontier)
    out["pos"] = jnp.where(own, pos, -1).reshape(-1)
    return out


def paged_block_of(view_lc: dict, wb, bt: int) -> dict:
    """Slice block `wb` (traced table index) out of a gathered/updated
    row view — the write-back unit after a forward advanced the view.
    Returns {k: [bt, H, D], v: [bt, H, D], pos: [bt]}."""
    start = wb * bt
    return {name: jax.lax.dynamic_slice_in_dim(a, start, bt, axis=0)
            for name, a in view_lc.items()}


def paged_block_window(view_lcs: list[dict], table_row, first_pos, n_written,
                       width: int, bt: int, nblocks: int
                       ) -> tuple[jax.Array, list[dict]]:
    """The write-back window after a forward wrote positions first_pos ..
    first_pos + n_written - 1 into a slot's gathered row views: the
    multi-block paged_block_of. `width` table entries (static: what the
    widest write of this program can span) are slid to start at the first
    written block — never clamped mid-block, so block alignment survives at
    the table's tail — and the entries outside [first written block, last
    written block] are masked to the drop sentinel `nblocks`.

    view_lcs: per layer the updated row view without a batch axis, or {}
    for a layer that is not pooled. Returns (pids [nwb], blks): the
    physical ids to scatter to, and per pooled layer {k, v: [nwb, bt, H,
    D], pos: [nwb, bt]} cut from its view ({} elsewhere) — the arguments
    of paged_scatter_blocks."""
    m = table_row.shape[0]
    nwb = min(width, m)
    b0 = first_pos // bt
    last_b = (first_pos + jnp.maximum(n_written, 1) - 1) // bt
    shift = jnp.clip(b0, 0, m - nwb)
    bidx = shift + jnp.arange(nwb, dtype=jnp.int32)
    touched = jnp.logical_and(bidx >= b0, bidx <= last_b)
    pids = jnp.where(touched, table_row[bidx], nblocks)

    def cut(a):
        return jax.lax.dynamic_slice_in_dim(
            a, shift * bt, nwb * bt, axis=0).reshape((nwb, bt) + a.shape[1:])

    return pids, [{n: cut(a) for n, a in lc.items()} for lc in view_lcs]


def paged_scatter_blocks(pl: dict, pids, blk: dict) -> dict:
    """Write block contents back into a layer's pool at physical ids
    `pids` ([n] int32, leaves [n, bt, ...]). Entries with pid ==
    num_blocks are DROPPED (the masked-slot / beyond-frontier guard);
    live pids are exclusively owned by their writer (refcounted blocks
    are forked before any write), so the scatter is injective."""
    return {name: buf.at[pids].set(blk[name], mode="drop")
            for name, buf in pl.items()}


def cache_reset(cache: dict) -> dict:
    """Clear all state (ref: cache clear on Goodbye, worker.rs:364-384)."""
    def zero_layer(lc):
        out = {}
        for name, buf in lc.items():
            if name == "pos":
                out[name] = jnp.full_like(buf, -1)
            else:
                out[name] = jnp.zeros_like(buf)
        return out
    return {"layers": [zero_layer(lc) for lc in cache["layers"]],
            "pos": jnp.zeros_like(cache["pos"])}
