"""Shared-prefix KV cache: device-resident LRU of prefill'd prefix blocks.

Chat traffic shares its system prompt across requests, and the engine used
to recompute the identical prefix KV on every admission. This cache keeps
that work (RadixAttention's insight — SGLang, Zheng et al. 2023 — minus
the radix tree): the prompt's prefix is cut into fixed BLOCK-sized pieces
at chunked-prefill boundaries, each block's KV (plus the state the row
holds at the block's END: a recurrent layer's conv/state snapshot, and of
a window layer whose ring is smaller than the block the ring itself, the
block's last `window` positions) is copied out of the pool row right
after the chunk that completed it, and a later admission whose prompt
starts with the same tokens restores the matched chain into its row and
prefills only the suffix. A ring lands by position % window, so each
block of a chain overwrites it whole and the chain's end leaves exactly
what a prefill to that boundary leaves. The restore is ONE program a
power-of-two piece of the chain (`splice`; 32 blocks: one dispatch), which
writes only what the row keeps: a full buffer the piece as one slab, a
ring its last blocks, a recurrent layer the last snapshot
(cache.slot_restore_chain_layers). Block by block it was 32 dispatches of
1.1 ms each on the host in front of a decode step (PERF.md §6 PR 54).

Matching is a hash CHAIN, which gives longest-prefix-match without a trie:
block b's key is blake2b(prompt[: (b+1)*block]) — equal key chains iff
equal prefixes — so lookup walks b = 0, 1, ... until the first miss. The
stored token prefix is compared on every hit, so a hash collision can
degrade performance but never output correctness. Reuse is capped at
n-1 tokens: the final prompt token is always prefilled live, because its
logits seed the first sampled token.

A block's size is read off its leaves. The K/V of a full layer grows with
the block; a recurrent layer's snapshot does not, and one rides EVERY
block, since any block may be the last of a matched chain: 0.36 MB a layer
for Jamba's Mamba state, 4.3 MB a layer for a delta-rule layer of 64 heads
x 128 x 128 in float32 with its conv tail (Solar-Open2: 26 MB a block over
six such layers beside 2 MB of two layers' K/V, 92 % of the block).
`occupancy()` says how many of the held bytes are such snapshots
(`state_bytes`). A snapshot rarer than one a block would shorten what a
hit can return and is not built (PERF.md §7).

Capacity is CAKE_PREFIX_CACHE_MB of device bytes (LRU over blocks; a
middle eviction just shortens the matchable chain). Everything here runs
on the engine's scheduler thread — no locking; the entries are plain jnp
arrays, so eviction is a dict pop and the buffers free with their last
reference.

Greedy outputs are BIT-identical between a hit and a miss: the restore
copies the exact bytes prefill wrote, and the suffix chunks land on the
same chunk-bucket boundaries either way (block size == chunk size), so
every matmul sees the same shapes and inputs.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from ..obs import (RECORDER, SERVE_PREFIX_BYTES, SERVE_PREFIX_EVICTIONS,
                   SERVE_PREFIX_HITS, SERVE_PREFIX_MISSES,
                   SERVE_PREFIX_RESTORE_BLOCKS,
                   SERVE_PREFIX_RESTORE_DISPATCHES, SERVE_PREFIX_STATE_BYTES)

__all__ = ["PrefixCache", "PagedPrefixCache"]


@dataclass
class _Block:
    tokens: np.ndarray      # the FULL prefix this block completes (verify)
    layers: list            # batch-1 layers pytree, this block's KV + state
    nbytes: int
    state_bytes: int = 0    # the part of nbytes that is recurrent state


@dataclass
class _PagedEntry:
    tokens: np.ndarray      # the FULL prefix this unit completes (verify)
    pids: list              # physical block ids this entry PINS (refcount)
    snap: list | None       # boundary row snapshot (SWA rings + linear
                            # state), installed only as a chain's FINAL unit
    nbytes: int
    state_bytes: int = 0    # the part of nbytes that is recurrent state


def _tree_bytes(layers, state_only: bool = False) -> int:
    """Bytes of a batch-1 layers pytree; `state_only`: of its recurrent
    layers alone (no `pos` leaf: cache.is_positional), the boundary
    snapshot a block carries whatever its length."""
    total = 0
    for lc in layers:
        if state_only and "pos" in lc:
            continue
        for buf in lc.values():
            total += int(np.prod(buf.shape)) * buf.dtype.itemsize
    return total


class PrefixCache:
    """LRU of prefix blocks for ONE engine (scheduler-thread only). A
    block holds, per layer, what `slot_extract` gives of it: the block's
    K/V of a full buffer or a ring at least a block long, the ring whole
    where it is shorter, every leaf of recurrent state."""

    def __init__(self, model, block: int, capacity_bytes: int):
        self.model = model
        self.block = block
        self.capacity = capacity_bytes
        self._blocks: OrderedDict[bytes, _Block] = OrderedDict()
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # restore programs dispatched by hits, and the blocks they restored
        self.restores = 0
        self.restored = 0
        # membership version: bumped on every insert/evict (NOT on LRU
        # touches) so the kvshare inventory mirror refreshes only when
        # the key set actually changed
        self.version = 0

    @staticmethod
    def block_bytes(model, ctx: int, block: int) -> int:
        """What ONE block of this model weighs: what `slot_extract` would
        copy out of a row, from shapes alone (nothing is allocated)."""
        import jax

        from ..models.common.cache import (init_cache,
                                           slot_extract_block_layers)
        row = jax.eval_shape(lambda: init_cache(
            model.cfg, 1, ctx, model.dtype)["layers"])
        return _tree_bytes(jax.eval_shape(
            lambda layers: slot_extract_block_layers(layers, 0, 0, block),
            row))

    @staticmethod
    def refusal(ctx: int, block: int, capacity_mb: float,
                block_bytes: int = 0) -> str | None:
        """Why no cache is built for these sizes (the engine logs it when
        one was asked for), or None. A window smaller than the block is no
        reason: the ring rides each block as boundary state. A block that
        alone outweighs the capacity is: every insert would copy it out
        of the row only to throw it away."""
        if capacity_mb <= 0:
            return "capacity 0"
        if block > ctx:
            return (f"a block of {block} tokens (the prefill chunk) does "
                    f"not fit a row of {ctx}")
        if block_bytes > capacity_mb * 1024 * 1024:
            return (f"one block of {block} tokens is {block_bytes} B of "
                    "this model's row, more than the whole capacity")
        return None

    @classmethod
    def build(cls, model, ctx: int, block: int,
              capacity_mb: float) -> "PrefixCache | None":
        """None when disabled (capacity <= 0), when a block does not fit a
        row or when one block outweighs the capacity (`refusal`), decided
        from shapes, before any block is extracted."""
        if capacity_mb <= 0 or cls.refusal(
                ctx, block, capacity_mb,
                cls.block_bytes(model, ctx, block)) is not None:
            return None
        return cls(model, block, int(capacity_mb * 1024 * 1024))

    # -- admission-side API -------------------------------------------------

    def chain_keys(self, prompt_ids: list[int]) -> list[bytes]:
        """Key of every block this prompt could match OR contribute
        ((n-1)//block of them — reuse keeps >= 1 live suffix token, and
        the same cap bounds what prefill can capture). One incremental
        blake2b pass per ADMISSION; the engine holds the list for the
        admission's lifetime so match/splice/insert never re-hash."""
        ids = np.asarray(prompt_ids, np.int32)
        h = hashlib.blake2b(digest_size=16)
        keys = []
        for b in range((len(ids) - 1) // self.block):
            h.update(ids[b * self.block:(b + 1) * self.block].tobytes())
            keys.append(h.digest())
        return keys

    def match(self, prompt_ids: list[int], keys: list[bytes]) -> int:
        """Longest cached block chain usable for this prompt, in BLOCKS
        (0 = miss). Refreshes LRU recency of every matched block and
        records the hit/miss counters — except for prompts structurally
        too short to ever hit (<= block tokens, zero keys), which would
        otherwise skew the hit ratio an operator sizes the cache by."""
        if not keys:
            return 0
        ids = np.asarray(prompt_ids, np.int32)
        matched = 0
        for key in keys:
            blk = self._blocks.get(key)
            if blk is None or not np.array_equal(
                    blk.tokens, ids[:len(blk.tokens)]):
                break
            self._blocks.move_to_end(key)
            matched += 1
        if matched:
            self.hits += 1
            SERVE_PREFIX_HITS.inc()
        else:
            self.misses += 1
            SERVE_PREFIX_MISSES.inc()
        return matched

    def splice(self, layers, slot: int, keys: list[bytes], matched: int):
        """Restore the matched chain into pool row `slot` (row must be
        freshly wiped). Returns the updated pool layers. The chain goes to
        the model in power-of-two pieces, largest first (32 blocks: one
        dispatch; 37: 32 + 4 + 1), so a hit costs popcount(matched)
        dispatches of at most log2(row / block) + 1 programs, and every
        piece starts on a multiple of its length (`slot_restore`)."""
        b = 0
        while b < matched:
            n = 1 << ((matched - b).bit_length() - 1)
            layers = self.model.slot_restore(
                layers, [self._blocks[k].layers for k in keys[b:b + n]],
                slot, b, self.block, final=(b + n == matched))
            b += n
            self.restores += 1
            SERVE_PREFIX_RESTORE_DISPATCHES.inc()
        self.restored += matched
        SERVE_PREFIX_RESTORE_BLOCKS.inc(matched)
        return layers

    def insert(self, layers, slot: int, prompt_ids: list[int],
               block_index: int, keys: list[bytes]) -> None:
        """Capture block `block_index` out of row `slot`. Must be called at
        the chunk boundary that completed the block — the row then holds
        exactly prefix_len tokens, so the linear-attention snapshot is the
        exact prefix state. Dedupes on key; evicts LRU past capacity."""
        key = keys[block_index]
        known = key in self._blocks
        # recorder on: which of these holds the scheduler behind a chunk
        with RECORDER.span("prefix.insert", cat="serve", block=block_index,
                           known=int(known)):
            if known:
                self._blocks.move_to_end(key)
                return
            end = (block_index + 1) * self.block
            ids = np.asarray(prompt_ids[:end], np.int32)
            with RECORDER.span("prefix.extract", cat="serve"):
                entry_layers = self.model.slot_extract(
                    layers, slot, block_index * self.block, self.block)
            blk = _Block(tokens=ids, layers=entry_layers,
                         nbytes=_tree_bytes(entry_layers),
                         state_bytes=_tree_bytes(entry_layers,
                                                 state_only=True))
            # (one block fits: `build` refused a capacity under
            # `block_bytes`)
            if self.bytes + blk.nbytes > self.capacity and self._blocks:
                with RECORDER.span("prefix.evict", cat="serve"):
                    while self.bytes + blk.nbytes > self.capacity \
                            and self._blocks:
                        _, old = self._blocks.popitem(last=False)
                        self.bytes -= old.nbytes
                        self.evictions += 1
                        self.version += 1
                        SERVE_PREFIX_EVICTIONS.inc()
            self._blocks[key] = blk
            self.bytes += blk.nbytes
            self.version += 1
            self.publish_bytes()

    # -- introspection ------------------------------------------------------

    @property
    def state_bytes(self) -> int:
        """The part of `bytes` that is recurrent layers' boundary snapshots
        (one rides every block; 0 for a model with none)."""
        return sum(b.state_bytes for b in self._blocks.values())

    def publish_bytes(self) -> None:
        """The held bytes, and the part that is snapshots, to /metrics."""
        SERVE_PREFIX_BYTES.set(self.bytes)
        SERVE_PREFIX_STATE_BYTES.set(self.state_bytes)

    def occupancy(self) -> dict:
        return {
            "blocks": len(self._blocks),
            "block_tokens": self.block,
            "bytes": self.bytes,
            "state_bytes": self.state_bytes,
            "capacity_bytes": self.capacity,
            "utilization": round(self.bytes / self.capacity, 4)
            if self.capacity else 0.0,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "restores": self.restores,
            "restored_blocks": self.restored,
        }


class PagedPrefixCache(PrefixCache):
    """Prefix index over SHARED paged-pool blocks (the allocator unified
    with the prefix cache): instead of extracting a block's bytes into a
    private copy, an insert PINS the live slot's physical blocks by
    refcount, and a hit maps those same blocks into the new slot's table
    — a prefix hit moves ZERO KV bytes (observable as the
    cake_serve_kv_blocks_shared gauge going positive). Only the boundary
    row snapshot (SWA rings + linear-attention conv/recurrent state: what
    its leaves hold, from a few hundred KB a Mamba layer to 4.3 MB a
    delta-rule layer of 64 heads x 128 x 128) is copied, because that
    state is per-slot, not pooled; it is
    installed for the FINAL matched unit exactly like the contiguous
    splice's `final` flag — the same boundary-exact GDN rule.

    The share unit stays one CHUNK of tokens (== chunk // block_tokens
    physical blocks), so the hash chain, match cap (n-1 live tokens) and
    capture boundaries are identical to the contiguous cache — match()
    and chain_keys() are inherited unchanged.

    Cache-held blocks are RECLAIMABLE capacity: the allocator evicts LRU
    units under allocation pressure (evict_for_pressure, wired as
    PagedKV.evictor), so the cache can use every otherwise-idle block
    without ever starving admissions. SWA state rides the boundary
    snapshot whole, as the contiguous cache carries a ring smaller than a
    block."""

    def __init__(self, model, paged, unit: int, capacity_bytes: int):
        super().__init__(model, unit, capacity_bytes)
        self.paged = paged
        self.bpu = unit // paged.bt           # physical blocks per unit
        self.pinned = 0     # physical blocks currently cache-pinned (a
                            # single int so /health reads it race-free)

    @staticmethod
    def unit_bytes(paged, unit: int) -> int:
        """What one share unit weighs: its pinned blocks and the boundary
        snapshot of a slot's rows (`insert`'s own arithmetic)."""
        snap = sum(leaf.nbytes // leaf.shape[0]
                   for lc in paged.rows for leaf in lc.values())
        return unit // paged.bt * paged.block_bytes + snap

    @staticmethod
    def refusal_paged(paged, unit: int, capacity_mb: float,
                      unit_bytes: int = 0) -> str | None:
        if unit % paged.bt:
            return (f"the share unit of {unit} tokens (the prefill chunk) "
                    f"is no multiple of the {paged.bt}-token blocks")
        return PrefixCache.refusal(paged.ctx, unit, capacity_mb, unit_bytes)

    @classmethod
    def build_paged(cls, model, paged, unit: int,
                    capacity_mb: float) -> "PagedPrefixCache | None":
        if cls.refusal_paged(paged, unit, capacity_mb,
                             cls.unit_bytes(paged, unit)) is not None:
            return None
        return cls(model, paged, unit, int(capacity_mb * 1024 * 1024))

    # -- admission-side API (paged semantics) -------------------------------

    def splice(self, layers, slot: int, keys: list[bytes], matched: int):
        """Map the matched chain's physical blocks into `slot`'s table
        (refcount bump per block — no KV copy) and install the final
        unit's row snapshot. Refs and mappings are taken host-side and
        the device table row is published ONCE (one scatter + one gauge
        publish per hit, not per block — admission hot path). `layers`
        is ignored (the paged engine keeps no contiguous pool) and
        returned untouched."""
        for b in range(matched):
            entry = self._blocks[keys[b]]
            for j, pid in enumerate(entry.pids):
                self.paged.alloc.ref(pid)
                self.paged.alloc.map(slot, b * self.bpu + j, pid)
        self.paged.sync_table_row(slot)
        final = self._blocks[keys[matched - 1]]
        if final.snap is not None:
            self.paged.rows = self.model.row_install(self.paged.rows,
                                                     final.snap, slot)
        return layers

    def insert(self, layers, slot: int, prompt_ids: list[int],
               block_index: int, keys: list[bytes]) -> None:
        """Pin unit `block_index` of `slot` as a shared entry. Must be
        called at the chunk boundary that completed the unit (the row
        snapshot is exact only there). `layers` is ignored. Dedupes on
        key — a concurrent admission that prefilled its own copy before
        this one captured keeps its private blocks (correct, just
        unshared)."""
        end = (block_index + 1) * self.block
        key = keys[block_index]
        if key in self._blocks:
            self._blocks.move_to_end(key)
            return
        pids = self.paged.alloc.tables[slot][block_index * self.bpu:
                                             (block_index + 1) * self.bpu]
        if self.paged.NULL in pids:
            return                  # row not fully backed (cannot happen
                                    # after a completed chunk; be safe)
        snap = None
        snap_bytes = state_bytes = 0
        if self.paged.has_rows:
            snap = self.model.row_snapshot(self.paged.rows, slot)
            snap_bytes = _tree_bytes(snap)
            state_bytes = _tree_bytes(snap, state_only=True)
        nbytes = len(pids) * self.paged.block_bytes + snap_bytes
        # (one unit fits: `build_paged` refused a capacity under it)
        while self.bytes + nbytes > self.capacity and self._blocks:
            self._evict_lru()
        for pid in pids:
            self.paged.alloc.ref(pid, cache_pin=True)
        self._blocks[key] = _PagedEntry(
            tokens=np.asarray(prompt_ids[:end], np.int32),
            pids=list(pids), snap=snap, nbytes=nbytes,
            state_bytes=state_bytes)
        self.bytes += nbytes
        self.version += 1
        self.pinned += len(pids)
        self.paged._publish()
        self.publish_bytes()

    # -- eviction -----------------------------------------------------------

    def _evict_lru(self) -> int:
        """Drop the LRU entry; returns how many device blocks were
        actually FREED (0 when every pinned block is still mapped by a
        live slot)."""
        _, old = self._blocks.popitem(last=False)
        self.bytes -= old.nbytes
        self.evictions += 1
        self.version += 1
        SERVE_PREFIX_EVICTIONS.inc()
        freed = sum(1 for pid in old.pids
                    if self.paged.alloc.deref(pid, cache_pin=True))
        self.pinned -= len(old.pids)
        self.publish_bytes()
        self.paged._publish()
        return freed

    def evict_for_pressure(self) -> int:
        """Allocator pressure hook (PagedKV.evictor): evict LRU entries
        until at least one block frees or the cache is empty. Returns
        blocks freed (0 = nothing reclaimable — escalate to
        preemption)."""
        while self._blocks:
            freed = self._evict_lru()
            if freed:
                return freed
        return 0

    def release_all(self) -> None:
        """Drop every entry and its pins (engine rebuild/shutdown of the
        paged pool; the allocator is being thrown away with us, so only
        the bookkeeping needs to stay consistent)."""
        while self._blocks:
            self._evict_lru()

    def occupancy(self) -> dict:
        out = super().occupancy()
        out["shared_blocks"] = self.paged.alloc.shared_count
        out["unit_blocks"] = self.bpu
        return out
