"""PagedKV: the device-facing facade of the paged-KV subsystem.

Owns the physical block pool (cache.init_paged_layers), the per-slot
row state (SWA rings + linear-attention conv/recurrent), the DEVICE
block-table array the traced programs read, and the host-side
BlockAllocator that mirrors it. The serve engine talks to this object;
the allocator never touches jax and the engine never touches block ids.

Everything here runs on the engine's scheduler thread. Device/host
mirrors are kept in lockstep: every allocator mutation that changes a
table entry immediately updates the [B, max_blocks] device array (a
scalar scatter — the same cost class as the engine's `active`-mask
flips, and like them it never changes a compiled shape).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...models.common.cache import init_paged_layers, layer_is_pooled
from ...obs import (SERVE_KV_BLOCKS_FREE, SERVE_KV_BLOCKS_SHARED,
                    SERVE_KV_BLOCKS_USED)
from .allocator import BlockAllocator

__all__ = ["PagedKV", "KVPoolExhausted", "pow2_block_tokens"]


class KVPoolExhausted(RuntimeError):
    """The block pool cannot satisfy an allocation even after prefix-
    cache eviction and preemption — the request is failed with a typed
    error instead of wedging the scheduler."""

    def __init__(self, msg: str, retry_after_s: int = 2):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


def pow2_block_tokens(n: int, chunk: int) -> int:
    """Clamp the block size to a power of two in [8, chunk]: chunk %
    block == 0 keeps every chunked-prefill boundary a block boundary
    (the prefix share unit and the GDN boundary-exact snapshot rule both
    hang off that alignment)."""
    n = max(8, min(int(n), chunk))
    b = 8
    while b * 2 <= n:
        b *= 2
    return b


class PagedKV:
    def __init__(self, model, slots: int, ctx: int, num_blocks: int,
                 block_tokens: int):
        self.model = model
        self.slots = slots
        self.ctx = ctx
        self.bt = block_tokens
        self.num_blocks = num_blocks
        self.max_blocks = ctx // block_tokens
        self.alloc = BlockAllocator(num_blocks, block_tokens, slots,
                                    self.max_blocks)
        self.NULL = self.alloc.NULL
        self.pool, self.rows = init_paged_layers(
            model.cfg, num_blocks, block_tokens, slots, ctx, model.dtype)
        self.has_rows = any(r for r in self.rows)
        # device bytes one physical block costs across every pooled layer
        # (the prefix cache's capacity accounting unit)
        self.block_bytes = sum(
            int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
            for pl in self.pool for leaf in pl.values())
        self.tables = jnp.full((slots, self.max_blocks), self.NULL,
                               jnp.int32)
        # eviction hook: () -> int, blocks actually freed (wired to the
        # paged prefix cache's LRU by the engine)
        self.evictor = None
        self.swaps = 0
        self._publish()

    @classmethod
    def build(cls, model, slots: int, ctx: int, num_blocks: int,
              block_tokens: int, chunk: int) -> "PagedKV":
        if chunk & (chunk - 1):
            raise ValueError(
                f"prefill chunk {chunk} must be a power of two — block "
                "boundaries must align with chunk boundaries (the "
                "engine's _pow2_chunk clamp guarantees this; direct "
                "callers must too)")
        bt = pow2_block_tokens(block_tokens, chunk)
        if ctx % bt:
            raise ValueError(
                f"CAKE_KV_BLOCK_TOKENS={bt} must divide the serve context "
                f"{ctx} so the paged view keeps the contiguous row layout")
        if not any(layer_is_pooled(s) for s in model.cfg.layer_specs()):
            raise ValueError(
                "paged KV needs at least one full-attention layer — "
                "SWA rings and linear state are O(window)/O(1) per slot "
                "and have nothing to page")
        return cls(model, slots, ctx, num_blocks, bt)

    # -- allocation (host) --------------------------------------------------

    def blocks_for(self, tokens: int) -> int:
        """Blocks a sequence of `tokens` tokens occupies (write frontier
        inclusive)."""
        return -(-tokens // self.bt)

    def _alloc_one(self) -> int | None:
        """One free block, evicting prefix-cache LRU units under
        pressure (cache-held blocks are reclaimable capacity, exactly
        like the contiguous prefix cache's LRU — unified here)."""
        pid = self.alloc.alloc()
        while pid is None and self.evictor is not None and self.evictor():
            pid = self.alloc.alloc()
        return pid

    def ensure_free(self, n: int) -> bool:
        """Evict prefix-cache LRU until at least `n` blocks are FREE.
        The allocation path reclaims cache blocks lazily (inside
        _alloc_one), but a PARKED preempted request never reaches an
        allocation — its resume gate must count cache pins as the
        reclaimable capacity they are, or blocks held only by the cache
        would starve it forever. False = short even with the cache
        empty."""
        while self.alloc.free_count < n:
            if self.evictor is None or not self.evictor():
                return False
        return True

    def sync_table_row(self, slot: int) -> None:
        """Publish the slot's host table row to the device in ONE write
        + one gauge publish — the batched companion to ensure()'s
        single-entry scatter, for callers that mapped several entries
        host-side (prefix splice, chunk reservation)."""
        self.tables = self.tables.at[slot].set(
            jnp.asarray(self.alloc.tables[slot], jnp.int32))
        self._publish()

    def ensure(self, slot: int, block_idx: int) -> bool:
        """Back table entry (slot, block_idx) with a physical block;
        False = pool exhausted even after cache eviction (the engine
        escalates to preemption)."""
        if self.alloc.tables[slot][block_idx] != self.NULL:
            return True
        pid = self._alloc_one()
        if pid is None:
            return False
        self.alloc.map(slot, block_idx, pid)
        self.tables = self.tables.at[slot, block_idx].set(pid)
        self._publish()
        return True

    def reserve_range(self, slot: int, pos0: int, n: int) -> bool:
        """Ensure blocks for logical positions [pos0, pos0 + n) — the
        pre-dispatch step of a prefill chunk. All-or-nothing is not
        required: already-mapped entries are kept on failure (they hold
        earlier KV), only the shortfall is reported. The device table
        update is BATCHED: allocations happen host-side first, then one
        row write + one gauge publish regardless of how many blocks the
        chunk spans (ensure()'s per-entry scatter would dispatch a
        device op per block on the admission hot path)."""
        fresh = False
        short = False
        for b in range(pos0 // self.bt, (pos0 + n - 1) // self.bt + 1):
            if self.alloc.tables[slot][b] != self.NULL:
                continue
            pid = self._alloc_one()
            if pid is None:
                short = True
                break
            self.alloc.map(slot, b, pid)
            fresh = True
        if fresh:
            self.sync_table_row(slot)
        return not short

    def trim_to(self, slot: int, tokens: int) -> int:
        """Speculative-frontier ROLLBACK: unmap every table entry past
        the blocks a `tokens`-token sequence occupies. The batched
        verify reserves blocks for the widest possible accept
        ([frontier, frontier + k]); after a rejection — or before a
        swap-out — the tail past the committed frontier is speculative
        over-reservation and this returns it to the pool. Freed blocks
        were exclusively owned (the frontier never maps shared blocks),
        and their bytes need no wipe: the committed write-back already
        masked uncommitted positions, and the gather's stale-tenant
        guard covers recycling. Returns the number of blocks freed."""
        keep = self.blocks_for(max(tokens, 1))
        freed = 0
        touched = False
        for idx in range(keep, self.max_blocks):
            if self.alloc.tables[slot][idx] == self.NULL:
                continue
            touched = True
            if self.alloc.unmap_entry(slot, idx):
                freed += 1
        if touched:
            self.sync_table_row(slot)
        return freed

    def map_shared(self, slot: int, block_idx: int, pid: int) -> None:
        """Point (slot, block_idx) at an existing block, sharing it
        (refcount bump — the paged prefix hit; NO bytes move)."""
        self.alloc.ref(pid)
        self.alloc.map(slot, block_idx, pid)
        self.tables = self.tables.at[slot, block_idx].set(pid)
        self._publish()

    def ensure_writable(self, slot: int, block_idx: int) -> bool:
        """Copy-on-write fork of a shared block before a write into it
        (not reachable from the serve scheduler's own flow — capture
        stops short of the write frontier — but the invariant the
        allocator promises anyone who maps shared blocks)."""
        pid = self.alloc.ensure_writable(slot, block_idx, self._copy_block)
        if pid is None:
            return False
        self.tables = self.tables.at[slot, block_idx].set(pid)
        self._publish()
        return True

    def _copy_block(self, src: int, dst: int) -> None:
        """Device-side copy of one physical block (the CoW fork body) —
        forks the stored KV bytes; the linear-state snapshot rides the
        prefix-cache entry, which the fork's owner re-captures at its
        own boundary (boundary-exact rule)."""
        for pl in self.pool:
            if not pl:
                continue
            for name in pl:
                pl[name] = pl[name].at[dst].set(pl[name][src])

    def release_slot(self, slot: int) -> None:
        """Per-request release: deref every mapped block (shared blocks
        survive under the prefix cache / other slots), clear the device
        table row, wipe the slot's SWA/linear rows. Freed pool blocks
        are NOT wiped — the gather's stale-tenant pos guard makes them
        invisible until a new owner overwrites them."""
        self.alloc.unmap_slot(slot)
        self.tables = self.tables.at[slot].set(self.NULL)
        if self.has_rows:
            self.rows = self.model.row_reset(self.rows, slot)
        self._publish()

    # -- traced-program dispatch -------------------------------------------

    def prefill_into(self, slot: int, ids, pos0: int):
        """One chunk of prompt into the slot's mapped blocks (caller
        reserved them). Returns the chunk's last-position logits."""
        logits, self.pool, self.rows = self.model.prefill_chunk_paged(
            self.pool, self.rows, self.tables, slot, ids, pos0, self.ctx)
        return logits

    # -- preemption transport (slow path: explicit host syncs) --------------

    def swap_out(self, slot: int, carries) -> dict:
        """Preempt-by-swap: fetch the slot's block bytes, row state and
        decode carries to HOST memory, then free its blocks. Returns the
        blob swap_in() restores bit-exactly; the carries tuple is
        (toks, pos, rngs, recents) device arrays indexed [slot]."""
        idx = [i for i, p in enumerate(self.alloc.tables[slot])
               if p != self.NULL]
        ids = jnp.asarray([self.alloc.tables[slot][i] for i in idx],
                          jnp.int32)
        blob = {"idx": idx, "layers": [], "rows": None, "carries": []}
        for pl in self.pool:
            # lint: disable=host-sync — preemption IS the planned swap to host;
            # this whole method is the slow path that frees HBM
            blob["layers"].append(
                {n: np.asarray(leaf[ids]) for n, leaf in pl.items()})
        if self.has_rows:
            # lint: disable=host-sync — row state rides the same swap blob
            blob["rows"] = jax.tree_util.tree_map(
                np.asarray, self.model.row_snapshot(self.rows, slot))
        # lint: disable=host-sync — decode carries (a few dozen bytes) complete
        # the bit-exact resume state
        blob["carries"] = [np.asarray(c[slot]) for c in carries]
        self.release_slot(slot)
        self.swaps += 1
        return blob

    def swap_in(self, slot: int, blob: dict) -> bool:
        """Restore a swapped-out slot into freshly allocated blocks.
        False = not enough free blocks yet (caller retries later; the
        blob is untouched). Table indices are restored verbatim, so the
        sequence resumes at its exact logical positions."""
        need = len(blob["idx"])
        if not self.ensure_free(need):
            return False
        pids = []
        for idx in blob["idx"]:
            pid = self._alloc_one()
            assert pid is not None        # guarded by free_count above
            self.alloc.map(slot, idx, pid)
            pids.append(pid)
        dst = jnp.asarray(pids, jnp.int32)
        for pl, saved in zip(self.pool, blob["layers"]):
            if not pl:
                continue
            for name in pl:
                pl[name] = pl[name].at[dst].set(jnp.asarray(saved[name]))
        if self.has_rows and blob["rows"] is not None:
            self.rows = self.model.row_install(
                self.rows, jax.tree_util.tree_map(jnp.asarray,
                                                  blob["rows"]), slot)
        host_row = np.full((self.max_blocks,), self.NULL, np.int32)
        host_row[blob["idx"]] = pids
        self.tables = self.tables.at[slot].set(jnp.asarray(host_row))
        self._publish()
        return True

    # -- observability ------------------------------------------------------

    def _publish(self) -> None:
        SERVE_KV_BLOCKS_FREE.set(self.alloc.free_count)
        SERVE_KV_BLOCKS_USED.set(self.alloc.used_count)
        SERVE_KV_BLOCKS_SHARED.set(self.alloc.shared_count)

    def occupancy(self, live_tokens: dict[int, int] | None = None) -> dict:
        """kv_pool health block. `live_tokens`: slot -> frontier tokens,
        for the fragmentation figure (allocated-but-unfilled tail share
        of live slots' blocks)."""
        out = {
            "blocks": self.num_blocks,
            "block_tokens": self.bt,
            "free": self.alloc.free_count,
            "used": self.alloc.used_count,
            # first-class occupancy in [0, 1]: consumers (the fleet
            # router's probe loop, autoscalers) read this directly
            # instead of re-deriving used/blocks by hand
            "occupancy": round(self.alloc.used_count
                               / max(self.num_blocks, 1), 4),
            "shared": self.alloc.shared_count,
            "cow_forks": self.alloc.cow_forks,
            "swaps": self.swaps,
        }
        if live_tokens:
            alloc_tokens = waste = 0
            for slot, toks in live_tokens.items():
                nblk = len(self.alloc.blocks_of(slot))
                alloc_tokens += nblk * self.bt
                waste += max(nblk * self.bt - toks, 0)
            out["fragmentation"] = round(waste / alloc_tokens, 4) \
                if alloc_tokens else 0.0
        return out
