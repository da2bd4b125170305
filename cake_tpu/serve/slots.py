"""Slot bookkeeping for the continuous-batching engine.

A SlotPool tracks which rows of the fixed B-row KV-cache pool are busy.
Allocation always returns the LOWEST free index: occupied slots cluster at
the bottom of the pool, so the PAGED decode programs only have to cover the
prefix 0..highest_busy of their per-slot rows (power-of-two bucketed by
`slot_bucket`) — as load drops, high slots drain and the paged executable
shrinks a bucket at a time. The contiguous decode program takes no bucket:
it runs on every row of the pool in place, under the engine's active mask
(text_model._decode_slots says why).

Pure host-side bookkeeping (no jax): unit-testable without a model. All
methods are called from the single scheduler thread; no locking.
"""
from __future__ import annotations


def slot_bucket(n: int, cap: int) -> int:
    """Smallest power-of-two >= n, capped at cap — the paged decode
    programs' static row count. (PREFILL_BUCKETS starts at 32, so
    text_model.bucket_for would pin every pool <= 32 slots to its full
    size and the occupied-prefix shrink would never engage.)"""
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


def slot_buckets(cap: int) -> tuple[int, ...]:
    """The bucket ladder a cap-slot PAGED pool can dispatch at: 1, 2, 4,
    ... cap (cap itself included even when not a power of two). Scaling
    CAKE_SERVE_SLOTS from 4 to 8/16 adds exactly ONE rung per doubling.
    The contiguous pool has one decode program whatever its occupancy
    (pinned in tests/test_spec_serve.py), so a warm-up that walks this
    ladder there only walks the occupancies. Warm-up code (today only
    benchmark/launch_server.py) iterates it instead of hand-rolling
    powers of two."""
    out = []
    b = 1
    while b < cap:
        out.append(b)
        b <<= 1
    out.append(cap)
    return tuple(out)


class SlotPool:
    def __init__(self, n: int):
        if n < 1:
            raise ValueError(f"slot pool needs >= 1 slot, got {n}")
        self.n = n
        self._busy: set[int] = set()

    @property
    def free_count(self) -> int:
        return self.n - len(self._busy)

    @property
    def busy_count(self) -> int:
        return len(self._busy)

    def busy(self) -> list[int]:
        """Occupied slot indices, ascending."""
        return sorted(self._busy)

    def alloc(self) -> int | None:
        """Claim the lowest free slot (keeps the paged programs' occupied
        prefix short); None when the pool is full."""
        for i in range(self.n):
            if i not in self._busy:
                self._busy.add(i)
                return i
        return None

    def free(self, i: int) -> None:
        if i not in self._busy:
            raise ValueError(f"slot {i} is not allocated")
        self._busy.discard(i)

    def prefix_len(self) -> int:
        """Smallest prefix length covering every busy slot (0 when idle) —
        the paged decode programs' row count before bucketing."""
        return max(self._busy) + 1 if self._busy else 0
