"""Paged KV subsystem (ISSUE 9): allocator invariants, paged-vs-contiguous
bit parity (llama + qwen3_5/GDN), refcount-bump prefix hits (no KV copy),
steady-state recompile pin across block-table updates, and pool-exhaustion
preemption (swap AND recompute) with bit-identical continuation.

Every engine in this module uses the SAME pool shape (12 blocks x 8
tokens, chunk 16, ctx 128) so the paged executables compile once per
model and are reused across engines — the tier-1 suite is timeout-capped
and a fresh pool shape costs ~10s of XLA compile on this box."""
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import KVPoolExhausted, ServeEngine
from cake_tpu.serve.paged import BlockAllocator, pow2_block_tokens

GREEDY = SamplingConfig(temperature=0.0)
CTX = 128
CHUNK = 16
BT = 8
BLOCKS = 12         # 96 tokens of pool — deliberately < slots * ctx


# ---------------------------------------------------------------------------
# allocator: pure host, no jax
# ---------------------------------------------------------------------------


def test_pow2_block_tokens_alignment():
    assert pow2_block_tokens(16, 64) == 16
    assert pow2_block_tokens(24, 64) == 16    # round down, never up
    assert pow2_block_tokens(7, 64) == 8      # floor 8
    assert pow2_block_tokens(256, 32) == 32   # never exceeds the chunk


def test_allocator_basic_refcount_and_double_free():
    a = BlockAllocator(4, 8, slots=2, max_blocks=4)
    p0, p1 = a.alloc(), a.alloc()
    a.map(0, 0, p0)
    a.map(0, 1, p1)
    assert a.used_count == 2 and a.free_count == 2
    # share p0 with slot 1 (the prefix-hit shape)
    a.ref(p0)
    a.map(1, 0, p0)
    assert a.shared_count == 1
    a.check()
    # releasing slot 1 keeps p0 alive under slot 0
    assert a.unmap_slot(1) == []
    assert a.refcount(p0) == 1 and a.shared_count == 0
    assert sorted(a.unmap_slot(0)) == sorted([p0, p1])
    assert a.free_count == 4
    with pytest.raises(ValueError):
        a.deref(p0)                           # double free
    a.check()


def test_allocator_cow_fork_moves_ref():
    a = BlockAllocator(4, 8, slots=2, max_blocks=4)
    shared = a.alloc()
    a.map(0, 0, shared)
    a.ref(shared)
    a.map(1, 0, shared)
    copies = []
    pid = a.ensure_writable(1, 0, lambda s, d: copies.append((s, d)))
    assert pid != shared and copies == [(shared, pid)]
    assert a.tables[1][0] == pid and a.tables[0][0] == shared
    assert a.refcount(shared) == 1 and a.refcount(pid) == 1
    assert a.cow_forks == 1
    a.check()
    # exclusive block: no fork, no copy
    assert a.ensure_writable(0, 0, lambda s, d: copies.append("no")) \
        == shared
    assert len(copies) == 1


def test_allocator_property_random_ops():
    """Randomized alloc/map/share/release churn keeps every invariant
    (refcounts == mappings + pins, no double ownership, free xor used)."""
    rng = random.Random(9)
    a = BlockAllocator(8, 8, slots=3, max_blocks=6)
    pins: list[int] = []
    for _ in range(400):
        op = rng.random()
        if op < 0.35:
            slot = rng.randrange(3)
            idx = rng.randrange(6)
            if a.tables[slot][idx] == a.NULL:
                a.ensure(slot, idx)
        elif op < 0.55:
            # share an existing mapped block into a free entry elsewhere
            owners = [(s, p) for s in range(3) for p in a.tables[s]
                      if p != a.NULL]
            if owners:
                _, pid = rng.choice(owners)
                dst = rng.randrange(3)
                empties = [i for i, p in enumerate(a.tables[dst])
                           if p == a.NULL]
                if empties and pid not in a.tables[dst]:
                    a.ref(pid)
                    a.map(dst, rng.choice(empties), pid)
        elif op < 0.7:
            used = [p for p in range(8) if a.refcount(p) >= 1]
            if used:
                pid = rng.choice(used)
                a.ref(pid, cache_pin=True)
                pins.append(pid)
        elif op < 0.85:
            if pins:
                a.deref(pins.pop(), cache_pin=True)
        else:
            a.unmap_slot(rng.randrange(3))
        a.check()
    for pid in pins:
        a.deref(pid, cache_pin=True)
    for s in range(3):
        a.unmap_slot(s)
    a.check()
    assert a.free_count == 8


def test_paged_gather_masks_stale_tenant():
    """A freed block is never wiped on the device: the gather masks
    entries from a previous tenant's block range (pos // bt != table
    index) AND entries at/past the slot's write frontier — the
    same-index recycling case that would otherwise present a stale key
    at a position the [cache ; chunk] prefill concat is about to write
    (the double-key corruption the frontier guard exists for)."""
    from cake_tpu.models.common.cache import paged_gather_layer
    pl = {"k": jnp.zeros((3, 4, 1, 2)), "v": jnp.zeros((3, 4, 1, 2)),
          "pos": jnp.full((3, 4), -1, jnp.int32)}
    # block 1 holds positions 4..7 (a previous tenant's block index 1)
    pl["pos"] = pl["pos"].at[1].set(jnp.arange(4, 8))
    # new tenant maps it at table index 0 (logical positions 0..3)
    table = jnp.asarray([1, 3, 3], jnp.int32)       # 3 == NULL
    out = paged_gather_layer(pl, table, jnp.int32(12))
    assert int(jnp.max(out["pos"])) == -1           # stale pos invisible
    # same block at its OWN index, frontier past it: passes through
    table = jnp.asarray([3, 1, 3], jnp.int32)
    out = paged_gather_layer(pl, table, jnp.int32(8))
    np.testing.assert_array_equal(np.asarray(out["pos"][4:8]),
                                  np.arange(4, 8))
    # same-index recycling: frontier BELOW the stale entries masks them
    # (the row's contract is "holds exactly positions 0..frontier-1")
    out = paged_gather_layer(pl, table, jnp.int32(6))
    np.testing.assert_array_equal(np.asarray(out["pos"][4:8]),
                                  [4, 5, -1, -1])


# ---------------------------------------------------------------------------
# e2e: tiny CPU llama through the paged engine
# ---------------------------------------------------------------------------


_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        _MODEL = TextModel(tiny_config("llama"), dtype=jnp.float32,
                           max_cache_len=CTX)
    return _MODEL


@pytest.fixture(scope="module")
def model():
    return _model()


def _engine(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("ctx_len", CTX)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("kv_blocks", BLOCKS)
    kw.setdefault("kv_block_tokens", BT)
    kw.setdefault("prefix_cache_mb", 0)
    return ServeEngine(model, **kw)


@pytest.fixture(scope="module")
def engine(model):
    eng = _engine(model, prefix_cache_mb=8)
    yield eng
    eng.close()


def _ref(model, prompt, n, sampling=GREEDY):
    toks, _ = model.generate(list(prompt), max_new_tokens=n,
                             sampling=sampling)
    return toks


P_A = [3, 17, 42, 99, 7]
P_B = [100, 2, 5, 9, 11, 40]
SYS = [3 + (i * 7) % 200 for i in range(40)]        # 2 full share units


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_paged_engine_greedy_matches_contiguous(model, engine):
    """Concurrent greedy requests through the paged pool reproduce the
    contiguous sequential path bit-for-bit — the gathered block view has
    the contiguous row's exact layout, so same bytes, same math."""
    reqs = [engine.submit(p, max_new_tokens=n, sampling=GREEDY)
            for p, n in ((P_A, 12), (P_B, 9))]
    for r, (p, n) in zip(reqs, ((P_A, 12), (P_B, 9))):
        assert r.wait(180)
        assert "error" not in r.result, r.result.get("error")
        assert r.result["tokens"] == _ref(model, p, n)


def test_paged_engine_repeat_penalty_parity(model, engine):
    scfg = SamplingConfig(temperature=0.0, repeat_penalty=1.3)
    r = engine.submit(P_A, max_new_tokens=10, sampling=scfg)
    assert r.wait(180)
    assert r.result["tokens"] == _ref(model, P_A, 10, scfg)


def test_paged_prefix_hit_is_refcount_bump(model, engine):
    """A prefix hit maps the CACHED physical blocks into the new slot's
    table — zero KV bytes copied. Pinned observably: the hit request
    reports skipped tokens, its table prefix IS the cache entry's block
    ids (identity, not equal bytes), and the shared gauge goes >= 1
    while both the cache and the slot hold the blocks."""
    from cake_tpu.obs import SERVE_KV_BLOCKS_SHARED
    pa = SYS + [9, 11]
    pb = SYS + [77, 31]
    ra = engine.submit(pa, max_new_tokens=6, sampling=GREEDY)
    assert ra.wait(180)
    assert ra.result["tokens"] == _ref(model, pa, 6)
    assert ra.stats["prefix_hit_tokens"] == 0
    # warm cache now pins the two SYS units
    rb = engine.submit(pb, max_new_tokens=40, sampling=GREEDY)
    deadline = time.monotonic() + 60
    while not rb.tokens and time.monotonic() < deadline:
        time.sleep(0.005)
    assert rb.tokens, "hit request never started decoding"
    # while rb is live its slot shares the cache's blocks by refcount
    alloc = engine.paged.alloc
    assert alloc.shared_count >= 2, "prefix blocks not shared"
    assert SERVE_KV_BLOCKS_SHARED.value() >= 2
    entry = next(iter(engine.prefix_cache._blocks.values()))
    slot_pids = alloc.tables[rb.slot][:len(entry.pids)]
    assert slot_pids == entry.pids, "hit did not map the cached blocks"
    rb.cancel()
    assert rb.wait(60)
    assert rb.stats["prefix_hit_tokens"] == 32      # 2 units x 16 tokens
    # and the spliced continuation is still bit-identical
    rc = engine.submit(pb, max_new_tokens=6, sampling=GREEDY)
    assert rc.wait(180)
    assert rc.result["tokens"] == _ref(model, pb, 6)


def test_paged_decode_steady_state_no_recompiles(model, engine):
    """Block-table updates (decode crossing block boundaries allocates
    fresh blocks mid-generation) must compile NOTHING new: the table is
    a traced argument, nb is the only static one."""
    from cake_tpu.analysis.sanitizers import assert_no_recompiles
    warm = engine.submit(P_A, max_new_tokens=20, sampling=GREEDY)
    assert warm.wait(180)
    with assert_no_recompiles(model._decode_slots_paged,
                              label="paged decode steady state"):
        # 5-token prompt + 20 tokens crosses block boundaries at 8, 16
        # and 24 — three live table remaps under the guard
        r = engine.submit(P_A, max_new_tokens=20, sampling=GREEDY)
        assert r.wait(180)
    assert r.result["tokens"] == warm.result["tokens"]


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_paged_exhaustion_preempts_then_bit_identical(model, mode):
    """Two streams whose KV outgrows the 96-token pool force preemption;
    the victim resumes when blocks free and BOTH outputs stay bit-
    identical to the sequential path (swap restores exact bytes;
    recompute replays — the rebuild parity rule)."""
    from cake_tpu.obs import SERVE_PREEMPTIONS
    before = SERVE_PREEMPTIONS.value(mode=mode)
    ref_a = _ref(model, P_A, 60)
    ref_b = _ref(model, P_B, 60)
    eng = _engine(model, preempt_mode=mode)
    try:
        ra = eng.submit(P_A, max_new_tokens=60, sampling=GREEDY)
        rb = eng.submit(P_B, max_new_tokens=60, sampling=GREEDY)
        assert ra.wait(600) and rb.wait(600)
        assert "error" not in ra.result, ra.result.get("error")
        assert "error" not in rb.result, rb.result.get("error")
        assert ra.result["tokens"] == ref_a
        assert rb.result["tokens"] == ref_b
        assert SERVE_PREEMPTIONS.value(mode=mode) > before, \
            "pool never exhausted — preemption untested"
        h = eng.health()["kv_pool"]
        assert h["preempted_slots"] == 0            # everyone resumed
        if mode == "swap":
            assert h["swaps"] >= 1
        # the paged pool runs with its fetch one step behind too, and a
        # preemption first lands the step in flight: an iteration that
        # dispatched a step and fetched with none queued behind the fetch
        decoded = [r for r in eng.flight.snapshot() if r["occupancy"]]
        assert sum(r["lag"] for r in decoded) > len(decoded) // 2
        assert any(r["lag"] == 0 and r["fetch_ms"] > 0 for r in decoded[1:])
    finally:
        eng.close()


def test_paged_pool_too_small_rejects_and_fails_typed(model):
    """Structural limits answer typed errors, not wedges: a prompt that
    can never fit is refused at submit; a generation that outgrows the
    pool with nothing left to reclaim fails with KVPoolExhausted and the
    engine keeps serving."""
    eng = _engine(model)
    try:
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit(list(range(3, 103)), max_new_tokens=4,
                       sampling=GREEDY)
        # single stream, 96-token pool, budget pushes past it: typed fail
        r = eng.submit(P_A, max_new_tokens=110, sampling=GREEDY)
        assert r.wait(600)
        assert isinstance(r.result.get("error"), KVPoolExhausted)
        assert len(r.tokens) > 80                   # got most of the way
        # engine survives and serves the next request
        r2 = eng.submit(P_B, max_new_tokens=6, sampling=GREEDY)
        assert r2.wait(180)
        assert r2.result["tokens"] == _ref(model, P_B, 6)
    finally:
        eng.close()


def test_paged_resume_gate_reclaims_cache_pins(model):
    """A parked request's resume gate must count prefix-cache pins as
    reclaimable capacity (ensure_free): the allocation path evicts
    lazily inside _alloc_one, but a PARKED preempted request never
    allocates — without the gate-side eviction, blocks held only by the
    cache would starve its resume forever."""
    from cake_tpu.serve.paged import PagedKV
    pk = PagedKV.build(model, 2, CTX, 6, BT, CHUNK)
    pids = [pk.alloc.alloc() for _ in range(4)]
    for p in pids:
        pk.alloc.ref(p, cache_pin=True)     # the cache's pin...
        pk.alloc.deref(p)                   # ...outlives the slot ref
    pk.evictor = lambda: (pids and pk.alloc.deref(pids.pop(),
                                                  cache_pin=True)) or 0
    assert pk.alloc.free_count == 2
    assert pk.ensure_free(5)                # reclaims 3 pinned blocks
    assert pk.alloc.free_count >= 5
    assert not pk.ensure_free(7)            # a 6-block pool never can
    pk.alloc.check()


# ---------------------------------------------------------------------------
# GDN (qwen3_5): linear-state boundary snapshots through the paged pool
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gdn_model():
    return TextModel(tiny_config("qwen3_5"), dtype=jnp.float32,
                     max_cache_len=CTX)


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_paged_gdn_parity_and_prefix_snapshot(gdn_model):
    """GDN hybrid (3 linear + 1 full layer): the paged pool pages only
    the full-attention layer; linear conv/recurrent state stays per-slot
    and prefix hits restore it from the share unit's boundary-exact
    snapshot. Greedy outputs are bit-identical to the sequential path,
    cold and spliced."""
    eng = _engine(gdn_model, prefix_cache_mb=8)
    try:
        pa = SYS + [9, 11]
        pb = SYS + [77, 31]
        ra = eng.submit(pa, max_new_tokens=8, sampling=GREEDY)
        assert ra.wait(600)
        assert "error" not in ra.result, ra.result.get("error")
        assert ra.result["tokens"] == _ref(gdn_model, pa, 8)
        rb = eng.submit(pb, max_new_tokens=8, sampling=GREEDY)
        assert rb.wait(600)
        assert rb.stats["prefix_hit_tokens"] == 32  # snapshot installed
        assert rb.result["tokens"] == _ref(gdn_model, pb, 8)
    finally:
        eng.close()
