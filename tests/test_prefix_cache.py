"""Shared-prefix KV cache (ISSUE 3): block extract/splice cache ops, the
hash-chain LRU, hit-vs-miss bit parity through the serve engine, and
eviction-under-pressure correctness — all on the tiny CPU model."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import PrefixCache, ServeEngine

GREEDY = SamplingConfig(temperature=0.0)
CTX = 128

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


def _ref(model, prompt, n):
    toks, _ = model.generate(list(prompt), max_new_tokens=n, sampling=GREEDY)
    return toks


PROMPT = [3 + (i * 7) % 200 for i in range(50)]


# ---------------------------------------------------------------------------
# cache ops: extract / splice roundtrip (no engine)
# ---------------------------------------------------------------------------


def test_slot_extract_splice_roundtrip(model):
    """Blocks copied out of a prefilled row and restored into a clean row of
    ANOTHER pool reproduce the original prefix bytes exactly, leave the
    rest of the row empty, and touch no neighbor."""
    chunk = 16
    layers = model.new_cache(3, kv_len=64)["layers"]
    for s in range(0, 32, chunk):
        _, layers = model.prefill_chunk(layers, 1, PROMPT[s:s + chunk], s)
    blocks = [model.slot_extract(layers, 1, b * chunk, chunk)
              for b in range(2)]
    for b, blk in enumerate(blocks):
        for lc in blk:
            np.testing.assert_array_equal(
                np.asarray(lc["pos"][0]),
                np.arange(b * chunk, (b + 1) * chunk))

    layers2 = model.new_cache(3, kv_len=64)["layers"]
    layers2 = model.slot_restore(layers2, blocks[:1], 2, 0, chunk,
                                 final=False)
    layers2 = model.slot_restore(layers2, blocks[1:], 2, 1, chunk,
                                 final=True)
    for lc_src, lc_dst in zip(layers, layers2):
        np.testing.assert_array_equal(np.asarray(lc_src["k"][1, :32]),
                                      np.asarray(lc_dst["k"][2, :32]))
        np.testing.assert_array_equal(np.asarray(lc_src["v"][1, :32]),
                                      np.asarray(lc_dst["v"][2, :32]))
        np.testing.assert_array_equal(np.asarray(lc_dst["pos"][2, :32]),
                                      np.arange(32))
        assert int(jnp.max(lc_dst["pos"][2, 32:])) == -1
        assert float(jnp.abs(lc_dst["k"][0]).max()) == 0.0   # neighbors
        assert float(jnp.abs(lc_dst["k"][1]).max()) == 0.0
    with pytest.raises(ValueError, match="aligned"):    # 2 blocks at 1
        model.slot_restore(layers2, blocks, 2, 1, chunk, final=True)


def test_spliced_prefix_continues_bitwise(model):
    """Prefilling the SUFFIX on top of a restored prefix yields the same
    final logits as prefilling the whole prompt into the row — the
    hit-path numerics are the miss-path numerics."""
    chunk = 16
    miss = model.new_cache(2, kv_len=64)["layers"]
    for s in range(0, len(PROMPT), chunk):
        ref_logits, miss = model.prefill_chunk(miss, 0,
                                               PROMPT[s:s + chunk], s)
    blocks = [model.slot_extract(miss, 0, b * chunk, chunk)
              for b in range(3)]
    hit = model.new_cache(2, kv_len=64)["layers"]
    hit = model.slot_restore(hit, blocks[:2], 1, 0, chunk, final=False)
    hit = model.slot_restore(hit, blocks[2:], 1, 2, chunk, final=True)
    hit_logits, hit = model.prefill_chunk(hit, 1, PROMPT[48:], 48)
    np.testing.assert_array_equal(np.asarray(hit_logits),
                                  np.asarray(ref_logits))


# ---------------------------------------------------------------------------
# one restore of a chain == block-by-block scatters, for every layer kind
# ---------------------------------------------------------------------------

# kind -> (family, config overrides, block): every kind of row a chain meets
_KINDS = {
    "full": ("llama", {}, 8),
    "ring_narrower_than_a_block": ("mimo_v2", {}, 32),      # ring of 16
    "ring_of_two_blocks": ("mistral", {"sliding_window": 16}, 8),
    "joined_keys": ("mimo_v2", {"sliding_window": 64}, 8),  # k [T, 384]
    "recurrent_snapshot": ("solar_open2", {}, 8),           # KDA + NoPE full
    "retention": ("brumby", {}, 8),
    "ring_no_multiple_of_the_block": ("mistral", {"sliding_window": 24}, 16),
}
_ROWS, _SRC, _DST = 3, 1, 2
_PROGRAMS: dict = {}


def _programs(kind):
    """A TextModel's programs for this kind's tiny config: no weights."""
    if kind not in _PROGRAMS:
        family, over, block = _KINDS[kind]
        m = TextModel.__new__(TextModel)
        m.cfg, m.dtype, m.mesh, m.tokenizer, m.max_cache_len = (
            tiny_config(family, **over), jnp.float32, None, None,
            64 * block)
        m._build()
        _PROGRAMS[kind] = m
    return _PROGRAMS[kind]


def _np_pool(model, rng, wiped=_DST):
    """A pool of _ROWS rows as numpy leaves, every row non-empty garbage
    but `wiped`, which is empty (pos -1, zeros) as a released row is."""
    pool = []
    for lc in model.new_cache(_ROWS)["layers"]:
        out = {}
        for name, buf in lc.items():
            a = (rng.integers(0, 7, buf.shape) if name == "pos"
                 else rng.standard_normal(buf.shape)).astype(buf.dtype)
            a[wiped] = -1 if name == "pos" else 0
            out[name] = a
        pool.append(out)
    return pool


def _row_at(pool, table, end):
    """`pool` with row _SRC as a prefill to exactly `end` tokens leaves it:
    a positional layer holds its last `size` positions at p % size, with
    the bytes `table` gives that absolute position; a recurrent layer the
    state `table` gives that boundary."""
    out = []
    for lc, tab in zip(pool, table):
        new = {n: a.copy() for n, a in lc.items()}
        if "pos" in lc:
            size = lc["pos"].shape[1]
            held = np.arange(max(0, end - size), end)
            new["pos"][_SRC] = -1
            new["pos"][_SRC, held % size] = held
            for n in ("k", "v"):
                new[n][_SRC, held % size] = tab[n][held]
        else:
            for n in lc:
                new[n][_SRC] = tab[n][end]
        out.append(new)
    return out


def _scatter_chain(pool, chain):
    """What the engine did before there was a chain restore, in numpy:
    block by block, every entry to position % size (pos -1: dropped), the
    last block's recurrent snapshot installed."""
    out = [{n: a.copy() for n, a in lc.items()} for lc in pool]
    for b, blk in enumerate(chain):
        for lo, lb in zip(out, blk):
            if "pos" not in lo:
                if b == len(chain) - 1:
                    for n in lo:
                        lo[n][_DST] = np.asarray(lb[n][0])
                continue
            size = lo["pos"].shape[1]
            for j, p in enumerate(np.asarray(lb["pos"][0])):
                if p >= 0:
                    for n in lo:
                        lo[n][_DST, p % size] = np.asarray(lb[n][0, j])
    return out


@pytest.mark.parametrize("matched", [1, 2, 3, 32])
@pytest.mark.parametrize("kind", list(_KINDS))
def test_chain_restore_equals_block_by_block_scatters(kind, matched):
    """The pool after ONE restore of a chain (popcount(matched) dispatches:
    3 = 2 + 1) equals, leaf for leaf and bit for bit, the pool after the
    block-by-block scatters it replaced: full rows, rings narrower than a
    block and of two blocks, joined keys, recurrent and retention state,
    and a ring no run rule covers (which keeps the scatter). Row 2 of 3,
    the rows beside it untouched; block 0 holds a `pos` of -1, which keeps
    what the row had."""
    import jax
    model = _programs(kind)
    block = _KINDS[kind][2]
    rng = np.random.default_rng(54)
    pool = _np_pool(model, rng)
    total = matched * block
    table = [{n: rng.standard_normal((total + 1,) + a.shape[2 if "pos" in lc
                                                            else 1:]
                                     ).astype(a.dtype)
              for n, a in lc.items() if n != "pos"} for lc in pool]
    prompt = [int(t) for t in rng.integers(1, 200, total + 1)]
    pc = PrefixCache(model, block, 1 << 30)
    keys = pc.chain_keys(prompt)
    assert len(keys) == matched
    for b in range(matched):
        at = jax.tree_util.tree_map(jnp.asarray,
                                    _row_at(pool, table, (b + 1) * block))
        pc.insert(at, _SRC, prompt, b, keys)
    first = pc._blocks[keys[0]].layers
    for lc in first:                       # the drop rule
        if "pos" in lc:
            lc["pos"] = lc["pos"].at[0, 3].set(-1)
    assert pc.match(prompt, keys) == matched
    got = pc.splice(jax.tree_util.tree_map(jnp.asarray, pool), _DST, keys,
                    matched)
    assert (pc.restores, pc.restored) == (bin(matched).count("1"), matched)
    want = _scatter_chain(pool, [pc._blocks[k].layers for k in keys])
    for i, (lg, lw) in enumerate(zip(got, want)):
        assert lg.keys() == lw.keys()
        for n in lw:
            assert lg[n].dtype == lw[n].dtype, (i, n)
            assert np.asarray(lg[n]).tobytes() == lw[n].tobytes(), (i, n)


def test_restoring_blocks_that_hold_nothing_leaves_the_pool():
    """A piece whose every `pos` is -1 (what `slot_extract` gives of an
    empty row), not final, is a no-op under the drop rule: the pool comes
    back byte for byte, recurrent state included."""
    import jax
    model = _programs("recurrent_snapshot")
    pool = _np_pool(model, np.random.default_rng(5), wiped=0)
    layers = jax.tree_util.tree_map(jnp.asarray, pool)
    blk = model.slot_extract(layers, 0, 0, 8)
    got = model.slot_restore(layers, [blk] * 4, 0, 0, 8, final=False)
    for lg, lw in zip(got, pool):
        for n in lw:
            assert np.asarray(lg[n]).tobytes() == lw[n].tobytes(), n


def test_restore_reads_only_what_the_row_keeps():
    """From shapes alone: a full buffer keeps the piece whole as one run; a
    ring its last blocks (one run where piece and ring divide one another),
    a ring narrower than a block the last block's tail; a ring that is no
    multiple of the block has no run rule. What is not read is not handed
    to the program."""
    from cake_tpu.models.common.cache import restore_reads, restore_runs
    assert restore_runs(16384, 256, 32) == [list(range(32))]
    assert restore_runs(512, 256, 32) == [[30, 31]]
    assert restore_runs(512, 256, 1) == [[0]]
    assert restore_runs(768, 256, 2) == [[0], [1]]      # may wrap: apart
    assert restore_runs(128, 256, 32) == [[31]]
    assert restore_runs(384, 256, 4) is None
    model = _programs("recurrent_snapshot")
    pool = model.new_cache(_ROWS)["layers"]
    blk = model.slot_extract(pool, 0, 0, 8)
    reads = restore_reads(pool, [blk] * 4, 8)
    state = [i for i, lc in enumerate(pool) if "pos" not in lc]
    assert state and all(not reads[b][i] for b in range(3) for i in state)
    assert all(reads[3][i].keys() == pool[i].keys() for i in state)
    assert all(reads[b][i] for b in range(4)
               for i in range(len(pool)) if i not in state)


# ---------------------------------------------------------------------------
# PrefixCache unit behavior
# ---------------------------------------------------------------------------


def test_prefix_cache_build_gating(model):
    assert PrefixCache.build(model, CTX, 16, 0) is None        # disabled
    assert PrefixCache.build(model, CTX, CTX * 2, 64) is None  # block > ctx
    pc = PrefixCache.build(model, CTX, 16, 64)
    assert pc is not None and pc.block == 16


def test_prefix_cache_match_requires_live_suffix(model):
    """Reuse is capped at n-1 tokens: a prompt exactly equal to a cached
    chain still prefills its final token live (its logits seed sampling)."""
    pc = PrefixCache.build(model, CTX, 16, 64)
    layers = model.new_cache(2, kv_len=64)["layers"]
    for s in range(0, 32, 16):
        _, layers = model.prefill_chunk(layers, 0, PROMPT[s:s + 16], s)
    keys = pc.chain_keys(PROMPT)
    pc.insert(layers, 0, PROMPT, 0, keys)
    pc.insert(layers, 0, PROMPT, 1, keys)
    assert len(pc._blocks) == 2

    def match(p):
        return pc.match(p, pc.chain_keys(p))
    assert match(PROMPT[:50]) == 2           # 32 < 50-1: both blocks usable
    assert match(PROMPT[:33]) == 2           # 32 == 33-1: still ok
    assert match(PROMPT[:32]) == 1           # full match would leave 0 live
    assert match(PROMPT[:16] + [9] * 16) == 1      # diverges after block 0
    assert match([9] * 40) == 0


# ---------------------------------------------------------------------------
# engine e2e: hit == miss, eviction under pressure
# ---------------------------------------------------------------------------


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_engine_prefix_hit_matches_miss(model):
    """The tentpole acceptance pin on the HIT side: greedy output is
    bit-identical whether the prefix was spliced from cache or computed,
    and the stats/metrics record the reuse."""
    ref = _ref(model, PROMPT, 10)
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=64)
    try:
        r1 = eng.submit(PROMPT, max_new_tokens=10, sampling=GREEDY)
        assert r1.wait(120)
        assert r1.result["tokens"] == ref
        assert r1.stats["prefix_hit_tokens"] == 0
        assert r1.stats["prefill_chunks"] == 4

        r2 = eng.submit(PROMPT, max_new_tokens=10, sampling=GREEDY)
        assert r2.wait(120)
        assert r2.result["tokens"] == ref                  # bit-identical
        assert r2.stats["prefix_hit_tokens"] == 48         # 3 blocks of 16
        assert r2.stats["prefill_chunks"] == 1             # suffix only

        # divergent suffix sharing 32 leading tokens: partial chain reuse
        p3 = PROMPT[:32] + [9, 9, 4, 4, 1]
        r3 = eng.submit(p3, max_new_tokens=10, sampling=GREEDY)
        assert r3.wait(120)
        assert r3.result["tokens"] == _ref(model, p3, 10)
        assert r3.stats["prefix_hit_tokens"] == 32

        occ = eng.health()["prefix_cache"]
        assert occ["hits"] == 2 and occ["blocks"] >= 3
        assert occ["bytes"] > 0
    finally:
        eng.close()


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_engine_prefix_eviction_under_pressure(model):
    """A capacity small enough for ~2 blocks forces LRU evictions while
    distinct prefixes stream through; outputs stay correct before, during
    and after eviction (a shortened chain only costs compute)."""
    eng = ServeEngine(model, slots=1, max_queue=8, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=0.04)
    try:
        prompts = [[5 + j] * 1 + [(j * 31 + i * 7) % 200 + 3
                                  for i in range(39)] for j in range(3)]
        refs = [_ref(model, p, 6) for p in prompts]
        for p, want in zip(prompts, refs):
            r = eng.submit(p, max_new_tokens=6, sampling=GREEDY)
            assert r.wait(120)
            assert r.result["tokens"] == want
        occ = eng.health()["prefix_cache"]
        assert occ["evictions"] > 0, occ
        assert occ["bytes"] <= occ["capacity_bytes"]
        # the first prefix was evicted: resubmitting it must still be
        # correct (miss or partial hit, never wrong)
        r = eng.submit(prompts[0], max_new_tokens=6, sampling=GREEDY)
        assert r.wait(120)
        assert r.result["tokens"] == refs[0]
    finally:
        eng.close()


def test_engine_three_block_hit_is_two_restore_dispatches(model):
    """A hit of 3 blocks goes to the device as popcount(3) = 2 restore
    programs (2 blocks + 1), by the cache's count, the /metrics counters
    and the admitting step's flight record; its greedy stream is the
    miss's, bit for bit."""
    from cake_tpu.obs import (SERVE_PREFIX_RESTORE_BLOCKS,
                              SERVE_PREFIX_RESTORE_DISPATCHES)
    d0 = SERVE_PREFIX_RESTORE_DISPATCHES.value()
    b0 = SERVE_PREFIX_RESTORE_BLOCKS.value()
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=64)
    try:
        miss = eng.submit(PROMPT, max_new_tokens=8, sampling=GREEDY)
        assert miss.wait(120)
        assert miss.stats["prefix_hit_tokens"] == 0
        pc = eng.prefix_cache
        assert (pc.restores, pc.restored) == (0, 0)
        hit = eng.submit(PROMPT, max_new_tokens=8, sampling=GREEDY)
        assert hit.wait(120)
        assert hit.stats["prefix_hit_tokens"] == 48         # 3 blocks of 16
        assert hit.result["tokens"] == miss.result["tokens"]
        assert (pc.restores, pc.restored) == (2, 3)
        assert SERVE_PREFIX_RESTORE_DISPATCHES.value() - d0 == 2
        assert SERVE_PREFIX_RESTORE_BLOCKS.value() - b0 == 3
        occ = eng.health()["prefix_cache"]
        assert (occ["restores"], occ["restored_blocks"]) == (2, 3)
        recs = eng.flight.snapshot()
        assert sum(r["restores"] for r in recs) == 2
        assert [r["restored"] for r in recs if r["restores"]] == [3]
    finally:
        eng.close()


def test_engine_prefix_cache_disabled(model):
    eng = ServeEngine(model, slots=1, max_queue=2, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=0)
    try:
        assert eng.prefix_cache is None
        r = eng.submit(PROMPT, max_new_tokens=6, sampling=GREEDY)
        assert r.wait(120)
        assert r.result["tokens"] == _ref(model, PROMPT, 6)
        assert "prefix_cache" not in eng.health()
    finally:
        eng.close()


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_engine_prefix_hit_matches_miss_gdn():
    """Same hit==miss pin through a qwen3_5-style model with LINEAR
    (GDN) layers: the per-block conv/recurrent-state snapshot — captured
    at the chunk boundary, installed only from the final matched block —
    must reproduce the sequential path bit-for-bit too."""
    m = TextModel(tiny_config("qwen3_5"), dtype=jnp.float32,
                  max_cache_len=CTX)
    prompt = [3 + (i * 11) % 200 for i in range(40)]
    ref, _ = m.generate(list(prompt), max_new_tokens=6, sampling=GREEDY)
    eng = ServeEngine(m, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=64)
    try:
        r1 = eng.submit(prompt, max_new_tokens=6, sampling=GREEDY)
        assert r1.wait(300)
        assert r1.result["tokens"] == ref
        assert r1.stats["prefix_hit_tokens"] == 0
        r2 = eng.submit(prompt, max_new_tokens=6, sampling=GREEDY)
        assert r2.wait(300)
        assert r2.result["tokens"] == ref                  # bit-identical
        assert r2.stats["prefix_hit_tokens"] == 32         # 2 blocks of 16
    finally:
        eng.close()


def test_engine_cancel_mid_prefill_frees_slot(model):
    """Cancelling a request while its CHUNKED prefill is still in flight
    aborts the admission, wipes the half-built row and frees the slot."""
    eng = ServeEngine(model, slots=1, max_queue=2, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=0)
    try:
        long_prompt = [3 + (i * 13) % 200 for i in range(120)]
        r = eng.submit(long_prompt, max_new_tokens=6, sampling=GREEDY)
        deadline = time.monotonic() + 30
        while not eng.health()["prefilling"] and time.monotonic() < deadline:
            time.sleep(0.001)
        r.cancel()
        assert r.wait(30)
        assert not r.tokens
        deadline = time.monotonic() + 30
        while eng.pool.busy_count and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.pool.busy_count == 0
        # the slot is clean: the next request reproduces the reference
        r2 = eng.submit(PROMPT, max_new_tokens=6, sampling=GREEDY)
        assert r2.wait(120)
        assert r2.result["tokens"] == _ref(model, PROMPT, 6)
    finally:
        eng.close()
