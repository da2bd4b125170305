"""Continuous-batching serve engine: slot/queue units (no model), batched
slot-decode cache ops, and end-to-end concurrent serving through the
aiohttp API on a tiny CPU model — the tier-1 pin for ISSUE 2's acceptance:
concurrent requests interleave, greedy outputs match the sequential path
exactly, backpressure answers 429, and disconnects reclaim slots."""
import asyncio
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import (AdmissionQueue, QueueFull, ServeEngine,
                            SlotPool, maybe_engine)

GREEDY = SamplingConfig(temperature=0.0)


# ---------------------------------------------------------------------------
# units: no model required
# ---------------------------------------------------------------------------


def test_slot_pool_lowest_first():
    p = SlotPool(3)
    assert [p.alloc(), p.alloc(), p.alloc()] == [0, 1, 2]
    assert p.alloc() is None and p.free_count == 0
    p.free(1)
    assert p.alloc() == 1                 # lowest free index, not LIFO
    p.free(0)
    p.free(2)
    assert p.busy() == [1] and p.prefix_len() == 2
    p.free(1)
    assert p.prefix_len() == 0
    with pytest.raises(ValueError):
        p.free(1)                         # double free


def test_slot_bucket_powers_of_two():
    from cake_tpu.serve.slots import slot_bucket
    assert [slot_bucket(n, 8) for n in (1, 2, 3, 4, 5, 8)] == \
        [1, 2, 4, 4, 8, 8]
    assert slot_bucket(3, 4) == 4 and slot_bucket(1, 1) == 1
    # the whole point vs bucket_for: a lone request decodes 1 row, not 32
    assert slot_bucket(1, 4) == 1


def test_admission_queue_purge():
    q = AdmissionQueue(maxsize=4)
    for x in ("a", "bb", "c", "dd"):
        q.put(x)
    dropped = q.purge(lambda s: len(s) == 2)
    assert dropped == ["bb", "dd"]
    assert q.pop() == "a" and q.pop() == "c" and q.pop() is None
    from cake_tpu.obs import SERVE_QUEUE_DEPTH
    assert SERVE_QUEUE_DEPTH.value() == 0


def test_admission_queue_fifo_and_bound():
    from cake_tpu.obs import SERVE_QUEUE_DEPTH
    q = AdmissionQueue(maxsize=2)
    q.put("a")
    q.put("b")
    assert SERVE_QUEUE_DEPTH.value() == 2
    with pytest.raises(QueueFull) as ei:
        q.put("c")
    assert ei.value.retry_after_s >= 1
    assert q.pop() == "a" and q.pop() == "b" and q.pop() is None
    assert SERVE_QUEUE_DEPTH.value() == 0
    q.put("d")
    assert q.drain() == ["d"] and q.depth() == 0


def test_slot_assign_and_reset_rehome():
    """slot_assign re-homes a batch-1 bucketed cache into one pool row
    (position -> slot remap, padding dropped) leaving other rows alone;
    slot_reset clears exactly one row. Pure cache ops, no model."""
    from cake_tpu.models.common.cache import (init_cache, slot_assign_layers,
                                              slot_reset_layers)
    cfg = tiny_config("llama")
    pool = init_cache(cfg, 3, 64, jnp.float32)
    # make row 0 and 2 recognizably non-empty
    layers = pool["layers"]
    layers = [{**lc, "k": lc["k"].at[0].set(7.0).at[2].set(9.0),
               "pos": lc["pos"].at[0, :4].set(jnp.arange(4))}
              for lc in layers]

    src = init_cache(cfg, 1, 32, jnp.float32)
    n = 5
    src_layers = []
    for lc in src["layers"]:
        k = lc["k"].at[0, :n].set(
            jnp.arange(n, dtype=jnp.float32)[:, None, None] + 1.0)
        pos = lc["pos"].at[0, :n].set(jnp.arange(n))
        src_layers.append({**lc, "k": k, "v": lc["v"], "pos": pos})

    out = slot_assign_layers(layers, src_layers, jnp.asarray(1))
    for lc in out:
        np.testing.assert_array_equal(np.asarray(lc["pos"][1, :n]),
                                      np.arange(n))
        assert int(jnp.max(lc["pos"][1, n:])) == -1      # rest of row empty
        np.testing.assert_allclose(np.asarray(lc["k"][1, :n, 0, 0]),
                                   np.arange(n) + 1.0)
        # neighbors untouched
        assert float(lc["k"][0, 0, 0, 0]) == 7.0
        assert float(lc["k"][2, 0, 0, 0]) == 9.0
        np.testing.assert_array_equal(np.asarray(lc["pos"][0, :4]),
                                      np.arange(4))

    out = slot_reset_layers(out, jnp.asarray(1))
    for lc in out:
        assert int(jnp.max(lc["pos"][1])) == -1
        assert float(jnp.abs(lc["k"][1]).max()) == 0.0
        assert float(lc["k"][0, 0, 0, 0]) == 7.0         # row 0 survives


def test_row_operations_go_by_the_leaves():
    """What a row of state is, is read off the layer's leaves (a `pos`
    leaf: entries by position; none: recurrent state copied whole), never
    off a kind's name: a pool whose recurrent layer carries leaves the code
    has never heard of goes assign -> extract -> restore (a chain of one,
    final False, then True) -> truncate -> reset, the named row changes as specified and
    every other row keeps its bytes. Pure cache ops, no model, no cfg."""
    from cake_tpu.models.common.cache import (
        is_positional, slot_assign_layers, slot_extract_block_layers,
        slot_reset_layers, slot_restore_chain_layers, truncate_layers)
    B, FULL, RING, H, D = 3, 32, 8, 2, 4
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def rnd(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def kv(batch, size, held):
        """A positional layer holding the positions `held` at p % size."""
        held = np.asarray(held)
        pos = np.full((batch, size), -1, np.int32)
        pos[:, held % size] = held
        return {"k": rnd((batch, size, H, D)), "v": rnd((batch, size, H, D)),
                "pos": jnp.asarray(pos)}

    def state(batch):
        return {"ssm": rnd((batch, 4, 8)),
                "tail": rnd((batch, 6, 3), jnp.bfloat16)}

    def same_bytes(a, b):
        return np.asarray(a).tobytes() == np.asarray(b).tobytes()

    def others_untouched(new, old, row):
        for ln, lo in zip(new, old):
            assert ln.keys() == lo.keys()
            for name in lo:
                assert ln[name].dtype == lo[name].dtype
                for r in set(range(B)) - {row}:
                    assert same_bytes(ln[name][r], lo[name][r]), (name, r)

    # every row of the pool starts non-empty: a full buffer, a ring, a state
    pool = [kv(B, FULL, range(3)), kv(B, RING, range(3)), state(B)]
    assert [is_positional(lc) for lc in pool] == [True, True, False]
    n = 10
    src = [kv(1, 16, range(n)), kv(1, RING, range(n - RING, n)), state(1)]

    # assign: row 1 holds exactly the source, re-homed at position % size
    out = slot_assign_layers(pool, src, jnp.asarray(1))
    others_untouched(out, pool, 1)
    for lo, ls, held in ((out[0], src[0], range(n)),
                         (out[1], src[1], range(n - RING, n))):
        size, ssize = lo["pos"].shape[1], ls["pos"].shape[1]
        want = np.full((size,), -1, np.int32)
        for p_ in held:
            want[p_ % size] = p_
            for name in ("k", "v"):
                assert same_bytes(lo[name][1, p_ % size],
                                  ls[name][0, p_ % ssize])
        np.testing.assert_array_equal(np.asarray(lo["pos"][1]), want)
    for name in src[2]:
        assert same_bytes(out[2][name][1], src[2][name][0])

    # extract positions 4..7 of row 1: entries by position, the state whole
    blk = slot_extract_block_layers(out, jnp.asarray(1), jnp.asarray(4), 4)
    for lb, lo in zip(blk[:2], out[:2]):
        size = lo["pos"].shape[1]
        np.testing.assert_array_equal(np.asarray(lb["pos"]),
                                      np.arange(4, 8)[None])
        for name in ("k", "v"):
            assert lb[name].shape == (1, 4, H, D)
            assert same_bytes(lb[name][0], lo[name][1, np.arange(4, 8) % size])
    assert blk[2].keys() == out[2].keys()
    for name in blk[2]:
        assert blk[2][name].shape == (1,) + out[2][name].shape[1:]
        assert same_bytes(blk[2][name][0], out[2][name][1])

    # restore into a wiped row 2: the block's entries land by position; the
    # state is a block-end snapshot, installed by the final piece only
    wiped = slot_reset_layers(out, jnp.asarray(2))
    for final in (False, True):
        got = slot_restore_chain_layers(wiped, [blk], jnp.asarray(2),
                                        jnp.asarray(1), 4,
                                        jnp.asarray(final))
        others_untouched(got, wiped, 2)
        for lg, lb in zip(got[:2], blk[:2]):
            size = lg["pos"].shape[1]
            want = np.full((size,), -1, np.int32)
            want[np.arange(4, 8) % size] = np.arange(4, 8)
            np.testing.assert_array_equal(np.asarray(lg["pos"][2]), want)
            for name in ("k", "v"):
                assert same_bytes(lg[name][2, np.arange(4, 8) % size],
                                  lb[name][0])
        for name in blk[2]:
            want = blk[2][name][0] if final else wiped[2][name][2]
            assert same_bytes(got[2][name][2], want)

    # truncate (the whole batch): positions >= 6 become empty, K/V bytes
    # and recurrent state stay
    cut = truncate_layers(got, jnp.asarray(6))
    for lc, lg in zip(cut[:2], got[:2]):
        pos = np.asarray(lg["pos"])
        np.testing.assert_array_equal(np.asarray(lc["pos"]),
                                      np.where(pos >= 6, -1, pos))
        assert same_bytes(lc["k"], lg["k"]) and same_bytes(lc["v"], lg["v"])
    assert int(jnp.max(cut[0]["pos"][1])) == 5           # 0..5 of 0..9 stay
    for name in got[2]:
        assert same_bytes(cut[2][name], got[2][name])

    # reset: row 1 empty (pos -1, every other leaf zero), the rest as it was
    clr = slot_reset_layers(cut, jnp.asarray(1))
    others_untouched(clr, cut, 1)
    for lc in clr:
        for name, buf in lc.items():
            row = np.asarray(buf[1].astype(jnp.float32))
            assert (row == (-1 if name == "pos" else 0)).all(), name


def test_sample_traced_matches_static_greedy():
    """The traced sampler (one executable for every per-slot config mix)
    must agree with the static dispatch on greedy, incl. repeat penalty
    and tie-breaking; stochastic draws must respect the top-k set."""
    from cake_tpu.ops.sampling import sample, sample_traced
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(jax.random.PRNGKey(1), (97,)) * 3
    recent = jnp.full((8,), -1, jnp.int32).at[:3].set(jnp.asarray([5, 9, 5]))
    for pen in (1.0, 1.3):
        a = sample(logits, rng,
                   SamplingConfig(temperature=0.0, repeat_penalty=pen),
                   recent)
        b = sample_traced(logits, rng, jnp.float32(0.0), jnp.int32(97),
                          jnp.float32(1.0), jnp.float32(pen), recent)
        assert int(a) == int(b)
    tie = jnp.zeros((10,)).at[3].set(5.0).at[7].set(5.0)
    none = jnp.full((4,), -1, jnp.int32)
    assert int(sample_traced(tie, rng, jnp.float32(0.0), jnp.int32(10),
                             jnp.float32(1.0), jnp.float32(1.0), none)) == 3
    topk = set(np.asarray(jax.lax.top_k(logits, 5)[1]).tolist())
    for i in range(20):
        t = sample_traced(logits, jax.random.PRNGKey(100 + i),
                          jnp.float32(0.8), jnp.int32(5), jnp.float32(1.0),
                          jnp.float32(1.0), recent)
        assert int(t) in topk


def test_sample_traced_topk_topp_renormalizes():
    """Combined top_k+top_p must measure top-p mass on the top-k-truncated
    RENORMALIZED distribution (sample_top_k_top_p semantics): 5 equal-top
    logits with k=5, p=0.5 keep ranks 0-2 (prev mass 0, .2, .4), never
    ranks 3-4 — under full-vocab mass all 5 would pass."""
    from cake_tpu.ops.sampling import sample_traced
    v = 64
    logits = jnp.full((v,), 1.9).at[:5].set(2.0)   # spread the tail mass
    none = jnp.full((4,), -1, jnp.int32)
    seen = set()
    for i in range(60):
        t = sample_traced(logits, jax.random.PRNGKey(i), jnp.float32(1.0),
                          jnp.int32(5), jnp.float32(0.5), jnp.float32(1.0),
                          none)
        seen.add(int(t))
    assert seen <= {0, 1, 2}, seen
    assert len(seen) > 1                           # actually stochastic


def test_maybe_engine_gating(monkeypatch):
    """Only plain TextModels get an engine; CAKE_SERVE_SLOTS=0 disables."""
    class NotATextModel:
        pass
    assert maybe_engine(NotATextModel()) is None
    monkeypatch.setenv("CAKE_SERVE_SLOTS", "0")
    # a real TextModel with slots=0 must also be None — checked via the
    # env without building a model (slots resolves before the isinstance
    # fails), so construct the cheapest possible one
    m = _model()
    assert maybe_engine(m) is None
    monkeypatch.setenv("CAKE_SERVE_SLOTS", "2")
    eng = maybe_engine(m, ctx_len=64)
    try:
        assert eng is not None and eng.slots == 2 and eng.ctx == 64
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# e2e: tiny CPU model
# ---------------------------------------------------------------------------

CTX = 256


class TinyTok:
    """Deterministic toy tokenizer: per-token decode concatenates exactly
    like whole-sequence decode, so streamed and blocking text agree."""

    def encode(self, text):
        return [3 + (sum(w.encode()) % 200) for w in text.split()][:24] or [3]

    def decode(self, ids):
        return "".join(f"<{i}>" for i in ids)


_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        _MODEL = TextModel(tiny_config("llama"), dtype=jnp.float32,
                           max_cache_len=CTX)
        _MODEL.tokenizer = TinyTok()
    return _MODEL


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def engine(model):
    eng = ServeEngine(model, slots=4, max_queue=8, ctx_len=CTX)
    yield eng
    eng.close()


def _ref(model, prompt, n, sampling=GREEDY):
    toks, _ = model.generate(list(prompt), max_new_tokens=n,
                             sampling=sampling)
    return toks


P_LONG = [3, 17, 42, 99, 7]
P_A = [8, 8, 1, 30]
P_B = [100, 2, 5, 9, 11, 40]


def test_engine_greedy_matches_sequential(model, engine):
    """3 concurrent greedy requests each reproduce the sequential path
    bit-for-bit (masked pool slots contribute exactly-zero attention)."""
    reqs = [engine.submit(p, max_new_tokens=n, sampling=GREEDY)
            for p, n in ((P_LONG, 12), (P_A, 6), (P_B, 9))]
    for r, (p, n) in zip(reqs, ((P_LONG, 12), (P_A, 6), (P_B, 9))):
        assert r.wait(120)
        assert r.result["tokens"] == _ref(model, p, n)
        assert r.result["stats"]["ttft_s"] > 0


def test_engine_repeat_penalty_parity(model, engine):
    """Traced per-slot repeat penalty matches the static sequential path
    (same recent-token window seeding: generated tokens only)."""
    scfg = SamplingConfig(temperature=0.0, repeat_penalty=1.3)
    r = engine.submit(P_LONG, max_new_tokens=10, sampling=scfg)
    assert r.wait(120)
    assert r.result["tokens"] == _ref(model, P_LONG, 10, scfg)


def test_engine_interleaves_short_past_long(model, engine):
    """Iteration-level scheduling: two short requests admitted after a
    long one finish while it is still decoding — impossible on the
    serialized locked path."""
    long_ref = _ref(model, P_LONG, 48)
    assert len(long_ref) >= 24            # precondition: no early EOS
    r_long = engine.submit(P_LONG, max_new_tokens=48, sampling=GREEDY)
    while not r_long.tokens:              # admitted and decoding
        time.sleep(0.005)
    r_a = engine.submit(P_A, max_new_tokens=4, sampling=GREEDY)
    r_b = engine.submit(P_B, max_new_tokens=4, sampling=GREEDY)
    assert r_a.wait(60) and r_b.wait(60)
    assert not r_long.done.is_set(), \
        "short requests should complete while the long one still decodes"
    assert r_long.wait(120)
    assert r_long.result["tokens"] == long_ref


def test_engine_concurrent_overlap(model, engine):
    """All 4 concurrent requests decode SIMULTANEOUSLY under iteration-
    level batching: a moment exists where every request has emitted >= 1
    token and none has finished. This is the scheduling property the old
    wall-clock-ratio assert (t_four / t_single < 2) inferred from
    timing — which flaked under CI machine load while passing standalone.
    Occupancy is load-immune: contention slows the scheduler and the
    poller together, and the overlap window only WIDENS (admissions
    stagger by ~1 iteration, completions sit ~36 iterations later)."""
    ref = _ref(model, P_LONG, 36)
    assert len(ref) == 36                 # precondition: no early EOS
    reqs = [engine.submit(P_LONG, max_new_tokens=36, sampling=GREEDY)
            for _ in range(4)]
    overlap = False
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        counts = [len(r.tokens) for r in reqs]
        done = [r.done.is_set() for r in reqs]
        if all(done):
            break
        if all(c > 0 for c in counts) and not any(done):
            overlap = True
            break
        time.sleep(0.002)
    assert overlap, \
        "4 concurrent requests never decoded simultaneously"
    for r in reqs:
        assert r.wait(300)
        assert r.result["tokens"] == ref  # batching never costs parity


def test_engine_cancel_frees_slot(model, engine):
    """Client disconnect mid-stream reclaims the slot: slots_busy returns
    to 0 and the generation stops well short of its budget."""
    from cake_tpu.obs import SERVE_SLOTS_BUSY
    r = engine.submit(P_LONG, max_new_tokens=180, sampling=GREEDY)
    while len(r.tokens) < 3:
        time.sleep(0.005)
    assert SERVE_SLOTS_BUSY.value() >= 1
    r.cancel()
    deadline = time.monotonic() + 10
    while SERVE_SLOTS_BUSY.value() != 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert SERVE_SLOTS_BUSY.value() == 0
    assert r.done.is_set()
    assert len(r.tokens) < 170            # budget was NOT decoded out


def test_engine_backpressure_queue_full(model):
    """slots=1 + max_queue=1: one decoding, one queued, the third submit
    raises QueueFull with a retry hint."""
    eng = ServeEngine(model, slots=1, max_queue=1, ctx_len=CTX)
    try:
        r_busy = eng.submit(P_LONG, max_new_tokens=180, sampling=GREEDY)
        while not r_busy.tokens:
            time.sleep(0.005)
        r_queued = eng.submit(P_A, max_new_tokens=4, sampling=GREEDY)
        with pytest.raises(QueueFull) as ei:
            eng.submit(P_B, max_new_tokens=4, sampling=GREEDY)
        assert ei.value.retry_after_s >= 1
        r_busy.cancel()
        assert r_queued.wait(120)         # queued one still served
        assert r_queued.result["tokens"] == _ref(model, P_A, 4)
    finally:
        eng.close()


def test_engine_burst_fills_idle_slots_without_429(model):
    """A burst of slots+queue submissions against an IDLE pool is fully
    admitted: the bound counts requests waiting beyond free slots, so
    arrivals outpacing the one-admission-per-iteration drain don't shed
    load while capacity sits idle (found by driving the live server)."""
    eng = ServeEngine(model, slots=4, max_queue=1, ctx_len=CTX)
    try:
        rs = [eng.submit(P_A, max_new_tokens=6, sampling=GREEDY)
              for _ in range(5)]               # 4 slots + 1 queued: all in
        assert all(r.wait(120) for r in rs)
        ref = _ref(model, P_A, 6)
        assert all(r.result["tokens"] == ref for r in rs)
    finally:
        eng.close()


def test_engine_cancelled_queued_purged(model):
    """A request abandoned while QUEUED stops pinning queue capacity at
    the next iteration — live clients are not 429ed behind ghosts."""
    eng = ServeEngine(model, slots=1, max_queue=1, ctx_len=CTX)
    try:
        r_busy = eng.submit(P_LONG, max_new_tokens=180, sampling=GREEDY)
        while not r_busy.tokens:
            time.sleep(0.005)
        r_ghost = eng.submit(P_A, max_new_tokens=4, sampling=GREEDY)
        r_ghost.cancel()                  # client vanished while waiting
        deadline = time.monotonic() + 10
        while eng.queue.depth() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.queue.depth() == 0
        assert r_ghost.done.is_set()
        # capacity is back: a live client gets in instead of a 429
        r_live = eng.submit(P_B, max_new_tokens=4, sampling=GREEDY)
        r_busy.cancel()
        assert r_live.wait(120)
        assert r_live.result["tokens"] == _ref(model, P_B, 4)
    finally:
        eng.close()


def test_engine_rejects_oversize_prompt(model, engine):
    with pytest.raises(ValueError):
        engine.submit(list(range(CTX)), max_new_tokens=4, sampling=GREEDY)


# ---------------------------------------------------------------------------
# chunked prefill (ISSUE 3 tentpole)
# ---------------------------------------------------------------------------

P_CHUNKY = [3 + (i * 7) % 200 for i in range(50)]


def test_prefill_chunk_matches_monolithic_logits(model):
    """A prompt prefilled chunk-by-chunk straight into a pool row matches
    the monolithic bucketed prefill's last-position logits to within one
    ulp (chunk matmuls have a different width, so the last bit can round
    differently; the greedy ARGMAX — what decode consumes — is pinned
    exact, and the engine-level test below pins the full token stream),
    and touches no other row."""
    from cake_tpu.models.common.text_model import bucket_for
    n, chunk = len(P_CHUNKY), 16
    c1 = model.new_cache(1, kv_len=bucket_for(n, CTX))
    ref_logits, _ = model.prefill(c1, P_CHUNKY)
    layers = model.new_cache(3, kv_len=64)["layers"]
    for s in range(0, n, chunk):
        logits, layers = model.prefill_chunk(
            layers, 1, P_CHUNKY[s:s + chunk], s)
    a, b = np.asarray(logits), np.asarray(ref_logits)
    np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    assert a.argmax() == b.argmax()
    # a chunk whose bucket equals the monolithic bucket IS bit-identical
    layers1 = model.new_cache(3, kv_len=64)["layers"]
    one_shot, layers1 = model.prefill_chunk(layers1, 1, P_CHUNKY, 0)
    np.testing.assert_array_equal(np.asarray(one_shot), b)
    for lc in layers:
        np.testing.assert_array_equal(np.asarray(lc["pos"][1, :n]),
                                      np.arange(n))
        assert int(jnp.max(lc["pos"][1, n:])) == -1
        assert float(jnp.abs(lc["k"][0]).max()) == 0.0   # neighbors clean
        assert float(jnp.abs(lc["k"][2]).max()) == 0.0


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_engine_chunked_long_prompt_parity(model):
    """Greedy output with a multi-chunk admission is bit-identical to the
    sequential (monolithic-prefill) path — the tentpole acceptance pin on
    the MISS side."""
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=0)
    try:
        r = eng.submit(P_CHUNKY, max_new_tokens=10, sampling=GREEDY)
        assert r.wait(120)
        assert r.result["tokens"] == _ref(model, P_CHUNKY, 10)
        assert r.stats["prefill_chunks"] == 4            # ceil(50 / 16)
        assert r.stats["prefix_hit_tokens"] == 0
    finally:
        eng.close()


def test_engine_decode_not_stalled_by_long_admission(model):
    """The head-of-line-blocking kill: while a LONG prompt is admitted
    chunk-by-chunk, an already-active request keeps emitting tokens — one
    decode step per chunk iteration — instead of stalling for the whole
    prefill as the monolithic path did. Pinned on token ORDER (tokens
    gained before the long request's first token), not wall time."""
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=0)
    try:
        r_short = eng.submit(P_A, max_new_tokens=200, sampling=GREEDY)
        while len(r_short.tokens) < 3:          # active and decoding
            time.sleep(0.005)
        long_prompt = [3 + (i * 13) % 200 for i in range(120)]  # 8 chunks
        gained_at_submit = len(r_short.tokens)
        r_long = eng.submit(long_prompt, max_new_tokens=6, sampling=GREEDY)
        deadline = time.monotonic() + 60
        while not r_long.tokens and time.monotonic() < deadline:
            time.sleep(0.002)
        assert r_long.tokens, r_long.result.get("error")
        gained = len(r_short.tokens) - gained_at_submit
        assert gained >= 4, \
            f"short request gained only {gained} tokens across an 8-chunk " \
            "admission — decode stalled behind the prefill"
        r_short.cancel()
        assert r_long.wait(120)
        assert r_long.result["tokens"] == _ref(model, long_prompt, 6)
    finally:
        eng.close()


def test_engine_round_robin_concurrent_admissions(model):
    """Admission fairness: two long prompts prefill CONCURRENTLY (both in
    flight at once, chunks round-robined) instead of the second waiting
    for the first's entire prefill; both reproduce the sequential path."""
    p1 = [3 + (i * 5) % 200 for i in range(100)]    # 7 chunks each
    p2 = [3 + (i * 9) % 200 for i in range(100)]
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=0)
    try:
        r1 = eng.submit(p1, max_new_tokens=5, sampling=GREEDY)
        r2 = eng.submit(p2, max_new_tokens=5, sampling=GREEDY)
        saw_both = False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if eng.health()["prefilling"] == 2:
                saw_both = True
                break
            if r1.done.is_set() and r2.done.is_set():
                break
            time.sleep(0.001)
        assert r1.wait(120) and r2.wait(120)
        # the poll above can sleep through the dozen iterations that hold
        # both on a loaded machine; the flight records saw every one
        saw_both = saw_both or any(r["prefilling"] == 2
                                   for r in eng.flight.snapshot())
        assert saw_both, "second admission waited out the first's prefill"
        assert r1.result["tokens"] == _ref(model, p1, 5)
        assert r2.result["tokens"] == _ref(model, p2, 5)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# e2e through the aiohttp API
# ---------------------------------------------------------------------------


def _api_state(model, engine):
    from cake_tpu.api import ApiState
    st = ApiState(model=model, tokenizer=model.tokenizer,
                  model_id="tiny-serve")
    st.engine = engine
    return st


def _run(coro):
    asyncio.new_event_loop().run_until_complete(coro)


def test_api_concurrent_chat_parity(model, engine):
    """3 concurrent API chats through the engine: all 200, greedy text
    identical to the sequential reference, shorts finish before the long
    one (wall-clock interleaving at the HTTP layer)."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app
    from cake_tpu.models.common.text_model import chat_prompt_ids

    msgs = [[{"role": "user", "content": f"hello world {i}"}]
            for i in range(3)]
    # wide long-vs-short margin (~76 decode iterations): the assertion
    # below compares HTTP completion ORDER, and on a loaded single-core
    # box the event loop can lag the engine by ~100ms of GIL starvation
    budgets = [80, 4, 4]
    refs = []
    for mm, n in zip(msgs, budgets):
        ids = chat_prompt_ids(model.tokenizer, mm)
        toks = _ref(model, ids, n)
        ended = model.cfg.is_eos(toks[-1])
        refs.append(model.tokenizer.decode(toks[:-1] if ended else toks))

    done_at = {}

    async def scenario():
        app = create_app(_api_state(model, engine))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            async def one(i):
                r = await client.post("/v1/chat/completions", json={
                    "messages": msgs[i], "max_tokens": budgets[i],
                    "temperature": 0.0})
                assert r.status == 200, await r.text()
                done_at[i] = time.monotonic()
                return await r.json()
            # long request first so it is admitted before the shorts
            t_long = asyncio.ensure_future(one(0))
            await asyncio.sleep(0.05)
            d1, d2 = await asyncio.gather(one(1), one(2))
            d0 = await t_long
            for i, d in enumerate((d0, d1, d2)):
                assert d["choices"][0]["message"]["content"] == refs[i], i
                assert d["usage"]["completion_tokens"] >= 1
            assert done_at[1] < done_at[0] and done_at[2] < done_at[0], \
                "short chats must complete while the long one decodes"
        finally:
            await client.close()
    _run(scenario())


def test_api_stream_engine_path(model, engine):
    """SSE through the engine: chunked content equals the blocking text,
    stream terminates with finish_reason + [DONE]."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app
    from cake_tpu.models.common.text_model import chat_prompt_ids

    msg = [{"role": "user", "content": "stream me"}]
    ids = chat_prompt_ids(model.tokenizer, msg)
    toks = _ref(model, ids, 8)
    ended = model.cfg.is_eos(toks[-1])
    want = model.tokenizer.decode(toks[:-1] if ended else toks)

    async def scenario():
        app = create_app(_api_state(model, engine))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/v1/chat/completions", json={
                "messages": msg, "max_tokens": 8, "temperature": 0.0,
                "stream": True})
            assert r.status == 200
            body = (await r.read()).decode()
            chunks = [json.loads(line[6:]) for line in body.split("\n\n")
                      if line.startswith("data: ") and line != "data: [DONE]"]
            text = "".join(c["choices"][0]["delta"].get("content", "")
                           for c in chunks)
            assert text == want
            assert chunks[-1]["choices"][0]["finish_reason"] in ("stop",
                                                                 "length")
            assert body.strip().endswith("data: [DONE]")
        finally:
            await client.close()
    _run(scenario())


def test_api_backpressure_429(model):
    """Queue saturation answers 429 + Retry-After instead of waiting."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app

    eng = ServeEngine(model, slots=1, max_queue=1, ctx_len=CTX)
    try:
        r_busy = eng.submit(P_LONG, max_new_tokens=180, sampling=GREEDY)
        while not r_busy.tokens:
            time.sleep(0.005)
        r_queued = eng.submit(P_A, max_new_tokens=4, sampling=GREEDY)

        async def scenario():
            app = create_app(_api_state(model, eng))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.post("/v1/chat/completions", json={
                    "messages": [{"role": "user", "content": "x"}]})
                assert r.status == 429
                assert int(r.headers["Retry-After"]) >= 1
                assert "overloaded" in (await r.json())["error"]
            finally:
                await client.close()
        _run(scenario())
        r_busy.cancel()
        assert r_queued.wait(120)
    finally:
        eng.close()


def test_api_disconnect_mid_stream_frees_slot(model, engine):
    """Closing the SSE connection mid-generation cancels the request and
    the engine's busy gauge returns to 0 (the acceptance assertion)."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app
    from cake_tpu.obs import SERVE_SLOTS_BUSY

    async def scenario():
        app = create_app(_api_state(model, engine))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "disconnect"}],
                "max_tokens": 200, "temperature": 0.0, "stream": True})
            assert r.status == 200
            await r.content.read(64)          # a few chunks, then vanish
            deadline = time.monotonic() + 10  # poll past the admission race
            while SERVE_SLOTS_BUSY.value() < 1 and time.monotonic() < deadline:
                await asyncio.sleep(0.005)
            assert SERVE_SLOTS_BUSY.value() >= 1
            r.close()                          # client disconnect
        finally:
            await client.close()
        deadline = time.monotonic() + 15
        while SERVE_SLOTS_BUSY.value() != 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        assert SERVE_SLOTS_BUSY.value() == 0
    _run(scenario())


def test_api_health_and_metrics_engine(model, engine):
    """/health exposes engine liveness; /metrics carries the serve series
    after traffic."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app

    async def scenario():
        app = create_app(_api_state(model, engine))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
            r = await client.get("/health")
            assert r.status == 200
            h = await r.json()
            assert h["engine"]["alive"] is True
            assert h["engine"]["slots"] == 4
            assert h["engine"]["last_step_age_s"] < 30
            r = await client.get("/metrics")
            text = await r.text()
            assert "cake_serve_slots_busy" in text
            assert "cake_serve_queue_wait_seconds_count" in text
            assert "cake_serve_batch_occupancy_count" in text
        finally:
            await client.close()
    _run(scenario())


def test_stream_leak_fix_cancel_event():
    """Legacy locked path: abandoning the stream iterator stops the
    generation worker (no executor thread parked on q.get forever, no
    decode-to-budget after disconnect)."""
    from cake_tpu.api.state import run_generation_streamed
    from cake_tpu.models.common.text_model import Token

    produced = []
    release = threading.Event()

    class SlowModel:
        def chat_generate(self, messages, on_token=None, **kw):
            for i in range(500):
                release.wait(0.002)
                on_token(Token(id=i, text=f"t{i}", is_end_of_stream=False))
                produced.append(i)
            return list(range(500)), {}

    async def scenario():
        aiter, result, cancel = run_generation_streamed(
            SlowModel(), [{"role": "user", "content": "x"}], {})
        seen = 0
        async for tok in aiter:
            seen += 1
            if seen >= 3:
                break                     # client walks away mid-stream
        await aiter.aclose()              # finalizer must cancel the worker
        assert cancel.is_set()
        return seen
    asyncio.new_event_loop().run_until_complete(scenario())
    n_at_close = len(produced)
    time.sleep(0.3)
    assert len(produced) <= n_at_close + 2, "worker kept generating"
    assert len(produced) < 500


# ---------------------------------------------------------------------------
# graceful drain + per-request queue deadline (fault-tolerance satellites)
# ---------------------------------------------------------------------------


def test_engine_queue_deadline_expires_waiters(model):
    """slots=1: a request stuck in the admission queue past
    CAKE_QUEUE_DEADLINE_S is failed with QueueDeadlineExceeded (503 at
    the API layer) instead of eventually occupying a slot for a client
    that already gave up; the busy request is unaffected and the timeout
    counter ticks."""
    from cake_tpu.obs import SERVE_QUEUE_TIMEOUTS
    from cake_tpu.serve import QueueDeadlineExceeded

    eng = ServeEngine(model, slots=1, max_queue=4, ctx_len=CTX,
                      queue_deadline_s=5.0)
    try:
        before = SERVE_QUEUE_TIMEOUTS.value()
        r_busy = eng.submit(P_LONG, max_new_tokens=180, sampling=GREEDY)
        while not r_busy.tokens:
            time.sleep(0.005)
        r_queued = eng.submit(P_A, max_new_tokens=4, sampling=GREEDY)
        # backdate the enqueue stamp rather than really sleeping out the
        # deadline: deterministic regardless of how fast the busy slot
        # decodes (the sweep must expire it at the next iteration)
        r_queued.t_enqueue -= 60.0
        assert r_queued.wait(30), "expired request never finished"
        err = r_queued.result.get("error")
        assert isinstance(err, QueueDeadlineExceeded), err
        assert err.waited_s >= 5.0
        assert SERVE_QUEUE_TIMEOUTS.value() == before + 1
        # the slot owner decodes on unharmed
        r_busy.cancel()
        assert r_busy.wait(120)
    finally:
        eng.close()


def test_engine_drain_stops_admission_and_finishes_active(model):
    """drain(): new submits are shed with EngineDraining while the active
    request runs to its normal completion; drain returns True once idle
    and health() reports draining."""
    from cake_tpu.serve import EngineDraining

    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX)
    try:
        r = eng.submit(P_A, max_new_tokens=6, sampling=GREEDY)
        while not r.tokens:
            time.sleep(0.005)
        done = {}

        def do_drain():
            done["clean"] = eng.drain(timeout=120)
        t = threading.Thread(target=do_drain, daemon=True)
        t.start()
        while not eng.health()["draining"]:
            time.sleep(0.005)
        with pytest.raises(EngineDraining) as ei:
            eng.submit(P_B, max_new_tokens=4, sampling=GREEDY)
        assert ei.value.retry_after_s >= 1
        t.join(timeout=120)
        assert done.get("clean") is True
        assert r.wait(10)           # drain observes idle a hair before the
                                    # finisher fires done — wait, don't poll
        assert r.result["tokens"] == _ref(model, P_A, 6)  # finished, not cut
    finally:
        eng.close()


def test_api_graceful_drain_on_shutdown(model):
    """The serve() entry registers graceful_drain on_shutdown: while
    draining, chat requests answer 503 + Retry-After; at shutdown the
    active work finishes and the engine is closed — Ctrl-C mid-decode no
    longer abandons in-flight requests without final chunks."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app
    from cake_tpu.api.server import graceful_drain

    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX)
    state = _api_state(model, eng)
    app = create_app(state)
    app.on_shutdown.append(graceful_drain)   # what serve() wires up

    async def scenario():
        client = TestClient(TestServer(app))
        await client.start_server()
        r = await client.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hi there"}],
            "max_tokens": 4, "temperature": 0.0})
        assert r.status == 200

        # draining: requests on kept-alive connections are shed
        state.draining = True
        r2 = await client.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "late"}],
            "max_tokens": 4, "temperature": 0.0})
        assert r2.status == 503
        assert int(r2.headers.get("Retry-After", "0")) >= 1
        state.draining = False

        await client.close()                 # shutdown -> graceful_drain
    _run(scenario())

    assert state.draining is True            # drain ran at shutdown
    assert not eng._thread.is_alive()        # engine closed cleanly
    with pytest.raises(RuntimeError):
        eng.submit(P_A, max_new_tokens=2, sampling=GREEDY)


def test_graceful_drain_flips_health_before_engine_drains(model):
    """graceful_drain flips the engine's draining flag SYNCHRONOUSLY —
    /health's engine block says draining while in-flight work is still
    finishing, so a fleet router probing it stops routing here before
    the first request bounces (ISSUE 12 satellite: the router could not
    previously distinguish draining from healthy until 503s flew)."""
    from cake_tpu.api import create_app
    from cake_tpu.api.server import graceful_drain
    from cake_tpu.serve import EngineDraining, faults

    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX)
    state = _api_state(model, eng)
    app = create_app(state)

    async def scenario():
        # keep the engine busy so the drain cannot finish instantly —
        # the assertion below must observe draining=True mid-drain
        faults.install("delay_ms=20")
        busy = eng.submit(P_LONG, max_new_tokens=60, sampling=GREEDY)
        while not busy.tokens:
            await asyncio.sleep(0.005)
        drain_task = asyncio.ensure_future(graceful_drain(app))
        try:
            deadline = time.monotonic() + 5
            while not eng.health()["draining"]:
                assert time.monotonic() < deadline, \
                    "engine block never reported draining"
                await asyncio.sleep(0.002)
            assert not drain_task.done()      # flag flipped mid-drain
            assert eng.pool.busy_count        # work still in flight
            # new submits are refused with a DERIVED Retry-After hint
            with pytest.raises(EngineDraining) as ei:
                eng.submit(P_A, max_new_tokens=2, sampling=GREEDY)
            assert ei.value.retry_after_s >= 1
        finally:
            faults.clear()
            busy.cancel()
            await drain_task
    _run(scenario())
    eng.close()


def test_retry_after_hint_scales_with_backlog(model):
    """Derived Retry-After (ISSUE 12 satellite): idle engine invites a
    near-immediate retry; a deep queue pushes clients out
    proportionally."""
    eng = ServeEngine(model, slots=2, max_queue=64, ctx_len=CTX)
    try:
        assert eng.retry_after_hint() == 1           # idle
        from cake_tpu.serve import faults
        faults.install("delay_ms=50")
        try:
            reqs = [eng.submit(P_A, max_new_tokens=4, sampling=GREEDY)
                    for _ in range(20)]
            deep = eng.retry_after_hint()
            assert deep > 1                           # backlog-derived
            assert deep <= 30                         # capped
            for r in reqs:
                r.cancel()
        finally:
            faults.clear()
    finally:
        eng.close()


def test_api_stream_queue_deadline_503(model):
    """A stream:true request shed by the queue deadline answers 503 +
    Retry-After BEFORE any SSE commits to a 200 — the same contract as
    the blocking path, so balancers see the shed-load signal."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app
    from cake_tpu.serve import faults

    eng = ServeEngine(model, slots=1, max_queue=4, ctx_len=CTX,
                      queue_deadline_s=0.1)
    state = _api_state(model, eng)

    async def scenario():
        app = create_app(state)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # occupy the single slot with a long decode. delay_ms paces
            # it deterministically: the ctx cap bounds the busy request
            # at ~122 decode steps, which a WARM executable finishes in
            # under the 0.1s deadline — the queued request then got
            # ADMITTED instead of shed (the in-suite flake this pacing
            # fixes); at 5 ms/iteration the slot is held for >0.5s no
            # matter how warm the cache is
            r_busy = eng.submit(P_LONG, max_new_tokens=180, sampling=GREEDY)
            while not r_busy.tokens:
                await asyncio.sleep(0.005)
            faults.install("delay_ms=5")
            # ...then a streaming request that must expire while queued
            resp = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "will expire"}],
                "max_tokens": 4, "temperature": 0.0, "stream": True})
            assert resp.status == 503, await resp.text()
            assert int(resp.headers.get("Retry-After", "0")) >= 1
            r_busy.cancel()
        finally:
            faults.clear()
            await client.close()
    _run(scenario())
    eng.close()


def test_engine_continuation_splice_bit_identical(model, engine):
    """The mid-stream resume contract at the engine level: prefilling
    prompt + the first k generated tokens (a continuation splice) and
    decoding the remainder reproduces the unbroken greedy run
    bit-for-bit — and the continuation flag rides the stats."""
    full = engine.submit(P_LONG, max_new_tokens=10, sampling=GREEDY)
    assert full.wait(120)
    toks = full.result["tokens"]
    assert toks == _ref(model, P_LONG, 10)
    k = 4
    resumed = engine.submit(P_LONG + toks[:k], max_new_tokens=10 - k,
                            sampling=GREEDY, continuation=True)
    assert resumed.wait(120)
    assert resumed.result["tokens"] == toks[k:]
    assert resumed.result["stats"].get("continuation") is True


# ---------------------------------------------------------------------------
# the lagged fetch (ISSUE 37): a decode step is dispatched before the step
# before it is fetched, so fan-out goes by the record taken at dispatch
# ---------------------------------------------------------------------------

class _Abstains:
    """A drafter that never proposes: the engine sees a drafter and runs
    at depth 0 (every step fetched before the next is planned), on the
    plain decode program — the schedule this engine had before the lag."""

    name, shareable = "abstains", True

    def propose(self, ids, k):
        return []

    def reset(self):
        pass


def _settle(eng, timeout=60.0):
    """Wait until the scheduler has nothing busy, queued or in flight and
    the iteration that saw so has written its record; returns the ring."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        ring = eng.flight.snapshot()
        if eng._inflight is None and not eng.pool.busy_count \
                and not eng.queue.depth() and ring \
                and not ring[-1]["occupancy"]:
            return ring
        time.sleep(0.005)
    raise AssertionError("engine did not go idle")


def _delivered(req):
    """Subscribe to a request's stream: the token ids delivered so far
    and to come, in order (DONE excluded)."""
    got = []

    def on_item(item):
        if item is not req.DONE:
            got.append(item.id)

    for item in req.subscribe(on_item):
        on_item(item)
    return got


_STREAMS = ((P_LONG, 12), (P_A, 3), (P_B, 9), (P_A + P_B, 5), (P_B[::-1], 7))


@pytest.mark.parametrize("scfg", [
    GREEDY,
    SamplingConfig(temperature=0.9, top_k=40, top_p=0.95,
                   repeat_penalty=1.1)], ids=["greedy", "sampled"])
def test_lagged_streams_equal_the_unlagged_schedule(model, scfg):
    """Five requests through two slots (so slots free and are taken again
    mid-run): every stream is the same function of (seed, prompt,
    sampling, admission order) whether each step's ids are fetched one
    iteration late or before the next step is planned — the lag changes
    when the host learns a token, never which token it is."""
    runs = {}
    for name, spec in (("lagged", False), ("unlagged", _Abstains())):
        # a seed under which no sampled stream draws the end-of-sequence id
        # before its length
        eng = ServeEngine(model, slots=2, max_queue=8, ctx_len=CTX, seed=3,
                          spec=spec)
        try:
            reqs = [eng.submit(p, max_new_tokens=n, sampling=scfg)
                    for p, n in _STREAMS]
            for r in reqs:
                assert r.wait(300) and "error" not in r.result
            runs[name] = ([r.result["tokens"] for r in reqs],
                          _settle(eng) if not spec else eng.flight.snapshot())
        finally:
            eng.close()
    (lagged, ring), (unlagged, ring0) = runs["lagged"], runs["unlagged"]
    assert lagged == unlagged
    assert [len(t) for t in lagged] == [n for _, n in _STREAMS]
    if scfg is GREEDY:
        assert lagged == [_ref(model, p, n) for p, n in _STREAMS]
    else:
        assert lagged != [_ref(model, p, n) for p, n in _STREAMS]
    decoded = [r for r in ring if r["occupancy"]]
    assert sum(r["lag"] for r in decoded) >= len(decoded) - 2
    assert sum(r["dropped"] for r in ring) == len(_STREAMS)
    assert all(r["lag"] == 0 and r["dropped"] == 0 for r in ring0)


@pytest.mark.parametrize("ending", ["max_tokens", "eos", "cancel"])
def test_request_ending_with_a_step_in_flight_gets_exactly_its_tokens(
        model, monkeypatch, ending):
    """The host learns that a stream ended one step late: the row has run
    one more step by then. That step's id is dropped and counted, never
    delivered; what was delivered is the stream, to the token."""
    import dataclasses
    n = 9
    ref = _ref(model, P_LONG, n)
    if ending == "eos":
        # the 6th token becomes the stop token
        eos = ref[5]
        assert eos not in ref[:5]
        monkeypatch.setattr(model, "cfg", dataclasses.replace(
            model.cfg, eos_token_ids=(eos,)))
        want = ref[:6]
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX)
    try:
        req = eng.submit(P_LONG, max_new_tokens=200 if ending == "cancel"
                         else n, sampling=GREEDY)
        got = _delivered(req)
        if ending == "cancel":
            while len(req.tokens) < 4:
                time.sleep(0.002)
            req.cancel()
        assert req.wait(120) and "error" not in req.result
        ring = _settle(eng)
    finally:
        eng.close()
    if ending == "cancel":
        long_ref = _ref(model, P_LONG, len(req.tokens))
        assert 4 <= len(got) <= len(req.tokens) < 200
        assert req.tokens == long_ref and got == long_ref[:len(got)]
    else:
        want = ref if ending == "max_tokens" else want
        assert req.result["tokens"] == want == got
    assert sum(r["dropped"] for r in ring) == 1
    assert ring[-1]["dropped"] == 1 and ring[-1]["occupancy"] == 0


def test_readmitted_slot_never_gets_the_old_tenants_id(model):
    """One slot, two requests: the second takes the slot in the very
    iteration that fans out the first one's last, overshot step. That id
    belongs to nobody; the new tenant's stream starts with its own first
    token and is its own to the end."""
    eng = ServeEngine(model, slots=1, max_queue=4, ctx_len=CTX)
    try:
        first = eng.submit(P_A, max_new_tokens=5, sampling=GREEDY)
        second = eng.submit(P_B, max_new_tokens=6, sampling=GREEDY)
        got = [_delivered(first), _delivered(second)]
        assert first.wait(120) and second.wait(120)
        ring = _settle(eng)
    finally:
        eng.close()
    assert first.result["tokens"] == got[0] == _ref(model, P_A, 5)
    assert second.result["tokens"] == got[1] == _ref(model, P_B, 6)
    assert second.result["stats"]["ttft_s"] > 0
    # the overshot step of `first` was fanned out with `second` in its slot
    drops = [r for r in ring if r["dropped"]]
    assert [r["dropped"] for r in drops] == [1, 1]
    # the first: `second` held the slot, still prefilling, so no step went
    assert drops[0]["occupancy"] == 0 and drops[0]["queued"] == 0
    assert drops[0]["seq"] < drops[1]["seq"] == ring[-1]["seq"]


def test_engine_goes_idle_with_nothing_in_flight(model):
    """The iteration that finds nothing to dispatch fetches the step in
    flight (`lag` 0: no program of its own is queued behind the fetch)
    and only then does the scheduler wait for work."""
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX)
    try:
        req = eng.submit(P_LONG, max_new_tokens=6, sampling=GREEDY)
        assert req.wait(120)
        ring = _settle(eng)
        last = ring[-1]
        assert last["occupancy"] == 0 and last["lag"] == 0
        assert last["fetch_ms"] > 0 and last["dropped"] == 1
        assert ring[-2]["occupancy"] == 1 and ring[-2]["lag"] == 1
        # the first step after idle has nothing to fetch
        first = next(r for r in ring if r["occupancy"])
        assert first["lag"] == 0 and first["fetch_ms"] == 0
        # (`steps` moves after `_step` has written its record: let the
        # last iteration's count land before reading it)
        time.sleep(0.05)
        steps = eng.steps
        time.sleep(0.1)
        assert eng.steps == steps and eng._inflight is None
        assert eng.flight.snapshot()[-1]["seq"] == last["seq"]
        # and it wakes up again
        again = eng.submit(P_A, max_new_tokens=3, sampling=GREEDY)
        assert again.wait(120)
        assert again.result["tokens"] == _ref(model, P_A, 3)
    finally:
        eng.close()


def test_drafter_engine_fetches_every_step_before_the_next_plan(model):
    """An n-gram drafter proposes from the tokens the host holds: its
    engine fetches each step in the iteration that dispatched it (`lag` 0
    throughout, nothing ever dropped or left in flight)."""
    prompt = [5, 9, 5, 9, 5, 9, 5, 9, 5, 9, 5]
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                      spec="ngram", spec_k=4)
    try:
        reqs = [eng.submit(p, max_new_tokens=n, sampling=GREEDY)
                for p, n in ((prompt, 16), (P_A, 5))]
        for r in reqs:
            assert r.wait(300) and "error" not in r.result
        ring = eng.flight.snapshot()
        assert eng._inflight is None
    finally:
        eng.close()
    assert reqs[0].result["tokens"] == _ref(model, prompt, 16)
    assert reqs[1].result["tokens"] == _ref(model, P_A, 5)
    assert any(r["occupancy"] for r in ring)
    assert all(r["lag"] == 0 and r["dropped"] == 0 for r in ring)
    assert all(r["fetch_ms"] > 0 for r in ring if r["occupancy"])
