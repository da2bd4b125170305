"""Batched speculative decoding in the serve engine (ISSUE 11): ragged
multi-token verify over the pool's rows, paged block-cursor
advance, drafter-free n-gram mode.

The invariants pinned here:
  * batched-spec greedy output is BIT-IDENTICAL to the plain engine /
    sequential path — llama (attention-only, truncate rollback) AND
    qwen3_5/GDN (linear state, valid_len-masked commit), contiguous AND
    paged KV layouts (speculation no longer stands down in paged mode);
  * ragged acceptance (one slot accepting, a neighbor abstaining or
    rejecting, in the same dispatch) compiles NOTHING in steady state —
    one executable per k on contiguous rows ((slot-bucket, k) paged);
  * rejection rollback survives preempt-by-swap: a swapped-out victim
    carries only committed KV (uncommitted speculative blocks are
    trimmed back to the pool) and resumes bit-identically;
  * sampled streams keep rng-rebase correctness on rejection: the rng
    carry advances exactly once per verify step regardless of the
    accepted length, so identical runs replay identical streams;
  * every occupancy of a contiguous pool runs ONE decode program.

Pool shapes match tests/test_paged.py (12 x 8-token blocks, chunk 16,
ctx 128) so paged executables stay cheap on the timeout-capped tier-1
suite.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine
from cake_tpu.serve.slots import slot_bucket, slot_buckets

GREEDY = SamplingConfig(temperature=0.0)
CTX = 128
CHUNK = 16
BT = 8
BLOCKS = 12

# period-4 repetition: the n-gram drafter proposes real multi-token
# continuations, so ragged accepts actually exercise the rollback
REP = [5, 9, 17, 23] * 4 + [5, 9]
# all-distinct: the drafter abstains -> plain decode inside the same
# spec dispatch (the ragged no-draft slot)
P_B = [100, 2, 5, 9, 11, 40]


@pytest.fixture(scope="module")
def model():
    return TextModel(tiny_config("llama"), dtype=jnp.float32,
                     max_cache_len=CTX)


@pytest.fixture(scope="module")
def gdn_model():
    return TextModel(tiny_config("qwen3_5"), dtype=jnp.float32,
                     max_cache_len=CTX)


def _engine(model, paged: bool, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("ctx_len", CTX)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("prefix_cache_mb", 0)
    kw.setdefault("spec", "ngram")
    kw.setdefault("spec_k", 4)
    if paged:
        kw.setdefault("kv_blocks", BLOCKS)
        kw.setdefault("kv_block_tokens", BT)
    return ServeEngine(model, **kw)


def _ref(model, prompt, n, sampling=GREEDY):
    toks, _ = model.generate(list(prompt), max_new_tokens=n,
                             sampling=sampling, spec=False)
    return toks


# ---------------------------------------------------------------------------
# greedy bit-parity: llama + GDN, contiguous + paged
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "paged",
    [pytest.param(False, marks=pytest.mark.slow), pytest.param(True, marks=pytest.mark.slow)],  # tier-2 spec smokes cover llama; gdn[contig] is the tier-1 representative (870s cap)
    ids=["contig", "paged"],
)
def test_batched_spec_greedy_parity_llama(model, paged):
    """Concurrent greedy requests through the batched-spec engine —
    one slot with live drafts, one whose drafter abstains — reproduce
    the plain sequential path bit-for-bit, and multi-token accepts
    actually happened (the llama tiny model's greedy continuation of
    the repetitive prompt is n-gram-predictable)."""
    eng = _engine(model, paged)
    try:
        ra = eng.submit(REP, max_new_tokens=24, sampling=GREEDY)
        rb = eng.submit(P_B, max_new_tokens=10, sampling=GREEDY)
        assert ra.wait(600) and rb.wait(600)
        assert "error" not in ra.result, ra.result.get("error")
        assert "error" not in rb.result, rb.result.get("error")
        assert ra.tokens == _ref(model, REP, 24)
        assert rb.tokens == _ref(model, P_B, 10)
        h = eng.health()["spec"]
        assert h["accepted"] >= 1
        assert h["steps"] < len(ra.tokens) - 1   # >= 1 multi-token accept
        if paged:
            eng.paged.alloc.check()
    finally:
        eng.close()


@pytest.mark.parametrize(
    "paged",
    [False, pytest.param(True, marks=pytest.mark.slow)],  # tier-1 keeps one family per KV layout (llama covers paged)
    ids=["contig", "paged"],
)
def test_batched_spec_greedy_parity_gdn(gdn_model, paged):
    """GDN hybrid (linear + full attention): the rejected-suffix
    rollback is the valid_len-masked state commit, per slot inside the
    vmapped verify — greedy output stays bit-identical in both KV
    layouts (paged mode pages only the full-attention layer)."""
    eng = _engine(gdn_model, paged)
    try:
        ra = eng.submit(REP, max_new_tokens=14, sampling=GREEDY)
        rb = eng.submit(P_B, max_new_tokens=8, sampling=GREEDY)
        assert ra.wait(600) and rb.wait(600)
        assert "error" not in ra.result, ra.result.get("error")
        assert "error" not in rb.result, rb.result.get("error")
        assert ra.tokens == _ref(gdn_model, REP, 14)
        assert rb.tokens == _ref(gdn_model, P_B, 8)
        assert eng.health()["spec"]["steps"] >= 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# ragged acceptance: zero recompiles in steady state
# ---------------------------------------------------------------------------


def test_spec_steady_state_zero_recompiles(model):
    """>= 8 speculative verify steps with RAGGED per-slot acceptance
    (a drafting slot next to an abstaining one, accepts of every length,
    block-table-free contiguous advance) compile ZERO new executables:
    one program per (slot-bucket, k), nb the only static argument."""
    from cake_tpu.analysis.sanitizers import assert_no_recompiles
    eng = _engine(model, paged=False)
    try:
        # warm every executable the steady state touches: both slot
        # buckets, the spec program, the plain-decode program (all-
        # abstain iterations), prefill chunks and first-token sampling
        wa = eng.submit(REP, max_new_tokens=24, sampling=GREEDY)
        wb = eng.submit(P_B, max_new_tokens=10, sampling=GREEDY)
        assert wa.wait(600) and wb.wait(600)
        # ...including the all-abstain two-slot iteration (plain decode
        # at nb=2: both drafters empty -> the cheaper width-1 program)
        wc = eng.submit(P_B, max_new_tokens=8, sampling=GREEDY)
        wd = eng.submit(list(reversed(P_B)), max_new_tokens=8,
                        sampling=GREEDY)
        assert wc.wait(600) and wd.wait(600)
        before = eng.spec_steps
        with assert_no_recompiles(model._spec_slots, model._decode_slots,
                                  label="batched spec steady state"):
            ra = eng.submit(REP, max_new_tokens=24, sampling=GREEDY)
            ra2 = eng.submit(REP, max_new_tokens=24, sampling=GREEDY)
            rb = eng.submit(P_B, max_new_tokens=10, sampling=GREEDY)
            assert ra.wait(600) and ra2.wait(600) and rb.wait(600)
        assert ra.tokens == wa.tokens and ra2.tokens == wa.tokens
        assert rb.tokens == wb.tokens
        assert eng.spec_steps - before >= 8, \
            "not enough spec iterations to call it steady state"
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# rejection rollback under preempt-by-swap
# ---------------------------------------------------------------------------


def test_spec_rejection_rollback_under_preempt_swap(model):
    """Two speculating streams outgrow the 96-token pool: the victim is
    swapped out mid-speculation and must carry only COMMITTED state —
    its uncommitted draft-window blocks are rolled back to the pool
    before the blob is captured, every position stored in the blob sits
    below the committed frontier, and both continuations stay
    bit-identical to the sequential path."""
    from cake_tpu.obs import SERVE_PREEMPTIONS
    before = SERVE_PREEMPTIONS.value(mode="swap")
    ref_a = _ref(model, REP, 60)
    ref_b = _ref(model, P_B, 60)
    eng = _engine(model, paged=True, preempt_mode="swap", spec_k=6)
    blob_checks = []
    real_swap_out = eng.paged.swap_out

    def spying_swap_out(slot, carries):
        blob = real_swap_out(slot, carries)
        frontier = int(blob["carries"][1])      # pos carry == committed
        worst = max((int(saved["pos"].max()) for saved in blob["layers"]
                     if saved), default=-1)
        blob_checks.append((worst, frontier))
        return blob

    eng.paged.swap_out = spying_swap_out
    try:
        ra = eng.submit(REP, max_new_tokens=60, sampling=GREEDY)
        rb = eng.submit(P_B, max_new_tokens=60, sampling=GREEDY)
        assert ra.wait(600) and rb.wait(600)
        assert "error" not in ra.result, ra.result.get("error")
        assert "error" not in rb.result, rb.result.get("error")
        assert ra.tokens == ref_a
        assert rb.tokens == ref_b
        assert SERVE_PREEMPTIONS.value(mode="swap") > before, \
            "pool never exhausted - speculative preemption untested"
        assert blob_checks, "no swap blob captured"
        for worst, frontier in blob_checks:
            assert worst < frontier, \
                f"swap blob carries uncommitted position {worst} at " \
                f"committed frontier {frontier}"
        eng.paged.alloc.check()
    finally:
        eng.close()


def test_spec_degrades_to_decode_at_pool_edge(model):
    """A draft window that cannot be backed with blocks must DEGRADE to
    a plain decode step, not preempt a victim or fail the request: a
    single speculating stream pushed past the pool gets exactly as far
    as the non-speculating engine does (typed KVPoolExhausted only once
    the pool genuinely cannot grow), with no preemptions along the way."""
    from cake_tpu.obs import SERVE_PREEMPTIONS
    from cake_tpu.serve import KVPoolExhausted
    pre = {m: SERVE_PREEMPTIONS.value(mode=m)
           for m in ("swap", "recompute")}
    eng = _engine(model, paged=True, spec_k=6)
    try:
        r = eng.submit(REP, max_new_tokens=110, sampling=GREEDY)
        assert r.wait(600)
        assert isinstance(r.result.get("error"), KVPoolExhausted)
        # the 96-token pool minus the 18-token prompt leaves ~78 decode
        # steps: speculation must ride right up to the same edge
        assert len(r.tokens) > 70, len(r.tokens)
        for m, v in pre.items():
            assert SERVE_PREEMPTIONS.value(mode=m) == v, \
                "speculative over-reservation preempted a victim"
        # engine keeps serving
        r2 = eng.submit(P_B, max_new_tokens=6, sampling=GREEDY)
        assert r2.wait(180)
        assert r2.result["tokens"] == _ref(model, P_B, 6)
    finally:
        eng.close()


def test_paged_trim_to_rolls_back_tail(model):
    """trim_to unmaps exactly the table entries past the committed
    token count and returns them to the free pool (the speculative
    frontier rollback primitive)."""
    from cake_tpu.serve.paged import PagedKV
    pk = PagedKV.build(model, 2, CTX, 8, BT, CHUNK)
    assert pk.reserve_range(0, 0, 3 * BT + 2)       # blocks 0..3 mapped
    assert pk.alloc.free_count == 4
    # committed 10 tokens (2 blocks); blocks 2,3 are speculative tail
    assert pk.trim_to(0, 10) == 2
    assert pk.alloc.free_count == 6
    assert pk.alloc.tables[0][2] == pk.NULL
    assert pk.alloc.tables[0][3] == pk.NULL
    assert pk.alloc.tables[0][0] != pk.NULL         # committed kept
    assert int(np.asarray(pk.tables)[0, 2]) == pk.NULL  # device mirror
    assert pk.trim_to(0, 10) == 0                   # idempotent
    pk.alloc.check()


# ---------------------------------------------------------------------------
# sampled streams: rng-rebase correctness on rejection
# ---------------------------------------------------------------------------


def test_spec_sampled_rng_rebase_parity(model):
    """The rng carry advances exactly ONCE per verify step (one split)
    no matter how many drafts were accepted or rejected, so a sampled
    stream through the speculating engine is reproducible: two fresh
    engines with the same seed replay the identical token stream."""
    scfg = SamplingConfig(temperature=0.8, top_k=40)

    def run():
        eng = _engine(model, paged=False, spec_k=4, seed=7)
        try:
            r = eng.submit(REP, max_new_tokens=16, sampling=scfg)
            assert r.wait(600)
            assert "error" not in r.result, r.result.get("error")
            return list(r.tokens), eng.spec_steps
        finally:
            eng.close()

    a, steps_a = run()
    b, steps_b = run()
    assert a == b, "sampled spec stream is not reproducible"
    assert steps_a == steps_b
    assert len(a) <= 16


# ---------------------------------------------------------------------------
# slot-bucket growth: 8/16 slots, new-bucket-only compiles
# ---------------------------------------------------------------------------


def test_slot_buckets_ladder():
    assert slot_buckets(4) == (1, 2, 4)
    assert slot_buckets(8) == (1, 2, 4, 8)
    assert slot_buckets(16) == (1, 2, 4, 8, 16)
    assert slot_buckets(6) == (1, 2, 4, 6)      # cap itself always last
    for cap in (4, 8, 16):
        for n in range(1, cap + 1):
            assert slot_bucket(n, cap) in slot_buckets(cap)


def test_every_occupancy_runs_one_decode_program(model):
    """Occupancy 1 -> 8 -> every occupancy compiles ONE decode program
    and then nothing: the contiguous step runs on the whole pool in
    place under the active mask, so neither load nor CAKE_SERVE_SLOTS'
    ladder adds an executable."""
    from cake_tpu.analysis.sanitizers import (assert_no_recompiles,
                                              cache_size)
    base = cache_size(model._decode_slots)  # other pool shapes' programs
    eng = ServeEngine(model, slots=8, max_queue=16, ctx_len=CTX,
                      prefill_chunk=CHUNK, prefix_cache_mb=0)
    try:
        # a lone request: occupancy 1 compiles the pool's program
        r = eng.submit(P_B, max_new_tokens=6, sampling=GREEDY)
        assert r.wait(600)
        assert r.tokens == _ref(model, P_B, 6)
        assert cache_size(model._decode_slots) - base == 1
        # 8 concurrent requests climb through every occupancy to 8
        rs = [eng.submit(P_B, max_new_tokens=8, sampling=GREEDY)
              for _ in range(8)]
        assert all(r.wait(600) for r in rs)
        for r in rs:
            assert "error" not in r.result, r.result.get("error")
            assert r.tokens == _ref(model, P_B, 8)
        grown = cache_size(model._decode_slots) - base
        assert grown == 1, \
            f"occupancy growth compiled {grown} executables, expected " \
            "the one program of the 8-slot pool"
        # and re-running at every occupancy compiles nothing further
        with assert_no_recompiles(model._decode_slots,
                                  label="every occupancy, one program"):
            rs = [eng.submit(P_B, max_new_tokens=4, sampling=GREEDY)
                  for _ in range(8)]
            assert all(r.wait(600) for r in rs)
        buckets = {f["bucket"] for f in eng.flight.snapshot()
                   if f["occupancy"]}
        assert buckets == {8}       # the rows the dispatched program ran
    finally:
        eng.close()
