"""Speculative decoding tests (cake_tpu/spec/ + the traced pieces in
ops/sampling.spec_accept, TextModel's verify programs and the cache
truncate ops).

The two invariants everything else hangs off:
  * greedy speculation is BIT-IDENTICAL to plain decoding (pinned for
    llama — attention-only, truncate rollback — and qwen3_5/GDN — linear
    state, valid_len-masked commit rollback);
  * sampled speculation preserves the target distribution (acceptance
    rule checked against hand-computed probabilities, plus an empirical
    marginal-distribution test at a fixed seed).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.ops.sampling import SamplingConfig, filtered_probs, spec_accept
from cake_tpu.spec import DraftModelDrafter, NGramDrafter, resolve_drafter

GREEDY = SamplingConfig(temperature=0.0)
# period-4 repetition: the n-gram drafter finds the continuation, and the
# verify step has real multi-token accepts to exercise
REP_PROMPT = [5, 9, 17, 23] * 4 + [5, 9]
RAND_PROMPT = list(range(3, 43))          # all-distinct: no bigram repeats


@pytest.fixture(scope="module")
def llama():
    return TextModel(tiny_config("llama"), dtype=jnp.float32,
                     max_cache_len=128, seed=3)


@pytest.fixture(scope="module")
def gdn():
    return TextModel(tiny_config("qwen3_5"), dtype=jnp.float32,
                     max_cache_len=128, seed=3)


# -- n-gram drafter -----------------------------------------------------------


def test_ngram_proposes_on_repetitive_prompt():
    d = NGramDrafter()
    # suffix [23, 5, 9] last occurred at index 7; continuation follows it
    assert d.propose(REP_PROMPT, 4) == [17, 23, 5, 9]
    assert d.propose(REP_PROMPT, 2) == [17, 23]


def test_ngram_abstains_on_random_prompt():
    assert NGramDrafter().propose(RAND_PROMPT, 4) == []
    assert NGramDrafter().propose([1, 2], 4) == []      # too short
    assert NGramDrafter().propose(REP_PROMPT, 0) == []  # no budget


def test_ngram_prefers_longest_match():
    # [7, 8] repeats with continuation 9; the 1-gram [8] also repeats with
    # a different continuation — min_ngram=1 must still take the longer
    # (more specific) match first
    ids = [7, 8, 9, 1, 8, 2, 7, 8]
    assert NGramDrafter(max_ngram=3, min_ngram=1).propose(ids, 1) == [9]


def test_ngram_validates_bounds():
    with pytest.raises(ValueError):
        NGramDrafter(max_ngram=2, min_ngram=3)


# -- acceptance rule against hand-computed probabilities ----------------------


def _accept(logits, draft, n_draft, key=0, temp=1.0, top_k=None, top_p=1.0,
            pen=1.0, recent_n=4):
    logits = jnp.asarray(logits, jnp.float32)
    v = logits.shape[-1]
    n_acc, nxt, recent = spec_accept(
        logits, jnp.asarray(draft, jnp.int32), jnp.asarray(n_draft,
                                                           jnp.int32),
        jax.random.PRNGKey(key), jnp.float32(temp),
        jnp.int32(top_k if top_k is not None else v), jnp.float32(top_p),
        jnp.float32(pen), jnp.full((recent_n,), -1, jnp.int32))
    return int(n_acc), int(nxt), recent


def test_accept_certain_draft_always_accepted():
    # p(draft token) ~= 1 at every position -> accept prob min(1, p) ~= 1
    big = 50.0
    logits = np.zeros((3, 4), np.float32)
    logits[0, 2] = big          # token after input 0 is surely 2
    logits[1, 1] = big          # after draft 2, surely 1
    logits[2, 3] = big          # bonus token: surely 3
    for key in range(8):
        n_acc, nxt, _ = _accept(logits, [2, 1], 2, key=key)
        assert n_acc == 2
        assert nxt == 3          # all accepted -> bonus sample from row 2


def test_accept_impossible_draft_always_rejected():
    # p(draft) ~= 0 -> reject; the correction comes from the residual,
    # which is p with the rejected token's mass removed -> surely token 2
    logits = np.zeros((2, 4), np.float32)
    logits[0, 2] = 50.0
    for key in range(8):
        n_acc, nxt, _ = _accept(logits, [1, 0], 2, key=key)
        assert n_acc == 0
        assert nxt == 2


def test_accept_rate_and_marginal_distribution():
    """Empirical check of the Leviathan delta-q rule: with p =
    [0.5, 0.3, 0.2] and draft token 0, accepts happen ~50% of the time
    and — the theorem — the emitted token's MARGINAL distribution is
    exactly p (accept contributes p(0) * delta_0, rejection contributes
    (1 - p(0)) * renorm(p without 0) = p elsewhere)."""
    p = np.array([0.5, 0.3, 0.2], np.float64)
    logits = jnp.asarray(np.log(p)[None, :].repeat(2, 0), jnp.float32)
    n = 4000

    def one(key):
        n_acc, nxt, _ = spec_accept(
            logits, jnp.asarray([0, 0], jnp.int32), jnp.asarray(1, jnp.int32),
            key, jnp.float32(1.0), jnp.int32(3), jnp.float32(1.0),
            jnp.float32(1.0), jnp.full((4,), -1, jnp.int32))
        first = jnp.where(n_acc > 0, 0, nxt)    # token emitted at position 0
        return n_acc, first

    keys = jax.random.split(jax.random.PRNGKey(1234), n)
    n_accs, firsts = jax.jit(jax.vmap(one))(keys)
    accept_rate = float(jnp.mean((n_accs > 0).astype(jnp.float32)))
    assert abs(accept_rate - 0.5) < 0.04
    counts = np.bincount(np.asarray(firsts), minlength=3) / n
    np.testing.assert_allclose(counts, p, atol=0.04)


def test_accept_greedy_is_exact_prefix_match():
    logits = np.zeros((3, 4), np.float32)
    logits[0, 1] = 2.0          # argmax chain: 1, 3, then bonus 0
    logits[1, 3] = 2.0
    logits[2, 0] = 2.0
    n_acc, nxt, _ = _accept(logits, [1, 3], 2, temp=0.0)
    assert (n_acc, nxt) == (2, 0)
    n_acc, nxt, _ = _accept(logits, [1, 2], 2, temp=0.0)   # mismatch at 1
    assert (n_acc, nxt) == (1, 3)                          # correction
    n_acc, nxt, _ = _accept(logits, [0, 3], 2, temp=0.0)   # mismatch at 0
    assert (n_acc, nxt) == (0, 1)


def test_accept_repeat_penalty_sees_accepted_prefix():
    """Position i's penalty window must contain the tokens accepted
    earlier in the SAME verify step (parity with one-at-a-time decode):
    token 1 leads everywhere, but after accepting it once a strong
    penalty flips the greedy choice to token 0 at the next position."""
    logits = np.full((3, 4), -1.0, np.float32)
    logits[:, 1] = 1.0
    logits[:, 0] = 0.9
    n_acc, nxt, _ = _accept(logits, [1, 1], 2, temp=0.0, pen=1.9)
    # draft[0]=1 accepted (fresh window); draft[1]=1 rejected (1 now
    # penalized: 1.0/1.9 < 0.9) with correction 0
    assert (n_acc, nxt) == (1, 0)


def test_accept_ignores_draft_padding():
    logits = np.zeros((3, 4), np.float32)
    logits[0, 1] = 50.0
    # n_draft=1: the pad entry (even if it "matches") can never accept
    n_acc, nxt, _ = _accept(logits, [1, 0], 1)
    assert n_acc == 1
    # n_draft=0 degenerates to a plain decode step
    n_acc, nxt, _ = _accept(logits, [0, 0], 0)
    assert n_acc == 0 and nxt == 1


def test_filtered_probs_matches_softmax():
    logits = jnp.asarray([0.3, -1.2, 2.0, 0.0], jnp.float32)
    p = filtered_probs(logits, jnp.float32(1.0), jnp.int32(4),
                       jnp.float32(1.0), jnp.float32(1.0),
                       jnp.full((4,), -1, jnp.int32))
    np.testing.assert_allclose(np.asarray(p),
                               np.asarray(jax.nn.softmax(logits)),
                               atol=1e-6)
    # top-k=1 concentrates all mass on the argmax
    p1 = filtered_probs(logits, jnp.float32(1.0), jnp.int32(1),
                        jnp.float32(1.0), jnp.float32(1.0),
                        jnp.full((4,), -1, jnp.int32))
    np.testing.assert_allclose(np.asarray(p1), [0, 0, 1, 0], atol=1e-6)


# -- bit-identity with the plain decode path ----------------------------------


@pytest.mark.parametrize(
    "fam", ["llama", pytest.param("gdn", marks=pytest.mark.slow)]  # tier-2 spec smokes cover gdn; 870s cap
)
def test_greedy_spec_bit_identical(fam, llama, gdn):
    m = {"llama": llama, "gdn": gdn}[fam]
    base, _ = m.generate(REP_PROMPT, max_new_tokens=24, sampling=GREEDY,
                         spec=False)
    spec, st = m.generate(REP_PROMPT, max_new_tokens=24, sampling=GREEDY,
                          spec="ngram")
    assert spec == base
    assert st["spec_steps"] > 0
    # and with a penalty in the greedy config (recent-window parity)
    pen = SamplingConfig(temperature=0.0, repeat_penalty=1.3)
    base_p, _ = m.generate(REP_PROMPT, max_new_tokens=16, sampling=pen,
                           spec=False)
    spec_p, _ = m.generate(REP_PROMPT, max_new_tokens=16, sampling=pen,
                           spec="ngram")
    assert spec_p == base_p


def test_greedy_spec_streaming_matches(llama):
    got = []
    base, _ = llama.generate(REP_PROMPT, max_new_tokens=20, sampling=GREEDY,
                             spec=False)
    spec, _ = llama.generate(REP_PROMPT, max_new_tokens=20, sampling=GREEDY,
                             spec="ngram", on_token=lambda t: got.append(t.id))
    assert spec == base
    assert got == spec          # every token streamed, first included


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_draft_model_drafter_perfect_draft(llama):
    """Draft model == target model -> every proposal accepts (the
    strongest end-to-end check of verify + rollback + re-proposal)."""
    d = TextModel(tiny_config("llama"), dtype=jnp.float32,
                  max_cache_len=128, seed=3)
    base, _ = llama.generate(REP_PROMPT, max_new_tokens=20, sampling=GREEDY,
                             spec=False)
    spec, st = llama.generate(REP_PROMPT, max_new_tokens=20, sampling=GREEDY,
                              spec=DraftModelDrafter(d))
    assert spec == base
    assert st["spec_accept_rate"] == 1.0
    assert st["spec_tokens_per_step"] > 2.0


def test_sampled_spec_deterministic_and_bounded(llama):
    scfg = SamplingConfig(temperature=0.9, top_k=40)
    k0 = jax.random.PRNGKey(7)
    a, st = llama.generate(REP_PROMPT, max_new_tokens=20, sampling=scfg,
                           spec="ngram", rng=k0)
    b, _ = llama.generate(REP_PROMPT, max_new_tokens=20, sampling=scfg,
                          spec="ngram", rng=k0)
    assert a == b               # same key -> same stream
    assert len(a) <= 20
    assert st["spec_steps"] >= 1


# -- KV rollback --------------------------------------------------------------


@pytest.mark.parametrize("fam", ["llama", "gdn"])
def test_kv_rollback_after_rejection(fam, llama, gdn):
    """After a verify step that REJECTS drafts, the cache must hold
    exactly the accepted prefix: the next decode step's logits must match
    a reference cache that never saw the rejected tokens. Covers both
    rollback strategies (truncate for attention-only, valid_len-masked
    commit for GDN)."""
    m = {"llama": llama, "gdn": gdn}[fam]
    prompt = REP_PROMPT[:8]
    k = 4

    cache = m.new_cache(1, kv_len=32)
    logits, cache = m.prefill(cache, prompt)
    first = int(np.argmax(np.asarray(logits[0])))
    # drafts chosen to be wrong: greedy acceptance rejects at position 0
    wrong = [(first + 3) % 250 + 1] * k
    recent = jnp.full((4,), -1, jnp.int32)
    packed, cache, _ = m.verify_tokens(cache, first, wrong, k, len(prompt),
                                       jax.random.PRNGKey(0), recent, GREEDY)
    n_acc, nxt = int(np.asarray(packed)[0]), int(np.asarray(packed)[1])
    assert n_acc == 0

    ref = m.new_cache(1, kv_len=32)
    _, ref = m.prefill(ref, prompt)
    ref_logits, ref = m.decode_logits(ref, first)
    assert int(np.argmax(np.asarray(ref_logits[0]))) == nxt

    # both caches now hold prompt + first; the next step must agree
    a, _ = m.decode_logits(cache, nxt)
    b, _ = m.decode_logits(ref, nxt)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_truncate_cache_drops_suffix(llama):
    from cake_tpu.models.common.cache import truncate_cache
    m = llama
    prompt = REP_PROMPT[:8]
    cache = m.new_cache(1, kv_len=32)
    logits, cache = m.prefill(cache, prompt)
    t = int(np.argmax(np.asarray(logits[0])))
    _, cache = m.decode_logits(cache, t)        # position 8
    _, cache = m.decode_logits(cache, t)        # position 9
    cache = truncate_cache(cache, len(prompt))
    assert int(cache["pos"]) == len(prompt)
    for lc in cache["layers"]:
        assert int(np.asarray(lc["pos"]).max()) < len(prompt)
    # a truncated cache continues exactly like a never-extended one
    ref = m.new_cache(1, kv_len=32)
    _, ref = m.prefill(ref, prompt)
    a, _ = m.decode_logits(cache, t)
    b, _ = m.decode_logits(ref, t)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_truncate_cache_rejects_linear(gdn):
    from cake_tpu.models.common.cache import truncate_cache
    cache = gdn.new_cache(1, kv_len=32)
    with pytest.raises(ValueError, match="linear"):
        truncate_cache(cache, 4)


def test_draft_model_drafter_consistent_after_rejection(llama):
    """The drafter's cache must hold exactly the confirmed prefix after a
    proposal round whose tokens the caller rejected: proposals for an
    extended sequence must match a FRESH drafter's."""
    d1 = DraftModelDrafter(TextModel(tiny_config("llama"), dtype=jnp.float32,
                                     max_cache_len=128, seed=11))
    d2 = DraftModelDrafter(TextModel(tiny_config("llama"), dtype=jnp.float32,
                                     max_cache_len=128, seed=11))
    ids = REP_PROMPT[:10]
    d1.propose(ids, 4)                   # speculates, then rolls back
    ext = ids + [42, 7]                  # caller went a different way
    assert d1.propose(ext, 4) == d2.propose(ext, 4)


def test_draft_model_drafter_rejects_linear(gdn):
    with pytest.raises(ValueError, match="linear"):
        DraftModelDrafter(gdn)


# -- resolve + engine ---------------------------------------------------------


def test_resolve_drafter(monkeypatch, llama):
    assert resolve_drafter(False)[0] is None
    assert resolve_drafter(None)[0] is None          # env unset -> off
    monkeypatch.setenv("CAKE_SPEC", "ngram")
    monkeypatch.setenv("CAKE_SPEC_K", "4")
    d, k = resolve_drafter(None)
    assert isinstance(d, NGramDrafter) and k == 4
    monkeypatch.setenv("CAKE_SPEC", "off")
    assert resolve_drafter(None)[0] is None
    with pytest.raises(ValueError):
        resolve_drafter("no-such-drafter")
    d, _ = resolve_drafter(llama)
    assert isinstance(d, DraftModelDrafter)


def test_engine_spec_e2e_multi_token_accept(llama):
    """Engine end-to-end with speculation on: greedy output bit-identical
    to the sequential path, with at least one MULTI-token accept (fewer
    verify steps than emitted tokens) and non-zero accept counters."""
    from cake_tpu.serve import ServeEngine
    base, _ = llama.generate(REP_PROMPT, max_new_tokens=24, sampling=GREEDY,
                             spec=False)
    eng = ServeEngine(llama, slots=2, max_queue=8, ctx_len=128,
                      prefix_cache_mb=0, spec="ngram", spec_k=6)
    try:
        r = eng.submit(REP_PROMPT, max_new_tokens=24, sampling=GREEDY)
        assert r.wait(300)
        assert "error" not in r.result, r.result.get("error")
        assert r.tokens == base
        h = eng.health()["spec"]
        assert h["accepted"] >= 1
        # fewer steps than decode tokens <=> >= 1 multi-token accept
        assert h["steps"] < len(r.tokens) - 1
    finally:
        eng.close()


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_engine_spec_sampled_slots_speculate(llama):
    """Sampled slots ride the batched verify too (each slot verifies
    with its own traced sampling params; spec_accept preserves the
    target distribution). top_k=1 makes the sampled pipeline a point
    mass, so the stochastic accept/resample path must reproduce the
    greedy stream exactly while actually taking verify steps."""
    from cake_tpu.serve import ServeEngine
    base, _ = llama.generate(REP_PROMPT, max_new_tokens=12, sampling=GREEDY,
                             spec=False)
    eng = ServeEngine(llama, slots=2, max_queue=8, ctx_len=128,
                      prefix_cache_mb=0, spec="ngram", spec_k=4)
    try:
        scfg = SamplingConfig(temperature=0.8, top_k=1)
        r1 = eng.submit(REP_PROMPT, max_new_tokens=12, sampling=scfg)
        r2 = eng.submit(REP_PROMPT, max_new_tokens=12, sampling=scfg)
        assert r1.wait(300) and r2.wait(300)
        assert "error" not in r1.result and "error" not in r2.result
        assert r1.tokens == base and r2.tokens == base
        assert eng.spec_steps > 0           # sampled slots speculate now
    finally:
        eng.close()


def test_engine_rejects_stateful_drafter(llama):
    from cake_tpu.serve import ServeEngine
    d = DraftModelDrafter(TextModel(tiny_config("llama"), dtype=jnp.float32,
                                    max_cache_len=64))
    with pytest.raises(ValueError, match="shareable|per-sequence"):
        ServeEngine(llama, slots=2, ctx_len=64, prefix_cache_mb=0, spec=d)
