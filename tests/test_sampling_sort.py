"""The traced sampler keeps its top-k / top-p set without a sort (PR 42).

`sample_traced` and `filtered_probs` used to sort the vocabulary, mask by
rank and by the running mass in rank order, and read the drawn rank's id
back (PR 28 had taken the gather `scaled[order]` out; the sort stayed, the
largest device op of a decode step). They now take `sampling.keep_mask`: the
same set, found in vocabulary order by searching for the value and id where
the kept prefix ends. The references below spell out the OLD formula, sort
and gather included. Greedy must agree with it bit for bit. A sampled row
draws over the vocabulary and not over the ranks, so per key it gives another
token of the same distribution: those cases compare the KEPT SET with the old
formula's (equal but where a token's preceding mass is within float rounding
of top_p, tied runs cut lowest id first), and a distribution test holds the
draws to `filtered_probs`. The static `sample_top_p` still sorts and still
agrees bit for bit. The structural tests at the end keep the sort, the gather
and the scatter from coming back.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.ops import sampling

V = 3000
RECENT = jnp.array([7, 11, 2999, 0, 7, -1, -1, -1], jnp.int32)

# name -> (temperature, top_k, top_p, repeat_penalty, tied logits?)
CASES = {
    "greedy": (0.0, V, 1.0, 1.0, False),
    "temperature": (0.7, V, 1.0, 1.0, False),
    "top_p": (0.7, V, 0.9, 1.0, False),
    "top_k": (0.7, 20, 1.0, 1.0, False),
    "top_k_top_p": (0.7, 20, 0.9, 1.0, False),
    "repeat_penalty": (0.7, V, 0.9, 1.3, False),
    "tied_maxima": (0.7, 50, 0.9, 1.0, True),
    "tied_maxima_greedy": (0.0, V, 1.0, 1.0, True),
    # a run of tied values straddles the cut: of top-k alone, of top-p alone
    "tied_cut_top_k": (0.7, 44, 1.0, 1.0, True),
    "tied_cut_top_p": (2.0, V, 0.9, 1.0, True),
}
GREEDY = [c for c in CASES if CASES[c][0] <= 0.0]
SAMPLED = [c for c in CASES if CASES[c][0] > 0.0]
NEAR_CUT = 1e-5     # a preceding mass this near top_p may fall on either side


def _logits(rows: int, tied: bool, v: int = V):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, v)) * 4
    if tied:
        # a coarse grid: hundreds of ties everywhere, and the maximum of
        # every row repeated at ids on both sides of where it first stands
        x = jnp.round(x)
        top = jnp.max(x, axis=-1, keepdims=True)
        x = x.at[:, jnp.array([5, v // 2, v - 1])].set(top)
    return x.astype(jnp.bfloat16)


def _penalized(logits, repeat_penalty, recent_tokens):
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    idx = jnp.where(recent_tokens < 0, v, recent_tokens)
    flagged = jnp.zeros((v,), jnp.bool_).at[idx].set(True, mode="drop")
    penalized = jnp.where(lf >= 0, lf / repeat_penalty, lf * repeat_penalty)
    return jnp.where(flagged, penalized, lf)


def _keep_and_probs(sorted_logits, top_k, top_p):
    v = sorted_logits.shape[-1]
    rank = jnp.arange(v, dtype=jnp.int32)
    probs = jax.nn.softmax(jnp.where(rank < top_k, sorted_logits, -jnp.inf))
    prev_mass = jnp.cumsum(probs) - probs
    keep = (rank < top_k) & (prev_mass < top_p)
    return keep.at[0].set(True), probs


def ref_sample_traced(logits, rng, temperature, top_k, top_p,
                      repeat_penalty, recent_tokens):
    """sample_traced as it stood before PR 28: argsort, then the gather."""
    v = logits.shape[-1]
    lf = _penalized(logits, repeat_penalty, recent_tokens)
    scaled = lf / jnp.maximum(temperature, 1e-6)
    order = jnp.argsort(-scaled)
    sorted_logits = scaled[order]                       # the [V] gather
    keep, _ = _keep_and_probs(sorted_logits, top_k, top_p)
    z = jnp.where(keep, sorted_logits, -jnp.inf) + jax.random.gumbel(
        rng, (v,), dtype=jnp.float32)
    choice = order[jnp.argmax(z)]
    return jnp.where(temperature > 0.0, choice, order[0]).astype(jnp.int32)


def ref_filtered_probs(logits, temperature, top_k, top_p, repeat_penalty,
                       recent_tokens):
    """filtered_probs (use_filters=True) as it stood before PR 28."""
    v = logits.shape[-1]
    lf = _penalized(logits, repeat_penalty, recent_tokens)
    scaled = lf / jnp.maximum(temperature, 1e-6)
    order = jnp.argsort(-scaled)
    sorted_logits = scaled[order]                       # the [V] gather
    keep, probs = _keep_and_probs(sorted_logits, top_k, top_p)
    kept = jnp.where(keep, probs, 0.0)
    kept = kept / jnp.maximum(jnp.sum(kept), 1e-30)
    return jnp.zeros((v,), jnp.float32).at[order].set(kept)


def ref_kept(logits, temperature, top_k, top_p, repeat_penalty,
             recent_tokens):
    """What the old formula keeps, in vocabulary order: the kept set, the
    mass its running sum had before each token, and the scaled logits."""
    v = logits.shape[-1]
    lf = _penalized(logits, repeat_penalty, recent_tokens)
    scaled = lf / jnp.maximum(temperature, 1e-6)
    order = jnp.argsort(-scaled)
    keep, probs = _keep_and_probs(scaled[order], top_k, top_p)
    before = jnp.cumsum(probs) - probs
    return (jnp.zeros((v,), jnp.bool_).at[order].set(keep),
            jnp.zeros((v,), jnp.float32).at[order].set(before), scaled)


def ref_sample_top_p(logits, rng, p, temperature):
    """The static sample_top_p as it stood before PR 28."""
    lf = logits.astype(jnp.float32) / temperature
    order = jnp.argsort(lf, axis=-1)[..., ::-1]
    sorted_logits = jnp.take_along_axis(lf, order, axis=-1)  # the gather
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    masked = jnp.where(cum - probs < p, sorted_logits, -jnp.inf)
    z = masked + jax.random.gumbel(rng, masked.shape, dtype=jnp.float32)
    choice = jnp.argmax(z, axis=-1)
    return jnp.take_along_axis(order, choice[..., None],
                               axis=-1)[..., 0].astype(jnp.int32)


# jitted once, so the cases of one row count share a compilation
NEW_TRACED = jax.jit(jax.vmap(sampling.sample_traced))
OLD_TRACED = jax.jit(jax.vmap(ref_sample_traced))
NEW_KEPT = jax.jit(jax.vmap(sampling.keep_mask))
OLD_KEPT = jax.jit(jax.vmap(ref_kept))
NEW_PROBS = jax.jit(jax.vmap(sampling.filtered_probs))
OLD_PROBS = jax.jit(jax.vmap(ref_filtered_probs))
NEW_TOP_P = jax.jit(lambda lg, k: sampling.sample_top_p(lg, k, 0.9, 0.7))
OLD_TOP_P = jax.jit(lambda lg, k: ref_sample_top_p(lg, k, 0.9, 0.7))
PASSES = jax.jit(jax.vmap(
    lambda scaled, k, p: sampling._keep_mask_and_passes(scaled, k, p)[1]))


def _params(case: str, rows: int, v: int = V):
    t, k, p, rp, _ = CASES[case]
    recent = jnp.where(RECENT < 0, -1, RECENT % v)
    return (jnp.full((rows,), t, jnp.float32),
            jnp.full((rows,), v if k >= V else k, jnp.int32),
            jnp.full((rows,), p, jnp.float32),
            jnp.full((rows,), rp, jnp.float32), jnp.tile(recent, (rows, 1)))


@pytest.mark.parametrize("rows", [1, 4, 8, 16])
@pytest.mark.parametrize("case", GREEDY)
def test_greedy_token_matches_gather_formula(case, rows):
    """Bit for bit: rows x 4 keys a case, vmapped as `_decode_slots` vmaps
    it, and the penalized argmax with ties to the lowest id."""
    logits = _logits(rows, CASES[case][4])
    params = _params(case, rows)
    for draw in range(4):
        keys = jax.random.split(jax.random.PRNGKey(100 + draw), rows)
        got = NEW_TRACED(logits, keys, *params)
        want = OLD_TRACED(logits, keys, *params)
        assert got.dtype == jnp.int32 and got.shape == (rows,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    lf = jax.vmap(_penalized)(logits, params[3], params[4])
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(lf, axis=-1)))


def _kept_both_ways(case: str, rows: int):
    """(new kept set, old kept set, tokens the licence covers, scaled)."""
    logits = _logits(rows, CASES[case][4])
    params = _params(case, rows)
    want, before, scaled = OLD_KEPT(logits, *params)
    got = NEW_KEPT(scaled, params[1], params[2])
    near = np.abs(np.asarray(before) - CASES[case][2]) < NEAR_CUT
    return np.asarray(got), np.asarray(want), near, np.asarray(scaled)


def _cut_runs(scaled_row, kept_row) -> int:
    """Asserts the kept set is a prefix of (value descending, id ascending)
    order: no dropped token outranks a kept one, a tied run is cut lowest
    ids first. Returns how many tied runs the cut splits."""
    ranked = np.lexsort((np.arange(scaled_row.size), -scaled_row))
    n_kept = int(kept_row.sum())
    assert kept_row[ranked[:n_kept]].all() and n_kept >= 1
    split = 0
    for value in np.unique(scaled_row[kept_row]):
        tied = scaled_row == value
        split += bool((tied & ~kept_row).any())
    return split


@pytest.mark.parametrize("rows", [1, 4, 8, 16])
@pytest.mark.parametrize("case", SAMPLED)
def test_kept_set_matches_sort_formula(case, rows):
    """The kept ids are the old formula's `order[keep]`, but where the old
    running sum stood within NEAR_CUT of top_p; every draw lands in the
    kept set; where the grid ties, the cut splits a run by lowest id."""
    got, want, near, scaled = _kept_both_ways(case, rows)
    assert not ((got != want) & ~near).any()
    splits = [_cut_runs(scaled[r], got[r]) for r in range(rows)]
    if case.startswith("tied_cut"):
        assert min(splits) >= 1, splits
    logits, params = _logits(rows, CASES[case][4]), _params(case, rows)
    for draw in range(4):
        keys = jax.random.split(jax.random.PRNGKey(100 + draw), rows)
        tokens = np.asarray(NEW_TRACED(logits, keys, *params))
        assert got[np.arange(rows), tokens].all()


@pytest.mark.parametrize("case", list(CASES))
def test_filtered_probs_equal_gather_formula(case):
    """The same support (but at the licence's tokens) and the same
    probabilities up to the rounding of two orders of summation."""
    rows = 4
    logits = _logits(rows, CASES[case][4])
    params = _params(case, rows)
    got, want = NEW_PROBS(logits, *params), OLD_PROBS(logits, *params)
    _, _, near, _ = _kept_both_ways(case, rows)
    assert not (((np.asarray(got) > 0) != (np.asarray(want) > 0))
                & ~near).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-5)


DRAWS, SMALL_V = 20_000, 64


@pytest.mark.parametrize("case", SAMPLED)
def test_draws_follow_filtered_probs(case):
    """The draw in vocabulary order is the categorical draw over the kept
    set: 20,000 keys on one row of 64 tokens, each token's frequency within
    five standard deviations of its probability, none outside the set."""
    logits = _logits(1, CASES[case][4], SMALL_V)[0]
    params = [a[0] for a in _params(case, 1, SMALL_V)]
    keys = jax.random.split(jax.random.PRNGKey(7), DRAWS)
    tokens = jax.jit(jax.vmap(
        lambda key: sampling.sample_traced(logits, key, *params)))(keys)
    probs = np.asarray(sampling.filtered_probs(logits, *params), np.float64)
    freq = np.bincount(np.asarray(tokens), minlength=SMALL_V) / DRAWS
    assert freq[probs == 0].sum() == 0 and (probs > 0).sum() > 1
    sigma = np.sqrt(probs * (1 - probs) / DRAWS)
    assert (np.abs(freq - probs) <= 5 * sigma + 1e-6).all()


@pytest.mark.parametrize("case", list(CASES))
def test_a_disabled_filter_costs_no_pass(case):
    """The four searches' trip counts (top-k value, top-k id, top-p value,
    top-p id), as the loops carry them: top_k >= V and top_p >= 1 close the
    predicate at entry, an enabled search ends inside its bound."""
    _, k, p, _, tied = CASES[case]
    rows = 4
    scaled = OLD_KEPT(_logits(rows, tied), *_params(case, rows))[2]
    k_value, k_id, p_value, p_id = (
        np.asarray(n) for n in PASSES(scaled, *_params(case, rows)[1:3]))
    value_bound = -(-32 // sampling._FAN_BITS)
    id_bound = -(-(V - 1).bit_length() // sampling._FAN_BITS)
    for on, value, ids in ((k < V, k_value, k_id), (p < 1.0, p_value, p_id)):
        if on:
            assert (1 <= value).all() and (value <= value_bound).all()
            assert (ids <= id_bound).all()
        else:
            assert not value.any() and not ids.any()
    if case.startswith("tied_cut"):
        assert (k_id if k < V else p_id).all()


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("shape", ["row", "batch"])
def test_static_sample_top_p_matches_gather_formula(shape, tied):
    """[V] and [B, V]; with ties the ascending-then-reversed order breaks
    them to the HIGHEST id, as it always did."""
    logits = _logits(4, tied)
    if shape == "row":
        logits = logits[0]
    for draw in range(8):
        key = jax.random.PRNGKey(200 + draw)
        np.testing.assert_array_equal(np.asarray(NEW_TOP_P(logits, key)),
                                      np.asarray(OLD_TOP_P(logits, key)))


def test_sort_with_order_is_argsort_and_its_gather():
    x = _logits(4, True).astype(jnp.float32)
    vals, order = sampling._sort_with_order(x)
    want = jnp.argsort(x, axis=-1)
    np.testing.assert_array_equal(np.asarray(order), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(vals), np.asarray(jnp.take_along_axis(x, want, axis=-1)))


# -- what keeps the sort, the gather and the scatter from coming back --------

SERVED_V = 151936            # Qwen3's vocabulary, both benchmark configs


def _lowered(fn, keyed: bool = True) -> str:
    """StableHLO of the decode step's sampler at the served shape: 8 slots,
    vmapped as `_decode_slots` vmaps it. Lowered only, never run."""
    b, n = 8, 64
    s = jax.ShapeDtypeStruct
    key = (s((b, 2), jnp.uint32),) if keyed else ()
    return jax.jit(jax.vmap(fn)).lower(
        s((b, SERVED_V), jnp.bfloat16), *key,
        s((b,), jnp.float32), s((b,), jnp.int32), s((b,), jnp.float32),
        s((b,), jnp.float32), s((b, n), jnp.int32)).as_text(debug_info=True)


def _wide(tensor_type: str) -> bool:
    return re.search(rf"x{SERVED_V}(x\d+)*x\w+>$", tensor_type) is not None


def _wide_gathers(text: str) -> list[str]:
    """Gather ops whose RESULT is as wide as the vocabulary. A gather has
    no region, so the op and its `-> result` type stand on one line."""
    ops = [ln for ln in text.splitlines()
           if re.search(r"stablehlo\.(dynamic_)?gather", ln)]
    assert all("->" in ln for ln in ops), ops
    return [ln for ln in ops
            if _wide(ln.split("->")[-1].split(" loc(")[0].strip())]


def _wide_scatters(text: str) -> list[str]:
    """Scatter ops whose UPDATES are as wide as the vocabulary (the penalty
    flags 64 recent ids into a row: that one is narrow). A scatter has a
    region, so its operand types stand on the line that closes it."""
    lines = text.splitlines()
    found = []
    for i, ln in enumerate(lines):
        if '"stablehlo.scatter"' not in ln:
            continue
        closing = next(x for x in lines[i:] if x.lstrip().startswith("}) : ("))
        operands = closing.split("}) : (")[1].split(") ->")[0]
        updates = re.findall(r"tensor<[^>]*>", operands)[-1]
        if _wide(updates):
            found.append(closing)
    return found


@pytest.mark.parametrize("fn,keyed,old", [
    (sampling.sample_traced, True, ref_sample_traced),
    (sampling.filtered_probs, False, ref_filtered_probs)],
    ids=["sample_traced", "filtered_probs"])
def test_lowered_sampler_sorts_gathers_and_scatters_nothing_wide(fn, keyed,
                                                                 old):
    """No sort, no gather over the vocabulary and no scatter of a
    vocabulary of updates; the searches are loops under the scopes the
    trace readers sum. The old formula, lowered the same way, shows the
    readers see each of the three when it is there."""
    text = _lowered(fn, keyed)
    assert "stablehlo.sort" not in text
    assert _wide_gathers(text) == [] and _wide_scatters(text) == []
    assert "take_along_axis" not in text
    for search in ("top_k", "top_p"):
        assert re.search(
            rf"cake\.sample\.select\)?/cake\.sample\.{search}/while/body", text)
    before = _lowered(old, keyed)
    assert len(re.findall(r"stablehlo\.sort\b", before)) == 1
    assert len(_wide_gathers(before)) == 1
    assert len(_wide_scatters(before)) == (0 if keyed else 1)
