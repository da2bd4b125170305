"""The sampler's sort hands back its sorted values (PR 28).

`sample_traced`, `filtered_probs` and the static `sample_top_p` used to
argsort the vocabulary and then fetch the sorted logits again with a gather
as wide as the vocabulary; on the TPU that gather cost five times the sort.
They now take both outputs of the one sort. The references below spell out
the OLD formula, gather included, and every case must agree with it bit for
bit: same token per key, same probability vector. The structural test at
the end keeps the gather from coming back.
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
}


def _logits(rows: int, tied: bool):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, V)) * 4
    if tied:
        # a coarse grid: hundreds of ties everywhere, and the maximum of
        # every row repeated at ids on both sides of where it first stands
        x = jnp.round(x)
        top = jnp.max(x, axis=-1, keepdims=True)
        x = x.at[:, jnp.array([5, 1500, V - 1])].set(top)
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
NEW_PROBS = jax.jit(jax.vmap(sampling.filtered_probs))
OLD_PROBS = jax.jit(jax.vmap(ref_filtered_probs))
NEW_TOP_P = jax.jit(lambda lg, k: sampling.sample_top_p(lg, k, 0.9, 0.7))
OLD_TOP_P = jax.jit(lambda lg, k: ref_sample_top_p(lg, k, 0.9, 0.7))


def _params(case: str, rows: int):
    t, k, p, rp, _ = CASES[case]
    return (jnp.full((rows,), t, jnp.float32), jnp.full((rows,), k, jnp.int32),
            jnp.full((rows,), p, jnp.float32),
            jnp.full((rows,), rp, jnp.float32),
            jnp.tile(RECENT, (rows, 1)))


@pytest.mark.parametrize("rows", [1, 4, 8, 16])
@pytest.mark.parametrize("case", list(CASES))
def test_sample_traced_token_matches_gather_formula(case, rows):
    """Per key: rows x 4 keys a case, vmapped as `_decode_slots` vmaps it."""
    logits = _logits(rows, CASES[case][4])
    params = _params(case, rows)
    for draw in range(4):
        keys = jax.random.split(jax.random.PRNGKey(100 + draw), rows)
        got = NEW_TRACED(logits, keys, *params)
        want = OLD_TRACED(logits, keys, *params)
        assert got.dtype == jnp.int32 and got.shape == (rows,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if CASES[case][0] <= 0.0:
        # greedy is the penalized argmax, ties to the lowest id
        lf = jax.vmap(_penalized)(logits, params[3], params[4])
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.argmax(lf, axis=-1)))


@pytest.mark.parametrize("case", list(CASES))
def test_filtered_probs_equal_gather_formula(case):
    rows = 4
    logits = _logits(rows, CASES[case][4])
    params = _params(case, rows)
    got, want = NEW_PROBS(logits, *params), OLD_PROBS(logits, *params)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-5)


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


# -- what keeps the gather from coming back ---------------------------------

SERVED_V = 151936            # Qwen3's vocabulary, both benchmark configs


def _lowered(fn) -> str:
    """StableHLO of the decode step's sampler at the served shape: 8 slots,
    vmapped as `_decode_slots` vmaps it. Lowered only, never run."""
    b, n = 8, 64
    s = jax.ShapeDtypeStruct
    return jax.jit(jax.vmap(fn)).lower(
        s((b, SERVED_V), jnp.bfloat16), s((b, 2), jnp.uint32),
        s((b,), jnp.float32), s((b,), jnp.int32), s((b,), jnp.float32),
        s((b,), jnp.float32), s((b, n), jnp.int32)).as_text(debug_info=True)


def _wide_gathers(text: str) -> list[str]:
    """Gather ops whose RESULT is as wide as the vocabulary. A gather has
    no region, so the op and its `-> result` type stand on one line."""
    ops = [ln for ln in text.splitlines()
           if re.search(r"stablehlo\.(dynamic_)?gather", ln)]
    assert all("->" in ln for ln in ops), ops
    return [ln for ln in ops
            if re.search(rf"x{SERVED_V}x\w+>$",
                         ln.split("->")[-1].split(" loc(")[0].strip())]


def test_lowered_sampler_has_one_sort_and_no_vocabulary_wide_gather():
    """Exactly one sort, still under the `cake.sample.sort` scope, and no
    gather over the vocabulary (the one-element `order[argmax]` read may
    stay). The old formula, lowered the same way, shows the reader sees
    such a gather when there is one."""
    text = _lowered(sampling.sample_traced)
    assert len(re.findall(r"stablehlo\.sort\b", text)) == 1
    assert "vmap(cake.sample)/cake.sample.sort/sort" in text
    assert _wide_gathers(text) == []
    assert "take_along_axis" not in text
    old = _lowered(ref_sample_traced)
    assert len(re.findall(r"stablehlo\.sort\b", old)) == 1
    assert len(_wide_gathers(old)) == 1
