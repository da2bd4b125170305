"""The experts' combine (`ops/moe.py: moe_ffn`) against a hand-written
reference, and the rule by which it walks the tokens.

The file keeps its name from the sort-based ragged dispatch that stood
beside the dense combine until PR 55 (the chip's sweep found it slower at
every token count, PERF.md section 5): what is pinned here now is that no
program takes it, that a program of at most EXPERT_BLOCK_TOKENS tokens is
the parent's four einsums and nothing else, and that the walk above it
gives the unblocked result."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.ops import moe
from cake_tpu.ops.moe import (EXPERT_BLOCK_TOKENS, combine_weights, moe_ffn,
                              router_topk)


def _bank(rng, e, i, h):
    return (jnp.asarray(rng.normal(0, 0.3, (e, h)), jnp.float32),
            jnp.asarray(rng.normal(0, 0.3, (e, i, h)), jnp.float32),
            jnp.asarray(rng.normal(0, 0.3, (e, i, h)), jnp.float32),
            jnp.asarray(rng.normal(0, 0.3, (e, h, i)), jnp.float32))


def _four_einsums(x, router_weight, gate_proj, up_proj, down_proj, k,
                  first=0, act="silu"):
    """The parent's dense combine (PR 54's `moe_ffn` below 32 tokens and
    for a share), kept here as the reference a program must equal: the
    router, the share's re-indexing, then four einsums over all of x."""
    e = gate_proj.shape[0]
    share = router_weight.shape[0] != e
    with jax.named_scope("cake.ffn.route"):
        logits = jnp.einsum("th,eh->te", x, router_weight,
                            preferred_element_type=jnp.float32)
        weights, idx = router_topk(logits, k, True, "softmax", None)
        if share:
            held = (idx >= first) & (idx < first + e)
            idx = jnp.where(held, idx - first, e)
            weights = jnp.where(held, weights, 0.0)
    with jax.named_scope("cake.ffn.experts"):
        w_te = combine_weights(weights, idx, e).astype(x.dtype)
        g = jnp.einsum("th,eih->tei", x, gate_proj)
        u = jnp.einsum("th,eih->tei", x, up_proj)
        a = moe._expert_act(g, u, act)
        y_e = jnp.einsum("tei,ehi->teh", a, down_proj)
        return jnp.einsum("te,teh->th", w_te, y_e).astype(x.dtype)


def _walks(lowered):
    """Whether a jaxpr's text holds the block walk's loop."""
    return "scan[" in lowered or "while[" in lowered


@pytest.mark.parametrize("act", ["silu", "gelu"])
@pytest.mark.parametrize("gate_act", ["softmax", "sigmoid"])
def test_ragged_matches_dense(act, gate_act, rng):
    """A prefill-sized chunk (T = 48, which took the ragged dispatch until
    PR 55) against a loop over tokens and their top-k experts."""
    e, i, h, t, k = 8, 16, 32, 48, 2
    router, gp, up, dp = _bank(rng, e, i, h)
    x = jnp.asarray(rng.normal(0, 1, (t, h)), jnp.float32)
    got = moe_ffn(x, router, gp, up, dp, k, True, gate_act, act)

    logits = jnp.einsum("th,eh->te", x, router,
                        preferred_element_type=jnp.float32)
    weights, idx = router_topk(logits, k, True, gate_act)
    w = np.asarray(weights)
    ref = np.zeros((t, h), np.float32)
    for tok in range(t):
        for j in range(k):
            ex = int(idx[tok, j])
            g = np.asarray(gp[ex]) @ np.asarray(x[tok])
            u = np.asarray(up[ex]) @ np.asarray(x[tok])
            if act == "silu":
                a = g / (1 + np.exp(-g)) * u
            else:
                a = 0.5 * g * (1 + np.tanh(np.sqrt(2 / np.pi)
                                           * (g + 0.044715 * g ** 3))) * u
            ref[tok] += w[tok, j] * (np.asarray(dp[ex]) @ a)
    assert np.max(np.abs(np.asarray(got) - ref)) < 2e-4


@pytest.mark.parametrize("tokens,first", [(15, 0), (16, 0), (17, 0), (40, 0),
                                          (64, 0), (40, 4)],
                         ids=["one_below", "at_the_block", "one_above",
                              "no_multiple", "four_blocks", "a_share"])
def test_the_block_walk_gives_the_unblocked_combine(tokens, first, rng,
                                                    monkeypatch):
    """Above the block size `moe_ffn` walks the tokens in blocks of it (a
    loop over whole blocks, then what is left): the same result as the
    four einsums over every token at once, for a whole model and for a
    share (experts `first`.. of the 8) alike: the token count decides."""
    monkeypatch.setattr(moe, "EXPERT_BLOCK_TOKENS", 16)
    e, i, h, k = 8, 16, 32, 2
    router, gp, up, dp = _bank(rng, e, i, h)
    gp, up, dp = gp[first:], up[first:], dp[first:]
    x = jnp.asarray(rng.normal(0, 1, (tokens, h)), jnp.float32)

    def f(t):
        return moe_ffn(t, router, gp, up, dp, k, True, first=first)

    want = _four_einsums(x, router, gp, up, dp, k, first=first)
    assert np.max(np.abs(np.asarray(f(x)) - np.asarray(want))) < 2e-4
    assert float(jnp.abs(want).max()) > 1e-3
    assert _walks(str(jax.make_jaxpr(f)(x))) == (tokens > 16)


def test_decode_still_dense_and_consistent(rng):
    """A decode-sized T is the dense combine: the parent's four einsums,
    to the last bit."""
    e, i, h, k = 8, 16, 32, 2
    router, gp, up, dp = _bank(rng, e, i, h)
    x = jnp.asarray(rng.normal(0, 1, (4, h)), jnp.float32)
    dense = moe_ffn(x, router, gp, up, dp, k, True)
    want = _four_einsums(x, router, gp, up, dp, k)
    assert np.array_equal(np.asarray(dense), np.asarray(want))


def test_dispatch_structure_by_token_count(rng):
    """The rule reads the token count and nothing else. At every chunk
    width the served path can dispatch (`TextModel.prefill_chunk` pads to a
    power of two, 16 ... 256) and at decode's T a whole model's experts
    lower to no `ragged_dot_general`, no sort and no loop; one token above
    the block size the loop is there and still no sort."""
    assert EXPERT_BLOCK_TOKENS >= 256     # no served chunk walks
    e, i, h, k = 16, 8, 32, 2
    router, gp, up, dp = _bank(rng, e, i, h)

    def f(x):
        return moe_ffn(x, router, gp, up, dp, k, True)

    for tokens in (1, 4, 16, 32, 64, 128, 256, EXPERT_BLOCK_TOKENS,
                   EXPERT_BLOCK_TOKENS + 1):
        x = jnp.zeros((tokens, h), jnp.float32)
        lowered = str(jax.make_jaxpr(f)(x))
        assert "ragged_dot_general" not in lowered, tokens
        assert " sort[" not in lowered, tokens
        assert _walks(lowered) == (tokens > EXPERT_BLOCK_TOKENS), tokens


@pytest.mark.parametrize("tokens,held", [(1, 16), (8, 16), (8, 4), (256, 4)],
                         ids=["whole_1", "whole_8", "share_8", "share_256"])
def test_the_programs_that_must_not_change_did_not(tokens, held, rng):
    """The decode programs of every cell (a whole model at T = 1 under the
    slot vmap, a verify width of 8) and the share cells' chunks lower to
    the parent's four einsums, equation for equation: an edit of the block
    walk cannot leak into them unnoticed."""
    e, i, h, k = 16, 8, 32, 2
    router, gp, up, dp = _bank(rng, e, i, h)
    first = 0 if held == e else 8
    cut = slice(first, first + held)
    x = jnp.zeros((tokens, h), jnp.float32)
    got = jax.make_jaxpr(lambda t: moe_ffn(
        t, router, gp[cut], up[cut], dp[cut], k, True, first=first))(x)
    want = jax.make_jaxpr(lambda t: _four_einsums(
        t, router, gp[cut], up[cut], dp[cut], k, first=first))(x)
    assert str(got) == str(want)


def test_a_whole_models_prefill_walks_to_the_same_logits(monkeypatch):
    """Through `TextModel.prefill` (the CLI's unchunked path, where a
    prompt can exceed the block): a 40-token prompt of the tiny qwen3_moe
    under a block of 16 gives the unblocked program's logits."""
    from cake_tpu.models.common.config import tiny_config
    from cake_tpu.models.common.text_model import TextModel
    ids = [int(v) for v in np.random.default_rng(5).integers(1, 200, 40)]

    def last_logits():
        m = TextModel(tiny_config("qwen3_moe"), dtype=jnp.float32,
                      max_cache_len=64)
        logits, _ = m.prefill(m.new_cache(), ids)
        return np.asarray(logits)

    want = last_logits()
    monkeypatch.setattr(moe, "EXPERT_BLOCK_TOKENS", 16)
    got = last_logits()
    assert np.max(np.abs(got - want)) < 2e-4 * max(1.0, np.abs(want).max())
