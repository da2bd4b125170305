"""MiMo-V2 on the served path: window layers with a learned sink beside full
layers of other head counts (keys wider than values), a sigmoid router that
selects by a bias it does not weigh by, and an expert layer told which of
the router's experts it holds.

Pinned here:
  * the adapter: the benchmark's configuration resolves to the layer kinds,
    shapes and share the issue states; what it cannot honour it refuses;
  * the two borrowed semantics against `transformers` (which has no
    `mimo_v2`): the router against `DeepseekV3TopkRouter`, the softmax with
    a sink column against `gpt_oss`'s eager attention, float32;
  * the program against the benchmark's plain reference
    (benchmark/reference/mimo_v2.py) through the three steps of
    benchmark/check.py, equal in float32, whole and as a share, and the
    four controls well apart from it;
  * the shares of an expert layer add up to the uncut layer, and a share
    takes the dense combine at every width;
  * the row operations on a pool whose layers differ in K/V heads and whose
    keys and values differ in width;
  * the prefix cache with a ring smaller than a block: a full-chain hit, a
    partial-chain hit and a miss give the same greedy tokens;
  * the lowering: one `_decode_slots` program, no slice of the pool;
  * loader <-> export round trip, split and fused qkv;
  * `flash_kernel_mode` / `decode_kernel_block` by layer kind; `--tp` on
    four virtual devices; `ring_tokens` in the flight record.
"""
import importlib
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import TextModel, init_params, tiny_config
from cake_tpu.models.common.cache import key_row_shape, truncate_layers
from cake_tpu.models.common.config import AttnShape, config_from_hf_dict
from cake_tpu.models.common.layers import (decode_kernel_block,
                                           flash_kernel_mode, make_rope)
from cake_tpu.ops import make_attention_mask, multi_head_attention
from cake_tpu.ops.moe import moe_ffn, router_topk
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
GREEDY = SamplingConfig(temperature=0.0)
CTX = 128

# the published MiMo-V2.5 keys at tiny widths: a dense first layer, both
# kinds twice, a share of 4 of 8 experts (the second of two)
TINY_HF = {
    "architectures": ["MiMoV2ForCausalLM"], "model_type": "mimo_v2",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "hybrid_layer_pattern": [0, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1], "num_attention_heads": 8,
    "num_key_value_heads": 4, "head_dim": 96, "v_head_dim": 64,
    "swa_num_attention_heads": 8, "swa_num_key_value_heads": 8,
    "swa_head_dim": 96, "swa_v_head_dim": 64, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000, "sliding_window": 16,
    "sliding_window_size": 16, "attention_chunk_size": 16,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "attention_value_scale": 0.707,
    "layernorm_epsilon": 1e-5, "max_position_embeddings": 512,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "n_shared_experts": None, "routed_scaling_factor": None,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "expert_parallel": {"size": 2, "rank": 1},
}
WHOLE_HF = {**TINY_HF, "n_routed_experts": 8, "expert_parallel": None}
# the same at widths whose keys no leaf joins (2 x 24, 4 x 24: rank-4 K)
NARROW = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 24,
          "v_head_dim": 16, "swa_num_attention_heads": 4,
          "swa_num_key_value_heads": 4, "swa_head_dim": 24,
          "swa_v_head_dim": 16}


@pytest.fixture(scope="module")
def bench():
    """benchmark/ importable: check.py, weights.py, reference/mimo_v2.py."""
    sys.path.insert(0, BENCH)
    try:
        yield {name: importlib.import_module(name)
               for name in ("check", "weights", "reference.mimo_v2")}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def model():
    return TextModel(tiny_config("mimo_v2"), dtype=jnp.float32,
                     max_cache_len=CTX)


# -- the adapter --------------------------------------------------------------

def test_adapter_resolves_the_benchmarks_configuration():
    with open(os.path.join(BENCH, "configs", "mimo-v2.5-l7-ep16.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf_dict(hf)
    specs = cfg.layer_specs()
    assert [s.kind for s in specs] == ["full"] + ["swa"] * 5 + ["full"]
    assert [s.is_moe for s in specs] == [False] + [True] * 6
    assert [s.sink for s in specs] == [False] + [True] * 5 + [False]
    assert all(s.window == 128 and s.local_rope_table for s in specs[1:6])
    assert cfg.attn_shape(specs[0]) == AttnShape(64, 4, 192, 128)
    assert cfg.attn_shape(specs[1]) == AttnShape(64, 8, 192, 128)
    assert (cfg.rotary_dim, cfg.rope_theta, cfg.local_rope_theta) == \
        (64, 1e7, 1e4)
    assert (cfg.num_experts, cfg.router_width, cfg.expert_first,
            cfg.num_experts_per_tok) == (16, 256, 0, 8)
    assert cfg.moe_select_bias and cfg.moe_gate_act == "sigmoid"
    assert cfg.rms_norm_eps == 1e-5 and cfg.attn_value_scale == 0.707
    assert cfg.vocab_size == 19072 and not cfg.qk_norm and cfg.fused_qkv
    # without the key a process holds every expert
    whole = config_from_hf_dict({**hf, "expert_parallel": None})
    assert (whole.router_width, whole.expert_first) == (16, 0)
    # the family is found by model_type where the string is not known
    assert config_from_hf_dict(
        {**hf, "architectures": ["SomethingElse"]}).arch == "mimo_v2"


@pytest.mark.parametrize("key,value,says", [
    ("n_group", 8, "group-limited"),
    ("n_shared_experts", 1, "shared experts"),
    ("routed_scaling_factor", 2.5, "scaling"),
    ("scoring_func", "softmax", "scoring_func"),
    ("swa_head_dim", 32, "swa_head_dim"),
    ("expert_parallel", {"size": 2, "rank": 2}, "rank 2 of 2"),
])
def test_adapter_refuses_what_it_cannot_honour(key, value, says):
    with pytest.raises(ValueError, match=says):
        config_from_hf_dict({**TINY_HF, key: value})


# -- the two borrowed semantics, against transformers -------------------------

def test_router_matches_transformers_deepseek_v3_topk_router():
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import \
        DeepseekV3TopkRouter
    e, h, k, t = 16, 32, 4, 50
    rng = np.random.default_rng(39)
    w = rng.normal(0, 0.3, (e, h)).astype(np.float32)
    bias = rng.normal(0, 0.1, (e,)).astype(np.float32)
    x = rng.normal(0, 1, (t, h)).astype(np.float32)
    router = DeepseekV3TopkRouter(types.SimpleNamespace(
        num_experts_per_tok=k, n_routed_experts=e, routed_scaling_factor=1.0,
        n_group=1, topk_group=1, norm_topk_prob=True, hidden_size=h))
    with torch.no_grad():
        router.weight.copy_(torch.from_numpy(w))
        router.e_score_correction_bias.copy_(torch.from_numpy(bias))
        idx_t, w_t = router(torch.from_numpy(x))
    want = np.zeros((t, e), np.float32)
    np.put_along_axis(want, idx_t.numpy(), w_t.numpy(), axis=1)
    weights, idx = router_topk(jnp.asarray(x @ w.T), k, True, "sigmoid",
                               jnp.asarray(bias))
    got = np.zeros((t, e), np.float32)
    np.put_along_axis(got, np.asarray(idx), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # the bias selects: without it other experts are chosen for some token
    _, plain = router_topk(jnp.asarray(x @ w.T), k, True, "sigmoid")
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(idx))).any()
    # and it does not weigh: the weights are the scores' own shares
    sc = 1 / (1 + np.exp(-(x @ w.T)))
    picked = np.take_along_axis(sc, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        np.asarray(weights), picked / picked.sum(-1, keepdims=True),
        atol=1e-6)


def test_sink_softmax_matches_transformers_gpt_oss_attention():
    torch = pytest.importorskip("torch")
    from transformers.models.gpt_oss.modeling_gpt_oss import \
        eager_attention_forward
    b, s, hq, hkv, d, dv, win = 2, 24, 4, 2, 24, 16, 8
    rng = np.random.default_rng(40)
    q = rng.normal(0, 1, (b, s, hq, d)).astype(np.float32)
    k = rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32)
    v = rng.normal(0, 1, (b, s, hkv, dv)).astype(np.float32)
    sink = rng.normal(0, 4, (hq,)).astype(np.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mask = make_attention_mask(pos, pos, window=win)
    got = multi_head_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), mask, sink=jnp.asarray(sink))
    module = types.SimpleNamespace(sinks=torch.from_numpy(sink),
                                   num_key_value_groups=hq // hkv,
                                   training=False)
    add = torch.from_numpy(np.where(np.asarray(mask), 0.0, -np.inf
                                    ).astype(np.float32))[:, None]
    with torch.no_grad():
        want, _ = eager_attention_forward(
            module, torch.from_numpy(q).transpose(1, 2),
            torch.from_numpy(k).transpose(1, 2),
            torch.from_numpy(v).transpose(1, 2), add, d ** -0.5)
    assert got.shape == (b, s, hq, dv)
    np.testing.assert_allclose(np.asarray(got), want.numpy(), atol=2e-6)
    # the sink takes mass and gives no value: rows shrink, never grow
    bare = multi_head_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), mask)
    assert float(jnp.abs(got - bare).max()) > 1e-2


# -- the program against the plain reference ----------------------------------

@pytest.mark.parametrize("hf,joined,room", [
    (TINY_HF, True, 2), (WHOLE_HF, True, 3),
    ({**TINY_HF, **NARROW}, False, 3), ({**WHOLE_HF, **NARROW}, False, 3)],
    ids=["share", "whole", "share_unjoined", "whole_unjoined"])
def test_program_equals_the_reference_through_the_check(bench, hf, joined,
                                                        room):
    """`room`: how far over the sound bf16 reading every control must read
    (at the joined widths the share's bias-less selection reads 2.5 x)."""
    check, W, ref = (bench[k] for k in ("check", "weights",
                                        "reference.mimo_v2"))
    cfg = config_from_hf_dict(hf)
    assert [key_row_shape(cfg.attn_shape(s)) for s in
            cfg.layer_specs()[:2]] == (
        [(384,), (768,)] if joined else [(2, 24), (4, 24)])
    assert ref.share(hf)[:2] == (cfg.router_width, cfg.expert_first)
    seed = 2 ** 31 + 39
    sound = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        w = W.make_weights(ref, hf, seed, dtype)
        m = TextModel(cfg, {**w, "rope": make_rope(cfg)}, dtype=dtype,
                      seed=1, max_cache_len=256)
        served = check.served_logits(
            m, 4, 256, 32, check.check_ids(seed, 512, [20, 90]), 3,
            {"temperature": 0.7, "top_p": 0.9})
        got = check.compare(ref, hf, w, served)
        sound[dtype] = got["pooled"]
        if dtype == jnp.float32:
            # chunks of 32 (two windows) with a last bucket of 26 padded to
            # 32, decode with rows 0 and 3 of 4 active, a chunk behind it
            assert len(got["points"]) == 6 and got["worst"] < 2e-5, got
            assert any("tail_after_decode" in k for k in got["points"])
    # each mechanism left out of the reference, in the program's place
    for lacking in ("sink", "select_bias", "value_scale"):
        alt = types.SimpleNamespace(
            forward_logits=lambda h, ww, ids, pos, quant=None: (
                ref.forward_logits(h, ww, ids, pos,
                                   **({lacking: False} if quant else {}))))
        ctl = check.control(alt, hf, w, served, lacking)
        assert ctl["pooled"] > room * sound[jnp.bfloat16], (lacking, ctl,
                                                            sound)
    int8 = check.control(ref, hf, w, served, "int8")
    assert int8["pooled"] > 1.3 * sound[jnp.bfloat16], (int8, sound)
    used, needed = ref.experts_used(hf, w, served[-1]["ids"])
    assert needed == hf["n_routed_experts"] and 0 < used <= needed


# -- the told expert layer ----------------------------------------------------

def _bank(seed=41, e=8, h=32, i=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": jax.random.normal(ks[0], (e, h)) * 0.3,
            "gate": jax.random.normal(ks[1], (e, i, h)) * 0.2,
            "up": jax.random.normal(ks[2], (e, i, h)) * 0.2,
            "down": jax.random.normal(ks[3], (e, h, i)) * 0.2,
            "bias": jax.random.normal(ks[4], (e,)) * 0.1}


def _share(bank, x, first=0, count=None):
    count = count or bank["gate"].shape[0]
    cut = slice(first, first + count)
    return moe_ffn(x, bank["router"], bank["gate"][cut], bank["up"][cut],
                   bank["down"][cut], 2, True, "sigmoid",
                   select_bias=bank["bias"], first=first)


@pytest.mark.parametrize("tokens", [8, 64],
                         ids=["dense_combine", "chunk_width"])
def test_the_shares_of_an_expert_layer_add_up(tokens):
    """8 experts as 2 shares of 4: routing and normalisation over all 8,
    each share the part its own experts give."""
    bank = _bank()
    x = jax.random.normal(jax.random.PRNGKey(7), (tokens, 32))
    whole = _share(bank, x)
    parts = [_share(bank, x, first, 4) for first in (0, 4)]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole), atol=1e-5)
    assert min(float(jnp.abs(p).max()) for p in parts) > 1e-3


def test_a_share_takes_the_dense_combine_at_every_width():
    """A chunk of a share, like a whole model's, lowers to no
    ragged_dot_general and no sort (on the chip that path gave zeros for
    most held rows of one served share, PERF.md PR 48, and was slower at
    every width, PR 55), and gives what the whole layer gives of the
    held experts."""
    bank = _bank(seed=42)
    x = jax.random.normal(jax.random.PRNGKey(8), (64, 32))
    for lowered in (jax.make_jaxpr(lambda t: _share(bank, t, 4, 4))(x),
                    jax.make_jaxpr(lambda t: _share(bank, t))(x)):
        assert "ragged_dot_general" not in str(lowered)
        assert " sort[" not in str(lowered)
    # the whole layer with the experts held elsewhere (0..3) silenced
    rest = {**bank, "down": bank["down"].at[:4].set(0.0)}
    got = _share(bank, x, 4, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_share(rest, x)),
                               atol=1e-5)
    assert float(jnp.abs(got).max()) > 1e-3


def test_a_token_whose_experts_all_live_elsewhere_gets_nothing():
    bank = _bank(seed=43)
    bank["bias"] = jnp.where(jnp.arange(8) < 4, -10.0, 10.0)    # 4..7 win
    for tokens in (4, 32):
        x = jax.random.normal(jax.random.PRNGKey(9), (tokens, 32))
        assert float(jnp.abs(_share(bank, x, 0, 4)).max()) == 0.0
        np.testing.assert_allclose(np.asarray(_share(bank, x, 4, 4)),
                                   np.asarray(_share(bank, x)), atol=1e-5)


# -- rows of a pool whose layers differ ----------------------------------------

def _row_bytes(layers, row):
    return [np.asarray(a[row]).tobytes()
            for a in jax.tree_util.tree_leaves(layers)]


def test_row_operations_on_a_pool_of_mixed_layers(model):
    """assign -> extract -> restore -> truncate -> reset on the model's own
    pool: full buffers of 4 K/V heads, rings of 8, keys 96 wide and so
    JOINED (a position's keys are one run of 384 or 768), values 64. The
    named row changes as specified, every other row keeps its bytes."""
    B = 3
    pool = model.new_cache(B, kv_len=CTX)["layers"]
    assert [(lc["k"].shape[1:], lc["v"].shape[1:]) for lc in pool] == [
        ((CTX, 384), (CTX, 4, 64)), ((16, 768), (16, 8, 64)),
        ((16, 768), (16, 8, 64)), ((CTX, 384), (CTX, 4, 64)),
        ((16, 768), (16, 8, 64))]
    rng = np.random.default_rng(39)
    for row in range(B):                    # every row starts non-empty
        _, pool = model.prefill_chunk(pool, row,
                                      rng.integers(1, 256, 5 + row), 0)

    def others_untouched(new, old, row):
        for r in set(range(B)) - {row}:
            assert _row_bytes(new, r) == _row_bytes(old, r), r

    ids = rng.integers(1, 256, 64).tolist()
    src = model.new_cache(1, kv_len=64)
    _, src = model.prefill(src, ids)
    keep = jax.tree_util.tree_map(jnp.copy, pool)
    out = model.slot_assign(pool, src, 1)               # pool is donated
    others_untouched(out, keep, 1)
    for i, lo in enumerate(out):
        held = np.sort(np.asarray(lo["pos"][1]))
        want = np.arange(64) if i in (0, 3) else np.arange(48, 64)
        np.testing.assert_array_equal(held[held >= 0], want)

    # block 1 of 32 (32..63): a full buffer gives all of it, a ring of 16
    # its last 16 positions, which is all the ring holds
    blk = model.slot_extract(out, 1, 32, 32)
    for i, lc in enumerate(blk):
        want = np.arange(32, 64) if i in (0, 3) else np.arange(48, 64)
        np.testing.assert_array_equal(np.asarray(lc["pos"][0]), want)
        src_lc = src["layers"][i]
        at = [int(np.where(np.asarray(src_lc["pos"][0]) == p)[0][0])
              for p in want]
        for name in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(lc[name][0]),
                                          np.asarray(src_lc[name][0])[at])

    wiped = model.slot_release(jax.tree_util.tree_map(jnp.copy, out), 2)
    keep = jax.tree_util.tree_map(jnp.copy, wiped)
    got = model.slot_restore(wiped, [blk], 2, 1, 32, True)
    others_untouched(got, keep, 2)
    for i, lc in enumerate(got):
        pos = np.asarray(lc["pos"][2])
        want = np.arange(32, 64) if i in (0, 3) else np.arange(48, 64)
        np.testing.assert_array_equal(np.sort(pos[pos >= 0]), want)
        if i not in (0, 3):                 # the ring, whole, by pos % 16
            assert _row_bytes([lc], 2) == _row_bytes([out[i]], 1)

    cut = truncate_layers(got, jnp.asarray(56))
    assert all(int(jnp.max(lc["pos"][2])) == 55 for lc in cut)
    keep = jax.tree_util.tree_map(jnp.copy, cut)
    clr = model.slot_release(cut, 1)
    others_untouched(clr, keep, 1)
    for lc in clr:
        for name, buf in lc.items():
            row = np.asarray(buf[1].astype(jnp.float32))
            assert (row == (-1 if name == "pos" else 0)).all(), name


# -- the prefix cache with a ring smaller than a block --------------------------

@pytest.mark.parametrize("family,window,chunk,paged", [
    ("mimo_v2", 16, 32, {}), ("mistral", 32, 16, {}),
    ("mimo_v2", 16, 32, {"kv_blocks": 24, "kv_block_tokens": 32})],
    ids=["ring_smaller_than_block", "ring_of_two_blocks",
         "joined_keys_in_paged_blocks"])
def test_prefix_hits_of_any_length_give_the_tokens_of_a_miss(family, window,
                                                             chunk, paged):
    """Built for a 16-token ring under 32-token blocks (it used to gate
    itself off, silently); a full-chain hit, a partial-chain hit and a miss
    give the same greedy tokens, as they do where window >= block. The
    blocks hold MiMo-V2's keys joined, in a row's leaves and in a paged
    pool's."""
    m = TextModel(tiny_config(family, sliding_window=window),
                  dtype=jnp.float32, max_cache_len=CTX)
    shared = [3 + (i * 11) % 200 for i in range(3 * chunk)]
    prompts = {"miss": shared + [7, 9, 11],
               "full": shared + [7, 9, 11],                 # 3 blocks
               "partial": shared[:2 * chunk] + [5] * 9,     # 2 of them
               "one": shared[:chunk] + [8] * (chunk + 3)}   # 1, then a miss
    eng = ServeEngine(m, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=chunk, prefix_cache_mb=64, **paged)
    try:
        assert eng.prefix_cache is not None
        assert (eng.paged is not None) == bool(paged)
        for name, hit in (("miss", 0), ("full", 3 * chunk),
                          ("partial", 2 * chunk), ("one", chunk)):
            want, _ = m.generate(list(prompts[name]), max_new_tokens=6,
                                 sampling=GREEDY)
            r = eng.submit(prompts[name], max_new_tokens=6, sampling=GREEDY)
            assert r.wait(300)
            assert r.stats["prefix_hit_tokens"] == hit, name
            assert r.result["tokens"] == want, name
        rec = [x for x in eng.flight.snapshot() if x["occupancy"]]
        rings = sum(s.window is not None for s in m.cfg.layer_specs())
        assert rec and all(
            x["ring_tokens"] == rings * min(x["kv_tokens"], window)
            for x in rec if x["occupancy"] == 1)
    finally:
        eng.close()


def test_engine_says_why_a_prefix_cache_it_was_asked_for_is_not_built(
        model, caplog, monkeypatch):
    from cake_tpu.serve.prefix_cache import PagedPrefixCache, PrefixCache
    assert PrefixCache.refusal(64, 32, 8) is None       # whatever the ring
    assert PrefixCache.refusal(64, 32, 0) == "capacity 0"
    assert "does not fit a row of 16" in PrefixCache.refusal(16, 32, 8)
    paged = types.SimpleNamespace(bt=32, ctx=64)
    assert "no multiple of the 32-token blocks" in \
        PagedPrefixCache.refusal_paged(paged, 16, 8)
    # the engine clamps its chunk to the row, so only a stand-in reason
    # reaches its log line
    monkeypatch.setattr(PrefixCache, "refusal",
                        staticmethod(lambda *a: "a reason"))
    with caplog.at_level("WARNING", logger="cake_tpu.serve"):
        eng = ServeEngine(model, slots=1, max_queue=2, ctx_len=64,
                          prefill_chunk=16, prefix_cache_mb=8)
    try:
        assert eng.prefix_cache is None
        assert "prefix cache of 8 MB asked for and not built: a reason" \
            in caplog.text
    finally:
        eng.close()


# -- the lowering ---------------------------------------------------------------

_SLICING = re.compile(
    r"stablehlo\.(slice|dynamic_slice|dynamic_update_slice)\b[^\n]*?"
    r":\s*\(?tensor<([^>]+)>")


def test_one_decode_program_that_never_slices_the_pool(model):
    slots = 4
    layers = model.new_cache(slots, kv_len=CTX)["layers"]
    z = lambda dt: jnp.zeros((slots,), dt)      # noqa: E731
    args = (model.params, layers, z(jnp.int32), z(jnp.int32),
            jnp.stack([jax.random.PRNGKey(i) for i in range(slots)]),
            # 8 recent tokens: a ring's `pos` leaf is [slots, 16] too
            jnp.full((slots, 8), -1, jnp.int32), z(jnp.float32),
            jnp.full((slots,), 256, jnp.int32), jnp.ones((slots,)),
            jnp.ones((slots,)), z(jnp.bool_))
    text = model._decode_slots.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    for scope in ("cake.attn", "cake.attn.window", "cake.ffn.route",
                  "cake.ffn.experts"):
        assert scope in text, scope
    leaf_types = {"x".join(map(str, a.shape)) + "x"
                  + {"float32": "f32", "int32": "i32"}[str(a.dtype)]
                  for a in jax.tree_util.tree_leaves(layers)}
    found = _SLICING.findall(text)
    assert found and not [t for _, t in found if t in leaf_types]
    st = list(args[2:])
    act = np.zeros(slots, bool)
    for n in range(slots + 1):              # every occupancy, one program
        act[:n] = True
        packed, layers, *st[:4] = model.decode_slots(
            layers, *st[:8], jnp.asarray(act))
        assert np.asarray(packed).shape == (2, slots)
    assert model._decode_slots._cache_size() == 1


# -- checkpoints -----------------------------------------------------------------

@pytest.mark.parametrize("fused", [False, True], ids=["split", "fused_qkv"])
def test_loader_and_export_round_trip(tmp_path, fused):
    from cake_tpu.utils.export import params_to_hf_tensors
    from cake_tpu.utils.loaders import load_model_params
    from cake_tpu.utils.safetensors_io import save_safetensors
    cfg = config_from_hf_dict(TINY_HF)
    assert cfg.fused_qkv is False
    if fused:
        cfg = config_from_hf_dict(
            {**TINY_HF, "attention_projection_layout": "fused_qkv"})
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tensors = params_to_hf_tensors(cfg, params, fuse_phi=fused)
    names = set(tensors)
    assert ("model.layers.1.self_attn.qkv_proj.weight" in names) == fused
    assert ("model.layers.1.self_attn.k_proj.weight" in names) != fused
    assert "model.layers.1.self_attn.attention_sink_bias" in names
    assert "model.layers.0.self_attn.attention_sink_bias" not in names
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in names
    assert "model.layers.0.mlp.gate_proj.weight" in names   # dense, unfused
    assert tensors["model.layers.1.mlp.gate.weight"].shape == (8, 64)
    assert "model.layers.1.mlp.experts.3.down_proj.weight" in names
    assert "model.layers.1.mlp.experts.4.down_proj.weight" not in names
    save_safetensors(str(tmp_path / "model.safetensors"), tensors)
    with open(tmp_path / "config.json", "w") as f:
        json.dump(TINY_HF, f)
    loaded = load_model_params(cfg, str(tmp_path), jnp.bfloat16)
    got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    for path, want in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        f32 = name.endswith("['e_score_correction_bias']") or "rope" in name
        assert got[path].dtype == (jnp.float32 if f32 else jnp.bfloat16), name
        np.testing.assert_allclose(
            np.asarray(got[path], np.float32), np.asarray(want, np.float32),
            atol=0 if f32 else 2e-2, err_msg=name)


# -- keys joined in the pool --------------------------------------------------------

def _by_head(lc, hkv):
    """A layer cache with joined keys as the same keys by head."""
    return {**lc, "k": lc["k"].reshape(lc["k"].shape[:2] + (hkv, -1))}


@pytest.mark.parametrize("valid_len", [1, 0], ids=["stepping", "masked_out"])
@pytest.mark.parametrize("kind,s", [
    ("full", 1), ("window", 1), ("full", 4), ("window", 24)],
    ids=["full_decode", "ring_decode_sink", "full_verify_width",
         "ring_chunk_sink"])
def test_joined_keys_read_as_the_keys_by_head(model, kind, s, valid_len):
    """attention_forward over a cache that holds its keys joined against
    the same cache with its keys by head, float32 to 1e-6, and the caches
    they leave equal to the byte: a decode step and a verify width (queries
    spread over the joined width, the keys as they lie), a chunk against a
    ring (the ring's heads split), with and without a sink, and a row that
    valid_len 0 masks out of the step."""
    from cake_tpu.models.common.layers import attention_forward
    cfg = model.cfg
    i, spec = next((i, sp) for i, sp in enumerate(cfg.layer_specs())
                   if (sp.window is not None) == (kind == "window"))
    assert spec.sink == (kind == "window")
    a = cfg.attn_shape(spec)
    p = model.params["layers"][i]["self_attn"]
    rows, held = 3, 21                      # past the ring's 16: it wrapped
    lc = model.new_cache(rows, kv_len=CTX)["layers"][i]
    assert lc["k"].shape[2:] == (a.size_k,)
    ks = jax.random.split(jax.random.PRNGKey(40), 3)
    size = lc["k"].shape[1]
    pos = np.full((rows, size), -1, np.int32)
    for t in range(max(held - size, 0), held):
        pos[:, t % size] = t
    pos[2] = -1                             # a row that holds nothing
    lc = {"k": jax.random.normal(ks[0], lc["k"].shape),
          "v": jax.random.normal(ks[1], lc["v"].shape),
          "pos": jnp.asarray(pos)}
    x = jax.random.normal(ks[2], (rows, s, cfg.hidden_size))
    args = (jnp.asarray(held, jnp.int32), model.params["rope"],
            jnp.asarray(valid_len * s, jnp.int32))
    got, new = attention_forward(cfg, spec, p, x, lc, *args)
    want, ref = attention_forward(cfg, spec, p, x, _by_head(lc, a.kv_heads),
                                  *args)
    assert ref["k"].ndim == 4 and new["k"].ndim == 3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert np.abs(np.asarray(want)).max() > 1e-3
    for name in ("k", "v", "pos"):
        np.testing.assert_array_equal(
            np.asarray(new[name]), np.asarray(ref[name]).reshape(
                new[name].shape))
    if not valid_len:                       # nothing was written
        np.testing.assert_array_equal(np.asarray(new["k"]),
                                      np.asarray(lc["k"]))


def test_spread_queries_put_each_head_on_its_own_keys():
    from cake_tpu.ops.attention import _spread_queries
    qf = jnp.arange(1, 2 * 3 * 2 * 4 + 1, dtype=jnp.float32).reshape(
        1, 2, 3, 2, 4)                      # [B, Sq, Hkv, G, D]
    qs = np.asarray(_spread_queries(qf)).reshape(1, 2, 3, 2, 3, 4)
    for k in range(3):
        for other in range(3):
            want = np.asarray(qf[:, :, k]) if other == k else 0
            np.testing.assert_array_equal(qs[:, :, k, :, other], want)


def test_engine_says_which_layers_hold_joined_keys(model):
    from cake_tpu.models.common.cache import joined_key_widths
    for paged in (False, True):
        eng = ServeEngine(model, slots=2, max_queue=2, ctx_len=CTX,
                          prefill_chunk=32,
                          **({"kv_blocks": 16, "kv_block_tokens": 16}
                             if paged else {}))
        try:
            want = [{"width": 384, "layers": 2}, {"width": 768, "layers": 3}]
            assert eng.health()["kv_pool"]["joined_keys"] == want
            assert eng.flight.static["joined_keys"] == want
            assert ("occupancy" in eng.health()["kv_pool"]) == paged
        finally:
            eng.close()
    plain = TextModel(tiny_config("qwen3"), dtype=jnp.float32,
                      max_cache_len=CTX)
    assert joined_key_widths(plain.new_cache(2)["layers"]) == {}


# the families of the benchmark's other cells at tiny widths and their
# published key width of 128, where no leaf is joined
_OLDER = {
    "qwen3": dict(head_dim=128),
    "qwen3_moe": dict(head_dim=128),
    "jamba": dict(num_attention_heads=2, num_key_value_heads=1,
                  hidden_size=256),
}
_PROGRAMS = [("decode", 1), ("fresh", 32), ("fresh", 256), ("append", 32),
             ("append", 256)]


def _lowered(m, program, slots=4):
    layers = jax.eval_shape(lambda: m.new_cache(slots, kv_len=512)["layers"])
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: m.params)
    mode, width = program
    if mode == "decode":
        z = lambda dt: sds((slots,), dt)    # noqa: E731
        traced = m._decode_slots.trace(
            params, layers, z(jnp.int32), z(jnp.int32),
            sds((slots, 2), jnp.uint32), sds((slots, 8), jnp.int32),
            z(jnp.float32), z(jnp.int32), z(jnp.float32), z(jnp.float32),
            z(jnp.bool_))
    else:
        i32 = sds((), jnp.int32)
        traced = m._prefill_slot.trace(
            params, sds((1, width), jnp.int32), layers, i32, i32, i32,
            flash_mode=mode)
    return traced.lower(lowering_platforms=("tpu",)).as_text()


@pytest.fixture(scope="module")
def older_models():
    return {fam: TextModel(tiny_config(fam, max_position_embeddings=512,
                                       **over), dtype=jnp.bfloat16,
                           max_cache_len=512)
            for fam, over in _OLDER.items()}


@pytest.mark.parametrize("program", _PROGRAMS,
                         ids=[f"{m}{w}" for m, w in _PROGRAMS])
@pytest.mark.parametrize("family", list(_OLDER))
def test_programs_of_128_wide_keys_do_not_reach_the_joined_code(
        older_models, monkeypatch, family, program):
    """The 15 programs of the benchmark's older cells (`_decode_slots`,
    `_prefill_slot` fresh and append at 32 and 256 tokens, three families;
    Pallas kernels off) lower to the same bytes with everything this rule
    added taken away: where keys are 128 wide no leaf is joined, no query
    is spread and no key is reshaped (PR 40 also compared the benchmark's
    own configurations against its parent: 15 of 15, PERF.md)."""
    from cake_tpu.models.common import cache, layers
    from cake_tpu.ops import attention
    m = older_models[family]
    assert not cache.joined_key_widths(m.new_cache(1)["layers"])
    want = _lowered(m, program)

    def never(*a, **k):
        raise AssertionError("the joined-keys code was reached")

    monkeypatch.setattr(attention, "_spread_queries", never)
    monkeypatch.setattr(cache, "keys_joined", lambda lc: False)
    monkeypatch.setattr(layers, "keys_joined", lambda lc: False)
    m2 = TextModel.__new__(TextModel)       # the same programs, traced anew
    m2.__dict__.update(m.__dict__)
    m2._build()
    assert _lowered(m2, program) == want


# -- which path each layer kind takes ----------------------------------------------

def test_attention_paths_by_layer_kind(monkeypatch):
    """Where the Pallas kernels are on: a window layer with a sink keeps
    the masked path in every program; a full layer prefills through the
    flash kernel (keys 192, values 128) and decodes masked: the decode
    kernel takes keys and values of one width."""
    from cake_tpu.ops import flash
    monkeypatch.setattr(flash, "flash_enabled", lambda: True)
    for mode in ("fresh", "append"):
        assert flash_kernel_mode(mode, 256, 128, True, sink=True) is None
        assert flash_kernel_mode(mode, 256, None, True, sink=False) == mode
    assert flash_kernel_mode("fresh", 256, 128, True, sink=False) == "fresh"

    def cache(dk, dv, t=512, hkv=4):
        return {"k": jnp.zeros((2, t, hkv, dk), jnp.bfloat16),
                "v": jnp.zeros((2, t, hkv, dv), jnp.bfloat16),
                "pos": jnp.zeros((2, t), jnp.int32)}

    assert decode_kernel_block(1, None, cache(128, 128), jnp.bfloat16) == 256
    assert decode_kernel_block(1, None, cache(192, 128), jnp.bfloat16) is None
    assert decode_kernel_block(1, None, cache(128, 128), jnp.bfloat16,
                               sink=True) is None
    assert decode_kernel_block(1, 128, cache(192, 128, t=128, hkv=8),
                               jnp.bfloat16, sink=True) is None
    joined = cache(192, 128)        # as the pool holds keys of 192
    joined["k"] = joined["k"].reshape(2, 512, 4 * 192)
    assert decode_kernel_block(1, None, joined, jnp.bfloat16) is None


def test_flash_kernel_with_keys_wider_than_values_matches_masked():
    from cake_tpu.ops.flash import flash_attention
    b, s, hq, hkv, d, dv = 1, 256, 4, 2, 48, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, dv))
    pos = jnp.arange(s)[None]
    want = multi_head_attention(q, k, v, make_attention_mask(pos, pos))
    got = flash_attention(q, k, v, interpret=True)
    assert got.shape == (b, s, hq, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    off = 128                       # append: a chunk behind 128 cached keys
    got = flash_attention(q[:, off:], k, v, q_offset=off, valid_len=s - off,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, off:]),
                               atol=2e-5)


@pytest.mark.parametrize("step", ["chunk", "decode"])
def test_tp_over_four_virtual_devices_gives_the_single_device_logits(step):
    """The joined K leaves split over `tp` on their last axis, whole heads
    a device (4 x 96 and 8 x 96 over 4); a chunk into a pool row, and a
    decode step's spread-query read of the sharded leaf."""
    from jax.sharding import Mesh
    cfg = tiny_config("mimo_v2")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = [3 + (i * 7) % 200 for i in range(40)]
    want = None
    for mesh in (None, Mesh(np.asarray(jax.devices()[:4]).reshape(4),
                            ("tp",))):
        m = TextModel(cfg, params, dtype=jnp.float32, max_cache_len=CTX,
                      mesh=mesh)
        if step == "chunk":
            layers = m.new_cache(2, kv_len=CTX)["layers"]
            assert [lc["k"].ndim for lc in layers] == [3] * 5
            if mesh is not None:    # a device holds one head of four
                assert layers[0]["k"].sharding.shard_shape(
                    layers[0]["k"].shape) == (2, CTX, 96)
            logits, layers = m.prefill_chunk(layers, 1, ids, 0)
        else:
            _, cache = m.prefill(m.new_cache(1, kv_len=CTX), ids)
            logits, _ = m.decode_logits(cache, 17)
        got = np.asarray(logits[0])
        if want is None:
            want = got
    np.testing.assert_allclose(got, want, atol=2e-4)
