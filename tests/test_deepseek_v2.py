"""DeepSeek-V2 on the served path: latent attention (MLA) whose pool rows
hold ONE vector a token (the normed latent beside the roped shared key
part), a step that reads a cache ABSORBED and a stateless pass EXPANDED,
group-limited routing with an unnormalised, scaled softmax top-k, YaRN's
factor in the softmax scale, rope rows de-interleaved at load.

Pinned here:
  * the adapter: the benchmark's configuration resolves to the layer kind,
    the row's shape, the scale and the share the issue states; what it
    cannot honour it refuses;
  * the program against the benchmark's plain reference
    (benchmark/reference/deepseek_v2.py, expanded form only) through the
    three steps of benchmark/check.py (chunked prefill, decode through the
    pool, a chunk behind it), equal in float32 at every point, whole and as
    a share; the stateless pass (expanded) alike; the five mechanism
    controls each over the bf16 reading;
  * the kernel `cake_latent_decode_attention`, interpreted, against XLA's
    read, and the mixer with the kernel in against the mixer without;
  * group-limited top-k against one written by hand, and `n_group` 1
    lowering to the program it lowered to before;
  * the eight shares add up, the shared experts counted once;
  * an HF-named checkpoint with INTERLEAVED rope rows through the loader
    and back through the exporter;
  * prefix-cache hits (`slot_restore`), the paged pool and `slot_verify`
    (accepted whole, rejected and rolled back) over latent leaves give the
    reference's greedy tokens;
  * the scopes in the lowering, `attention_kinds()` with the row's bytes,
    `--tp 4` on four virtual devices.
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
from cake_tpu.models.common.cache import init_cache, latent_row_width
from cake_tpu.models.common.config import (AttnShape, LatentAttnConfig,
                                           config_from_hf_dict)
from cake_tpu.models.common.layers import (forward_train, make_rope,
                                           moe_forward)
from cake_tpu.ops.moe import group_limited, router_topk
from cake_tpu.ops.rope import RopeScaling, apply_rope
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
GREEDY = SamplingConfig(temperature=0.0)
CTX = 128

# the published DeepSeek-V2 keys at tiny widths: 4 heads of 16 + 8 with
# values of 16 through ranks 24 and 32 (a row of 40 numbers in 128 lanes),
# layer 0 dense, then the second share of 4 of 8 experts in 4 groups of 2
# of which a token keeps 2, top 3, two shared experts
TINY_HF = {
    "model_type": "deepseek_v2", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "max_position_embeddings": 512, "attention_bias": False,
    "rms_norm_eps": 1e-6, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16},
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_routed_experts": 4,
    "n_shared_experts": 2, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "n_group": 4, "topk_group": 2,
    "topk_method": "group_limited_greedy", "scoring_func": "softmax",
    "norm_topk_prob": False, "routed_scaling_factor": 16,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "expert_parallel": {"size": 2, "rank": 1},
}
WHOLE_HF = {**TINY_HF, "n_routed_experts": 8, "expert_parallel": None}
# reference/deepseek_v2.py's initialisers at hidden 64, so that they lead to
# the numbers they lead to at 5,120: q 0.78 a number, k_pe and an FFN's gate
# and up 1.43, k_nope and v 1.8, an embedding of 1.07 and sublayers that add
# 0.15-0.3 a channel to it
TINY_INIT = dict(Q_B_STD=0.16, KV_A_STD=0.18, KV_B_STD=0.32,
                 O_PROJ_STD=0.04, FFN_IN_STD=0.18, RESIDUAL_STD=0.02,
                 EXPERT_DOWN_STD=0.01, EMBED_SCALE=6.7)


@pytest.fixture(scope="module")
def bench():
    """benchmark/ importable: check.py, weights.py, the reference, with
    the reference's initialisers set for these widths."""
    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name)
                for name in ("check", "weights", "reference.deepseek_v2")}
        ref = mods["reference.deepseek_v2"]
        was = {k: getattr(ref, k) for k in TINY_INIT}
        for k, v in TINY_INIT.items():
            setattr(ref, k, v)
        yield mods
        for k, v in was.items():
            setattr(ref, k, v)
    finally:
        sys.path.remove(BENCH)


def _model(bench, hf, seed=56, dtype=jnp.float32, ctx=CTX):
    W, ref = bench["weights"], bench["reference.deepseek_v2"]
    cfg = config_from_hf_dict(hf)
    w = W.make_weights(ref, hf, seed, dtype)
    return TextModel(cfg, {**w, "rope": make_rope(cfg)}, dtype=dtype,
                     seed=1, max_cache_len=ctx), w


# -- the adapter --------------------------------------------------------------

def test_adapter_resolves_the_benchmarks_configuration():
    with open(os.path.join(BENCH, "configs",
                           "deepseek-v2-l5-ep8.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf_dict(hf)
    assert cfg.arch == "deepseek_v2"
    specs = cfg.layer_specs()
    assert [s.kind for s in specs] == ["latent"] * 5
    assert [s.is_moe for s in specs] == [False] + [True] * 4
    assert not any(s.recurrent or s.window for s in specs)
    assert cfg.latent_attn == LatentAttnConfig(1536, 512, 128, 64, 128)
    assert cfg.latent_attn.row_width == 576
    assert cfg.attn_shape(specs[0]) == AttnShape(128, 1, 576, 512,
                                                 latent=True)
    assert cfg.rotary_dim == 64 and cfg.rope_theta == 10000.0
    # m = 0.1 x 0.707 x ln 40 + 1 = 1.26080; s = 192^-1/2 m^2
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    np.testing.assert_allclose(m, 1.26080, rtol=1e-5)
    np.testing.assert_allclose(cfg.attn_scale, 0.114721, rtol=1e-5)
    np.testing.assert_allclose(cfg.attn_scale, 192 ** -0.5 * m * m,
                               rtol=1e-12)
    # cos and sin carry mscale / mscale_all_dim = 1.0, never YaRN's default
    assert cfg.rope_scaling == RopeScaling(
        factor=40.0, original_max_position_embeddings=4096,
        rope_type="yarn", beta_fast=32.0, beta_slow=1.0,
        attention_factor=1.0)
    np.testing.assert_allclose(np.asarray(make_rope(
        config_from_hf_dict({**hf, "max_position_embeddings": 64})
    )["cos"][0]), 1.0)
    assert (cfg.num_experts, cfg.router_width, cfg.expert_first,
            cfg.num_experts_per_tok) == (20, 160, 0, 6)
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (8, 3)
    assert cfg.moe_routed_scale == 16.0 and not cfg.norm_topk_prob
    assert cfg.moe_gate_act == "softmax" and not cfg.moe_select_bias
    assert cfg.shared_expert_intermediate_size == 3072
    assert not cfg.shared_expert_gated
    assert (cfg.vocab_size, cfg.rms_norm_eps) == (12800, 1e-6)
    assert cfg.attention_kinds() == [{
        "kind": "latent", "layers": 5, "heads": 128, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "row_width": 576,
        "row_lanes": 640, "rotary_dim": 64, "rope_theta": 10000.0,
        "rope_scaling": "yarn", "row_bytes": 5 * 1152}]
    # the pool's leaf: one vector a position, in whole lane tiles
    lc = jax.eval_shape(lambda: init_cache(cfg, 2, 256)["layers"][0])
    assert {k: v.shape for k, v in lc.items()} == {
        "kv": (2, 256, 640), "pos": (2, 256)}
    assert latent_row_width(576) == 640 and latent_row_width(512) == 512
    # without the key a process holds every expert; greedy is plain top-k
    whole = config_from_hf_dict({**hf, "n_routed_experts": 160,
                                 "expert_parallel": None})
    assert (whole.router_width, whole.expert_first) == (160, 0)
    plain = config_from_hf_dict({**hf, "topk_method": "greedy"})
    assert (plain.moe_n_group, plain.moe_topk_group) == (1, 1)
    assert config_from_hf_dict(
        {**hf, "architectures": ["DeepseekV2ForCausalLM"],
         "model_type": "x"}).arch == "deepseek_v2"


@pytest.mark.parametrize("key,value,says", [
    ("q_lora_rank", None, "q_lora_rank null"),
    ("topk_method", "noaux_tc", "topk_method 'noaux_tc'"),
    ("scoring_func", "sigmoid", "scoring_func 'sigmoid'"),
    ("moe_layer_freq", 2, "moe_layer_freq 2"),
    ("attention_bias", True, "attention_bias"),
    ("norm_topk_prob", True, "norm_topk_prob"),
    ("rope_scaling", {"type": "dynamic", "factor": 4}, "only yarn"),
    ("n_group", 3, "whole groups"),
    ("expert_parallel", {"size": 8, "rank": 0}, "whole groups"),
    ("expert_parallel", {"size": 2, "rank": 2}, "rank 2 of 2"),
], ids=["full_rank_q", "noaux", "sigmoid", "layer_freq", "bias",
        "norm_and_scale", "dynamic_rope", "groups_divide", "share_in_group",
        "rank"])
def test_adapter_refuses_what_it_cannot_honour(key, value, says):
    with pytest.raises(ValueError, match=says):
        config_from_hf_dict({**TINY_HF, key: value})


# -- the program against the plain reference ----------------------------------

# what the bf16 program may differ from the float32 reference by (pooled
# relative RMS through the check) at these widths: the readings are 0.012
# (whole) and 0.032 (share); every mechanism's control reads 0.18 or more
BF16_TOLERANCE = 0.08
CONTROLS = {"rope_pe": {"rope_pe": "off"}, "kv_norm": {"kv_norm": "off"},
            "mscale": {"mscale": 1.0}, "groups": {"groups": "off"},
            "routed_scale": {"routed_scale": 1.0}}


@pytest.mark.parametrize("hf", [TINY_HF, WHOLE_HF], ids=["share", "whole"])
def test_program_equals_the_reference_through_the_check(bench, hf):
    check, ref = bench["check"], bench["reference.deepseek_v2"]
    cfg = config_from_hf_dict(hf)
    assert ref.share(hf)[:2] == (cfg.router_width, cfg.expert_first)
    seed = 2 ** 31 + 56
    sound = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        m, w = _model(bench, hf, seed, dtype, ctx=256)
        served = check.served_logits(
            m, 4, 256, 32, check.check_ids(seed, 512, [20, 90]), 3,
            {"temperature": 0.7, "top_p": 0.9})
        got = check.compare(ref, hf, w, served)
        sound[dtype] = got["pooled"]
        if dtype == jnp.float32:
            # chunks of 32 with a last bucket of 26 padded to 32, decode
            # with rows 0 and 3 of 4 active (absorbed), a chunk behind it:
            # every point equals the EXPANDED reference
            assert len(got["points"]) == 6 and got["worst"] < 2e-5, got
            assert any("tail_after_decode" in k for k in got["points"])
    assert sound[jnp.bfloat16] < BF16_TOLERANCE, sound
    read = {}
    for name, kw in CONTROLS.items():
        alt = types.SimpleNamespace(
            forward_logits=lambda h, ww, ids, pos, quant=None, kw=kw: (
                ref.forward_logits(h, ww, ids, pos,
                                   **(kw if quant else {}))))
        read[name] = check.control(alt, hf, w, served, name)["pooled"]
    assert all(v > BF16_TOLERANCE for v in read.values()), (read, sound)
    used, needed = ref.experts_used(hf, w, served[-1]["ids"])
    assert needed == hf["n_routed_experts"] and used == needed


def test_the_stateless_pass_is_the_expanded_form_and_equals_the_reference(
        bench):
    ref = bench["reference.deepseek_v2"]
    m, w = _model(bench, TINY_HF)
    ids = [3 + (i * 11) % 500 for i in range(70)]
    text = jax.jit(lambda p, t: forward_train(m.cfg, p, t)).lower(
        m.params, jnp.asarray([ids])).as_text(debug_info=True)
    assert "cake.attn.latent.expand" in text
    assert "cake.attn.latent.absorb" not in text
    got = np.asarray(forward_train(m.cfg, m.params, jnp.asarray([ids]))[0])
    want = ref.forward_logits(TINY_HF, w, ids, list(range(len(ids))))
    np.testing.assert_allclose(got, want, atol=3e-5 * np.abs(want).max())


# -- the kernel ------------------------------------------------------------------

def _rows(key, b, s, h, d, t, held):
    ks = jax.random.split(key, 2)
    q = jax.random.normal(ks[0], (b, s, h, d))
    kv = jax.random.normal(ks[1], (b, t, d))
    held = jnp.asarray(held, jnp.int32)
    idx = jnp.arange(t)[None, :]
    return q, kv, jnp.where(idx < held[:, None], idx, -1).astype(jnp.int32)


KERNEL_CASES = {
    # name: (s, heads, buffer, pos0 a row, limit a row, holes (row, index))
    "decode_rows": (1, 8, 512, [5, 300, 0, 511], [6, 301, 0, 512], ()),
    "chunk": (16, 8, 512, [200], [216], ()),
    "padded_chunk": (16, 8, 512, [200], [209], ()),
    "heads128": (8, 128, 512, [0, 130], [8, 138], ()),
    # the decode body: a row's key steps (2048 latents = 4 blocks where the
    # buffer divides) are a loop inside the kernel. A frontier below one
    # block, inside the first step's second block, at an exact multiple of
    # the step, one past it, two steps on, and at the buffer's end
    "decode_h128_frontiers": (
        1, 128, 6144, [99, 700, 2047, 2048, 4500, 6143],
        [100, 701, 2048, 2049, 4501, 6144], ()),
    "decode_h32_frontiers": (
        1, 32, 6144, [99, 700, 2047, 2048, 4500, 6143],
        [100, 701, 2048, 2049, 4501, 6144], ()),
    # rows the step masks out (limit 0) between live rows, first and last
    "decode_h128_idle_rows_between": (
        1, 128, 4096, [0, 3000, 0, 0, 40, 2100, 9],
        [0, 3001, 0, 0, 41, 2101, 0], ()),
    "decode_h32_idle_rows_between": (
        1, 32, 4096, [2500, 7, 4095, 0], [2501, 0, 4096, 0], ()),
    # buffers no 2048 divides: key steps of 1024 (two blocks), of one block
    # of 512, of one block of 128
    "decode_only_1024_divides": (1, 32, 3072, [511, 1023, 1024, 3071],
                                 [512, 1024, 1025, 3072], ()),
    "decode_only_512_divides": (1, 32, 1536, [511, 512, 1535, 0],
                                [512, 513, 1536, 0], ()),
    "decode_only_128_divides": (1, 8, 640, [127, 128, 300, 639],
                                [128, 129, 301, 640], ()),
    # the whole mask stands on every block: an empty entry (-1) BELOW a
    # row's frontier, in an interior key step and in the last, is not read
    "decode_h128_hole_below_the_frontier": (
        1, 128, 4096, [3700, 2100], [3701, 2101],
        ((0, 3), (0, 600), (0, 2050), (0, 3699), (1, 2048), (1, 511))),
    "decode_h32_hole_below_the_frontier": (
        1, 32, 1536, [1400, 520], [1401, 521], ((0, 0), (0, 1399), (1, 512))),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_kernel_interpreted_matches_the_masked_read(case):
    """A decode step walks each row to its own frontier in key steps of
    several blocks where the buffer divides, and gives zeros for a row the
    step masks out (limit 0); a chunk's query blocks hold tq tokens x all
    heads and each token sees its own prefix."""
    from cake_tpu.ops.latent_attention import (latent_attention,
                                               latent_read, query_tokens)
    s, h, t, pos0, limit, holes = KERNEL_CASES[case]
    b, d, dv = len(pos0), 128, 96
    q, kv, kv_pos = _rows(jax.random.PRNGKey(5), b, s, h, d, t, limit)
    for row, index in holes:
        kv_pos = kv_pos.at[row, index].set(-1)
    pos0, limit = jnp.asarray(pos0, jnp.int32), jnp.asarray(limit, jnp.int32)
    got = latent_attention(q, kv, kv_pos, pos0, limit, dv, 0.1,
                           interpret=True)
    want = latent_read(q, kv, kv_pos,
                       pos0[:, None] + jnp.arange(s)[None, :], dv, 0.1)
    live = np.asarray(limit) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=3e-6)
    assert not np.asarray(got)[~live].any()
    assert query_tokens(s, h) * h <= max(512, h)


@pytest.mark.parametrize("s", [16, 3, 2, "slot_verify"])
def test_a_chunk_and_a_verify_step_never_reach_the_decode_body(
        s, monkeypatch):
    """The form is chosen by shape: a call of more than one token a row (a
    chunk; `slot_verify`'s [last token, drafts], whose odd lengths have
    query blocks of ONE token) keeps `_latent_kernel`, a decode step alone
    takes `_decode_kernel`."""
    import functools

    from cake_tpu.models import deepseek_v2
    from cake_tpu.ops import latent_attention as la
    traced = []

    def never(*a, **k):
        raise AssertionError("the decode body was traced")

    def chunk_body(*a, _kernel=la._latent_kernel, **k):
        traced.append(k["tq"])
        return _kernel(*a, **k)

    monkeypatch.setattr(la, "_decode_kernel", never)
    monkeypatch.setattr(la, "_latent_kernel", chunk_body)
    la._entry.cache_clear()
    try:
        if s == "slot_verify":
            monkeypatch.setattr(deepseek_v2, "kernel_enabled", lambda: True)
            monkeypatch.setattr(
                deepseek_v2, "latent_attention", functools.partial(
                    deepseek_v2.latent_attention, interpret=True))
            m = TextModel(tiny_config("deepseek_v2"), dtype=jnp.float32,
                          max_cache_len=CTX)
            prompt = [3 + (i * 11) % 200 for i in range(20)]
            _, cache = m.prefill(m.new_cache(1, kv_len=CTX), prompt)
            packed, cache, _ = m.verify_tokens(
                cache, 7, [9, 11], 2, len(prompt), jax.random.PRNGKey(0),
                jnp.full((4,), -1, jnp.int32), GREEDY)
            assert np.asarray(packed).shape == (2,)
            # the prompt's chunk and the verify step of 3 tokens (query
            # blocks of one token: 3 is odd), every latent layer
            assert 1 in traced and max(traced) > 1, traced
            with pytest.raises(AssertionError, match="decode body"):
                m.decode_logits(cache, 5)
            return
        h, d, dv, t = 8, 128, 96, 1024
        q, kv, kv_pos = _rows(jax.random.PRNGKey(s), 2, s, h, d, t,
                              [700 + s, 30 + s])
        pos0 = jnp.asarray([700, 30], jnp.int32)
        got = la.latent_attention(q, kv, kv_pos, pos0, pos0 + s, dv, 0.1,
                                  interpret=True)
        want = la.latent_read(q, kv, kv_pos,
                              pos0[:, None] + jnp.arange(s)[None, :], dv, 0.1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-6)
        assert traced == [la.query_tokens(s, h)]
        with pytest.raises(AssertionError, match="decode body"):
            la.latent_attention(q[:, :1], kv, kv_pos, pos0, pos0 + 1, dv,
                                0.1, interpret=True)
    finally:
        la._entry.cache_clear()


def test_the_mixer_with_the_kernel_in_equals_the_mixer_without(monkeypatch):
    """`_decode_slots` (vmapped rows merged into the kernel's row axis) and
    a chunk, the kernel interpreted."""
    import functools

    from cake_tpu.models import deepseek_v2
    cfg = tiny_config("deepseek_v2")
    params = jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim >= 2 else a,
        init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    ids = [3 + (i * 7) % 200 for i in range(40)]

    def run():
        m = TextModel(cfg, params, dtype=jnp.float32, max_cache_len=CTX)
        layers = m.new_cache(2, kv_len=CTX)["layers"]
        a, layers = m.prefill_chunk(layers, 1, ids[:32], 0)
        b, layers = m.prefill_chunk(layers, 1, ids[32:], 32)
        text = m._decode_slots.lower(
            m.params, layers, jnp.zeros((2,), jnp.int32),
            jnp.asarray([0, 40], jnp.int32),
            jnp.stack([jax.random.PRNGKey(i) for i in range(2)]),
            jnp.full((2, 32), -1, jnp.int32), jnp.zeros((2,)),
            jnp.full((2,), 256, jnp.int32), jnp.ones((2,)), jnp.ones((2,)),
            jnp.asarray([False, True])).as_text()
        packed, *_ = m.decode_slots(
            layers, jnp.asarray([0, 9], jnp.int32),
            jnp.asarray([0, 40], jnp.int32),
            jnp.stack([jax.random.PRNGKey(i) for i in range(2)]),
            jnp.full((2, 32), -1, jnp.int32), jnp.zeros((2,)),
            jnp.full((2,), 256, jnp.int32), jnp.ones((2,)), jnp.ones((2,)),
            jnp.asarray([False, True]), nb=2)
        return np.asarray(a), np.asarray(b), np.asarray(packed), text

    plain = run()
    assert "cake_latent_decode_attention" not in plain[3]
    monkeypatch.setattr(deepseek_v2, "kernel_enabled", lambda: True)
    monkeypatch.setattr(deepseek_v2, "latent_attention", functools.partial(
        deepseek_v2.latent_attention, interpret=True))
    kernel = run()
    for got, want in zip(kernel[:2], plain[:2]):
        np.testing.assert_allclose(got, want, atol=2e-5)
    assert (kernel[2] == plain[2]).all()


# -- group-limited routing -------------------------------------------------------

def test_group_limited_top_k_against_one_written_by_hand():
    """8 groups of 20, the 3 best by their best member, top 6 of what is
    left, weights as scored times 16."""
    t, e, g, m, k = 64, 160, 8, 3, 6
    logits = jax.random.normal(jax.random.PRNGKey(3), (t, e)) * 0.8
    w, idx = router_topk(logits, k, False, "softmax", None, g, m)
    p = np.asarray(jax.nn.softmax(logits.astype(jnp.float32), -1))
    for row in range(t):
        best = p[row].reshape(g, -1).max(-1)
        kept = np.argsort(-best, kind="stable")[:m]
        masked = np.where(np.isin(np.arange(e) // (e // g), kept),
                          p[row], 0.0)
        want = np.argsort(-masked, kind="stable")[:k]
        assert sorted(np.asarray(idx[row])) == sorted(want)
        assert set(np.asarray(idx[row]) // 20) <= set(kept)
        np.testing.assert_allclose(np.sort(np.asarray(w[row])),
                                   np.sort(p[row][want]), rtol=1e-6)
    # plain top-k reaches more groups than a token may keep
    _, plain = router_topk(logits, k, False)
    assert max(len(set(r // 20)) for r in np.asarray(plain)) > m
    # the mask alone: what is outside the kept groups is 0, the rest as it was
    kept = np.asarray(group_limited(jnp.asarray(p), g, m))
    assert ((kept == 0) | (kept == p)).all()
    assert ((kept.reshape(t, g, -1) > 0).any(-1).sum(-1) == m).all()


def test_one_group_lowers_to_the_program_it_lowered_to_before():
    """`n_group` 1 / `topk_group` 1 (every other family) traces nothing
    more: the same lowering as a call that does not know the arguments."""
    logits = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    for norm, act, bias in ((True, "softmax", None), (False, "sigmoid", 1)):
        b = None if bias is None else jnp.ones((128,))
        new = jax.jit(lambda lg: router_topk(lg, 8, norm, act, b, 1, 1))
        old = jax.jit(lambda lg: router_topk(lg, 8, norm, act, b))
        assert new.lower(logits).as_text() == old.lower(logits).as_text()
    grouped = jax.jit(lambda lg: router_topk(lg, 8, True, "softmax", None,
                                             8, 3))
    assert grouped.lower(logits).as_text() != old.lower(logits).as_text()


# -- the shares add up ----------------------------------------------------------

def test_the_eight_shares_add_up_with_the_shared_experts_counted_once(bench):
    """160 experts as 8 shares of 20, each exactly one of the router's 8
    groups, top 6 of the 3 best groups, unnormalised, times 16: the held
    experts' parts of all shares, plus the shared experts ONCE, equal the
    uncut reference's layer output (and the uncut program's)."""
    ref = bench["reference.deepseek_v2"]
    hf = {**WHOLE_HF, "n_routed_experts": 160, "num_experts_per_tok": 6,
          "n_group": 8, "topk_group": 3}
    cfg = config_from_hf_dict(hf)
    ks = jax.random.split(jax.random.PRNGKey(56), 8)
    h, im, e = 64, 32, 160

    def ffn(k, n):
        a, b, c = jax.random.split(k, 3)
        return {"gate_proj": {"weight": jax.random.normal(a, (n, h)) * 0.2},
                "up_proj": {"weight": jax.random.normal(b, (n, h)) * 0.2},
                "down_proj": {"weight": jax.random.normal(c, (h, n)) * 0.2}}

    p = {"gate": {"weight": jax.random.normal(ks[0], (e, h)) * 0.1},
         "experts": {"gate_proj": jax.random.normal(ks[1], (e, im, h)) * .2,
                     "up_proj": jax.random.normal(ks[2], (e, im, h)) * .2,
                     "down_proj": jax.random.normal(ks[3], (e, h, im)) * .2},
         "shared_expert": ffn(ks[4], 2 * im)}
    x = jax.random.normal(ks[5], (1, 24, h))
    c = dict(ref.static(hf))
    want, _ = ref.sparse_ffn(x[0], p, c)
    np.testing.assert_allclose(np.asarray(moe_forward(cfg, p, x)[0]),
                               np.asarray(want), atol=3e-5)
    shared = ref.sparse_ffn(x[0], p, c, routed_scale=0.0)[0]
    parts = []
    for rank in range(8):
        share_hf = {**hf, "n_routed_experts": 20,
                    "expert_parallel": {"size": 8, "rank": rank}}
        share_cfg = config_from_hf_dict(share_hf)
        assert (share_cfg.router_width, share_cfg.expert_first) == \
            (160, 20 * rank)
        held = {"gate": p["gate"], "experts": {
            k: v[20 * rank:20 * rank + 20] for k, v in p["experts"].items()}}
        parts.append(moe_forward(share_cfg, held, x)[0])
        # the reference given that share gives the program's part
        got, _ = ref.sparse_ffn(
            x[0], {**held, "shared_expert": p["shared_expert"]},
            dict(ref.static(share_hf)))
        np.testing.assert_allclose(np.asarray(parts[-1] + shared),
                                   np.asarray(got), atol=3e-5)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(want), atol=5e-5)
    # a token reaches at most 3 of the 8 shares, and some share each token
    reached = np.stack([np.abs(np.asarray(q)).max(-1) > 0 for q in parts])
    assert reached.sum(0).max() <= 3 and reached.sum(0).min() >= 1
    assert float(jnp.abs(shared).max()) > 1e-3


# -- checkpoints -----------------------------------------------------------------

def test_loader_and_export_round_trip_with_interleaved_rope_rows(tmp_path):
    from cake_tpu.utils.export import params_to_hf_tensors
    from cake_tpu.utils.loaders import load_model_params
    from cake_tpu.utils.safetensors_io import save_safetensors
    cfg = config_from_hf_dict(TINY_HF)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tensors = params_to_hf_tensors(cfg, params)
    names = set(tensors)
    sa = "model.layers.1.self_attn."
    for name, shape in (("q_a_proj", (24, 64)), ("q_a_layernorm", (24,)),
                        ("q_b_proj", (4 * 24, 24)),
                        ("kv_a_proj_with_mqa", (40, 64)),
                        ("kv_a_layernorm", (32,)),
                        ("kv_b_proj", (4 * 32, 32)), ("o_proj", (64, 64))):
        assert tensors[sa + name + ".weight"].shape == shape, name
    assert not {n for n in names if "q_proj" in n or "k_proj" in n}
    assert "model.layers.0.mlp.gate_proj.weight" in names       # dense
    assert tensors["model.layers.1.mlp.gate.weight"].shape == (8, 64)
    assert "model.layers.1.mlp.experts.3.down_proj.weight" in names
    assert "model.layers.1.mlp.experts.4.down_proj.weight" not in names
    assert "model.layers.1.mlp.shared_experts.up_proj.weight" in names
    assert "model.layers.1.mlp.shared_expert.up_proj.weight" not in names
    # the file's rope rows are INTERLEAVED: row 2i of a head's last 8 is the
    # tree's row i, row 2i + 1 its row 4 + i
    tree = np.asarray(params["layers"][1]["self_attn"]["q_b_proj"]["weight"]
                      ).reshape(4, 24, 24)
    filed = tensors[sa + "q_b_proj.weight"].reshape(4, 24, 24)
    np.testing.assert_array_equal(filed[:, :16], tree[:, :16])
    np.testing.assert_array_equal(filed[:, 16::2], tree[:, 16:20])
    np.testing.assert_array_equal(filed[:, 17::2], tree[:, 20:])
    kva = np.asarray(params["layers"][1]["self_attn"]["kv_a_proj_with_mqa"]
                     ["weight"])
    np.testing.assert_array_equal(
        tensors[sa + "kv_a_proj_with_mqa.weight"][32::2], kva[32:36])
    # and rope on the file's pairs is rope on the tree's halves: q . k is a
    # sum over the pairs, whatever their order
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 24))
    y = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 64))
    rope, pos = make_rope(cfg), jnp.arange(6) + 3

    def scores(wq, wk, interleaved):
        q = apply_rope((x @ wq.reshape(4, 24, 24)[0, 16:].T)[:, :, None],
                       rope["cos"], rope["sin"], pos,
                       interleaved=interleaved)
        k = apply_rope((y @ wk[32:].T)[:, :, None], rope["cos"],
                       rope["sin"], pos, interleaved=interleaved)
        return np.asarray(jnp.einsum("bshd,bthd->bst", q, k))

    np.testing.assert_allclose(
        scores(tensors[sa + "q_b_proj.weight"],
               tensors[sa + "kv_a_proj_with_mqa.weight"], True),
        scores(np.asarray(tree).reshape(96, 24), kva, False), atol=1e-5)
    save_safetensors(str(tmp_path / "model.safetensors"), tensors)
    with open(tmp_path / "config.json", "w") as f:
        json.dump(TINY_HF, f)
    loaded = load_model_params(cfg, str(tmp_path), jnp.float32)
    got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    for path, want in jax.tree_util.tree_leaves_with_path(params):
        np.testing.assert_array_equal(
            np.asarray(got[path]), np.asarray(want),
            err_msg=jax.tree_util.keystr(path))


# -- the pool's other paths over latent leaves -----------------------------------

def _reference_greedy(ref, hf, w, ids, n):
    ids = list(ids)
    for _ in range(n):
        logits = ref.forward_logits(hf, w, ids, [len(ids) - 1])
        ids.append(int(np.argmax(logits[0])))
    return ids[-n:]


def test_prefix_hits_restore_latent_rows_and_give_the_references_tokens(
        bench):
    """A full-chain hit, a partial hit and a miss through `slot_restore`:
    a block is 16 positions of one 128-lane leaf a layer."""
    ref = bench["reference.deepseek_v2"]
    m, w = _model(bench, WHOLE_HF)
    chunk = 16
    shared = [3 + (i * 11) % 200 for i in range(4 * chunk)]
    prompts = (("miss", shared + [7, 9, 11], 0),
               ("full", shared + [7, 9, 11], 4 * chunk),
               ("partial", shared[:2 * chunk] + [5] * 9, 2 * chunk))
    eng = ServeEngine(m, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=chunk, prefix_cache_mb=8)
    try:
        assert eng.prefix_cache is not None
        for name, ids, hit in prompts:
            r = eng.submit(ids, max_new_tokens=4, sampling=GREEDY)
            assert r.wait(300)
            assert r.stats["prefix_hit_tokens"] == hit, name
            assert r.result["tokens"] == _reference_greedy(
                ref, WHOLE_HF, w, ids, 4), name
        kinds = eng.health()["attention_kinds"]
        assert kinds == eng.flight.static["attention_kinds"]
        assert (kinds[0]["kind"], kinds[0]["row_width"],
                kinds[0]["row_lanes"], kinds[0]["row_bytes"]) == \
            ("latent", 40, 128, 3 * 80)
        assert eng.health()["kv_pool"] == {"joined_keys": []}
    finally:
        eng.close()


def test_the_paged_pool_over_latent_leaves(bench):
    """Blocks of 16 latents behind a block table, two rows at once: the
    reference's greedy tokens."""
    ref = bench["reference.deepseek_v2"]
    m, w = _model(bench, WHOLE_HF)
    prompts = ([5, 9, 13, 17] * 9 + [5, 9], [7 + (i * 5) % 90
                                              for i in range(21)])
    eng = ServeEngine(m, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=0, kv_blocks=12,
                      kv_block_tokens=16)
    try:
        assert eng.paged is not None
        assert [sorted(pl) for pl in eng.paged.pool] == [["kv", "pos"]] * 3
        assert eng.paged.pool[0]["kv"].shape == (12, 16, 128)
        assert eng.paged.block_bytes == 3 * 16 * (128 * 4 + 4)
        reqs = [eng.submit(ids, max_new_tokens=6, sampling=GREEDY)
                for ids in prompts]
        for r, ids in zip(reqs, prompts):
            assert r.wait(300)
            assert r.result["tokens"] == _reference_greedy(
                ref, WHOLE_HF, w, ids, 6)
    finally:
        eng.close()


def test_slot_verify_accepts_and_rolls_back_over_latent_leaves(bench):
    """A verify step over [last token, drafts]: the reference's greedy
    continuation is accepted whole; a wrong draft is rejected and its
    latents rolled back by position, so the next step agrees with a cache
    that never saw it."""
    ref = bench["reference.deepseek_v2"]
    m, w = _model(bench, WHOLE_HF)
    prompt, k = [3 + (i * 11) % 200 for i in range(20)], 3
    want = _reference_greedy(ref, WHOLE_HF, w, prompt, k + 2)
    recent = jnp.full((4,), -1, jnp.int32)

    def prefilled():
        logits, cache = m.prefill(m.new_cache(1, kv_len=64), prompt)
        assert int(np.argmax(np.asarray(logits[0]))) == want[0]
        return cache

    packed, cache, _ = m.verify_tokens(
        prefilled(), want[0], want[1:k + 1], k, len(prompt),
        jax.random.PRNGKey(0), recent, GREEDY)
    assert [int(v) for v in np.asarray(packed)] == [k, want[k + 1]]
    wrong = [(want[1] + 3) % 500] * k
    packed, cache, _ = m.verify_tokens(
        prefilled(), want[0], wrong, k, len(prompt), jax.random.PRNGKey(0),
        recent, GREEDY)
    assert [int(v) for v in np.asarray(packed)] == [0, want[1]]
    assert int(np.asarray(cache["layers"][0]["pos"]).max()) == len(prompt)
    a, _ = m.decode_logits(cache, want[1])
    plain = prefilled()
    _, plain = m.decode_logits(plain, want[0])
    b, _ = m.decode_logits(plain, want[1])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert int(np.argmax(np.asarray(a[0]))) == want[2]


# -- what a run says of it ---------------------------------------------------------

def test_the_decode_program_carries_the_new_scopes():
    model = TextModel(tiny_config("deepseek_v2"), dtype=jnp.float32,
                      max_cache_len=CTX)
    slots = 4
    layers = model.new_cache(slots, kv_len=CTX)["layers"]
    assert [lc["kv"].shape for lc in layers] == [(slots, CTX, 128)] * 3
    z = lambda dt: jnp.zeros((slots,), dt)      # noqa: E731
    args = (model.params, layers, z(jnp.int32), z(jnp.int32),
            jnp.stack([jax.random.PRNGKey(i) for i in range(slots)]),
            jnp.full((slots, 8), -1, jnp.int32), z(jnp.float32),
            jnp.full((slots,), 256, jnp.int32), jnp.ones((slots,)),
            jnp.ones((slots,)), z(jnp.bool_))
    text = model._decode_slots.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    from cake_tpu.obs.spans import SCOPE_CATALOG
    names = {n for n, _ in SCOPE_CATALOG}
    assert "cake.attn.latent.expand" in names
    for scope in ("cake.attn.latent", "cake.attn.latent.proj",
                  "cake.attn.latent.absorb", "cake.attn.latent.read",
                  "cake.ffn.shared", "cake.ffn.experts", "cake.ffn.route"):
        assert scope in text and scope in names, scope
    assert "cake.attn.latent.expand" not in text     # a step with a cache
    # nested: a reader of the parent scope (`[/(]cake\.attn[/)]`,
    # trace_reduce.scope_ms) counts the mixer, one of the mixer its parts
    for outer, inner in (("attn", "attn.latent"),
                         ("attn.latent", "attn.latent.proj"),
                         ("attn.latent", "attn.latent.absorb"),
                         ("attn.latent", "attn.latent.read")):
        assert re.search(rf"[/(]cake\.{re.escape(outer)}(?=[/)])[^\"]*[/(]"
                         rf"cake\.{re.escape(inner)}[/)]", text), inner


# -- --tp 4 ---------------------------------------------------------------------

@pytest.mark.parametrize("step", ["chunk", "decode"])
def test_tp_over_four_virtual_devices_gives_the_single_device_logits(step):
    """The heads of q_b, kv_b and o over `tp`; q_a, kv_a, their norms and
    the rows of latents replicated: every device reads the one shared
    key. (The Pallas read keeps to one device: under a mesh XLA's.)"""
    from jax.sharding import Mesh

    from cake_tpu.parallel.sharding import check_tp_divisibility
    cfg = tiny_config("deepseek_v2")
    params = jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim >= 2 else a,
        init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    ids = [3 + (i * 7) % 200 for i in range(40)]
    want = None
    for mesh in (None, Mesh(np.asarray(jax.devices()[:4]).reshape(4),
                            ("tp",))):
        m = TextModel(cfg, params, dtype=jnp.float32, max_cache_len=CTX,
                      mesh=mesh)
        if mesh is not None:
            check_tp_divisibility(cfg, mesh)
            sa = m.params["layers"][1]["self_attn"]
            for name, shard in (("kv_b_proj", (32, 32)),
                                ("q_b_proj", (24, 24)),
                                ("o_proj", (64, 16)),
                                ("kv_a_proj_with_mqa", (40, 64)),
                                ("q_a_proj", (24, 64))):
                leaf = sa[name]["weight"]
                assert leaf.sharding.shard_shape(leaf.shape) == shard, name
        if step == "chunk":
            layers = m.new_cache(2, kv_len=CTX)["layers"]
            if mesh is not None:
                kv = layers[0]["kv"]
                assert kv.sharding.shard_shape(kv.shape) == kv.shape
            logits, layers = m.prefill_chunk(layers, 1, ids, 0)
        else:
            _, cache = m.prefill(m.new_cache(1, kv_len=CTX), ids)
            logits, _ = m.decode_logits(cache, 17)
        got = np.asarray(logits[0])
        if want is None:
            want = got
    np.testing.assert_allclose(got, want, atol=2e-4)
    with pytest.raises(ValueError, match="must divide heads"):
        check_tp_divisibility(tiny_config("deepseek_v2",
                                          num_attention_heads=6), mesh)
