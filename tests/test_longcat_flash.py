"""LongCat-Flash on the served path: a shortcut-connected block whose two
latent sub-layers are two entries of the layer list with a value carried
between them, a router that scores identity experts no share holds, and
latents scaled before their expansions.

Pinned here (the letters are ISSUE 64's):
  (a) the program against the benchmark's plain reference
      (benchmark/reference/longcat_flash.py, expanded attention, the layer
      as its four lines) through the three steps of benchmark/check.py,
      equal in float32 at every point, whole and as a share; the stateless
      pass alike; each mechanism's control over the bf16 reading;
  (b) the shares add up: every share's held experts' part, plus the
      identity term ONCE, equal the uncut reference's sparse layer;
  (c) the held-back output: the second attention sub-layer never sees `m`,
      and a `layer_range` that splits a pair is refused by name;
  (d) a prefix-cache hit gives what a fresh prefill gives, contiguous and
      paged pool, with the kv scale folded where W_uk / W_uv are absorbed
      (the rows hold the unscaled latent);
  (e) `slot_verify` over the latent sub-layers;
  (f) the router against one written by hand: softmax over all outputs,
      bias in selection only, weights unnormalised x 6, all-identity and
      all-elsewhere tokens;
  (g) each refusal of the adapter by name;
  (h) the older families' programs lower to the text they lowered to with
      the parent commit's `moe_ffn` and `forward_layers` in their place;
  and the scopes in the lowering, the static description, `--tp 4`.
"""
import dataclasses
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
from cake_tpu.models.common import layers as layers_mod
from cake_tpu.models.common.cache import init_cache
from cake_tpu.models.common.config import (AttnShape, LatentAttnConfig,
                                           config_from_hf_dict)
from cake_tpu.models.common.layers import (block_forward, forward_layers,
                                           forward_train, make_rope,
                                           moe_forward)
from cake_tpu.ops import moe
from cake_tpu.ops.moe import moe_ffn
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine
from tests.test_deepseek_v2 import _reference_greedy
from tests.test_ling3 import _decode_text

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
GREEDY = SamplingConfig(temperature=0.0)
CTX = 128

# the published LongCat-Flash keys at tiny widths: two layers = four latent
# sub-layers (4 heads of 16 + 8 with values of 16 through ranks 24 and 32,
# both latents scaled: a row of 40 numbers in 128 lanes), dense FFNs of 128,
# the second share of 4 of 8 experts of 32 under a router of 8 + 4 identity
# outputs, top 3, unnormalised, times 6
TINY_HF = {
    "model_type": "longcat_flash", "vocab_size": 512, "hidden_size": 64,
    "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "max_position_embeddings": 512,
    "attention_bias": False, "attention_method": "MLA",
    "rms_norm_eps": 1e-5, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "rope_theta": 10000000, "n_routed_experts": 4, "zero_expert_num": 4,
    "zero_expert_type": "identity", "moe_topk": 3,
    "routed_scaling_factor": 6, "expert_parallel": {"size": 2, "rank": 1},
}
WHOLE_HF = {**TINY_HF, "n_routed_experts": 8, "expert_parallel": None}
# reference/longcat_flash.py's initialisers at hidden 64, so that they lead
# to the numbers they lead to at 6,144: q, k_pe, k_nope, v and an FFN's gate
# and up ~1.6 a number, an embedding of 2.35 a channel, attention adding
# ~0.1, a dense FFN ~0.4, a picked expert ~0.3
TINY_INIT = dict(Q_B_STD=0.2, KV_A_STD=0.2, KV_B_STD=0.2, O_PROJ_STD=0.025,
                 FFN_IN_STD=0.2, DENSE_DOWN_STD=0.022, EXPERT_DOWN_STD=0.04,
                 EMBED_SCALE=14.7, SELECT_BIAS_STD=0.03)


@pytest.fixture(scope="module")
def bench():
    """benchmark/ importable: check.py, weights.py, the reference, with
    the reference's initialisers set for these widths."""
    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name)
                for name in ("check", "weights", "reference.longcat_flash")}
        ref = mods["reference.longcat_flash"]
        was = {k: getattr(ref, k) for k in TINY_INIT}
        for k, v in TINY_INIT.items():
            setattr(ref, k, v)
        yield mods
        for k, v in was.items():
            setattr(ref, k, v)
    finally:
        sys.path.remove(BENCH)


def _model(bench, hf, seed=64, dtype=jnp.float32, ctx=CTX):
    W, ref = bench["weights"], bench["reference.longcat_flash"]
    cfg = config_from_hf_dict(hf)
    w = W.make_weights(ref, hf, seed, dtype)
    return TextModel(cfg, {**w, "rope": make_rope(cfg)}, dtype=dtype,
                     seed=1, max_cache_len=ctx), w


# -- the adapter --------------------------------------------------------------

def test_adapter_resolves_the_benchmarks_configuration():
    with open(os.path.join(BENCH, "configs",
                           "longcat-flash-chat-l4-ep32.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf_dict(hf)
    assert cfg.arch == "longcat_flash" and cfg.shortcut_pairs
    specs = cfg.layer_specs()
    # 4 published layers = 8 latent sub-layers, in pairs
    assert cfg.num_hidden_layers == 8 and len(specs) == 8
    assert [s.kind for s in specs] == ["latent"] * 8
    assert [s.shortcut for s in specs] == ["open", "close"] * 4
    assert not any(s.is_moe or s.recurrent or s.window for s in specs)
    assert cfg.latent_attn == LatentAttnConfig(
        1536, 512, 128, 64, 128, q_scale=2.0, kv_scale=12 ** 0.5)
    assert cfg.latent_attn.row_width == 576
    assert cfg.attn_shape(specs[0]) == AttnShape(64, 1, 576, 512,
                                                 latent=True)
    assert cfg.rotary_dim == 64 and cfg.rope_theta == 1e7
    assert cfg.rope_scaling is None
    np.testing.assert_allclose(cfg.attn_scale, 192 ** -0.5, rtol=1e-12)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (12288,
                                                                  2048)
    # 16 held of 512 from 0; the router also scores 256 identity experts
    assert (cfg.num_experts, cfg.router_experts, cfg.expert_first,
            cfg.moe_zero_experts, cfg.router_width,
            cfg.num_experts_per_tok) == (16, 512, 0, 256, 768, 12)
    assert cfg.moe_routed_scale == 6.0 and not cfg.norm_topk_prob
    assert cfg.moe_gate_act == "softmax" and cfg.moe_select_bias
    assert cfg.shared_expert_intermediate_size is None
    assert (cfg.vocab_size, cfg.rms_norm_eps, cfg.max_seq_len) == (
        16384, 1e-5, 131072)
    assert cfg.attention_kinds() == [{
        "kind": "latent", "layers": 8, "heads": 64, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "row_width": 576,
        "row_lanes": 640, "rotary_dim": 64, "rope_theta": 1e7,
        "rope_scaling": None, "row_bytes": 8 * 1152, "q_scale": 2.0,
        "kv_scale": 12 ** 0.5}]
    assert cfg.sparse_layers() == {
        "layers": 4, "router_width": 768, "routed_experts": 512,
        "identity_experts": 256, "held": 16, "held_from": 0, "top_k": 12,
        "routed_scale": 6.0,
        "shortcut_pairs": [[0, 1], [2, 3], [4, 5], [6, 7]]}
    # the pool's leaves: one a SUB-layer, one vector a position
    lc = jax.eval_shape(lambda: init_cache(cfg, 2, 256)["layers"])
    assert [{k: v.shape for k, v in one.items()} for one in lc] == [
        {"kv": (2, 256, 640), "pos": (2, 256)}] * 8
    # an even sub-layer holds the pair's sparse layer beside its dense FFN
    shapes = jax.eval_shape(lambda: init_params(
        tiny_config("longcat_flash"), jax.random.PRNGKey(0))["layers"])
    assert ["moe" in p for p in shapes] == [True, False, True, False]
    assert shapes[0]["moe"]["gate"]["weight"].shape == (12, 64)
    assert shapes[0]["moe"]["gate"]["e_score_correction_bias"].shape == (
        12,)
    assert shapes[0]["moe"]["experts"]["gate_proj"].shape == (4, 32, 64)
    assert shapes[0]["mlp"]["gate_proj"]["weight"].shape == (128, 64)
    # without the keys: every expert held, no identity outputs, no scales
    whole = config_from_hf_dict({**hf, "n_routed_experts": 512,
                                 "expert_parallel": None,
                                 "zero_expert_num": 0,
                                 "mla_scale_q_lora": False,
                                 "mla_scale_kv_lora": False})
    assert (whole.router_width, whole.expert_first,
            whole.moe_zero_experts) == (512, 0, 0)
    assert (whole.latent_attn.q_scale, whole.latent_attn.kv_scale) == (1, 1)
    assert "q_scale" not in whole.attention_kinds()[0]
    assert config_from_hf_dict(
        {**hf, "architectures": ["LongcatFlashForCausalLM"],
         "model_type": "x"}).arch == "longcat_flash"


# -- (g) the refusals ---------------------------------------------------------

@pytest.mark.parametrize("key,value,says", [
    ("zero_expert_type", "copy", "zero_expert_type 'copy'"),
    ("attention_method", "GQA", "attention_method 'GQA'"),
    ("attention_bias", True, "attention_bias"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("norm_topk_prob", True, "norm_topk_prob true beside"),
    ("router_bias", True, "router_bias true"),
    ("q_lora_rank", None, "q_lora_rank null"),
    ("expert_parallel", {"size": 2, "rank": 2}, "rank 2 of 2"),
], ids=["zero_type", "method", "bias", "rope_scaling", "norm_and_scale",
        "router_bias", "full_rank_q", "rank"])
def test_adapter_refuses_by_name_what_it_cannot_honour(key, value, says):
    with pytest.raises(ValueError, match=f"longcat_flash: .*{says}"):
        config_from_hf_dict({**TINY_HF, key: value})


@pytest.mark.parametrize("key,value", [("mla_scale_q_lora", True),
                                       ("mla_scale_kv_lora", True),
                                       ("zero_expert_num", 256)])
def test_the_deepseek_adapter_names_the_family_that_has_it(key, value):
    from tests.test_deepseek_v2 import TINY_HF as DS
    with pytest.raises(ValueError, match=f"deepseek_v2: {key} .*"
                                         "`longcat_flash` has it"):
        config_from_hf_dict({**DS, key: value})


def test_a_checkpoint_is_refused_by_name_both_ways(tmp_path):
    from cake_tpu.utils.export import params_to_hf_tensors
    cfg = tiny_config("longcat_flash")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(NotImplementedError,
                       match="longcat_flash: exporting a checkpoint"):
        params_to_hf_tensors(cfg, params)
    from cake_tpu.utils.loaders import ParamLoader
    loader = ParamLoader.__new__(ParamLoader)
    loader.cfg, loader.prefix = cfg, "model"
    with pytest.raises(NotImplementedError,
                       match="longcat_flash: loading a checkpoint"):
        loader._layer(0)


# -- (a) the program against the plain reference ------------------------------

# what the bf16 program may differ from the float32 reference by (pooled
# relative RMS through the check) at these widths: the readings are 0.005
# (share and whole); every mechanism's control reads 0.04 or more
BF16_TOLERANCE = 0.02
CONTROLS = {"zero": {"zero": "off"}, "early": {"shortcut": "early"},
            "post1": {"shortcut": "post1"}, "q_scale": {"q_scale": 1.0},
            "kv_scale": {"kv_scale": 1.0},
            "select_bias": {"select_bias": "off"},
            "router": {"router": "real"}, "routed_scale":
            {"routed_scale": 1.0}}


@pytest.mark.parametrize("hf", [TINY_HF, WHOLE_HF], ids=["share", "whole"])
def test_program_equals_the_reference_through_the_check(bench, hf):
    check, ref = bench["check"], bench["reference.longcat_flash"]
    cfg = config_from_hf_dict(hf)
    real, first, held, zeros = ref.share(hf)
    assert (real + zeros, first, held) == (
        cfg.router_width, cfg.expert_first, cfg.num_experts)
    seed = 2 ** 31 + 64
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
            # with rows 0 and 3 of 4 active (absorbed, the kv scale on the
            # absorbed query and output), a chunk behind it: every point
            # equals the EXPANDED reference
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
    # every held expert AND the identity path, in every sparse layer
    used, needed = ref.experts_used(hf, w, served[-1]["ids"])
    assert needed == hf["n_routed_experts"] + 1 and used == needed


def test_the_stateless_pass_equals_the_reference(bench):
    ref = bench["reference.longcat_flash"]
    m, w = _model(bench, TINY_HF)
    ids = [3 + (i * 11) % 500 for i in range(70)]
    text = jax.jit(lambda p, t: forward_train(m.cfg, p, t)).lower(
        m.params, jnp.asarray([ids])).as_text(debug_info=True)
    assert "cake.attn.latent.expand" in text and "cake.ffn.zero" in text
    assert "cake.attn.latent.absorb" not in text
    got = np.asarray(forward_train(m.cfg, m.params, jnp.asarray([ids]))[0])
    want = ref.forward_logits(TINY_HF, w, ids, list(range(len(ids))))
    np.testing.assert_allclose(got, want, atol=3e-5 * np.abs(want).max())


# -- (b) the shares add up ----------------------------------------------------

def _sparse_params(key, e, width, h=64, im=32):
    ks = jax.random.split(key, 5)
    return {"gate": {"weight": jax.random.normal(ks[0], (width, h)) * 0.1,
                     "e_score_correction_bias":
                     jax.random.normal(ks[4], (width,)) * 0.02},
            "experts": {
                "gate_proj": jax.random.normal(ks[1], (e, im, h)) * .2,
                "up_proj": jax.random.normal(ks[2], (e, im, h)) * .2,
                "down_proj": jax.random.normal(ks[3], (e, h, im)) * .2}}


def test_the_four_shares_add_up_with_the_identity_term_counted_once(bench):
    """16 experts as 4 shares of 4 under a router of 16 + 8 identity
    outputs, top 6, unnormalised, times 6: the held experts' parts of all
    shares, plus the identity term ONCE, equal the uncut reference's sparse
    layer (and the uncut program's)."""
    ref = bench["reference.longcat_flash"]
    hf = {**WHOLE_HF, "n_routed_experts": 16, "zero_expert_num": 8,
          "moe_topk": 6}
    cfg = config_from_hf_dict(hf)
    assert cfg.router_width == 24
    key = jax.random.PRNGKey(64)
    p = _sparse_params(key, 16, 24)
    x = jax.random.normal(jax.random.fold_in(key, 9), (1, 24, 64))
    c = dict(ref.static(hf))
    want, idx = ref.sparse_ffn(x[0], p, c)
    np.testing.assert_allclose(np.asarray(moe_forward(cfg, p, x)[0]),
                               np.asarray(want), atol=3e-5)
    # the identity term alone: the reference with it minus without
    identity = want - ref.sparse_ffn(x[0], p, c, zero="off")[0]
    assert float(jnp.abs(identity).max()) > 1e-2
    assert np.any(np.asarray(idx) >= 16)
    parts = []
    for rank in range(4):
        share_hf = {**hf, "n_routed_experts": 4,
                    "expert_parallel": {"size": 4, "rank": rank}}
        share_cfg = config_from_hf_dict(share_hf)
        assert (share_cfg.router_width, share_cfg.router_experts,
                share_cfg.expert_first) == (24, 16, 4 * rank)
        held = {"gate": p["gate"], "experts": {
            k: v[4 * rank:4 * rank + 4] for k, v in p["experts"].items()}}
        got = moe_forward(share_cfg, held, x)[0]
        # a share computes the WHOLE identity term for the rows it serves
        parts.append(got - identity)
        # the reference given that share gives the program's part
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.sparse_ffn(
                x[0], held, dict(ref.static(share_hf)))[0]), atol=3e-5)
    np.testing.assert_allclose(np.asarray(sum(parts) + identity),
                               np.asarray(want), atol=5e-5)
    # some token reaches more than one share, and the routed parts differ
    reached = np.stack([np.abs(np.asarray(q)).max(-1) > 1e-6
                        for q in parts])
    assert reached.sum(0).max() > 1 and reached.any(1).all()


# -- (c) the held-back output -------------------------------------------------

def test_the_second_sub_layer_never_sees_the_sparse_layers_output():
    """Two pairs; the first pair's experts' down_proj made 10,000 times
    larger, so `m` is huge: what the pair's SECOND sub-layer writes to its
    cache (the latents of norm_in1(x)) does not move at all, the stream
    behind the pair moves by exactly the change of `m`, and the next pair's
    first sub-layer sees it."""
    cfg = tiny_config("longcat_flash")
    params = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    for p in params["layers"][0::2]:
        # logits of std ~1.3 and no bias: the picks go by the token, to
        # held experts, experts held elsewhere and identity experts alike
        gate = p["moe"]["gate"]
        gate["weight"] = gate["weight"] * 8
        gate["e_score_correction_bias"] *= 0
    big = jax.tree_util.tree_map(lambda a: a, params)
    big["layers"][0] = {**params["layers"][0], "moe": {
        **params["layers"][0]["moe"], "experts": {
            **params["layers"][0]["moe"]["experts"],
            "down_proj":
            params["layers"][0]["moe"]["experts"]["down_proj"] * 1e4}}}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 64))
    pos0 = jnp.asarray(0, jnp.int32)

    def run(p, layer_range=None):
        cache = init_cache(cfg, 1, 32, jnp.float32)
        if layer_range is not None:
            lo, hi = layer_range
            p = {**p, "layers": p["layers"][lo:hi]}
            cache = {**cache, "layers": cache["layers"][lo:hi]}
        return forward_layers(cfg, p, x, cache, pos0,
                              layer_range=layer_range)

    (ya, ca), (yb, cb) = run(params), run(big)
    kv = lambda c, j: np.asarray(c["layers"][j]["kv"])      # noqa: E731
    np.testing.assert_array_equal(kv(ca, 0), kv(cb, 0))
    np.testing.assert_array_equal(kv(ca, 1), kv(cb, 1))     # never saw m
    assert np.abs(kv(ca, 2) - kv(cb, 2)).max() > 1e-3       # the next pair
    # behind the first pair alone the stream moved by the change of m
    (y1a, _), (y1b, _) = run(params, (0, 2)), run(big, (0, 2))
    eps = cfg.rms_norm_eps
    p0 = params["layers"][0]
    h = layers_mod.rms_norm(x, p0["input_layernorm"]["weight"], eps)
    a, _ = layers_mod._attn(cfg, cfg.layer_spec(0), p0, h,
                            init_cache(cfg, 1, 32, jnp.float32)["layers"][0],
                            pos0, params["rope"])
    h0 = layers_mod.rms_norm(x + a, p0["post_attention_layernorm"]["weight"],
                             eps)
    m_a = moe_forward(cfg, p0["moe"], h0)
    m_b = moe_forward(cfg, big["layers"][0]["moe"], h0)
    assert float(jnp.abs(m_b).max()) > 10 * float(jnp.abs(y1a).max())
    np.testing.assert_allclose(np.asarray(y1b - y1a), np.asarray(m_b - m_a),
                               atol=2e-3 * float(jnp.abs(m_b).max()))
    # a sub-layer is the pre-norm block when nothing is carried: the closing
    # one with `held` zero is block_forward
    spec1, p1 = cfg.layer_spec(1), params["layers"][1]
    lc = init_cache(cfg, 1, 32, jnp.float32)["layers"][1]
    want, _ = block_forward(cfg, dataclasses.replace(spec1, shortcut=None),
                            p1, x, lc, pos0, params["rope"])
    got, held, _ = layers_mod.shortcut_forward(
        cfg, spec1, p1, x, jnp.zeros_like(x), lc, pos0, params["rope"])
    assert held is None
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("layer_range", [(0, 1), (1, 3), (1, 4), (2, 3)])
def test_a_layer_range_that_splits_a_pair_is_refused_by_name(layer_range):
    cfg = tiny_config("longcat_flash")
    params = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    lo, hi = layer_range
    part = {**params, "layers": params["layers"][lo:hi]}
    x = jnp.zeros((1, 4, 64))
    with pytest.raises(ValueError, match="longcat_flash: layer_range "
                                         f"\\({lo}, {hi}\\) separates"):
        forward_layers(cfg, part, x, None, jnp.asarray(0, jnp.int32),
                       layer_range=layer_range)
    # whole pairs are a stage
    y, _ = forward_layers(cfg, {**params, "layers": params["layers"][2:4]},
                          x + 1.0, None, jnp.asarray(0, jnp.int32),
                          layer_range=(2, 4))
    assert y.shape == x.shape


# -- (d), (e) the pool's other paths over the latent sub-layers ---------------

@pytest.mark.parametrize("pool", ["contiguous", "paged"])
def test_a_prefix_hit_gives_what_a_fresh_prefill_gives(bench, pool):
    """A miss, a full-chain hit and a partial hit: the restored rows hold
    the UNSCALED normed latents a fresh prefill writes (the kv scale rides
    on the absorbed query and output), so the reference's greedy tokens
    come out either way; a block is 16 positions of one 128-lane leaf a
    sub-layer."""
    ref = bench["reference.longcat_flash"]
    m, w = _model(bench, WHOLE_HF)
    chunk = 16
    shared = [3 + (i * 11) % 200 for i in range(4 * chunk)]
    prompts = (("miss", shared + [7, 9, 11], 0),
               ("full", shared + [7, 9, 11], 4 * chunk),
               ("partial", shared[:2 * chunk] + [5] * 9, 2 * chunk))
    paged = dict(kv_blocks=24, kv_block_tokens=16) if pool == "paged" else {}
    eng = ServeEngine(m, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=chunk, prefix_cache_mb=8, **paged)
    try:
        assert eng.prefix_cache is not None
        assert (eng.paged is not None) == (pool == "paged")
        for name, ids, hit in prompts:
            r = eng.submit(ids, max_new_tokens=4, sampling=GREEDY)
            assert r.wait(300)
            assert r.stats["prefix_hit_tokens"] == hit, name
            assert r.result["tokens"] == _reference_greedy(
                ref, WHOLE_HF, w, ids, 4), name
        health = eng.health()
        kinds = health["attention_kinds"]
        assert kinds == eng.flight.static["attention_kinds"]
        assert (kinds[0]["kind"], kinds[0]["layers"], kinds[0]["row_width"],
                kinds[0]["row_lanes"], kinds[0]["row_bytes"]) == \
            ("latent", 4, 40, 128, 4 * 80)
        np.testing.assert_allclose(kinds[0]["kv_scale"], 2 ** 0.5)
        assert health["sparse_layers"] == \
            eng.flight.static["sparse_layers"] == {
                "layers": 2, "router_width": 12, "routed_experts": 8,
                "identity_experts": 4, "held": 8, "held_from": 0,
                "top_k": 3, "routed_scale": 6.0,
                "shortcut_pairs": [[0, 1], [2, 3]]}
    finally:
        eng.close()


def test_slot_verify_accepts_and_rolls_back_over_the_sub_layers(bench):
    """A verify step over [last token, drafts] through all four latent
    sub-layers: the reference's greedy continuation is accepted whole; a
    wrong draft is rejected and its latents rolled back by position in
    every sub-layer, so the next step agrees with a cache that never saw
    it."""
    ref = bench["reference.longcat_flash"]
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
    assert len(cache["layers"]) == 4
    assert all(int(np.asarray(lc["pos"]).max()) == len(prompt)
               for lc in cache["layers"])
    a, _ = m.decode_logits(cache, want[1])
    plain = prefilled()
    _, plain = m.decode_logits(plain, want[0])
    b, _ = m.decode_logits(plain, want[1])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert int(np.argmax(np.asarray(a[0]))) == want[2]


# -- (f) the router against one written by hand -------------------------------

def _by_hand(x, p, k, scale, first, held, zeros):
    """The sparse layer, token by token, in numpy float64."""
    x = np.asarray(x, np.float64)
    wr = np.asarray(p["gate"]["weight"], np.float64)
    bias = np.asarray(p["gate"]["e_score_correction_bias"], np.float64)
    ex = {n: np.asarray(v, np.float64) for n, v in p["experts"].items()}
    width = wr.shape[0]
    out, picks = np.zeros_like(x), []
    for t, row in enumerate(x):
        logits = wr @ row
        s = np.exp(logits - logits.max())
        s /= s.sum()                                # over ALL outputs
        sel = np.argsort(-(s + bias), kind="stable")[:k]    # bias selects
        picks.append(sorted(sel))
        for e in sel:
            w = scale * s[e]                        # score alone, not normed
            if e >= width - zeros:
                out[t] += w * row                   # identity expert
            elif first <= e < first + held:
                j = e - first
                g, u = ex["gate_proj"][j] @ row, ex["up_proj"][j] @ row
                out[t] += w * (ex["down_proj"][j] @ (g / (1 + np.exp(-g))
                                                     * u))
    return out, picks


def test_the_router_against_one_written_by_hand():
    """A share of 4 (experts 4..7) of 8 under a router of 8 + 4 identity
    outputs, top 3 x 6; then a bias that sends every pick to the identity
    experts (the token comes back times 6 sum s_z), and one that sends
    every pick to experts held elsewhere (zeros)."""
    key = jax.random.PRNGKey(7)
    p = _sparse_params(key, 4, 12)
    x = jax.random.normal(jax.random.fold_in(key, 1), (40, 64))
    args = (p["experts"]["gate_proj"], p["experts"]["up_proj"],
            p["experts"]["down_proj"], 3, False, "softmax", "silu")

    def run(bias):
        return moe_ffn(x, p["gate"]["weight"], *args, select_bias=bias,
                       first=4, routed_scale=6.0, zero_experts=4)

    bias = p["gate"]["e_score_correction_bias"]
    want, picks = _by_hand(x, p, 3, 6.0, 4, 4, 4)
    np.testing.assert_allclose(np.asarray(run(bias)), want, atol=2e-5)
    flat = np.concatenate(picks)
    assert (flat >= 8).any() and ((flat >= 4) & (flat < 8)).any() \
        and (flat < 4).any()
    # the bias selects and never weighs: without it other experts are picked
    _, plain = _by_hand(x, {**p, "gate": {**p["gate"],
                                          "e_score_correction_bias":
                                          jnp.zeros(12)}}, 3, 6.0, 4, 4, 4)
    assert plain != picks
    # every pick an identity expert: 6 (s_8 + .. of the three picked) x
    s = jax.nn.softmax(x @ p["gate"]["weight"].T, axis=-1)
    to_zero = jnp.where(jnp.arange(12) >= 8, 10.0, 0.0)
    top3 = jnp.sum(jax.lax.top_k(s[:, 8:], 3)[0], -1)
    np.testing.assert_allclose(np.asarray(run(to_zero)),
                               np.asarray(6.0 * top3[:, None] * x),
                               rtol=2e-5, atol=1e-6)
    # every pick held elsewhere: nothing
    elsewhere = jnp.where(jnp.arange(12) < 4, 10.0, 0.0)
    assert float(jnp.abs(run(elsewhere)).max()) == 0.0
    # zero_experts 0 with the same 12 outputs: a plain share of 4 of 12
    none = moe_ffn(x, p["gate"]["weight"], *args, select_bias=bias, first=4,
                   routed_scale=6.0)
    routed, _ = _by_hand(x, p, 3, 6.0, 4, 4, 0)
    np.testing.assert_allclose(np.asarray(none), routed, atol=2e-5)


# -- (h) the older families ---------------------------------------------------

def _parent_moe_ffn(x, router_weight, gate_proj, up_proj, down_proj, k,
                    norm_topk_prob, gate_act="softmax", act="silu",
                    select_bias=None, first=0, routed_scale=1.0, n_group=1,
                    topk_group=1, group_score_top=1, zero_experts=0):
    """ops.moe.moe_ffn as the parent commit had it."""
    assert zero_experts == 0
    e = gate_proj.shape[0]
    share = router_weight.shape[0] != e
    with jax.named_scope("cake.ffn.route"):
        logits = jnp.einsum("th,eh->te", x, router_weight,
                            preferred_element_type=jnp.float32)
        weights, idx = moe.router_topk(logits, k, norm_topk_prob, gate_act,
                                       select_bias, n_group, topk_group,
                                       group_score_top)
        if routed_scale != 1.0:
            weights = weights * routed_scale
        if share:
            held = (idx >= first) & (idx < first + e)
            idx = jnp.where(held, idx - first, e)
            weights = jnp.where(held, weights, 0.0)

    def combine(xb, wb):
        g = jnp.einsum("th,eih->tei", xb, gate_proj)
        u = jnp.einsum("th,eih->tei", xb, up_proj)
        a = moe._expert_act(g, u, act)
        y_e = jnp.einsum("tei,ehi->teh", a, down_proj)
        return jnp.einsum("te,teh->th", wb, y_e).astype(xb.dtype)

    with jax.named_scope("cake.ffn.experts"):
        w_te = moe.combine_weights(weights, idx, e).astype(x.dtype)
        t, block = x.shape[0], moe.EXPERT_BLOCK_TOKENS
        if t <= block:
            return combine(x, w_te)
        cut = t - t % block
        out = jax.lax.map(lambda xw: combine(*xw),
                          (x[:cut].reshape(-1, block, x.shape[1]),
                           w_te[:cut].reshape(-1, block, e))
                          ).reshape(cut, -1)
        if cut < t:
            out = jnp.concatenate([out, combine(x[cut:], w_te[cut:])])
        return out


def _parent_forward_layers(cfg, params, x, cache, pos0, layer_range=None,
                           valid_len=None, flash_mode="off", mesh=None):
    """layers.forward_layers as the parent commit had it."""
    lo, hi = layer_range or (0, len(params["layers"]))
    specs = cfg.layer_specs()[lo:hi]
    rope = params["rope"]
    if cache is None:
        for j, spec in enumerate(specs):
            x, _ = block_forward(cfg, spec, params["layers"][j], x, None,
                                 pos0, rope, valid_len)
        return x, None
    new_layers = list(cache["layers"])
    for j, spec in enumerate(specs):
        x, new_layers[j] = block_forward(cfg, spec, params["layers"][j], x,
                                         cache["layers"][j], pos0, rope,
                                         valid_len, flash_mode, mesh=mesh)
    advance = x.shape[1] if valid_len is None else valid_len
    return x, {"layers": new_layers, "pos": pos0 + advance}


@pytest.mark.parametrize("arch", ["qwen3_moe", "qwen3_5_moe", "mimo_v2",
                                  "laguna", "solar_open2", "deepseek_v2",
                                  "ling3"])
def test_the_older_families_programs_lower_as_before(monkeypatch, arch):
    """Decode and a prefill chunk of each older sparse family, bfloat16,
    with this tree's expert layer and layer walk against the parent
    commit's put back in their place: the same text. (The latent mixer's
    scales are guarded by `!= 1.0` and trace nothing for `deepseek_v2` and
    `ling3`: their programs are among the cases.)"""
    import cake_tpu.models.common.text_model as text_model
    new = _decode_text(arch, jnp.bfloat16)
    monkeypatch.setattr(layers_mod, "moe_ffn", _parent_moe_ffn)
    monkeypatch.setattr(text_model, "forward_layers", _parent_forward_layers)
    old = _decode_text(arch, jnp.bfloat16)
    assert new == old


def test_no_identity_experts_lowers_to_the_expert_layer_it_was():
    x = jax.ShapeDtypeStruct((32, 64), jnp.bfloat16)
    p = _sparse_params(jax.random.PRNGKey(0), 4, 8)
    args = (p["gate"]["weight"], p["experts"]["gate_proj"],
            p["experts"]["up_proj"], p["experts"]["down_proj"], 3, False)
    new = jax.jit(lambda xx: moe_ffn(xx, *args, first=4, routed_scale=6.0))
    old = jax.jit(lambda xx: _parent_moe_ffn(xx, *args, first=4,
                                             routed_scale=6.0))
    assert new.lower(x).as_text() == old.lower(x).as_text()
    zero = jax.jit(lambda xx: moe_ffn(xx, *args, first=4, routed_scale=6.0,
                                      zero_experts=2))
    assert zero.lower(x).as_text() != old.lower(x).as_text()


# -- what a run says of it ----------------------------------------------------

def test_the_decode_program_carries_the_scopes_by_name():
    model = TextModel(tiny_config("longcat_flash"), dtype=jnp.float32,
                      max_cache_len=CTX)
    slots = 4
    layers = model.new_cache(slots, kv_len=CTX)["layers"]
    assert [lc["kv"].shape for lc in layers] == [(slots, CTX, 128)] * 4
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
    for scope in ("cake.attn.latent", "cake.attn.latent.proj",
                  "cake.attn.latent.absorb", "cake.attn.latent.read",
                  "cake.ffn", "cake.ffn.dense", "cake.ffn.route",
                  "cake.ffn.experts", "cake.ffn.zero"):
        assert scope in text and scope in names, scope
    assert "cake.attn.latent.expand" not in text     # a step with a cache
    assert "cake.ffn.shared" not in text             # no shared expert
    # the four parts are nested in cake.ffn and in nothing else of it: a
    # reader of the parent counts them, one of a part counts it alone
    for inner in ("dense", "route", "experts", "zero"):
        assert re.search(rf"[/(]cake\.ffn(?=[/)])[^\"]*[/(]"
                         rf"cake\.ffn\.{inner}[/)]", text), inner
    for a, b in (("route", "zero"), ("experts", "zero"), ("dense", "zero"),
                 ("dense", "route"), ("dense", "experts")):
        assert not re.search(rf"cake\.ffn\.{a}[/)][^\"]*cake\.ffn\.{b}[/)]",
                             text), (a, b)
        assert not re.search(rf"cake\.ffn\.{b}[/)][^\"]*cake\.ffn\.{a}[/)]",
                             text), (a, b)


@pytest.mark.parametrize("step", ["chunk", "decode"])
def test_tp_over_four_virtual_devices_gives_the_single_device_logits(step):
    """The heads of q_b, kv_b and o and the channels of the dense FFNs and
    the experts over `tp`; the router, its bias and the rows of latents
    replicated; the value a pair carries is a [B, S, hidden] activation
    like the stream."""
    from jax.sharding import Mesh

    from cake_tpu.parallel.sharding import check_tp_divisibility
    cfg = tiny_config("longcat_flash")
    params = jax.tree_util.tree_map(
        lambda a: a * 4 if a.ndim >= 2 else a,
        init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    ids = [3 + (i * 7) % 200 for i in range(40)]
    want = None
    for mesh in (None, Mesh(np.asarray(jax.devices()[:4]).reshape(4),
                            ("tp",))):
        m = TextModel(cfg, params, dtype=jnp.float32, max_cache_len=CTX,
                      mesh=mesh)
        if mesh is not None:
            check_tp_divisibility(cfg, mesh)
            p0 = m.params["layers"][0]
            for leaf, shard in (
                    (p0["self_attn"]["kv_b_proj"]["weight"], (32, 32)),
                    (p0["mlp"]["gate_proj"]["weight"], (32, 64)),
                    (p0["moe"]["experts"]["gate_proj"], (4, 8, 64)),
                    (p0["moe"]["gate"]["weight"], (12, 64)),
                    (p0["moe"]["gate"]["e_score_correction_bias"], (12,))):
                assert leaf.sharding.shard_shape(leaf.shape) == shard
        if step == "chunk":
            layers = m.new_cache(2, kv_len=CTX)["layers"]
            logits, layers = m.prefill_chunk(layers, 1, ids, 0)
        else:
            _, cache = m.prefill(m.new_cache(1, kv_len=CTX), ids)
            logits, _ = m.decode_logits(cache, 17)
        got = np.asarray(logits[0])
        if want is None:
            want = got
    np.testing.assert_allclose(got, want, atol=2e-5)
