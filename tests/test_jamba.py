"""Jamba on the served path: Mamba-1 layers whose state lives beside keys and
values in the slot pool, attention without rope between them.

Pinned here (HF parity is tests/test_hf_parity.py::test_jamba):
  * the adapter: layer order, the widths of the mixer, what it refuses;
  * the program against the benchmark's plain reference
    (benchmark/reference/jamba.py) through the three steps of
    benchmark/check.py — chunked prefill with a padded last bucket, the
    batched decode on a half-active pool, a chunk behind the decoded
    tokens — equal in float32, and both controls well apart from it;
  * the row operations on a pool with a full buffer and Mamba layers;
  * the prefix cache and the speculative verify give the plain path's
    tokens (the re-forward `has_recurrent_state` asks for);
  * the decode program holds no loop; the chunk program holds the scan;
  * loader <-> export round trip under the checkpoint's names;
  * `--tp` is refused with a sentence; `state_bytes` in the flight record.
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
from cake_tpu.models.common.cache import (is_positional, row_state_bytes,
                                          truncate_layers)
from cake_tpu.models.common.config import config_from_hf_dict
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
GREEDY = SamplingConfig(temperature=0.0)
CTX = 128

# the published Jamba2-3B config keys at tiny widths: two periods of 4
TINY_HF = {
    "architectures": ["JambaForCausalLM"], "model_type": "jamba",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
    "attn_layer_period": 4, "attn_layer_offset": 2,
    "expert_layer_period": 2, "expert_layer_offset": 1, "num_experts": 1,
    "num_experts_per_tok": 1, "mamba_expand": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_dt_rank": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "max_position_embeddings": 512,
    "tie_word_embeddings": True,
}


@pytest.fixture(scope="module")
def bench():
    """benchmark/ importable: check.py, weights.py, reference/jamba.py."""
    sys.path.insert(0, BENCH)
    try:
        yield {name: importlib.import_module(name)
               for name in ("check", "weights", "reference.jamba")}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def model():
    return TextModel(tiny_config("jamba"), dtype=jnp.float32,
                     max_cache_len=CTX)


def test_adapter_resolves_the_published_config():
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf_dict(hf)
    kinds = [s.kind for s in cfg.layer_specs()]
    assert [i for i, k in enumerate(kinds) if k == "full"] == [7, 21]
    assert kinds.count("mamba") == 26 and cfg.has_recurrent_state
    assert not any(s.use_rope or s.is_moe for s in cfg.layer_specs())
    m = cfg.mamba
    assert (m.d_inner, m.d_state, m.d_conv, m.dt_rank) == (5120, 16, 4, 160)
    assert (cfg.head_dim, cfg.num_key_value_heads) == (128, 1)
    assert cfg.tie_word_embeddings and cfg.rms_norm_eps == 1e-6
    with pytest.raises(ValueError, match="num_experts"):
        config_from_hf_dict({**hf, "num_experts": 16})


def test_program_equals_the_reference_through_the_check(bench):
    check, W, ref = (bench[k] for k in ("check", "weights",
                                        "reference.jamba"))
    from cake_tpu.models.common.layers import make_rope
    cfg = config_from_hf_dict(TINY_HF)
    assert [s.kind for s in cfg.layer_specs()] == \
        ["mamba", "mamba", "full", "mamba"] * 2
    seed = 2 ** 31 + 36
    sound = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        w = W.make_weights(ref, TINY_HF, seed, dtype)
        m = TextModel(cfg, {**w, "rope": make_rope(cfg)}, dtype=dtype,
                      seed=1, max_cache_len=256)
        served = check.served_logits(
            m, 4, 256, 32, check.check_ids(seed, 512, [20, 90]), 3,
            {"temperature": 0.7, "top_p": 0.9})
        got = check.compare(ref, TINY_HF, w, served)
        sound[dtype] = got["pooled"]
        if dtype == jnp.float32:
            # chunks of 32 with a last bucket of 26 padded to 32, decode
            # with rows 0 and 3 of 4 active, a chunk behind the decode
            assert len(got["points"]) == 6 and got["worst"] < 2e-5, got
            assert any("tail_after_decode" in k for k in got["points"])
    # the reference that lost the state at every chunk boundary and the
    # reference in int8, in the program's place: both far from the bf16
    # program's reading
    boundaries = [p for o in served
                  for p in list(range(32, o["n"], 32))
                  + [o["n"], o["n"] + 3]]
    lossy = types.SimpleNamespace(forward_logits=lambda hf, w, ids, pos,
                                  quant=None: ref.forward_logits(
        hf, w, ids, pos,
        drop_state_at=boundaries if quant == "drop_state" else None))
    dropped = check.control(lossy, TINY_HF, w, served, "drop_state")
    int8 = check.control(ref, TINY_HF, w, served, "int8")
    assert dropped["pooled"] > 3 * sound[jnp.bfloat16], (dropped, sound)
    assert int8["pooled"] > 1.5 * sound[jnp.bfloat16], (int8, sound)


def _row_bytes(layers, row):
    return [np.asarray(a[row]).tobytes()
            for a in jax.tree_util.tree_leaves(layers)]


def test_row_operations_on_a_pool_with_mamba_layers(model):
    """assign -> extract -> restore -> truncate -> reset on the model's own
    pool (Mamba, Mamba, a full buffer, Mamba): the named row changes as
    specified, every other row keeps its bytes, and no operation needed to
    know the kind: `conv` and `ssm` carry no `pos` leaf."""
    B = 3
    pool = model.new_cache(B, kv_len=CTX)["layers"]
    assert [is_positional(lc) for lc in pool] == [False, False, True, False]
    assert {k: v.shape[1:] for k, v in pool[0].items()} == \
        {"conv": (3, 128), "ssm": (8, 128)}
    assert pool[0]["ssm"].dtype == jnp.float32
    assert row_state_bytes(pool) == 3 * (3 * 128 + 8 * 128) * 4
    rng = np.random.default_rng(36)
    for row in range(B):                    # every row starts non-empty
        _, pool = model.prefill_chunk(pool, row,
                                      rng.integers(1, 256, 5 + row), 0)

    def others_untouched(new, old, row):
        for r in set(range(B)) - {row}:
            assert _row_bytes(new, r) == _row_bytes(old, r), r

    ids = rng.integers(1, 256, 16).tolist()
    src = model.new_cache(1, kv_len=16)
    _, src = model.prefill(src, ids)
    keep = jax.tree_util.tree_map(jnp.copy, pool)
    out = model.slot_assign(pool, src, 1)               # pool is donated
    others_untouched(out, keep, 1)
    for lo, ls in zip(out, src["layers"]):
        if is_positional(lo):
            np.testing.assert_array_equal(np.asarray(lo["pos"][1, :16]),
                                          np.arange(16))
            assert (np.asarray(lo["pos"][1, 16:]) == -1).all()
        else:
            for name in lo:
                assert np.asarray(lo[name][1]).tobytes() == \
                    np.asarray(ls[name][0]).tobytes()

    blk = model.slot_extract(out, 1, 8, 8)              # positions 8..15
    np.testing.assert_array_equal(np.asarray(blk[2]["pos"]),
                                  np.arange(8, 16)[None])
    for i in (0, 1, 3):
        for name in blk[i]:
            assert np.asarray(blk[i][name][0]).tobytes() == \
                np.asarray(out[i][name][1]).tobytes()

    wiped = model.slot_release(jax.tree_util.tree_map(jnp.copy, out), 2)
    for final in (False, True):
        keep = jax.tree_util.tree_map(jnp.copy, wiped)
        got = model.slot_restore(jax.tree_util.tree_map(jnp.copy, wiped),
                                 [blk], 2, 1, 8, final)
        others_untouched(got, keep, 2)
        for i in (0, 1, 3):                 # the state: the last block only
            for name in blk[i]:
                want = blk[i][name][0] if final else keep[i][name][2]
                assert np.asarray(got[i][name][2]).tobytes() == \
                    np.asarray(want).tobytes()

    cut = truncate_layers(got, jnp.asarray(12))
    assert int(jnp.max(cut[2]["pos"][2])) == 11
    for i in (0, 1, 3):                     # a state is never rolled back
        assert _row_bytes([cut[i]], 2) == _row_bytes([got[i]], 2)

    keep = jax.tree_util.tree_map(jnp.copy, cut)
    clr = model.slot_release(cut, 1)
    others_untouched(clr, keep, 1)
    for lc in clr:
        for name, buf in lc.items():
            row = np.asarray(buf[1].astype(jnp.float32))
            assert (row == (-1 if name == "pos" else 0)).all(), name


def test_prefix_cache_hit_gives_the_tokens_of_a_miss(model):
    """The conv tail and the state are captured at the chunk boundary and
    installed from the last matched block only."""
    prompt = [3 + (i * 11) % 200 for i in range(40)]
    ref, _ = model.generate(list(prompt), max_new_tokens=6, sampling=GREEDY)
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=64)
    try:
        for hit in (0, 32):
            r = eng.submit(prompt, max_new_tokens=6, sampling=GREEDY)
            assert r.wait(300)
            assert r.result["tokens"] == ref
            assert r.stats["prefix_hit_tokens"] == hit
        rec = [x for x in eng.flight.snapshot() if x["occupancy"]]
        assert rec and all(
            x["state_bytes"] == x["occupancy"] * eng._row_state_bytes
            for x in rec)
        assert eng._row_state_bytes == 3 * (3 * 128 + 8 * 128) * 4
    finally:
        eng.close()


def test_speculative_verify_gives_the_tokens_of_the_plain_path(model):
    """A rejected suffix cannot be rolled out of a state: the commit is the
    valid_len-masked re-forward, per slot inside the vmapped verify."""
    rep = [5, 9, 17, 23] * 4 + [5, 9]
    other = [100, 2, 5, 9, 11, 40]
    eng = ServeEngine(model, slots=2, max_queue=8, ctx_len=CTX,
                      prefill_chunk=16, prefix_cache_mb=0, spec="ngram",
                      spec_k=4)
    try:
        ra = eng.submit(rep, max_new_tokens=14, sampling=GREEDY)
        rb = eng.submit(other, max_new_tokens=8, sampling=GREEDY)
        assert ra.wait(600) and rb.wait(600)
        for r, p, n in ((ra, rep, 14), (rb, other, 8)):
            assert "error" not in r.result, r.result.get("error")
            want, _ = model.generate(list(p), max_new_tokens=n,
                                     sampling=GREEDY, spec=False)
            assert r.tokens == want
        assert eng.health()["spec"]["steps"] >= 1
    finally:
        eng.close()


def test_decode_program_holds_no_loop_and_the_chunk_holds_the_scan(model):
    """A one-token step takes the closed form at every occupancy (one
    program: no static argument); a chunk scans its tokens."""
    slots = 4
    layers = model.new_cache(slots, kv_len=CTX)["layers"]
    z = lambda dt: jnp.zeros((slots,), dt)
    # lowered for the TPU (nothing compiles or runs): the CPU lowering of
    # the sampler's key split is a loop of its own
    text = model._decode_slots.trace(
        model.params, layers, z(jnp.int32), z(jnp.int32),
        jnp.stack([jax.random.PRNGKey(i) for i in range(slots)]),
        jnp.full((slots, 16), -1, jnp.int32), z(jnp.float32),
        jnp.full((slots,), 256, jnp.int32), jnp.ones((slots,), jnp.float32),
        jnp.ones((slots,), jnp.float32), z(jnp.bool_)
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    # but the sampler's four searches (sampling.keep_mask), no loop
    loops = re.findall(r'loc\("([^"]*/while)"', text)
    assert text.count("stablehlo.while") == 4
    assert loops and all("cake.sample.select" in name for name in loops)
    for scope in ("cake.ssm.proj", "cake.ssm.conv",
                  "cake.ssm.scan", "cake.attn", "cake.ffn"):
        assert scope in text, scope
    chunk = model._prefill_slot.lower(
        model.params, jnp.zeros((1, 16), jnp.int32), layers,
        jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.asarray(16, jnp.int32), flash_mode="fresh").as_text()
    assert chunk.count("stablehlo.while") == 3      # one a Mamba layer


def test_loader_and_export_round_trip_under_the_checkpoints_names(tmp_path):
    from cake_tpu.utils.export import params_to_hf_tensors
    from cake_tpu.utils.loaders import load_model_params
    from cake_tpu.utils.safetensors_io import save_safetensors
    cfg = tiny_config("jamba")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tensors = params_to_hf_tensors(cfg, params)
    names = set(tensors)
    assert "model.final_layernorm.weight" in names
    assert "model.layers.0.pre_ff_layernorm.weight" in names
    assert "model.layers.0.feed_forward.gate_proj.weight" in names
    assert "model.layers.2.self_attn.q_proj.weight" in names
    assert {n.split(".mamba.")[1] for n in names
            if n.startswith("model.layers.0.mamba.")} == {
        "in_proj.weight", "conv1d.weight", "conv1d.bias", "x_proj.weight",
        "dt_proj.weight", "dt_proj.bias", "A_log", "D", "out_proj.weight",
        "dt_layernorm.weight", "b_layernorm.weight", "c_layernorm.weight"}
    assert not any(".mlp." in n or "post_attention" in n for n in names)
    save_safetensors(str(tmp_path / "model.safetensors"), tensors)
    with open(tmp_path / "config.json", "w") as f:
        json.dump({"architectures": ["JambaForCausalLM"]}, f)
    loaded = load_model_params(cfg, str(tmp_path), jnp.bfloat16)
    got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    for path, want in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        leaf = got[path]
        # what feeds exp() on the state stays float32 under a bf16 load
        f32 = name.endswith("['A_log']") or name.endswith("['D']")
        assert leaf.dtype == (jnp.float32 if f32 else jnp.bfloat16), name
        np.testing.assert_allclose(
            np.asarray(leaf, np.float32), np.asarray(want, np.float32),
            atol=0 if f32 else 2e-2, err_msg=name)
    assert "rope" in loaded and not loaded["rope"]      # nothing rotates


def test_tp_is_refused_with_a_sentence():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("tp",))
    with pytest.raises(ValueError, match="--tp 2 is not supported"):
        TextModel(tiny_config("jamba"), dtype=jnp.float32,
                  max_cache_len=CTX, mesh=mesh)
