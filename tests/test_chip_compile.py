"""Compile the Pallas attention kernels (prefill flash, decode) for a
DESCRIBED TPU v5e (nothing runs).

The TPU compiler is installed without a chip: it lowers for a topology
description and refuses what the chip would refuse — a kernel that does
not fit VMEM, a misaligned slice, a Mosaic call GSPMD cannot partition.
Interpret mode (tests/test_flash.py) sees none of that. This is the only
file that describes a chip: the description loads the TPU library, which
one process holds until it exits, so it happens inside a fixture, never at
import, and every compile runs in this test's own process.
"""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from cake_tpu.ops.decode_attention import decode_attention
from cake_tpu.ops.flash import flash_attention

# Qwen3-0.6B attention widths (the chip_smoke.py model)
HQ, HKV, D = 16, 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else: logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("tp",))


@contextlib.contextmanager
def _no_compile_cache():
    """A described-device compile can be written to the persistent cache
    but never read back without a chip — keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(sharding, sq, skv, *, d=D, append=False, window=None,
             mesh=None, scalar_sharding=None, hq=HQ, hkv=HKV, dv=None):
    scalar_sharding = scalar_sharding or sharding
    q = jax.ShapeDtypeStruct((1, sq, hq, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, skv, hkv, d), jnp.bfloat16,
                              sharding=sharding)
    v = jax.ShapeDtypeStruct((1, skv, hkv, dv or d), jnp.bfloat16,
                             sharding=sharding)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar_sharding)

    def f(q, k, v, valid_len, pos0):
        return flash_attention(q, k, v, valid_len=valid_len,
                               q_offset=pos0 if append else None,
                               window=window, mesh=mesh)

    with _no_compile_cache():
        return jax.jit(f).lower(q, kv, v, scalar, scalar).compile()


@pytest.mark.parametrize("sq,skv,kw", [
    (512, 512, {}),                                  # fresh S=512
    (4096, 4096, {}),                                # fresh S=4096
    (256, 4096, {"append": True}),                   # serve default chunk/ctx
    (256, 32768, {"append": True}),                  # whole-K/V VMEM wall
    (512, 512, {"d": 64}),
    (512, 512, {"d": 256}),
    (512, 512, {"window": 128}),                     # SWA layers
    # Jamba2-3B's attention: a group of 20 query heads on ONE K/V head
    (256, 256, {"hq": 20, "hkv": 1}),
    (256, 4096, {"hq": 20, "hkv": 1, "append": True}),
    # MiMo-V2's full layers: keys 192 wide, values 128, 64 q on 4 K/V heads
    (256, 256, {"hq": 64, "hkv": 4, "d": 192, "dv": 128}),
    (256, 16384, {"hq": 64, "hkv": 4, "d": 192, "dv": 128, "append": True}),
], ids=["fresh512", "fresh4096", "append256x4096", "append256x32768",
        "d64", "d256", "windowed", "group20-fresh", "group20-append",
        "k192v128-fresh", "k192v128-append"])
def test_flash_compiles_on_one_chip(one_chip, sq, skv, kw):
    compiled = _compile(one_chip, sq, skv, **kw)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("append", [False, True], ids=["fresh", "append"])
def test_flash_compiles_head_sharded_under_tp_mesh(tp_mesh, append):
    """GSPMD cannot partition a Mosaic kernel: under a mesh the call must
    be a shard_map over `tp` with the kernel still in the program."""
    heads = NamedSharding(tp_mesh, P(None, None, "tp", None))
    compiled = _compile(heads, 256 if append else 512,
                        4096 if append else 512, append=append,
                        mesh=tp_mesh,
                        scalar_sharding=NamedSharding(tp_mesh, P()))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # heads stay where they are: no collective moves q/k/v/out
    assert "all-gather" not in text and "all-to-all" not in text


# -- the decode kernel over a pool as the benchmark's cells hold it ----------

def _compile_decode(sharding, rows, hq, hkv, *, ctx=4096, mesh=None,
                    row_sharding=None):
    row_sharding = row_sharding or sharding
    q = jax.ShapeDtypeStruct((rows, 1, hq, D), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((rows, ctx, hkv, D), jnp.bfloat16,
                              sharding=sharding)
    pos = jax.ShapeDtypeStruct((rows, ctx), jnp.int32, sharding=row_sharding)
    q_pos = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=row_sharding)
    act = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=row_sharding)

    def f(q, k, v, kv_pos, q_pos, act):
        return decode_attention(q, k, v, kv_pos, q_pos, act, mesh=mesh)

    with _no_compile_cache():
        return jax.jit(f).lower(q, kv, kv, pos, q_pos, act).compile()


@pytest.mark.parametrize("rows,hq,hkv", [
    (8, 32, 8),                      # qwen3-4b's pool: 8 slots x 4096
    (16, 32, 4),                     # qwen3-30b-a3b's: 16 slots, 4 K/V heads
    (1, 16, 8),                      # a sequential generate's batch-1 cache
    (32, 48, 8),                     # Laguna's full layers: 6 q heads a K/V
                                     # head, 12 rows a head pair padded to 16
], ids=["4b-pool", "moe-pool", "batch1", "laguna-group-of-6"])
def test_decode_kernel_compiles_on_one_chip(one_chip, rows, hq, hkv):
    """The K and V buffers reach the kernel as they lie: viewing
    [rows, T, Hkv, D] as [rows, T * Hkv, D] is a bitcast in the tiled
    layout, so no copy, transpose or slice of a pool-shaped operand
    stands around the call."""
    text = _compile_decode(one_chip, rows, hq, hkv).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    pool = re.compile(r"= bf16\[%d,(4096,%d|%d),128\]\S* (\w[\w-]*)\("
                      % (rows, hkv, 4096 * hkv))
    ops = {m.group(2) for m in pool.finditer(text)}
    assert ops <= {"parameter", "bitcast"}, ops


def test_decode_kernel_compiles_head_sharded_under_tp_mesh(tp_mesh):
    heads = NamedSharding(tp_mesh, P(None, None, "tp", None))
    text = _compile_decode(heads, 8, 32, 8, mesh=tp_mesh,
                           row_sharding=NamedSharding(tp_mesh, P())
                           ).as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-to-all" not in text


# -- the serve programs over a pool: no pool-shaped operand is converted -------

# two-layer cuts at published head widths, as the benchmark's cells hold them
_POOLS = {
    # MiMo-V2.5: one full layer (64 q on 4 K/V heads) and one window layer
    # (on 8), keys 192 and values 128, 32 rows x 16,384: keys lie joined
    "mimo_v2": (dict(
        model_type="mimo_v2", architectures=["MiMoV2ForCausalLM"],
        vocab_size=19072, hidden_size=4096, intermediate_size=2048,
        num_hidden_layers=2, hybrid_layer_pattern=[0, 1],
        moe_layer_freq=[0, 0], num_attention_heads=64,
        num_key_value_heads=4, head_dim=192, v_head_dim=128,
        swa_num_attention_heads=64, swa_num_key_value_heads=8,
        swa_head_dim=192, swa_v_head_dim=128, partial_rotary_factor=0.334,
        rope_theta=10000000, swa_rope_theta=10000, sliding_window=128,
        add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
        attention_value_scale=0.707, layernorm_epsilon=1e-5,
        max_position_embeddings=16384, n_routed_experts=16,
        num_experts_per_tok=8, moe_intermediate_size=2048, n_group=1,
        topk_group=1, norm_topk_prob=True, scoring_func="sigmoid",
        tie_word_embeddings=False, hidden_act="silu"), 32, 16384),
    # Laguna-S-2.1: one full layer (48 q heads, YaRN over half a head) and
    # one window layer (72 q heads, ring of 512) on the same 8 K/V heads of
    # 128, a gate a head, 32 rows x 16,384: the full layer decodes through
    # the kernel, beside a masked ring, in one program
    "laguna": (dict(
        model_type="laguna", vocab_size=12544, hidden_size=3072,
        intermediate_size=2048, num_hidden_layers=2,
        num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        max_position_embeddings=16384, rms_norm_eps=1e-6, num_experts=16,
        num_experts_per_tok=10, moe_intermediate_size=1024,
        shared_expert_intermediate_size=1024, norm_topk_prob=True,
        mlp_only_layers=[0, 1], tie_word_embeddings=False,
        gating="per-head", sliding_window=512,
        rope_parameters={
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        layer_types=["full_attention", "sliding_attention"],
        mlp_layer_types=["dense", "dense"],
        num_attention_heads_per_layer=[48, 72],
        moe_routed_scaling_factor=2.5), 32, 16384),
    # the control, Qwen3-4B's widths (an eighth of its vocabulary, as the
    # other has): keys 128 wide, 8 rows x 4096
    "qwen3": (dict(
        model_type="qwen3", architectures=["Qwen3ForCausalLM"],
        vocab_size=18992, hidden_size=2560, intermediate_size=9728,
        num_hidden_layers=2, num_attention_heads=32, num_key_value_heads=8,
        head_dim=128, rms_norm_eps=1e-6, rope_theta=1000000,
        max_position_embeddings=4096, tie_word_embeddings=True), 8, 4096),
}
# Laguna at its published reach, served to 16,384: the tables end where the
# caches do (layers.cut_rope). Whole, the window layers' 64-wide cos and sin
# were each laid out by rows in front of the gather of a step's positions
_TABLE_1M = 2 ** 20 * 64
_POOLS["laguna_1m"] = ({**_POOLS["laguna"][0],
                        "max_position_embeddings": 2 ** 20}, 32, 16384)
_RESULT = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]+)\]\S* "
                     r"(copy|copy-start|transpose)\(", re.M)
_FUSED = re.compile(r" fusion\(.*calls=(%[\w.-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.-]+) \(.*?^}", re.M | re.S)


def _converted(text, elements):
    """The copies and transposes of an optimised HLO module whose result
    holds at least `elements` and is written to memory: one inside a fused
    computation is its consumer's way of reading (the weighted values'
    convolution reads V through one) and moves no buffer."""
    fused = set(_FUSED.findall(text))
    return [(m.group(3), m.group(1), m.group(2))
            for c in _COMPUTATION.finditer(text) if c.group(1) not in fused
            for m in _RESULT.finditer(c.group(0))
            if np.prod([int(d) for d in m.group(2).split(",")]) >= elements]


def _pool_programs(cfg, rows, ctx, one_chip):
    from cake_tpu.models import TextModel
    from cake_tpu.models.common.cache import init_cache, restore_reads
    from cake_tpu.models.common.layers import cut_rope, init_params
    from cake_tpu.serve.engine import RECENT_N
    m = TextModel.__new__(TextModel)        # programs alone: no weights
    m.cfg, m.dtype, m.mesh, m.tokenizer, m.max_cache_len = (
        cfg, jnp.bfloat16, None, None, ctx)
    m._build()

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(tree))

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def held():      # what TextModel.__init__ keeps of what it is given
        p = init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
        return {**p, "rope": cut_rope(p["rope"], ctx)}

    params = described(held)
    layers = described(lambda: init_cache(cfg, rows, ctx)["layers"])
    i32, f32 = jnp.int32, jnp.float32
    with _no_compile_cache():
        yield "decode", layers, m._decode_slots.lower(
            params, layers, of(i32, rows), of(i32, rows),
            of(jnp.uint32, rows, 2), of(i32, rows, RECENT_N), of(f32, rows),
            of(i32, rows), of(f32, rows), of(f32, rows),
            of(jnp.bool_, rows)).compile()
        yield "append256", layers, m._prefill_slot.lower(
            params, of(i32, 1, 256), layers, of(i32), of(i32), of(i32),
            flash_mode="append").compile()
        # a prefix hit restores a chain of 256-token blocks into a row: 32
        # of them (the cells' shared 8k) are one program, handed only what
        # the row keeps of them
        block = described(lambda: m._slot_extract(
            init_cache(cfg, rows, ctx)["layers"], 0, 0, width=256))
        yield "restore32x256", layers, m._slot_restore.lower(
            layers, restore_reads(layers, [block] * 32, 256), of(i32, 3),
            block=256).compile()


@pytest.mark.parametrize("family", list(_POOLS))
def test_serve_programs_take_the_pool_in_place(one_chip, monkeypatch, family):
    """`_decode_slots`, `_prefill_slot` (append, 256 tokens) and the prefix
    cache's `_slot_restore` of a 32-block chain as the chip would run them (the Pallas kernels
    on): the optimised HLO holds no copy or transpose whose result is as
    large as a layer's K or V buffer, the pool is donated through, and the
    temporaries are a row's, not a pool's: at keys of 192 by head the
    runtime stored K length-minor and the decode step and a block's splice each
    copied 805 MB to a D-minor layout and back around their scatter (1.08
    GB of temporaries; PERF.md, PR 40). The largest temporary left is the
    masked decode read's float32 scores, rows x Hq x T. Laguna's full
    layer (keys 128 = values 128 on 8 K/V heads, no sink) is the first of
    a window/full model to hold the decode kernel in its decode program."""
    from cake_tpu.models.common.config import config_from_hf_dict
    from cake_tpu.ops import flash
    monkeypatch.setattr(flash, "flash_enabled", lambda: True)
    hf, rows, ctx = _POOLS[family]
    cfg = config_from_hf_dict(hf)
    for name, layers, compiled in _pool_programs(cfg, rows, ctx, one_chip):
        full = max(layers, key=lambda lc: lc["k"].size)
        k, pool_shaped = full["k"], min(full["k"].size, full["v"].size)
        assert (k.ndim == 3) == (family == "mimo_v2")
        text, mem = compiled.as_text(), compiled.memory_analysis()
        # the kernels are in it, but for the masked decode of keys of 192
        assert ("tpu_custom_call" in text) == (
            name != "restore32x256" and (family, name) != ("mimo_v2", "decode")
        ), name
        big = _converted(text, min(pool_shaped, _TABLE_1M))
        assert not big, (name, big)
        pool_bytes = sum(a.size * a.dtype.itemsize
                         for a in jax.tree_util.tree_leaves(layers))
        assert mem.alias_size_in_bytes >= pool_bytes, name
        scores = rows * cfg.num_attention_heads * ctx * 4
        limit = (scores if name == "decode" and family == "mimo_v2"
                 else 0) + 64 * 2 ** 20
        if (cfg.arch, name) == ("laguna", "append256"):
            # a chunk's window layer attends masked over ring + chunk at
            # 72 heads: float32 scores and their exponentials
            limit += 2 * 72 * 256 * (512 + 256) * 4
        assert mem.temp_size_in_bytes < limit, (name,
                                                mem.temp_size_in_bytes)


def test_retention_programs_take_the_state_in_place(one_chip, monkeypatch):
    """Brumby's widths (40 / 8 heads of 128: a state of 128 x 8,320 float32
    a key/value head), two layers, an eighth of the vocabulary, 16 rows:
    `_decode_slots` holds ONE call of the state kernel a layer
    (ops/retention_state.py: a tile is read once for the read-out and the
    update) and `_prefill_slot` the chunk form; both donate the pool
    through, no copy or transpose of a pool-shaped buffer stands in either,
    and the temporaries are a chunk's (phi of 256 tokens' queries, 338 MB
    in float32), never a second copy of the state."""
    from cake_tpu.models import brumby
    from cake_tpu.models.common.config import config_from_hf_dict
    monkeypatch.setattr(brumby, "state_kernel_enabled", lambda: True)
    rows, ctx = 16, 4096
    cfg = config_from_hf_dict(dict(
        model_type="brumby", vocab_size=18992, hidden_size=5120,
        intermediate_size=17408, num_hidden_layers=2,
        num_attention_heads=40, num_key_value_heads=8, head_dim=128,
        rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None,
        sliding_window=None, use_sliding_window=False,
        attention_bias=False, max_position_embeddings=32768,
        tie_word_embeddings=False))
    from cake_tpu.models import TextModel
    from cake_tpu.models.common.cache import init_cache
    from cake_tpu.models.common.layers import cut_rope, init_params
    from cake_tpu.serve.engine import RECENT_N
    m = TextModel.__new__(TextModel)        # programs alone: no weights
    m.cfg, m.dtype, m.mesh, m.tokenizer, m.max_cache_len = (
        cfg, jnp.bfloat16, None, None, ctx)
    m._build()

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(tree))

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def held():
        p = init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
        return {**p, "rope": cut_rope(p["rope"], ctx)}

    params = described(held)
    layers = described(lambda: init_cache(cfg, rows, ctx)["layers"])
    assert layers[0]["state"].shape == (rows, 8, 128, 8320)
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(layers))
    i32, f32 = jnp.int32, jnp.float32
    with _no_compile_cache():
        decode = m._decode_slots.lower(
            params, layers, of(i32, rows), of(i32, rows),
            of(jnp.uint32, rows, 2), of(i32, rows, RECENT_N), of(f32, rows),
            of(i32, rows), of(f32, rows), of(f32, rows),
            of(jnp.bool_, rows)).compile()
        chunk = m._prefill_slot.lower(
            params, of(i32, 1, 256), layers, of(i32), of(i32), of(i32),
            flash_mode="off").compile()
    for name, compiled, kernels, limit in (
            ("decode", decode, 2, 64 * 2 ** 20),
            ("chunk256", chunk, 0, 2 ** 30)):
        text, mem = compiled.as_text(), compiled.memory_analysis()
        assert text.count('custom_call_target="tpu_custom_call"') \
            == kernels, name
        big = _converted(text, layers[0]["state"].size // rows)
        assert not big, (name, big)
        assert mem.alias_size_in_bytes >= pool_bytes, name
        assert mem.temp_size_in_bytes < limit, (name,
                                                mem.temp_size_in_bytes)


def test_delta_rule_programs_take_the_state_in_place(one_chip, monkeypatch):
    """Solar-Open2's widths as its cell runs them (64 delta-rule heads of
    128 x 128, 64 / 8 gated-GQA heads, 10 held experts of 320, an eighth of
    the vocabulary), one period of the pattern (G, K, K, K), 32 rows:
    `_decode_slots` holds ONE call of `cake_delta_rule_state` a K layer
    (ops/delta_rule_state.py: a tile of 16 heads is read once, updated and
    written back) beside the G layer's decode kernel; the pool is donated
    through, so no copy of a layer's f32[32,64,128,128] stands in it and
    the temporaries are a step's, never a second state. Mosaic takes the
    tile: 1 MB in and 1 MB out, each double-buffered, under the 16 MiB a
    kernel may use."""
    import json
    from cake_tpu.models.common.config import config_from_hf_dict
    from cake_tpu.ops import flash
    from cake_tpu.ops.delta_rule_state import head_block
    monkeypatch.setattr(flash, "flash_enabled", lambda: True)
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "solar-open2-l8-ep32.json")) as f:
        hf = json.load(f)
    del hf["benchmark"]
    cfg = config_from_hf_dict({**hf, "num_hidden_layers": 4,
                               "gqa_layers": [0]})
    name, layers, decode = next(_pool_programs(cfg, 32, 4096, one_chip))
    assert name == "decode"
    state = layers[1]["state"]
    assert (state.shape, state.dtype) == ((32, 64, 128, 128), jnp.float32)
    tile = head_block(64, 4 * 128 * 128) * 4 * 128 * 128
    assert tile == 2 ** 20 and 4 * tile < 16 * 2 ** 20
    text, mem = decode.as_text(), decode.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert len(re.findall(r"= \(f32\[32,64,128\]\S*, "
                          r"f32\[32,64,128,128\]\S*\) custom-call\(",
                          text)) == 3
    assert "cake_delta_rule_state" in text
    big = _converted(text, state.size)
    assert not big, big
    # and the kernel is the ONE reader of a K layer's leaf (XLA's step had
    # three fusions read it and a fourth write it)
    entry = text[text.index("\nENTRY "):].splitlines()[2:]
    for layer in (1, 2, 3):
        users = [line for line in entry if re.search(
            rf"[(, ]%layers_{layer}___state__[.\d]*[,)]", line)]
        assert len(users) == 1 and "cake_delta_rule_state" in users[0], users
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(layers))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, mem.temp_size_in_bytes


def test_latent_programs_take_the_pool_in_place(one_chip, monkeypatch):
    """DeepSeek-V2's widths (128 heads, a row of 576 numbers in 640 lanes),
    layer 0 and one sparse layer with a group of 4 of the router's 32
    experts, an eighth of the vocabulary, 32 rows x 24,576 (the cell's
    pool): `_decode_slots` and `_prefill_slot` hold ONE call of
    `cake_latent_decode_attention` a layer (a decode step and a chunk take
    the same kernel), both donate the pool through, and no copy or
    transpose of a buffer as large as one row's latents stands in them or
    in the prefix restore. The temporaries are named: a decode step's are
    the routed experts' [32, E, I] activations and the logits; a
    256-token chunk's are its absorbed queries and weighted latents,
    [256, 128, 640] and [256, 128, 512] bfloat16 (42 + 34 MB), the
    layer-0 FFN's [256, 12288] pair and the routed experts' [256, E, I]."""
    from cake_tpu.models import deepseek_v2
    from cake_tpu.models.common.config import config_from_hf_dict
    monkeypatch.setattr(deepseek_v2, "kernel_enabled", lambda: True)
    rows, ctx = 32, 24576
    cfg = config_from_hf_dict(dict(
        model_type="deepseek_v2", vocab_size=12800, hidden_size=5120,
        intermediate_size=12288, num_hidden_layers=2,
        num_attention_heads=128, num_key_value_heads=128,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rms_norm_eps=1e-6,
        rope_theta=10000, max_position_embeddings=163840,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 4096},
        first_k_dense_replace=1, moe_layer_freq=1, n_routed_experts=4,
        n_shared_experts=2, num_experts_per_tok=6,
        moe_intermediate_size=1536, n_group=8, topk_group=3,
        topk_method="group_limited_greedy", scoring_func="softmax",
        norm_topk_prob=False, routed_scaling_factor=16,
        expert_parallel={"size": 8, "rank": 0}, tie_word_embeddings=False))
    for name, layers, compiled in _pool_programs(cfg, rows, ctx, one_chip):
        assert [sorted(lc) for lc in layers] == [["kv", "pos"]] * 2
        assert layers[0]["kv"].shape == (rows, ctx, 640)
        text, mem = compiled.as_text(), compiled.memory_analysis()
        kernels = text.count('custom_call_target="tpu_custom_call"')
        assert kernels == (0 if name == "restore32x256" else 2), name
        # (a chunk's absorbed queries are laid out once a layer for the
        # kernel's [tokens x heads, 640] blocks: 42 MB, no part of the pool)
        big = [c for c in _converted(text, ctx * 640)
               if c[2] != "1,256,128,640"]
        assert not big, (name, big)
        pool_bytes = sum(a.size * a.dtype.itemsize
                         for a in jax.tree_util.tree_leaves(layers))
        assert mem.alias_size_in_bytes >= pool_bytes, name
        print(name, "temporaries", mem.temp_size_in_bytes)
        assert mem.temp_size_in_bytes < 320 * 2 ** 20, (
            name, mem.temp_size_in_bytes)
        if name == "decode":
            # ONE custom call a latent layer whichever body the step's
            # shape takes (PR 63: a decode step's own), and the 6 MB the
            # configuration's memory_plan states
            assert len(re.findall(
                r"custom-call\([^\n]*cake_latent_decode_attention",
                text[text.index("\nENTRY "):])) == 2
            assert abs(mem.temp_size_in_bytes - 6e6) < 2e6, \
                mem.temp_size_in_bytes


def test_ling3_programs_take_a_row_of_both_kinds_in_place(one_chip,
                                                          monkeypatch):
    """The benchmark's Ling-3.0-flash configuration as it is served (32
    heads; layer 0 and a whole period: six delta-rule layers beside one
    latent layer; 64 of 512 experts, an eighth of the vocabulary), 32 rows
    x 24,576: ONE row holds float32 state [32, 128, 128] and conv tails of
    six layers beside 640-lane latents of one. `_decode_slots` holds the
    state kernel six times (a head's tile is whole at 32 heads as at 64)
    and the latent read once (a query block of 32 heads where DeepSeek-V2
    has 128); a 256-token chunk the latent read once and the delta rule as
    matrix products; both donate the whole row through, no leaf of either
    kind is copied or transposed, and the restore of a 32-block chain
    writes a snapshot and a slab of latents with ~1 MB of temporaries."""
    import json as json_mod
    from cake_tpu.models import deepseek_v2
    from cake_tpu.models.common.config import config_from_hf_dict
    from cake_tpu.ops import flash
    monkeypatch.setattr(flash, "flash_enabled", lambda: True)
    monkeypatch.setattr(deepseek_v2, "kernel_enabled", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "ling-3.0-flash-vl-l7-ep8.json")) as f:
        hf = json_mod.load(f)
    del hf["benchmark"]
    cfg = config_from_hf_dict(hf)
    rows, ctx = 32, 24576
    want = {"decode": (7, 6, 1), "append256": (1, 0, 1),
            "restore32x256": (0, 0, 0)}
    for name, layers, compiled in _pool_programs(cfg, rows, ctx, one_chip):
        assert [sorted(lc) for lc in layers] == (
            [["conv", "state"]] * 5 + [["kv", "pos"], ["conv", "state"]])
        assert layers[0]["state"].shape == (rows, 32, 128, 128)
        assert layers[5]["kv"].shape == (rows, ctx, 640)
        text, mem = compiled.as_text(), compiled.memory_analysis()
        kernels = text.count('custom_call_target="tpu_custom_call"')
        entry = text[text.index("\nENTRY "):]
        assert (kernels,
                len(re.findall(r"custom-call\([^\n]*cake_delta_rule_state",
                               entry)),
                len(re.findall(
                    r"custom-call\([^\n]*cake_latent_decode_attention",
                    entry))) == want[name], name
        # nothing as large as one layer's state (the smaller leaf kind) or
        # one row's latents is copied or transposed
        big = [c for c in _converted(text, min(layers[0]["state"].size,
                                               ctx * 640))
               if c[2] != "1,256,32,640"]
        assert not big, (name, big)
        pool_bytes = sum(a.size * a.dtype.itemsize
                         for a in jax.tree_util.tree_leaves(layers))
        assert pool_bytes == 6 * rows * (32 * 128 * 128 * 4
                                         + 3 * 4096 * 3 * 2) \
            + rows * ctx * (640 * 2 + 4)
        assert mem.alias_size_in_bytes >= pool_bytes, name
        print(name, "temporaries", mem.temp_size_in_bytes)
        assert mem.temp_size_in_bytes < 256 * 2 ** 20, (
            name, mem.temp_size_in_bytes)
        if name == "decode":
            # the 55.7 MB the configuration's memory_plan states
            assert abs(mem.temp_size_in_bytes - 55.7e6) < 2e6, \
                mem.temp_size_in_bytes


def test_longcat_programs_take_eight_latent_leaves_in_place(one_chip,
                                                            monkeypatch):
    """The benchmark's LongCat-Flash configuration as it is served (4
    shortcut layers = 8 latent sub-layers of 64 heads, 8 dense FFNs of
    12,288, 4 sparse layers of 16 held experts under a 768-wide router, an
    eighth of the vocabulary), 32 rows x 6,144: `_decode_slots` and a
    256-token chunk hold the latent read eight times (a query block of 64
    heads, a shape the kernel had not seen), both donate all eight leaves
    through, and no leaf is copied or transposed; the value a pair carries
    is a [tokens, 6144] activation and costs no buffer of its own worth
    naming."""
    import json as json_mod
    from cake_tpu.models import deepseek_v2
    from cake_tpu.models.common.config import config_from_hf_dict
    from cake_tpu.ops import flash
    monkeypatch.setattr(flash, "flash_enabled", lambda: True)
    monkeypatch.setattr(deepseek_v2, "kernel_enabled", lambda: True)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "longcat-flash-chat-l4-ep32.json")) as f:
        hf = json_mod.load(f)
    del hf["benchmark"]
    cfg = config_from_hf_dict(hf)
    rows, ctx = 32, 6144
    for name, layers, compiled in _pool_programs(cfg, rows, ctx, one_chip):
        assert [sorted(lc) for lc in layers] == [["kv", "pos"]] * 8
        assert layers[0]["kv"].shape == (rows, ctx, 640)
        text, mem = compiled.as_text(), compiled.memory_analysis()
        entry = text[text.index("\nENTRY "):]
        reads = len(re.findall(
            r"custom-call\([^\n]*cake_latent_decode_attention", entry))
        assert reads == (0 if name.startswith("restore") else 8), name
        # nothing as large as one row's latents is copied or transposed
        # (a chunk's absorbed queries are laid out once a sub-layer for the
        # kernel's [tokens x heads, 640] blocks: 21 MB, no part of the pool)
        big = _converted(text, ctx * 640)
        assert len([c for c in big if c[2] == "1,256,64,640"]) == (
            8 if name == "append256" else 0), (name, big)
        big = [c for c in big if c[2] != "1,256,64,640"]
        assert not big, (name, big)
        pool_bytes = sum(a.size * a.dtype.itemsize
                         for a in jax.tree_util.tree_leaves(layers))
        assert pool_bytes == 8 * rows * ctx * (640 * 2 + 4)
        assert mem.alias_size_in_bytes >= pool_bytes, name
        print(name, "arguments", mem.argument_size_in_bytes, "temporaries",
              mem.temp_size_in_bytes)
        # the 19.4 / 175.9 / 1.4 MB the configuration's memory_plan states
        want = {"decode": 19.4e6, "append256": 175.9e6,
                "restore32x256": 1.4e6}[name]
        assert abs(mem.temp_size_in_bytes - want) < 0.1 * want + 1e6, (
            name, mem.temp_size_in_bytes)
        assert abs(mem.argument_size_in_bytes - 12.37e9) < 0.05e9 \
            or name.startswith("restore"), mem.argument_size_in_bytes
