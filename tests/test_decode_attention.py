"""The Pallas decode-attention kernel vs the masked XLA path (interpret
mode on the CPU — the compiled kernel needs a TPU; tests/test_chip_compile
compiles it for a described one).

Pinned here:
  * the kernel gives what make_attention_mask + multi_head_attention give,
    for GQA ratios 4 and 8, 32- and 16-bit buffers, frontiers at and around
    a block edge and at the buffer's end, a `pos` hole below the frontier,
    rows masked out of the step, alone (batch 1) and under a `vmap` over 8
    rows;
  * `_decode_slots`' lowering for the TPU holds one kernel call per
    full-attention layer and no `while` or `dynamic_slice` over a
    pool-shaped operand: `vmap` batches INTO the kernel's row axis, it does
    not loop over rows;
  * on the serve path, rows built by chunked prefill, a prefix splice, a
    release and a re-admission into the used row decode to the masked
    path's greedy ids, with the masked-out rows' state left byte-identical.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cake_tpu.ops.decode_attention as da
import cake_tpu.ops.flash as fl
from cake_tpu.models import TextModel, tiny_config
from cake_tpu.models.common.layers import decode_kernel_block
from cake_tpu.ops.attention import make_attention_mask, multi_head_attention
from tests.test_decode_in_place import _SLICING, _carries, _row_bytes

T, BK, D = 256, 64, 32
ROWS = 8
# row 0 takes the case's frontier; the others stay as they are
FRONTIERS = [None, 0, 17, BK - 1, 2 * BK, 3 * BK + 5, T - 1, 100]
MASKED_OUT = (2, 5)


def _inputs(hq, hkv, dtype, frontier):
    rng = np.random.default_rng(hq * 1000 + hkv * 100 + frontier)
    q = jnp.asarray(rng.standard_normal((ROWS, 1, hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((ROWS, T, hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((ROWS, T, hkv, D)), dtype)
    q_pos = np.asarray([frontier] + FRONTIERS[1:], np.int32)
    idx = np.arange(T, dtype=np.int32)[None, :]
    kv_pos = np.where(idx <= q_pos[:, None], idx, -1)
    kv_pos[6, 40] = -1                      # a hole below row 6's frontier
    if frontier >= 2:
        kv_pos[0, frontier // 2] = -1       # and one below the case's
    # entries past row 4's frontier that carry their positions still
    kv_pos[4, 2 * BK + 1:2 * BK + 4] = idx[0, 2 * BK + 1:2 * BK + 4]
    act = np.ones((ROWS,), bool)
    act[list(MASKED_OUT)] = False
    return q, k, v, jnp.asarray(kv_pos), jnp.asarray(q_pos), jnp.asarray(act)


@pytest.mark.parametrize("mode", ["batch1", "vmap"])
@pytest.mark.parametrize("frontier", [0, 1, BK - 1, BK, BK + 1, T - 1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (16, 2)])
def test_kernel_matches_masked_attention(hq, hkv, dtype, frontier, mode):
    dtype = jnp.dtype(dtype)
    q, k, v, kv_pos, q_pos, act = _inputs(hq, hkv, dtype, frontier)
    mask = make_attention_mask(q_pos[:, None], kv_pos)
    want = np.asarray(multi_head_attention(q, k, v, mask), np.float32)

    def one(q, k, v, kv_pos, q_pos, act):   # a batch-1 view, as slot_step's
        return da.decode_attention(q[None], k[None], v[None], kv_pos[None],
                                   q_pos, act, block_k=BK,
                                   interpret=True)[0]

    if mode == "vmap":
        got = jax.jit(jax.vmap(one))(q, k, v, kv_pos, q_pos, act)
        flipped = jax.jit(jax.vmap(one))(q, k, v, kv_pos, q_pos,
                                         jnp.ones_like(act))
    else:
        rows = (0, MASKED_OUT[0], 6)
        got = jnp.zeros_like(q).at[jnp.asarray(rows)].set(jnp.stack(
            [one(q[r], k[r], v[r], kv_pos[r], q_pos[r], act[r])
             for r in rows]))
        flipped = None
    got = np.asarray(got, np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    live = [r for r in (range(ROWS) if mode == "vmap" else (0, 6))
            if r not in MASKED_OUT]
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    # a row masked out of the step walks nothing and reads as zeros
    assert not got[list(MASKED_OUT)].any()
    if flipped is not None:
        # and whether it steps or not leaves the others' output as it is
        flipped = np.asarray(flipped, np.float32)
        assert np.array_equal(flipped[live], got[live])
        np.testing.assert_allclose(flipped, want, atol=tol, rtol=tol)


def test_rule_names_the_layers_that_run_the_kernel(monkeypatch):
    f32, bf16 = jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)
    rule = decode_kernel_block
    monkeypatch.setattr(fl, "flash_enabled", lambda: True)
    lc = {"k": jnp.zeros((1, 512, 2, D)), "v": jnp.zeros((1, 512, 2, D)),
          "pos": jnp.zeros((1, 512), jnp.int32)}
    assert rule(1, None, lc, f32) == da.DEFAULT_BLOCK_K
    assert rule(2, None, lc, f32) is None           # a verify width
    assert rule(1, 128, lc, f32) is None            # an SWA ring
    assert rule(1, None, None, f32) is None         # no cache
    assert rule(1, None, {"state": lc["k"]}, f32) is None
    assert rule(1, None, lc, bf16) is None          # not the queries' dtype
    short = {n: a[:, :128] for n, a in lc.items()}
    assert rule(1, None, short, f32) == 128         # one short block
    ragged = {n: a[:, :160] for n, a in lc.items()}
    assert rule(1, None, ragged, f32) is None       # no whole blocks
    odd = {n: (a[:, :, :1].astype(bf16) if n != "pos" else a)
           for n, a in lc.items()}
    assert rule(1, None, odd, bf16) is None         # one packed head
    monkeypatch.setattr(fl, "flash_enabled", lambda: False)
    assert rule(1, None, lc, f32) is None


# -- the programs ------------------------------------------------------------

CTX = 512          # not the tiny vocabulary (256): the sampler slices [slots, vocab]
SLOTS = 8


@pytest.fixture
def kernel_on(monkeypatch):
    """Route attention_forward's one-token branch to the kernel, interpreted
    (as tests/test_flash.py patches the flash kernel in)."""
    calls = []
    orig = da.decode_attention

    def spy(*a, **kw):
        calls.append(1)
        kw["interpret"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(fl, "flash_enabled", lambda: True)
    monkeypatch.setattr(da, "decode_attention", spy)
    return calls


def _args(model, layers, st):
    return (model.params, layers, st["toks"], st["pos"], st["rngs"],
            st["recents"], st["temps"], st["top_ks"], st["top_ps"],
            st["pens"], st["act"])


@pytest.mark.parametrize("family,kw", [
    ("llama", {}),
    # window and full layers mixed: every second layer is a ring
    ("gemma3", {"sliding_window": 64, "sliding_window_pattern": 2}),
])
def test_decode_slots_lowers_to_one_kernel_a_full_layer(monkeypatch, family,
                                                        kw):
    """Lowered for the TPU (nothing compiles or runs): under `vmap` the
    kernel is ONE call a full-attention layer with the pool's rows in its
    own row axis — not pallas_call's batching fallback, a `while`
    that dynamic_slices each row's K and V out of the pool."""
    monkeypatch.setattr(fl, "flash_enabled", lambda: True)
    model = TextModel(tiny_config(family, **kw), dtype=jnp.float32,
                      max_cache_len=CTX)
    pool = model.new_cache(SLOTS, kv_len=CTX)["layers"]
    st = _carries(model, SLOTS, (0, 3))
    text = model._decode_slots.trace(*_args(model, pool, st)).lower(
        lowering_platforms=("tpu",)).as_text()
    full = [s for s, lc in zip(model.cfg.layer_specs(), pool)
            if s.window is None and lc["pos"].shape[1] >= da.MIN_BLOCK_K]
    assert full and (family != "gemma3" or len(full) < len(pool))
    # the layers share one function that holds the kernel (traced and
    # lowered for Mosaic once), and each calls it once
    assert text.count("tpu_custom_call") == 1
    assert "cake_decode_attention" in text
    assert len(re.findall(r"call @decode_rows\b", text)) == len(full)
    # the only loops are the sampler's four searches (sampling.keep_mask)
    assert text.count("stablehlo.while") == 4
    leaf_types = {"x".join(map(str, a.shape)) + "x"
                  + {"float32": "f32", "int32": "i32"}[str(a.dtype)]
                  for a in jax.tree_util.tree_leaves(pool)}
    found = _SLICING.findall(text)
    assert found, "the pattern no longer matches the lowered text"
    hits = [(op, t) for op, t in found if t in leaf_types]
    assert not hits, hits


def _serve_run(model):
    """Rows built the four ways the engine builds them, then 5 greedy
    decode steps with rows 4 and 6 masked out: (ids [steps, slots], bytes
    of the masked-out rows before and after)."""
    rng = np.random.default_rng(35)
    vocab = model.cfg.vocab_size
    prompt = lambda n: rng.integers(1, vocab, n).tolist()
    layers = model.new_cache(SLOTS, kv_len=CTX)["layers"]
    st = _carries(model, SLOTS, (0, 1, 2, 3))

    def admit(row, ids, pos0=0):
        nonlocal layers
        logits, layers = model.prefill_chunk(layers, row, ids, pos0)
        st["toks"] = st["toks"].at[row].set(
            jnp.argmax(logits[0]).astype(jnp.int32))
        st["pos"] = st["pos"].at[row].set(pos0 + len(ids))

    # row 0: a prompt in three chunks, ending past the first block
    p0 = prompt(70)
    for lo, hi in ((0, 32), (32, 64), (64, 70)):
        admit(0, p0[lo:hi], lo)
    # row 1: a 32-token prefix spliced in from row 0, then its own tail
    blk = model.slot_extract(layers, 0, 0, 32)
    layers = model.slot_restore(layers, [blk], 1, 0, 32, True)
    admit(1, prompt(9), 32)
    # row 2: a short prompt in one chunk
    admit(2, prompt(5))
    # row 3: used, released, and admitted again with a shorter prompt
    admit(3, prompt(40))
    layers = model.slot_release(layers, 3)
    admit(3, prompt(11))
    # rows 4 and 6: half-built prefixes a chunked admission left behind
    for row in (4, 6):
        _, layers = model.prefill_chunk(layers, row, prompt(20 + row), 0)
        st["pos"] = st["pos"].at[row].set(20 + row)
    before = [_row_bytes(layers, r) for r in (4, 6)]
    ids = []
    for _ in range(5):
        (packed, layers, st["toks"], st["pos"], st["rngs"],
         st["recents"]) = model.decode_slots(
            layers, st["toks"], st["pos"], st["rngs"], st["recents"],
            st["temps"], st["top_ks"], st["top_ps"], st["pens"], st["act"])
        ids.append(np.asarray(packed)[1])
    return np.stack(ids), before, [_row_bytes(layers, r) for r in (4, 6)]


def test_serve_path_greedy_ids_equal_the_masked_path(kernel_on, monkeypatch):
    cfg = tiny_config("qwen3", max_position_embeddings=512)
    got, before, after = _serve_run(
        TextModel(cfg, dtype=jnp.float32, max_cache_len=CTX))
    assert len(kernel_on) == cfg.num_hidden_layers      # traced once
    assert before == after, "a masked-out row was written"
    monkeypatch.setattr(fl, "flash_enabled", lambda: False)
    want, _, _ = _serve_run(
        TextModel(cfg, dtype=jnp.float32, max_cache_len=CTX))
    assert np.array_equal(got[:, :4], want[:, :4])
    assert len(kernel_on) == cfg.num_hidden_layers
