"""Brumby on the served path: power retention (degree 2) in every layer, a
row that is float32 state (the symmetric square of its keys against its
values) and no keys, values or positions at all.

Pinned here, at tiny widths (d 8, D 36) on the CPU:
  * the adapter: the benchmark's configuration resolves to a retention
    layer in every place; what it cannot honour it refuses;
  * phi(q) . phi(k) == (q . k)^2; one step against numbers worked by hand;
    the step body token by token == the chunk form (chunks of 1, 3, 8 with
    padding masked) == the reference's attention form; the one-pass decode
    identity;
  * the program against the benchmark's plain reference
    (benchmark/reference/brumby.py) through the three steps of
    benchmark/check.py, equal in float32, and the three mechanism controls
    each over the bfloat16 tolerance;
  * through `ServeEngine`: prefill in chunks and decode through the pool
    give `TextModel.generate`'s tokens and the reference's; a crash replays;
    the paged pool (where preemption lives) refuses a model with nothing
    to page;
  * no leaf is addressed by position: `kv_capacity` is None, the flight
    record's `state_bytes` the whole row (`kv_tokens` stays the tokens the
    stepping rows have taken, whatever holds them);
  * the three scopes in the lowering, /health's kind, loader <-> export
    with and without the gate's bias, `--tp 2` refused, the prefix cache
    refused before it extracts a block it could never hold.
"""
import importlib
import json
import os
import re
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import TextModel, init_params, tiny_config
from cake_tpu.models.brumby import (EPS, padded_width, phi, retention_chunk,
                                    retention_step, state_width)
from cake_tpu.models.common.cache import (is_positional, kv_capacity,
                                          row_state_bytes)
from cake_tpu.models.common.config import config_from_hf_dict
from cake_tpu.models.common.layers import make_rope
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine, faults

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
GREEDY = SamplingConfig(temperature=0.0)
CTX = 128
# bfloat16 at hidden 64, three layers (the fixture's initialisers): served
# 0.004-0.006, the state dropped at every block 0.04 or more, the gate off
# 0.1, the power 1 0.9
BF16_TOLERANCE = 0.015

# the published Brumby-14B-Base keys at tiny widths
TINY_HF = {
    "model_type": "brumby", "attention_bias": False, "head_dim": 8,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 512, "max_window_layers": 3,
    "num_attention_heads": 4, "num_hidden_layers": 3,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 512,
}


@pytest.fixture(scope="module")
def bench():
    """benchmark/ importable: check.py, weights.py, the family's file. The
    family's residual projections are drawn at STD / sqrt(80) for a stream
    of hidden 5,120 under an embedding of 1.07; at hidden 64 (an embedding
    of 0.12, fan-ins of 32 and 128) they would add a hundredth of that and
    no control could show: here they and the embedding are drawn at STD
    like the rest."""
    sys.path.insert(0, BENCH)
    try:
        mods = {name: importlib.import_module(name)
                for name in ("check", "weights", "reference.brumby")}
        ref = mods["reference.brumby"]
        scaled = ref.RESIDUAL_STD, ref.EMBED_SCALE
        assert abs(scaled[0] - 0.02 / 80 ** 0.5) < 1e-9
        ref.RESIDUAL_STD, ref.EMBED_SCALE = ref.STD, 1 / 8
        yield mods
        ref.RESIDUAL_STD, ref.EMBED_SCALE = scaled
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def model():
    return TextModel(tiny_config("brumby"), dtype=jnp.float32,
                     max_cache_len=CTX)


def _settle(eng, timeout=30.0):
    """The engine keeps its last, overshot step in flight behind a wait."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = eng.flight.snapshot()
        if eng._inflight is None and snap and not snap[-1]["occupancy"]:
            return
        time.sleep(0.01)
    raise AssertionError("the engine did not settle")


# -- the adapter --------------------------------------------------------------

def test_adapter_resolves_the_benchmarks_configuration():
    with open(os.path.join(BENCH, "configs", "brumby-14b-l8.json")) as f:
        hf = json.load(f)
    hf.pop("benchmark")
    cfg = config_from_hf_dict(hf)
    assert cfg.arch == "brumby" and cfg.retention.power == 2
    assert cfg.qk_norm and not cfg.qkv_bias and cfg.rope_scaling is None
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size,
            cfg.vocab_size) == (8, 5120, 17408, 151936)
    specs = set(cfg.layer_specs())
    assert len(specs) == 1
    spec = specs.pop()
    assert (spec.kind, spec.recurrent, spec.use_rope, spec.window,
            spec.is_moe) == ("retention", True, True, None, False)
    assert cfg.has_recurrent_state
    # a row: 8 layers x 8 key/value heads x (8,320 x 128 + 8,320) float32
    # (272,646,144 B at the minimal width of 8,256; 65 lane tiles lie)
    assert cfg.attention_kinds() == [{
        "kind": "retention", "layers": 8, "power": 2, "heads": 40,
        "kv_heads": 8, "key_dim": 128, "state_width": 8256,
        "padded_width": 8320, "rotary_dim": 128, "rope_theta": 1000000.0,
        "state_bytes": 274_759_680}]


@pytest.mark.parametrize("key,value,says", [
    ("sliding_window", 4096, "sliding window"),
    ("use_sliding_window", True, "sliding window"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}, "rope_scaling"),
    ("retention_power", 4, "power 4"),
    ("attention_bias", True, "attention_bias"),
])
def test_adapter_refuses_what_it_cannot_honour(key, value, says):
    with pytest.raises(ValueError, match=says):
        config_from_hf_dict({**TINY_HF, key: value})


# -- the mathematics ----------------------------------------------------------

def _draws(b=2, c=11, hq=4, hkv=2, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, c, hq, d))
    k = jax.random.normal(ks[1], (b, c, hkv, d))
    v = jax.random.normal(ks[2], (b, c, hkv, d))
    log_g = jax.nn.log_sigmoid(2.0 * jax.random.normal(ks[3], (b, c, hkv)))
    return q, k, v, log_g


def _zero_state(b, hkv, d):
    """D = 36 products at d = 8, in one 128-lane tile as a row holds them
    (the state transposed: [d, D'])."""
    return (jnp.zeros((b, hkv, d, padded_width(d))),
            jnp.zeros((b, hkv, padded_width(d))))


def _token_by_token(q, k, v, log_g):
    state, norm = _zero_state(q.shape[0], k.shape[2], q.shape[3])
    ys = []
    for t in range(q.shape[1]):
        state, norm, y = retention_step(state, norm, q[:, t], k[:, t],
                                        v[:, t], log_g[:, t])
        ys.append(y)
    return state, norm, jnp.stack(ys, 1)


def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k():
    q, k, _, _ = _draws()
    assert state_width(8) == 36 and state_width(128) == 8256
    assert padded_width(8) == 128 and padded_width(128) == 8320
    assert phi(k).shape == (2, 11, 2, 128) and phi(k).dtype == jnp.float32
    assert float(jnp.abs(phi(k)[..., 36:]).max()) == 0
    got = jnp.einsum("bthn,bthn->bth", phi(q[:, :, :2]), phi(k))
    want = jnp.einsum("bthd,bthd->bth", q[:, :, :2], k) ** 2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    # bfloat16 in: the picks are exact, the products float32
    qb = q.astype(jnp.bfloat16)
    np.testing.assert_array_equal(phi(qb), phi(qb.astype(jnp.float32)))


def test_one_step_against_numbers_worked_by_hand():
    """One key/value head of width 2 (phi(x) = [x0^2, sqrt2 x0 x1, x1^2]),
    two query heads, two tokens, g = 1/2 at the second."""
    k1, v1 = [1.0, 2.0], [1.0, -1.0]
    k2, v2 = [3.0, -1.0], [0.5, 2.0]
    q2 = [[1.0, 1.0], [2.0, 0.0]]
    state, norm = _zero_state(1, 1, 2)
    arr = lambda x: jnp.asarray(x, jnp.float32)[None]   # noqa: E731
    state, norm, y1 = retention_step(
        state, norm, arr([[0.0, 1.0], [1.0, 0.0]]), arr([k1]), arr([v1]),
        jnp.zeros((1, 1)))
    r2 = 2 ** 0.5
    np.testing.assert_allclose(norm[0, 0, :3], [1.0, 2 * r2, 4.0],
                               rtol=1e-6)
    np.testing.assert_allclose(state[0, 0, :, :3],
                               [[1.0, 2 * r2, 4.0], [-1.0, -2 * r2, -4.0]],
                               rtol=1e-6)
    assert float(jnp.abs(state[0, 0, :, 3:]).max()) == 0
    # alone in its row, a key gives its value back whatever the query
    np.testing.assert_allclose(y1[0], [v1, v1], rtol=1e-5)
    state, norm, y2 = retention_step(
        state, norm, arr(q2), arr([k2]), arr([v2]),
        jnp.log(jnp.full((1, 1), 0.5)))
    # z = z/2 + phi(k2) = [0.5 + 9, r2 - 3 r2, 2 + 1]
    np.testing.assert_allclose(norm[0, 0, :3], [9.5, -2 * r2, 3.0],
                               rtol=1e-6)
    # head 0: weights 1/2 (q.k1)^2 = 4.5 and (q.k2)^2 = 4
    w1, w2 = 0.5 * 9.0, 4.0
    want0 = [(w1 * v1[i] + w2 * v2[i]) / (w1 + w2 + EPS) for i in (0, 1)]
    # head 1: 1/2 (2)^2 = 2 and (6)^2 = 36
    w1, w2 = 2.0, 36.0
    want1 = [(w1 * v1[i] + w2 * v2[i]) / (w1 + w2 + EPS) for i in (0, 1)]
    np.testing.assert_allclose(y2[0], [want0, want1], rtol=1e-5)


@pytest.mark.parametrize("sizes", [[11], [3, 8], [1] * 11, [8, 3]],
                         ids=["whole", "3+8", "ones", "8+3"])
def test_chunk_form_is_the_step_body_token_by_token(sizes):
    """Chunks padded to a bucket of 8 (16 for the whole), the padding
    filled with numbers that must advance nothing. The read-out's
    denominator z . phi(q) is a sum of 36 signed products: where (q . k)^2
    is small beside |phi(q)| |phi(k)| float32 leaves 2e-4 of y."""
    q, k, v, log_g = _draws()
    want_state, want_norm, want = _token_by_token(q, k, v, log_g)
    state, norm = _zero_state(2, 2, 8)
    out, t = [], 0
    for n in sizes:
        width = 1 if n == 1 else 8 if n <= 8 else 16

        def cut(a):
            pad = [(0, 0), (0, width - n)] + [(0, 0)] * (a.ndim - 2)
            return jnp.pad(a[:, t:t + n], pad, constant_values=0.3)

        state, norm, y = retention_chunk(state, norm, cut(q), cut(k), cut(v),
                                         cut(log_g), jnp.int32(n))
        out.append(y[:, :n])
        t += n
    got = jnp.concatenate(out, 1)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(state, want_state, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(norm, want_norm, rtol=1e-5, atol=1e-5)


def test_a_masked_step_leaves_the_state_as_it_was():
    q, k, v, log_g = _draws(c=1)
    state = jax.random.normal(jax.random.PRNGKey(9), (2, 2, 8, 128))
    norm = jax.random.normal(jax.random.PRNGKey(8), (2, 2, 128))
    s2, n2, _ = retention_chunk(state, norm, q, k, v, log_g, jnp.int32(0))
    np.testing.assert_array_equal(s2, state)
    np.testing.assert_array_equal(n2, norm)


def test_the_one_pass_decode_identity():
    """y_t = (g S_{t-1}^T phi(q) + (q . k)^2 v) / (g z_{t-1} . phi(q) +
    (q . k)^2 + eps): the read-out of the OLD state and the token's own
    weight give what updating first and reading the NEW state gives."""
    q, k, v, log_g = _draws(c=6, seed=3)
    state, norm, _ = _token_by_token(q[:, :5], k[:, :5], v[:, :5],
                                     log_g[:, :5])
    _, _, want = retention_step(state, norm, q[:, 5], k[:, 5], v[:, 5],
                                log_g[:, 5])
    g = jnp.exp(log_g[:, 5])                              # [B, Hkv]
    pq = phi(q[:, 5]).reshape(2, 2, 2, 128)               # [B, Hkv, G, D']
    own = jnp.einsum("bhgd,bhd->bhg", q[:, 5].reshape(2, 2, 2, 8),
                     k[:, 5]) ** 2
    num = g[..., None, None] * jnp.einsum("bhgn,bhdn->bhgd", pq, state) \
        + own[..., None] * v[:, 5][:, :, None, :]
    den = g[..., None] * jnp.einsum("bhgn,bhn->bhg", pq, norm) + own + EPS
    by_hand = (num / den[..., None]).reshape(2, 4, 8)
    np.testing.assert_allclose(by_hand, want, rtol=5e-4, atol=5e-4)
    # and it is what the chunk form computes at C = 1
    _, _, got = retention_chunk(state, norm, q[:, 5:], k[:, 5:], v[:, 5:],
                                log_g[:, 5:])
    np.testing.assert_allclose(got[:, 0], by_hand, rtol=1e-5, atol=1e-5)


def test_the_state_kernel_is_the_xla_arm_of_a_decode_step(monkeypatch):
    """ops/retention_state.py interpreted: one pass over the state gives
    the read-out of the OLD state and the decayed update, for every row of
    a mapped batch (the decode program maps its rows), a masked row left
    as it was; through `retention_chunk` the kernel's arm and the XLA arm
    give one y (the kernel's read-out rounds phi(q) and S to bfloat16 once,
    as the TPU's default precision does for the XLA arm)."""
    from cake_tpu.models import brumby
    from cake_tpu.ops.retention_state import lane_tile, retention_state_step
    assert lane_tile(8320) == 1664 and lane_tile(128) == 128
    q, k, v, log_g = _draws(b=3, c=1, seed=5)
    state = jax.random.normal(jax.random.PRNGKey(9), (3, 2, 8, 128))
    norm = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (3, 2, 128)))
    want_state, want_norm, want = retention_chunk(state, norm, q, k, v,
                                                  log_g)
    monkeypatch.setattr(brumby, "state_kernel_enabled", lambda: True)
    monkeypatch.setattr(
        brumby, "retention_state_step",
        lambda *a: retention_state_step(*a, interpret=True))
    got_state, got_norm, got = retention_chunk(state, norm, q, k, v, log_g)
    np.testing.assert_allclose(got_state, want_state, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got_norm, want_norm)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)
    # mapped over rows, one of them masked out of the step
    step = jax.vmap(lambda s, n, qq, kk, vv, lg, act: retention_chunk(
        s[None], n[None], qq[None], kk[None], vv[None], lg[None], act))
    act = jnp.asarray([1, 0, 1], jnp.int32)
    vs, vn, vy = step(state, norm, q, k, v, log_g, act)
    np.testing.assert_array_equal(vs[1, 0], state[1])
    np.testing.assert_array_equal(vn[1, 0], norm[1])
    np.testing.assert_allclose(vs[0, 0], got_state[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(vy[2, 0], got[2], rtol=1e-5, atol=1e-5)


def test_chunk_form_is_the_references_attention_form(bench):
    """One layer's mixer, the reference's [S, S] rows of weights against
    the chunk form on a carried state, through the layer's own
    projections."""
    ref, W = bench["reference.brumby"], bench["weights"]
    cfg = config_from_hf_dict(TINY_HF)
    w = W.make_weights(ref, TINY_HF, 7, jnp.float32)
    p = w["layers"][0]["self_attn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 64))
    c = dict(ref.qwen3._static(TINY_HF))
    want = ref.retention(x, p, c)
    from cake_tpu.models.common.layers import _attn
    spec, rope = cfg.layer_spec(0), make_rope(cfg)
    lc = {"state": jnp.zeros((1, 2, 8, 128)), "norm": jnp.zeros((1, 2, 128))}
    outs = []
    for t0, n in ((0, 16), (16, 16), (32, 8)):
        y, lc = _attn(cfg, spec, {"self_attn": p}, x[None, t0:t0 + n], lc,
                      jnp.int32(t0), rope)
        outs.append(y[0])
    np.testing.assert_allclose(jnp.concatenate(outs), want, rtol=2e-4,
                               atol=2e-5)


# -- the program against the reference ---------------------------------------

def test_program_equals_the_reference_through_the_check(bench):
    check, W, ref = (bench[k] for k in ("check", "weights",
                                        "reference.brumby"))
    cfg = config_from_hf_dict(TINY_HF)
    seed = 2 ** 31 + 53
    sound = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        w = W.make_weights(ref, TINY_HF, seed, dtype)
        m = TextModel(cfg, {**w, "rope": make_rope(cfg)}, dtype=dtype,
                      seed=1, max_cache_len=256)
        served = check.served_logits(
            m, 4, 256, 32, check.check_ids(seed, 512, [20, 90, 200]), 3,
            {"temperature": 0.7, "top_p": 0.9})
        got = check.compare(ref, TINY_HF, w, served)
        sound[dtype] = got["pooled"]
        if dtype == jnp.float32:
            # chunks of 32 with last buckets padded: the state is carried
            # across nine boundaries, then decode with three of four rows
            # active, a chunk behind it. Float32 against float32 at the
            # highest precision: what is left is the order of the sums
            assert len(got["points"]) == 14 and got["worst"] < 2e-5, got
            assert any("tail_after_decode" in k for k in got["points"])
    assert sound[jnp.bfloat16] < BF16_TOLERANCE, sound
    controls = {"gate": {"gate": "off"}, "power": {"power": 1},
                "state": {"state": "dropped", "state_block": 32}}
    read = {}
    for name, kw in controls.items():
        alt = types.SimpleNamespace(
            forward_logits=lambda h, ww, ids, pos, quant=None, kw=kw: (
                ref.forward_logits(h, ww, ids, pos,
                                   **(kw if quant else {}))))
        read[name] = check.control(alt, TINY_HF, w, served, name)["pooled"]
    assert all(v > BF16_TOLERANCE for v in read.values()), (read, sound)
    # (at hidden 64 int8 is no decade below bf16, as it is at 5,120)
    int8 = check.control(ref, TINY_HF, w, served, "int8")["pooled"]
    assert int8 > 1.5 * sound[jnp.bfloat16], (int8, sound)


# -- through the engine --------------------------------------------------------

P_A = [3 + (i * 7) % 200 for i in range(45)]
P_B = [5 + (i * 11) % 180 for i in range(70)]
P_C = [9, 4, 33, 120, 77]


def _reference_greedy(bench, w, ids, n):
    ref, ids = bench["reference.brumby"], list(ids)
    for _ in range(n):
        logits = ref.forward_logits(TINY_HF, w, ids, [len(ids) - 1])
        ids.append(int(np.argmax(logits[0])))
    return ids[-n:]


def test_the_engine_gives_generates_tokens_and_the_references(bench):
    """Prompts of 45, 70 and 5 tokens in chunks of 16 (the state carried
    across boundaries, last buckets padded), decoded side by side through
    the pool."""
    W, ref = bench["weights"], bench["reference.brumby"]
    cfg = config_from_hf_dict(TINY_HF)
    w = W.make_weights(ref, TINY_HF, 53, jnp.float32)
    m = TextModel(cfg, {**w, "rope": make_rope(cfg)}, dtype=jnp.float32,
                  seed=1, max_cache_len=CTX)
    plans = ((P_A, 10), (P_B, 8), (P_C, 9))
    want = [m.generate(list(p), max_new_tokens=n, sampling=GREEDY)[0]
            for p, n in plans]
    assert want[0] == _reference_greedy(bench, w, P_A, 10)
    eng = ServeEngine(m, slots=4, max_queue=8, ctx_len=CTX, prefill_chunk=16,
                      prefix_cache_mb=0)      # as the cell runs it
    try:
        assert eng.prefix_cache is None and eng.paged is None
        rs = [eng.submit(p, max_new_tokens=n, sampling=GREEDY)
              for p, n in plans]
        assert all(r.wait(300) for r in rs)
        for r, toks in zip(rs, want):
            assert "error" not in r.result, r.result.get("error")
            assert r.result["tokens"] == toks
        _settle(eng)
        row = 3 * 2 * (128 * 8 + 128) * 4
        assert eng._row_state_bytes == row
        stepping = [r for r in eng.flight.snapshot() if r["occupancy"]]
        assert stepping and all(
            r["state_bytes"] == row * r["occupancy"]
            and r["ring_tokens"] == 0 for r in stepping)
    finally:
        eng.close()


def test_a_crashed_step_replays_to_the_same_tokens(model):
    plans = ((P_A, 9), (P_C, 8))
    want = [model.generate(list(p), max_new_tokens=n, sampling=GREEDY)[0]
            for p, n in plans]
    faults.install("raise_on_step=5;kind=device")
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                      prefill_chunk=16)
    try:
        rs = [eng.submit(p, max_new_tokens=n, sampling=GREEDY)
              for p, n in plans]
        assert all(r.wait(300) for r in rs)
        for r, toks in zip(rs, want):
            assert "error" not in r.result, r.result.get("error")
            assert r.result["tokens"] == toks
        assert eng.supervisor.rebuild_count == 1
    finally:
        faults.clear()
        eng.close()


def test_the_paged_pool_refuses_a_model_with_nothing_to_page(model):
    """Preemption lives in the paged pool alone (a row is parked when the
    blocks run out), and the paged pool pages full buffers of keys and
    values: a model of retention rows has none, and is told so at once
    rather than served from a pool whose blocks hold nothing."""
    with pytest.raises(ValueError, match="paged KV needs at least one "
                       "full-attention layer"):
        ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                    prefill_chunk=16, kv_blocks=12, kv_block_tokens=8)


# -- no leaf is addressed by position ------------------------------------------

def test_a_row_is_state_alone_and_nothing_asks_for_a_pos_leaf(model):
    layers = model.new_cache(4, kv_len=CTX)["layers"]
    assert [sorted(lc) for lc in layers] == [["norm", "state"]] * 4
    assert not any(map(is_positional, layers))
    assert layers[0]["state"].shape == (4, 2, 8, 128)
    assert layers[0]["norm"].shape == (4, 2, 128)
    assert {leaf.dtype for lc in layers for leaf in lc.values()} == \
        {jnp.dtype(jnp.float32)}
    assert row_state_bytes(layers) == 4 * 2 * (128 * 8 + 128) * 4
    # the context limit is the rope table's reach alone
    assert kv_capacity(model.cfg, {"layers": layers}) is None
    ids = [3 + (i * 5) % 200 for i in range(20)]
    _, filled = model.prefill_chunk(layers, 2, ids, 0)
    assert model.last_chunk_attn is not None
    with pytest.raises(ValueError, match="past cache end"):
        model.prefill_chunk(filled, 2, ids, CTX - 10)
    assert float(jnp.abs(filled[1]["state"][2]).max()) > 1e-4
    assert float(jnp.abs(filled[1]["state"][jnp.asarray([0, 1, 3])]
                         ).max()) == 0
    cleared = model.slot_release(filled, 2)
    assert all(float(jnp.abs(leaf).max()) == 0
               for lc in cleared for leaf in lc.values())


def test_the_programs_carry_the_three_scopes(model):
    slots = 4
    layers = model.new_cache(slots, kv_len=CTX)["layers"]
    z = lambda dt: jnp.zeros((slots,), dt)      # noqa: E731
    args = (model.params, layers, z(jnp.int32), z(jnp.int32),
            jnp.stack([jax.random.PRNGKey(i) for i in range(slots)]),
            jnp.full((slots, 8), -1, jnp.int32), z(jnp.float32),
            jnp.full((slots,), 256, jnp.int32), jnp.ones((slots,)),
            jnp.ones((slots,)), z(jnp.bool_))
    decode = model._decode_slots.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    i32 = jnp.int32
    chunk = model._prefill_slot.trace(
        model.params, jnp.zeros((1, 32), i32), layers, jnp.asarray(0, i32),
        jnp.asarray(0, i32), jnp.asarray(32, i32),
        flash_mode="off").lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    from cake_tpu.obs.spans import SCOPE_CATALOG
    names = {n for n, _ in SCOPE_CATALOG}
    for text in (decode, chunk):
        for scope in ("cake.attn.retention", "cake.attn.retention.proj",
                      "cake.attn.retention.expand",
                      "cake.attn.retention.scan"):
            assert scope in text and scope in names, scope
        # nested: a reader of the parent scope counts the mixer's ops
        for outer, inner in (("attn", "attn.retention"),
                             ("attn.retention", "attn.retention.scan")):
            assert re.search(rf"[/(]cake\.{re.escape(outer)}[/)]"
                             rf"(?:[^\"]*[/(])?cake\.{re.escape(inner)}[/)]",
                             text), inner
    # matrix products: no loop over the tokens of a chunk (the decode
    # program's only loops are the sampler's searches)
    assert "stablehlo.while" not in chunk


def test_health_and_the_flight_record_name_the_kind(model):
    eng = ServeEngine(model, slots=2, max_queue=2, ctx_len=CTX,
                      prefill_chunk=32)
    try:
        want = [{"kind": "retention", "layers": 4, "power": 2, "heads": 4,
                 "kv_heads": 2, "key_dim": 8, "state_width": 36,
                 "padded_width": 128, "rotary_dim": 8,
                 "rope_theta": 1000000.0,
                 "state_bytes": 4 * 2 * (128 * 8 + 128) * 4}]
        assert eng.health()["attention_kinds"] == want
        assert eng.flight.static["attention_kinds"] == want
    finally:
        eng.close()


# -- checkpoints -----------------------------------------------------------------

@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
def test_loader_and_export_round_trip(tmp_path, bias):
    from cake_tpu.utils.export import params_to_hf_tensors
    from cake_tpu.utils.loaders import load_model_params
    from cake_tpu.utils.safetensors_io import save_safetensors
    cfg = config_from_hf_dict(TINY_HF)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tensors = params_to_hf_tensors(cfg, params)
    sa = "model.layers.1.self_attn."
    assert tensors[sa + "q_proj.weight"].shape == (32, 64)
    assert tensors[sa + "k_proj.weight"].shape == (16, 64)
    assert tensors[sa + "q_norm.weight"].shape == (8,)
    assert tensors[sa + "g_proj.weight"].shape == (2, 64)
    assert tensors[sa + "g_proj.bias"].shape == (2,)
    if not bias:
        # a checkpoint without the gate's bias loads with a zero one
        for i in range(cfg.num_hidden_layers):
            del tensors[f"model.layers.{i}.self_attn.g_proj.bias"]
    save_safetensors(str(tmp_path / "model.safetensors"), tensors)
    with open(tmp_path / "config.json", "w") as f:
        json.dump(TINY_HF, f)
    loaded = load_model_params(cfg, str(tmp_path), jnp.bfloat16)
    got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    for path, want in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if "g_proj" in name and "bias" in name and not bias:
            want = jnp.zeros_like(want)
        np.testing.assert_allclose(
            np.asarray(got[path], np.float32), np.asarray(want, np.float32),
            atol=5e-2, err_msg=name)


def test_tp_above_one_is_refused_with_the_sentence():
    from jax.sharding import Mesh

    from cake_tpu.parallel.sharding import check_tp_divisibility
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("tp",))
    with pytest.raises(ValueError, match="--tp 2 is not supported for "
                       "brumby.*no placement of a power-retention row's "
                       "state"):
        check_tp_divisibility(tiny_config("brumby"), mesh)
    with pytest.raises(ValueError, match="--tp 2 is not supported"):
        TextModel(tiny_config("brumby"), dtype=jnp.float32,
                  max_cache_len=CTX, mesh=mesh)


# -- the prefix cache ------------------------------------------------------------

def test_the_prefix_cache_is_refused_before_a_block_is_extracted(
        model, monkeypatch, caplog):
    """A block of this model is its whole row's state: 36,864 B of float32
    here (275 MB at the published widths). A capacity under one block
    builds no cache, says so once, and no chunk pays a `slot_extract`."""
    from cake_tpu.serve.prefix_cache import PrefixCache
    one = PrefixCache.block_bytes(model, CTX, 16)
    assert one == 4 * 2 * (128 * 8 + 128) * 4
    assert "one block" in PrefixCache.refusal(CTX, 16, 0.02, one)
    assert PrefixCache.refusal(CTX, 16, 1.0, one) is None
    assert PrefixCache.build(model, CTX, 16, 0.02) is None
    calls = []
    monkeypatch.setattr(model, "slot_extract",
                        lambda *a, **k: calls.append(a) or 1 / 0)
    with caplog.at_level("WARNING", logger="cake_tpu.serve.engine"):
        eng = ServeEngine(model, slots=2, max_queue=2, ctx_len=CTX,
                          prefill_chunk=16, prefix_cache_mb=0.02)
    try:
        assert eng.prefix_cache is None
        assert sum("one block" in r.getMessage()
                   for r in caplog.records) == 1
        r = eng.submit(P_B, max_new_tokens=3, sampling=GREEDY)
        assert r.wait(300) and "error" not in r.result
        assert not calls
    finally:
        eng.close()
    # with room for two blocks the cache is built and holds what it is given
    pc = PrefixCache.build(model, CTX, 16, 2.5 * one / 2 ** 20)
    assert pc is not None and pc.capacity >= 2 * one
