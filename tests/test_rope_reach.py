"""The rope tables a TextModel holds end where its caches do.

`make_rope(cfg)` builds cos and sin of `cfg.max_seq_len` rows, the published
reach (1,048,576 for Laguna-S-2.1 and MiMo-V2.5); a model built with
`max_cache_len` below that keeps the first `max_cache_len` rows of each
(`layers.cut_rope`, in `TextModel.__init__`), because on the chip XLA laid a
table narrower than the lanes out by rows, WHOLE, in front of every step's
gather of a few positions (PERF.md, PR 49).

Pinned here:
  * the rows kept are the full tables' first rows to every bit, for a plain
    table, a YaRN-scaled one (its attention factor in cos and sin) and a
    local table of its own theta and width;
  * a model built the way the benchmark builds it (`{**w, "rope":
    make_rope(cfg)}`, `max_cache_len` < `max_seq_len`) holds `max_cache_len`
    rows and serves, chunk by chunk and step by step, the logits and the
    sampled tokens of one that holds the full tables, to every digit in
    float32;
  * a chunk whose padded bucket runs past the table, and a free pool row
    whose carried position lies far past it, gather the last row (jnp's
    indexing clamps) and move no valid logit: no clamp op is needed;
  * a model with no rotating layer still holds no table, one served at its
    full `max_seq_len` keeps the tables it was given;
  * `/health` and the flight record's `static` say what is held:
    `rope_rows`, `rope_bytes`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import TextModel, init_params, tiny_config
from cake_tpu.models.common.layers import cut_rope, make_rope
from cake_tpu.ops.rope import rope_tables
from cake_tpu.serve import ServeEngine
from cake_tpu.serve.engine import RECENT_N

PUBLISHED, REACH = 512, 128     # max_position_embeddings, max_cache_len


def _cfg(arch):
    return tiny_config(arch, max_position_embeddings=PUBLISHED)


# -- the tables -----------------------------------------------------------------

# which tables of which family, and the arguments rope_tables built them from
def _main(c):
    return c.rotary_dim, c.rope_theta, c.rope_scaling


def _local(c):
    return c.local_rotary_dim, c.local_rope_theta, c.local_rope_scaling


_TABLES = {"plain": ("qwen3", ("cos", "sin"), _main),
           "yarn": ("laguna", ("cos", "sin"), _main),
           "local": ("laguna", ("cos_local", "sin_local"), _local)}


@pytest.mark.parametrize("kind", list(_TABLES))
def test_the_rows_kept_are_the_full_tables_first_rows_to_every_bit(kind):
    arch, names, args = _TABLES[kind]
    cfg = _cfg(arch)
    full = make_rope(cfg)
    cut = cut_rope(full, REACH)
    assert set(cut) == set(full)
    assert (args(cfg)[2] is not None) == (kind == "yarn")
    # a table made for REACH positions alone reads the same: a row depends
    # on its own position only
    made = dict(zip(names, rope_tables(REACH, *args(cfg))))
    for name in names:
        assert full[name].shape[0] == PUBLISHED
        assert cut[name].shape == (REACH,) + full[name].shape[1:]
        assert cut[name].dtype == full[name].dtype == jnp.float32
        want = np.asarray(full[name])[:REACH].tobytes()
        assert np.asarray(cut[name]).tobytes() == want
        assert np.asarray(made[name]).tobytes() == want
    if kind == "yarn":      # the attention factor is in what was kept
        assert float(cut["cos"][0, 0]) == pytest.approx(
            0.1 * np.log(8.0) + 1)
    # nothing to cut: the tables themselves, no new buffer
    assert all(cut_rope(full, PUBLISHED)[n] is full[n] for n in full)
    assert all(cut_rope(cut, PUBLISHED)[n] is cut[n] for n in cut)


# -- a model cut to its reach against one that holds the full tables ------------

CHUNK, PROMPT, STEPS, TAIL = 32, 100, 3, 20
FAR = 2 ** 30       # a free row's carried position, far past any table


def _serve(model):
    """The served path as benchmark/check.py walks it, on a pool of four
    rows: a 100-token prompt in chunks of 32 into row 3 (the last chunk 4
    tokens in a bucket of 32 that ends at the table's end), three sampled
    decode steps beside free rows whose carried positions lie far past the
    table, then a 20-token chunk at position 103, whose bucket of 32 runs
    to position 134: past the 128 rows a cut model holds."""
    slots, row = 4, 3
    ids = [3 + (i * 7) % 200 for i in range(PROMPT + TAIL)]
    layers = model.new_cache(slots, kv_len=REACH)["layers"]
    logits = {}
    for p0 in range(0, PROMPT, CHUNK):
        part = ids[p0:min(p0 + CHUNK, PROMPT)]
        got, layers = model.prefill_chunk(layers, row, part, p0)
        logits[p0 + len(part) - 1] = np.asarray(got[0])
    temp, top_p = jnp.float32(0.7), jnp.float32(0.9)
    first = model.sample_one(
        got[0], jax.random.PRNGKey(row), temp, jnp.int32(256), top_p,
        jnp.float32(1.0), jnp.full((RECENT_N,), -1, jnp.int32))
    toks = jnp.zeros((slots,), jnp.int32).at[row].set(first)
    pos = jnp.full((slots,), FAR, jnp.int32).at[row].set(PROMPT)
    act = jnp.zeros((slots,), jnp.bool_).at[row].set(True)
    rngs = jnp.stack([jax.random.PRNGKey(i) for i in range(slots)])
    recents = jnp.full((slots, RECENT_N), -1, jnp.int32)
    sampled = [int(first)]
    for _ in range(STEPS):
        packed, layers, toks, pos, rngs, recents = model.decode_slots(
            layers, toks, pos, rngs, recents,
            jnp.full((slots,), 0.7, jnp.float32),
            jnp.full((slots,), 256, jnp.int32),
            jnp.full((slots,), 0.9, jnp.float32),
            jnp.ones((slots,), jnp.float32), act)
        sampled.append(int(np.asarray(packed)[1, row]))
    p0 = PROMPT + STEPS
    assert p0 + TAIL <= REACH < p0 + CHUNK      # the bucket passes the table
    got, layers = model.prefill_chunk(layers, row, ids[PROMPT:], p0)
    logits["tail"] = np.asarray(got[0])
    return logits, sampled


@pytest.fixture(scope="module", params=["qwen3", "laguna"])
def pair(request):
    """What a model built as the benchmark builds it serves, beside what the
    same model serves from the full tables (put back in its params: the
    programs take them as an argument)."""
    cfg = _cfg(request.param)
    w = {k: v for k, v in init_params(cfg, jax.random.PRNGKey(7),
                                      jnp.float32).items() if k != "rope"}
    model = TextModel(cfg, {**w, "rope": make_rope(cfg)}, dtype=jnp.float32,
                      seed=1, max_cache_len=REACH)
    held = dict(model.params["rope"])
    cut = _serve(model)
    model.params = {**model.params, "rope": make_rope(cfg)}
    return cfg, held, cut, _serve(model)


def test_the_benchmarks_call_sequence_holds_max_cache_len_rows(pair):
    cfg, held, _, _ = pair
    full = make_rope(cfg)
    assert set(held) == set(full) and len(held) == (
        4 if cfg.local_rope_theta is not None else 2)
    for name, table in held.items():
        assert table.shape[0] == REACH < full[name].shape[0]
        assert np.array_equal(np.asarray(table),
                              np.asarray(full[name])[:REACH])


def test_chunks_and_sampled_steps_equal_the_full_tables_to_every_digit(pair):
    _, _, (logits, sampled), (want_logits, want_sampled) = pair
    ends = [p for p in logits if p != "tail"]
    assert ends == [31, 63, 95, 99]
    for p in ends:
        assert np.isfinite(logits[p]).all()
        assert np.array_equal(logits[p], want_logits[p]), p
    # the steps sampled at 0.7 / top-p 0.9 beside free rows at position
    # 2^30, which gathered the table's last row in both models
    assert sampled == want_sampled and len(sampled) == STEPS + 1


def test_a_bucket_that_runs_past_the_table_moves_no_valid_logit(pair):
    _, _, (logits, _), (want_logits, _) = pair
    assert np.isfinite(logits["tail"]).all()
    assert np.array_equal(logits["tail"], want_logits["tail"])
    # and the tail is no constant: it read the positions it was given
    assert not np.array_equal(logits["tail"], logits[99])


# -- what is held, and who says so ------------------------------------------------

def test_a_model_without_a_rotating_layer_holds_no_table():
    cfg = tiny_config("jamba")
    assert make_rope(cfg) == {} and cut_rope({}, REACH) == {}
    model = TextModel(cfg, dtype=jnp.float32, max_cache_len=64)
    assert model.params["rope"] == {}
    eng = ServeEngine(model, slots=2, max_queue=2, ctx_len=64,
                      prefill_chunk=32)
    try:
        h = eng.health()
        assert (h["rope_rows"], h["rope_bytes"]) == (0, 0)
        assert eng.flight.static["rope_rows"] == 0
        assert eng.flight.static["rope_bytes"] == 0
    finally:
        eng.close()


def test_a_model_served_at_its_full_reach_keeps_the_tables_it_was_given():
    cfg = _cfg("qwen3")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    model = TextModel(cfg, params, dtype=jnp.float32)
    assert model.max_cache_len == PUBLISHED
    assert all(model.params["rope"][n] is params["rope"][n]
               for n in ("cos", "sin"))
    # a reach asked for beyond the published one is the published one
    model = TextModel(cfg, params, dtype=jnp.float32, max_cache_len=4096)
    assert model.params["rope"]["cos"].shape[0] == PUBLISHED


@pytest.mark.parametrize("arch,tables", [
    ("qwen3", {"cos": 8, "sin": 8}),
    ("laguna", {"cos": 4, "sin": 4, "cos_local": 8, "sin_local": 8})])
def test_health_and_the_flight_record_say_what_is_held(arch, tables):
    model = TextModel(_cfg(arch), dtype=jnp.float32, max_cache_len=REACH)
    assert {n: t.shape for n, t in model.params["rope"].items()} == {
        n: (REACH, w) for n, w in tables.items()}
    eng = ServeEngine(model, slots=2, max_queue=2, ctx_len=64,
                      prefill_chunk=32)
    try:
        want = {"rope_rows": REACH,
                "rope_bytes": REACH * sum(tables.values()) * 4}
        h = eng.health()
        assert {k: h[k] for k in want} == want
        assert {k: eng.flight.static[k] for k in want} == want
        # the rows are the model's reach, not the engine's shorter context
        assert eng.ctx == 64
    finally:
        eng.close()
