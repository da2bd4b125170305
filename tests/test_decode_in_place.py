"""The contiguous decode step runs on the pool's rows in place at every
occupancy (PR 33): one `_decode_slots` / `_spec_slots` program per pool
shape, inactive rows carried under the `active` mask, no slice of the pool
and no write-back.

Pinned here:
  * a half-active pool steps with every inactive row's K / V / pos / conv /
    state byte-identical and every active row's tokens equal to the same
    request decoded alone — for a `full`, an `swa`, a `linear` and a `mamba`
    family;
  * the lowered programs hold no `slice` / `dynamic_slice` /
    `dynamic_update_slice` over an operand of the pool's leaf shape;
  * `decode_slots(..., nb=k)` for every rung of the paged ladder returns
    `[2, slots]` ids from ONE executable (what benchmark/check.py calls).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve.slots import slot_buckets

GREEDY = SamplingConfig(temperature=0.0)
CTX = 64
SLOTS = 8
RECENT = 16
ACTIVE = (0, 2, 5)
STEPS = 6

FAMILIES = {
    "full": lambda: tiny_config("llama"),
    "swa": lambda: tiny_config("mistral", sliding_window=8),
    "linear": lambda: tiny_config("qwen3_5"),
    "mamba": lambda: tiny_config("jamba"),
}


def _model(kind: str) -> TextModel:
    return TextModel(FAMILIES[kind](), dtype=jnp.float32,
                     max_cache_len=CTX)


def _carries(model, slots: int, active=()):
    act = np.zeros((slots,), bool)
    act[list(active)] = True
    return {
        "toks": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
        "rngs": jnp.stack([jax.random.PRNGKey(i) for i in range(slots)]),
        "recents": jnp.full((slots, RECENT), -1, jnp.int32),
        "temps": jnp.zeros((slots,), jnp.float32),
        "top_ks": jnp.full((slots,), model.cfg.vocab_size, jnp.int32),
        "top_ps": jnp.ones((slots,), jnp.float32),
        "pens": jnp.ones((slots,), jnp.float32),
        "act": jnp.asarray(act),
    }


def _step(model, layers, st, **kw):
    (packed, layers, st["toks"], st["pos"], st["rngs"],
     st["recents"]) = model.decode_slots(
        layers, st["toks"], st["pos"], st["rngs"], st["recents"],
        st["temps"], st["top_ks"], st["top_ps"], st["pens"], st["act"],
        **kw)
    return np.asarray(packed), layers


def _row_bytes(layers, row: int) -> list[bytes]:
    return [np.asarray(a[row]).tobytes()
            for a in jax.tree_util.tree_leaves(layers)]


@pytest.mark.parametrize("kind", sorted(FAMILIES))
def test_half_active_pool_steps_in_place(kind):
    """Rows 0, 2, 5 of 8 decode; the other five hold half-built prefixes
    (a chunked admission under way). Six steps leave every byte of the
    five alone and give the three what each gives decoded alone."""
    model = _model(kind)
    rng = np.random.default_rng(33)
    vocab = model.cfg.vocab_size
    prompts = {r: rng.integers(1, vocab, 9 + 3 * r).tolist()
               for r in range(SLOTS)}
    layers = model.new_cache(SLOTS, kv_len=CTX)["layers"]
    st = _carries(model, SLOTS, ACTIVE)
    for row, ids in prompts.items():
        if row in ACTIVE:
            logits, layers = model.prefill_chunk(layers, row, ids, 0)
            st["toks"] = st["toks"].at[row].set(
                jnp.argmax(logits[0]).astype(jnp.int32))
            st["pos"] = st["pos"].at[row].set(len(ids))
        else:                       # the first chunk of a longer prompt
            _, layers = model.prefill_chunk(layers, row, ids[:5 + row], 0)
            st["pos"] = st["pos"].at[row].set(5 + row)
    idle = [r for r in range(SLOTS) if r not in ACTIVE]
    before = {r: _row_bytes(layers, r) for r in idle}
    carried = {k: np.asarray(st[k])[idle] for k in ("toks", "pos")}
    got = {r: [] for r in ACTIVE}
    for _ in range(STEPS):
        packed, layers = _step(model, layers, st)
        assert packed.shape == (2, SLOTS)
        for r in ACTIVE:
            got[r].append(int(packed[0, r]))
    for r in ACTIVE:
        got[r].append(int(np.asarray(st["toks"])[r]))
        want, _ = model.generate(prompts[r], max_new_tokens=STEPS + 1,
                                 sampling=GREEDY, spec=False)
        assert got[r] == want, f"row {r}"
    for r in idle:
        assert _row_bytes(layers, r) == before[r], f"row {r} was written"
    for k, v in carried.items():
        assert np.array_equal(np.asarray(st[k])[idle], v), k
    assert np.array_equal(np.asarray(st["pos"])[list(ACTIVE)],
                          [len(prompts[r]) + STEPS for r in ACTIVE])


# one statement of the lowered module: `%r = stablehlo.<op> %operand ...
# : (tensor<first operand's type>, ...` — the first operand is what a
# slice reads and what an update writes into
_SLICING = re.compile(
    r"stablehlo\.(slice|dynamic_slice|dynamic_update_slice)\b[^\n]*?"
    r":\s*\(?tensor<([^>]+)>")


def _lowered(model, program: str, layers) -> str:
    st = _carries(model, SLOTS, ACTIVE)
    args = (model.params, layers, st["toks"], st["pos"], st["rngs"],
            st["recents"], st["temps"], st["top_ks"], st["top_ps"],
            st["pens"], st["act"])
    if program == "_spec_slots":
        return model._spec_slots.lower(
            *args, jnp.zeros((SLOTS, 4), jnp.int32),
            jnp.zeros((SLOTS,), jnp.int32), filt=True).as_text()
    return model._decode_slots.lower(*args).as_text()


@pytest.mark.parametrize("program", ["_decode_slots", "_spec_slots"])
@pytest.mark.parametrize("kind", sorted(FAMILIES))
def test_lowered_program_never_slices_the_pool(kind, program):
    """No slice reads, and no update-slice writes, a buffer of a pool
    leaf's shape: the rows are mapped by `vmap`, never cut out."""
    model = _model(kind)
    pool = model.new_cache(SLOTS, kv_len=CTX)["layers"]
    leaf_types = {"x".join(map(str, a.shape)) + "x"
                  + {"float32": "f32", "int32": "i32"}[str(a.dtype)]
                  for a in jax.tree_util.tree_leaves(pool)}
    found = _SLICING.findall(_lowered(model, program, pool))
    assert found, "the pattern no longer matches the lowered text"
    hits = [(op, t) for op, t in found if t in leaf_types]
    assert not hits, hits


def test_every_rung_of_the_ladder_is_one_executable():
    """benchmark/check.py calls `decode_slots(..., nb=k)` with k = slots
    and then k = 1, 2, 4 ...: every call runs the one program and hands
    back ids for every row."""
    model = _model("full")
    layers = model.new_cache(SLOTS, kv_len=CTX)["layers"]
    st = _carries(model, SLOTS)             # no row decodes: load only
    for nb in slot_buckets(SLOTS)[::-1] + (None,):
        kw = {} if nb is None else {"nb": nb}
        packed, layers = _step(model, layers, st, **kw)
        assert packed.shape == (2, SLOTS)
    assert model._decode_slots._cache_size() == 1
    drafts = jnp.zeros((SLOTS, 4), jnp.int32)
    for nb in slot_buckets(SLOTS):
        (packed, layers, st["toks"], st["pos"], st["rngs"],
         st["recents"]) = model.spec_slots(
            layers, st["toks"], st["pos"], st["rngs"], st["recents"],
            st["temps"], st["top_ks"], st["top_ps"], st["pens"], st["act"],
            drafts, jnp.zeros((SLOTS,), jnp.int32), nb=nb)
        assert np.asarray(packed).shape == (3, SLOTS)
    assert model._spec_slots._cache_size() == 1
