"""A prompt's end is one program (ISSUE 50): `TextModel._slot_join` derives
the request's key, samples the first token and writes every per-slot carry
in one dispatch. Pinned here: it writes what the sequence of small programs
it replaced wrote (kept below as the plain reference), id for id and bit
for bit; an engine's streams are those of an engine that still runs that
sequence; one executable serves every slot and sampling config; and
`_complete_prefill` reaches the device through that one call and two small
host arrays, in the contiguous, the paged and the speculative engine."""
import contextlib
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.obs import SERVE_PREFILL_CHUNKS, SERVE_SLOT_JOINS, TIMELINES
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine
from cake_tpu.serve.engine import RECENT_N, _traced_sampling
from tests.test_serve import _settle

CTX, CHUNK, SLOTS = 128, 16, 4
BT, BLOCKS = 8, 24
CARRIES = ("_toks", "_pos", "_rngs", "_recents", "_temps", "_top_ks",
           "_top_ps", "_pens", "_act")
CONFIGS = {
    "greedy": SamplingConfig(temperature=0.0),
    "temperature": SamplingConfig(temperature=0.8),
    "top_k": SamplingConfig(temperature=0.9, top_k=5),
    "top_p": SamplingConfig(temperature=0.7, top_p=0.9),
    "penalty": SamplingConfig(temperature=0.6, repeat_penalty=1.3),
    "together": SamplingConfig(temperature=1.1, top_k=12, top_p=0.8,
                               repeat_penalty=1.2),
    "greedy_penalty": SamplingConfig(temperature=0.0, repeat_penalty=1.5),
}


@pytest.fixture(scope="module")
def model():
    return TextModel(tiny_config(), dtype=jnp.float32, seed=0,
                     max_cache_len=CTX)


# -- the plain reference: the parent's sequence of small programs ------------

def parent_join(model, base_rng, seq, logits, c: dict, slot: int, n: int,
                scfg: SamplingConfig, vocab: int) -> dict:
    """What `ServeEngine._complete_prefill` + `_set_slot_sampling` issued
    at a prompt's end before ISSUE 50, one program or transfer a line."""
    rng = jax.random.fold_in(base_rng, seq)
    rng, sk = jax.random.split(rng)
    recent = jnp.full((RECENT_N,), -1, jnp.int32)
    tid = model.sample_one(
        logits[0], sk, jnp.float32(scfg.temperature),
        jnp.int32(scfg.top_k or vocab),
        jnp.float32(scfg.top_p if scfg.top_p is not None else 1.0),
        jnp.float32(scfg.repeat_penalty), recent)
    return {
        "_rngs": c["_rngs"].at[slot].set(rng),
        "_recents": c["_recents"].at[slot].set(recent.at[-1].set(tid)),
        "_toks": c["_toks"].at[slot].set(tid),
        "_pos": c["_pos"].at[slot].set(n),
        "_temps": c["_temps"].at[slot].set(scfg.temperature),
        "_top_ks": c["_top_ks"].at[slot].set(scfg.top_k or vocab),
        "_top_ps": c["_top_ps"].at[slot].set(
            scfg.top_p if scfg.top_p is not None else 1.0),
        "_pens": c["_pens"].at[slot].set(scfg.repeat_penalty),
        "_act": c["_act"].at[slot].set(True),
    }


class ParentEngine(ServeEngine):
    """An engine whose prompts end the parent's way."""

    def _complete_prefill(self, pf, logits):
        req, slot = pf.req, pf.slot
        seq, self._seq = self._seq, self._seq + 1
        new = parent_join(self.model, self._base_rng, seq, logits,
                          {k: getattr(self, k) for k in CARRIES}, slot,
                          pf.n, req.sampling, self._vocab)
        for k, v in new.items():
            setattr(self, k, v)
        self._prefills.remove(pf)
        req.budget = min(req.max_new_tokens - 1, self.ctx - pf.n - 1)
        req._first_pending = True
        req.stats["prefill_chunks"] = pf.chunks
        req.stats["prefix_hit_tokens"] = pf.hit_tokens
        SERVE_PREFILL_CHUNKS.observe(max(pf.chunks, 1))
        TIMELINES.event(req.id, "prefill_done", chunks=pf.chunks,
                        hit_tokens=pf.hit_tokens)


def _carries(vocab: int, seed: int) -> dict:
    """A pool's carries mid-run: every row holds something to keep."""
    r = np.random.default_rng(seed)
    return {
        "_toks": jnp.asarray(r.integers(0, vocab, SLOTS), jnp.int32),
        "_pos": jnp.asarray(r.integers(1, CTX, SLOTS), jnp.int32),
        "_rngs": jnp.stack([jax.random.PRNGKey(int(s))
                            for s in r.integers(0, 1 << 30, SLOTS)]),
        "_recents": jnp.asarray(
            r.integers(-1, vocab, (SLOTS, RECENT_N)), jnp.int32),
        "_temps": jnp.asarray(r.uniform(0, 1.5, SLOTS), jnp.float32),
        "_top_ks": jnp.asarray(r.integers(1, vocab, SLOTS), jnp.int32),
        "_top_ps": jnp.asarray(r.uniform(0.5, 1, SLOTS), jnp.float32),
        "_pens": jnp.asarray(r.uniform(1, 1.5, SLOTS), jnp.float32),
        "_act": jnp.asarray(r.integers(0, 2, SLOTS), jnp.bool_),
    }


def _join(model, base_rng, seq, logits, c, slot, n, scfg, vocab) -> dict:
    out = model.slot_join(
        logits, base_rng, *(c[k] for k in CARRIES), slot=slot, seq=seq, n=n,
        **_traced_sampling(scfg, vocab))
    return dict(zip(CARRIES, out))


# -- (a) the carries and the first token, bit for bit ------------------------

@pytest.mark.parametrize("name", list(CONFIGS))
def test_join_writes_what_the_parents_sequence_wrote(model, name):
    scfg, vocab = CONFIGS[name], model.cfg.vocab_size
    base = jax.random.PRNGKey(11)
    firsts = set()
    for k, (slot, seq, n) in enumerate(
            [(0, 0, 5), (3, 1, 37), (1, 7, 100), (2, 1234, 64),
             (3, 2 ** 20 + 3, 9)]):
        logits = 4.0 * jax.random.normal(jax.random.PRNGKey(100 + k),
                                         (1, vocab), jnp.float32)
        want = parent_join(model, base, seq, logits, _carries(vocab, k),
                           slot, n, scfg, vocab)
        got = _join(model, base, seq, logits, _carries(vocab, k), slot, n,
                    scfg, vocab)      # its own copy: the join donates
        for key in CARRIES:
            a, b = np.asarray(got[key]), np.asarray(want[key])
            assert a.dtype == b.dtype and a.shape == b.shape, key
            assert a.tobytes() == b.tobytes(), (name, key, slot, seq)
        tid = int(got["_toks"][slot])
        firsts.add(tid)
        assert int(got["_recents"][slot, -1]) == tid
        assert bool(got["_act"][slot]) and int(got["_pos"][slot]) == n
        if scfg.temperature == 0.0:
            assert tid == int(jnp.argmax(logits[0]))
    assert len(firsts) > 1      # the logits, not a constant, chose them


# -- (b) an engine's streams are the parent engine's -------------------------

JOBS = [([3, 17, 42, 99, 7], "temperature", 12),
        (list(range(20, 57)), "together", 10),
        ([5] * 18, "greedy", 9),
        (list(range(60, 80)), "top_p", 12),
        ([9, 8, 7], "penalty", 11),
        (list(range(100, 117)), "top_k", 8)]


def _served(eng, jobs=JOBS) -> list:
    """The jobs' finished requests; each admitted once the one before holds
    its first token, so the admission order (the key derivation's `seq`)
    is the list's."""
    reqs = []
    for ids, cfg, n in jobs:
        req = eng.submit(list(ids), max_new_tokens=n, sampling=CONFIGS[cfg])
        reqs.append(req)
        deadline = time.monotonic() + 600
        while not req.tokens and not req.done.is_set():
            assert time.monotonic() < deadline
            time.sleep(0.002)
    for r in reqs:
        assert r.wait(600) and "error" not in r.result, r.result
    if eng.spec_drafter is None:    # a drafter's engine holds no step
        _settle(eng)
    return reqs


def _streams(eng, jobs=JOBS) -> list[list[int]]:
    return [list(r.tokens) for r in _served(eng, jobs)]


def _prefill_done_events(reqs) -> int:
    return sum(e["kind"] == "prefill_done"
               for r in reqs for e in TIMELINES.get(r.id)["events"])


@contextlib.contextmanager
def _closing(eng):
    try:
        yield eng
    finally:
        eng.close()


def test_engine_streams_equal_the_parent_engines(model):
    kw = dict(slots=SLOTS, max_queue=8, ctx_len=CTX, prefill_chunk=CHUNK,
              seed=5)
    with _closing(ParentEngine(model, **kw)) as eng:
        want = _streams(eng)
    joins0 = SERVE_SLOT_JOINS.value()
    with _closing(ServeEngine(model, **kw)) as eng:
        reqs = _served(eng)
        recs = eng.flight.snapshot()
    got = [list(r.tokens) for r in reqs]
    assert got == want
    assert len({tuple(s) for s in got}) == len(JOBS)
    # the counter and the flight records say what was dispatched
    assert SERVE_SLOT_JOINS.value() - joins0 == len(JOBS) \
        == _prefill_done_events(reqs)
    assert sum(r["joined"] for r in recs) == len(JOBS)
    assert all(r["joined"] == (r["kind"] == "last_chunk") for r in recs)


# -- (c) one executable ------------------------------------------------------

def test_one_executable_whatever_the_slot_and_the_sampling():
    model = TextModel(tiny_config(), dtype=jnp.float32, seed=0,
                      max_cache_len=CTX)
    assert model._slot_join._cache_size() == 0
    with _closing(ServeEngine(model, slots=SLOTS, max_queue=8, ctx_len=CTX,
                              prefill_chunk=CHUNK)) as eng:
        streams = _streams(eng)
        assert len(streams) == len(JOBS)
        # the engine's own carries, between and behind decode steps
        assert model._slot_join._cache_size() == 1
    vocab = model.cfg.vocab_size
    for k, name in enumerate(CONFIGS):
        _join(model, jax.random.PRNGKey(k), k, jnp.zeros((1, vocab)),
              _carries(vocab, k), k % SLOTS, 3 + k, CONFIGS[name], vocab)
    assert model._slot_join._cache_size() == 1


# -- (d) a prompt's end: the chunk's dispatch, one program, two arrays -------

class _Counted:
    """A jitted program of the model, counted while a flag is up."""

    def __init__(self, fn, name, log, inside):
        self._fn, self._name, self._log, self._inside = fn, name, log, inside

    def __call__(self, *a, **kw):
        if self._inside:
            self._log.append(self._name)
        return self._fn(*a, **kw)

    def __getattr__(self, item):
        return getattr(self._fn, item)


@contextlib.contextmanager
def _watched(eng, monkeypatch):
    """Run `eng._complete_prefill` with implicit host-to-device transfers
    refused (a Python scalar shipped by `.at[].set`, `jnp.float32(...)`,
    `jnp.full`, an index: what the parent's ~25 programs each carried),
    the explicit ones counted, and the model's programs counted."""
    log = {"programs": [], "puts": [], "ends": 0}
    inside = []
    model = eng.model
    for name, fn in list(vars(model).items()):
        if hasattr(fn, "_cache_size"):
            monkeypatch.setattr(model, name, _Counted(
                fn, name, log["programs"], inside))
    put = jax.device_put

    def counted_put(x, *a, **kw):
        if inside:
            log["puts"].append((np.asarray(x).dtype.name, np.shape(x)))
        return put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counted_put)
    orig = eng._complete_prefill

    def guarded(pf, logits):
        inside.append(1)
        try:
            with jax.transfer_guard("disallow"):
                orig(pf, logits)
            log["ends"] += 1
        finally:
            inside.pop()

    eng._complete_prefill = guarded
    yield log


def _assert_one_program_two_arrays(log, ends: int):
    assert log["ends"] == ends
    assert log["programs"] == ["_slot_join"] * ends
    assert sorted(set(log["puts"])) == [("float32", (3,)), ("int32", (4,))]
    assert len(log["puts"]) == 2 * ends


def test_a_prompts_end_is_one_program_and_two_host_arrays(model,
                                                          monkeypatch):
    with _closing(ServeEngine(model, slots=SLOTS, max_queue=8, ctx_len=CTX,
                              prefill_chunk=CHUNK)) as eng, \
            _watched(eng, monkeypatch) as log:
        _streams(eng)
    _assert_one_program_two_arrays(log, len(JOBS))


def test_complete_prefill_touches_the_carries_through_the_join_alone():
    """With carries and logits that are bare objects and a model that only
    records, any operation of the method's own on them would raise: the
    method hands them to `slot_join` once and keeps what comes back."""
    calls = []
    old = {k: object() for k in CARRIES}
    new = {k: object() for k in CARRIES}
    logits, base = object(), object()

    def slot_join(lg, rng, *carries, **scalars):
        calls.append((lg, rng, carries, scalars))
        return tuple(new[k] for k in CARRIES)

    for scfg, top_k, top_p in (
            (SamplingConfig(temperature=0.0), 77, 1.0),
            (SamplingConfig(temperature=0.5, top_k=0, top_p=0.25), 77, 0.25),
            (CONFIGS["together"], 12, 0.8)):
        req = SimpleNamespace(id="r", sampling=scfg, max_new_tokens=9,
                              stats={})
        pf = SimpleNamespace(req=req, slot=2, n=40, chunks=3, hit_tokens=16)
        eng = SimpleNamespace(model=SimpleNamespace(slot_join=slot_join),
                              _base_rng=base, _vocab=77, _seq=6, _joined=0,
                              _prefills=[pf], ctx=CTX, **old)
        calls.clear()
        ServeEngine._complete_prefill(eng, pf, logits)
        ((lg, rng, carries, scalars),) = calls
        assert lg is logits and rng is base
        assert carries == tuple(old[k] for k in CARRIES)
        assert scalars == dict(slot=2, seq=6, n=40, temp=scfg.temperature,
                               top_k=top_k, top_p=top_p,
                               penalty=scfg.repeat_penalty)
        assert all(getattr(eng, k) is new[k] for k in CARRIES)
        assert (eng._seq, eng._joined, eng._prefills) == (7, 1, [])
        assert req.budget == 8 and req._first_pending


# -- (e) the paged and the speculative engine end a prompt the same way ------

@pytest.mark.parametrize("kind, kw", [
    ("paged", dict(kv_blocks=BLOCKS, kv_block_tokens=BT)),
    ("speculative", dict(spec="ngram", spec_k=2)),
    ("speculative_paged", dict(spec="ngram", spec_k=2, kv_blocks=BLOCKS,
                               kv_block_tokens=BT)),
])
def test_other_engines_end_a_prompt_through_the_same_program(
        model, monkeypatch, kind, kw):
    jobs = [(ids, "greedy" if "spec" in kind else cfg, n)
            for ids, cfg, n in JOBS[:4]]
    base = dict(slots=2, max_queue=8, ctx_len=CTX, prefill_chunk=CHUNK,
                prefix_cache_mb=0, seed=5)
    with _closing(ParentEngine(model, **base, **kw)) as eng:
        want = _streams(eng, jobs)
    joins0 = SERVE_SLOT_JOINS.value()
    with _closing(ServeEngine(model, **base, **kw)) as eng, \
            _watched(eng, monkeypatch) as log:
        assert (eng.paged is not None) == ("paged" in kind)
        assert (eng.spec_drafter is not None) == ("spec" in kind)
        got = _streams(eng, jobs)
        recs = eng.flight.snapshot()
    assert got == want
    _assert_one_program_two_arrays(log, len(jobs))
    assert SERVE_SLOT_JOINS.value() - joins0 == len(jobs)
    assert sum(r["joined"] for r in recs) == len(jobs)
