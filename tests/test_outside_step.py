"""A run that stood still says where (ISSUE 41), part two: a tiny engine's
flight records cover the whole iteration and the gap before it, a slow
iteration's stall record names the collector and the compile that ran in
it, and with the recorder off nothing new reads the clock."""
import asyncio
import gc
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.obs import PROCESS, RECORDER
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine, engine as engine_mod, faults
from cake_tpu.serve.faults import ServeFaultInjector
from cake_tpu.serve.flight import KINDS, PHASES, STALL_FLOOR_MS
from tests.test_serve import CTX, _model, _run, _settle

GREEDY = SamplingConfig(temperature=0.0)
CHUNK = 16


@pytest.fixture(scope="module")
def model():
    return _model()


def _engine(model, **kw):
    return ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                       prefill_chunk=CHUNK, queue_deadline_s=0,
                       request_deadline_s=0, **kw)


@pytest.fixture(scope="module")
def ran(model):
    """Two requests, an idle wait, a third: the records and the engine's
    health."""
    eng = _engine(model)
    try:
        warm = eng.submit(list(range(3, 40)), max_new_tokens=4,
                          sampling=GREEDY)
        assert warm.wait(600) and "error" not in warm.result
        seq0 = _settle(eng)[-1]["seq"]
        RECORDER.clear()
        RECORDER.enable()
        reqs = [eng.submit(list(range(60 + n, 60 + 2 * n)), max_new_tokens=6,
                           sampling=GREEDY) for n in (20, 40)]
        for r in reqs:
            assert r.wait(600) and "error" not in r.result
        seq1 = _settle(eng)[-1]["seq"]
        time.sleep(0.7)                     # the idle wait's heartbeat too
        last = eng.submit([5, 6, 7, 8], max_new_tokens=4, sampling=GREEDY)
        assert last.wait(600) and "error" not in last.result
        ring = _settle(eng)
        health = eng.health()
    finally:
        RECORDER.disable()
        eng.close()
    steps = {e["args"]["step"]: e for e in RECORDER.events()
             if e["name"] == "serve.step"}
    RECORDER.clear()
    return {"recs": [r for r in ring if r["seq"] > seq0], "seq1": seq1,
            "steps": steps, "health": health, "all": ring}


def test_phases_add_up_to_the_wall_time(ran):
    for r in ran["recs"]:
        assert len(r["ph"]) == len(PHASES) and min(r["ph"]) >= 0.0
        assert sum(r["ph"]) == pytest.approx(r["wall_ms"], abs=0.01)
        assert r["host_ms"] + r["fetch_ms"] == pytest.approx(r["wall_ms"],
                                                             abs=0.002)
        assert r["stall_ms"] == 0.0 or \
            r["wall_ms"] + r["gap_ms"] > STALL_FLOOR_MS
        # the lagged landing's fetch is the record's `fetch` phase
        assert r["ph"][PHASES.index("fetch")] == pytest.approx(
            r["fetch_ms"], abs=0.01)
        assert r["ph"][PHASES.index("late_land")] < 1.0     # depth 1 here


def test_kinds_say_what_an_iteration_carried(ran):
    kinds = [r["kind"] for r in ran["recs"]]
    assert set(kinds) <= set(KINDS)
    # 20 and 40 tokens in chunks of 16: 1 + 2 mid chunks, 3 prompt ends
    assert kinds.count("chunk") == 3 and kinds.count("last_chunk") == 3
    assert kinds.count("idle") == 2         # one landing after each burst
    for r in ran["recs"]:
        if r["kind"] == "idle":
            assert r["occupancy"] == 0 and r["of_step"] is not None
        if r["kind"] == "decode":
            assert r["occupancy"] > 0
        if r["fetch_ms"] > 0:
            assert r["of_step"] < r["seq"]


def test_the_gap_is_zero_after_idle_and_positive_between_busy_steps(ran):
    recs = ran["recs"]
    first = recs[0]
    after_idle = next(r for r in recs if r["seq"] > ran["seq1"])
    assert first["gap_ms"] == 0.0 and after_idle["gap_ms"] == 0.0
    # every other one follows an iteration that left work behind (busy
    # rows, a queue or a step in flight): the `_run` loop's own time, far
    # under the idle wait's 0.5 s heartbeat
    busy = [r for r in recs if r is not first and r is not after_idle]
    assert busy and all(0.0 < r["gap_ms"] < 400.0 for r in busy)


def test_a_gap_covers_what_lies_between_two_steps_spans(ran):
    """`serve.step` wraps the stamps a record is made of: the span is no
    shorter than `wall_ms`, and `gap_ms`, from one iteration's last stamp
    to the next one's first, no shorter than the space between two spans."""
    for r in ran["recs"]:
        e, before = ran["steps"][r["seq"]], ran["steps"].get(r["seq"] - 1)
        assert e["dur"] / 1e3 >= r["wall_ms"] - 0.01
        if r["gap_ms"] > 0 and before is not None:
            assert r["gap_ms"] * 1e3 >= \
                e["ts"] - (before["ts"] + before["dur"]) - 10


def test_health_tells_runs_apart(ran):
    h = ran["health"]
    by_kind = h["steps_by_kind"]
    assert set(by_kind) == set(KINDS)
    assert sum(k["n"] for k in by_kind.values()) == ran["all"][-1]["seq"]
    assert by_kind["last_chunk"]["n"] == 4 and by_kind["last_chunk"]["ms"] > 0
    assert h["occupancy_sum"] == sum(r["occupancy"] for r in ran["all"])
    assert {"count", "total_ms", "reference_ms", "worst"} == set(h["stalls"])
    # the warm-up compiled: those iterations stood still, and say why
    assert h["stalls"]["count"] == len(h["stalls"]["worst"]) >= 1
    assert all(s["phase"] in PHASES + ("between",)
               for s in h["stalls"]["worst"])


class _SlowOnce(ServeFaultInjector):
    """On decode dispatch `at`: a collection of a large cycle, a compile of
    a function nobody has compiled, and a sleep, all on the scheduler
    thread, as a program's own stall would be."""

    at: int = 3
    seen: int = 0

    def on_decode(self, reqs):
        self.seen += 1
        if self.seen != self.at:
            return
        junk = []
        for _ in range(300_000):
            a, b = [], []
            a.append(b)
            b.append(a)
            junk.append(a)
        del junk, a, b
        gc.collect()
        jax.jit(lambda x: jnp.tanh(x * 41.0) + self.seen)(
            jnp.ones((7, 5))).block_until_ready()
        time.sleep(0.6)


def test_a_slow_iteration_names_the_collector_and_the_compile(model):
    PROCESS.install()
    eng = _engine(model)
    try:
        warm = eng.submit([3, 4, 5, 6, 7], max_new_tokens=3, sampling=GREEDY)
        assert warm.wait(600) and "error" not in warm.result
        seq0 = _settle(eng)[-1]["seq"]
        hook = faults.install(_SlowOnce())
        try:
            req = eng.submit([9, 8, 7, 6, 5], max_new_tokens=8,
                             sampling=GREEDY)
            assert req.wait(600) and "error" not in req.result
        finally:
            faults.clear()
        _settle(eng)
        assert hook.seen >= hook.at
        stalls = [s for s in eng.flight.stalls()["worst"] if s["seq"] > seq0]
    finally:
        eng.close()
    (s,) = stalls
    assert s["phase"] == "decode_dispatch" and s["kind"] == "decode"
    assert s["ph"][PHASES.index("decode_dispatch")] >= 600.0
    assert s["stall_ms"] == pytest.approx(
        s["wall_ms"] + s["gap_ms"] - STALL_FLOOR_MS, abs=0.01)
    assert s["gc_ms"] >= 1.0 and s["compiles"] >= 1 and s["compile_ms"] > 0
    assert s["gc_ms"] + s["compile_ms"] < s["wall_ms"]


class _CountingClock:
    """obs.now with a count a caller's function name."""

    def __init__(self):
        self.by = {}

    def __call__(self):
        name = sys._getframe(1).f_code.co_name
        self.by[name] = self.by.get(name, 0) + 1
        return time.perf_counter()


@pytest.mark.parametrize("recorder_on", [False, True])
def test_the_clock_reads_a_step_and_a_token_cost(model, monkeypatch,
                                                 recorder_on):
    """Recorder off: `_step` reads the clock eight times an iteration, as
    its parent did (the phase stamps; both deadline sweeps are off here),
    `_land` once a landing (it is timed from `_step`'s stamp at the call),
    and the stream's pump and iterator never.
    On: one more a chunk (where `serve.prefill_finish` begins), and the
    pump and the iterator one each a token and one each for DONE."""
    clock = _CountingClock()
    eng = _engine(model)
    got, stamps = [], []

    async def scenario():
        req = eng.submit(list(range(70, 90)), max_new_tokens=5,
                         sampling=GREEDY)
        while not req.admitted.is_set():    # as the API does: slot 0 here
            await asyncio.sleep(0.001)
        aiter, _ = eng.stream(req)
        async for tok in aiter:
            got.append(tok)
            stamps.append(aiter.handoff)

    try:
        warm = eng.submit([3, 4, 5], max_new_tokens=2, sampling=GREEDY)
        assert warm.wait(600)
        begun0 = _settle(eng)[-1]["seq"]
        landed0 = sum(1 for r in eng.flight.snapshot()
                      if r["of_step"] is not None)
        monkeypatch.setattr(engine_mod, "now", clock)
        if recorder_on:
            RECORDER.enable()
        try:
            _run(scenario())
            ring = _settle(eng)
        finally:
            RECORDER.disable()
            RECORDER.clear()
    finally:
        eng.close()
    recs = [r for r in ring if r["seq"] > begun0]
    assert len(recs) == ring[-1]["seq"] - begun0    # none returned early
    chunks = sum(1 for r in recs if r["kind"] in ("chunk", "last_chunk"))
    landed = sum(1 for r in ring if r["of_step"] is not None) - landed0
    assert chunks == 2 and len(got) == 5
    assert clock.by["_step"] == 8 * len(recs)
    assert clock.by["_land"] == landed
    assert clock.by.get("_advance_prefill", 0) == \
        (chunks if recorder_on else 0)
    assert not any(hasattr(t, "handoff") for t in got)  # the stream's
    if recorder_on:
        # (a token the engine emitted before the stream subscribed comes
        # from the backlog, past the pump: it carries no stamp)
        stamped = [h for h in stamps if h is not None]
        assert len(stamped) >= 1
        assert clock.by["pump"] == len(stamped) + 1         # and DONE
        assert clock.by["aiter"] == len(stamped)
        assert all(got >= handed for handed, got in stamped)
    else:
        assert "pump" not in clock.by and "aiter" not in clock.by
        assert stamps == [None] * len(got)


def test_every_stream_is_stamped_and_none_once_the_recorder_is_off(model):
    eng = ServeEngine(model, slots=8, max_queue=8, ctx_len=CTX,
                      prefill_chunk=CHUNK)
    got = {}

    async def one(req):
        while not req.admitted.is_set():
            await asyncio.sleep(0.001)
        aiter, _ = eng.stream(req)
        got[req.slot] = [aiter.handoff async for _ in aiter]

    async def scenario(n):
        reqs = [eng.submit([11 + i, 12, 13, 14], max_new_tokens=12,
                           sampling=GREEDY) for i in range(n)]
        await asyncio.gather(*(one(r) for r in reqs))

    RECORDER.enable()
    try:
        _run(scenario(6))
        on = dict(got)
        RECORDER.disable()
        got.clear()
        _run(scenario(2))
    finally:
        RECORDER.disable()
        RECORDER.clear()
        eng.close()
    assert sorted(on) == [0, 1, 2, 3, 4, 5]
    for stamps in on.values():
        assert len(stamps) >= 6
        assert sum(h is not None for h in stamps) >= len(stamps) - 2
    assert all(h is None for stamps in got.values() for h in stamps)
