"""A run that stood still says where (ISSUE 41), part one: the flight
recorder's stall records on an injected clock, and the process's own
witnesses (obs/process.py) they are joined with."""
import json
import logging

import pytest

from cake_tpu import obs
from cake_tpu.obs.process import ProcessWatch
from cake_tpu.serve import flight as flight_mod
from cake_tpu.serve.flight import (PHASES, STALL_FLOOR_MS, STALLS_KEPT,
                                   FlightRecorder)

NOTHING = {"gc_ms": 0.0, "compiles": 0, "compile_ms": 0.0, "compiled": [],
           "loop_lag_ms": 0.0}


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class Watch:
    """Stands in for PROCESS: says what it is told to have seen."""

    def __init__(self, **saw):
        self.saw = {**NOTHING, **saw}
        self.asked = []

    def between(self, t0, t1):
        self.asked.append((t0, t1))
        return dict(self.saw)

    def boot(self):
        return {"phases": []}


def _rec(fr, clock, wall, gap=0.0, at=None, **more):
    """One iteration of `wall` ms whose time went to phase `at`."""
    ph = [0.0] * len(PHASES)
    ph[PHASES.index(at or "fetch")] = wall
    clock.t += (wall + gap) / 1e3
    fr.record(kind="decode", wall_ms=wall, gap_ms=gap, ph=ph, occupancy=1,
              **more)


def _flight(capacity=8, **saw):
    clock, watch = Clock(), Watch(**saw)
    return FlightRecorder(capacity=capacity, clock=clock, watch=watch), \
        clock, watch


@pytest.mark.parametrize("at", PHASES + ("between",))
def test_a_stall_names_its_phase_and_outlives_the_ring(at):
    fr, clock, _ = _flight()
    if at == "between":
        _rec(fr, clock, 20.0, gap=900.0)
    else:
        _rec(fr, clock, 900.0, gap=0.2, at=at)
    t_stall = clock.t
    for _ in range(1000):                   # the ring turns 125 times
        _rec(fr, clock, 20.0, gap=0.1)
    assert all(r["stall_ms"] == 0.0 for r in fr.snapshot())
    s = fr.stalls()
    assert s["count"] == 1 and len(s["worst"]) == 1
    (got,) = s["worst"]
    assert got["phase"] == at and got["seq"] == 1
    assert got["t"] == pytest.approx(t_stall)
    assert got["stall_ms"] == pytest.approx(
        got["wall_ms"] + got["gap_ms"] - STALL_FLOOR_MS)
    assert s["total_ms"] == pytest.approx(got["wall_ms"] + got["gap_ms"])
    assert {"gc_ms", "compiles", "compile_ms", "loop_lag_ms"} <= set(got)


def test_a_record_under_the_threshold_is_no_stall():
    fr, clock, watch = _flight()
    _rec(fr, clock, STALL_FLOOR_MS - 1.0, gap=0.5)
    _rec(fr, clock, 100.0, gap=399.0)
    assert fr.stalls() == {"count": 0, "total_ms": 0.0,
                           "reference_ms": None, "worst": []}
    assert [r["stall_ms"] for r in fr.snapshot()] == [0.0, 0.0]
    assert watch.asked == []                # nobody was asked anything


def test_the_reference_is_taken_once_a_turn(monkeypatch):
    calls = []
    real = flight_mod.median

    def counting(values):
        calls.append(1)
        return real(values)

    monkeypatch.setattr(flight_mod, "median", counting)
    fr, clock, _ = _flight(capacity=8)
    for _ in range(7):
        _rec(fr, clock, 80.0)
    assert fr.stalls()["reference_ms"] is None and not calls
    _rec(fr, clock, 80.0)                   # the eighth ends the turn
    assert fr.stalls()["reference_ms"] == 80.0 and len(calls) == 1
    # ten medians is the threshold now, not the floor
    _rec(fr, clock, 700.0)
    assert fr.stalls()["count"] == 0
    _rec(fr, clock, 900.0)
    assert fr.stalls()["count"] == 1
    for _ in range(5):
        _rec(fr, clock, 30.0)
    assert len(calls) == 1                  # 15 records: still one turn
    _rec(fr, clock, 30.0)
    assert len(calls) == 2
    # the median of the turn that ended: 700, 900 and six of 30
    assert fr.stalls()["reference_ms"] == 30.0


def test_a_large_ring_still_takes_a_reference():
    fr, clock, _ = _flight(capacity=65536)
    for _ in range(flight_mod.REFERENCE_TURN):
        _rec(fr, clock, 40.0)
    assert fr.stalls()["reference_ms"] == 40.0


def test_stalls_are_bounded_and_all_are_counted():
    fr, clock, _ = _flight(capacity=1024)   # no reference within 100 records
    for i in range(100):
        _rec(fr, clock, 600.0 + i)
    s = fr.stalls()
    assert s["count"] == 100 and len(s["worst"]) == STALLS_KEPT
    assert s["total_ms"] == pytest.approx(sum(600.0 + i for i in range(100)))
    # the newest are kept, the largest come first
    assert [w["wall_ms"] for w in s["worst"]][:3] == [699.0, 698.0, 697.0]
    assert min(w["wall_ms"] for w in s["worst"]) == 636.0


def test_a_stall_is_joined_by_the_next_record_or_the_next_reader():
    fr, clock, watch = _flight(gc_ms=412.5, loop_lag_ms=3.0)
    _rec(fr, clock, 500.0, gap=20.0, at="fanout")
    t1 = clock.t
    assert watch.asked == []                # the loop's tick may yet land
    _rec(fr, clock, 20.0)
    assert watch.asked == [(pytest.approx(t1 - 0.52), pytest.approx(t1))]
    (got,) = fr.stalls()["worst"]
    assert got["gc_ms"] == 412.5 and got["loop_lag_ms"] == 3.0
    assert got["phase"] == "fanout"
    _rec(fr, clock, 700.0, at="plan")
    assert len(watch.asked) == 1
    assert fr.stalls()["worst"][0]["phase"] == "plan"   # the reader did it
    assert len(watch.asked) == 2


def test_a_cold_starts_compiles_cannot_hide_a_later_shorter_stall():
    """Every kept record is served: nine warm-up compiles of 2 s, then the
    window's one stall of 0.6 s, which a list of the 8 largest dropped."""
    fr, clock, watch = _flight(capacity=1024, compiles=3,
                               compile_ms=1900.0)
    for _ in range(9):
        _rec(fr, clock, 2000.0, at="prefill")
    _rec(fr, clock, 20.0)
    watch.saw = dict(NOTHING, loop_lag_ms=590.0)
    _rec(fr, clock, 600.0)
    worst = fr.stalls()["worst"]
    assert len(worst) == 10
    assert [w["compiles"] for w in worst] == [3] * 9 + [0]
    assert worst[-1]["wall_ms"] == 600.0 and worst[-1]["phase"] == "fetch"


@pytest.mark.parametrize("compiles, level, verb, label", [
    (0, logging.WARNING, "stood still", "no"),
    (2, logging.INFO, "compiled", "yes"),
])
def test_a_stall_that_compiled_says_so_in_the_log_and_the_label(
        caplog, compiles, level, verb, label):
    before = {v: obs.SERVE_STEP_STALLS.value(phase="plan", compiled=v)
              for v in ("yes", "no")}
    fr, clock, _ = _flight(compiles=compiles, compile_ms=300.0 * compiles)
    with caplog.at_level(logging.INFO, logger="cake_tpu.serve.flight"):
        _rec(fr, clock, 900.0, at="plan")
        fr.stalls()
    (said,) = [r for r in caplog.records if "scheduler iteration" in
               r.getMessage()]
    assert said.levelno == level and f" 1 {verb}: 900 ms" in said.getMessage()
    for v in ("yes", "no"):
        assert obs.SERVE_STEP_STALLS.value(phase="plan", compiled=v) == \
            before[v] + (v == label)


def test_one_warning_a_stall_and_at_most_one_a_second(caplog):
    fr, clock, _ = _flight(gc_ms=350.0)
    with caplog.at_level(logging.WARNING, logger="cake_tpu.serve.flight"):
        for _ in range(4):                  # ending at 0.6, 1.2, 1.8, 2.4 s
            _rec(fr, clock, 600.0)
        clock.t += 1.0
        _rec(fr, clock, 600.0)              # at 4.0 s
        fr.stalls()
    said = [r.getMessage() for r in caplog.records
            if "stood still" in r.getMessage()]
    assert fr.stalls()["count"] == 5
    # the first, the one 1.2 s after it, the one 1.6 s after that
    assert [m.split()[2] for m in said] == ["1", "3", "5"]
    assert "mostly in fetch" in said[0] and "gc 350 ms" in said[0]


def test_stalls_count_in_the_registry_by_phase():
    before = obs.SERVE_STEP_STALLS.value(phase="admit", compiled="no")
    secs = obs.SERVE_STEP_STALL_SECONDS.value()
    fr, clock, _ = _flight()
    _rec(fr, clock, 800.0, gap=200.0, at="admit")
    fr.stalls()
    assert obs.SERVE_STEP_STALLS.value(phase="admit", compiled="no") == \
        before + 1
    assert obs.SERVE_STEP_STALL_SECONDS.value() == pytest.approx(secs + 1.0)


def test_totals_by_kind_and_records_without_the_fields():
    fr, clock, _ = _flight()
    fr.record(iteration=1, occupancy=3)     # a record of another shape
    assert "stall_ms" not in fr.snapshot()[0]
    _rec(fr, clock, 10.0)
    fr.record(kind="last_chunk", wall_ms=60.0, gap_ms=0.1, occupancy=2)
    fr.record(kind="idle", wall_ms=0.1, gap_ms=0.0, occupancy=0)
    t = fr.totals()
    assert t["occupancy_sum"] == 3
    assert t["steps_by_kind"]["decode"] == {"n": 1, "ms": 10.0}
    assert t["steps_by_kind"]["last_chunk"] == {"n": 1, "ms": 60.0}
    assert t["steps_by_kind"]["chunk"] == {"n": 0, "ms": 0.0}
    assert t["steps_by_kind"]["idle"]["n"] == 1


def test_the_dump_carries_the_stalls(tmp_path, monkeypatch):
    monkeypatch.setenv("CAKE_TRACE_DIR", str(tmp_path))
    fr, clock, _ = _flight()
    _rec(fr, clock, 900.0, at="decode_dispatch")
    path = fr.dump("wedge")
    with open(path) as f:
        body = json.load(f)
    assert body["stalls"]["count"] == 1
    assert body["stalls"]["worst"][0]["phase"] == "decode_dispatch"
    assert len(body["iterations"]) == 1
    assert body["static"]["boot"] == {"phases": []}     # the watch's, read now


# -- the process's own witnesses ---------------------------------------------

def _watch():
    reg = obs.MetricsRegistry()
    return ProcessWatch(reg.counter("c", labelnames=("cache",)),
                        reg.counter("cs", labelnames=("cache",)),
                        reg.counter("bs", labelnames=("stage",)),
                        reg.histogram("g"), reg.histogram("l")), reg


def test_the_collector_hook_keeps_pauses_of_a_millisecond(monkeypatch):
    w, reg = _watch()
    clock = Clock(5.0)
    monkeypatch.setattr("cake_tpu.obs.process.now", clock)
    for pause, gen in ((0.0004, 0), (0.0300, 2), (0.0009, 1), (0.0020, 0)):
        w._on_gc("start", {"generation": gen})
        clock.t += pause
        w._on_gc("stop", {"generation": gen})
        clock.t += 1.0
    kept = list(w._pauses)
    assert [(round(ms, 3), g) for _, ms, g in kept] == [(30.0, 2), (2.0, 0)]
    assert reg.histogram("g").count() == 2
    # overlap, not containment: the long pause began before the stretch
    t0 = kept[0][0]
    assert w.between(t0 + 0.01, t0 + 0.5)["gc_ms"] == 30.0
    assert w.between(t0 + 0.04, t0 + 0.5)["gc_ms"] == 0.0
    assert w.between(0.0, 100.0)["gc_ms"] == 32.0


def test_the_compile_listener_counts_backend_compiles(monkeypatch):
    w, reg = _watch()
    clock = Clock(50.0)
    monkeypatch.setattr("cake_tpu.obs.process.now", clock)
    w._on_build("/jax/core/compile/jaxpr_trace_duration", 9.0)
    w._on_build("/jax/core/compile/backend_compile_duration", 0.25)
    clock.t = 60.0
    w._on_build("/jax/core/compile/backend_compile_duration", 1.5,
                fun_name="jit(f)")
    # no cache event came before either: the persistent cache was not asked
    assert w._m_compiles.value(cache="off") == 2
    assert w._m_compile_s.value(cache="off") == pytest.approx(1.75)
    assert w._m_build_s.value(stage="trace") == 0   # nothing lowered it
    assert w.between(49.0, 51.0) == {**NOTHING, "compiles": 1,
                                     "compile_ms": 250.0, "compiled": [""]}
    assert w.between(49.0, 61.0)["compile_ms"] == 1750.0
    assert w.between(49.0, 61.0)["compiled"] == ["", "f"]


def test_the_loop_lag_ring_and_its_largest_overlapping_sample(monkeypatch):
    w, reg = _watch()
    clock = Clock(100.0)
    monkeypatch.setattr("cake_tpu.obs.process.now", clock)
    assert w.loop_lag() is None
    w.note_loop_lag(10.00, 0.001)
    w.note_loop_lag(99.00, 0.800)           # due 99.0, ran 99.8
    w.note_loop_lag(99.85, 0.002)
    assert w.loop_lag() == {"last": 2.0, "max_60s": 800.0}
    assert reg.histogram("l").count() == 3
    # a tick due inside the stretch that ran after it still bears witness
    assert w.between(98.5, 99.2)["loop_lag_ms"] == 800.0
    assert w.between(99.81, 99.9)["loop_lag_ms"] == 2.0
    assert w.between(20.0, 30.0)["loop_lag_ms"] == 0.0
    clock.t = 200.0                         # the minute has passed
    assert w.loop_lag() == {"last": 2.0, "max_60s": 2.0}
