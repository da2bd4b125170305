"""`setup_s` has layers beneath it (ISSUE 60): the process records every
program's trace, lowering and compile-or-cache-load by name and its own
boot phases; health, the flight dump, /metrics, the span recorder and a
request's timeline read them; five per-layer readers move `setup_s`."""
import json
import os
import sys
import threading
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from cake_tpu import obs
from cake_tpu.models.common.text_model import TextModel
from cake_tpu.models.common.config import tiny_config
from cake_tpu.obs import PROCESS, RECORDER, TIMELINES
from cake_tpu.obs.process import ProcessWatch, process_age_s
from cake_tpu.obs.spans import SpanRecorder
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine, maybe_engine
from cake_tpu.serve.flight import FlightRecorder
from cake_tpu.utils import compile_cache
from tests.test_serve import CTX, _settle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
GREEDY = SamplingConfig(temperature=0.0)
CHUNK = 64
TRACE, LOWER, BACKEND = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")
NEW = ("process.trace_lower_s", "process.cache_load_s", "process.compile_s",
       "programs.build_s", "engine.build_s")


def _builds_of(program):
    return [b for b in list(PROCESS._builds) if b["program"] == program]


# -- the builds, from JAX's own events ---------------------------------------

def test_three_stages_by_name_and_nested_traces_dropped():
    PROCESS.install()

    @jax.jit
    def boot_account_inner(x):
        return jnp.tanh(x) * 2.0

    def boot_account_outer(x):
        return boot_account_inner(x).sum() + jnp.matmul(x, x.T).sum()

    n0 = PROCESS.backend_count
    jax.jit(boot_account_outer)(jnp.ones((5, 5))).block_until_ready()
    recs = _builds_of("boot_account_outer")
    assert [r["stage"] for r in recs] == ["trace", "lower", "backend"]
    assert all(r["seconds"] > 0 for r in recs)
    assert recs[0]["t_end"] <= recs[1]["t_end"] <= recs[2]["t_end"]
    assert "cache" not in recs[0] and "cache" not in recs[1]
    assert recs[2]["cache"] in ("off", "miss", "hit")
    # traced inside the outer program, within its trace's time: dropped
    assert _builds_of("boot_account_inner") == []
    assert not [b for b in list(PROCESS._builds)
                if b["program"] in ("tanh", "matmul") and b["n"] > recs[0]["n"]
                and b["stage"] == "trace" and b["n"] < recs[2]["n"]]
    mine = PROCESS.built_since(n0)
    assert [r["program"] for r in mine][-1] == "boot_account_outer"
    assert PROCESS.built_since(PROCESS.backend_count) == []
    # and from another thread's point of view nothing of this one's
    seen = []
    t = threading.Thread(target=lambda: seen.extend(PROCESS.built_since(n0)))
    t.start()
    t.join(10)
    assert seen == []


def test_miss_then_hit_against_a_temporary_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    PROCESS.install()
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def make():
        # the same code under a new function object: a new trace, lowering
        # and backend stage, the same HLO
        def boot_account_cached(x):
            return jnp.cos(x * 3.0).sum()
        return jax.jit(boot_account_cached)

    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        hits0 = PROCESS._m_compiles.value(cache="hit")
        make()(jnp.ones((6,))).block_until_ready()
        make()(jnp.ones((6,))).block_until_ready()
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    backend = [r for r in _builds_of("boot_account_cached")
               if r["stage"] == "backend"]
    assert [r["cache"] for r in backend] == ["miss", "hit"]
    assert PROCESS._m_compiles.value(cache="hit") == hits0 + 1
    boot = PROCESS.boot()
    assert boot["hits"] >= 1 and boot["misses"] >= 1
    assert boot["cache_load_s"] >= backend[1]["seconds"] - 1e-4
    assert boot["compile_s"] >= backend[0]["seconds"] - 1e-4


def _watch(recorder=None):
    reg = obs.MetricsRegistry()
    return ProcessWatch(reg.counter("c", labelnames=("cache",)),
                        reg.counter("cs", labelnames=("cache",)),
                        reg.counter("bs", labelnames=("stage",)),
                        reg.histogram("g"), reg.histogram("l"),
                        recorder=recorder)


NESTED = 2.0 ** -6          # every stamp below is exact in binary


class Clock:
    def __init__(self, t):
        self.t = t

    def __call__(self):
        return self.t


def _build(w, clock, name, trace=0.5, lower=0.25, backend=1.0, cache=None,
           nested=()):
    """One program's events as JAX fires them, each at its end."""
    for inner in nested:
        clock.t += NESTED
        w._on_build(TRACE, NESTED, fun_name=inner)
    clock.t += trace
    w._on_build(TRACE, trace + NESTED * len(nested), fun_name=name)
    clock.t += lower
    w._on_build(LOWER, lower, fun_name=f"jit({name})")
    if cache is not None:
        w._on_cache(f"/jax/compilation_cache/cache_{cache}")
    clock.t += backend
    w._on_build(BACKEND, backend, fun_name=f"jit({name})")


def test_install_registers_its_listeners_once(monkeypatch):
    import gc
    calls = []
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        lambda f: calls.append(("duration", f)))
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda f: calls.append(("event", f)))
    w = _watch()
    try:
        for _ in range(3):
            w.install()
        assert [k for k, _ in calls] == ["duration", "event"]
        assert gc.callbacks.count(w._on_gc) == 1
    finally:
        gc.callbacks.remove(w._on_gc)
    age = process_age_s()
    assert w.age_at_install_s is not None and 0 <= w.age_at_install_s <= age


@pytest.fixture(scope="module")
def model():
    return TextModel(tiny_config("llama"), dtype=jnp.float32,
                     max_cache_len=CTX)


@pytest.mark.parametrize("via", ["enable_compile_cache", "TextModel",
                                 "maybe_engine"])
def test_install_is_reached_from(monkeypatch, model, via):
    installs = []
    monkeypatch.setattr(PROCESS, "install", lambda: installs.append(via))
    if via == "enable_compile_cache":
        # (the test's process keeps its own cache settings)
        monkeypatch.setattr(jax.config, "update", lambda k, v: None)
        compile_cache.enable_compile_cache()
    elif via == "TextModel":
        TextModel(model.cfg, model.params, dtype=jnp.float32,
                  max_cache_len=CTX)
    else:
        monkeypatch.setenv("CAKE_SERVE_SLOTS", "2")
        eng = maybe_engine(model)
        try:
            assert eng is not None
        finally:
            eng.close()
    assert installs == [via]


# -- the account: phases, programs, totals -----------------------------------

def test_the_account_adds_up_by_stage_and_cache(monkeypatch):
    w, clock = _watch(), Clock(100.0)
    monkeypatch.setattr("cake_tpu.obs.process.now", clock)
    with w.phase("boot.model"):
        with w.phase("boot.rope"):
            clock.t += 2.0
        _build(w, clock, "_prefill_slot", cache="hits",
               nested=("matmul", "tanh"))
    _build(w, clock, "_prefill_slot", cache="misses")
    _build(w, clock, "add", trace=0.125, lower=0.0625, backend=0.25)
    boot = w.boot()
    assert boot["age_at_install_s"] is None         # never installed
    assert [(p["name"], p["dur_s"]) for p in boot["phases"]] == [
        ("boot.rope", 2.0), ("boot.model", 3.781)]
    assert boot["programs"][0] == {
        "program": "_prefill_slot", "builds": 2, "trace_s": 1.0312,
        "lower_s": 0.5, "backend_s": 2.0, "hits": 1, "misses": 1}
    assert (boot["builds"], boot["hits"], boot["misses"]) == (3, 1, 1)
    assert boot["trace_s"] == 1.1562         # the nested two are inside it
    assert boot["lower_s"] == 0.5625
    assert boot["cache_load_s"] == 1.0
    assert boot["compile_s"] == 1.25        # the miss, and the one `off`
    assert w._m_compiles.value(cache="hit") == 1
    assert w._m_compiles.value(cache="miss") == 1
    assert w._m_compiles.value(cache="off") == 1
    assert w._m_build_s.value(stage="trace") == 1.15625
    assert w._m_build_s.value(stage="lower") == 0.5625
    recs = list(w._builds)
    assert [r.get("phase") for r in recs] == ["boot.model"] * 3 + [None] * 6
    assert {r["program"] for r in recs} == {"_prefill_slot", "add"}
    saw = w.between(100.0, 104.0)
    assert (saw["compiles"], saw["compiled"]) == (1, ["_prefill_slot"])
    assert saw["compile_ms"] == 1000.0


def test_a_phase_belongs_to_its_thread(monkeypatch):
    w, clock = _watch(), Clock(10.0)
    monkeypatch.setattr("cake_tpu.obs.process.now", clock)
    with w.phase("boot.engine"):
        t = threading.Thread(target=_build, args=(w, clock, "_decode_slots"))
        t.start()
        t.join(10)
        _build(w, clock, "_slot_join")
    by = {r["program"]: r.get("phase") for r in w._builds}
    assert by == {"_decode_slots": None, "_slot_join": "boot.engine"}


# -- the recorder's copy -----------------------------------------------------

def test_the_recorder_is_handed_the_past_once_and_keeps_it(monkeypatch):
    rec = SpanRecorder(max_events=8, enabled=False)
    w, clock = _watch(rec), Clock(50.0)
    rec.source = w.hand_over
    monkeypatch.setattr("cake_tpu.obs.process.now", clock)
    with w.phase("boot.model"):
        with w.phase("boot.rope"):
            clock.t += 1.0
        _build(w, clock, "_prefill_slot", cache="hits", nested=("matmul",))
    assert rec.events() == []               # off: nothing is handed over
    rec.enable()
    first = rec.events()
    rec.enable()                            # switched on twice: once each
    assert rec.events() == first
    names = [e["name"] for e in first]
    assert names == ["boot.rope"] + ["process.compile"] * 3 + ["boot.model"]
    by = {e["name"]: e for e in first}
    model_id = by["boot.model"]["args"]["id"]
    assert by["boot.rope"]["args"]["parent"] == model_id
    assert by["boot.rope"]["cat"] == by["boot.model"]["cat"] == "boot"
    # past stamps, on the recorder's clock (microseconds)
    assert by["boot.model"]["ts"] == 50_000_000
    assert by["boot.model"]["dur"] == 2_765_625
    stages = [e for e in first if e["name"] == "process.compile"]
    assert [(e["args"]["stage"], e["ts"], e["dur"]) for e in stages] == [
        ("trace", 51_000_000, 515_625), ("lower", 51_515_625, 250_000),
        ("backend", 51_765_625, 1_000_000)]
    assert all(e["args"]["program"] == "_prefill_slot"
               and e["args"]["phase"] == "boot.model"
               and e["args"]["parent"] == model_id for e in stages)
    assert stages[2]["args"]["cache"] == "hit"
    assert "cache" not in stages[0]["args"]
    # from then on each record as it happens
    _build(w, clock, "_slot_join", cache="misses")
    assert [e["args"]["program"] for e in rec.events()
            if e["name"] == "process.compile"][-3:] == ["_slot_join"] * 3
    # the ring turns over and is cleared; the start-up stays, and first
    for i in range(20):
        rec.add("api.sse_write", 60_000_000 + i, 5, cat="api")
    rec.clear()
    rec.add("serve.step", 70_000_000, 9, cat="serve")
    after = rec.events()
    assert [e["name"] for e in after[:5]] == names
    assert after[-1]["name"] == "serve.step" and len(after) == 5 + 3 + 1
    assert w.boot()["handed"]["spans"] == 8
    exported = rec.to_chrome_trace()["traceEvents"]
    assert exported[0]["name"] == "boot.rope"


def test_the_span_catalog_names_the_start_up():
    named = {name for name, _ in obs.SPAN_CATALOG}
    assert {"process.compile", "boot.model", "boot.rope", "boot.engine",
            "boot.engine.pool", "serve.capture_blocks", "prefix.insert",
            "prefix.extract", "prefix.evict"} <= named
    assert "compile" in obs.EVENT_KINDS
    text = obs.REGISTRY.render()
    for name in ("cake_compiles_total", "cake_compile_seconds_total",
                 "cake_program_build_seconds_total",
                 "cake_serve_inband_compiles_total"):
        assert f"# TYPE {name} counter" in text


# -- a stall names its programs ----------------------------------------------

def test_a_stall_record_names_the_programs_it_compiled(monkeypatch):
    w, clock = _watch(), Clock(1000.0)
    monkeypatch.setattr("cake_tpu.obs.process.now", clock)
    fr = FlightRecorder(capacity=8, clock=clock, watch=w)
    _build(w, clock, "_prefill_slot", trace=0.125, lower=0.125, backend=0.5)
    clock.t += 0.125        # the iteration ends after its compile does
    fr.record(kind="chunk", wall_ms=905.0, gap_ms=0.1, occupancy=1,
              ph=[0, 0, 0, 0, 0, 0, 905.0, 0])
    (s,) = fr.stalls()["worst"]
    assert s["phase"] == "prefill"
    assert (s["compiles"], s["compile_ms"]) == (1, 500.0)
    assert s["compiled"] == ["_prefill_slot"]
    assert fr.static_view()["boot"]["builds"] == 1


# -- the engine's hooks ------------------------------------------------------

def _engine(model, chunk=CHUNK, **kw):
    return ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX,
                       prefill_chunk=chunk, queue_deadline_s=0,
                       request_deadline_s=0, **kw)


def _run(eng, rid, ids, n=3):
    req = eng.submit(list(ids), max_new_tokens=n, sampling=GREEDY,
                     request_id=rid)
    assert req.wait(600) and "error" not in req.result
    return [e for e in TIMELINES.get(rid)["events"] if e["kind"] == "compile"]


def test_boot_is_in_health_and_in_the_flight_static(model, tmp_path,
                                                    monkeypatch):
    eng = _engine(model)
    try:
        _run(eng, "boot-h1", range(3, 23))
        _settle(eng)
        boot = eng.health()["boot"]
        static = eng.flight.static_view()
        monkeypatch.setenv("CAKE_TRACE_DIR", str(tmp_path))
        with open(eng.flight.dump("asked")) as f:
            dumped = json.load(f)["static"]
    finally:
        eng.close()
    assert set(boot) == {"age_at_install_s", "phases", "programs", "builds",
                         "hits", "misses", "trace_s", "lower_s",
                         "cache_load_s", "compile_s", "handed"}
    for view in (static["boot"], dumped["boot"]):
        assert view["builds"] >= boot["builds"] - 2
        assert [p["name"] for p in view["phases"]] == \
            [p["name"] for p in boot["phases"]]
    assert "attention_kinds" in static and "joined_keys" in dumped
    json.dumps(boot)
    names = [p["name"] for p in boot["phases"]]
    assert {"boot.model", "boot.rope", "boot.engine",
            "boot.engine.pool"} <= set(names)
    # a child closes before its parent, and lies inside it
    by = {p["name"]: p for p in boot["phases"][-2:]}
    assert names[-2:] == ["boot.engine.pool", "boot.engine"]
    assert by["boot.engine.pool"]["t_s"] >= by["boot.engine"]["t_s"]
    assert by["boot.engine.pool"]["dur_s"] <= by["boot.engine"]["dur_s"]
    programs = {p["program"]: p for p in eng.health()["boot"]["programs"]}
    assert programs["_prefill_slot"]["builds"] >= 1
    assert programs["_prefill_slot"]["trace_s"] > 0
    assert boot["trace_s"] + boot["lower_s"] > 0 and boot["compile_s"] > 0
    # the pool's zeros are built inside the engine's constructor
    assert any(b.get("phase") == "boot.engine.pool"
               for b in list(PROCESS._builds))


def test_a_bucket_first_met_while_serving_leaves_a_compile_event(model):
    eng = _engine(model)
    try:
        _run(eng, "boot-w1", range(3, 23))          # bucket 32, and decode
        before = obs.SERVE_INBAND_COMPILES.value(program="_prefill_slot")
        first = _run(eng, "boot-c1", range(5, 55))      # bucket 64: new
        mid = obs.SERVE_INBAND_COMPILES.value(program="_prefill_slot")
        second = _run(eng, "boot-c2", range(105, 155))  # met before
        after = obs.SERVE_INBAND_COMPILES.value(program="_prefill_slot")
        _settle(eng)
    finally:
        eng.close()
    assert [e["program"] for e in first] == ["_prefill_slot"]
    assert first[0]["ms"] > 0 and first[0]["cache"] in ("off", "miss", "hit")
    chunk = next(e for e in TIMELINES.get("boot-c1")["events"]
                 if e["kind"] == "prefill_chunk")
    assert first[0]["step"] == chunk["step"]
    assert second == []
    assert (mid - before, after - mid) == (1, 0)


def test_capture_spans_lie_beneath_prefill_finish(model):
    block = 32
    eng = _engine(model, chunk=block, prefix_cache_mb=8)
    try:
        _run(eng, "boot-p0", range(3, 23))
        _settle(eng)
        RECORDER.clear()
        RECORDER.enable()
        try:
            _run(eng, "boot-p1", range(7, 7 + 2 * block + 9))
            _settle(eng)
        finally:
            RECORDER.disable()
        events = RECORDER.events()
    finally:
        RECORDER.clear()
        eng.close()
    by_id = {e["args"]["id"]: e for e in events if "id" in e.get("args", {})}

    def parent(e):
        return by_id[e["args"]["parent"]]["name"]

    captures = [e for e in events if e["name"] == "serve.capture_blocks"]
    inserts = [e for e in events if e["name"] == "prefix.insert"]
    extracts = [e for e in events if e["name"] == "prefix.extract"]
    assert len(captures) == len(inserts) == len(extracts) == 2
    assert {parent(e) for e in captures} == {"serve.prefill_finish"}
    assert {parent(e) for e in inserts} == {"serve.capture_blocks"}
    assert {parent(e) for e in extracts} == {"prefix.insert"}
    assert [e["args"]["block"] for e in inserts] == [0, 1]
    for e in captures:
        finish = by_id[e["args"]["parent"]]
        assert finish["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= finish["ts"] + finish["dur"] + 50
        assert e["args"]["step"] == finish["args"]["step"]
    # every chunk has its serve.prefill_finish, a capture or not
    finishes = [e for e in events if e["name"] == "serve.prefill_finish"]
    assert len(finishes) == 3 and len({e["args"]["id"] for e in finishes}) == 3
    # the global recorder was handed this process's start-up when it was
    # switched on, and keeps it beside the ring
    assert events[0]["name"] in ("process.compile", "boot.rope", "boot.model")
    assert any(e["name"] == "boot.engine" for e in events)


# -- the five readers and their entries --------------------------------------

@pytest.fixture(scope="module")
def manifest():
    sys.path.insert(0, BENCH)
    try:
        import manifest as m
        yield m
    finally:
        sys.path.remove(BENCH)


def _span(name, end_s, dur_s, **args):
    return {"name": name, "cat": "process", "ph": "X",
            "ts": int((end_s - dur_s) * 1e6), "dur": int(dur_s * 1e6),
            "args": args}


SPANS = [
    _span("boot.rope", 3.0, 1.0, id=2, parent=1),
    _span("process.compile", 4.0, 0.5, program="p", stage="trace"),
    _span("process.compile", 4.5, 0.25, program="p", stage="lower"),
    _span("process.compile", 6.0, 1.5, program="p", stage="backend",
          cache="hit"),
    _span("process.compile", 8.0, 2.0, program="q", stage="backend",
          cache="miss"),
    _span("process.compile", 8.5, 0.125, program="r", stage="backend",
          cache="off"),
    _span("boot.model", 9.0, 7.0, id=1),
    _span("boot.engine", 9.75, 0.75, id=3),
    # built while serving: the window's, not the start-up's
    _span("process.compile", 12.0, 4.0, program="late", stage="backend",
          cache="miss"),
    _span("process.compile", 10.5, 1.0, program="late", stage="trace"),
    {"name": "serve.step", "ts": int(11e6), "dur": 30_000, "args": {"id": 9}},
]
WANT = {"process.trace_lower_s": 0.75, "process.cache_load_s": 1.5,
        "process.compile_s": 2.125, "programs.build_s": 7.0,
        "engine.build_s": 0.75}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_sums_what_ended_before_the_window(manifest, name):
    read = manifest.metric_reader(BENCH, name)
    ctx = SimpleNamespace(spans=SPANS, window_perf=[10.0, 50.0])
    assert read(ctx) == pytest.approx(WANT[name])
    # the parent's spans: nothing of the start-up, nothing to read
    assert read(SimpleNamespace(spans=SPANS[-1:],
                                window_perf=[10.0, 50.0])) is None
    assert read(SimpleNamespace(spans=[], window_perf=[10.0, 50.0])) is None


def test_a_warm_run_reads_zero_compile_seconds_not_none(manifest):
    warm = [s for s in SPANS if s["args"].get("cache") in (None, "hit")]
    ctx = SimpleNamespace(spans=warm, window_perf=[10.0, 50.0])
    assert manifest.metric_reader(BENCH, "process.compile_s")(ctx) == 0.0
    assert manifest.metric_reader(BENCH, "process.cache_load_s")(ctx) == 1.5


def test_the_five_entries_move_setup_s_in_every_cell(manifest):
    assert manifest.validate(ROOT) == []
    m = manifest.load(ROOT)
    cells = [w["name"] for w in m["workloads"]]
    by = {e["name"]: e for e in m["per_layer"]}
    assert set(NEW) <= set(by)
    for name in NEW:
        e = by[name]
        assert (e["moves"], e["unit"], e["better"], e["source"]) == \
            ("setup_s", "s", "lower", "program_span")
        assert e["workloads"] == cells
        assert e["layer"] == name.split(".")[0]
    # `setup_s` is judged in every cell, and these are what lies beneath it
    setup = next(e for e in m["end_to_end"] if e["name"] == "setup_s")
    assert "workloads" not in setup
    assert {e["name"] for e in m["per_layer"]
            if e["moves"] == "setup_s"} >= set(NEW)
