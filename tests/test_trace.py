"""Request-scoped tracing plane (ISSUE 13): timeline store semantics,
trace-id propagation router -> replica -> engine, timeline completeness
for a preempted + replayed request, SLO exemplars, and the flight
recorder dumping on an injected wedge."""
import asyncio
import glob
import json
import os
import threading

import jax.numpy as jnp
import pytest

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.obs import TIMELINES, TRACE_HEADER, TimelineStore
from cake_tpu.obs.metrics import MetricsRegistry
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine
from cake_tpu.serve import faults
from cake_tpu.serve.flight import FlightRecorder

GREEDY = SamplingConfig(temperature=0.0)
CTX = 128
CHUNK = 16

P_A = [3, 17, 42, 99, 7]
P_B = [100, 2, 5, 9, 11, 40]


# ---------------------------------------------------------------------------
# units: no model required
# ---------------------------------------------------------------------------


def test_timeline_store_ring_and_event_cap():
    st = TimelineStore(capacity=2, max_events=3)
    st.begin("a")
    st.begin("b")
    st.begin("c")                       # evicts a (ring of 2)
    assert st.get("a") is None and st.ids() == ["b", "c"]
    for _ in range(5):
        st.event("b", "decode", bucket=1)
    st.event("b", "finish", outcome="ok")   # terminal bypasses the cap
    tl = st.get("b")
    assert len(tl["events"]) == 4
    assert tl["events"][-1]["kind"] == "finish"
    assert tl["dropped"] == 2
    # monotonic offsets
    ts = [e["t_ms"] for e in tl["events"]]
    assert ts == sorted(ts)


def test_timeline_alias_and_unknown_ids():
    st = TimelineStore(capacity=4)
    st.begin("trace-1", tier="router")
    st.alias("chatcmpl-9", "trace-1")
    st.event("chatcmpl-9", "received")      # alias records into trace-1
    assert st.get("chatcmpl-9")["request_id"] == "trace-1"
    assert st.get("trace-1")["tier"] == "router"
    st.event("never-begun", "received")     # unknown id: silent no-op
    assert st.get("never-begun") is None
    with pytest.raises(ValueError):
        st.event("trace-1", "not_a_kind")   # vocabulary is closed


def test_timeline_chrome_export_shape():
    st = TimelineStore(capacity=2)
    st.begin("r")
    st.event("r", "enqueue", depth=3)
    trace = st.to_chrome("r")
    (ev,) = trace["traceEvents"]
    assert ev["ph"] == "i" and ev["name"] == "enqueue"
    assert ev["args"]["depth"] == 3 and ev["args"]["request_id"] == "r"
    assert st.to_chrome("missing") is None


def test_histogram_exemplars_per_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("cake_test_ex_seconds", "t", labelnames=("outcome",),
                      buckets=(0.1, 1.0))
    h.observe(0.05, exemplar="req-fast", outcome="ok")
    h.observe(0.5, exemplar="req-mid", outcome="ok")
    h.observe(0.6, exemplar="req-mid2", outcome="ok")   # last wins
    h.observe(5.0, exemplar="req-slow", outcome="ok")
    ex = h.exemplars(outcome="ok")
    assert ex["0.1"]["exemplar"] == "req-fast"
    assert ex["1"]["exemplar"] == "req-mid2"
    assert ex["+Inf"]["exemplar"] == "req-slow"
    assert h.exemplars(outcome="error") == {}
    h.clear()
    assert h.exemplars(outcome="ok") == {}


def test_flight_recorder_ring_and_dump(tmp_path, monkeypatch):
    fr = FlightRecorder(capacity=3)
    for i in range(5):
        fr.record(occupancy=i)
    snap = fr.snapshot()
    assert [r["occupancy"] for r in snap] == [2, 3, 4]
    assert [r["seq"] for r in snap] == [3, 4, 5]
    monkeypatch.delenv("CAKE_TRACE_DIR", raising=False)
    assert fr.dump("wedge") is None         # no trace dir: no file
    monkeypatch.setenv("CAKE_TRACE_DIR", str(tmp_path))
    path = fr.dump("down", extra={"last_failure": {"kind": "oom"}})
    with open(path) as f:
        body = json.load(f)
    assert body["reason"] == "down"
    assert len(body["iterations"]) == 3
    assert body["last_failure"]["kind"] == "oom"


# ---------------------------------------------------------------------------
# engine + API: adoption, completeness, SLO exemplars
# ---------------------------------------------------------------------------


class TinyTok:
    def encode(self, text):
        return [3 + (sum(w.encode()) % 200) for w in text.split()][:24] \
            or [3]

    def decode(self, ids):
        return "".join(f"<{i}>" for i in ids)


_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        _MODEL = TextModel(tiny_config("llama"), dtype=jnp.float32,
                           max_cache_len=CTX)
        _MODEL.tokenizer = TinyTok()
    return _MODEL


@pytest.fixture(scope="module")
def model():
    return _model()


def _run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def test_api_adopts_trace_header_into_engine_timeline(model):
    """The replica API adopts X-Cake-Request-Id as THE request id: the
    engine's lifecycle events land on it, /api/v1/requests resolves it
    (and the completion-id alias), the response echoes it, and the SLO
    endpoint's exemplars point at it."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import ApiState, create_app

    engine = ServeEngine(model, slots=2, max_queue=8, ctx_len=CTX,
                         prefill_chunk=CHUNK)
    state = ApiState(model=model, tokenizer=model.tokenizer,
                     model_id="trace-test")
    state.engine = engine
    rid = "trace-feedc0ffee123456"

    async def drive():
        client = TestClient(TestServer(create_app(state)))
        await client.start_server()
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user",
                                    "content": "hello trace"}],
                      "max_tokens": 5, "temperature": 0.0},
                headers={TRACE_HEADER: rid})
            assert r.status == 200, await r.text()
            assert r.headers.get(TRACE_HEADER) == rid
            cid = (await r.json())["id"]
            t1 = await client.get(f"/api/v1/requests/{rid}")
            assert t1.status == 200
            tl = await t1.json()
            t2 = await client.get(f"/api/v1/requests/{cid}")
            assert t2.status == 200          # completion-id alias
            assert (await t2.json())["request_id"] == rid
            perf = await client.get(f"/api/v1/requests/{rid}"
                                    "?format=perfetto")
            assert perf.status == 200
            assert (await perf.json())["traceEvents"]
            t404 = await client.get("/api/v1/requests/nope")
            assert t404.status == 404
            idx = await client.get("/api/v1/requests")
            assert rid in (await idx.json())["requests"]
            slo = await client.get("/api/v1/slo")
            return tl, await slo.json()
        finally:
            await client.close()

    try:
        tl, slo = _run(drive())
    finally:
        engine.close()
    kinds = [e["kind"] for e in tl["events"]]
    for k in ("received", "enqueue", "admit", "prefill_chunk",
              "prefill_done", "first_token", "decode", "finish"):
        assert k in kinds, (k, kinds)
    assert kinds.index("enqueue") < kinds.index("admit") \
        < kinds.index("prefill_done") < kinds.index("first_token")
    finish = [e for e in tl["events"] if e["kind"] == "finish"][0]
    assert finish["outcome"] == "ok" and finish["tokens"] > 0
    assert finish["e2e_ms"] >= finish["ttft_ms"] > 0
    exemplars = [ex["exemplar"]
                 for hist in slo.values() for series in hist["series"]
                 for ex in series["exemplars"].values()]
    assert rid in exemplars


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_timeline_preempted_and_replayed_request_is_complete(model):
    """A request preempted under paged-pool pressure (recompute mode)
    keeps one coherent timeline: enqueue -> admit -> prefill ->
    first_token -> preempt -> resume -> replay -> finish, with the
    output still bit-identical to the sequential path."""
    ref_a = model.generate(P_A, max_new_tokens=60, sampling=GREEDY)[0]
    ref_b = model.generate(P_B, max_new_tokens=60, sampling=GREEDY)[0]
    eng = ServeEngine(model, slots=2, max_queue=8, ctx_len=CTX,
                      prefill_chunk=CHUNK, prefix_cache_mb=0,
                      kv_blocks=12, kv_block_tokens=8,
                      preempt_mode="recompute")
    try:
        ra = eng.submit(P_A, max_new_tokens=60, sampling=GREEDY)
        rb = eng.submit(P_B, max_new_tokens=60, sampling=GREEDY)
        assert ra.wait(600) and rb.wait(600)
        assert "error" not in ra.result and "error" not in rb.result
        assert ra.result["tokens"] == ref_a
        assert rb.result["tokens"] == ref_b
    finally:
        eng.close()
    kinds_by_req = {rid: [e["kind"] for e in TIMELINES.get(rid)["events"]]
                    for rid in (ra.id, rb.id)}
    preempted = [ks for ks in kinds_by_req.values() if "preempt" in ks]
    assert preempted, f"pool never preempted: {kinds_by_req}"
    ks = preempted[0]
    for k in ("enqueue", "admit", "first_token", "preempt", "resume",
              "replay", "finish"):
        assert k in ks, (k, ks)
    assert ks.index("preempt") < ks.index("resume") < ks.index("replay")
    assert ks[-1] == "finish"


def test_cancelled_request_records_error_outcome(model):
    from cake_tpu.obs import SERVE_E2E_SECONDS
    eng = ServeEngine(model, slots=1, max_queue=4, ctx_len=CTX,
                      prefill_chunk=CHUNK)
    before = SERVE_E2E_SECONDS.count(outcome="cancelled")
    try:
        req = eng.submit(P_A, max_new_tokens=200, sampling=GREEDY)
        # wait until it is actually decoding, then cancel
        deadline = 60.0
        while not req.tokens and deadline > 0 and not req.done.is_set():
            threading.Event().wait(0.01)
            deadline -= 0.01
        req.cancel()
        assert req.wait(60)
    finally:
        eng.close()
    kinds = [e["kind"] for e in TIMELINES.get(req.id)["events"]]
    assert kinds[-1] == "finish"
    finish = [e for e in TIMELINES.get(req.id)["events"]
              if e["kind"] == "finish"][0]
    assert finish["outcome"] == "cancelled"
    assert SERVE_E2E_SECONDS.count(outcome="cancelled") > before


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    yield
    faults.clear()


def test_flight_recorder_dumps_on_injected_wedge(model, tmp_path,
                                                 monkeypatch):
    """A stalled dispatch (CAKE_SERVE_FAULT_PLAN stall) past the wedge
    watchdog limit must leave a flight-recorder dump in CAKE_TRACE_DIR
    carrying the last iterations' records — the black box the operator
    reads after the process is killed."""
    monkeypatch.setenv("CAKE_TRACE_DIR", str(tmp_path))
    faults.install("stall_on_step=2;stall_step_ms=600")
    eng = ServeEngine(model, slots=1, max_queue=4, ctx_len=CTX,
                      prefill_chunk=CHUNK, step_watchdog_s=0.1)
    try:
        req = eng.submit(P_A, max_new_tokens=8, sampling=GREEDY)
        assert req.wait(600)
        assert "error" not in req.result
        # the stall returned, so the wedge flag cleared (gray
        # semantics) — but the dump must have been written while the
        # dispatch was stuck
        deadline = 30.0
        while deadline > 0:
            dumps = glob.glob(os.path.join(str(tmp_path),
                                           "cake-flight-*-wedge.json"))
            if dumps:
                break
            threading.Event().wait(0.05)
            deadline -= 0.05
        assert dumps, "watchdog never dumped the flight recorder"
        with open(dumps[0]) as f:
            body = json.load(f)
        assert body["reason"] == "wedge"
        assert body["iterations"], "dump carries no iteration records"
        rec = body["iterations"][-1]
        assert {"seq", "t", "occupancy", "bucket", "host_ms", "fetch_ms",
                "queued"} <= set(rec)
        assert eng.supervisor.wedge_count >= 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# router tier: propagation + stitching over a fake replica
# ---------------------------------------------------------------------------


class _FakeReplica:
    """Minimal replica: records the trace header it received, serves a
    canned completion, and answers /api/v1/requests/<id> with a
    replica-tier timeline for ids it saw."""

    def __init__(self, name="r0"):
        self.name = name
        self.seen_headers: list = []
        self.server = None

    def app(self):
        from aiohttp import web

        async def chat(request):
            self.seen_headers.append(request.headers.get(TRACE_HEADER))
            return web.json_response(
                {"id": "chatcmpl-fake", "object": "chat.completion",
                 "choices": [{"index": 0, "message":
                              {"role": "assistant", "content": "hi"},
                              "finish_reason": "stop"}]})

        async def timeline(request):
            rid = request.match_info["rid"]
            if rid not in self.seen_headers:
                return web.json_response({"error": "unknown"}, status=404)
            return web.json_response(
                {"request_id": rid, "tier": "replica", "start_unix": 0.0,
                 "events": [{"t_ms": 0.0, "kind": "received"},
                            {"t_ms": 1.0, "kind": "finish",
                             "outcome": "ok"}],
                 "dropped": 0})

        async def health(request):
            return web.json_response({"engine": {
                "alive": True, "slots": 2, "queue_depth": 0}})

        from aiohttp import web as w
        app = w.Application()
        app.router.add_post("/v1/chat/completions", chat)
        app.router.add_get("/api/v1/requests/{rid}", timeline)
        app.router.add_get("/health", health)
        return app

    async def start(self):
        from aiohttp.test_utils import TestServer
        self.server = TestServer(self.app())
        await self.server.start_server()
        return str(self.server.make_url("")).rstrip("/")

    async def stop(self):
        if self.server is not None:
            await self.server.close()


def test_router_injects_trace_id_and_stitches_tiers():
    """cake route mints a trace id, injects it into the replica attempt,
    echoes it on the response, and /api/v1/requests/<id> on the router
    returns the stitched router + replica timeline."""
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.fleet.registry import MembershipPolicy, ReplicaRegistry
    from cake_tpu.fleet.router import FleetRouter, create_router_app

    rep = _FakeReplica()
    registry = ReplicaRegistry(MembershipPolicy())

    async def drive():
        url = await rep.start()
        registry.add(rep.name, url)
        router = FleetRouter(registry, retries=1, backoff_s=0.001,
                             probe_s=30.0, hedge_ms=0.0)
        client = TestClient(TestServer(create_router_app(router)))
        await client.start_server()
        try:
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hello"}]})
            assert r.status == 200, await r.text()
            rid = r.headers.get(TRACE_HEADER)
            assert rid and rid.startswith("trace-")
            # the replica received the SAME id the client got back
            assert rep.seen_headers == [rid]
            st = await client.get(f"/api/v1/requests/{rid}")
            assert st.status == 200
            stitched = await st.json()
            # a client-supplied id is adopted, not replaced
            r2 = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "again"}]},
                headers={TRACE_HEADER: "trace-client-chosen"})
            assert r2.headers.get(TRACE_HEADER) == "trace-client-chosen"
            assert rep.seen_headers[-1] == "trace-client-chosen"
            missing = await client.get("/api/v1/requests/trace-unknown")
            assert missing.status == 404
            return stitched
        finally:
            await client.close()
            await rep.stop()

    stitched = _run(drive())
    tiers = {t["tier"]: t for t in stitched["tiers"]}
    assert set(tiers) == {"router", "replica"}
    router_kinds = [e["kind"] for e in tiers["router"]["events"]]
    assert ["route", "attempt", "done"] == router_kinds
    attempt = [e for e in tiers["router"]["events"]
               if e["kind"] == "attempt"][0]
    assert attempt["replica"] == rep.name and attempt["status"] == 200
    assert tiers["replica"]["replica"] == rep.name
