"""End-to-end observability through the aiohttp API: a short generation on
a real tiny model must leave non-zero TTFT / decode-latency histograms on
GET /metrics (valid Prometheus text exposition) and per-token phase events
in the span recorder's Chrome-trace export — the acceptance path for the
obs subsystem. /health is asserted alongside (worker liveness shape)."""
import json
import re

import jax.numpy as jnp
import pytest

from cake_tpu import obs
from cake_tpu.api import ApiState, create_app
from tests.test_api import MockTokenizer, with_client

# label VALUES are quoted strings and may hold braces: the registry is
# process-global, and whichever test file shared this xdist worker may have
# hit a templated route (endpoint="/api/v1/requests/{rid}")
PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{(?:[^"}]|"[^"]*")*\})? '
    r'(-?[0-9.e+-]+|NaN|[+-]Inf)$')


def _metric_value(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{name} not found in exposition")


def _assert_valid_exposition(text: str):
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert PROM_LINE.match(line), f"bad exposition line: {line!r}"


@pytest.fixture(scope="module")
def tiny_cluster_state():
    """DistributedTextModel over a single LOCAL stage (no sockets): runs
    the real per-token decode loop — embed / layers / lm_head / sample as
    distinct phases — on a tiny random-weight CPU model."""
    from cake_tpu.cluster.master import DistributedTextModel, Stage
    from cake_tpu.models import TextModel, tiny_config
    from cake_tpu.models.common.text_model import LocalStage

    cfg = tiny_config("qwen3")
    tm = TextModel(cfg, dtype=jnp.float32, max_cache_len=64)
    stage = Stage("local", 0, cfg.num_hidden_layers,
                  LocalStage(cfg, tm.params, 0, cfg.num_hidden_layers))
    dist = DistributedTextModel(cfg, tm.params, [stage],
                                tokenizer=MockTokenizer(),
                                dtype=jnp.float32, max_cache_len=64)
    return ApiState(model=dist, tokenizer=MockTokenizer(),
                    model_id="tiny-dist")


def test_metrics_health_and_trace_after_generation(tiny_cluster_state):
    obs.RECORDER.enable()
    obs.RECORDER.clear()
    ttft_before = obs.TTFT_SECONDS.count()
    decode_before = obs.DECODE_TOKEN_SECONDS.count()
    out = {}

    async def scenario(client):
        resp = await client.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hi there"}],
            "max_tokens": 6, "temperature": 0.0})
        assert resp.status == 200
        body = await resp.json()
        assert body["id"].startswith("chatcmpl-")
        assert body["usage"]["completion_tokens"] >= 2
        out["cid"] = body["id"]

        m = await client.get("/metrics")
        assert m.status == 200
        assert m.headers["Content-Type"].startswith("text/plain")
        out["metrics"] = await m.text()

        h = await client.get("/health")
        assert h.status == 200
        out["health"] = await h.json()

    with_client(tiny_cluster_state, scenario)

    # -- /metrics: valid exposition, non-zero TTFT + decode histograms ------
    text = out["metrics"]
    _assert_valid_exposition(text)
    assert _metric_value(text, "cake_ttft_seconds_count") >= ttft_before + 1
    assert _metric_value(text, "cake_decode_token_seconds_count") \
        >= decode_before + 1
    assert _metric_value(text, "cake_ttft_seconds_sum") > 0
    assert 'cake_generated_tokens_total{path="cluster"}' in text
    assert 'cake_generations_total{kind="text",status="ok"}' in text
    # the middleware counted this very scrape's sibling requests
    assert 'endpoint="/v1/chat/completions",status="200"' in text

    # -- /health ------------------------------------------------------------
    health = out["health"]
    assert health["status"] == "ok"
    assert health["workers"] == []          # local-only stage chain
    assert any(m.startswith("tiny-dist") for m in health["models"])

    # -- span recorder: Chrome-trace JSON with per-token phase events -------
    trace = json.loads(json.dumps(obs.RECORDER.to_chrome_trace()))
    # the process's start-up and program builds (obs/process.py) are held
    # beside the ring and come first, whenever they happened and whichever
    # request built them: this test reads the ring's
    held = [e.get("cat") in ("process", "boot")
            for e in trace["traceEvents"]]
    assert held == sorted(held, reverse=True)
    events = [e for e, h in zip(trace["traceEvents"], held) if not h]
    names = [e["name"] for e in events]
    assert "prefill" in names
    decode_tokens = [e for e in events if e["name"] == "decode_token"]
    assert len(decode_tokens) >= 2          # one span per decoded token
    for phase in ("embed", "layers", "lm_head", "sample"):
        assert names.count(phase) >= len(decode_tokens), phase
    # events append in completion order, so per thread the END timestamps
    # are monotonic (a parent's start precedes its earlier-appended
    # children — fine for Perfetto, which nests by ts+dur)
    ends: dict = {}
    for e in events:
        if e["ph"] != "X":
            continue
        assert e["dur"] >= 0
        assert e["ts"] + e["dur"] >= ends.get(e["tid"], 0)
        ends[e["tid"]] = e["ts"] + e["dur"]
    # spans recorded inside the generation carry the completion id
    gen_events = [e for e in events
                  if e.get("args", {}).get("request_id")]
    assert gen_events and all(
        e["args"]["request_id"] == out["cid"] for e in gen_events)


def test_trace_endpoint():
    state = ApiState(model=None)

    async def scenario(client):
        obs.RECORDER.disable()
        r = await client.get("/api/v1/trace")
        assert r.status == 409              # recorder off -> explicit error
        obs.RECORDER.enable()
        obs.RECORDER.clear()
        with obs.RECORDER.span("x"):
            pass
        r = await client.get("/api/v1/trace?clear=1")
        assert r.status == 200
        body = await r.json()
        assert any(e["name"] == "x" for e in body["traceEvents"])
        assert len(obs.RECORDER) == 0       # ?clear=1 drained the buffer

    with_client(state, scenario)


def test_health_without_model():
    state = ApiState(model=None)

    async def scenario(client):
        h = await client.get("/health")
        assert h.status == 200
        body = await h.json()
        assert body["status"] == "ok"
        assert body["workers"] == [] and body["models"] == []

    with_client(state, scenario)


def test_metrics_endpoint_label_bounded():
    """Unmatched paths must not mint unbounded endpoint labels."""
    state = ApiState(model=None)

    async def scenario(client):
        for path in ("/nope/a", "/nope/b", "/nope/c"):
            r = await client.get(path)
            assert r.status == 404
        m = await client.get("/metrics")
        text = await m.text()
        assert 'endpoint="unmatched",status="404"' in text
        assert "/nope/a" not in text

    with_client(state, scenario)


def test_flight_endpoint_on_demand():
    """GET /api/v1/flight serves the scheduler-iteration ring read-only
    (?n=K truncates to the newest K); 409 without an engine — the ring
    must be inspectable without waiting for a wedge/DOWN dump."""
    from cake_tpu.serve.flight import FlightRecorder

    state = ApiState(model=None)

    async def scenario(client):
        r = await client.get("/api/v1/flight")
        assert r.status == 409              # no engine -> explicit error

        class FakeEngine:
            flight = FlightRecorder(capacity=8)
        for i in range(12):                 # overflow the ring
            FakeEngine.flight.record(iteration=i, occupancy=0.5)
        state.engine = FakeEngine()
        try:
            r = await client.get("/api/v1/flight")
            assert r.status == 200
            body = await r.json()
            assert body["capacity"] == 8 and body["count"] == 8
            assert [it["iteration"] for it in body["iterations"]] == \
                list(range(4, 12))          # oldest evicted, order kept
            r = await client.get("/api/v1/flight?n=3")
            body = await r.json()
            assert [it["iteration"] for it in body["iterations"]] == \
                [9, 10, 11]
            r = await client.get("/api/v1/flight?n=bogus")
            assert (await r.json())["count"] == 8   # tolerated
        finally:
            state.engine = None

    with_client(state, scenario)


def test_worker_health_reports_last_ok_age():
    from cake_tpu.api.obs_routes import STALE_WORKER_S, worker_health
    from cake_tpu.cluster.client import RemoteStage
    from cake_tpu.cluster.master import Stage

    rs = RemoteStage("127.0.0.1", 0, "k", name="w0")
    rs.total_ops = 1
    rs.last_attempt = obs.now() - 2.0
    rs.last_ok = obs.now() - 2.0

    class M:
        stages = [Stage("remote", 0, 4, rs)]

    (w,) = worker_health(M())
    assert w["name"] == "w0" and w["layers"] == [0, 4] and w["ops"] == 1
    assert 1.5 <= w["last_ok_age_s"] <= 10.0
    assert w["failing"] is False

    # long-idle channel stays healthy (idleness is not failure) ...
    rs.last_attempt = rs.last_ok = obs.now() - 10 * STALE_WORKER_S
    (w,) = worker_health(M())
    assert w["failing"] is False
    # ... but attempts without successes for > threshold flag it
    rs.last_attempt = obs.now()
    (w,) = worker_health(M())
    assert w["failing"] is True
    # wedged mid-forward: one attempt newer than the last success, frozen
    # for > threshold with no further attempts arriving
    rs.last_ok = obs.now() - 2 * STALE_WORKER_S
    rs.last_attempt = rs.last_ok + 0.05
    (w,) = worker_health(M())
    assert w["failing"] is True
    # tried and never succeeded: failing immediately
    rs.last_ok = None
    (w,) = worker_health(M())
    assert w["failing"] is True
