"""A run that stood still says where (ISSUE 41), part three: the api
layer's first spans: the event loop's tick and its lag, and one
`api.sse_write` a streamed content token."""
import asyncio
import json
import time

import pytest

from cake_tpu import obs
from cake_tpu.obs import PROCESS, RECORDER, LoopTick
from cake_tpu.obs.process import TICK_S
from cake_tpu.serve import ServeEngine, faults
from tests.test_serve import CTX, _api_state, _model, _run
from tests.test_stalls import _watch


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def engine(model):
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=CTX)
    yield eng
    eng.close()


def test_the_tick_reads_a_deliberate_block_of_the_loop():
    watch, reg = _watch()
    block_s = 0.25 + TICK_S         # a tick is due within TICK_S of its start

    async def scenario():
        tick = LoopTick(asyncio.get_running_loop(), watch)
        tick.start()
        await asyncio.sleep(3 * TICK_S)
        quiet = watch.loop_lag()
        t0 = obs.now()
        time.sleep(block_s)         # the loop stands still, on purpose
        t1 = obs.now()
        await asyncio.sleep(2 * TICK_S)
        tick.stop()
        n = len(watch._lags)
        await asyncio.sleep(3 * TICK_S)
        assert len(watch._lags) == n        # stopped: no tick re-arms
        return quiet, t0, t1

    quiet, t0, t1 = asyncio.run(scenario())
    assert quiet is not None and quiet["max_60s"] < 200.0
    lag = watch.loop_lag()
    assert lag["max_60s"] >= 250.0 > lag["last"]
    assert watch.between(t0, t1)["loop_lag_ms"] == lag["max_60s"]
    assert watch.between(t0 - 9.0, t0 - 8.0)["loop_lag_ms"] == 0.0
    assert reg.histogram("l").count() == len(watch._lags) >= 3
    assert reg.histogram("l").sum() >= 0.25


def test_the_app_ticks_from_start_to_cleanup_and_health_reads_it(model,
                                                                 engine):
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app

    seen = {}

    async def scenario():
        client = TestClient(TestServer(create_app(_api_state(model,
                                                             engine))))
        await client.start_server()
        try:
            RECORDER.clear()
            RECORDER.enable()
            await asyncio.sleep(4 * TICK_S)
            RECORDER.disable()
            r = await client.get("/health")
            seen["health"] = await r.json()
            r = await client.get("/api/v1/flight?n=2")
            seen["flight"] = await r.json()
            r = await client.get("/metrics")
            seen["metrics"] = await r.text()
        finally:
            RECORDER.disable()
            await client.close()
        seen["n"] = len(PROCESS._lags)
        await asyncio.sleep(3 * TICK_S)
        seen["n_after"] = len(PROCESS._lags)

    _run(scenario())
    # always on, and no span: the recorder saw nothing of the ticks
    assert not [e for e in RECORDER.events() if e["cat"] == "api"]
    RECORDER.clear()
    assert seen["n"] >= 2
    eng = seen["health"]["engine"]
    assert set(eng["loop_lag_ms"]) == {"last", "max_60s"}
    assert {"stalls", "steps_by_kind", "occupancy_sum"} <= set(eng)
    assert set(seen["flight"]["stalls"]) == {"count", "total_ms",
                                            "reference_ms", "worst"}
    assert "cake_api_loop_lag_seconds_count" in seen["metrics"]
    # a full ring turns without growing: stopped means no new sample
    assert seen["n_after"] == seen["n"] < PROCESS._lags.maxlen


@pytest.mark.parametrize("recorder_on", [True, False])
def test_one_sse_write_span_a_content_token(model, engine, recorder_on):
    from aiohttp.test_utils import TestClient, TestServer
    from cake_tpu.api import create_app

    out = {}

    async def scenario():
        client = TestClient(TestServer(create_app(_api_state(model,
                                                             engine))))
        await client.start_server()
        try:
            r = await client.post(
                "/v1/chat/completions",
                headers={"X-Cake-Request-Id": "sse-span"},
                json={"messages": [{"role": "user", "content": "count me"}],
                      "max_tokens": 9, "temperature": 0.0, "stream": True})
            assert r.status == 200
            out["body"] = (await r.read()).decode()
        finally:
            await client.close()

    RECORDER.clear()
    if recorder_on:
        RECORDER.enable()
    # a paced engine (30 ms a decode dispatch): the handler, which polls
    # for the slot every 20 ms, subscribes while the request still runs
    faults.install("delay_ms=30")
    try:
        _run(scenario())
    finally:
        faults.clear()
        RECORDER.disable()
    writes = [e for e in RECORDER.events() if e["name"] == "api.sse_write"]
    RECORDER.clear()
    chunks = [json.loads(line[6:]) for line in out["body"].split("\n\n")
              if line.startswith("data: ") and line != "data: [DONE]"]
    content = [c for c in chunks
               if c["choices"][0]["delta"].get("content")]
    assert len(content) >= 5
    if not recorder_on:
        assert writes == []
        return
    # (a token emitted before the stream subscribed arrives unstamped)
    assert len(content) - 2 <= len(writes) <= len(content)
    for e in writes:
        assert e["cat"] == "api" and e["dur"] >= 0
        assert e["args"]["wait_us"] >= 0 and e["args"]["rid"] == "sse-span"
    assert [e["ts"] for e in writes] == sorted(e["ts"] for e in writes)
