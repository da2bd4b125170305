"""Unified admission plane (ISSUE 14): weighted-fair dequeue invariants,
per-class backpressure, tenant quotas, QoS-aware preemption policy, job
executor drain semantics, and engine-level preempt-resume bit-parity for
a batch slot evicted under interactive pressure (swap AND recompute).

The engine tests reuse test_paged's pool shape (12 blocks x 8 tokens,
chunk 16, ctx 128) so the paged executables compile once per model."""
import threading
import time
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from cake_tpu.models import TextModel, tiny_config
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine
from cake_tpu.serve.admission import (AdmissionQueue, GenerationJob,
                                      JobCancelled, JobExecutor,
                                      JobsDraining, QueueFull,
                                      TenantQuotaExceeded, TenantRegistry,
                                      resolve_class, retry_after_for)
from cake_tpu.serve.paged import choose_victim

GREEDY = SamplingConfig(temperature=0.0)
CTX = 128
CHUNK = 16
BT = 8
BLOCKS = 12
WEIGHTS = {"interactive": 8.0, "standard": 4.0, "batch": 1.0}


def _item(qos):
    return SimpleNamespace(qos=qos)


# ---------------------------------------------------------------------------
# weighted-fair dequeue (pure host)
# ---------------------------------------------------------------------------


def test_weighted_fair_ratio_under_saturation():
    """With both lanes saturated, dequeues converge to the weight ratio
    — and batch is served at least once per replenish round (no
    starvation)."""
    q = AdmissionQueue(64, weights=WEIGHTS)
    for _ in range(32):
        q.put(_item("interactive"))
        q.put(_item("batch"))
    first_27 = [q.pop().qos for _ in range(27)]     # 3 full rounds
    assert first_27.count("batch") == 3             # 1 per 9, exactly
    assert first_27.count("interactive") == 24      # 8 per 9
    # batch appears within every round of 9 — never starved
    for r in range(3):
        assert "batch" in first_27[r * 9:(r + 1) * 9]


def test_batch_progresses_under_continuous_interactive_arrivals():
    """Interactive arrivals that never stop cannot starve batch: each
    replenish round still credits the batch lane."""
    q = AdmissionQueue(256, weights=WEIGHTS)
    for _ in range(4):
        q.put(_item("batch"))
    served_batch = 0
    for _ in range(50):
        q.put(_item("interactive"))     # keep the fast lane saturated
        it = q.pop()
        if it.qos == "batch":
            served_batch += 1
    assert served_batch == 4, "batch starved behind interactive arrivals"


def test_deficit_resets_when_class_empties():
    """DRR reset-on-empty: an idle class banks no credit, so a burst
    after idling is served at its weight ratio, not its backlog age."""
    q = AdmissionQueue(64, weights=WEIGHTS)
    q.put(_item("batch"))
    assert q.pop().qos == "batch"       # round replenished, batch drains
    # batch lane idles through many interactive rounds
    for _ in range(20):
        q.put(_item("interactive"))
    for _ in range(20):
        assert q.pop().qos == "interactive"
        assert q._deficit["batch"] == 0.0   # reset while empty
    # now a mixed burst: interactive still gets its 8:1 share first
    for _ in range(9):
        q.put(_item("interactive"))
        q.put(_item("batch"))
    assert [q.pop().qos for _ in range(8)] == ["interactive"] * 8


def test_fifo_preserved_within_class():
    q = AdmissionQueue(64, weights=WEIGHTS)
    items = [SimpleNamespace(qos="interactive", n=i) for i in range(5)]
    for it in items:
        q.put(it)
    assert [q.pop().n for _ in range(5)] == [0, 1, 2, 3, 4]


def test_per_class_bound_and_class_aware_retry_after():
    """Bounds are per class: a full batch lane sheds batch (typed, with
    a LONGER Retry-After than the same depth would earn interactive)
    while interactive admission stays open."""
    q = AdmissionQueue(4, weights=WEIGHTS,
                       bounds={"interactive": 4, "standard": 4, "batch": 2})
    q.put(_item("batch"))
    q.put(_item("batch"))
    with pytest.raises(QueueFull) as ei:
        q.put(_item("batch"))
    assert ei.value.qos == "batch"
    assert ei.value.retry_after_s >= 1
    q.put(_item("interactive"))         # other lanes unaffected
    # the hint scales inversely with the class's service share
    assert retry_after_for(40, "batch", WEIGHTS) \
        > retry_after_for(40, "interactive", WEIGHTS)


def test_queue_depth_gauges_sum_across_queues():
    """The engine queue and the job queue publish into the SAME depth
    instruments — per class and in total."""
    import gc
    from cake_tpu.obs import SERVE_QOS_QUEUE_DEPTH, SERVE_QUEUE_DEPTH
    gc.collect()        # drop earlier tests' queues from the weak board
    qa = AdmissionQueue(64, weights=WEIGHTS)
    qb = AdmissionQueue(64, weights=WEIGHTS)
    qa.put(_item("interactive"))
    qb.put(_item("batch"))
    qb.put(_item("batch"))
    assert SERVE_QUEUE_DEPTH.value() == 3
    assert SERVE_QOS_QUEUE_DEPTH.value(qos="interactive") == 1
    assert SERVE_QOS_QUEUE_DEPTH.value(qos="batch") == 2
    qa.drain()
    qb.drain()
    assert SERVE_QUEUE_DEPTH.value() == 0


# ---------------------------------------------------------------------------
# class resolution + tenants (pure host)
# ---------------------------------------------------------------------------


def test_resolve_class_default_override_clamp():
    assert resolve_class("batch") == "batch"
    assert resolve_class("batch", header="interactive") == "interactive"
    assert resolve_class("interactive", body_value="batch") == "batch"
    # header wins over body
    assert resolve_class("batch", header="standard",
                         body_value="interactive") == "standard"
    # tenant ceiling clamps upward requests, never downward ones
    assert resolve_class("batch", header="interactive",
                         max_class="standard") == "standard"
    assert resolve_class("batch", max_class="standard") == "batch"
    with pytest.raises(ValueError):
        resolve_class("interactive", header="premium")


def test_tenant_bucket_refill_and_inflight():
    clock = [0.0]
    tr = TenantRegistry("acme:rps=2,burst=2,inflight=8;free:inflight=1",
                        clock=lambda: clock[0])
    rel = [tr.acquire("acme"), tr.acquire("acme")]      # burst of 2
    with pytest.raises(TenantQuotaExceeded) as ei:
        tr.acquire("acme")
    assert ei.value.reason == "rate"
    assert ei.value.retry_after_s >= 1
    assert ei.value.body()["type"] == "tenant_quota"
    clock[0] += 0.5                                     # refills 1 token
    rel.append(tr.acquire("acme"))
    for r in rel:
        r()
    # inflight cap, released on terminal
    r1 = tr.acquire("free")
    with pytest.raises(TenantQuotaExceeded) as ei:
        tr.acquire("free")
    assert ei.value.reason == "inflight"
    r1()
    r1()                                                # idempotent
    tr.acquire("free")()
    # default-open: unknown tenants and anonymous requests are unlimited
    for _ in range(50):
        tr.acquire("someone-else")
        tr.acquire(None)


def test_tenant_max_class_and_wildcard():
    tr = TenantRegistry("acme:max_class=standard;*:max_class=batch")
    assert tr.max_class("acme") == "standard"
    assert tr.max_class("anyone") == "batch"            # wildcard
    assert TenantRegistry("").max_class("anyone") is None


# ---------------------------------------------------------------------------
# QoS-aware victim choice (policy unit)
# ---------------------------------------------------------------------------


def test_choose_victim_lowest_class_first_lifo_within():
    def req(qos, t):
        return SimpleNamespace(qos=qos, t_enqueue=t)
    cands = [(0, req("interactive", 3.0)),   # newest overall
             (1, req("batch", 1.0)),
             (2, req("batch", 2.0)),
             (3, req("standard", 4.0))]
    # batch first even though interactive/standard are newer; LIFO
    # within batch picks slot 2
    assert choose_victim(cands)[0] == 2
    # exclude the preferred victim: the other batch slot goes
    assert choose_victim(cands, exclude=2)[0] == 1
    # no batch left: standard before interactive
    assert choose_victim([c for c in cands if c[1].qos != "batch"])[0] == 3
    # single class degrades to the pre-QoS LIFO rule
    only_i = [(0, req("interactive", 1.0)), (1, req("interactive", 9.0))]
    assert choose_victim(only_i)[0] == 1
    # foreign objects without .qos rank as interactive (never
    # preferentially evicted)
    mixed = [(0, SimpleNamespace(t_enqueue=9.0)), (1, req("batch", 1.0))]
    assert choose_victim(mixed)[0] == 1


# ---------------------------------------------------------------------------
# job executor: weighted lanes, checkpoint cancel, drain semantics
# ---------------------------------------------------------------------------


def test_job_executor_runs_and_reports():
    ex = JobExecutor(workers=1, max_queue=8)
    try:
        job = ex.submit(GenerationJob("image", lambda j: 42, qos="batch"))
        assert job.wait(10)
        assert job.result["value"] == 42
        from cake_tpu.obs import TIMELINES
        tl = TIMELINES.get(job.id)
        kinds = [e["kind"] for e in tl["events"]]
        assert kinds == ["enqueue", "admit", "finish"]
        assert all(e.get("qos") == "batch" for e in tl["events"])
    finally:
        ex.close()


def test_job_checkpoint_cancellation():
    ex = JobExecutor(workers=1, max_queue=8)
    started = threading.Event()

    def fn(job):
        started.set()
        for _ in range(2000):
            job.checkpoint()
            time.sleep(0.005)
        return "finished"
    try:
        job = ex.submit(GenerationJob("image", fn))
        assert started.wait(10)
        job.cancel()
        assert job.wait(10)
        assert isinstance(job.result["error"], JobCancelled)
    finally:
        ex.close()


def test_drain_refuses_new_batch_jobs_finishes_running():
    """The acceptance-criteria drain contract: a running batch job
    finishes across the drain; a NEW batch job is refused typed."""
    ex = JobExecutor(workers=1, max_queue=8)
    release = threading.Event()
    started = threading.Event()

    def fn(job):
        started.set()
        assert release.wait(10)
        return "done"
    try:
        running = ex.submit(GenerationJob("image", fn, qos="batch"))
        assert started.wait(10)
        ex.begin_drain()
        with pytest.raises(JobsDraining) as ei:
            ex.submit(GenerationJob("image", lambda j: 1, qos="batch"))
        assert ei.value.retry_after_s >= 1
        release.set()
        assert ex.drain(10), "running job did not finish under drain"
        assert running.result["value"] == "done"
    finally:
        ex.close()


# ---------------------------------------------------------------------------
# engine e2e: batch preempted under interactive pressure, bit-identical
# resume (swap + recompute) — the acceptance-criteria parity pin
# ---------------------------------------------------------------------------

def _model():
    # SHARE test_paged's module-level model (same CTX/CHUNK/BT/BLOCKS
    # shapes, same process, test_paged runs first alphabetically): the
    # paged decode/prefill executables compile once for both files —
    # a second TextModel instance here cost the tier-1 budget ~40s of
    # duplicate XLA compiles
    from tests.test_paged import _model as paged_model
    return paged_model()


def _engine(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("ctx_len", CTX)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("kv_blocks", BLOCKS)
    kw.setdefault("kv_block_tokens", BT)
    kw.setdefault("prefix_cache_mb", 0)
    return ServeEngine(_model(), **kw)


def _ref(prompt, n):
    toks, _ = _model().generate(list(prompt), max_new_tokens=n,
                                sampling=GREEDY)
    return toks


P_BATCH = [3, 17, 42, 99, 7]
# 78-token prompt → 10 of the 12 pool blocks for the prefill alone, so
# admitting it while the batch slot holds blocks deterministically
# exhausts the pool mid-prefill (choose_victim runs with the batch slot
# as the decoding candidate)
P_INTER = [5 + (i * 11) % 180 for i in range(78)]


# swap mode stays tier-1; recompute rides tier-2 (slow) — the suite sits
# near the 870s cap on this 1-core box and the two modes share every
# code path except the resume mechanism, which test_paged's own
# exhaustion parity already pins for recompute
@pytest.mark.parametrize("mode", [
    "swap",
    pytest.param("recompute", marks=pytest.mark.slow),
])
def test_qos_preempt_batch_slot_resumes_bit_identical(mode):
    """A decoding BATCH request is preempted when an interactive
    admission's prefill exhausts the 96-token pool (the batch slot is
    the policy victim), parks, resumes after the interactive request
    finishes, and completes bit-identical to the sequential path — for
    swap (exact bytes) and recompute (replay). The interactive request
    is never preempted."""
    from cake_tpu.obs import SERVE_PREEMPTIONS, TIMELINES
    ref_b = _ref(P_BATCH, 28)
    ref_i = _ref(P_INTER, 6)
    before = SERVE_PREEMPTIONS.value(mode=mode)
    eng = _engine(preempt_mode=mode)
    try:
        rb = eng.submit(P_BATCH, max_new_tokens=28, sampling=GREEDY,
                        qos="batch", tenant="acme")
        deadline = time.monotonic() + 60
        while len(rb.tokens) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert rb.tokens, "batch request never started decoding"
        ri = eng.submit(P_INTER, max_new_tokens=6, sampling=GREEDY,
                        qos="interactive")
        assert ri.wait(300) and rb.wait(300)
        assert "error" not in ri.result, ri.result.get("error")
        assert "error" not in rb.result, rb.result.get("error")
        assert ri.result["tokens"] == ref_i
        assert rb.result["tokens"] == ref_b
        assert SERVE_PREEMPTIONS.value(mode=mode) > before, \
            "pool never exhausted — QoS preemption untested"
        kinds_b = [e["kind"] for e in TIMELINES.get(rb.id)["events"]]
        kinds_i = [e["kind"] for e in TIMELINES.get(ri.id)["events"]]
        assert "preempt" in kinds_b, "batch slot was not the victim"
        assert "preempt" not in kinds_i, "interactive request preempted"
        # class + tenant attrs ride the timeline (enqueue + finish)
        ev_b = TIMELINES.get(rb.id)["events"]
        assert any(e.get("qos") == "batch" and e.get("tenant") == "acme"
                   for e in ev_b if e["kind"] == "enqueue")
    finally:
        eng.close()


def test_engine_qos_slo_instruments_labeled():
    """The per-class SLO histograms observe engine terminals with the
    request's class label."""
    from cake_tpu.obs import SERVE_QOS_E2E_SECONDS, SERVE_QOS_TTFT_SECONDS
    b_e2e = SERVE_QOS_E2E_SECONDS.count(qos="standard", outcome="ok")
    b_ttft = SERVE_QOS_TTFT_SECONDS.count(qos="standard", outcome="ok")
    eng = _engine()
    try:
        r = eng.submit(P_BATCH, max_new_tokens=4, sampling=GREEDY,
                       qos="standard")
        assert r.wait(120)
        assert "error" not in r.result
    finally:
        eng.close()
    assert SERVE_QOS_E2E_SECONDS.count(qos="standard", outcome="ok") \
        > b_e2e
    assert SERVE_QOS_TTFT_SECONDS.count(qos="standard", outcome="ok") \
        > b_ttft


# ---------------------------------------------------------------------------
# API integration: tenant 429 body, image job timeline, size clamp
# ---------------------------------------------------------------------------


def _api_state():
    from tests.test_api import (MockAudioModel, MockImageModel,
                                MockTextModel, MockTokenizer)
    from cake_tpu.api import ApiState
    return ApiState(model=MockTextModel(), tokenizer=MockTokenizer(),
                    model_id="mock-model", image_model=MockImageModel(),
                    audio_model=MockAudioModel())


def _with_client(state, fn):
    from tests.test_api import with_client
    with_client(state, fn)


def test_api_image_job_traced_end_to_end():
    """An image request adopts X-Cake-Request-Id, echoes it, and its
    enqueue→admit→finish lifecycle is retrievable from
    GET /api/v1/requests/<id> with class + workload attrs."""
    state = _api_state()

    async def scenario(client):
        rid = "trace-img-e2e-1"
        r = await client.post("/v1/images/generations",
                              json={"prompt": "a cake", "size": "32x32"},
                              headers={"X-Cake-Request-Id": rid})
        assert r.status == 200
        assert r.headers["X-Cake-Request-Id"] == rid
        t = await client.get(f"/api/v1/requests/{rid}")
        assert t.status == 200
        tl = await t.json()
        kinds = [e["kind"] for e in tl["events"]]
        assert kinds[:2] == ["received", "enqueue"]
        assert "admit" in kinds and "finish" in kinds
        admit = next(e for e in tl["events"] if e["kind"] == "admit")
        assert admit["qos"] == "batch" and admit["workload"] == "image"
    _with_client(state, scenario)


def test_api_image_qos_override_and_invalid():
    state = _api_state()

    async def scenario(client):
        r = await client.post("/v1/images/generations",
                              json={"prompt": "x", "size": "16x16",
                                    "qos": "interactive"},
                              headers={"X-Cake-Request-Id": "img-q1"})
        assert r.status == 200
        t = await (await client.get("/api/v1/requests/img-q1")).json()
        admit = next(e for e in t["events"] if e["kind"] == "admit")
        assert admit["qos"] == "interactive"
        r = await client.post("/v1/images/generations",
                              json={"prompt": "x", "size": "16x16"},
                              headers={"X-Cake-QoS": "premium"})
        assert r.status == 400
    _with_client(state, scenario)


def test_api_image_size_clamped():
    state = _api_state()

    async def scenario(client):
        for size in ("999999x64", "64x999999", "0x64", "-2x32", "axb"):
            r = await client.post("/v1/images/generations",
                                  json={"prompt": "x", "size": size})
            assert r.status == 400, size
        # the knob widens/narrows the clamp
        import os
        os.environ["CAKE_IMAGE_MAX_SIZE"] = "64"
        try:
            r = await client.post("/v1/images/generations",
                                  json={"prompt": "x", "size": "65x32"})
            assert r.status == 400
            r = await client.post("/v1/images/generations",
                                  json={"prompt": "x", "size": "64x32"})
            assert r.status == 200
        finally:
            del os.environ["CAKE_IMAGE_MAX_SIZE"]
    _with_client(state, scenario)


def test_api_tenant_quota_429_all_endpoints(monkeypatch):
    """An over-quota tenant is answered the typed 429 tenant_quota body
    on chat, images AND audio — before any queue slot is consumed."""
    monkeypatch.setenv("CAKE_QOS_TENANTS", "acme:rps=1000,inflight=1")
    state = _api_state()
    # hold the tenant's single inflight slot via a stuck image job
    from cake_tpu.serve.admission import get_plane
    plane = get_plane(state)
    release = plane.admit("acme")

    async def scenario(client):
        hdrs = {"X-Cake-Tenant": "acme"}
        for path, body in (
                ("/v1/chat/completions",
                 {"messages": [{"role": "user", "content": "hi"}]}),
                ("/v1/images/generations",
                 {"prompt": "x", "size": "16x16"}),
                ("/v1/audio/speech", {"input": "hello"})):
            r = await client.post(path, json=body, headers=hdrs)
            assert r.status == 429, path
            data = await r.json()
            assert data["type"] == "tenant_quota"
            assert data["tenant"] == "acme"
            assert int(r.headers["Retry-After"]) >= 1
        # anonymous requests are untouched (default-open)
        r = await client.post("/v1/images/generations",
                              json={"prompt": "x", "size": "16x16"})
        assert r.status == 200
    try:
        _with_client(state, scenario)
    finally:
        release()


def test_api_audio_traced_and_draining(monkeypatch):
    state = _api_state()

    async def scenario(client):
        r = await client.post("/v1/audio/speech",
                              json={"input": "hello"},
                              headers={"X-Cake-Request-Id": "tts-1"})
        assert r.status == 200
        assert r.headers["X-Cake-Request-Id"] == "tts-1"
        t = await (await client.get("/api/v1/requests/tts-1")).json()
        admit = next(e for e in t["events"] if e["kind"] == "admit")
        assert admit["workload"] == "audio"
        # drain: new image/audio work refused typed while state drains
        state.draining = True
        r = await client.post("/v1/audio/speech", json={"input": "x"})
        assert r.status == 503
        r = await client.post("/v1/images/generations",
                              json={"prompt": "x", "size": "16x16"})
        assert r.status == 503
    _with_client(state, scenario)


def test_depth_publish_is_reentrant_under_gc():
    """A dead queue's finalizer is _publish, and the collector can run it
    on the thread that already holds the board lock inside _publish (the
    locked WeakSet copy allocates). That must not deadlock: the engine's
    scheduler thread and every submitter go through this lock."""
    from cake_tpu.serve.admission import queue as qmod
    done = threading.Event()

    def nested():
        with qmod._BOARD_LOCK:          # where the collector interrupts
            qmod._publish()             # what the finalizer calls
        done.set()

    t = threading.Thread(target=nested, daemon=True)
    t.start()
    assert done.wait(10), "_publish deadlocked against its own lock"
