"""Observability endpoints: Prometheus /metrics + /health.

/metrics renders the process-global registry (text exposition 0.0.4):
serving histograms fed by the model layer, per-hop cluster timing fed by
the master's RemoteStage clients, and HTTP request counters fed by the
server middleware.

/health reports what the reference's topology endpoint cannot: per-worker
last-seen age (from each RemoteStage's monotonic last_ok, refreshed by
every successful forward) and local accelerator memory from
jax.Device.memory_stats() — so "is the cluster alive and how full is HBM"
is one unauthenticated-scrape-shaped GET instead of a generation attempt.
"""
from __future__ import annotations

import time

from aiohttp import web

from ..obs import (RECORDER, REGISTRY, SERVE_E2E_SECONDS,
                   SERVE_ITL_SECONDS, SERVE_TTFT_SECONDS, TIMELINES, now)
from .state import ApiState

# a worker is reported degraded when forwards keep being ATTEMPTED without
# a success for longer than this — recency of traffic alone never degrades
# health (an idle cluster is healthy; a liveness probe must not restart a
# server just because no one is generating)
STALE_WORKER_S = 120.0

# serve engine with pending work but no completed scheduler iteration for
# this long reports wedged (must exceed any single in-iteration XLA
# compile — the first decode (of each slot-count bucket on a paged pool)
# and each prefill chunk bucket compiles in-line; chunked admission means
# a long prompt is otherwise spread over MANY short iterations, so a quiet
# scheduler really is stuck, not just prefilling). The engine block also
# surfaces `prefilling` (in-flight chunked admissions) and `prefix_cache`
# occupancy (blocks/bytes/hits/misses/evictions) straight from
# engine.health()
ENGINE_WEDGED_S = 120.0

PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# process-start anchor on the MONOTONIC clock: /health's
# started_at_age_s counts from here. A router watching the age move
# BACKWARD knows a NEW process answers behind the same URL (wall-clock
# uptime_s can't say that — NTP steps it), and resets that replica's
# warm-up clock (fleet/registry.py, CAKE_SCALE_WARMUP_S).
_STARTED_AT = now()


async def metrics(request: web.Request) -> web.Response:
    return web.Response(body=REGISTRY.render().encode(),
                        headers={"Content-Type": PROM_CONTENT_TYPE})


def _device_health() -> dict:
    """Local accelerator snapshot as JAX reports it: platform, device kind
    and device count, the first device's memory, and (on more than one
    device) each device's memory — so "is the model spread over the chips"
    is read from the server that holds them. Memory keys are absent where
    the platform exposes no stats (CPU). A broken backend raises: /health
    answering 500 is the honest report, an empty block is not."""
    import jax
    devs = jax.local_devices()
    d = devs[0]
    out = {"platform": d.platform, "device": str(d),
           "device_kind": d.device_kind, "count": len(jax.devices())}
    keys = ("bytes_in_use", "bytes_limit", "peak_bytes_in_use")
    mem = d.memory_stats() or {}
    out.update({k: int(mem[k]) for k in keys if k in mem})
    if mem.get("bytes_limit"):
        out["hbm_used_frac"] = round(
            mem.get("bytes_in_use", 0) / mem["bytes_limit"], 4)
    if len(devs) > 1:
        out["devices"] = [
            {"id": x.id, **{k: int(m[k]) for k in keys if k in m}}
            for x in devs for m in [x.memory_stats() or {}]]
    return out


def worker_health(model) -> list[dict]:
    """Per-remote-stage liveness from the master's client channels. A
    worker is `failing` when forwards are being attempted without success:
    the newest attempt is > STALE_WORKER_S past the newest success, an old
    attempt is still unanswered (wedged mid-forward: last_attempt frozen
    ahead of last_ok), or attempts exist and none has ever succeeded. Mere
    idleness (success as recent as the last attempt, or a never-used
    channel) is healthy."""
    out = []
    t = now()
    for s in getattr(model, "stages", None) or []:
        if s.kind != "remote":
            continue
        last_ok = getattr(s.runner, "last_ok", None)
        last_attempt = getattr(s.runner, "last_attempt", None)
        if last_attempt is None:
            failing = False                    # channel never exercised
        elif last_ok is None:
            failing = True                     # tried, never succeeded
        else:
            pending = last_attempt > last_ok   # newest forward unanswered
            failing = (last_attempt - last_ok > STALE_WORKER_S
                       or (pending and t - last_attempt > STALE_WORKER_S))
        entry = {
            "name": getattr(s.runner, "name", "?"),
            "layers": [s.start, s.end],
            "last_ok_age_s": None if last_ok is None
            else round(t - last_ok, 3),
            "failing": failing,
            "ops": getattr(s.runner, "total_ops", 0),
        }
        # gray failure: slow-but-alive — ops succeed but the rolling RTT
        # p95 sits above CAKE_HOP_DEGRADED_MS. Surfaced BEFORE the per-op
        # deadline turns the slowness into a hard failure; never a 503 on
        # its own (a slow cluster still serves)
        if getattr(s.runner, "degraded_ms", 0) > 0:
            entry["degraded"] = bool(getattr(s.runner, "gray_degraded",
                                             False))
            entry["rtt_p95_ms"] = s.runner.rtt_p95_ms()
        out.append(entry)
    return out


async def trace(request: web.Request) -> web.Response:
    """Chrome-trace JSON of the span ring buffer (open in Perfetto).
    ?clear=1 drains the buffer after the snapshot. 409 while the recorder
    is disabled (enable via CAKE_TRACE_DIR or programmatically)."""
    if not RECORDER.enabled:
        return web.json_response(
            {"error": "span recorder disabled (set CAKE_TRACE_DIR)"},
            status=409)
    body = RECORDER.to_chrome_trace()
    if request.query.get("clear") in ("1", "true"):
        RECORDER.clear()
    return web.json_response(body)


async def request_index(request: web.Request) -> web.Response:
    """Recent request ids with retrievable timelines (oldest first;
    the ring keeps the last CAKE_TRACE_REQUESTS requests)."""
    return web.json_response({"requests": TIMELINES.ids()})


async def request_timeline(request: web.Request) -> web.Response:
    """One request's typed lifecycle timeline (by trace id or completion
    id). `?format=perfetto` returns the same events as Chrome-trace
    instant events on the span recorder's clock, mergeable with
    /api/v1/trace in Perfetto."""
    rid = request.match_info["rid"]
    if request.query.get("format") == "perfetto":
        body = TIMELINES.to_chrome(rid)
    else:
        body = TIMELINES.get(rid)
    if body is None:
        return web.json_response(
            {"error": f"no timeline for request {rid!r} (evicted from "
                      "the ring, or never traced by this process)"},
            status=404)
    return web.json_response(body)


async def slo(request: web.Request) -> web.Response:
    """Serve-engine SLO decomposition as JSON: the TTFT / inter-token /
    e2e histograms by outcome, each bucket carrying its sampled exemplar
    request id — the link from a bad percentile to the concrete
    /api/v1/requests/<id> timeline that explains it."""
    out = {}
    for h in (SERVE_TTFT_SECONDS, SERVE_ITL_SECONDS, SERVE_E2E_SECONDS):
        series = []
        for labels in h.labelsets():
            n = h.count(**labels)
            series.append({
                "labels": labels,
                "count": n,
                "sum_s": round(h.sum(**labels), 6),
                "mean_s": round(h.sum(**labels) / n, 6) if n else 0.0,
                "exemplars": h.exemplars(**labels),
            })
        out[h.name] = {"help": h.help, "series": series}
    return web.json_response(out)


async def flight(request: web.Request) -> web.Response:
    """Flight-recorder-on-demand: the serve engine's scheduler-iteration
    ring as JSON, WITHOUT waiting for a wedge/DOWN dump — a read-only
    snapshot (the recorder's own lock, no scheduler pause) so `cake top`
    and the profiling workflow can inspect a live engine. 409 when no
    engine (or no recorder) is attached to this process."""
    state: ApiState = request.app["state"]
    engine = getattr(state, "engine", None)
    recorder = getattr(engine, "flight", None) if engine is not None \
        else None
    if recorder is None:
        return web.json_response(
            {"error": "no serve engine (or flight recorder) in this "
                      "process — flight records scheduler iterations"},
            status=409)
    iterations = recorder.snapshot()
    n = request.query.get("n")
    if n is not None:
        try:
            iterations = iterations[-max(int(n), 0):]
        except ValueError:
            pass
    return web.json_response({
        "capacity": recorder.capacity,
        "static": recorder.static_view(),
        # the iterations that stood still, kept beside the ring (as in
        # /health's engine block)
        "stalls": recorder.stalls(),
        "count": len(iterations),
        "iterations": iterations,
    })


async def health(request: web.Request) -> web.Response:
    state: ApiState = request.app["state"]
    workers = worker_health(state.model)
    stale = [w["name"] for w in workers if w["failing"]]
    degraded = bool(stale)
    body = {
        "uptime_s": max(int(time.time()) - state.created, 0),
        "started_at_age_s": round(now() - _STARTED_AT, 3),
        "models": [m["id"] + ":" + m["kind"] for m in state.owned_models()],
        "workers": workers,
        "stale_workers": stale,
        # gray failures: flagged, never 503 — a slow cluster still serves,
        # and a liveness probe must not restart it for being slow
        "degraded_workers": [w["name"] for w in workers
                             if w.get("degraded")],
        "device": _device_health(),
    }
    if getattr(state, "draining", False):
        body["draining"] = True
    # hard cluster degradation: a worker is quarantined with the recovery
    # retry budget exhausted — requests fail fast (ClusterDegradedError),
    # so the balancer should route elsewhere until the restore loop
    # revives the worker. This one IS a 503.
    # locked accessor where the model provides one (DistributedTextModel:
    # the flag is guarded-by _degraded_lock and the lint only polices the
    # declaring class, so out-of-class readers must use the accessor)
    getter = getattr(state.model, "degraded_info", None)
    dead = getter() if getter is not None \
        else getattr(state.model, "degraded", None)
    if dead:
        degraded = True
        body["cluster"] = {
            "degraded": True,
            "worker": dead["worker"],
            "down_for_s": round(now() - dead["since"], 1),
            "error": dead["error"],
        }
    engine = getattr(state, "engine", None)
    if engine is not None:
        # continuous-batching engine liveness: a dead scheduler thread, or
        # one that has work (busy slots / queued requests) but hasn't
        # iterated recently, means chat requests will hang — degrade.
        # The threshold sits far above a per-bucket XLA compile (a first
        # batched-decode compile happens IN-iteration, and a liveness
        # probe must not restart a server that is merely warming up).
        einfo = engine.health()
        busy = einfo["slots_busy"] or einfo["queue_depth"]
        # wedged = the engine's own watchdog flag (a dispatch stuck past
        # CAKE_STEP_WATCHDOG_S) OR the coarse fallback here for engines
        # running without a watchdog
        einfo["wedged"] = bool(einfo.get("wedged")) or bool(
            busy and einfo["last_step_age_s"] > ENGINE_WEDGED_S)
        # down = the supervisor's rebuild budget is exhausted: submits
        # answer 503 + Retry-After and the restore loop is probing, so
        # the balancer should route elsewhere until `down` clears. The
        # block carries down_for_s + last_failure for the operator.
        if not einfo["alive"] or einfo["wedged"] or einfo.get("down"):
            degraded = True
        body["engine"] = einfo
    plane = getattr(state, "plane", None)
    if plane is not None:
        # unified admission plane: heavy-job executor occupancy +
        # per-class queue depths (jobs + chat share the class gauges;
        # this block is the per-process view a fleet router probes)
        body["admission"] = plane.health()
    body["status"] = "degraded" if degraded else "ok"
    return web.json_response(body, status=503 if degraded else 200)
