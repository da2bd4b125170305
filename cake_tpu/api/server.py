"""HTTP server assembly: aiohttp app with the OpenAI-compatible + legacy
route set (ref: cake-core/src/cake/sharding/api/mod.rs:66-117).

Routes:
  POST /v1/chat/completions     chat (JSON + SSE)
  GET  /v1/models               model list
  POST /v1/images/generations   image gen (b64_json)
  POST /api/v1/image            image gen (raw png, legacy)
  POST /v1/audio/speech         TTS (wav/pcm)
  GET  /api/v1/topology         cluster topology JSON
  GET  /api/v1/layers           per-layer tensor detail (static, fetch once)
  GET  /api/v1/stats            last generation's timing snapshot
  GET  /metrics                 Prometheus text exposition
  GET  /health                  liveness: workers' last-seen age, HBM usage
  GET  /api/v1/trace            Chrome-trace JSON of recorded spans
  GET  /api/v1/requests         recent traced request ids
  GET  /api/v1/requests/{rid}   one request's lifecycle timeline
                                (?format=perfetto for Chrome-trace)
  GET  /api/v1/slo              TTFT/ITL/e2e histograms + exemplar ids
  GET  /api/v1/flight           flight recorder ring on demand (?n=K)
  GET  /                        embedded web UI
"""
from __future__ import annotations

import asyncio
import base64
import logging
import time

from aiohttp import web

from .. import knobs
from ..obs import (API_REQUESTS, API_REQUEST_SECONDS, PROCESS, LoopTick,
                   now)
from . import audio as audio_routes
from . import images as image_routes
from . import obs_routes
from . import text as text_routes
from . import ui as ui_routes
from .state import ApiState

log = logging.getLogger("cake_tpu.api")


@web.middleware
async def metrics_middleware(request, handler):
    """Per-request counters/latency for every route. The endpoint label is
    the matched route's canonical pattern (bounded cardinality — arbitrary
    404 paths all land on "unmatched")."""
    t0 = now()
    status = 500
    try:
        resp = await handler(request)
        status = resp.status
        return resp
    except web.HTTPException as e:
        status = e.status
        raise
    finally:
        resource = getattr(request.match_info.route, "resource", None)
        endpoint = getattr(resource, "canonical", None) or "unmatched"
        API_REQUESTS.inc(endpoint=endpoint, status=str(status))
        API_REQUEST_SECONDS.observe(now() - t0, endpoint=endpoint)


@web.middleware
async def basic_auth_middleware(request, handler):
    """Optional HTTP basic auth (ref: api/ui.rs basic-auth option)."""
    creds = request.app.get("basic_auth")
    if creds:
        hdr = request.headers.get("Authorization", "")
        ok = False
        if hdr.startswith("Basic "):
            try:
                import hmac
                user_pass = base64.b64decode(hdr[6:]).decode()
                # constant-time compare, same as the cluster handshake
                # (ref: constant_time_eq in auth.rs)
                ok = hmac.compare_digest(user_pass.encode(), creds.encode())
            except Exception:
                ok = False
        if not ok:
            return web.Response(
                status=401, headers={"WWW-Authenticate": 'Basic realm="cake"'})
    return await handler(request)


async def _loop_tick(app: web.Application):
    """The serving loop's own lag, always on (obs/process.py): a 50 ms tick
    from the app's start to its cleanup — `cake_api_loop_lag_seconds`, the
    engine block's `loop_lag_ms`, what a stall record reads."""
    tick = LoopTick(asyncio.get_running_loop(), PROCESS)
    tick.start()
    yield
    tick.stop()


def create_app(state: ApiState, basic_auth: str | None = None) -> web.Application:
    app = web.Application(middlewares=[metrics_middleware,
                                       basic_auth_middleware],
                          client_max_size=64 * 1024 * 1024)
    state.created = int(time.time())
    app["state"] = state
    if basic_auth:
        app["basic_auth"] = basic_auth
    app.router.add_post("/v1/chat/completions", text_routes.chat_completions)
    app.router.add_get("/v1/models", text_routes.list_models)
    app.router.add_post("/v1/images/generations",
                        image_routes.images_generations)
    app.router.add_post("/api/v1/image", image_routes.images_generations)
    app.router.add_post("/v1/audio/speech", audio_routes.audio_speech)
    app.router.add_get("/api/v1/topology", ui_routes.topology)
    app.router.add_get("/api/v1/layers", ui_routes.layers)
    app.router.add_get("/api/v1/stats", ui_routes.stats)
    app.router.add_get("/metrics", obs_routes.metrics)
    app.router.add_get("/health", obs_routes.health)
    app.router.add_get("/api/v1/trace", obs_routes.trace)
    app.router.add_get("/api/v1/requests", obs_routes.request_index)
    app.router.add_get("/api/v1/requests/{rid}",
                       obs_routes.request_timeline)
    app.router.add_get("/api/v1/slo", obs_routes.slo)
    app.router.add_get("/api/v1/flight", obs_routes.flight)
    app.router.add_get("/", ui_routes.index)
    app.cleanup_ctx.append(_loop_tick)
    # fleet-shared KV tier (CAKE_KVSHARE): blob export/import routes +
    # the per-engine agent. Gated on a paged pool + prefix cache — the
    # contiguous pool has no block plane to share
    engine = state.engine
    if knobs.get("CAKE_KVSHARE") and engine is not None \
            and getattr(engine, "paged", None) is not None \
            and getattr(engine, "prefix_cache", None) is not None:
        from ..fleet.kvshare import KVShareReplica
        state.kvshare = KVShareReplica(engine)
        engine.kv_share = state.kvshare
    from . import kv_routes
    app.router.add_get("/api/v1/kv/prefix/{chain}", kv_routes.kv_prefix_get)
    app.router.add_post("/api/v1/kv/prefix/{chain}", kv_routes.kv_prefix_put)
    app.router.add_get("/api/v1/kv/stream/{rid}", kv_routes.kv_stream_get)
    app.router.add_post("/api/v1/kv/stream/{rid}", kv_routes.kv_stream_put)
    return app


async def graceful_drain(app: web.Application):
    """SIGTERM/SIGINT drain (runs as aiohttp's on_shutdown, i.e. after the
    listener stopped accepting but while in-flight handlers still run):
    stop admission — new chat requests on kept-alive connections answer
    503 + Retry-After — let active slots finish up to CAKE_DRAIN_TIMEOUT_S,
    then close the engine so whatever is left gets its final chunks
    instead of a severed socket."""
    state = app["state"]
    state.draining = True
    # the admission plane's job lanes drain with the engine: NEW image/
    # audio jobs answer typed 503s from this instant, queued + running
    # jobs finish inside the same CAKE_DRAIN_TIMEOUT_S budget below
    plane = getattr(state, "plane", None)
    if plane is not None:
        plane.begin_drain()
    engine = getattr(state, "engine", None)
    if engine is None:
        if plane is not None:
            timeout = knobs.get("CAKE_DRAIN_TIMEOUT_S")
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None,
                                       lambda: plane.drain(timeout))
            plane.close()
        return
    # flip the engine's own draining flag BEFORE the blocking drain is
    # handed to an executor thread: /health's engine block must say
    # draining from the first instant, so a fleet router probing it
    # stops routing here without waiting for a request to bounce (the
    # gap used to last until engine.drain() ran inside the executor)
    engine.begin_drain()
    timeout = knobs.get("CAKE_DRAIN_TIMEOUT_S")
    log.info("draining serve engine (up to %.0fs): %d busy, %d queued",
             timeout, engine.pool.busy_count, engine.queue.depth())
    # drain() busy-waits — keep the event loop free to stream the final
    # SSE chunks of exactly the requests being drained
    loop = asyncio.get_running_loop()
    t0 = now()
    clean = await loop.run_in_executor(None, lambda: engine.drain(timeout))
    if not clean:
        log.warning("drain timed out; failing remaining requests")
    engine.close()
    if plane is not None:
        # ONE shared budget: the job lanes get whatever the engine
        # drain left (small floor so a quick engine drain never
        # zero-times the jobs) — CAKE_DRAIN_TIMEOUT_S stays the
        # worst-case total an operator sizes terminationGracePeriod to
        remaining = max(timeout - (now() - t0), 2.0)
        await loop.run_in_executor(None, lambda: plane.drain(remaining))
        plane.close()


def serve(state: ApiState, host: str = "0.0.0.0", port: int = 8000,
          basic_auth: str | None = None):
    """Blocking server entry (ref: `cake serve`)."""
    app = create_app(state, basic_auth)
    # graceful drain on SIGTERM/SIGINT (web.run_app installs the signal
    # handlers; on_shutdown runs after the listener stops accepting).
    # Registered HERE and not in create_app: the server entry owns the
    # engine's lifecycle — an embedding test/app closing its TestClient
    # must not drain an engine it merely borrowed.
    app.on_shutdown.append(graceful_drain)
    log.info("serving API on http://%s:%d", host, port)
    web.run_app(app, host=host, port=port, print=None)
