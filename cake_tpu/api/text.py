"""OpenAI-compatible chat completions: blocking JSON + SSE streaming
(ref: cake-core/src/cake/sharding/api/text.rs:101-230 — usage accounting,
finish_reason, stream chunks).

Two execution paths share the response assembly:
  * engine (state.engine, plain TextModels): requests are submitted to the
    continuous-batching scheduler and decode CONCURRENTLY — a full
    admission queue is a 429 + Retry-After, not an unbounded wait;
  * locked fallback (distributed/offload models): the inherited
    one-inference-at-a-time asyncio.Lock.
"""
from __future__ import annotations

import asyncio
import json
import time
import uuid

from aiohttp import web

from ..obs import (GENERATIONS, RECORDER, TIMELINES, TRACE_HEADER,
                   current_request_id, now, set_request_id)
from ..ops.sampling import SamplingConfig
from ..serve import (EngineDown, EngineDraining, PoisonedRequest,
                     QueueDeadlineExceeded, QueueFull,
                     RequestDeadlineExceeded)
from .state import (ApiState, await_job, run_blocking,
                    run_generation_blocking, run_generation_streamed)


TOP_K_CHOICES = (1, 5, 10, 20, 40, 64, 100, 200)

# continuation handshake with the fleet router (mirrored there by name —
# the router tier stays import-light): a streamed continuation-mode
# response reports how many chars of the partial assistant text this
# replica consumed, so the router's mid-stream resume can strip any
# re-emitted overlap by POSITION instead of guessing from content. This
# implementation always continues the partial verbatim, so it reports
# the full length.
CONTINUATION_CHARS_HEADER = "X-Cake-Continuation-Chars"

# fleet-shared KV tier handshake (fleet/kvshare), mirrored by NAME for
# the same import-light reason as the continuation header above:
#   * X-Cake-KV-Peers   router -> replica: compact directory of warm
#                       peers and their advertised prefix chains
#   * X-Cake-KV-Resume  router -> replica: adopt the staged stream blob
#                       for this request id before falling back to a
#                       plain continuation re-prefill
#   * X-Cake-KV-Resumed replica -> router: this response replays the
#                       stream from token 0 out of an adopted blob —
#                       strip everything the client already received
KV_DIR_HEADER = "X-Cake-KV-Peers"
KV_RESUME_HEADER = "X-Cake-KV-Resume"
KV_RESUMED_HEADER = "X-Cake-KV-Resumed"


def _grid(v: float, step: float, lo: float, hi: float) -> float:
    return round(round(max(lo, min(hi, v)) / step) * step, 2)


def _sampling_from_request(body: dict) -> SamplingConfig:
    """Clamp + quantize client sampling params onto a small grid.

    SamplingConfig is a STATIC jit argument of the decode programs: every
    distinct value combination compiles and permanently caches a new XLA
    executable, so raw client-controlled floats would be an unbounded
    compile-cache DoS. The grid bounds the executable count while staying
    well inside perceptual resolution.
    """
    temp = _grid(float(body.get("temperature", 0.7)), 0.05, 0.0, 2.0)
    top_p = body.get("top_p")
    if top_p is not None:
        top_p = _grid(float(top_p), 0.05, 0.05, 1.0)
        if top_p >= 1.0:
            top_p = None
    top_k = body.get("top_k")
    if top_k is not None:
        top_k = int(top_k)
        if top_k <= 0:
            top_k = None       # llama.cpp/OpenAI convention: 0 = disabled
        else:
            top_k = min(TOP_K_CHOICES, key=lambda c: abs(c - top_k))
    rp = _grid(float(body.get("repetition_penalty",
                              body.get("repeat_penalty", 1.0))),
               0.05, 1.0, 2.0)
    return SamplingConfig(temperature=temp, top_k=top_k, top_p=top_p,
                          repeat_penalty=rp)


def _gen_kwargs(body: dict) -> dict:
    return {
        "max_new_tokens": int(body.get("max_tokens",
                                       body.get("max_completion_tokens", 256))),
        "sampling": _sampling_from_request(body),
    }


MAX_STOPS = 4           # OpenAI caps `stop` at 4 sequences


def _stops_from_request(body: dict) -> list[str]:
    """Validated OpenAI `stop` field: a string or a list of up to 4
    non-empty strings (empty/None = no stop sequences)."""
    stop = body.get("stop")
    if stop is None:
        return []
    if isinstance(stop, str):
        return [stop] if stop else []
    if isinstance(stop, list):
        if len(stop) > MAX_STOPS:
            raise ValueError(f"stop accepts at most {MAX_STOPS} sequences")
        for s in stop:
            if not isinstance(s, str) or not s:
                raise ValueError("stop sequences must be non-empty strings")
        return list(stop)
    raise ValueError("stop must be a string or a list of strings")


def apply_stop(text: str, stops: list[str]) -> tuple[str, bool]:
    """Trim `text` at the EARLIEST occurrence of any stop sequence
    (matched text excluded, OpenAI semantics). Returns (text, matched)."""
    best = -1
    for s in stops:
        i = text.find(s)
        if i >= 0 and (best < 0 or i < best):
            best = i
    return (text[:best], True) if best >= 0 else (text, False)


class StopMatcher:
    """Incremental stop-sequence scanner for token streams.

    feed() returns the text that is SAFE to emit: everything up to (and
    excluding) a completed stop match, holding back the longest suffix
    that could still be the prefix of a match split across token
    boundaries (max stop length - 1 chars). flush() releases the held
    tail when the stream ends without a match — so a client never sees
    any part of a stop sequence, and never loses text to the holdback.
    """

    def __init__(self, stops: list[str]):
        self.stops = [s for s in stops if s]
        self.hold = max((len(s) for s in self.stops), default=1) - 1
        self.buf = ""
        self.stopped = False

    def feed(self, piece: str) -> str:
        if self.stopped or not piece:
            return ""
        self.buf += piece
        trimmed, matched = apply_stop(self.buf, self.stops)
        if matched:
            self.stopped = True
            self.buf = ""
            return trimmed
        if self.hold and len(self.buf) > self.hold:
            safe, self.buf = self.buf[:-self.hold], self.buf[-self.hold:]
            return safe
        if not self.hold:
            safe, self.buf = self.buf, ""
            return safe
        return ""

    def flush(self) -> str:
        tail, self.buf = self.buf, ""
        return "" if self.stopped else tail


def _completion_id() -> str:
    return "chatcmpl-" + uuid.uuid4().hex[:24]


def _adopt_request_id(request: web.Request, cid: str) -> str:
    """Cross-tier trace adoption: when the fleet router (or any client)
    sent an X-Cake-Request-Id header, that id becomes THE request id for
    this generation — the contextvar every span carries, the id the
    serve engine stamps timeline events against, and the key
    /api/v1/requests/<id> answers to — so one id names the request end
    to end (router retry events stitch onto the same timeline the
    engine's admit/decode events land on). Without the header the
    completion id serves as the request id, as before. The completion
    id is always registered as an alias, so either id resolves the
    timeline."""
    rid = request.headers.get(TRACE_HEADER) or cid
    set_request_id(rid)
    TIMELINES.begin(rid)
    TIMELINES.event(rid, "received")
    TIMELINES.alias(cid, rid)
    return rid


def _retry_after(state: ApiState, floor: int = 1) -> int:
    """Derived Retry-After for 503s that used to ship constants: scale
    with the engine's live congestion (queue depth per slot, or the
    restore-probe interval while DOWN) so a router/client backs off
    proportionally — an idle engine invites a near-immediate retry, a
    deep backlog pushes the herd out. Engines expose the derivation as
    retry_after_hint(); engineless (locked-path) servers fall back to
    the restore interval, the knob that bounds how soon a degraded
    cluster can possibly recover."""
    engine = getattr(state, "engine", None)
    if engine is not None:
        try:
            return max(floor, engine.retry_after_hint())
        except Exception:
            pass                    # engine racing shutdown: use floor
    from .. import knobs
    return max(floor, int(knobs.get("CAKE_RESTORE_INTERVAL_S")) + 1)


def _stream_migrated(err: BaseException) -> bool:
    """True when the engine failed this request because its KV state was
    parked for fleet migration (lazy import: the fleet package is only
    reached when kvshare is live enough to have raised it)."""
    try:
        from ..fleet.kvshare import StreamMigrated
    except Exception:
        return False
    return isinstance(err, StreamMigrated)


def _typed_error_response(err: BaseException,
                          state: ApiState | None = None
                          ) -> web.Response | None:
    """Map a typed engine failure onto its documented status — shared by
    the blocking path and the SSE path's pre-commit refusal, so a
    degraded engine answers the SAME way everywhere: 503 + Retry-After
    for retry-elsewhere conditions (queue deadline, engine down), 504
    for a request that outlived its deadline, 500 for a poisoned
    request. None means not a typed engine error (caller decides).
    Retry-After prefers the hint the error carries (computed where the
    failure happened); errors without one derive from live state."""
    if isinstance(err, (QueueDeadlineExceeded, EngineDown)):
        ra = getattr(err, "retry_after_s", None)
        if ra is None:
            ra = _retry_after(state) if state is not None else 5
        return web.json_response(
            {"error": str(err)}, status=503,
            headers={"Retry-After": str(int(ra))})
    if isinstance(err, RequestDeadlineExceeded):
        return web.json_response({"error": str(err)}, status=504)
    if isinstance(err, PoisonedRequest):
        return web.json_response({"error": str(err)}, status=500)
    if _stream_migrated(err):
        # this (non-streamed) request's KV was parked for migration:
        # answer retryable so the router/client re-runs it elsewhere
        return web.json_response(
            {"error": str(err)}, status=503,
            headers={"Retry-After": "1"})
    return None


async def chat_completions(request: web.Request) -> web.StreamResponse:
    state: ApiState = request.app["state"]
    if state.model is None:
        return web.json_response({"error": "no text model loaded"}, status=503)
    if state.draining:
        # graceful shutdown in progress: requests arriving on kept-alive
        # connections are shed so the balancer fails them over while
        # in-flight generations finish their final chunks. Retry-After
        # scales with the engine backlog being drained — an idle drain
        # finishes (and the replacement process starts) almost at once
        return web.json_response(
            {"error": "server draining for shutdown"}, status=503,
            headers={"Retry-After": str(_retry_after(state, floor=2))})
    degraded = getattr(state.model, "degraded", None)
    if degraded:
        # quarantined worker with the recovery retry budget exhausted:
        # fail fast with the SAME 503 on every path — the streaming path
        # would otherwise have committed to a 200 SSE response before
        # generate() could raise, hiding the reroute signal from the
        # balancer (the restore loop clears the flag when the worker
        # comes back). Retry-After = the restore-probe interval: the
        # soonest the flag can possibly clear
        return web.json_response(
            {"error": f"cluster degraded: worker {degraded['worker']} "
                      "down; recovery in progress"},
            status=503,
            headers={"Retry-After": str(_retry_after(state, floor=2))})
    try:
        body = await request.json()
    except Exception:
        return web.json_response({"error": "invalid JSON body"}, status=400)
    messages = body.get("messages")
    if not isinstance(messages, list) or not messages:
        return web.json_response({"error": "messages[] required"}, status=400)
    for m in messages:
        if not isinstance(m, dict) or "role" not in m or "content" not in m:
            return web.json_response(
                {"error": "each message needs role and content"}, status=400)
    # continuation mode: a final assistant message carrying
    # `"continue": true` is a PARTIAL turn — the prompt is templated
    # WITHOUT a second assistant header, the engine prefills
    # prompt + partial content, and generation continues the same
    # message (greedy continuations are bit-identical to the stream
    # that was never broken; sampled ones resume on a fresh rng fold,
    # the documented rebuild-parity exception). The fleet router's
    # transparent mid-stream resume splices through this, and a client
    # holding a typed stream-broken error finishes through it by hand.
    continuation = bool(messages[-1].get("continue"))
    if continuation and messages[-1].get("role") != "assistant":
        return web.json_response(
            {"error": '"continue": true requires the final message to '
                      "be role=assistant (the partial turn being "
                      "continued)"}, status=400)

    try:
        # validate/quantize sampling params BEFORE any streaming response
        # is prepared: a malformed float must be a 400, not a hung SSE
        gen_kwargs = _gen_kwargs(body)
        stops = _stops_from_request(body)
    except (TypeError, ValueError) as e:
        return web.json_response({"error": f"invalid sampling params: {e}"},
                                 status=400)
    # unified admission plane: QoS class (chat defaults interactive;
    # X-Cake-QoS / body "qos" override, tenant ceiling clamp) + tenant
    # token-bucket/inflight quota, charged BEFORE any queue slot. The
    # inflight lease spans the whole handler — streamed responses hold
    # it until their final chunk — released in the finally
    from .qos import resolve_admission
    resolved = resolve_admission(state, request, body, "interactive")
    if isinstance(resolved, web.Response):
        return resolved
    qos, tenant, release = resolved
    try:
        if state.engine is not None:
            return await _chat_engine(request, state, messages, gen_kwargs,
                                      stream=bool(body.get("stream")),
                                      stops=stops, qos=qos, tenant=tenant,
                                      continuation=continuation)
        if body.get("stream"):
            return await _chat_stream(request, state, messages, gen_kwargs,
                                      stops, continuation=continuation)
        return await _chat_blocking(request, state, messages, gen_kwargs,
                                    stops, continuation=continuation)
    finally:
        release()


def _prompt_token_count(state: ApiState, messages) -> int:
    try:
        from ..models.common.text_model import render_chat
        # same fallback as the content decode: a model built with its own
        # tokenizer must yield consistent usage accounting
        tok = state.tokenizer or getattr(state.model, "tokenizer", None)
        enc = tok.encode(render_chat(tok, messages))
        return len(enc.ids if hasattr(enc, "ids") else enc)
    except Exception:
        return 0


def _decode_text(tokenizer, ids: list[int]) -> str:
    """Decode output ids, degrading per-token on failure so one bad id
    (e.g. out-of-range special) drops only itself, matching the streamed
    path's per-token behavior."""
    if tokenizer is None or not ids:
        return ""
    try:
        return tokenizer.decode(ids)
    except Exception:
        parts = []
        for i in ids:
            try:
                parts.append(tokenizer.decode([i]))
            except Exception:
                pass
        return "".join(parts)


def _stats_snapshot(stats: dict, cid: str | None = None) -> dict:
    """JSON-safe snapshot of a generation's stats for /api/v1/stats:
    timings, per-hop RTT wire/fwd split and prefill pipelining info (the
    reference surfaces topology only; the wire/compute attribution is
    what actually localizes a slow cluster). `request_id` is the
    cross-tier trace id (may be router-injected); `completion_id` the
    OpenAI response id — distinct when a router fronted the request, so
    consumers matching on either keep working."""
    out = {"ts": int(time.time())}
    rid = current_request_id()
    if rid:
        out["request_id"] = rid
    if cid:
        out["completion_id"] = cid
    for k in ("ttft_s", "decode_tokens", "decode_s", "tok_per_s",
              "stage_rtts", "prefill", "queue_wait_s", "prefill_chunks",
              "prefix_hit_tokens", "continuation"):
        if k in stats:
            out[k] = stats[k]
    return out


def _completion_json(state: ApiState, cid: str, toks: list[int],
                     stats: dict, n_in: int,
                     stops: list[str] | None = None) -> web.Response:
    """Assemble the blocking chat.completion body — shared by the engine
    and locked paths so usage accounting/finish_reason cannot diverge.
    `stops`: OpenAI stop sequences — the content is trimmed at the
    earliest match and finish_reason becomes "stop" (the engine path also
    cancels generation at the match; the locked path trims here)."""
    n_out = len(toks)
    ended = bool(toks) and state.model.cfg.is_eos(toks[-1])
    finish = "stop" if ended else "length"
    content_ids = toks[:-1] if ended else toks
    tokenizer = state.tokenizer or getattr(state.model, "tokenizer", None)
    text = _decode_text(tokenizer, content_ids)
    if stops:
        text, matched = apply_stop(text, stops)
        if matched:
            finish = "stop"
    return web.json_response({
        "id": cid,
        "object": "chat.completion",
        "created": int(time.time()),
        "model": state.model_id,
        "choices": [{
            "index": 0,
            "message": {"role": "assistant", "content": text},
            "finish_reason": finish,
        }],
        "usage": {
            "prompt_tokens": n_in,
            "completion_tokens": n_out,
            "total_tokens": n_in + n_out,
            "tokens_per_second": round(stats.get("tok_per_s", 0.0), 2),
        },
    })


async def _continuation_ids(state: ApiState, messages):
    """Token ids for a continuation-mode request (final message is the
    partial assistant turn) — the locked fallback paths hand these to
    generate() directly, since chat_generate would re-template with a
    duplicate assistant header."""
    from ..models.common.text_model import continuation_prompt_ids
    tok = state.tokenizer or getattr(state.model, "tokenizer", None)
    return await run_blocking(lambda: continuation_prompt_ids(tok, messages))


async def _chat_blocking(request, state: ApiState, messages, gen_kwargs,
                         stops: list[str] | None = None,
                         continuation: bool = False):
    cid = _completion_id()
    # the request id (router-injected trace id, or the completion id)
    # rides the contextvar: spans recorded during this generation (model
    # phases, cluster hops) carry it, so a trace export is joinable with
    # API logs/responses — and with the fleet router's timeline
    rid = _adopt_request_id(request, cid)
    prompt_in, n_in = messages, None
    if continuation:
        try:
            prompt_in = await _continuation_ids(state, messages)
        except Exception as e:
            return web.json_response(
                {"error": f"chat template failed: {e}"}, status=400)
        n_in = len(prompt_in)
    async with state.lock:                  # one inference at a time
        try:
            toks, stats = await run_generation_blocking(state.model,
                                                        prompt_in,
                                                        gen_kwargs)
            state.last_stats = _stats_snapshot(stats, cid)
        except Exception as e:
            GENERATIONS.inc(kind="text", status="error")
            # lazy import, error path only: the API layer must not drag
            # the whole cluster subpackage (and faults.py's CAKE_FAULT_PLAN
            # env activation) into single-node servers at import time
            from ..cluster.master import ClusterDegradedError
            if isinstance(e, ClusterDegradedError):
                # typed fast-fail: a worker is quarantined with its retry
                # budget spent — 503 (retryable elsewhere), not a 500;
                # Retry-After = the restore-probe interval (the soonest
                # the quarantined worker can revive)
                return web.json_response(
                    {"error": str(e)}, status=503,
                    headers={"Retry-After":
                             str(_retry_after(state, floor=2))})
            return web.json_response({"error": f"generation failed: {e}"},
                                     status=500)
    GENERATIONS.inc(kind="text", status="ok")
    resp = _completion_json(state, cid, toks, stats,
                            n_in if n_in is not None
                            else _prompt_token_count(state, messages), stops)
    resp.headers[TRACE_HEADER] = rid
    return resp


# -- continuous-batching path (state.engine) ---------------------------------


async def _chat_engine(request, state: ApiState, messages, gen_kwargs,
                       stream: bool, stops: list[str] | None = None,
                       qos: str = "interactive",
                       tenant: str | None = None,
                       continuation: bool = False):
    """Submit to the serve engine: concurrent decode, bounded queue."""
    from ..models.common.text_model import (chat_prompt_ids,
                                            continuation_prompt_ids)
    cid = _completion_id()
    rid = _adopt_request_id(request, cid)
    tokenizer = state.tokenizer or getattr(state.model, "tokenizer", None)
    try:
        prompt_ids = await run_blocking(
            lambda: continuation_prompt_ids(tokenizer, messages)
            if continuation else chat_prompt_ids(tokenizer, messages))
    except Exception as e:
        return web.json_response({"error": f"chat template failed: {e}"},
                                 status=400)
    kvs = state.kvshare
    resumed_req = None
    if kvs is not None:
        resume_rid = request.headers.get(KV_RESUME_HEADER)
        if resume_rid:
            # a migrated stream's blob was staged here (POST
            # /api/v1/kv/stream/<rid>): adopt it through the engine's
            # swap-resume path so the sampled sequence continues
            # bit-exactly. None (nothing staged, or the blob does not
            # fit this pool) falls through to the plain continuation
            # admission below — migration failures are never
            # client-visible
            try:
                resumed_req = await run_blocking(
                    lambda: kvs.submit_job(
                        "adopt",
                        {"rid": resume_rid,
                         "sampling": gen_kwargs["sampling"],
                         "qos": qos, "tenant": tenant},
                        kvs.fetch_timeout))
            except Exception:
                resumed_req = None
        else:
            peers = request.headers.get(KV_DIR_HEADER)
            if peers:
                # fetch-before-recompute: pull the longest matching
                # prefix chain a warm peer advertises before prefilling.
                # Best-effort by contract — any failure inside leaves
                # the cache unchanged and the admission below computes
                # honestly
                try:
                    await kvs.fetch_before_prefill(rid, prompt_ids, peers)
                except Exception:
                    pass
    if resumed_req is not None:
        req = resumed_req
    else:
        try:
            req = state.engine.submit(
                prompt_ids, max_new_tokens=gen_kwargs["max_new_tokens"],
                sampling=gen_kwargs["sampling"],
                request_id=rid, qos=qos, tenant=tenant,
                continuation=continuation)
        except QueueFull as e:
            # backpressure is a first-class answer: shed load instead of
            # queueing unboundedly behind a bounded slot pool. The 429
            # is class-aware: Retry-After reflects THIS class's backlog
            # over its weighted-fair service share
            from .qos import admission_refusal
            return admission_refusal(e)
        except EngineDraining as e:
            return web.json_response(
                {"error": str(e)}, status=503,
                headers={"Retry-After": str(e.retry_after_s)})
        except (EngineDown, PoisonedRequest) as e:
            # typed refusals share the terminal-error mapping: 503 +
            # Retry-After for a down engine (the balancer reroutes, the
            # restore loop revives), 500 for a quarantined poison prompt
            return _typed_error_response(e, state)
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        except RuntimeError as e:           # engine dead (legacy path)
            return web.json_response({"error": str(e)}, status=503)
    if stream:
        # never commit to a 200 SSE while the request can still be
        # refused outright: wait for admission (or a terminal failure)
        # first, so a shed request — queue deadline, engine going down,
        # poison quarantine — answers its documented typed status
        # instead of an in-band error chunk no balancer ever sees. A
        # queued-but-unadmitted request has no tokens to stream anyway,
        # so holding the headers back costs nothing.
        try:
            while not (req.admitted.is_set() or req.done.is_set()):
                await asyncio.sleep(0.02)
        except asyncio.CancelledError:
            req.cancel()            # client gone while queued
            raise
        if req.done.is_set() and "error" in req.result:
            resp = _typed_error_response(req.result["error"], state)
            if resp is not None:
                GENERATIONS.inc(kind="text", status="error")
                return resp
            if resumed_req is not None:
                # an adopted stream that died pre-commit (e.g. the pool
                # can never fit its blob) must answer retryable, not an
                # in-band error chunk: the router then continues the
                # stream on the next candidate as a plain continuation
                GENERATIONS.inc(kind="text", status="error")
                return web.json_response(
                    {"error": f"adopted stream failed: "
                              f"{req.result['error']}"},
                    status=503, headers={"Retry-After": "1"})
        resume_text = None
        if resumed_req is not None:
            # replay every already-generated token as one leading chunk,
            # marked by the KV_RESUMED header: per-token emission builds
            # text via _mk_token(tid), so this concatenation is
            # byte-identical to what the source replica streamed — the
            # router strips the client-delivered prefix by POSITION
            toks = list(req.tokens)
            model = state.engine.model
            resume_text = await run_blocking(lambda: "".join(
                model._mk_token(t).text for t in toks))
        aiter, result = state.engine.stream(req)
        return await _sse_drain(request, state, cid, aiter, result,
                                req.cancel, stops,
                                cont_chars=len(str(
                                    messages[-1].get("content") or ""))
                                if continuation else None,
                                resume_text=resume_text)
    if stops:
        # early termination: watch the token stream from the scheduler
        # thread and cancel at the first completed stop match, so a
        # matched request frees its slot instead of decoding to budget
        # (the response text is trimmed in _completion_json either way)
        from ..serve import ServeRequest
        matcher = StopMatcher(stops)

        def _watch(item):
            if item is ServeRequest.DONE or matcher.stopped:
                return
            matcher.feed(getattr(item, "text", None) or "")
            if matcher.stopped:
                req.cancel()
        for backlog_item in req.subscribe(_watch):
            _watch(backlog_item)

    # await completion via the shared done-callback -> future helper
    # (no executor thread parked per in-flight request; a cancelled
    # handler — client gone — cancels the request and frees the slot)
    await await_job(req)
    if "error" in req.result:
        err = req.result["error"]
        GENERATIONS.inc(kind="text", status="error")
        # typed engine failures answer their documented status (503 +
        # Retry-After for retryable-elsewhere, 504 past the request
        # deadline, 500 for poison) — only untyped bugs fall to bare 500
        resp = _typed_error_response(err, state)
        if resp is not None:
            return resp
        return web.json_response(
            {"error": f"generation failed: {err}"}, status=500)
    GENERATIONS.inc(kind="text", status="ok")
    stats = req.result.get("stats", {})
    state.last_stats = _stats_snapshot(stats, cid)
    resp = _completion_json(state, cid, req.result.get("tokens", []), stats,
                            len(prompt_ids), stops)
    resp.headers[TRACE_HEADER] = rid
    return resp


async def _sse_drain(request, state: ApiState, cid: str, aiter, result: dict,
                     cancel, stops: list[str] | None = None,
                     cont_chars: int | None = None,
                     resume_text: str | None = None
                     ) -> web.StreamResponse:
    """Drain a token stream into SSE chunks — shared by the engine and
    locked paths. `cancel` is a thunk that aborts the producer; it fires
    when the client disconnects mid-stream so the generation (and, on the
    engine path, its KV slot) is reclaimed instead of decoding on.
    `stops`: OpenAI stop sequences — matched text is never emitted (a
    StopMatcher holds back potential partial matches across token
    boundaries), the stream finishes with finish_reason="stop", and the
    producer is cancelled at the match. `cont_chars`: continuation mode
    only — chars of the partial assistant turn consumed (reported to the
    router's resume splice via the handshake header)."""
    hdrs = {
        "Content-Type": "text/event-stream",
        "Cache-Control": "no-cache",
        "Connection": "keep-alive",
        # the cross-tier trace id rides the SSE headers too, so a
        # streaming client can pull /api/v1/requests/<id> afterwards
        TRACE_HEADER: current_request_id() or cid,
    }
    if cont_chars is not None:
        hdrs[CONTINUATION_CHARS_HEADER] = str(cont_chars)
    if resume_text is not None:
        # adopted-blob replay: the body repeats the stream from token 0,
        # so the router must strip by cumulative delivered position, not
        # by the continuation splice arithmetic
        hdrs[KV_RESUMED_HEADER] = "1"
    resp = web.StreamResponse(headers=hdrs)
    try:
        return await _sse_drain_inner(request, state, cid, aiter, result,
                                      cancel, resp, stops, resume_text)
    except BaseException:
        # disconnect/cancellation BEFORE the token loop starts would skip
        # the iterator's finalizer (an async generator that was never
        # started runs no finally) — cancel here so an abandoned stream
        # can never leak its generation/slot for the full budget
        cancel()
        raise


async def _sse_drain_inner(request, state: ApiState, cid: str, aiter,
                           result: dict, cancel, resp: web.StreamResponse,
                           stops: list[str] | None = None,
                           resume_text: str | None = None
                           ) -> web.StreamResponse:
    await resp.prepare(request)
    created = int(time.time())
    rid = current_request_id() or cid

    def chunk(delta: dict, finish=None) -> bytes:
        payload = {
            "id": cid, "object": "chat.completion.chunk", "created": created,
            "model": state.model_id,
            "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
        }
        return f"data: {json.dumps(payload)}\n\n".encode()

    await resp.write(chunk({"role": "assistant"}))
    if resume_text:
        # migrated-stream replay (see _sse_drain): the stop matcher (if
        # any) intentionally sees only NEW tokens, same as a plain
        # continuation leg — its holdback state never spans the
        # migration boundary
        await resp.write(chunk({"content": resume_text}))
    finish = "length"
    client_gone = False
    matcher = StopMatcher(stops) if stops else None

    async def write_safe(data: bytes) -> None:
        # a disconnected client must not abort the drain below — note it,
        # stop the producer, and keep consuming to the DONE sentinel so
        # the worker/slot winds down cleanly
        nonlocal client_gone
        if client_gone:
            return
        try:
            await resp.write(data)
        except (ConnectionError, ConnectionResetError):
            client_gone = True
            cancel()

    async def write_token(text: str) -> None:
        await write_safe(chunk({"content": text}))
        # recorder on only: the engine's stream stamped the token when the
        # scheduler handed it to the loop and when the loop handed it over
        # here (`aiter.handoff`; the locked fallback's iterator has none);
        # the span runs from the hand-over until the write returned
        # (json.dumps + aiohttp's write), `wait_us` is the time before it
        # (the GIL and the loop's queue)
        if RECORDER.enabled:
            handoff = getattr(aiter, "handoff", None)
            if handoff is not None:
                t_pump, t_got = handoff
                RECORDER.add("api.sse_write", int(t_got * 1e6),
                             int((now() - t_got) * 1e6), cat="api", rid=rid,
                             wait_us=int((t_got - t_pump) * 1e6))
    try:
        # drain to the DONE sentinel even past EOS: breaking out would
        # abandon pending tokens and drop a worker error raised after the
        # EOS token (the iterator's own finalizer also cancels, covering
        # hard disconnects that cancel this handler task outright)
        async for tok in aiter:
            if tok.is_end_of_stream:
                finish = "stop"
                continue
            if finish == "length" and tok.text:
                if matcher is None:
                    await write_token(tok.text)
                    continue
                safe = matcher.feed(tok.text)
                if safe:
                    await write_token(safe)
                if matcher.stopped:
                    # stop sequence completed: nothing past it is ever
                    # emitted; cancel the producer (frees the engine
                    # slot / generation thread) and keep consuming to
                    # the DONE sentinel for a clean wind-down
                    finish = "stop"
                    cancel()
        if matcher is not None and not matcher.stopped:
            tail = matcher.flush()      # held-back partial-match suffix
            if tail:
                await write_safe(chunk({"content": tail}))
    except Exception as e:
        if _stream_migrated(e):
            # the engine parked this stream's KV for migration: sever
            # the socket WITHOUT a finish chunk or [DONE], so the router
            # classifies the leg as broken mid-body and runs its resume
            # plane (a clean close would read as a final answer — and
            # the client, behind the router, never sees the break)
            cancel()
            tr = request.transport
            if tr is not None:
                tr.abort()
            return resp
        # mid-stream generation failure: still close the SSE stream
        # with a final chunk + [DONE] so clients don't hang
        await write_safe(chunk({"content": f"\n[error: {e}]"}))
        finish = "error"
    GENERATIONS.inc(kind="text",
                    status="error" if finish == "error" else "ok")
    if "stats" in result:
        state.last_stats = _stats_snapshot(result["stats"], cid)
    await write_safe(chunk({}, finish=finish))
    await write_safe(b"data: [DONE]\n\n")
    if not client_gone:
        await resp.write_eof()
    return resp


async def _chat_stream(request, state: ApiState, messages, gen_kwargs,
                       stops: list[str] | None = None,
                       continuation: bool = False):
    cid = _completion_id()
    _adopt_request_id(request, cid)     # spans carry the trace id / cid
    prompt_in = messages
    if continuation:
        try:
            prompt_in = await _continuation_ids(state, messages)
        except Exception as e:
            return web.json_response(
                {"error": f"chat template failed: {e}"}, status=400)
    async with state.lock:      # locked fallback: one inference at a time
        aiter, result, cancel = run_generation_streamed(state.model,
                                                        prompt_in,
                                                        gen_kwargs)
        return await _sse_drain(request, state, cid, aiter, result,
                                cancel.set, stops,
                                cont_chars=len(str(
                                    messages[-1].get("content") or ""))
                                if continuation else None)


async def list_models(request: web.Request) -> web.Response:
    state: ApiState = request.app["state"]
    return web.json_response({"object": "list", "data": state.owned_models()})
