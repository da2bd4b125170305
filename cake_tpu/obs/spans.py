"""Span recorder: request-scoped phase spans with Chrome-trace export.

The reference exports Chrome traces via tracing-chrome (ref: --sd-tracing,
sd.rs:358-384) and logs per-token phase breakdowns (ref:
text_model.rs:357-365); here both collapse into one recorder: hot paths
record bounded complete ("X") events tagged with the current request id,
and the buffer exports as Perfetto-loadable Chrome-trace JSON
({"traceEvents": [...]}) on demand or into $CAKE_TRACE_DIR.

The recorder is off by default — a disabled span() is one attribute check —
and turns on explicitly (RECORDER.enable()) or via the CAKE_TRACE_DIR env
var. Timestamps are monotonic microseconds (perf_counter_ns), so exported
events always satisfy the Perfetto monotonic-ts requirement.

Every recorded span carries an `id` (process-wide counter) and, when it was
opened inside another span of the same thread, that span's id as `parent`,
both in `args`: a reader nests spans by id, not by comparing intervals.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import threading
import time
import uuid
from collections import deque

from .. import knobs

# -- request-id propagation --------------------------------------------------

_request_id: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "cake_request_id", default=None)


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


def set_request_id(rid: str | None):
    _request_id.set(rid)


def current_request_id() -> str | None:
    return _request_id.get()


@contextlib.contextmanager
def request_scope(rid: str | None = None):
    """Bind a request id for the duration of the block (generates one when
    not given); spans recorded inside carry it in their args."""
    rid = rid or new_request_id()
    token = _request_id.set(rid)
    try:
        yield rid
    finally:
        _request_id.reset(token)


def _now_us() -> int:
    return time.perf_counter_ns() // 1000


# span ids: one counter for the process, so ids from several recorders (the
# tests build their own) never collide in a merged export
_next_id = itertools.count(1).__next__


def new_span_id() -> int:
    """An id for a span whose children are recorded before it is: hand it
    to them as `parent`, and to `add` / `hold` as `sid` when the span
    itself is recorded."""
    return _next_id()


# held spans past this go to the ring like any other (a process that keeps
# compiling while it is traced must not grow without bound)
HELD_MAX = 8192


class SpanRecorder:
    """Bounded ring buffer of Chrome-trace complete events."""

    def __init__(self, max_events: int | None = None, enabled: bool | None = None):
        if max_events is None:
            max_events = knobs.get("CAKE_TRACE_EVENTS")
        self._events: deque = deque(maxlen=max_events)
        # spans that outlive the ring's turnover and `clear()`: the
        # process's start-up (obs/process.py), first in every export
        self._held: list = []
        # called by enable() with nothing: whoever has spans from before
        # the recorder was on (the process's watch) hands them over then
        self.source = None
        self._lock = threading.Lock()
        self._export_seq = 0
        self._open = threading.local()      # per-thread stack: _stack()
        if enabled is None:
            enabled = bool(knobs.get_str("CAKE_TRACE_DIR"))
        self.enabled = enabled

    def enable(self):
        self.enabled = True
        if self.source is not None:
            self.source()

    def disable(self):
        self.enabled = False

    def clear(self):
        """Empty the ring. The held spans stay: they are the start-up's,
        recorded once."""
        with self._lock:
            self._events.clear()

    def __len__(self) -> int:
        return len(self._events)

    # -- recording -----------------------------------------------------------

    def add(self, name: str, ts_us: int, dur_us: int, cat: str = "phase",
            parent: int | None = None, sid: int | None = None,
            **args) -> int | None:
        """Record a complete event from externally measured timestamps
        (microseconds on the perf_counter clock). `parent` is the id of
        the span that caused it; left out, it is the span open on this
        thread, if any. `sid` is an id `new_span_id` gave beforehand (its
        children are recorded already). Returns the new span's id (None
        when disabled)."""
        if not self.enabled:
            return None
        if parent is None:
            stack = self._stack()
            parent = stack[-1] if stack else None
        return self._record(name, ts_us, dur_us, cat, sid or _next_id(),
                            parent, args)

    def hold(self, name: str, ts_us: int, dur_us: int, cat: str,
             tid: int, parent: int | None = None, sid: int | None = None,
             **args) -> int | None:
        """`add`, for a span that must outlive the ring's turnover and
        `clear()`: kept beside the ring, first in `events()` and in every
        export. It is recorded from past stamps, maybe by another thread
        than made it: `tid` is its thread, its parent and its request id
        (in `args`) are those given, never the caller's."""
        if not self.enabled:
            return None
        return self._record(name, ts_us, dur_us, cat, sid or _next_id(),
                            parent, args, held_tid=tid)

    def _stack(self) -> list:
        """Ids of the spans open on the calling thread, innermost last."""
        try:
            return self._open.stack
        except AttributeError:
            self._open.stack = []
            return self._open.stack

    def _record(self, name, ts_us, dur_us, cat, sid, parent, args,
                held_tid: int | None = None) -> int:
        args["id"] = sid
        if parent is not None:
            args["parent"] = parent
        held = held_tid is not None
        rid = None if held else _request_id.get()
        if rid is not None:
            args.setdefault("request_id", rid)
        ev = {"name": name, "cat": cat, "ph": "X", "ts": int(ts_us),
              "dur": max(int(dur_us), 0), "pid": os.getpid(),
              "tid": held_tid if held else threading.get_ident(),
              "args": args}
        with self._lock:
            if held and len(self._held) < HELD_MAX:
                self._held.append(ev)
            else:
                self._events.append(ev)
        return sid

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "phase",
             parent: int | None = None, **args):
        """Record the wrapped block as one complete event; yields the
        span's id (None when disabled). Spans opened inside the block on
        this thread get it as their parent; its own is `parent`, or left
        out the span open on this thread. Disabled-path cost is a single
        attribute check."""
        if not self.enabled:
            yield None
            return
        stack = self._stack()
        sid = _next_id()
        if parent is None:
            parent = stack[-1] if stack else None
        stack.append(sid)
        t0 = _now_us()
        try:
            yield sid
        finally:
            dur = _now_us() - t0
            stack.pop()
            # a recorder switched off mid-span drops the span, as add() does
            if self.enabled:
                self._record(name, t0, dur, cat, sid, parent, args)

    def instant(self, name: str, cat: str = "mark", **args):
        if not self.enabled:
            return
        rid = _request_id.get()
        if rid is not None:
            args.setdefault("request_id", rid)
        ev = {"name": name, "cat": cat, "ph": "i", "ts": _now_us(), "s": "t",
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    # -- export --------------------------------------------------------------

    def events(self) -> list[dict]:
        """The held spans (the process's start-up), then the ring."""
        with self._lock:
            return [dict(e) for e in self._held] \
                + [dict(e) for e in self._events]

    def to_chrome_trace(self) -> dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path: str | None = None) -> str:
        """Write the buffer as Chrome-trace JSON (open in Perfetto /
        chrome://tracing). Default path: $CAKE_TRACE_DIR/cake-trace-<pid>-<n>.json."""
        if path is None:
            trace_dir = knobs.get_str("CAKE_TRACE_DIR") or "."
            os.makedirs(trace_dir, exist_ok=True)
            with self._lock:
                self._export_seq += 1
                seq = self._export_seq
            path = os.path.join(trace_dir,
                                f"cake-trace-{os.getpid()}-{seq}.json")
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


# process-global recorder: every layer (model decode, cluster hops, API,
# bench probe) records into this one buffer so a single export shows the
# whole request path
RECORDER = SpanRecorder()

# span vocabulary: every name recorded into RECORDER, with the layer that
# records it — the observability catalog (docs/observability.md) is
# generated from this table, so a span cannot ship undocumented (the
# metric-registry lint's span analog is this table plus the pinned doc)
SPAN_CATALOG: tuple[tuple[str, str], ...] = (
    ("prefill", "TextModel / offload / distributed generate: prompt "
                "prefill (one device call)"),
    ("decode_segment", "local TextModel: one fused decode segment"),
    ("decode_dispatch", "local TextModel: decode program dispatch"),
    ("decode_wait", "local TextModel: host wait on the fetched token"),
    ("decode_token", "distributed/offload per-token decode loop "
                     "(contains embed/layers/lm_head/sample)"),
    ("embed", "per-token embedding phase (distributed/offload loops)"),
    ("layers", "per-token transformer layers; remote hops carry "
               "worker/start/end args"),
    ("lm_head", "per-token lm_head phase (distributed/offload loops)"),
    ("sample", "per-token sampling phase"),
    ("recover", "cluster master: quarantine->reconnect->replay cycle "
                "after a stage failure"),
    ("replay_prefill", "cluster master: rebuild-by-replay prefill "
                       "reconstructing lost worker KV"),
    ("serve.step", "serve engine: one scheduler iteration (args: "
                   "slots, queued, `step` = the flight record's `seq`); "
                   "the eight spans below are its children, carry its "
                   "`step`, and cover it end to end"),
    ("serve.sweep", "serve engine: cancel, queue-deadline and "
                    "request-deadline sweeps"),
    ("serve.admit", "serve engine: preempted slots resumed, queued "
                    "requests started into free slots incl. the prefix "
                    "splice (args: admitted)"),
    ("serve.plan", "serve engine: choice of the chunk job, block "
                   "reservation, draft building"),
    ("serve.decode_dispatch", "serve engine: host cost of dispatching "
                              "the batched decode / verify program "
                              "(args: slots, bucket, kv_tokens = the "
                              "tokens the stepping rows hold, ring_tokens "
                              "= those they hold in window rings, "
                              "state_bytes = the bytes of recurrent state "
                              "they hold)"),
    ("serve.prefill_chunk", "serve engine: one chunked-admission "
                            "prefill dispatch (args: tokens, pos0, slot)"),
    ("serve.prefill_finish", "serve engine: prefix-cache block capture "
                             "and, on the last chunk, first-token sample "
                             "and slot activation (args: final)"),
    ("serve.capture_blocks", "serve engine, inside serve.prefill_finish: "
                             "the prefix-cache capture of the blocks the "
                             "chunk completed (args: step, blocks)"),
    ("prefix.insert", "prefix cache, inside serve.capture_blocks: one "
                      "block's insertion (args: block, known = 1 when its "
                      "key was held already and nothing was extracted, "
                      "bytes = what was copied out of the row, state_bytes "
                      "= the part of it that is a recurrent snapshot; both "
                      "0 where known); "
                      "the contiguous cache's: the paged one pins pool "
                      "blocks by reference and records none"),
    ("prefix.extract", "prefix cache, inside prefix.insert: the "
                       "`slot_extract` dispatch that copies the block out "
                       "of the pool row (the host's part of it; the "
                       "program runs behind the chunk)"),
    ("prefix.evict", "prefix cache, inside prefix.insert: least-recently "
                     "used blocks dropped to make room"),
    ("serve.fetch", "serve engine: the one device->host fetch of packed "
                    "ids, those of the step iteration `of_step` "
                    "dispatched; the scheduler is blocked on the device "
                    "(args: of_step, lag = 1 when that was an earlier "
                    "iteration and this one's own decode step was "
                    "already queued behind it, else 0)"),
    ("serve.fanout", "serve engine: the fetched ids fanned out to the "
                     "streams of the requests that were active when "
                     "`of_step` dispatched, finished rows released (args: "
                     "of_step, lag, tokens, finished, dropped = ids whose "
                     "request had ended since)"),
    ("serve.replay", "serve engine: one slot's crash/preemption replay"),
    ("spec.verify", "speculative verify dispatch (generate path and "
                    "batched serve path)"),
    ("read", "worker wire phase: request frame read (PhaseTimer)"),
    ("deser", "worker wire phase: payload deserialization (PhaseTimer)"),
    ("fwd", "worker wire phase: stage forward compute (PhaseTimer)"),
    ("ser", "worker wire phase: result serialization (PhaseTimer)"),
    ("process.compile", "process (obs/process.py), cat `process`, held "
                        "beside the ring: one stage of one program's "
                        "build, from jax.monitoring (args: program, stage "
                        "= trace | lower | backend, cache = hit | miss | "
                        "off on the backend stage, phase = the boot phase "
                        "open on its thread, request_id when a request's "
                        "dispatch built it in-band); handed over with its "
                        "past stamps when the recorder is switched on"),
    ("boot.model", "process, cat `boot`, held: TextModel.__init__ whole "
                   "(params placed, rope tables cut, the jitted programs "
                   "defined; nothing compiles here)"),
    ("boot.rope", "process, cat `boot`, held: layers.make_rope building "
                  "the rope tables on the host and placing them, and "
                  "layers.cut_rope cutting them (a child of boot.model "
                  "there; make_rope is called by whoever builds the "
                  "params, before the model's constructor)"),
    ("boot.engine", "process, cat `boot`, held: ServeEngine.__init__ "
                    "whole"),
    ("boot.engine.pool", "process, cat `boot`, held, inside boot.engine: "
                         "the slot pool's and the prefix cache's "
                         "allocation, waited for"),
    ("api.sse_write", "api: one streamed token through the SSE writer, "
                      "from the instant the event loop handed it over to "
                      "the instant `resp.write` returned: `json.dumps` and "
                      "aiohttp's write (args: rid, wait_us = how long it "
                      "waited between the scheduler's "
                      "`call_soon_threadsafe` and that hand-over: the GIL "
                      "and the loop's queue)"),
)

# device-side vocabulary: every jax.named_scope the programs carry, with the
# code it wraps. A scope is metadata on the ops traced inside it (the HLO
# `op_name` holds `.../cake.attn/...`), so a trace reader can sum device
# time by part of the model across edits that renumber the fusions. Nested
# scopes nest in the name: `cake.sample/cake.sample.select`.
SCOPE_CATALOG: tuple[tuple[str, str], ...] = (
    ("cake.embed", "token embedding lookup (layers.embed_tokens)"),
    ("cake.attn", "one layer's attention incl. its KV write "
                  "(layers._attn enters the scopes of the layer's row in "
                  "models/common/mixers.py: layers.attention_forward, "
                  "a delta-rule mixer under cake.attn.linear, power "
                  "retention under cake.attn.retention, or latent "
                  "attention under cake.attn.latent)"),
    ("cake.attn.window", "a window layer's masked attention over its "
                         "ring and the chunk: scores, the softmax (with "
                         "its sink column where the layer has one) and "
                         "the weighted values (layers.attention_forward)"),
    ("cake.attn.full", "a full layer's read of its cache in a decode or "
                       "masked step, the counterpart of cake.attn.window: "
                       "the Pallas decode kernel's call, or the masked "
                       "scores, softmax and weighted values over the "
                       "whole buffer (layers.attention_forward)"),
    ("cake.attn.gate", "the attention output gate: a per-head gate's "
                       "projection and sigmoid and its product with the "
                       "heads' outputs (Laguna), or the elementwise "
                       "gate's sigmoid and product (Qwen3.5) "
                       "(layers.attention_forward); a latent layer's gate "
                       "a head on its heads' outputs (Ling-3.0: "
                       "deepseek_v2.latent_forward)"),
    ("cake.attn.linear", "a delta-rule layer's whole mixer, inside "
                         "cake.attn (the rows qwen3_5.MIXER and kda.MIXER: "
                         "gdn_forward, kda_forward)"),
    ("cake.attn.linear.proj", "its projections: q, k, v (one fused in_proj "
                              "for the gated delta net), the decay's and the "
                              "gate's (a low-rank pair each, or one matrix "
                              "of full rank: LinearAttnConfig.full_proj), "
                              "beta's, and the output's"),
    ("cake.attn.linear.conv", "depthwise causal conv over the row's conv "
                              "tail and the chunk, and the new tail "
                              "(qwen3_5.short_conv)"),
    ("cake.attn.linear.scan", "l2 norms, decay (softplus, or bounded below: "
                              "kda.log_decay) and beta, the delta-rule "
                              "state update and read-out (one step for a "
                              "token, on the TPU the kernel "
                              "cake_delta_rule_state; a chunk as matrix "
                              "products over blocks of tokens: "
                              "qwen3_5.delta_rule_chunk) and the gated "
                              "output norm"),
    ("cake.attn.retention", "a power-retention layer's whole mixer, inside "
                            "cake.attn (the row brumby.MIXER: "
                            "retention_forward)"),
    ("cake.attn.retention.proj", "its projections: q, k, v and the gate's, "
                                 "the per-head norms, rope, logsigmoid, "
                                 "and the output's"),
    ("cake.attn.retention.expand", "phi of q and k: the symmetric squares "
                                   "(brumby.phi, two 0/1 picks and a "
                                   "product)"),
    ("cake.attn.retention.scan", "every pass over the row's state S and "
                                 "normaliser z: the products inside the "
                                 "chunk, the read-out against the carried "
                                 "state, the division and the decayed "
                                 "update (brumby.retention_chunk; a "
                                 "decode step is its C = 1)"),
    ("cake.attn.latent", "a latent-attention layer's whole mixer, inside "
                         "cake.attn (the row deepseek_v2.MIXER: "
                         "latent_forward); the scatter of the step's rows "
                         "lies in it, outside its parts"),
    ("cake.attn.latent.proj", "its projections: q_a and q_b (or ONE q_proj "
                              "of full rank), kv_a, the low-rank norms, "
                              "rope on the queries' and the "
                              "shared key's rope part, and the output's"),
    ("cake.attn.latent.absorb", "W_uk folded into the queries (a step that "
                                "reads a cache scores against the latents "
                                "themselves) and W_uv out of the weighted "
                                "latents"),
    ("cake.attn.latent.expand", "keys and values of every head from the "
                                "latents (kv_b_proj): the stateless pass, "
                                "which attends in the expanded form"),
    ("cake.attn.latent.read", "scores, softmax and weighted sum over the "
                              "cache: the call of the Pallas kernel "
                              "cake_latent_decode_attention (by shape: a decode step loops over a row's "  # noqa: E501
                              "key steps inside the body, a chunk or a "
                              "verify step has a grid step a key block) or "
                              "XLA's masked ops over the whole buffer (ops.latent_attention)"),  # noqa: E501
    ("cake.ssm", "one Mamba layer's state-space mixer, in cake.attn's "
                 "place for that layer kind (the row jamba.MIXER: "
                 "mamba_forward)"),
    ("cake.ssm.proj", "mamba_forward: the in, x, dt and out projections "
                      "with the norms on dt, B and C"),
    ("cake.ssm.conv", "mamba_forward: depthwise causal conv over the "
                      "row's conv tail and the chunk, and the new tail"),
    ("cake.ssm.scan", "mamba_forward: the state update (closed form for "
                      "one token, a scan along a chunk's tokens) and the "
                      "gated output"),
    ("cake.ffn", "one layer's feed-forward (layers._ffn: mlp_forward or "
                 "moe_forward; layers.shortcut_forward: a shortcut "
                 "sub-layer's dense FFN and, where it opens a pair, the "
                 "pair's sparse layer)"),
    ("cake.ffn.dense", "a shortcut sub-layer's dense FFN with its sum into "
                       "the stream and, where the sub-layer closes a pair, "
                       "the sum of the sparse layer's held-back output "
                       "(layers.shortcut_forward); older families' dense "
                       "FFNs enter no such scope"),
    ("cake.ffn.route", "MoE router: logits, the group mask where routing "
                       "is group-limited (a group scored by its best "
                       "member, or by the sum of its two best of score + "
                       "bias: a second top-k), and top-k over every output "
                       "of the router: every expert of the model and the "
                       "identity experts where a family has them "
                       "(ops.moe.moe_ffn)"),
    ("cake.ffn.experts", "MoE expert GEMMs and combine, of the experts "
                         "this process holds (ops.moe.moe_ffn)"),
    ("cake.ffn.shared", "the shared expert every token passes, with its "
                        "sigmoid gate where the family has one, and its "
                        "sum into the routed result (layers.moe_forward)"),
    ("cake.ffn.zero", "the identity experts' term: the summed weights of a "
                      "token's picks among the router's last `zero_experts` "
                      "outputs, their product with the layer's input and "
                      "its sum into the routed result (ops.moe.moe_ffn)"),
    ("cake.lm_head", "final norm and vocabulary projection "
                     "(layers.lm_head_logits)"),
    ("cake.sample", "on-device sampling: sample, sample_traced and the "
                    "verify programs' spec_accept (ops.sampling)"),
    ("cake.sample.penalty", "sample_traced: repeat-penalty flag scatter "
                            "and select"),
    ("cake.sample.select", "keep_mask: the vocabulary filter in vocabulary "
                           "order, the ordered keys and both searches "
                           "(sample_traced and filtered_probs)"),
    ("cake.sample.top_k", "keep_mask: the search for the k-th largest value "
                          "and the id that cuts its tied run; no pass with "
                          "top_k >= V"),
    ("cake.sample.top_p", "keep_mask: the survivors' softmax weights and "
                          "the search for the value and id where the mass "
                          "before a token reaches top_p"),
    ("cake.sample.draw", "sample_traced: gumbel noise and argmax"),
)


@contextlib.contextmanager
def jax_trace(log_dir: str | None):
    """Wrap a region in a JAX profiler trace (xprof / Perfetto viewable).
    No-op when log_dir is None. Device-side complement to SpanRecorder's
    host-side spans (ref: tracing-chrome behind --sd-tracing)."""
    if not log_dir:
        yield
        return
    import logging

    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logging.getLogger("cake_tpu.obs").info(
            "profiler trace written to %s", log_dir)
