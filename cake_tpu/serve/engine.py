"""Continuous-batching scheduler: slot-based batched decode for concurrent
text serving.

The reference serializes every request through Arc<RwLock<Master>> (ref:
api/mod.rs:71) and the inherited locked path does the same — request N+1
waits for request N's entire decode. This engine applies iteration-level
scheduling (Orca, OSDI'22) with a fixed slot pool (vLLM's slot idea minus
paging — slots here are whole KV rows of a preallocated batch-B cache):

  * a bounded admission queue feeds a single scheduler thread;
  * admission is CHUNKED (Sarathi-Serve, OSDI'24): a queued request takes
    a free slot immediately (splicing any shared-prefix KV the PrefixCache
    already holds — see prefix_cache.py), then each iteration advances at
    most ONE in-flight admission by one CAKE_PREFILL_CHUNK-token chunk
    (`TextModel.prefill_chunk` scatters straight into the pool row at
    pos0), round-robin over in-flight prefills so a huge prompt cannot
    starve the queue behind it;
  * each iteration also runs ONE batched step over the occupied prefix
    (per-slot positions, RNG keys, recent-token windows, traced sampling
    params, and an `active` mask that freezes rows still mid-prefill):
    a plain `decode_slots` step, or — when a drafter is configured and
    proposed for any slot — a batched multi-token `spec_slots` verify in
    which every slot carries its own draft window and accepts a RAGGED
    per-slot prefix (Leviathan-style speculative decoding folded into
    continuous batching; the paged layout moves each slot's block cursor
    by its accepted length). Either way an iteration fans each slot's
    new tokens out to its request's stream — decode latency under
    admission is bounded by the CHUNK, not the prompt, which kills the
    head-of-line blocking a monolithic prefill imposed on every active
    decode;
  * the fetch of a step's sampled ids LAGS its dispatch by one iteration:
    every carry the next step needs is device-resident, so iteration k
    dispatches step k and only then fetches step k-1's ids — the device
    runs step k while the host fans out, sweeps, admits and plans, and a
    step costs max(device, host) instead of their sum. What the host
    learns one step late (EOS, a spent budget, a cancel) costs the row
    one masked-in step whose id is dropped; fan-out goes by the (slot,
    request) pairs recorded at dispatch, never by what a slot holds now.
    The lag is 0 where the next plan needs every id on the host (a
    drafter, kvshare's stream parking, a paged preemption) — `_step` 4.;
  * EOS / budget / client-cancel free the slot for the next admission.

Every jax call happens on the scheduler thread, so the engine needs no
device-side locking; API handlers only touch thread-safe queues/events.
Greedy outputs are bit-identical to the sequential path (masked slots
contribute exactly-zero attention weight; chunked prefill reproduces the
monolithic program's numerics; a prefix-cache hit splices the exact bytes
a miss would recompute), which the tier-1 e2e tests pin.

The engine is CRASH-ONLY (supervisor.py owns the policy): a step failure
no longer kills serving — the supervisor classifies it, reallocates the
pool, and `_rebuild` replays every live slot's prompt+generated tokens
through the chunked-prefill path (the prefix cache makes shared prefixes
nearly free to replay; replay lands on the same chunk buckets admission
compiled). Greedy continuations across a rebuild are bit-identical —
every carry the decode program needs (last token, position, recent
window) is reconstructible from the host-side token record; sampled
(temperature > 0) streams resume on a FRESH rng fold, which is the one
documented parity exception. Repeated failures are budgeted; past the
budget the engine goes honestly DOWN (typed 503s, /health engine block,
restore probe) instead of silently dead — see docs/fault_tolerance.md.
"""
from __future__ import annotations

import logging
import queue as queue_mod
import threading
import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np

from .. import knobs
from ..obs import (PROCESS, RECORDER, SERVE_BATCH_OCCUPANCY,
                   SERVE_E2E_SECONDS, SERVE_INBAND_COMPILES,
                   SERVE_ITL_SECONDS, SERVE_PREFILL_CHUNKS, SERVE_POISONED,
                   SERVE_PREEMPTIONS, SERVE_QOS_E2E_SECONDS,
                   SERVE_QOS_TTFT_SECONDS, SERVE_QUEUE_TIMEOUTS,
                   SERVE_QUEUE_WAIT_SECONDS, SERVE_REQUEST_TIMEOUTS,
                   SERVE_SLOT_JOINS, SERVE_SLOTS_BUSY, SERVE_TTFT_SECONDS,
                   TIMELINES, now, set_request_id)
from ..obs.spans import new_span_id
from ..models.common.cache import joined_key_widths, row_state_bytes
from ..ops.sampling import SamplingConfig, config_has_filters
from ..spec import resolve_drafter
from ..spec.verify import record_step
from . import faults
from .admission import AdmissionQueue, QueueFull
from .admission.classes import class_of, priority
from .flight import FlightRecorder
from .paged import (KVPoolExhausted, PagedKV, PreemptedSlot, choose_victim,
                    victim_rank)
from .prefix_cache import PagedPrefixCache, PrefixCache
from .slots import SlotPool, slot_bucket
from .supervisor import (EngineDown, PoisonedRequest,
                         RequestDeadlineExceeded, Supervisor, classify)

__all__ = ["ServeEngine", "ServeRequest", "QueueFull", "EngineDraining",
           "QueueDeadlineExceeded", "EngineDown", "KVPoolExhausted",
           "PoisonedRequest", "RequestDeadlineExceeded", "maybe_engine"]

log = logging.getLogger("cake_tpu.serve")


class EngineDraining(RuntimeError):
    """Admission refused because the engine is draining for shutdown; the
    API answers 503 + Retry-After so load balancers fail the client over
    instead of letting it wait on a server that is leaving."""

    def __init__(self, retry_after_s: int = 5):
        super().__init__("serve engine draining for shutdown")
        self.retry_after_s = retry_after_s


class QueueDeadlineExceeded(RuntimeError):
    """The request sat in the admission queue past CAKE_QUEUE_DEADLINE_S:
    it is finished with 503 instead of eventually occupying a slot for a
    client that already gave up."""

    def __init__(self, waited_s: float, retry_after_s: int = 1):
        super().__init__(
            f"request expired in admission queue after {waited_s:.1f}s")
        self.waited_s = waited_s
        self.retry_after_s = retry_after_s

# device-resident repeat-penalty window per slot — derived from the
# SamplingConfig default so the engine's window can never silently diverge
# from the sequential path's (the API grid never varies repeat_last_n, so
# one static width serves all)
RECENT_N = SamplingConfig().repeat_last_n

# default pool row length when the model's max_cache_len is unbounded-ish:
# the pool is B x ctx x layers of KV, allocated up front. Derived from
# the registry so ServeEngine callers that pass ctx_len=None without
# going through maybe_engine can never drift from the knob default
DEFAULT_CTX = int(knobs.REGISTRY["CAKE_SERVE_CTX"].default)


def _pow2_chunk(n: int, ctx: int) -> int:
    """Clamp the prefill chunk to a power of two in [16, ctx] — fixed
    chunk buckets keep the per-(bucket, flash_mode) executable count at
    O(log chunk), and block-size == chunk-size keeps prefix-cache splice
    boundaries aligned with chunk boundaries (the bit-parity invariant)."""
    n = max(16, min(int(n), ctx))
    b = 16
    while b * 2 <= n:
        b *= 2
    return b


class _Prefill:
    """Scheduler-private state of one in-flight chunked admission."""

    __slots__ = ("req", "slot", "ids", "n", "pos", "chunks", "next_block",
                 "hit_tokens", "keys")

    def __init__(self, req: "ServeRequest", slot: int):
        self.req = req
        self.slot = slot
        self.ids = req.prompt_ids
        self.n = len(self.ids)
        self.pos = 0            # next prompt position to prefill
        self.chunks = 0         # chunks dispatched so far
        self.next_block = 0     # next prefix-cache block index to capture
        self.hit_tokens = 0     # tokens skipped via prefix-cache splice
        self.keys: list = []    # per-block hash chain (computed once)


def _traced_sampling(scfg: SamplingConfig, vocab: int) -> dict:
    """A request's sampling params as the traced carries hold them, with
    sample_traced's disabled values (top_k >= vocab, top_p 1.0)."""
    return {"temp": scfg.temperature, "top_k": scfg.top_k or vocab,
            "top_p": scfg.top_p if scfg.top_p is not None else 1.0,
            "penalty": scfg.repeat_penalty}


class _InFlight:
    """A dispatched decode step whose packed ids the host has not fetched
    yet. `rows` is the (slot, request) snapshot taken at dispatch: by the
    time the ids are fanned out a slot may have been freed and given to
    another request, so fan-out goes by this record and never by what the
    slot holds now."""

    __slots__ = ("step", "packed", "rows", "ids", "nb", "spec")

    def __init__(self, step: int, rows: list, nb: int, spec):
        self.step = step        # id of the dispatching iteration
        self.packed = None      # device array [2|3, slots], set by dispatch
        self.rows = rows        # [(slot, ServeRequest)] active at dispatch
        self.ids = tuple(r.id for _, r in rows)
        self.nb = nb            # rows the program ran (the flight `bucket`)
        self.spec = spec        # (drafts, n_drafts) of a verify step, or None


class ServeRequest:
    """One submitted generation: token stream + terminal state.

    The engine fills `tokens`/`stats`/`error` (mirroring the legacy
    streamed-path result dict) and feeds `out_q` with Token objects ending
    in DONE. `cancel()` may be called from any thread — the scheduler
    frees the slot on its next iteration.
    """

    DONE = object()

    def __init__(self, prompt_ids: list[int], max_new_tokens: int,
                 sampling: SamplingConfig, request_id: str | None = None,
                 qos: str = "interactive", tenant: str | None = None,
                 continuation: bool = False):
        self.id = request_id or "serve-" + uuid.uuid4().hex[:16]
        self.prompt_ids = list(prompt_ids)
        self.max_new_tokens = max_new_tokens
        self.sampling = sampling or SamplingConfig()
        # QoS class (admission lane, weighted-fair share, preemption
        # rank) + tenant (quota accounting / timeline attribution)
        self.qos = qos
        self.tenant = tenant
        # continuation admission: the prompt's tail is a PARTIAL
        # assistant turn being continued (mid-stream resume splice or
        # client-side finish of a broken stream) — flagged through the
        # enqueue timeline event and stats so operators can tell a
        # splice prefill from a fresh conversation
        self.continuation = continuation
        self.out_q: queue_mod.Queue = queue_mod.Queue()
        self.cancelled = threading.Event()
        self.admitted = threading.Event()   # set when a slot is assigned
        self.done = threading.Event()
        self.result: dict = {}          # tokens / stats / error, like the
                                        # legacy streamed-path result dict
        self.tokens: list[int] = []
        self.stats: dict = {}
        self.t_enqueue = now()
        # delivery handoff state: written by API handler threads
        # registering subscribers, read by the scheduler thread fanning
        # tokens out (the lock-discipline lint enforces the annotations)
        self._sub_lock = threading.Lock()
        self._token_cb = None           # guarded-by: self._sub_lock
        self._done_cbs: list = []       # guarded-by: self._sub_lock
        # scheduler-owned fields
        self.slot: int | None = None
        self.budget = 0                 # decode tokens left after the first
        self.t_first = 0.0              # first-token timestamp (decode t0)
        self._first_pending = False     # first token sampled, not fetched
        self._engine = None

    def cancel(self):
        """Client disconnect: release the slot at the next iteration."""
        self.cancelled.set()
        eng = self._engine
        if eng is not None:
            eng._wake.set()

    def wait(self, timeout: float | None = None) -> bool:
        return self.done.wait(timeout)

    # -- delivery: push subscribers beat thread-parking -------------------
    # API handlers register callbacks instead of blocking an executor
    # thread per in-flight request (the default executor also serves
    # tokenization and every other endpoint — parking a thread per
    # generation would deadlock the server at exactly the concurrency
    # this engine exists to provide).

    def subscribe(self, cb) -> list:
        """Route future token/DONE deliveries through cb (invoked on the
        scheduler thread); returns the backlog accumulated so far."""
        backlog = []
        with self._sub_lock:
            while True:
                try:
                    backlog.append(self.out_q.get_nowait())
                except queue_mod.Empty:
                    break
            self._token_cb = cb
        return backlog

    def add_done_callback(self, cb):
        """cb fires (scheduler thread) when the request completes; fires
        immediately (caller thread) if it already has."""
        with self._sub_lock:
            if not self.done.is_set():
                self._done_cbs.append(cb)
                return
        cb()

    def _deliver(self, item):           # scheduler thread
        with self._sub_lock:
            cb = self._token_cb
            if cb is None:
                self.out_q.put(item)
        if cb is not None:
            try:
                cb(item)
            except Exception:
                pass                    # subscriber's loop may be gone

    def _fire_done(self):               # scheduler thread
        with self._sub_lock:
            self.done.set()
            cbs, self._done_cbs = self._done_cbs, []
        for cb in cbs:
            try:
                cb()
            except Exception:
                pass


class _TokenStream:
    """What `ServeEngine.stream` hands the API: the async generator over a
    request's tokens and, beside it, `handoff`: with the span recorder on,
    (when the scheduler handed the token last yielded to the event loop,
    when the loop handed it over), else None. The stamps are the stream's,
    not the token's: the SSE writer reads them for `api.sse_write`."""
    __slots__ = ("_gen", "_handoff")

    def __init__(self, gen, handoff: list):
        # the generator writes `handoff[0]`; holding the list (and not the
        # generator holding this object) keeps abandonment's finalizer a
        # matter of reference counts
        self._gen, self._handoff = gen, handoff

    @property
    def handoff(self) -> tuple[float, float] | None:
        return self._handoff[0]

    def __aiter__(self):
        return self

    def __anext__(self):
        return self._gen.__anext__()

    def aclose(self):
        return self._gen.aclose()


class ServeEngine:
    """Owns the slot pool, the admission queue, and the scheduler thread."""

    @PROCESS.phase("boot.engine")
    def __init__(self, model, slots: int = 4, max_queue: int = 64,
                 ctx_len: int | None = None, seed: int = 0,
                 prefill_chunk: int | None = None,
                 prefix_cache_mb: float | None = None,
                 queue_deadline_s: float | None = None,
                 request_deadline_s: float | None = None,
                 spec=None, spec_k: int | None = None,
                 spec_reserve: int | None = None,
                 step_watchdog_s: float | None = None,
                 rebuild_budget: int | None = None,
                 rebuild_window_s: float | None = None,
                 restore_interval_s: float | None = None,
                 kv_blocks: int | None = None,
                 kv_block_tokens: int | None = None,
                 preempt_mode: str | None = None):
        if not hasattr(model, "decode_slots"):
            raise TypeError(
                f"{type(model).__name__} has no batched slot decode; the "
                "engine serves plain TextModels only (distributed/offload "
                "models keep the locked path)")
        self.model = model
        self.slots = slots
        self.ctx = min(ctx_len or DEFAULT_CTX, model.max_cache_len)
        if prefill_chunk is None:
            prefill_chunk = knobs.get("CAKE_PREFILL_CHUNK")
        self.chunk = _pow2_chunk(prefill_chunk, self.ctx)
        if prefix_cache_mb is None:
            prefix_cache_mb = knobs.get("CAKE_PREFIX_CACHE_MB")
        self._prefix_mb = prefix_cache_mb    # rebuilds reconstruct the cache
        # -- paged KV pool (CAKE_KV_BLOCKS > 0) ---------------------------
        # Replaces the worst-case-provisioned slots x ctx rows with a
        # shared pool of fixed-size blocks behind per-slot block tables:
        # memory follows actual sequence length, prefix hits become
        # refcount bumps, and exhaustion preempts a victim (swap or
        # recompute) instead of capping admission. 0 keeps the
        # contiguous pool (see docs/serving.md#paged-kv-pool).
        if kv_blocks is None:
            kv_blocks = knobs.get("CAKE_KV_BLOCKS")
        self.kv_blocks = max(int(kv_blocks), 0)
        if kv_block_tokens is None:
            kv_block_tokens = knobs.get("CAKE_KV_BLOCK_TOKENS")
        self.kv_block_tokens = kv_block_tokens
        if preempt_mode is None:
            preempt_mode = knobs.get("CAKE_PREEMPT_MODE")
        if preempt_mode not in ("swap", "recompute"):
            raise ValueError(
                f"CAKE_PREEMPT_MODE must be 'swap' or 'recompute', got "
                f"{preempt_mode!r}")
        self.preempt_mode = preempt_mode
        self.paged: PagedKV | None = None
        self._preempted: list[PreemptedSlot] = []
        # fleet-shared KV tier hook (fleet/kvshare/KVShareReplica), set
        # by the API server when CAKE_KVSHARE is on. Duck-typed on
        # purpose: serve never imports fleet. When set, _step drains its
        # scheduler-thread mailbox (blob export/import, stream parking)
        # before doing anything else, and health() carries its inventory
        self.kv_share = None
        self.pool = SlotPool(slots)
        self.queue = AdmissionQueue(max_queue)
        # per-request queue deadline (CAKE_QUEUE_DEADLINE_S, 0 disables):
        # a request whose client-side timeout has surely elapsed is 503ed
        # by the sweep instead of admitted into a slot nobody will read
        if queue_deadline_s is None:
            queue_deadline_s = knobs.get("CAKE_QUEUE_DEADLINE_S")
        self.queue_deadline_s = queue_deadline_s
        # per-request TOTAL deadline (CAKE_REQUEST_DEADLINE_S, 0 disables):
        # the queue sweep above only covers waiting — this one cancels
        # ADMITTED slots whose whole-request age expired (504, typed)
        if request_deadline_s is None:
            request_deadline_s = knobs.get("CAKE_REQUEST_DEADLINE_S")
        self.request_deadline_s = request_deadline_s
        # -- speculative decoding: batched over every occupied slot ------
        # CAKE_SPEC names the drafter ("ngram"; unset = off), CAKE_SPEC_K
        # the per-slot draft window. Speculation rides the SAME batched
        # iteration as plain decode: every occupied slot carries its own
        # draft window through one spec_slots dispatch with ragged
        # per-slot acceptance, so there is no occupancy cliff and no
        # paged-mode stand-down — a slot whose drafter abstains simply
        # takes a plain decode step inside the same executable.
        # CAKE_SPEC_RESERVE caps how much speculative frontier a paged
        # slot may reserve ahead of a verify (0 = the full window).
        drafter, k = resolve_drafter(spec, spec_k)
        if drafter is not None and not drafter.shareable:
            raise ValueError(
                f"drafter {drafter.name!r} keeps per-sequence state and "
                "cannot be shared across engine slots — use 'ngram' "
                "(DraftModelDrafter belongs on the generate() path)")
        self.spec_drafter = drafter
        self.spec_k = k
        if spec_reserve is None:
            spec_reserve = knobs.get("CAKE_SPEC_RESERVE")
        self.spec_reserve = max(int(spec_reserve), 0)
        self.spec_steps = self.spec_proposed = self.spec_accepted = 0
        # this iteration's per-slot draft lengths (slot -> n_draft):
        # the speculative-frontier trim must keep blocks the PENDING
        # verify dispatch will write, so rollback reads it
        self._cur_nd: dict[int, int] = {}
        self._draining = threading.Event()

        self._seed = seed
        self._vocab = model.cfg.vocab_size
        self._base_rng = jax.random.PRNGKey(seed)
        with PROCESS.phase("boot.engine.pool"):
            self._init_device_state()
            self.prefix_cache = self._build_prefix_cache()
            # the zeros are born on the device asynchronously: waited for,
            # so the phase is the allocation's and not its dispatch's
            jax.block_until_ready(self._layers if self.paged is None else
                                  (self.paged.pool, self.paged.rows))
        self._reqs: list[ServeRequest | None] = [None] * slots
        self._prefills: list[_Prefill] = []   # in-flight chunked admissions
        self._rr = 0                          # round-robin cursor over them
        self._seq = 0

        self._wake = threading.Event()
        self._stop = threading.Event()
        self.steps = 0                  # completed scheduler iterations
        self.last_step = now()
        # flight recorder: ring of recent iteration records the
        # supervisor dumps to CAKE_TRACE_DIR on wedge/DOWN — built
        # before the supervisor so the watchdog can always reach it
        self.flight = FlightRecorder()
        self.flight.static["joined_keys"] = self._joined_keys
        self.flight.static["attention_kinds"] = self._attention_kinds
        if self._sparse_layers is not None:
            self.flight.static["sparse_layers"] = self._sparse_layers
        self.flight.static.update(self._rope_held)
        self._step_id = 0           # the running iteration's flight seq
        # running totals the iteration's record takes differences of
        # (_land): tokens emitted, requests a fan-out finished, ids fetched
        # and not delivered (their request had ended), seconds blocked in
        # a fetch; and (_complete_prefill) slots joined to the decode batch
        self._emitted = self._retired = self._dropped = self._joined = 0
        self._fetch_s = 0.0
        self._chunk_kind = None     # `chunk` | `last_chunk` when it ran one
        self._chunk_end = None      # recorder on: when its dispatch ended
        self._finish_id = None      # and serve.prefill_finish's span id
        # the last stamp of the previous iteration when it left work behind
        # (busy rows, a queue, a step in flight), else None: the next
        # iteration's `gap_ms` is its first stamp minus this
        self._t_prev_end: float | None = None
        self.dead: BaseException | None = None
        # the supervisor needs _stop (watchdog lifetime) — build it after
        # the events, before the scheduler thread can possibly fail
        self.supervisor = Supervisor(
            self, watchdog_s=step_watchdog_s, rebuild_budget=rebuild_budget,
            rebuild_window_s=rebuild_window_s,
            restore_interval_s=restore_interval_s)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="cake-serve")
        self._thread.start()

    def _init_device_state(self, layers=None, paged=None):
        """(Re)allocate the pool cache and every per-slot carry — called
        at construction and by crash recovery (`_rebuild`/`_revive`),
        which trusts NOTHING device-resident after a failure (donated
        buffers may be consumed, results may be garbage: crash-only).

        ALL per-slot state is device-resident: rows are written at
        admission/release only, and the whole carry (tokens, positions,
        RNG, recent windows) advances inside the batched decode program
        — an iteration ships nothing host->device and fetches only the
        packed sampled ids. In paged mode the pool is a PagedKV (shared
        physical blocks + per-slot tables) instead of B contiguous
        rows; the carries are identical."""
        slots = self.slots
        if self.kv_blocks > 0:
            self.paged = paged or PagedKV.build(
                self.model, slots, self.ctx, self.kv_blocks,
                self.kv_block_tokens, self.chunk)
            self._layers = None
        else:
            if layers is None:
                layers = self.model.new_cache(slots,
                                              kv_len=self.ctx)["layers"]
            self._layers = layers
        # what a stepping row's recurrent state weighs, read off the pool's
        # leaves once (the flight record's `state_bytes`)
        self._row_state_bytes = row_state_bytes(
            self.paged.rows if self._layers is None else self._layers)
        # the layers whose keys lie joined in the pool (cache.key_row_shape:
        # a key width that is no multiple of the lanes), by joined width:
        # health's `kv_pool` and the flight record's static part say so
        self._joined_keys = [
            {"width": w, "layers": n} for w, n in sorted(joined_key_widths(
                self.paged.pool + self.paged.rows if self._layers is None
                else self._layers).items())]
        # the attention layers by kind: heads, K/V heads, window, rotary
        # width and the rope table each kind reads (health's static part
        # and the flight record's)
        self._attention_kinds = self.model.cfg.attention_kinds()
        # the sparse layers: what the router scores (experts of the group,
        # identity experts), what of it is held here, the shortcut pairs
        self._sparse_layers = self.model.cfg.sparse_layers()
        # the rope tables the model holds: a model cut to its reach holds
        # max_cache_len rows of each (0 / 0 where no layer rotates)
        tables = list(self.model.params["rope"].values())
        self._rope_held = {
            "rope_rows": max((t.shape[0] for t in tables), default=0),
            "rope_bytes": sum(t.nbytes for t in tables)}
        # the window layers' ring lengths (the flight record's
        # `ring_tokens`); none for a model without window layers
        self._ring_sizes = [s.window for s in self.model.cfg.layer_specs()
                            if s.window is not None]
        self._toks = jnp.zeros((slots,), jnp.int32)
        self._pos = jnp.zeros((slots,), jnp.int32)
        self._temps = jnp.zeros((slots,), jnp.float32)
        self._top_ks = jnp.full((slots,), self._vocab, jnp.int32)
        self._top_ps = jnp.ones((slots,), jnp.float32)
        self._pens = jnp.ones((slots,), jnp.float32)
        self._rngs = jnp.stack([jax.random.PRNGKey(self._seed + i)
                                for i in range(slots)])
        self._recents = jnp.full((slots, RECENT_N), -1, jnp.int32)
        # the decode step dispatched and not yet fetched (_step 4.): what a
        # failure leaves there is dropped with the pool it ran on, and the
        # replay regenerates its ids as it does a crashed step's
        self._inflight: _InFlight | None = None
        # decode-eligibility mask: True only for slots whose prefill has
        # COMPLETED. Mutated at transitions only (prefill done / release),
        # never donated — the engine keeps its handle across iterations,
        # so steady-state decode still ships nothing host->device
        self._act = jnp.zeros((slots,), jnp.bool_)

    def _build_prefix_cache(self):
        """Mode-matched prefix cache: the paged variant pins shared pool
        blocks by refcount (a hit is a table remap, no KV copy) and is
        wired in as the allocator's under-pressure evictor; the
        contiguous variant keeps private block copies."""
        if self.paged is not None:
            pc = PagedPrefixCache.build_paged(self.model, self.paged,
                                              self.chunk, self._prefix_mb)
            self.paged.evictor = pc.evict_for_pressure if pc else None
        else:
            pc = PrefixCache.build(self.model, self.ctx, self.chunk,
                                   self._prefix_mb)
        if pc is None and self._prefix_mb > 0:
            log.warning(
                "prefix cache of %s MB asked for and not built: %s",
                self._prefix_mb,
                PrefixCache.refusal(
                    self.ctx, self.chunk, self._prefix_mb,
                    PrefixCache.block_bytes(self.model, self.ctx,
                                            self.chunk))
                if self.paged is None else PagedPrefixCache.refusal_paged(
                    self.paged, self.chunk, self._prefix_mb,
                    PagedPrefixCache.unit_bytes(self.paged, self.chunk)))
        return pc

    # -- client surface (any thread) ----------------------------------------

    def submit(self, prompt_ids: list[int], max_new_tokens: int = 256,
               sampling: SamplingConfig | None = None,
               request_id: str | None = None, qos: str = "interactive",
               tenant: str | None = None,
               continuation: bool = False) -> ServeRequest:
        """Enqueue a generation under QoS class `qos` (admission lane,
        weighted-fair share, preemption rank — resolved and clamped by
        the API's admission plane). `continuation` marks a splice
        prefill whose prompt tail is a partial assistant turn being
        continued in place (the prefix cache makes the shared head
        nearly free, so a resume's TTFR is the warm path, not a full
        re-prefill). Raises QueueFull under backpressure
        (class-aware: the 429's Retry-After reflects that class's
        backlog), EngineDown while the engine is dead or in
        budget-exhausted degraded mode (API: 503 + Retry-After),
        PoisonedRequest for quarantined prompts, and ValueError for
        prompts the pool can never hold."""
        if self.dead is not None or not self._thread.is_alive():
            raise EngineDown(f"serve engine is down: {self.dead}",
                             retry_after_s=30)
        down = self.supervisor.down_info()
        if down is not None:
            raise EngineDown(
                "serve engine down for "
                f"{down['down_for_s']}s (rebuild budget exhausted: "
                f"{down.get('last_failure', 'unknown failure')}); "
                "restore loop probing",
                retry_after_s=max(
                    int(self.supervisor.restore_interval_s) + 1, 5))
        if self._draining.is_set():
            raise EngineDraining(retry_after_s=self.retry_after_hint())
        if self.supervisor.is_quarantined(prompt_ids):
            raise PoisonedRequest(
                "request fingerprint quarantined: identical prompt was "
                "implicated in repeated engine crashes")
        n = len(prompt_ids)
        if n < 1:
            raise ValueError("empty prompt")
        if n > self.ctx - 2:
            raise ValueError(
                f"prompt length {n} exceeds the serve context "
                f"({self.ctx} tokens per slot)")
        # bind a local: the scheduler thread nulls self.paged transiently
        # during _rebuild/_fail_all, and submit runs on client threads
        paged = self.paged
        if paged is not None and paged.blocks_for(n + 1) > paged.num_blocks:
            raise ValueError(
                f"prompt needs {paged.blocks_for(n + 1)} KV blocks "
                f"but the pool holds {paged.num_blocks} "
                f"(CAKE_KV_BLOCKS x CAKE_KV_BLOCK_TOKENS tokens total)")
        req = ServeRequest(prompt_ids, max_new_tokens, sampling, request_id,
                           qos=qos, tenant=tenant, continuation=continuation)
        req._engine = self
        # free slots extend the bound: a burst that fits the idle pool is
        # admitted even though the scheduler drains one per iteration
        self.queue.put(req, allow_extra=self.pool.free_count)
        TIMELINES.begin(req.id)
        TIMELINES.event(req.id, "enqueue", depth=self.queue.depth(),
                        qos=req.qos,
                        **({"tenant": req.tenant} if req.tenant else {}),
                        **({"continuation": True} if req.continuation
                           else {}))
        self._wake.set()
        if self.dead is not None or self.supervisor.is_down():
            # the scheduler crashed (or went down) between the liveness
            # check above and the put: its crash drain may have missed
            # this request, so release the waiter ourselves (double-fail
            # is harmless)
            self.queue.purge(lambda r: r is req)
            if self.dead is not None:
                err = EngineDown(f"serve engine is down: {self.dead}",
                                 retry_after_s=30)
            else:
                err = EngineDown(
                    "serve engine down: rebuild budget exhausted; "
                    "restore loop probing",
                    retry_after_s=max(
                        int(self.supervisor.restore_interval_s) + 1, 5))
            self._fail(req, err)
            raise err
        return req

    def stream(self, req: ServeRequest):
        """(async iterator, result dict) over the request's token stream —
        the same contract as the legacy run_generation_streamed, so the SSE
        writer is path-agnostic. Tokens are pushed from the scheduler
        thread straight into an asyncio queue (call_soon_threadsafe): no
        executor thread is parked per stream, and the iterator's finalizer
        cancels the request on abandonment so a client disconnect frees
        the slot instead of leaking it. Must be called on the event loop."""
        import asyncio

        loop = asyncio.get_running_loop()
        aq: asyncio.Queue = asyncio.Queue()

        # every queued entry is (item, stamp): with the span recorder on
        # the stamp is the instant the scheduler handed the item to the
        # loop, and the writer's `api.sse_write` span reads how long it
        # waited there (`wait_us`); off, no stamp is taken (None). A token
        # the engine emitted before the stream subscribed comes from the
        # backlog, past the pump, and has none either.
        def pump(item):             # scheduler thread
            t = now() if RECORDER.enabled else None
            try:
                loop.call_soon_threadsafe(aq.put_nowait, (item, t))
            except RuntimeError:
                pass                    # loop closed; finalizer cancels

        for item in req.subscribe(pump):
            aq.put_nowait((item, None))
        handoff = [None]

        async def aiter():
            try:
                while True:
                    item, t_pump = await aq.get()
                    if item is ServeRequest.DONE:
                        break
                    handoff[0] = None if t_pump is None else (t_pump, now())
                    yield item
            finally:
                req.cancel()
            if "error" in req.result:
                raise req.result["error"]

        return _TokenStream(aiter(), handoff), req.result

    def health(self) -> dict:
        h = {
            "alive": self.dead is None and self._thread.is_alive(),
            "slots": self.slots,
            "slots_busy": self.pool.busy_count,
            "queue_depth": self.queue.depth(),
            "queue_by_class": self.queue.depths(),
            "ctx_len": self.ctx,
            "prefill_chunk": self.chunk,
            "prefilling": len(self._prefills),
            "draining": self._draining.is_set(),
            "steps": self.steps,
            "last_step_age_s": round(now() - self.last_step, 3),
            # supervision: lifetime recovery counters + live wedge flag
            "rebuilds": self.supervisor.rebuild_count,
            "wedged": self.supervisor.wedged(),
        }
        lf = self.supervisor.last_failure()
        if lf is not None:
            h["last_failure"] = lf
        down = self.supervisor.down_info()
        if down is not None:
            h["down"] = down
        q = self.supervisor.quarantined_count()
        if q:
            h["quarantined"] = q
        pc = self.prefix_cache
        if pc is not None:
            h["prefix_cache"] = pc.occupancy()
        h["kv_pool"] = {"joined_keys": self._joined_keys}
        h["attention_kinds"] = self._attention_kinds
        if self._sparse_layers is not None:
            h["sparse_layers"] = self._sparse_layers
        h.update(self._rope_held)
        # local binding: health() runs on API threads while the scheduler
        # may null self.paged transiently during _rebuild/_fail_all
        paged = self.paged
        if paged is not None:
            live = {}
            for i in self.pool.busy():
                req = self._reqs[i]
                if req is not None:
                    live[i] = len(req.prompt_ids) \
                        + max(len(req.tokens) - 1, 0)
            h["kv_pool"].update(paged.occupancy(live),
                                preempted_slots=len(self._preempted))
            if pc is not None:
                # the peer directory and `cake top` both want the cache
                # size next to pool occupancy, not only in prefix_cache
                h["kv_pool"]["prefix_entries"] = len(pc._blocks)
                h["kv_pool"]["prefix_pinned_blocks"] = getattr(
                    pc, "pinned", 0)
        ks = self.kv_share
        if ks is not None:
            h["kvshare"] = ks.health_view()
        # where a run stood still, and what tells two runs apart when
        # neither did (flight.py): the stalls beside the ring, the
        # iterations by kind, the event loop's lag
        h["stalls"] = self.flight.stalls()
        h.update(self.flight.totals())
        # what the process did to become useful, and every program it has
        # built since, by name and stage (obs/process.py)
        h["boot"] = PROCESS.boot()
        lag = PROCESS.loop_lag()
        if lag is not None:
            h["loop_lag_ms"] = lag
        if self.spec_drafter is not None:
            h["spec"] = {
                "drafter": self.spec_drafter.name,
                "k": self.spec_k,
                "mode": "batched",
                "steps": self.spec_steps,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
            }
        return h

    def begin_drain(self) -> None:
        """Flip the draining flag WITHOUT waiting: new submits raise
        EngineDraining and /health's engine block reports draining
        immediately — a fleet router probing /health stops routing here
        before the first bounced request, instead of discovering the
        drain from 503s. drain() calls this; the API's graceful shutdown
        calls it up front, before handing the blocking wait to an
        executor thread."""
        self._draining.set()
        self._wake.set()

    def retry_after_hint(self) -> int:
        """Seconds a shed/refused client should wait before retrying,
        derived from live state instead of a constant: a DOWN engine
        says the restore-probe interval (the soonest revival can
        happen), a backlogged engine scales with queue depth per slot —
        so routers and clients back off proportionally to the actual
        congestion."""
        down = self.supervisor.down_info()
        if down is not None:
            return max(int(self.supervisor.restore_interval_s) + 1, 5)
        depth = self.queue.depth()
        return max(1, min(30, 1 + (2 * depth) // max(self.slots, 1)))

    def drain(self, timeout: float | None = None) -> bool:
        """Graceful-shutdown phase 1: stop admission (new submits raise
        EngineDraining -> 503 + Retry-After) and wait for in-flight work —
        busy slots AND already-queued requests — to finish, up to timeout
        seconds. Returns True when the engine went idle; False means the
        timeout hit and close() will fail whatever is left. Safe to call
        from any thread; blocks the caller, not the scheduler."""
        self.begin_drain()
        deadline = None if timeout is None else now() + timeout
        while self.pool.busy_count or self.queue.depth() or self._preempted \
                or self._inflight is not None:
            if self.dead is not None or not self._thread.is_alive():
                return False
            if deadline is not None and now() >= deadline:
                return False
            time.sleep(0.02)
        return True

    def close(self, timeout: float = 5.0):
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=timeout)
        for req in self.queue.drain():
            self._fail(req, EngineDown("serve engine shut down"))
        if self._thread.is_alive():
            # scheduler still inside a device call (e.g. a long compile):
            # release the waiters but do NOT touch pool/_reqs/_layers —
            # racing the live thread's state would crash it mid-step
            # (_fail is benign if the scheduler later finishes the slot)
            self.dead = self.dead or RuntimeError(
                "serve engine shutdown timed out")
            for req in list(self._reqs):
                if req is not None:
                    self._fail(req, EngineDown("serve engine shut down"))
            return
        # ids still on the device belong to requests cancelled below
        self._inflight = None
        self._prefills.clear()
        for entry in self._drain_preempted():
            self._fail(entry.req, EngineDown("serve engine shut down"))
        for i, req in enumerate(self._reqs):
            if req is not None:
                self._finish(i, req, cancelled=True)

    def _drain_preempted(self) -> list:
        entries, self._preempted = self._preempted, []
        return entries

    # -- scheduler thread ---------------------------------------------------

    def _loop(self):
        """Supervision shell: the inner `_run` loop does the work; a
        failure escaping it goes to the supervisor's recovery state
        machine (classify -> rebuild-by-replay -> budget -> down). Only
        when the supervisor itself gives up (or breaks) does the engine
        fall to the legacy terminal `dead` state."""
        while not self._stop.is_set():
            try:
                self._run()
                return                      # clean _stop
            except BaseException as e:
                try:
                    recovered = self.supervisor.on_failure(e)
                except BaseException as sup_exc:
                    self._die(sup_exc)      # supervisor bug: last resort
                    return
                if not recovered:
                    self._die(e)
                    return

    def _run(self):
        while not self._stop.is_set():
            if self.supervisor.is_down():
                self._down_cycle()
                continue
            worked = self._step()
            self.supervisor.disarm()
            self.last_step = now()
            if worked:
                self.steps += 1
                self.supervisor.note_ok()
            else:
                # idle: block on the wake event (submit/cancel/close
                # all set it); the 0.5s timeout is only a heartbeat
                # for last_step, not a polling cadence
                self._wake.wait(0.5)
                self._wake.clear()

    def _die(self, e: BaseException):
        """Terminal failure: every waiter is released, loudly."""
        self.dead = e
        self._prefills.clear()      # their reqs are in _reqs below
        for entry in self._drain_preempted():
            self._fail(entry.req, e)
        for req in self.queue.drain():
            self._fail(req, e)
        for i, req in enumerate(self._reqs):
            if req is not None:
                req.result.setdefault("error", e)
                self._finish(i, req, cancelled=True, release=False)

    # -- degraded mode (rebuild budget exhausted) ---------------------------

    def _down_cycle(self):
        """One restore-loop turn while the engine is DOWN: shed whatever
        raced into the queue, wait CAKE_ENGINE_RESTORE_S, then probe the
        device with a trial prefill. Success rebuilds an empty pool and
        resumes serving; failure stays down for the next probe."""
        err = EngineDown("serve engine down: rebuild budget exhausted; "
                         "restore loop probing")
        for req in self.queue.drain():
            self._fail(req, err)
        if self._stop.wait(self.supervisor.restore_interval_s):
            return
        try:
            # recovery-grace watchdog limit: the trial may compile
            self.supervisor.arm("trial", (), grace=True)
            if self.kv_blocks > 0:
                state = PagedKV.build(self.model, self.slots, self.ctx,
                                      self.kv_blocks, self.kv_block_tokens,
                                      self.chunk)
                state.reserve_range(0, 0, 1)
                state.prefill_into(0, [1], 0)
                state.release_slot(0)
                jax.block_until_ready((state.pool, state.rows))
            else:
                layers = self.model.new_cache(self.slots,
                                              kv_len=self.ctx)["layers"]
                _, layers = self.model.prefill_chunk(layers, 0, [1], 0)
                layers = self.model.slot_release(layers, 0)
                state = layers
                # the dispatches above are async — a broken device
                # surfaces its error here, inside the probe's try, not
                # mid-serving
                jax.block_until_ready(layers)
            self.supervisor.disarm()
        except Exception as e:
            self.supervisor.disarm()
            self.supervisor.note_probe_failure(e)
            return
        self._revive(state)

    def _revive(self, state):
        """Trial step succeeded: adopt its (wiped) pool, fresh carries,
        fresh prefix cache, and rejoin the serving loop."""
        if self.kv_blocks > 0:
            self._init_device_state(paged=state)
        else:
            self._init_device_state(state)
        self.prefix_cache = self._build_prefix_cache()
        self.supervisor.clear_down()
        log.warning("serve engine revived: trial step succeeded, pool "
                    "rebuilt empty, admission reopened")

    def _step(self) -> bool:
        # kvshare mailbox FIRST — before the idle early-return below, so
        # an idle engine still serves blob export/import jobs (submit
        # sets _wake, which lands the _run loop here). It parks streams by
        # swapping out their carries, so an engine that has one runs at
        # depth 0 (see 4. below) and nothing is in flight here
        ks = self.kv_share
        if ks is not None:
            self._land_inflight()       # attached with a step in flight
            ks.run_pending()
        busy = self.pool.busy()
        queued = self.queue.depth() > 0
        # taken at once: an iteration that fails or finds nothing to do
        # leaves no stamp for the next one's gap
        t_prev, self._t_prev_end = self._t_prev_end, None
        if not (busy or queued or self._preempted
                or self._inflight is not None):
            return False
        # the iteration's id: its flight record's `seq`, carried by its
        # spans and by the timeline events it stamps
        step = self._step_id = self.flight.begin()
        with RECORDER.span("serve.step", cat="serve", slots=len(busy),
                           queued=self.queue.depth(), step=step):
            # reset failure-attribution context: a crash in the host
            # bookkeeping below must not implicate the PREVIOUS step's
            # request set (the decode/prefill dispatches and the fetch
            # re-arm with their own sets)
            self.supervisor.arm("step", ())
            # phase boundaries: one clock read each, every read ending one
            # phase and opening the next, so the phases cover the step. The
            # flight record always gets the split at the fetch; the span
            # recorder, when on, gets one child span per phase
            # (_emit_phases) — nothing else is paid per step when it is off
            rec_on = RECORDER.enabled
            t_sweep = now()
            emitted0, retired0 = self._emitted, self._retired
            dropped0, fetch_s0 = self._dropped, self._fetch_s
            joined0 = self._joined
            pc = self.prefix_cache
            restores0, restored0 = (pc.restores, pc.restored) if pc \
                else (0, 0)
            # 1. cancel sweeps: decoding slots, mid-prefill slots, and
            # abandoned-while-queued requests (those would otherwise pin
            # queue capacity and 429 live clients while slots sit idle).
            # A row finished here may still ride the step in flight: its
            # id is dropped when that step is fanned out
            prefilling = {p.slot for p in self._prefills}
            for i in busy:
                req = self._reqs[i]
                if req is not None and req.cancelled.is_set() \
                        and i not in prefilling:
                    self._finish(i, req, cancelled=True)
            for pf in [p for p in self._prefills
                       if p.req.cancelled.is_set()]:
                self._abort_prefill(pf, None)
            for entry in [e for e in self._preempted
                          if e.req.cancelled.is_set()
                          or e.req.done.is_set()]:
                self._preempted.remove(entry)
                self._fail(entry.req, None)
            for req in self.queue.purge(lambda r: r.cancelled.is_set()):
                self._fail(req, None)
            # queue-deadline sweep: a request that has waited past
            # CAKE_QUEUE_DEADLINE_S is 503ed here rather than admitted
            # into a slot for a client that already gave up
            if self.queue_deadline_s > 0:
                cutoff = now() - self.queue_deadline_s
                for req in self.queue.purge(
                        lambda r: r.t_enqueue < cutoff):
                    SERVE_QUEUE_TIMEOUTS.inc()
                    self._fail(req, QueueDeadlineExceeded(
                        now() - req.t_enqueue))
            # request-deadline sweep (CAKE_REQUEST_DEADLINE_S): ADMITTED
            # requests whose TOTAL age expired are cancelled with a typed
            # 504 — the queue sweep above only covers waiting, so without
            # this a slow decode could hold a slot long past the point
            # every client timeout has fired
            if self.request_deadline_s > 0:
                cutoff = now() - self.request_deadline_s
                for i in self.pool.busy():
                    req = self._reqs[i]
                    if req is None or req.t_enqueue >= cutoff:
                        continue
                    SERVE_REQUEST_TIMEOUTS.inc()
                    err = RequestDeadlineExceeded(
                        now() - req.t_enqueue, self.request_deadline_s)
                    pf = next((p for p in self._prefills if p.slot == i),
                              None)
                    if pf is not None:
                        self._abort_prefill(pf, err)
                    else:
                        req.result["error"] = err
                        self._finish(i, req, cancelled=True)
                for entry in [e for e in self._preempted
                              if e.req.t_enqueue < cutoff]:
                    self._preempted.remove(entry)
                    SERVE_REQUEST_TIMEOUTS.inc()
                    self._fail(entry.req, RequestDeadlineExceeded(
                        now() - entry.req.t_enqueue,
                        self.request_deadline_s))
            t_admit = now()
            busy0 = self.pool.busy_count
            # 2. preempted slots resume FIRST (oldest-first, as soon as a
            # slot + enough blocks free up — their clients are mid-stream),
            # then every queued request takes a free slot, so several
            # admissions are in flight at once. The prefill itself is
            # chunked below; what an admission costs HERE, in front of the
            # decode dispatch, is a prefix hit's restore: one program a
            # power-of-two piece of the matched chain (PrefixCache.splice;
            # the record's `restores` / `restored`), or in paged mode a
            # table publish and a row install
            if self._preempted:
                self._resume_preempted()
            while self.pool.free_count > 0 and self._start_admission():
                pass
            if not (self.pool.busy_count or self.queue.depth()
                    or self._inflight is not None):
                # only parked entries remain and none could resume yet:
                # report idle so _run waits on the wake event (0.5s
                # heartbeat retries the resume) instead of hot-spinning
                return False
            t_plan = now()
            admitted = self.pool.busy_count - busy0
            # 3. dispatch ONE batched step over the slots whose prefill
            # has completed (mid-prefill rows ride along frozen under the
            # active mask): a speculative verify step when the drafter
            # proposed for ANY slot — every slot's draft window rides the
            # same dispatch with ragged per-slot acceptance — else a
            # plain batched decode. Both paths cost exactly one device
            # call and one fetch per iteration.
            # 3a. choose the admission to advance this iteration (round-
            # robin) and, in paged mode, reserve its chunk's blocks NOW —
            # BEFORE the decode dispatch. The reservation may preempt a
            # decoding victim, and preemption is only safe on carries the
            # host has fanned out: a swap-out behind a step whose ids are
            # still on the device would capture a sampled token the host
            # never saw, silently dropping it from the stream on resume
            # (_preempt_one lands the step in flight first)
            self._cur_nd = {}
            pf_job = None
            if self._prefills:
                pf_job = self._prefills[self._rr % len(self._prefills)]
                if self.paged is not None:
                    pf_job = self._prepare_prefill(pf_job)
            prefilling = {p.slot for p in self._prefills}   # post-admission
            active = [i for i in self.pool.busy()
                      if self._reqs[i] is not None and i not in prefilling]
            # 3b. host-side draft building (the n-gram lookup reads the
            # tokens the host holds, all of them: a drafter's engine runs
            # at depth 0)
            spec_job = None
            if active and self.spec_drafter is not None:
                spec_job = self._build_drafts(active)
            if self.paged is not None and active:
                # every decoding slot needs blocks for its write frontier
                # — and, when speculating, its whole draft window —
                # mapped BEFORE dispatch; exhaustion preempts a victim
                # (which may shrink `active`) — see _ensure_decode_blocks
                active = self._ensure_decode_blocks(active, spec_job)
            cur = None
            nb = kv_tokens = ring_tokens = state_bytes = 0
            t_dispatch = now()
            spec_acc0 = self.spec_accepted
            if active:
                # the rows the dispatched program runs: the contiguous one
                # takes the whole pool in place under the active mask, the
                # paged twins the bucketed occupied prefix of their `rows`
                nb = self.slots if self.paged is None else \
                    slot_bucket(active[-1] + 1, self.slots)
                SERVE_BATCH_OCCUPANCY.observe(len(active))
                rows = [(i, self._reqs[i]) for i in active]
                # what the active rows hold (prompt + generated so far, as
                # far as the host has them): nb * the context length minus
                # this is the part of the pool a step that walks rows to
                # their frontier leaves unread
                held = [len(r.prompt_ids) + len(r.tokens) for _, r in rows]
                kv_tokens = sum(held)
                # and what they hold in window rings: min(frontier, window)
                # a layer, whatever the row's length
                ring_tokens = sum(min(n, w) for n in held
                                  for w in self._ring_sizes)
                # and what they hold that no position addresses: recurrent
                # state, read and written whole whatever the row's length
                state_bytes = self._row_state_bytes * len(active)
                # arm BEFORE the fault hook: an injected stall simulates a
                # dispatch stuck on the device, and the watchdog must see
                # it; real crashes here implicate every active request
                cur = _InFlight(step, rows, nb, spec_job)
                self.supervisor.arm("decode", cur.ids)
                hook = faults.FAULT_HOOK
                if hook is not None:
                    hook.on_decode([r for _, r in rows])
                if spec_job is not None:
                    drafts, n_drafts = spec_job
                    # static no-vocab-filters fast path: when no slot in
                    # the dispatch uses top-k/top-p the accept rule skips
                    # its per-row sorts (at most one extra executable —
                    # traffic mixes flip between two programs, both warm
                    # in steady state)
                    filt = any(config_has_filters(r.sampling)
                               for _, r in rows)
                    with RECORDER.span("spec.verify", cat="serve",
                                       slots=len(active),
                                       drafts=int(n_drafts.sum())):
                        if self.paged is not None:
                            (packed, self.paged.pool, self.paged.rows,
                             self._toks, self._pos, self._rngs,
                             self._recents) = self.model.spec_slots_paged(
                                self.paged.pool, self.paged.rows,
                                self.paged.tables, self._toks, self._pos,
                                self._rngs, self._recents, self._temps,
                                self._top_ks, self._top_ps, self._pens,
                                self._act, drafts, n_drafts, nb=nb,
                                filt=filt)
                        else:
                            (packed, self._layers, self._toks, self._pos,
                             self._rngs,
                             self._recents) = self.model.spec_slots(
                                self._layers, self._toks, self._pos,
                                self._rngs, self._recents, self._temps,
                                self._top_ks, self._top_ps, self._pens,
                                self._act, drafts, n_drafts, filt=filt)
                elif self.paged is not None:
                    (packed, self.paged.pool, self.paged.rows, self._toks,
                     self._pos, self._rngs,
                     self._recents) = self.model.decode_slots_paged(
                        self.paged.pool, self.paged.rows, self.paged.tables,
                        self._toks, self._pos, self._rngs, self._recents,
                        self._temps, self._top_ks, self._top_ps, self._pens,
                        self._act, nb=nb)
                else:
                    (packed, self._layers, self._toks, self._pos,
                     self._rngs, self._recents) = self.model.decode_slots(
                        self._layers, self._toks, self._pos, self._rngs,
                        self._recents, self._temps, self._top_ks,
                        self._top_ps, self._pens, self._act)
                cur.packed = packed
            # 4. ONE host fetch per iteration, and it LAGS the dispatch by
            # one: the ids fetched here are those of the step the PREVIOUS
            # iteration dispatched, whose successor is queued behind it
            # already — so the device goes from one step into the next
            # while the host fans out, sweeps, admits and plans. Every
            # carry the successor needed was device-resident; what the
            # host could not know when it dispatched (the previous step
            # sampled EOS, spent a budget, or its client left) costs that
            # row one more step whose id `_fanout` drops. The ids go out
            # to their streams at once, before the chunk below: what the
            # chunk's dispatch costs the host must not hold back tokens
            # that are already here. (An iteration with nothing to
            # dispatch lands what is in flight all the same and leaves
            # nothing behind, so the engine never idles on a step; a paged
            # preemption lands it before it swaps anything out,
            # `_preempt_one`.)
            prev = self._inflight
            lag = int(prev is not None and cur is not None)
            # The depth is 0 — the step just dispatched is landed in its
            # own iteration, AFTER the chunk (6.) — where the next plan
            # needs every id on the host: a drafter proposes from
            # `req.tokens`, kvshare parks streams by their carries. Such
            # an engine never has a step in flight here.
            keep = self.spec_drafter is None and ks is None
            self._inflight = cur if keep else None
            of_step = t_fan = None
            t_land = t_mid = now()
            if prev is not None:
                of_step, t_fan = prev.step, self._land(prev, t_land)
                t_mid = t_fan       # the record's split of fetch | fanout
            t_chunk = now()
            self._chunk_end = self._chunk_kind = self._finish_id = None
            # 5. ...then advance the chosen admission by one chunk, AFTER
            # the lagged fetch: the step fetched has ended, so the chunk
            # queued behind it has begun and at most one other waits
            # behind the running one (a reader of the trace ties a chunk's
            # execution to the last `serve.prefill_chunk` span that began
            # before it). The device's queue still holds this iteration's
            # decode step. (Its blocks were reserved in 3a; the job may
            # have been requeued by a decode slot's own preemption since,
            # hence the membership re-check.)
            if pf_job is not None and pf_job in self._prefills:
                idx = self._prefills.index(pf_job)
                if self._advance_prefill(pf_job):
                    self._rr = idx + 1      # still in flight: move past it
                else:
                    self._rr = idx          # removed: next job slid here
            # 6. at depth 0 this iteration's own step lands here, behind
            # the chunk's dispatch: the chunk runs while the host fans out
            t_late = now()
            if cur is not None and not keep:
                of_step, t_fan = cur.step, self._land(cur, t_late)
            t_end = now()
            tokens = self._emitted - emitted0
            dropped = self._dropped - dropped0
            if rec_on:
                self._emit_phases(
                    step, (t_sweep, t_admit, t_plan, t_dispatch, t_land,
                           t_chunk, t_late, t_end), t_fan,
                    admitted=admitted, slots=len(active), bucket=nb,
                    kv_tokens=kv_tokens, ring_tokens=ring_tokens,
                    state_bytes=state_bytes, decoded=bool(active),
                    of_step=of_step, lag=lag,
                    tokens=tokens, finished=self._retired - retired0,
                    dropped=dropped)
            # flight record: one bounded dict per iteration — the black
            # box the supervisor dumps on wedge/DOWN (see flight.py).
            # fetch_ms is the scheduler blocked on the device, host_ms the
            # rest of the step: which side of the fetch a slow step was on.
            # `ph` is the same stamps phase by phase (flight.PHASES; the
            # lagged landing splits at `t_fan`), `gap_ms` the `_run` loop's
            # own time since the previous iteration, when that left work
            fetch_s = self._fetch_s - fetch_s0
            wall_ms = round((t_end - t_sweep) * 1e3, 3)
            fetch_ms = round(fetch_s * 1e3, 3)
            stamps = (t_sweep, t_admit, t_plan, t_dispatch, t_land, t_mid,
                      t_chunk, t_late, t_end)
            rec = {
                "kind": self._chunk_kind
                or ("decode" if active else "idle"),
                "joined": self._joined - joined0,
                "restores": pc.restores - restores0 if pc else 0,
                "restored": pc.restored - restored0 if pc else 0,
                "wall_ms": wall_ms,
                "gap_ms": 0.0 if t_prev is None
                else round((t_sweep - t_prev) * 1e3, 3),
                "ph": [round((b - a) * 1e3, 3)
                       for a, b in zip(stamps, stamps[1:])],
                "occupancy": len(active), "bucket": nb,
                "kv_tokens": kv_tokens, "ring_tokens": ring_tokens,
                "state_bytes": state_bytes,
                "host_ms": round(wall_ms - fetch_ms, 3),
                "fetch_ms": fetch_ms,
                "lag": lag, "of_step": of_step, "dropped": dropped,
                "queued": self.queue.depth(),
                "prefilling": len(self._prefills),
                "spec_accepted": self.spec_accepted - spec_acc0,
            }
            if self.paged is not None:
                rec["kv_free"] = self.paged.alloc.free_count
                rec["kv_used"] = self.paged.alloc.used_count
            self.flight.record(step, **rec)
            if self.pool.busy_count or self._inflight is not None \
                    or self.queue.depth():
                self._t_prev_end = t_end
        return True

    def _land(self, fl: _InFlight, t0: float) -> float:
        """Fetch one dispatched step's ids and fan them out to their
        streams. `t0` is the caller's stamp at the call: the fetch is timed
        from it, so the record's `fetch` phase and its `fetch_ms` come from
        the same two clock reads. Returns the stamp between the fetch and
        the fan-out. `_step` lands the previous step once a successor is
        queued behind it (or its own, at depth 0); `_land_inflight` lands
        it out of that order. Either way the iteration's record takes its
        `fetch_ms`, tokens and `dropped` from the running totals this moves.

        The fetch is where an async device failure (or a wedge) of that
        step materializes: armed with ITS request set, whichever iteration
        dispatched it and whatever was dispatched since; a crash in the
        fan-out implicates the same set."""
        self.supervisor.arm("decode", fl.ids)
        # lint: disable=host-sync — THE one planned fetch per iteration: the
        # packed ids ([input;sampled], or [input;n_acc;next] on a
        # speculative step) for every slot in one transfer
        arr = np.asarray(fl.packed)
        t1 = now()
        self._fetch_s += t1 - t0
        busy = self.pool.busy_count
        self._fanout(fl, arr)
        self._retired += busy - self.pool.busy_count
        return t1

    def _land_inflight(self):
        """Land the step in flight NOW, if there is one: the caller is
        about to read or move carries and needs every sampled id on the
        host first. Under no span of its own."""
        fl, self._inflight = self._inflight, None
        if fl is not None:
            self._land(fl, now())

    def _emit_phases(self, step: int, t: tuple, t_fan: float | None, *,
                     admitted: int, slots: int, bucket: int, kv_tokens: int,
                     ring_tokens: int, state_bytes: int, decoded: bool,
                     of_step: int | None,
                     lag: int, tokens: int, finished: int, dropped: int):
        """The children of `serve.step` from the step's own stamps (seconds
        on obs.now()'s clock, which is the recorder's), in the order the
        step runs them: sweep, admit, plan, this step's decode dispatch,
        the fetch of the ids of step `of_step` and their fan-out (`t_fan`
        is the stamp between the two), the chunk — or, at depth 0, the
        chunk and then the fetch and the fan-out of this step's own ids.
        The prefill interval is split at the end of the chunk's dispatch:
        the `serve.prefill_chunk` span records itself (the roofline reader
        ties device executions to it), the remainder is
        `serve.prefill_finish`. Recorder-on only."""
        t_sweep, t_admit, t_plan, t_dispatch, t_land, t_chunk, \
            t_late, t_end = (int(x * 1e6) for x in t)

        def add(name, t0, t1, **args):
            RECORDER.add(name, t0, t1 - t0, cat="serve", step=step, **args)

        def landed(t0, t1):
            mid = int(t_fan * 1e6)
            add("serve.fetch", t0, mid, of_step=of_step, lag=lag)
            add("serve.fanout", mid, t1, of_step=of_step, lag=lag,
                tokens=tokens, finished=finished, dropped=dropped)

        add("serve.sweep", t_sweep, t_admit)
        add("serve.admit", t_admit, t_plan, admitted=admitted)
        add("serve.plan", t_plan, t_dispatch)
        if decoded:
            add("serve.decode_dispatch", t_dispatch, t_land,
                slots=slots, bucket=bucket, kv_tokens=kv_tokens,
                ring_tokens=ring_tokens, state_bytes=state_bytes)
        if of_step is not None and of_step != step:
            landed(t_land, t_chunk)
        if self._chunk_end is not None:
            add("serve.prefill_finish", int(self._chunk_end * 1e6), t_late,
                sid=self._finish_id,
                final=self._chunk_kind == "last_chunk")
        if of_step == step:
            landed(t_late, t_end)

    # -- chunked admission --------------------------------------------------

    def _start_admission(self) -> bool:
        """Move the first live queued request into a free slot as an
        in-flight chunked prefill; splice any cached shared prefix so only
        the suffix needs compute. Returns False when the queue is empty."""
        while True:
            req = self.queue.pop()
            if req is None:
                return False
            if req.cancelled.is_set():
                self._fail(req, None)   # abandoned while queued
                continue
            break
        SERVE_QUEUE_WAIT_SECONDS.observe(now() - req.t_enqueue)
        slot = self.pool.alloc()
        # register BEFORE any fallible device work: if anything below (or
        # the loop itself) dies, the crash handler finds the request in
        # _reqs and releases its waiter instead of hanging the client
        self._reqs[slot] = req
        req.slot = slot
        req.admitted.set()
        req.stats = {"queue_wait_s": now() - req.t_enqueue}
        if req.continuation:
            req.stats["continuation"] = True
        TIMELINES.event(req.id, "admit", slot=slot, qos=req.qos,
                        queue_wait_ms=round(
                            req.stats["queue_wait_s"] * 1e3, 3))
        self._begin_prefill(_Prefill(req, slot))
        SERVE_SLOTS_BUSY.set(self.pool.busy_count)
        return True

    def _begin_prefill(self, pf: _Prefill) -> bool:
        """Open a chunked admission for an already-registered request:
        splice any cached shared prefix, then put it in flight. Shared by
        fresh admissions and rebuild restarts. Returns False (request
        failed) when the splice dies."""
        set_request_id(pf.req.id)
        try:
            if self.prefix_cache is not None:
                pf.keys = self.prefix_cache.chain_keys(pf.ids)
                matched = self.prefix_cache.match(pf.ids, pf.keys)
                if matched:
                    built = PROCESS.backend_count
                    self._layers = self.prefix_cache.splice(
                        self._layers, pf.slot, pf.keys, matched)
                    if PROCESS.backend_count != built:
                        self._note_compiles(pf.req, built)
                    pf.pos = matched * self.chunk
                    pf.next_block = matched
                    pf.hit_tokens = pf.pos
                    TIMELINES.event(pf.req.id, "prefix_hit",
                                    tokens=pf.hit_tokens)
        except Exception as e:
            self._abort_prefill(pf, e, register=False)
            return False
        finally:
            set_request_id(None)
        self._prefills.append(pf)
        return True

    def _advance_prefill(self, pf: _Prefill) -> bool:
        """Prefill ONE chunk of an in-flight admission into its pool row;
        capture any block the chunk completed into the prefix cache; on
        the final chunk, sample the first token and activate the slot for
        decode. Returns True while the job remains in flight."""
        take = min(self.chunk, pf.n - pf.pos)
        set_request_id(pf.req.id)
        # the programs this may build in-band: the chunk's bucket, a block's
        # extract, the join (one integer compare when none was)
        built = PROCESS.backend_count
        try:
            with RECORDER.span("serve.prefill_chunk", cat="serve",
                               tokens=take, pos0=pf.pos, slot=pf.slot,
                               step=self._step_id):
                self.supervisor.arm("prefill", (pf.req.id,))
                hook = faults.FAULT_HOOK
                if hook is not None:
                    hook.on_prefill(pf.req)
                if self.paged is not None:
                    logits = self.paged.prefill_into(
                        pf.slot, pf.ids[pf.pos:pf.pos + take], pf.pos)
                else:
                    logits, self._layers = self.model.prefill_chunk(
                        self._layers, pf.slot,
                        pf.ids[pf.pos:pf.pos + take], pf.pos)
            pf.pos += take
            pf.chunks += 1
            self._chunk_kind = "last_chunk" if pf.pos >= pf.n else "chunk"
            if RECORDER.enabled:
                # where serve.prefill_finish begins (_emit_phases), and the
                # id it will have: its children are recorded before it is
                self._chunk_end = now()
                self._finish_id = new_span_id()
            TIMELINES.event(pf.req.id, "prefill_chunk", step=self._step_id,
                            pos0=pf.pos - take, tokens=take,
                            attn=self.model.last_chunk_attn)
            pf.next_block = self._capture_blocks(pf.ids, pf.slot, pf.pos,
                                                 pf.n, pf.next_block,
                                                 pf.keys)
            if pf.pos >= pf.n:
                self._complete_prefill(pf, logits)
                return False
            return True
        except Exception as e:
            # request-scoped containment first: the admission dies alone
            # (poison isolation for free — a prompt that crashes its own
            # prefill never takes the pool with it)...
            self._abort_prefill(pf, e)
            if classify(e) in ("device", "oom"):
                # ...but a device/oom failure impeaches the WHOLE pool's
                # state, not just this row: escalate to the supervisor
                raise
            return False
        finally:
            if PROCESS.backend_count != built:
                self._note_compiles(pf.req, built)
            set_request_id(None)

    def _note_compiles(self, req: ServeRequest, built: int):
        """A dispatch for `req` built programs in-band (PROCESS's count of
        backend stages grew past `built` around it): its timeline says
        which, and which step paid."""
        for rec in PROCESS.built_since(built):
            SERVE_INBAND_COMPILES.inc(program=rec["program"])
            TIMELINES.event(req.id, "compile", program=rec["program"],
                            ms=round(rec["seconds"] * 1e3, 3),
                            cache=rec["cache"], step=self._step_id)

    def _complete_prefill(self, pf: _Prefill, logits):
        """Final chunk done: sample the first token (device-resident — it
        rides the next decode iteration's packed fetch) and hand the slot
        to the batched decode, in ONE dispatch behind the chunk's
        (TextModel.slot_join: the key derivation, the sample and every
        carry's write at `slot`). The scheduler ships two small arrays and
        goes round to dispatch the next decode step while the chunk runs."""
        req, slot, scfg = pf.req, pf.slot, pf.req.sampling
        seq, self._seq = self._seq, self._seq + 1
        (self._toks, self._pos, self._rngs, self._recents, self._temps,
         self._top_ks, self._top_ps, self._pens,
         self._act) = self.model.slot_join(
            logits, self._base_rng, self._toks, self._pos, self._rngs,
            self._recents, self._temps, self._top_ks, self._top_ps,
            self._pens, self._act, slot=slot, seq=seq, n=pf.n,
            **_traced_sampling(scfg, self._vocab))
        self._joined += 1
        SERVE_SLOT_JOINS.inc()
        self._prefills.remove(pf)
        req.budget = min(req.max_new_tokens - 1, self.ctx - pf.n - 1)
        req._first_pending = True       # emitted at the next decode fetch
        # ttft_s is stamped when the first token is FETCHED (everything
        # above is an async dispatch — stamping here would understate the
        # client's real wait)
        req.stats["prefill_chunks"] = pf.chunks
        req.stats["prefix_hit_tokens"] = pf.hit_tokens
        SERVE_PREFILL_CHUNKS.observe(max(pf.chunks, 1))
        TIMELINES.event(req.id, "prefill_done", chunks=pf.chunks,
                        hit_tokens=pf.hit_tokens)

    def _capture_blocks(self, ids, slot: int, pos: int, n: int,
                        next_block: int, keys: list) -> int:
        """Insert every prefix-cache block the chunk that just landed
        completed — captured at the boundary while the row state IS that
        exact prefix (the linear-attention snapshot is only right there).
        The block holding the final token is never cached (its logits
        must be computed live to seed sampling), hence the n-1 cap.
        Shared by admission and crash-replay so the boundary rule cannot
        drift between them. Returns the next uncaptured block index."""
        if self.prefix_cache is None:
            return next_block
        last = min(pos, n - 1) // self.chunk    # blocks complete by now
        if next_block >= last:
            return next_block
        # recorder on: a child of the step's serve.prefill_finish, with the
        # cache's own spans beneath it (a replay's capture, outside a
        # step's chunk, hangs under whatever span is open)
        with RECORDER.span("serve.capture_blocks", cat="serve",
                           parent=self._finish_id, step=self._step_id,
                           blocks=last - next_block):
            for block in range(next_block, last):
                self.prefix_cache.insert(self._layers, slot, ids, block,
                                         keys)
        return last

    def _set_slot_sampling(self, slot: int, scfg: SamplingConfig):
        """Write a request's sampling params into the slot's traced
        carries, one scatter each: crash replay and preemption resume (a
        prompt's end writes them inside the join)."""
        v = _traced_sampling(scfg, self._vocab)
        self._temps = self._temps.at[slot].set(v["temp"])
        self._top_ks = self._top_ks.at[slot].set(v["top_k"])
        self._top_ps = self._top_ps.at[slot].set(v["top_p"])
        self._pens = self._pens.at[slot].set(v["penalty"])

    def _abort_prefill(self, pf: _Prefill, error: BaseException | None,
                       register: bool = True):
        """Tear down a mid-prefill admission (client cancel or device
        failure): release the waiter, free the slot, wipe the half-built
        row. The wipe comes LAST and must still escalate on failure —
        splice and prefill_chunk assume a clean row, so a failed wipe
        cannot silently hand ghost KV to the row's next occupant (the
        supervisor's rebuild reallocates the pool). But it must not MASK
        the original error either: the step failure stays the exception
        being raised, the wipe failure rides its __cause__ — first
        exception wins, nothing swallowed or substituted."""
        if register:
            self._prefills.remove(pf)
        self._reqs[pf.slot] = None
        self.pool.free(pf.slot)
        SERVE_SLOTS_BUSY.set(self.pool.busy_count)
        self._fail(pf.req, error)
        try:
            self._release_row(pf.slot)
        except Exception as wipe_exc:
            if error is not None:
                raise error from wipe_exc
            raise

    def _release_row(self, slot: int):
        """Per-request row release, mode-dispatched: contiguous wipes the
        pool row; paged derefs the slot's blocks (shared blocks survive
        under the prefix cache / other slots) and wipes only the SWA/
        linear rows — freed pool blocks need no wipe thanks to the
        gather's stale-tenant pos guard."""
        if self.paged is not None:
            self.paged.release_slot(slot)
        else:
            self._layers = self.model.slot_release(self._layers, slot)

    # -- paged-pool pressure: reserve / preempt / resume --------------------

    def _prepare_prefill(self, pf: _Prefill):
        """Reserve the blocks pf's next chunk will write — called BEFORE
        the decode dispatch so any preemption it triggers sees pre-step
        carries (see _step 3a). Returns pf when the chunk may dispatch;
        None when the admission was failed typed. Reservation failure
        implies the pool is exhausted with pf as the ONLY occupant
        (_reserve_blocks evicts the prefix cache, preempts every
        decoding slot, and requeues every other admission before giving
        up), so the prompt can never fit and parking would hang it."""
        take = min(self.chunk, pf.n - pf.pos)
        got = self._reserve_blocks(pf.slot, pf.pos, take,
                                   requester=pf.req)
        if got == "self":
            # every reclaimable block is held by HIGHER-class work:
            # this admission parks itself (clean restart — nothing
            # emitted) and retries when blocks free, instead of
            # evicting an interactive slot to admit a batch prompt
            self._requeue_admission(pf)
            return None
        if got:
            return pf
        self._abort_prefill(pf, KVPoolExhausted(
            f"KV pool exhausted admitting {pf.req.id}: the prompt needs "
            "more blocks than the pool can ever free"))
        return None

    def _reserve_blocks(self, slot: int, pos0: int, n: int,
                        requester=None):
        """Back positions [pos0, pos0+n) of `slot` with physical blocks,
        evicting prefix-cache LRU (inside the allocator) and then
        preempting victims (QoS policy via _preempt_one) until it fits.
        "self" = only higher-class work holds blocks, the caller must
        park itself; False = nothing left to reclaim."""
        while not self.paged.reserve_range(slot, pos0, n):
            got = self._preempt_one(exclude=slot, requester=requester)
            if got is not True:
                return got
        return True

    def _ensure_decode_blocks(self, active: list[int],
                              spec_job=None) -> list[int]:
        """Back every decoding slot's write reach with physical blocks
        before the batched dispatch: the write-frontier block for a plain
        decode step, the whole speculative frontier [wp, wp + n_draft]
        when the slot carries a draft window (the verify may commit up to
        n_draft + 1 positions — reserving past the frontier is what lets
        the block cursor move by accepted length without a mid-program
        allocation). Exhaustion evicts prefix-cache LRU, then rolls back
        other slots' speculative tails, then preempts a victim; a slot
        that cannot grow with NOTHING left to reclaim is failed typed
        rather than wedging the scheduler. Returns the surviving active
        list (preemption and failure both shrink it)."""
        n_drafts = spec_job[1] if spec_job is not None else None
        for i in active:
            req = self._reqs[i]
            if req is None:
                continue        # preempted by an earlier slot's ensure
            # the row's write position as the DEVICE has it: one past the
            # host's count while a step that carries the row is in flight
            # (landing that step moves a token from the one to the other)
            fl = self._inflight
            wp = len(req.prompt_ids) + max(len(req.tokens) - 1, 0) \
                + (fl is not None and (i, req) in fl.rows)
            reach = 1 + (int(n_drafts[i]) if n_drafts is not None else 0)
            while self._reqs[i] is req \
                    and not self.paged.reserve_range(i, wp, reach):
                if reach > 1:
                    # speculation never costs anyone their blocks: under
                    # pressure the slot DROPS its draft window to a plain
                    # decode step (n_drafts gates it out of the dispatch)
                    # and retries with just the write-frontier block —
                    # preemption and typed failure stay reserved for the
                    # growth a non-speculating engine would need too
                    reach = 1
                    n_drafts[i] = 0
                    self._cur_nd[i] = 0
                    continue
                got = self._preempt_one(exclude=i, requester=req)
                if got == "self":
                    # the only reclaimable space is held by HIGHER-class
                    # work: this slot parks itself (swap/recompute — it
                    # resumes bit-identical when blocks free) instead of
                    # kicking an interactive admission back to the queue
                    self._preempt_slot(i, req)
                    break
                if not got:
                    req.result["error"] = KVPoolExhausted(
                        f"KV pool exhausted: request {req.id} cannot "
                        f"grow past {wp} tokens and nothing is left to "
                        "reclaim")
                    self._finish(i, req, cancelled=True)
                    break
        return [i for i in active if self._reqs[i] is not None]

    def _preempt_one(self, exclude: int, requester=None):
        """Free blocks by reclaiming the cheapest thing first: other
        slots' speculative frontier tails (pure rollback — nobody loses
        work), then a DECODING victim (QoS policy: lowest class first,
        LIFO within a class — the cheapest to redo, and the oldest
        request in its class can never be starved by newcomers), else
        an OTHER in-flight admission goes back to readmission (it has
        emitted nothing, so a restart is clean; lowest class, youngest
        first). When the only candidate admission outranks `requester`'s
        class, returns "self": the caller's slot must park itself
        rather than displace higher-class work (a batch decoder never
        requeues an interactive admission). False = nothing left to
        reclaim or preempt.

        Before any of it, the step in flight lands: a victim's carries
        and `req.tokens` must hold every id its row has sampled (a
        swap-out behind unfetched ids would lose one on resume), and what
        that fan-out finishes frees blocks for nothing."""
        if self._inflight is not None:
            self._land_inflight()
            return True
        if self.spec_drafter is not None and self._trim_spec_tails(exclude):
            return True

        def outranks(r):
            return requester is not None and \
                priority(class_of(r)) > priority(class_of(requester))
        prefilling = {p.slot for p in self._prefills}
        cands = [(i, self._reqs[i]) for i in self.pool.busy()
                 if i not in prefilling]
        victim = choose_victim(cands, exclude=exclude)
        others = [p for p in self._prefills if p.slot != exclude]
        pick = max(others, key=lambda p: victim_rank(p.req)) \
            if others else None
        # evict in policy order, but never displace strictly-higher-
        # class work: a protected victim falls through to the admission
        # check (a lower-class admission may still be requeued — the
        # review caught the early "self" return inverting priority when
        # e.g. a standard decode was blocked by interactive decodes
        # while a batch prefill held reclaimable blocks)
        if victim is not None and not outranks(victim[1]):
            self._preempt_slot(*victim)
            return True
        if pick is not None and not outranks(pick.req):
            self._requeue_admission(pick)
            return True
        if victim is not None or pick is not None:
            return "self"       # only higher-class work holds blocks
        return False

    def _preempt_slot(self, slot: int, req: ServeRequest):
        """Evict one decoding slot to free its blocks. Swap mode keeps
        the bytes host-side — resume is bit-identical even for SAMPLED
        streams (the RNG carry rides the blob); recompute mode drops
        them and replays at resume (greedy bit-identical, the rebuild
        parity rule)."""
        wp = len(req.prompt_ids) + max(len(req.tokens) - 1, 0)
        if self.preempt_mode == "swap":
            # roll back the speculative frontier first: a swapped-out
            # victim must carry only COMMITTED state — uncommitted
            # draft-window blocks return to the pool instead of riding
            # the blob into host RAM and back
            self.paged.trim_to(slot, wp)
            blob = self.paged.swap_out(
                slot, (self._toks, self._pos, self._rngs, self._recents))
            entry = PreemptedSlot(req, "swap", wp, blob)
        else:
            self.paged.release_slot(slot)
            if not req.tokens:
                req._first_pending = False  # unfetched 1st token is lost
            entry = PreemptedSlot(req, "recompute", wp)
        SERVE_PREEMPTIONS.inc(mode=entry.mode)
        TIMELINES.event(req.id, "preempt", mode=entry.mode, tokens=wp)
        self.pool.free(slot)
        self._reqs[slot] = None
        req.slot = None
        self._act = self._act.at[slot].set(False)
        self._toks = self._toks.at[slot].set(0)
        self._pos = self._pos.at[slot].set(0)
        self._preempted.append(entry)
        SERVE_SLOTS_BUSY.set(self.pool.busy_count)
        log.warning("preempted slot %d (%s, %d tokens): KV pool "
                    "exhausted", slot, entry.mode, wp)

    def _requeue_admission(self, pf: _Prefill):
        """Push a mid-prefill admission back to readmission to free its
        blocks (no tokens emitted yet — a clean restart, ordered ahead
        of every queued request via the preempted list)."""
        self._prefills.remove(pf)
        self.paged.release_slot(pf.slot)
        self.pool.free(pf.slot)
        self._reqs[pf.slot] = None
        pf.req.slot = None
        SERVE_PREEMPTIONS.inc(mode="recompute")
        TIMELINES.event(pf.req.id, "preempt", mode="requeue",
                        tokens=pf.pos)
        # resume gate = the WHOLE prompt's blocks (submit already
        # validated it fits an empty pool): gating on fewer would
        # re-admit the prefill while higher-class work still holds the
        # pool, and the "self" park path would bounce it back every
        # scheduler iteration — preempt/resume churn in the counters,
        # the timeline ring, and the log
        self._preempted.append(
            PreemptedSlot(pf.req, "recompute",
                          max(len(pf.req.prompt_ids) - 1, 0)))
        SERVE_SLOTS_BUSY.set(self.pool.busy_count)
        log.warning("readmitting request %s: KV pool exhausted "
                    "mid-prefill", pf.req.id)

    def _resume_preempted(self):
        """Oldest-first resume of preempted requests: swap entries
        re-allocate blocks and restore bytes + carries; recompute
        entries replay prompt + generated[:-1] through chunked prefill.
        Stops at the first entry that does not fit yet — strict FIFO, so
        a big parked request cannot be starved by smaller ones behind
        it."""
        while self._preempted and self.pool.free_count > 0:
            entry = self._preempted[0]
            req = entry.req
            if entry.mode == "swap":
                slot = self.pool.alloc()
                if not self.paged.swap_in(slot, entry.blob):
                    self.pool.free(slot)
                    self._fail_unresumable(entry)
                    return              # blocks still short; wait
                self._preempted.pop(0)
                toks_b, pos_b, rngs_b, recents_b = entry.blob["carries"]
                self._toks = self._toks.at[slot].set(int(toks_b))
                self._pos = self._pos.at[slot].set(int(pos_b))
                self._rngs = self._rngs.at[slot].set(jnp.asarray(rngs_b))
                self._recents = self._recents.at[slot].set(
                    jnp.asarray(recents_b))
                self._set_slot_sampling(slot, req.sampling)
                self._act = self._act.at[slot].set(True)
                self._reqs[slot] = req
                req.slot = slot
                # a kvshare-adopted stream enters HERE without ever
                # passing _start_admission — its API handler is waiting
                # on the admitted event (no-op for normal preempts,
                # whose admission already set it)
                if not req.admitted.is_set():
                    req.admitted.set()
                TIMELINES.event(req.id, "resume", mode="swap", slot=slot)
            else:
                need = self.paged.blocks_for(entry.tokens_at_preempt + 1)
                # ensure_free counts cache pins as reclaimable: a parked
                # request never reaches the allocation path where lazy
                # eviction runs, so the gate must evict for it
                if not self.paged.ensure_free(need):
                    self._fail_unresumable(entry)
                    return      # replaying now would thrash straight
                                # back into preemption; wait for room
                slot = self.pool.alloc()
                self._preempted.pop(0)
                self._reqs[slot] = req
                req.slot = slot
                # resume stamps BEFORE the replay it triggers, so the
                # timeline reads preempt -> resume -> replay
                TIMELINES.event(req.id, "resume", mode="recompute",
                                slot=slot)
                if req.tokens:
                    self._replay_slot(req, slot)
                else:
                    self._begin_prefill(_Prefill(req, slot))
            SERVE_SLOTS_BUSY.set(self.pool.busy_count)
            log.warning("resumed preempted request %s into slot %d (%s)",
                        req.id, req.slot if req.slot is not None else -1,
                        entry.mode)

    def _fail_unresumable(self, entry: PreemptedSlot):
        """A parked entry whose resume gate failed: if live work still
        holds blocks, more room is coming — leave it parked. With
        NOTHING running and the cache already drained by the gate, no
        future event can free another block, so the request is failed
        typed instead of hanging its client forever."""
        if self.pool.busy_count or self.queue.depth():
            return
        self._preempted.remove(entry)
        self._fail(entry.req, KVPoolExhausted(
            f"KV pool exhausted: preempted request {entry.req.id} needs "
            "more blocks than the pool can ever free"))

    # -- crash recovery (called by the supervisor, scheduler thread) --------

    def _rebuild(self, suspects: frozenset = frozenset()):
        """Rebuild-by-replay after a step failure: trust NOTHING on the
        device (donated inputs may be consumed, results may be garbage) —
        reallocate the pool and prefix cache, then reconstruct every live
        slot from its host-side token record by replaying prompt +
        generated[:-1] through the chunked-prefill path. Replay lands on
        the same chunk buckets admission compiled (usually zero new
        executables), and the fresh prefix cache is repopulated as replay
        runs, so slots sharing prefixes splice instead of recompute.

        Greedy continuations are bit-identical afterwards: position, last
        token, and the repeat-penalty window are all derivable from the
        record (cache rows hold prompt+generated minus the LAST emitted
        token — its KV is appended by the next decode step, exactly as it
        would have been without the crash). Requests that had emitted
        NOTHING yet restart admission from scratch instead.

        Suspects (requests implicated in the triggering crash) replay
        LAST and one at a time — a poisoned request re-crashes on its own
        solo replay, which is how the supervisor attributes it."""
        t0 = now()
        replays: list[ServeRequest] = []
        restarts: list[ServeRequest] = []
        for i, req in enumerate(self._reqs):
            if req is None:
                continue
            if req.cancelled.is_set() or req.done.is_set():
                self._fail(req, None)       # no row left to wipe: gone
                continue
            if req.tokens:
                replays.append(req)
            else:
                req._first_pending = False  # unfetched 1st token is lost
                restarts.append(req)
        self._prefills.clear()
        self.pool = SlotPool(self.slots)
        self._reqs = [None] * self.slots
        # release the impeached device state BEFORE reallocating: the
        # prefix cache's blocks and the old pool (rows or paged blocks)
        # pin HBM, and an oom-classified failure would re-OOM every
        # rebuild attempt if the replacement pool had to coexist with
        # the one it replaces. Preempted entries SURVIVE a rebuild —
        # swap blobs are host memory and recompute entries replay from
        # the host token record either way
        self._layers = None
        self.paged = None
        self.prefix_cache = None
        self._init_device_state()
        self.prefix_cache = self._build_prefix_cache()
        # register EVERY survivor before any device work: if a replay
        # crashes, the next rebuild's harvest must still see the ones
        # that hadn't replayed yet
        replays.sort(key=lambda r: r.id in suspects)    # innocents first
        jobs = []
        for req in replays:
            slot = self.pool.alloc()
            self._reqs[slot] = req
            req.slot = slot
            jobs.append((req, slot))
        for req in restarts:
            slot = self.pool.alloc()
            self._reqs[slot] = req
            req.slot = slot
        for req, slot in jobs:
            self._replay_slot(req, slot)
            # each completed replay is the CONTRAST that lets a later
            # replay crash be attributed to its own request's data
            self.supervisor.note_replay_ok()
        for req in restarts:
            self._begin_prefill(_Prefill(req, slot=req.slot))
        SERVE_SLOTS_BUSY.set(self.pool.busy_count)
        self._wake.set()
        log.warning("serve engine rebuilt in %.0f ms: %d slot(s) replayed, "
                    "%d admission(s) restarted, %d queued untouched",
                    (now() - t0) * 1e3, len(jobs), len(restarts),
                    self.queue.depth())

    def _replay_slot(self, req: ServeRequest, slot: int):
        """Replay one surviving request's recorded tokens into a fresh
        pool row and restore its decode carries bit-exactly (greedy).
        The rng carry is a fresh fold — unused under temperature 0; for
        sampled requests the stream is documented as resuming on a new
        rng after a rebuild."""
        ids = req.prompt_ids + req.tokens[:-1]
        n = len(ids)
        TIMELINES.event(req.id, "replay", tokens=n)
        hook = faults.FAULT_HOOK
        set_request_id(req.id)
        try:
            with RECORDER.span("serve.replay", cat="serve", slot=slot,
                               tokens=n):
                pos = 0
                keys: list = []
                matched = 0
                if self.prefix_cache is not None:
                    keys = self.prefix_cache.chain_keys(ids)
                    matched = self.prefix_cache.match(ids, keys)
                    if matched:
                        self._layers = self.prefix_cache.splice(
                            self._layers, slot, keys, matched)
                        pos = matched * self.chunk
                next_block = matched
                while pos < n:
                    take = min(self.chunk, n - pos)
                    if self.paged is not None and not \
                            self.paged.reserve_range(slot, pos, take):
                        # replay never preempts (it runs inside recovery
                        # / resume, where victim churn would thrash);
                        # cache eviction already happened inside
                        # reserve_range, so this is a genuinely full pool
                        raise KVPoolExhausted(
                            f"KV pool exhausted replaying {req.id}")
                    # recovery-grace watchdog limit: a replay chunk may
                    # carry an in-iteration compile for a bucket fresh
                    # generations never hit
                    self.supervisor.arm("replay", (req.id,), grace=True)
                    if hook is not None:
                        hook.on_prefill(req)
                    if self.paged is not None:
                        self.paged.prefill_into(slot, ids[pos:pos + take],
                                                 pos)
                    else:
                        _, self._layers = self.model.prefill_chunk(
                            self._layers, slot, ids[pos:pos + take], pos)
                    pos += take
                    next_block = self._capture_blocks(ids, slot, pos, n,
                                                      next_block, keys)
        finally:
            set_request_id(None)
        last = req.tokens[-1]
        recent = np.full((RECENT_N,), -1, np.int32)
        tail = req.tokens[-RECENT_N:]
        recent[RECENT_N - len(tail):] = tail
        rng = jax.random.fold_in(self._base_rng, self._seq)
        self._seq += 1
        self._toks = self._toks.at[slot].set(last)
        self._pos = self._pos.at[slot].set(n)
        self._rngs = self._rngs.at[slot].set(rng)
        self._recents = self._recents.at[slot].set(jnp.asarray(recent))
        self._set_slot_sampling(slot, req.sampling)
        self._act = self._act.at[slot].set(True)

    def _drop_poisoned(self, rid: str, err: PoisonedRequest) -> bool:
        """Fail ONE request (attributed poison) with its typed 500 and
        quarantine its fingerprint; the pool lives on for everyone else.
        Row state is not wiped — the caller is about to rebuild."""
        for i, req in enumerate(self._reqs):
            if req is None or req.id != rid:
                continue
            self._reqs[i] = None
            self.pool.free(i)
            self._prefills[:] = [p for p in self._prefills
                                 if p.req.id != rid]
            self.supervisor.quarantine(req.prompt_ids)
            SERVE_POISONED.inc()
            SERVE_SLOTS_BUSY.set(self.pool.busy_count)
            log.error("poisoned request %s dropped and quarantined: %s",
                      rid, err)
            self._fail(req, err)
            return True
        return False

    def _fail_all(self, err: EngineDown):
        """Budget exhausted: every live request is released with the
        typed down error (503 at the API — never a hang), the pool
        bookkeeping resets, and the device pool is dropped (the restore
        trial allocates the replacement)."""
        self._prefills.clear()
        for entry in self._drain_preempted():
            self._fail(entry.req, err)
        for req in self.queue.drain():
            self._fail(req, err)
        for i, req in enumerate(self._reqs):
            if req is not None:
                self._reqs[i] = None
                self._fail(req, err)
        self.pool = SlotPool(self.slots)
        self._inflight = None
        self._act = jnp.zeros((self.slots,), jnp.bool_)
        # drop the device pool AND the prefix cache's blocks: an
        # oom-downed engine must not pin the old HBM while the restore
        # trial tries to allocate its replacement (_revive rebuilds both)
        self._layers = None
        self.paged = None
        self.prefix_cache = None
        SERVE_SLOTS_BUSY.set(0)

    # -- speculative decode (batched, accept-aware) -------------------------

    def _build_drafts(self, active: list[int]):
        """Host-side draft windows for this iteration's batched verify:
        the shared drafter proposes up to spec_k continuation tokens per
        slot from the slot's own committed token history (prompt +
        generated — the drafter-free n-gram mode needs no weights and no
        device work, and the lookup overlaps the previous iteration's
        still-queued prefill chunk). Slots the drafter abstains on, slots
        whose first token the host has not fetched yet, and slots out of
        budget/context headroom get an empty window — they take a plain
        decode step INSIDE the same dispatch. Returns (drafts [B, k]
        int32, n_drafts [B] int32), or None when every window came back
        empty (the iteration then dispatches the cheaper width-1 decode
        program)."""
        k = self.spec_k
        drafts = np.zeros((self.slots, k), np.int32)
        n_drafts = np.zeros((self.slots,), np.int32)
        any_draft = False
        for i in active:
            req = self._reqs[i]
            if req._first_pending:
                continue        # newest token still rides the next fetch
            pos = len(req.prompt_ids) + max(len(req.tokens) - 1, 0)
            ki = min(k, self.ctx - pos - 1, max(req.budget, 0))
            if self.paged is not None and self.spec_reserve > 0:
                # frontier-reservation cap: never back more speculative
                # frontier with blocks than CAKE_SPEC_RESERVE tokens
                ki = min(ki, self.spec_reserve)
            if ki <= 0:
                continue
            d = list(self.spec_drafter.propose(
                req.prompt_ids + req.tokens, ki))[:ki]
            if not d:
                continue
            drafts[i, :len(d)] = d
            n_drafts[i] = len(d)
            self._cur_nd[i] = len(d)
            any_draft = True
        return (drafts, n_drafts) if any_draft else None

    def _trim_spec_tails(self, exclude: int | None = None) -> bool:
        """Pressure-relief ROLLBACK of speculative frontier reservations:
        blocks mapped past what each slot's committed tokens plus its
        PENDING draft window need are returned to the pool — strictly
        cheaper than preempting a victim, so the exhaustion path tries
        this first. Keeps every block the in-flight or about-to-dispatch
        verify may still write (the _cur_nd window). True = at least one
        block freed (the caller retries its allocation)."""
        freed = 0
        for i in self.pool.busy():
            if i == exclude:
                continue
            req = self._reqs[i]
            if req is None:
                continue
            wp = len(req.prompt_ids) + max(len(req.tokens) - 1, 0)
            freed += self.paged.trim_to(
                i, wp + self._cur_nd.get(i, 0) + 1)
        return freed > 0

    def _fanout(self, fl: _InFlight, arr: np.ndarray):
        """Fan one step's packed ids out to the streams of the requests
        that were active when it was DISPATCHED (`fl.rows`, not what the
        slots hold now). Row 0 carries each slot's input token (a
        just-activated slot's unemitted FIRST token: the first step a row
        is active in is the one whose row 0 it gets); a plain step's row 1
        is the token it sampled; a verify step's row 1 is its
        accepted-draft count and row 2 its correction/bonus token — the
        host already knows the drafts it proposed, so n_acc + 1 tokens per
        slot ride a fetch no bigger than the plain decode path's.

        A request that ended between the dispatch and this fan-out (EOS or
        a spent budget in the step before, a cancel, a deadline) ran this
        step for nothing: its id is dropped and counted, and whoever holds
        its slot now never sees it."""
        step, nb = self._step_id, fl.nb
        drafts, n_drafts = fl.spec or (None, None)
        for i, req in fl.rows:
            if self._reqs[i] is not req:
                self._dropped += 1
                continue
            if req._first_pending:
                req._first_pending = False
                req.t_first = now()     # first token actually on host:
                req.stats["ttft_s"] = req.t_first - req.t_enqueue
                TIMELINES.event(req.id, "first_token", step=step,
                                of_step=fl.step)
                first = int(arr[0, i])
                self._emit(req, first)
                if self.model.cfg.is_eos(first) or req.budget <= 0:
                    # the overshoot token is discarded — a wasted slot-row
                    # step, no recompute
                    self._finish(i, req)
                    continue
            if drafts is None:
                n_prop, new = 0, (int(arr[1, i]),)
            else:
                n_prop, n_acc = int(n_drafts[i]), int(arr[1, i])
                new = [int(t) for t in drafts[i, :n_acc]] + [int(arr[2, i])]
            if n_prop:
                self.spec_steps += 1
                self.spec_proposed += n_prop
                self.spec_accepted += n_acc
                record_step(n_prop, n_acc, bucket=nb)
                TIMELINES.event(req.id, "spec_verify", step=step,
                                of_step=fl.step, bucket=nb,
                                proposed=n_prop, accepted=n_acc)
            else:
                TIMELINES.event(req.id, "decode", step=step,
                                of_step=fl.step, bucket=nb)
            for tid in new:
                req.budget -= 1
                self._emit(req, tid)
                if self.model.cfg.is_eos(tid) or req.budget <= 0:
                    self._finish(i, req)
                    break

    def _emit(self, req: ServeRequest, tid: int):
        req.tokens.append(tid)
        self._emitted += 1
        if not req.cancelled.is_set():
            req._deliver(self.model._mk_token(tid))

    def _finish(self, slot: int, req: ServeRequest, cancelled: bool = False,
                release: bool = True):
        self.pool.free(slot)
        self._reqs[slot] = None
        if release:
            # wipe the row so a cancelled/finished request's KV never
            # lingers into the next occupant's prefix (prefix-cache splice
            # and chunked prefill both assume a clean row), and drop the
            # slot from the active mask — a freed row inside the decode
            # prefix is frozen outright, not stepped
            self._release_row(slot)
            self._toks = self._toks.at[slot].set(0)
            self._pos = self._pos.at[slot].set(0)
            self._act = self._act.at[slot].set(False)
        dt = now() - req.t_first if req.t_first else 0.0
        ndec = max(len(req.tokens) - 1, 0)
        req.stats.update({
            "decode_tokens": ndec, "decode_s": dt,
            "tok_per_s": ndec / dt if dt > 0 and ndec else 0.0,
        })
        req.result["tokens"] = req.tokens
        req.result["stats"] = req.stats
        if not cancelled and req.tokens:
            from ..models.common.text_model import _observe_generation
            _observe_generation(req.stats, len(req.tokens), path="serve")
        # SLO + terminal event only for a request not already finalized:
        # _fail may have released this waiter earlier (close() timeout
        # path), and a second terminal would double-count the histograms
        # and leave two conflicting terminals on the timeline
        if not req.done.is_set():
            outcome = "cancelled" if cancelled and "error" not in req.result \
                else ("error" if cancelled else "ok")
            self._observe_slo(req, outcome)
            TIMELINES.event(
                req.id, "finish", outcome=outcome, tokens=len(req.tokens),
                qos=req.qos,
                ttft_ms=round(req.stats.get("ttft_s", 0.0) * 1e3, 3),
                e2e_ms=round((now() - req.t_enqueue) * 1e3, 3),
                **({"tenant": req.tenant} if req.tenant else {}))
        SERVE_SLOTS_BUSY.set(self.pool.busy_count)
        req._deliver(ServeRequest.DONE)
        req._fire_done()

    def _observe_slo(self, req: ServeRequest, outcome: str):
        """Batched-path SLO decomposition, per terminal request: TTFT /
        mean ITL / e2e histograms labeled by outcome, each observation
        carrying the request id as its exemplar so a bad percentile in a
        scrape links to a concrete /api/v1/requests/<id> timeline."""
        SERVE_E2E_SECONDS.observe(now() - req.t_enqueue, exemplar=req.id,
                                  outcome=outcome)
        SERVE_QOS_E2E_SECONDS.observe(now() - req.t_enqueue,
                                      exemplar=req.id, qos=req.qos,
                                      outcome=outcome)
        if req.t_first:
            SERVE_TTFT_SECONDS.observe(req.t_first - req.t_enqueue,
                                       exemplar=req.id, outcome=outcome)
            SERVE_QOS_TTFT_SECONDS.observe(req.t_first - req.t_enqueue,
                                           exemplar=req.id, qos=req.qos,
                                           outcome=outcome)
            ndec = max(len(req.tokens) - 1, 0)
            if ndec:
                SERVE_ITL_SECONDS.observe(
                    (now() - req.t_first) / ndec, exemplar=req.id,
                    outcome=outcome)

    def _fail(self, req: ServeRequest, error: BaseException | None):
        if error is not None:
            req.result["error"] = error
        req.result.setdefault("tokens", req.tokens)
        # keep whatever stats accrued (queue_wait_s, prefill progress) —
        # failed/cancelled requests are the ones worth diagnosing
        req.result.setdefault("stats", req.stats)
        if not req.done.is_set():
            err = req.result.get("error")
            self._observe_slo(req, "error" if err is not None
                              else "cancelled")
            TIMELINES.event(req.id, "error",
                            type=type(err).__name__ if err is not None
                            else "cancelled")
        req._deliver(ServeRequest.DONE)
        req._fire_done()


def maybe_engine(model, slots: int | None = None,
                 max_queue: int | None = None,
                 ctx_len: int | None = None) -> ServeEngine | None:
    """Engine for serve-capable models, tuned by env: CAKE_SERVE_SLOTS
    (default 4, 0 disables), CAKE_MAX_QUEUE (default 64), CAKE_SERVE_CTX
    (default 4096, capped by the model's max_cache_len), CAKE_PREFILL_CHUNK
    (default 256 — per-iteration chunked-admission token budget),
    CAKE_PREFIX_CACHE_MB (default 256, 0 disables shared-prefix KV reuse),
    the paged-KV knobs CAKE_KV_BLOCKS / CAKE_KV_BLOCK_TOKENS /
    CAKE_PREEMPT_MODE (CAKE_KV_BLOCKS > 0 swaps the contiguous slot rows
    for a shared block pool with refcounted prefix sharing and
    preemption — see docs/serving.md#paged-kv-pool),
    the speculative-decoding knobs CAKE_SPEC / CAKE_SPEC_K /
    CAKE_SPEC_NGRAM / CAKE_SPEC_RESERVE (batched draft/verify/accept
    rides the same slot iteration — see docs/speculative.md), and the
    supervision
    knobs CAKE_STEP_WATCHDOG_S / CAKE_ENGINE_REBUILDS /
    CAKE_ENGINE_REBUILD_WINDOW_S / CAKE_ENGINE_RESTORE_S /
    CAKE_REQUEST_DEADLINE_S (see docs/fault_tolerance.md) — all read
    inside ServeEngine. Distributed / offloaded models return None —
    the API keeps its locked fallback."""
    from ..models.common.text_model import TextModel
    # the process's own witnesses of a stall (obs/process.py): compiles and
    # collector pauses count from here on, for `cake serve` as for every
    # other embedding of the engine
    PROCESS.install()
    if not isinstance(model, TextModel):
        return None
    if slots is None:
        slots = knobs.get("CAKE_SERVE_SLOTS")
    if slots <= 0:
        return None
    if max_queue is None:
        max_queue = knobs.get("CAKE_MAX_QUEUE")
    if ctx_len is None:
        ctx_len = knobs.get("CAKE_SERVE_CTX")
    return ServeEngine(model, slots=slots, max_queue=max_queue,
                       ctx_len=ctx_len)
