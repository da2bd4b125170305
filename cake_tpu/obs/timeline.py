"""Per-request timelines: a bounded ring of typed lifecycle events.

The metrics registry answers "how is the fleet doing"; the span recorder
answers "where did wall time go inside this process". Neither answers the
operator question this module exists for: *this one request was slow —
which tier ate the time?* A request crosses four tiers (fleet router →
replica API → serve engine → cluster stages), and every hop already
shares one request id (the router injects `X-Cake-Request-Id`, the
replica adopts it into the request-id contextvar, the engine keys its
scheduler bookkeeping by it). This store records that id's lifecycle as
typed events — enqueue, admit, each prefill chunk, each decode/spec
iteration the slot participated in, preemption/swap/resume,
rebuild-replay, router retry/failover/hedge — against monotonic
timestamps, bounded two ways:

  * the store keeps the last `CAKE_TRACE_REQUESTS` request timelines
    (ring: oldest evicted first);
  * each timeline keeps at most `MAX_EVENTS` events (newest dropped,
    counted in `dropped`; terminal events always land so a truncated
    timeline still says how the request ended).

`GET /api/v1/requests/<id>` serves a timeline as JSON; the fleet router's
version of the route stitches its own tier's events onto the replica's.
`to_chrome(rid)` exports one timeline as Chrome-trace instant events on
the SAME perf_counter microsecond clock the span recorder uses, so a
timeline merges with a `RECORDER.export()` in Perfetto.

Recording is always on (one dict lookup + list append per event — the
scheduler iteration doing it also runs a device dispatch), unlike the
span recorder, which buffers far more events and stays opt-in.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict

from .. import knobs
from .spans import current_request_id

__all__ = ["EVENT_KINDS", "TIMELINES", "TimelineStore", "TRACE_HEADER",
           "MAX_EVENTS"]

# the one header every tier propagates; the router injects it, the
# replica API adopts it, responses echo it back to the client
TRACE_HEADER = "X-Cake-Request-Id"

# per-timeline event cap: newest events drop past this (counted), except
# terminal kinds, which always land
MAX_EVENTS = 512

# typed event vocabulary — event() rejects unknown kinds, and the
# observability catalog (docs/observability.md) is generated from this
# table, so an event kind cannot ship undocumented. Grouped by the tier
# that records it.
EVENT_KINDS: dict[str, str] = {
    # replica API tier
    "received": "request reached the replica API handler (chat, image, "
                "or audio)",
    "kv_fetch": "fleet-shared KV tier: this replica tried to fetch a "
                "matching prefix blob from a warm peer before "
                "recomputing the prefill (`outcome` = hit | miss | "
                "timeout | error | mismatch, `tokens` installed on a "
                "hit, `peer`)",
    "kv_migrate": "fleet-shared KV tier: a live stream's swap blob "
                  "moved through the router's resume plane (`outcome` "
                  "= shipped | source_miss | ship_error, `from`, `to`)",
    # admission plane + serve engine tier
    "enqueue": "request/job entered the admission queue (`depth` behind "
               "it, `qos` class, `tenant`/`workload` when set)",
    "admit": "slot assigned (chunked prefill opens: `slot`, "
             "`queue_wait_ms`) or heavy job started (`workload`); "
             "carries `qos`",
    "prefix_hit": "prefix-cache splice skipped `tokens` prompt tokens",
    "prefill_chunk": "one chunk scattered into the pool row (`step`, "
                     "`pos0`, `tokens`, `attn`: `flash-fresh` / `flash-append` "
                     "when the chunk's program holds the Pallas kernel, "
                     "`masked` for the XLA path)",
    "compile": "a dispatch for this request built a program in-band: a "
               "chunk bucket, a restore piece's length or a join first "
               "met while serving (`program`, `ms` of its backend stage, "
               "`cache` = hit | miss | off, `step`)",
    "prefill_done": "prompt fully prefilled; first token sampled "
                    "(`chunks`, `hit_tokens`)",
    "first_token": "first token fetched to the host (client-visible "
                   "TTFT stamps here; `step`, `of_step`)",
    "decode": "one batched decode step this slot participated in, as "
              "its id reaches the host (`bucket` = rows the dispatched "
              "program ran, `step` = the flight `seq` of the iteration "
              "that fetched and fanned the id out, which its `serve.*` "
              "spans carry, `of_step` = that of the iteration that had "
              "dispatched the step: the one before, or the same)",
    "spec_verify": "one batched speculative verify this slot "
                   "participated in (`step`, `of_step`, `bucket`, "
                   "`proposed`, `accepted`)",
    "preempt": "slot evicted under KV-pool pressure (`mode` = "
               "swap | recompute | requeue, `tokens`)",
    "resume": "preempted request re-entered a slot (`mode`, `slot`)",
    "replay": "prompt+generated replayed through chunked prefill "
              "(crash rebuild or recompute resume; `tokens`)",
    "step_failure": "a scheduler step implicating this request failed "
                    "(`failure` = classified kind, `phase`)",
    "finish": "terminal: generation completed (`outcome`, `tokens`, "
              "`ttft_ms`, `e2e_ms`)",
    "error": "terminal: request failed or was cancelled (`type`)",
    # cluster tier (distributed master, per remote hop)
    "cluster_hop": "one remote-stage forward attributed to this request "
                   "(`worker`, `ms`)",
    # fleet router tier
    "route": "router accepted the request and ordered candidates "
             "(`candidates`, `stream`)",
    "attempt": "one outbound try against a replica (`replica`, "
               "`outcome`, `status`)",
    "retry": "failover: the next candidate gets the request",
    "hedge": "tail hedge fired a duplicate at the next-best replica",
    "shed": "router refused before any replica admitted (`reason`)",
    "commit": "first streamed byte relayed; the request is committed "
              "to `replica`",
    "stream_broken": "stream severed after commit (`replica`, `chunks` "
                     "relayed so far); the resume budget decides what "
                     "happens next",
    "stream_resume": "router began a transparent splice-resume of the "
                     "broken stream (`replica` that broke, `attempt`, "
                     "`sampled` when the rng-fold parity exception "
                     "applies)",
    "resume_spliced": "resumed replica's continuation reached the "
                      "client: first spliced chunk relayed on the same "
                      "socket (`replica`, `overlap_chars` stripped)",
    "done": "terminal: router relayed the final response (`status`)",
    "replica_partition_suspected": "membership ejected a replica on "
                                   "data-path/transport evidence while "
                                   "its probe path may still answer "
                                   "(`replica`, `reason`, `hold_s`); "
                                   "readmit now requires a data-path "
                                   "trial",
    "partition_healed": "a suspected-partition episode ended: the "
                        "replica passed a data-path trial and rejoined "
                        "routing (`replica`, `episode_s`)",
}

# terminal kinds bypass the per-timeline cap: a truncated timeline must
# still say how the request ended
_TERMINAL = frozenset({"finish", "error", "done"})


class _Timeline:
    __slots__ = ("rid", "tier", "start_unix", "t0_us", "events", "dropped")

    def __init__(self, rid: str, tier: str):
        self.rid = rid
        self.tier = tier
        self.start_unix = time.time()
        self.t0_us = time.perf_counter_ns() // 1000
        self.events: list[dict] = []
        self.dropped = 0


class TimelineStore:
    """Thread-safe bounded store. begin() opens a timeline (idempotent),
    event() appends to a known id (unknown ids are a cheap no-op — the
    cluster hop recorder fires for every request, but only requests a
    tier opened a timeline for keep events), alias() lets a second id
    (the OpenAI completion id) resolve to the same timeline."""

    def __init__(self, capacity: int | None = None,
                 max_events: int = MAX_EVENTS):
        if capacity is None:
            capacity = knobs.get("CAKE_TRACE_REQUESTS")
        self.capacity = max(int(capacity), 1)
        self.max_events = max_events
        self._lock = threading.Lock()
        self._by_id: OrderedDict[str, _Timeline] = OrderedDict()
        self._aliases: dict[str, str] = {}

    # -- recording -----------------------------------------------------------

    def begin(self, rid: str, tier: str = "replica") -> None:
        with self._lock:
            if rid in self._by_id or rid in self._aliases:
                return
            self._by_id[rid] = _Timeline(rid, tier)
            while len(self._by_id) > self.capacity:
                old, _ = self._by_id.popitem(last=False)
                self._aliases = {a: r for a, r in self._aliases.items()
                                 if r != old}

    def alias(self, alias_id: str, rid: str) -> None:
        """Make alias_id resolve to rid's timeline (completion id →
        trace id). No-op when rid is unknown or the ids are equal."""
        if alias_id == rid:
            return
        with self._lock:
            if rid in self._by_id:
                self._aliases[alias_id] = rid

    def event(self, rid: str | None, kind: str, **attrs) -> None:
        """Append one typed event. rid=None reads the request-id
        contextvar (the cluster-hop recorder's path). Unknown ids are
        dropped silently: recording is always on, so a tier that never
        opened a timeline (scripts, tests driving the model
        directly) costs one dict lookup and nothing else."""
        if kind not in EVENT_KINDS:
            raise ValueError(f"unknown timeline event kind {kind!r} — "
                             "add it to obs.timeline.EVENT_KINDS (and "
                             "regenerate the catalog)")
        if rid is None:
            rid = current_request_id()
            if rid is None:
                return
        t_us = time.perf_counter_ns() // 1000
        with self._lock:
            tl = self._by_id.get(rid)
            if tl is None:
                canon = self._aliases.get(rid)
                tl = self._by_id.get(canon) if canon else None
            if tl is None:
                return
            if len(tl.events) >= self.max_events and kind not in _TERMINAL:
                tl.dropped += 1
                return
            ev = {"t_ms": round((t_us - tl.t0_us) / 1e3, 3), "kind": kind}
            if attrs:
                ev.update(attrs)
            tl.events.append(ev)

    # -- views ---------------------------------------------------------------

    def get(self, rid: str) -> dict | None:
        """JSON-shaped snapshot of one timeline (by id or alias).
        `t_ms` is milliseconds since the timeline opened; `start_unix`
        anchors the monotonic offsets to wall clock so tiers recorded in
        different processes can be laid on one axis, and `t0_us` is the
        opening instant on the span recorder's clock (perf_counter
        microseconds, which on Linux is every local process's monotonic
        clock): `t0_us + 1000 * t_ms` lays an event beside the spans."""
        with self._lock:
            tl = self._by_id.get(rid) or self._by_id.get(
                self._aliases.get(rid, ""))
            if tl is None:
                return None
            return {
                "request_id": tl.rid,
                "tier": tl.tier,
                "start_unix": round(tl.start_unix, 6),
                "t0_us": tl.t0_us,
                "events": [dict(e) for e in tl.events],
                "dropped": tl.dropped,
            }

    def ids(self) -> list[str]:
        """Known request ids, oldest first."""
        with self._lock:
            return list(self._by_id.keys())

    def to_chrome(self, rid: str) -> dict | None:
        """One timeline as Chrome-trace instant events on the span
        recorder's perf_counter-microsecond clock, so the export merges
        with RECORDER.export() in Perfetto."""
        with self._lock:
            tl = self._by_id.get(rid) or self._by_id.get(
                self._aliases.get(rid, ""))
            if tl is None:
                return None
            events = []
            for e in tl.events:
                args = {k: v for k, v in e.items() if k != "kind"}
                args["request_id"] = tl.rid
                args["tier"] = tl.tier
                events.append(
                    {"name": e["kind"], "cat": "request", "ph": "i",
                     "s": "t", "ts": int(tl.t0_us + e["t_ms"] * 1e3),
                     "pid": 0, "tid": 0, "args": args})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def clear(self) -> None:
        with self._lock:
            self._by_id.clear()
            self._aliases.clear()


# process-global store: the API handlers, the serve engine, the fleet
# router, and the cluster master all record into this one ring
TIMELINES = TimelineStore()
