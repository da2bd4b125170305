"""Generator for docs/observability.md — the metric / span / timeline
catalog plus the endpoint and tracing prose, all from one source.

The hand-written observability page predated serve/paged/spec/fleet and
went three subsystems stale; like docs/knobs.md it is now GENERATED
(`make metrics-doc`, `python -m cake_tpu.obs`) and pinned to this module
by test. The metric table renders the process-global REGISTRY after the
canonical declarations in obs/__init__.py import, the span and scope tables
render spans.SPAN_CATALOG and spans.SCOPE_CATALOG, and the timeline-event
table renders
timeline.EVENT_KINDS — so the `metric-registry` lint (which checks every
constructed instrument name against the generated file) closes the loop:
an instrument cannot ship undocumented, and the doc cannot drift from
the code.
"""
from __future__ import annotations

_HEADER = """\
# Observability

<!-- GENERATED FILE — do not edit. Source of truth is
     cake_tpu/obs/catalog.py (metric table: the canonical declarations
     in cake_tpu/obs/__init__.py; span and scope tables: obs/spans.py
     SPAN_CATALOG / SCOPE_CATALOG; timeline events: obs/timeline.py
     EVENT_KINDS).
     Regenerate with `make metrics-doc`; tests/test_analysis.py pins
     this file, and the `metric-registry` lint checks every
     constructed instrument name against it. -->

`cake_tpu/obs/` is the measurement layer for the whole stack: a metrics
registry (counters / gauges / histograms with Prometheus text
exposition), a span recorder (Chrome-trace / Perfetto JSON export),
request-id propagation, and per-request lifecycle timelines. Every
serving tier records into the same process-global instruments, so one
`/metrics` scrape, one trace export, or one timeline fetch shows the
whole request path — fleet router → replica API → serve engine →
cluster stages.

## Endpoints

| endpoint | serves |
|---|---|
| `GET /metrics` | Prometheus text exposition 0.0.4 of every instrument below (per process; worker-side series live in each worker process) |
| `GET /health` | JSON liveness: worker last-seen ages, gray/hard cluster degradation, the serve-engine block (`alive` / `wedged` / `down` / `draining`, queue depth, `prefilling`, prefix-cache and `kv_pool` occupancy — the paged block carries a first-class `occupancy` field in [0, 1]; every pool's block carries `joined_keys`, one `{width, layers}` a joined width: how many layers hold a position's keys as one run of Hkv x D because the key width is no multiple of the 128 lanes, empty for a model whose key widths all are; beside it `attention_kinds`, one entry a kind of attention layer: `{kind, layers, heads, kv_heads, window, rotary_dim, rope_theta, rope_scaling}`, so a run says which rope table each kind read, and one for the delta-rule layers: `{kind: linear, layers, heads, key_dim, value_dim, decay: head | channel, conv_kernel, state_bytes}`, the float32 state a row holds over all of them; one for the latent layers: `{kind: latent, layers, heads, q_lora_rank (null: a full-rank query), kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim, row_width, row_lanes, rotary_dim, rope_theta, rope_scaling, row_bytes}`, with `q_scale` and `kv_scale` where a family scales its latents (a row holds the unscaled one), and a model may report a linear and a latent entry together (one pool row then holds both kinds); `sparse_layers`, where the model has any: `{layers, router_width, routed_experts, identity_experts, held, held_from, top_k, routed_scale}` (what the router scores: the experts of the whole group and, behind them, identity experts no share holds; what of it this process holds) and `shortcut_pairs`, the pairs of entries of the layer list between which a sparse layer's output is carried (LongCat-Flash: every entry is a latent sub-layer, `attention_kinds` counts 2 x `num_layers` of them); `rope_rows` and `rope_bytes`, the rows and bytes of the rope tables the model HOLDS: a model built with `max_cache_len` below the published reach keeps the first `max_cache_len` rows of each table, 0 / 0 where no layer rotates; the `prefix_cache` block's `state_bytes` is the part of its `bytes` that is recurrent layers' boundary snapshots); 503 while degraded |
| `GET /api/v1/stats` | last generation's timing snapshot (TTFT, tok/s, per-hop RTT split), with its `request_id` (the cross-tier trace id) and `completion_id` |
| `GET /api/v1/trace` | Chrome-trace JSON of the span ring buffer (`?clear=1` drains; 409 while the recorder is disabled) |
| `GET /api/v1/requests` | recent request ids with retrievable timelines |
| `GET /api/v1/requests/<id>` | one request's typed lifecycle timeline (`?format=perfetto` for Chrome-trace instant events); on the fleet router this view STITCHES the router tier's events onto the replica's |
| `GET /api/v1/slo` | the serve TTFT / inter-token / e2e histograms by outcome as JSON, each bucket carrying its sampled exemplar request id |
| `GET /api/v1/flight` | flight-recorder-on-demand: the scheduler-iteration ring as JSON without waiting for a wedge/DOWN dump, with its `static` part (what holds for every iteration: `joined_keys`, as in `/health`'s `kv_pool`, and `attention_kinds`, `sparse_layers`, `rope_rows` and `rope_bytes`, as in `/health`) and its `stalls` (the iterations that stood still, kept beside the ring) beside it (`?n=K` for the newest K; 409 without an engine) |
| `GET /api/v1/fleet/telemetry` | ROUTER ONLY: the fleet telemetry rollup — time-series, burn rates, headroom, outliers (see [telemetry.md](telemetry.md)) |
| `GET /api/v1/fleet/autoscale` | ROUTER ONLY: the autoscaler's decision ring, policy, and managed-replica lifecycle state (see [autoscaling.md](autoscaling.md); `{"enabled": false}` when the loop is off) |

## Request-scoped tracing

One id names a request end to end: `cake route` injects an
`X-Cake-Request-Id` header (minting `trace-…` when the client sent
none), the replica API adopts it into the request-id contextvar (spans
and `/api/v1/stats` carry it), the serve engine keys its timeline events
by it, and every response echoes the header back. The OpenAI completion
id (`chatcmpl-…`) is registered as an alias, so either id resolves
`/api/v1/requests/<id>`.

Timelines are ALWAYS recorded (a dict lookup + list append per event):
the last `CAKE_TRACE_REQUESTS` requests are kept, each bounded to 512
events (newest dropped and counted, terminal events always land). The
span recorder stays opt-in (`CAKE_TRACE_DIR` or `RECORDER.enable()`)
and bounded by `CAKE_TRACE_EVENTS`; spans recorded while serving a
request carry the request id in their args, and a timeline's Perfetto
export uses the same perf_counter clock, so both merge on one axis at
<https://ui.perfetto.dev>.

Every span carries an `id` (one counter for the process) and, when it
was opened inside another span of its thread, that span's id as
`parent`, both in `args`: nest spans by id, not by comparing intervals.
`RECORDER.span()` yields the id; `RECORDER.add(..., parent=)` takes it
for a span emitted from stamps.

## One step, one id, one clock

A scheduler iteration that does work takes the next flight `seq` as its
step id. Its `serve.step` span carries it as `step`; so do the leaf spans
under it (`serve.sweep`, `serve.admit`, `serve.plan`,
`serve.decode_dispatch`, `serve.fetch`, `serve.fanout`,
`serve.prefill_chunk`, `serve.prefill_finish`, in the order a step runs
them — an engine that lands each step in its own iteration, a drafter's
or kvshare's, runs the chunk's two before the fetch: they cover the step
end to end and do not overlap), the flight record,
and the `decode` / `spec_verify` / `first_token` / `prefill_chunk` events
of every request the step touched. The ids a step fetches and fans out
are those of the decode step the iteration BEFORE it dispatched (its own
is queued behind that one and runs while the host works): `serve.fetch`,
`serve.fanout` and the token events carry that iteration's id as
`of_step` beside their own `step`. So a token in
`/api/v1/requests/<id>` names the iteration that delivered it and the
one that dispatched it, and their spans say where its time went. The
phases come from eight clock reads a step; with the recorder off nothing else is
paid, and the same reads give the flight record its `host_ms` /
`fetch_ms` split. `spec.verify` overlaps `serve.decode_dispatch` (both
are children of `serve.step`).

A timeline snapshot carries `t0_us`, the instant it opened on the span
recorder's clock (perf_counter microseconds; on Linux also every local
client's `time.monotonic`): `t0_us + 1000 * t_ms` lays an event beside
the spans. To lay a span export on a `jax.profiler` xplane, write one
`jax.profiler.TraceAnnotation` beside a `time.perf_counter_ns()` reading
while the trace runs and subtract the two (the benchmark's `bench.sync`,
`benchmark/launch_server.py`, does exactly that).

## Outside the step: the gap, the event loop, the SSE writer

What `serve.step` does not cover is counted always, span recorder or not.
The `_run` loop's own time from one iteration's last stamp to the next
one's first, when the earlier one left work behind, is the flight
record's `gap_ms`. The serving event loop re-arms a 50 ms tick and
records how late it fired (`cake_api_loop_lag_seconds`, the engine
block's `loop_lag_ms` = `{last, max_60s}`, the ring a stall record reads).
One span joins them, recorder on only: `api.sse_write` is one streamed
token through the writer. The scheduler stamps the token as it hands it
to the loop (`call_soon_threadsafe`), the stream stamps it again as the
loop hands it over (`ServeEngine.stream`'s iterator carries both as
`handoff`), and the writer records a span from that hand-over until
`resp.write` returned (`json.dumps` + aiohttp's write) with `wait_us` =
the time between the two stamps (the GIL and the loop's queue). With the
recorder off the scheduler takes no stamp and the writer pays one
attribute check a token.

## Start-up: what the process did before it was ready

How long a replica takes to become useful is accounted for from inside,
always on (`cake_tpu/obs/process.py`; installed by
`utils.compile_cache.enable_compile_cache`, the CLI's and the benchmark's
first call into the package, and again, idempotently, by `TextModel` and
`serve.maybe_engine`). `jax.monitoring` hands a listener every stage of
every program JAX builds, by name: `trace` (the Python function to a
jaxpr), `lower` (the jaxpr to an MLIR module) and `backend` (XLA's
compile, or on a persistent-cache hit the executable's retrieval and
load). One record each is kept in a ring of 4,096: `{t_end, seconds,
stage, program, cache, phase}`. Functions traced inside a program
(`matmul`, an inner `jit`) emit trace events of their own within the outer
one's time; a trace is kept only where the next `lower` event of its
thread names it. `cache` (backend records) is `hit`, `miss`, or `off`
where the persistent cache was not asked. Beside the builds, a dozen
boot phases a process (two clock reads and an append each): `boot.model`
(`TextModel.__init__`), `boot.rope` (`layers.make_rope` and `cut_rope`),
`boot.engine` (`ServeEngine.__init__`), `boot.engine.pool` (the pool's
and the prefix cache's allocation, waited for), and the process's own age
when the watch was installed (the interpreter's start and the imports
before it; from `/proc/self/stat`). A build carries the phase open on its thread.

Read it in `/health`'s engine block and `GET /api/v1/flight`'s `static`
(so in every flight dump), under `boot`: `{age_at_install_s, phases:
[{name, t_s, dur_s}], programs: [{program, builds, trace_s, lower_s,
backend_s, hits, misses}] (the 32 costliest), builds, hits, misses,
trace_s, lower_s, cache_load_s, compile_s, handed: {spans, seconds} (what
the span recorder was handed, and what that cost)}`: `trace_s + lower_s` is
Python's part, paid with a warm cache too; `cache_load_s` the backend time
of hits; `compile_s` that of misses. In `/metrics`:
`cake_compiles_total{cache}`, `cake_compile_seconds_total{cache}`,
`cake_program_build_seconds_total{stage}`. As spans: when the recorder is
switched on it is handed every build (`process.compile`) and every phase
(`cat="boot"`, nested by `parent`) since the process began, with their
past stamps, and each later one as it happens; they are held beside the
ring, outlive its turnover and `clear()`, and come first in an export, so
a Chrome trace begins with the start-up.

**Which step recompiled.** The engine reads the watch's count of backend
stages around a dispatch that may build a program (a prefill chunk with
its block captures and join, a prefix hit's restore): one integer compare
when nothing was built. Where it grew, the request's timeline takes a
`compile` event (`program`, `ms`, `cache`, `step`) and
`cake_serve_inband_compiles_total{program}` counts it; a stall record's
`compiled` names the programs behind its `compiles`.

## Engine flight recorder

The serve engine appends one record per scheduler iteration (`seq` = the
step id, occupancy, dispatch bucket, `kv_tokens` = the tokens the
stepping rows hold (prompt + generated: `bucket` x the context length
minus it is what a step that walks rows to their frontier leaves unread),
`ring_tokens` = the tokens those rows hold in window rings (the sum over
rows and window layers of min(frontier, window); 0 for a model with none),
`state_bytes` = the bytes of recurrent state those rows hold (leaves no
position addresses, read and written whole by every step; 0 for a model
with none),
`fetch_ms` = the scheduler blocked
on the device for the sampled ids, `host_ms` = the rest of the step's
wall time, `lag` = 1 when the ids fetched were dispatched by an earlier
iteration and this one's own decode step was queued behind them before
the fetch (the share of decode iterations with `lag` 1 is how often the
device went from one step straight into the next), 0 when they were
fetched with nothing queued behind (a drafter's or kvshare's engine, a
paged preemption, the iteration that finds nothing to dispatch) or none
were, `dropped` = ids fetched and not delivered because their request
had ended since the dispatch (over the tokens: what the lag wastes),
spec accepts, queue depth, paged-pool free/used) into a ring
of the last `CAKE_FLIGHT_RECORDER` iterations: a stuck or slow step says
which side of the fetch it was on. The record also covers the whole
iteration and the gap before it, from the same clock reads: `wall_ms`,
`ph` = its eight phases in ms (sweep, admit, plan, decode_dispatch,
fetch, fanout, prefill, late_land: they add up to `wall_ms`), `kind` =
`decode` / `chunk` / `last_chunk` / `idle`, `joined` = the slots the
iteration handed to the batched decode, one `_slot_join` program each (1
on a `last_chunk` record, else 0; their sum is
`cake_serve_slot_joins_total`), `restores` / `restored` = the restore
programs the iteration's prefix hits dispatched in its admit phase and the
cached blocks they restored (one `_slot_restore` program a power-of-two
piece of the matched chain: 1 / 32 for a hit of 32 blocks, 2 / 33 for one
of 33; their sums are `cake_serve_prefix_restore_dispatches_total` and
`cake_serve_prefix_restore_blocks_total`), `of_step` = the iteration
whose ids it fetched, and `gap_ms` = the `_run` loop's time since the
previous iteration when that one left work behind, else 0. An iteration that failed or found
nothing to do leaves its `seq` out of the ring. The supervisor dumps the ring to `CAKE_TRACE_DIR` as JSON
when the wedge watchdog flags a stuck dispatch or the rebuild budget
puts the engine DOWN — the post-mortem for the wedge failure mode where
the process usually gets killed with the evidence in memory. The same
ring is readable ON DEMAND at `GET /api/v1/flight` (a lock-protected
read-only snapshot, with the record's `static` part: `joined_keys`, the
layers whose keys lie joined in the pool by joined width, which says that
the rule of `cache.key_row_shape` engaged, and `attention_kinds`, the
attention layers by kind with their heads, K/V heads, window, rotary width
and rope scaling, and `sparse_layers`, what the router of a model's sparse
layers scores and what of it is held here, and `rope_rows` / `rope_bytes`, what the rope tables the
model holds come to, and `boot`, the process's account of its start-up
read at that instant; a dump carries them all) —
`cake top` and the profiling workflow inspect a live engine without
waiting for a failure.

## A run that stood still says where

Always on, beside the ring and never cleared by its turning: an
iteration whose `wall_ms + gap_ms` passes max(10 x reference, 500 ms) —
reference = the median `wall_ms` of the ring's last turn (256 records at
most), taken once a turn — is a STALL. Its flight record's `stall_ms`
holds the excess (0 on every other record) and a copy is kept (the 64
newest, plus a count and the total ms of all), joined by what else the
process saw in that stretch, from the process's own witnesses
(`cake_tpu/obs/process.py`): `gc_ms`
(collector pauses of 1 ms and more, `gc.callbacks`), `compiles` /
`compile_ms` / `compiled` (`jax.monitoring`'s backend compiles, cache
retrievals too, and the programs' names), `loop_lag_ms` (the largest lag of the event loop's tick), and
`phase`: the largest entry of `ph`, or `between` for the gap. Read it
so: `fetch` large and nothing else — the device or the runtime; a host
phase or `between` with `gc_ms` — the collector; with `compiles` — a
recompile; `loop_lag_ms` of the stall's size, on another thread, with no
pause of ours — the process or the machine stood still. Served in
`/health`'s engine block (`stalls` = `{count, total_ms, reference_ms,
worst}`, every kept record, largest first; `t` is on the recorder's
clock, so a reader with a window drops the warm-up's compile stalls), in
`GET /api/v1/flight` and the dump, as one log line a stall (at most one a
second; a WARNING, or INFO where the iteration compiled, as a start
without a warm-up does) and in
`cake_serve_step_stalls_total{phase,compiled}` /
`cake_serve_step_stall_seconds_total`: alert on `compiled="no"`. The engine block also carries
`steps_by_kind` (cumulative `{n, ms}` a kind) and `occupancy_sum`: what
tells two runs apart when neither stalled.

## Fleet telemetry plane

The router rolls per-replica signals up into decision-grade series once
per probe cycle: fleet-merged SLO percentiles (bucket-wise histogram
sums), multi-window burn rates (`cake_fleet_slo_burn_rate{window}`),
capacity headroom (`cake_fleet_headroom_tokens_per_s`), and per-replica
anomaly flags (`cake_fleet_replica_outlier`, with
`cake_fleet_replica_stale` marking probe-dead replicas whose mirrored
gauges were retracted). Served at `GET /api/v1/fleet/telemetry` and
rendered live by `cake top`. [telemetry.md](telemetry.md) is the
operator guide (series model, burn-rate formula, headroom model,
outlier rule). With `CAKE_SCALE=1` the rollup also FEEDS the
closed-loop autoscaler: scale actions are counted in
`cake_fleet_scale_actions_total{direction,reason}` with spawn/drain
progress in `cake_fleet_scale_pending_spawns` /
`cake_fleet_scale_managed_replicas`, and the typed decision ring is
served at `GET /api/v1/fleet/autoscale`
([autoscaling.md](autoscaling.md) is the operator guide).

## SLO accounting

The batched engine path decomposes request latency into
`cake_serve_ttft_seconds` / `cake_serve_itl_seconds` /
`cake_serve_e2e_seconds`, labeled by outcome (`ok` / `cancelled` /
`error`) and observed per terminal request. Every observation carries
the request id as a per-bucket sampled exemplar (JSON via
`/api/v1/slo` — the 0.0.4 text format has no exemplar syntax), so a bad
percentile links to the concrete timeline that explains it. The
sequential loops keep feeding `cake_ttft_seconds` /
`cake_decode_token_seconds` as before.

## Wire timing echo

Workers echo `tm = {read_ms, deser_ms, fwd_ms, ser_ms}` in every
`tensor_result`; the master subtracts the echoed phases from its
observed RTT and the remainder is `wire` (TCP + response write +
scheduling). `RemoteStage.rtt_stats()` reports p50/p95/mean/min per
phase, and each successful hop also lands a `cluster_hop` timeline
event against the request in flight.

## Guardrails

`make obs-smoke` runs `make lint` (the static-analysis pass — its
`metric-registry` rule checks every constructed instrument name against
this file, `hot-timing` keeps ad-hoc wall clocks off hot paths), the
`make trace-smoke` cross-tier drive (one request through a real
router + replica must yield a stitched two-tier timeline and non-zero
SLO histograms), and `scripts/obs_smoke.py` (a traced CPU generation
asserting `/metrics` histograms and the Chrome-trace export are live).
The `CAKE_TRACE_*` / `CAKE_FLIGHT_RECORDER` knobs are registered in
`cake_tpu/knobs.py` and listed in the generated [knobs.md](knobs.md).
"""


def generate_doc() -> str:
    """The docs/observability.md body, fully generated."""
    # the canonical instrument declarations live in obs/__init__.py;
    # importing the package populates REGISTRY before we render it
    from . import REGISTRY
    from .spans import SCOPE_CATALOG, SPAN_CATALOG
    from .timeline import EVENT_KINDS

    out = [_HEADER]
    out += ["## Metric catalog", "",
            "Every instrument in the process-global registry, declared "
            "once in", "`cake_tpu/obs/__init__.py`:", "",
            "| metric | type | labels | meaning |", "|---|---|---|---|"]
    for m in sorted(REGISTRY._metrics.values(), key=lambda m: m.name):
        labels = ", ".join(m.labelnames) if m.labelnames else "—"
        out.append(f"| `{m.name}` | {m.typ} | {labels} | {m.help} |")
    out += ["", "## Span catalog", "",
            "Names recorded into the span recorder (RECORDER), by the "
            "layer that records them:", "",
            "| span | recorded by |", "|---|---|"]
    for name, where in SPAN_CATALOG:
        out.append(f"| `{name}` | {where} |")
    out += ["", "## Scope catalog", "",
            "`jax.named_scope` names inside the compiled programs. A scope "
            "is metadata on the", "ops traced inside it: in a device trace "
            "(xprof's op view; in a TPU xplane the `tf_op` stat of the "
            "op's event metadata)", "the op's name holds "
            "`.../cake.attn/...`, or `.../vmap(cake.attn)/...` where the",
            "batching transform wraps the outermost scope, so device time "
            "sums by part of the", "model across edits that renumber the "
            "fusions: `benchmark/trace_reduce.py`'s", "`compact` "
            "reduces a run's trace to it per execution of `_decode_slots` "
            "and `_prefill_slot`. The", "persistent compile cache keys on "
            "this metadata (`utils/compile_cache.py`), so a", "cached "
            "executable always carries the scopes of the code that asked "
            "for it.",
            "", "| scope | wraps |", "|---|---|"]
    for name, where in SCOPE_CATALOG:
        out.append(f"| `{name}` | {where} |")
    out += ["", "## Timeline event catalog", "",
            "Typed per-request lifecycle events "
            "(`/api/v1/requests/<id>`); the store rejects kinds missing "
            "from this table:", "",
            "| event | meaning |", "|---|---|"]
    for kind, doc in EVENT_KINDS.items():
        out.append(f"| `{kind}` | {doc} |")
    out.append("")
    return "\n".join(out)
