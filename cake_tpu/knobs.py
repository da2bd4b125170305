"""Central registry of every `CAKE_*` environment knob.

Before this module existed, 27 raw `os.environ` reads in 18 files each
carried their own default and their own parsing quirks, and the knob
tables in docs/ drifted from the code (the serving docs said one default,
the engine shipped another). Now:

  * every knob is declared ONCE here with a type, a default and a
    one-line doc;
  * call sites read through :func:`get` (env is still consulted on every
    call, so tests that monkeypatch `os.environ` keep working — nothing
    is snapshotted at import);
  * `docs/knobs.md` is GENERATED from this registry (`make knobs-doc`,
    `python -m cake_tpu.knobs`), and tests/test_analysis.py pins the file
    to the registry so it cannot drift again;
  * the `knob-registry` lint rule (cake_tpu/analysis) fails the build on
    any raw `os.environ`/`os.getenv` read of a `CAKE_*` name outside this
    module.

Empty-string env values fall back to the default everywhere (the historic
call sites were split between `get(k, d)` and `get(k, d) or d`; the `or`
form is the one that survives `CAKE_X=` in a wrapper script).
"""
from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = ["Knob", "REGISTRY", "get", "get_str", "generate_doc"]


@dataclass(frozen=True)
class Knob:
    name: str
    cast: type              # int | float | str | bool
    default: object
    area: str               # docs/knobs.md section
    doc: str                # one line, imperative — what turning it does


REGISTRY: dict[str, Knob] = {}


def _knob(name: str, cast: type, default, area: str, doc: str) -> None:
    if name in REGISTRY:
        raise ValueError(f"duplicate knob {name}")
    REGISTRY[name] = Knob(name, cast, default, area, doc)


def _parse_bool(raw: str) -> bool:
    return raw.strip().lower() not in ("0", "false", "off", "no")


def get(name: str):
    """Typed value of knob `name`: the parsed env var when set and
    non-empty, else the registered default. Unregistered names are a
    programming error (KeyError), not a silent empty read."""
    kb = REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return kb.default
    if kb.cast is bool:
        return _parse_bool(raw)
    return kb.cast(raw)


def get_str(name: str) -> str:
    """`get` for str knobs where callers want "" (not None) when unset."""
    v = get(name)
    return "" if v is None else str(v)


# -- serve ----------------------------------------------------------------
_knob("CAKE_SERVE_SLOTS", int, 4, "serve",
      "KV slots = max concurrent batched decodes; 0 disables the engine "
      "(API falls back to the locked sequential path)")
_knob("CAKE_MAX_QUEUE", int, 64, "serve",
      "bounded admission queue: requests waiting beyond free slots; "
      "overflow answers HTTP 429 + Retry-After")
_knob("CAKE_SERVE_CTX", int, 4096, "serve",
      "per-slot context (prompt + generation), capped by the model's "
      "max_cache_len; pool HBM scales with slots x ctx")
_knob("CAKE_PREFILL_CHUNK", int, 256, "serve",
      "per-iteration chunked-admission token budget (clamped to a power "
      "of two in [16, ctx]); also the prefix-cache block size")
_knob("CAKE_PREFIX_CACHE_MB", float, 256.0, "serve",
      "device bytes for shared-prefix KV blocks (LRU); 0 disables "
      "prefix reuse")
_knob("CAKE_QUEUE_DEADLINE_S", float, 0.0, "serve",
      "max admission-queue wait before a request is 503ed instead of "
      "admitted for a client that gave up; 0 disables")
_knob("CAKE_DRAIN_TIMEOUT_S", float, 30.0, "serve",
      "graceful-shutdown budget: admission stops (503 + Retry-After) and "
      "active slots get this long to finish before close()")
_knob("CAKE_REQUEST_DEADLINE_S", float, 0.0, "serve",
      "max TOTAL request age (queue + prefill + decode) before an "
      "admitted slot is cancelled with a typed 504; 0 disables")
_knob("CAKE_STEP_WATCHDOG_S", float, 0.0, "serve",
      "wedge watchdog: a device dispatch in flight longer than this "
      "flags the engine wedged in /health (503) without killing it; "
      "0 disables — set it above your worst in-iteration XLA compile")
_knob("CAKE_ENGINE_REBUILDS", int, 3, "serve",
      "slot-pool rebuild-by-replay budget per rolling "
      "CAKE_ENGINE_REBUILD_WINDOW_S; exhausting it puts the engine in "
      "the honest DOWN state (503 + Retry-After, restore loop probing)")
_knob("CAKE_ENGINE_REBUILD_WINDOW_S", float, 300.0, "serve",
      "rolling window over which CAKE_ENGINE_REBUILDS is counted — a "
      "crash storm is a dying device, sparse blips are not")
_knob("CAKE_ENGINE_RESTORE_S", float, 5.0, "serve",
      "DOWN-state probe interval: a trial prefill runs this often until "
      "one succeeds, then the pool is rebuilt and admission reopens")
_knob("CAKE_KV_BLOCKS", int, 0, "serve",
      "paged-KV pool size in physical blocks; > 0 replaces the "
      "contiguous slots x ctx rows with a shared block pool behind "
      "per-slot block tables (refcounted prefix sharing + preemption); "
      "0 keeps the contiguous pool")
_knob("CAKE_KV_BLOCK_TOKENS", int, 16, "serve",
      "tokens per paged-KV block (clamped to a power of two in "
      "[8, CAKE_PREFILL_CHUNK] so chunk boundaries stay block-aligned); "
      "pool HBM = blocks x block-tokens of KV")
_knob("CAKE_PREEMPT_MODE", str, "swap", "serve",
      'paged-pool exhaustion policy: "swap" parks the victim\'s blocks '
      'in host RAM (bit-identical resume, even sampled); "recompute" '
      "drops them and replays prompt+generated at resume (greedy "
      "bit-identical)")
_knob("CAKE_SERVE_FAULT_PLAN", str, None, "serve",
      'deterministic serve-engine fault injection (tests/drills only), '
      'e.g. "raise_on_step=6;kind=device" — see serve/faults.py')

# -- qos (unified admission plane) ----------------------------------------
_knob("CAKE_QOS_WEIGHTS", str, None, "qos",
      'weighted-fair dequeue weights per QoS class, e.g. '
      '"interactive=8,standard=4,batch=1" (the default); weights must '
      "be > 0 — under saturation service converges to the weight ratio "
      "and every class still progresses")
_knob("CAKE_QOS_BOUNDS", str, None, "qos",
      'per-class admission-queue bounds overriding the engine default, '
      'e.g. "batch=128,interactive=32"; overflow answers a class-aware '
      "429 whose Retry-After reflects that class's backlog")
_knob("CAKE_QOS_TENANTS", str, None, "qos",
      'per-tenant quota policies, e.g. "acme:rps=5,burst=10,inflight=4,'
      'max_class=standard;*:rps=20" — token-bucket rate + concurrent '
      "inflight + QoS ceiling, keyed by X-Cake-Tenant or the bearer "
      "key; unconfigured tenants are default-open (typed 429 "
      "tenant_quota when over)")
_knob("CAKE_JOB_WORKERS", int, 1, "qos",
      "max concurrently RUNNING heavy generation jobs (image "
      "diffusion / TTS) under the admission plane; queued jobs drain "
      "weighted-fair behind interactive traffic")
_knob("CAKE_IMAGE_MAX_SIZE", int, 2048, "qos",
      "max image width/height the /v1/images endpoints accept; "
      "out-of-range sizes answer 400 instead of letting one request "
      "OOM the device")
_knob("CAKE_QOS_BATCH_SHED_FRAC", float, 0.8, "qos",
      "router-tier batch shedding threshold as a fraction of the "
      "global in-flight cap: batch-class requests shed 429 at this "
      "fill level so the remaining headroom stays reserved for "
      "interactive traffic; >= 1 disables the early shed")

# -- speculative decoding -------------------------------------------------
_knob("CAKE_SPEC", str, None, "spec",
      'drafter for spec=None paths: "ngram" enables prompt-lookup '
      'speculation; unset/empty/"off" disables')
_knob("CAKE_SPEC_K", int, 6, "spec",
      "per-slot draft window: tokens proposed per verify step, clamped "
      "to [1, 32]; in the serve engine every occupied slot carries its "
      "own window through ONE batched verify dispatch (k static "
      "via the draft shape: one executable on contiguous rows, one per "
      "slot-bucket on a paged pool)")
_knob("CAKE_SPEC_NGRAM", int, 3, "spec",
      "n-gram drafter max match window: the prompt-lookup drafter "
      "matches the last [2, this] tokens against the slot's own history "
      "(bigger = more specific matches tried first)")
_knob("CAKE_SPEC_RESERVE", int, 0, "spec",
      "paged-mode speculative frontier-reservation cap, tokens per slot "
      "per verify: draft windows are clamped so at most this much "
      "unwritten frontier is backed by blocks ahead of the dispatch "
      "(rolled back on rejection/preemption); 0 = the full draft window")

# -- fleet (router tier over N serve replicas) ----------------------------
_knob("CAKE_FLEET_PROBE_S", float, 2.0, "fleet",
      "router health-probe interval per replica: each tick GETs /health "
      "and consumes the engine block (down/wedged/draining, queue depth, "
      "kv_pool occupancy) into the membership state machine")
_knob("CAKE_FLEET_EJECT_FAILS", int, 3, "fleet",
      "consecutive transport failures (connect refused/reset/timeout) "
      "that eject a replica from routing")
_knob("CAKE_FLEET_ERR_WINDOW", int, 32, "fleet",
      "rolling per-replica result window the gray-failure detector "
      "computes its error rate and TTFT p95 over")
_knob("CAKE_FLEET_ERR_RATE", float, 0.5, "fleet",
      "gray-failure eject threshold: error rate over the rolling window "
      "(needs >= 8 samples) at or above this ejects the replica")
_knob("CAKE_FLEET_DEGRADED_TTFT_MS", float, 0.0, "fleet",
      "gray-failure eject threshold on rolling TTFT p95 — a slow-but-"
      "alive replica is ejected before clients notice; 0 disables "
      "(same shape as the cluster hop detector's CAKE_HOP_DEGRADED_MS)")
_knob("CAKE_FLEET_EJECT_S", float, 5.0, "fleet",
      "ejection hold before the half-open probe (doubles per consecutive "
      "re-eject, capped at 8x); a half-open replica readmits on one "
      "successful trial request or two consecutive healthy probes")
_knob("CAKE_FLEET_RETRIES", int, 2, "fleet",
      "per-request failover budget: how many ADDITIONAL replicas a "
      "non-streamed (or pre-first-token streamed) request may retry on "
      "after its first attempt fails; exhaustion answers a typed 503")
_knob("CAKE_FLEET_BACKOFF_S", float, 0.05, "fleet",
      "retry backoff base between failover attempts (capped exponential "
      "+/-25% jitter, same scheme as cluster recovery)")
_knob("CAKE_FLEET_HEDGE_MS", float, 0.0, "fleet",
      "tail-hedging threshold for non-streamed requests: no reply after "
      "this long fires a duplicate at the next-best replica and the "
      "first response wins (Dean & Barroso hedged requests); 0 disables")
_knob("CAKE_FLEET_MAX_INFLIGHT", int, 0, "fleet",
      "global router admission bound: in-flight proxied requests at or "
      "past this shed typed 429s AT THE ROUTER before any replica "
      "admits; 0 = auto (sum of per-replica caps)")
_knob("CAKE_FLEET_REPLICA_INFLIGHT", int, 0, "fleet",
      "per-replica in-flight cap; 0 = auto (2x the replica's slot count "
      "from its last health probe, 8 before the first probe lands)")
_knob("CAKE_FLEET_AFFINITY", bool, True, "fleet",
      "prefix-affinity routing (blake2b chain over the rendered prompt, "
      "rendezvous-hashed onto replicas so conversational follow-ups land "
      "on the replica holding their KV blocks); off = round-robin")
_knob("CAKE_FLEET_AFFINITY_BLOCKS", int, 64, "fleet",
      "affinity chain depth cap in 256-byte blocks over the conversation "
      "head (leading system message + first user message) — a cost "
      "backstop against pathological first messages, NOT a spreading "
      "window: it must comfortably cover the system prompt, or every "
      "conversation hashes to the same key and one replica goes hot")
_knob("CAKE_FLEET_ATTEMPT_TIMEOUT_S", float, 0.0, "fleet",
      "DEPRECATED single per-attempt deadline on one replica try "
      "(connect + full response); still honored when set > 0, but the "
      "0.0=forever default is superseded by the split "
      "CAKE_FLEET_CONNECT_TIMEOUT_S / CAKE_FLEET_FIRST_BYTE_TIMEOUT_S "
      "deadlines, which bound the partition-shaped hangs this knob left "
      "unbounded by default")
_knob("CAKE_FLEET_CONNECT_TIMEOUT_S", float, 5.0, "fleet",
      "per-attempt TCP connect deadline on one replica try; an overrun "
      "counts as a transport failure and the request fails over — "
      "bounds the refused/black-holed-SYN partition shapes; 0 disables "
      "(not recommended: that re-opens the unbounded hang)")
_knob("CAKE_FLEET_FIRST_BYTE_TIMEOUT_S", float, 120.0, "fleet",
      "per-attempt first-byte deadline: time from request sent to the "
      "first response byte (headers) on one replica try, covering the "
      "accept-then-never-respond black hole; streamed bodies stay "
      "unbounded after the first byte (the stream-resume plane handles "
      "mid-body breaks); an overrun is a retryable transport failure; "
      "0 disables")
_knob("CAKE_FLEET_DISCOVER_S", float, 0.0, "fleet",
      "periodic UDP re-discovery interval: newly announced `cake serve "
      "--announce` replicas join the registry without a router restart; "
      "0 = discover once at startup only")
_knob("CAKE_FLEET_STREAM_RESUMES", int, 1, "fleet",
      "per-stream self-healing budget: how many times the router may "
      "transparently splice-resume a stream broken AFTER its commit "
      "point (first relayed byte) by re-issuing the buffered partial "
      "content in continuation mode on the affinity next-best replica; "
      "0 restores the client-visible typed error event on every break")
_knob("CAKE_FLEET_RESUME_BUFFER_KB", int, 256, "fleet",
      "per-stream replay-buffer bound (KB of relayed assistant text) "
      "the resume splice is built from; a stream whose content outgrows "
      "the buffer falls back to the typed error event (the resume_token "
      "still lets the client finish via continuation mode)")
_knob("CAKE_FLEET_FAULT_PLAN", str, None, "fleet",
      'deterministic router fault injection (tests/drills only), e.g. '
      '"replica=r1;refuse_after_ops=3" — see fleet/faults.py')
_knob("CAKE_KVSHARE", bool, False, "fleet",
      "fleet-shared KV tier (fleet/kvshare/): replicas export/import "
      "prefix-cache chains as checksummed blobs, the router injects a "
      "peer directory so cache-cold replicas fetch a warm peer's prefix "
      "instead of re-prefilling, and broken/drained streams migrate "
      "their live swap blob to the new owner (bit-exact resume, rng "
      "included); off keeps all KV strictly replica-local")
_knob("CAKE_KVSHARE_FETCH_TIMEOUT_S", float, 2.0, "fleet",
      "deadline on ONE cross-replica KV blob fetch (prefix fetch-"
      "before-recompute, and the router's stream-blob GET/POST legs); "
      "an overrun falls back to honest recompute / continuation-mode "
      "re-prefill — never a client-visible error")
_knob("CAKE_KVSHARE_INVENTORY", int, 32, "fleet",
      "hot chain keys each replica advertises through /health into the "
      "router's peer directory (most-recently-used first); bounds the "
      "directory header the router injects per request, so it must stay "
      "well under the ~8 KB header limit")

# -- telemetry (fleet rollups, SLO objectives) ----------------------------
_knob("CAKE_SLO_TTFT_MS", float, 2000.0, "telemetry",
      "fleet TTFT objective in milliseconds: a request whose serve-side "
      "TTFT lands in a histogram bucket above this counts as BAD in the "
      "burn-rate computation (alongside errored requests)")
_knob("CAKE_SLO_ERR_RATE", float, 0.01, "telemetry",
      "fleet error budget as a bad-request fraction: burn rate = "
      "windowed bad fraction / this, so burn > 1 means the budget is "
      "burning faster than it accrues and burn = 1 exactly spends it")
_knob("CAKE_TELEM_FAST_WINDOW_S", float, 300.0, "telemetry",
      "fast burn-rate window (page-worthy: a high burn here means the "
      "budget dies in hours) — also the window for headroom token rates")
_knob("CAKE_TELEM_SLOW_WINDOW_S", float, 3600.0, "telemetry",
      "slow burn-rate window (ticket-worthy sustained burn); also the "
      "retention window of every telemetry ring, so it bounds how much "
      "history /api/v1/fleet/telemetry can return")
_knob("CAKE_TELEM_RING", int, 4096, "telemetry",
      "hard per-series sample cap backing the fixed-window rings — a "
      "memory bound independent of probe rate x window length")
_knob("CAKE_TELEM_OUTLIER_K", float, 3.0, "telemetry",
      "anomaly threshold: a replica whose TTFT p95 or error rate sits "
      "more than k robust standard deviations (MAD-scaled) from the "
      "fleet median is flagged `outlier` in /fleet — never auto-ejected")
_knob("CAKE_TELEM_OUTLIER_MIN_N", int, 3, "telemetry",
      "minimum live replicas before outlier detection runs (a median "
      "over 2 replicas cannot say which one is wrong)")

# -- autoscale (closed-loop elastic fleet) --------------------------------
_knob("CAKE_SCALE", bool, False, "autoscale",
      "closed-loop autoscaling in the router: each probe/telemetry "
      "cycle the controller (fleet/autoscale.py) decides scale-out / "
      "scale-in / hold and the lifecycle manager executes it; off = "
      "the telemetry plane stays advisory")
_knob("CAKE_SCALE_SPAWN_CMD", str, None, "autoscale",
      'scale-out spawn template, e.g. "cake serve model.safetensors '
      '--announce --port {port}" — {port} and {name} are filled per '
      "spawn; the replica is admitted to routing only after its "
      "/health answers 200 (UDP discovery admits announced replicas "
      "too); unset disables scale-out execution (decisions still log)")
_knob("CAKE_SCALE_BURN_FAST", float, 2.0, "autoscale",
      "scale-out trigger on the FAST-window SLO burn rate: burn above "
      "this means interactive TTFT/error budget is burning page-fast, "
      "so capacity is added even while batch backlog absorbs")
_knob("CAKE_SCALE_HEADROOM_MIN", float, 0.0, "autoscale",
      "scale-out trigger on fleet capacity headroom (tokens/s): "
      "headroom below this floor adds a replica before saturation "
      "turns into burn; 0 disables the headroom trigger")
_knob("CAKE_SCALE_HEADROOM_HIGH", float, 0.0, "autoscale",
      "scale-in high-water mark (tokens/s): only when headroom sits "
      "ABOVE this continuously for a full CAKE_SCALE_COOLDOWN_S with "
      "clean fast+slow burn does the controller retire a replica; "
      "0 disables scale-in entirely (scale-out-only autoscaling)")
_knob("CAKE_SCALE_COOLDOWN_S", float, 60.0, "autoscale",
      "hysteresis clock: minimum spacing between scale actions, AND "
      "how long the scale-in conditions must hold continuously before "
      "one fires (restoring the CAKE_SCALE_MIN floor is exempt)")
_knob("CAKE_SCALE_MIN", int, 1, "autoscale",
      "replica floor: scale-in never drops below it, and a fleet found "
      "under it (replica died, kill -9) is topped back up immediately, "
      "cooldown or not")
_knob("CAKE_SCALE_MAX", int, 8, "autoscale",
      "replica ceiling: scale-out (pending spawns included) never "
      "exceeds it no matter how hard the burn/headroom triggers pull")
_knob("CAKE_SCALE_WARMUP_S", float, 30.0, "autoscale",
      "warm-up grace after a replica is first seen (or restarts): "
      "while any replica is this young the controller holds — a cold "
      "replica's empty histograms would misread as zero headroom and "
      "re-trigger the very scale-out that just ran")
_knob("CAKE_SCALE_SPAWN_TIMEOUT_S", float, 180.0, "autoscale",
      "spawn-to-healthy admission deadline: a spawned replica whose "
      "/health never answers 200 within this is killed and the spawn "
      "recorded spawn_failed (model load + XLA compile budget)")
_knob("CAKE_SCALE_DECISIONS", int, 256, "autoscale",
      "decisions-ring capacity: typed controller/lifecycle events kept "
      "for GET /api/v1/fleet/autoscale (oldest dropped first)")

# -- cluster --------------------------------------------------------------
_knob("CAKE_CLUSTER_KEY", str, None, "cluster",
      "pre-shared key enabling distributed mode (mutual auth between "
      "master and workers); unset = single-host")
_knob("CAKE_HOP_TIMEOUT_S", float, 120.0, "cluster",
      "per-op deadline on every remote stage forward; an overrun is a "
      "typed `timeout` StageFailure and recovery takes over")
_knob("CAKE_HOP_DEGRADED_MS", float, 0.0, "cluster",
      "gray-failure threshold: rolling RTT p95 above this flags the hop "
      "degraded in /health without failing anything; 0 disables")
_knob("CAKE_REVIVE_GRACE_S", float, 60.0, "cluster",
      "deadline for the FIRST forward after a recovery reconnect (it may "
      "carry an in-band XLA compile on the re-assigned worker)")
_knob("CAKE_RECOVERY_RETRIES", int, 3, "cluster",
      "quarantine -> reconnect -> replay cycles one generation may spend "
      "before failing fast with ClusterDegradedError")
_knob("CAKE_RECOVERY_BACKOFF_S", float, 0.5, "cluster",
      "reconnect backoff base (exponential, capped, +/-25% jitter)")
_knob("CAKE_RESTORE_INTERVAL_S", float, 5.0, "cluster",
      "degraded-mode background probe interval until the lost worker "
      "comes back")
_knob("CAKE_FAULT_PLAN", str, None, "cluster",
      'deterministic fault injection plan (tests/drills only), e.g. '
      '"w0:drop_after_ops=5"')

# -- observability --------------------------------------------------------
_knob("CAKE_TRACE_DIR", str, None, "obs",
      "directory for Chrome-trace span exports; setting it also enables "
      "the span recorder at startup")
_knob("CAKE_TRACE_EVENTS", int, 16384, "obs",
      "span recorder ring-buffer capacity (oldest events drop first)")
_knob("CAKE_TRACE_REQUESTS", int, 256, "obs",
      "per-request timeline ring: how many recent requests keep their "
      "typed lifecycle timeline retrievable via /api/v1/requests/<id> "
      "(oldest evicted first; recording is always on)")
_knob("CAKE_FLIGHT_RECORDER", int, 256, "obs",
      "serve-engine flight recorder: scheduler iterations kept in the "
      "in-memory ring the supervisor dumps to CAKE_TRACE_DIR on a "
      "wedge flag or DOWN classification")

# -- ops / kernels --------------------------------------------------------
_knob("CAKE_TPU_FLASH", bool, True, "ops",
      "the Pallas attention kernels (prefill and decode) on TPU backends "
      "(CPU always uses the reference path)")

# -- paths ----------------------------------------------------------------
_knob("CAKE_TPU_CACHE", str, "~/.cache/cake-tpu", "paths",
      "worker model-data cache root (split weights, downloaded shards)")


_AREA_TITLES = (
    ("serve", "Serving (continuous-batching engine)"),
    ("qos", "QoS (unified admission plane)"),
    ("spec", "Speculative decoding"),
    ("fleet", "Fleet (router tier over N serve replicas)"),
    ("telemetry", "Telemetry (fleet rollups, SLO objectives)"),
    ("autoscale", "Autoscale (closed-loop elastic fleet)"),
    ("cluster", "Cluster (distributed pipeline + fault tolerance)"),
    ("obs", "Observability"),
    ("ops", "Ops / kernels"),
    ("paths", "Paths"),
)


def generate_doc() -> str:
    """docs/knobs.md body — one table per area, straight from REGISTRY."""
    out = [
        "# Environment knobs",
        "",
        "<!-- GENERATED FILE — do not edit. Source of truth is",
        "     cake_tpu/knobs.py; regenerate with `make knobs-doc`",
        "     (tests/test_analysis.py pins this file to the registry). -->",
        "",
        "Every `CAKE_*` environment variable, generated from the central",
        "registry in `cake_tpu/knobs.py`. All knobs are read at use time",
        "(not import time), and an empty value behaves like unset. The",
        "`knob-registry` lint rule (see [static_analysis.md]"
        "(static_analysis.md)) keeps raw `os.environ` reads of these",
        "names out of the tree.",
        "",
    ]
    for area, title in _AREA_TITLES:
        knobs = [k for k in REGISTRY.values() if k.area == area]
        if not knobs:
            continue
        out += [f"## {title}", "",
                "| knob | type | default | meaning |",
                "|---|---|---|---|"]
        for kb in knobs:
            default = "unset" if kb.default is None else str(kb.default)
            out.append(f"| `{kb.name}` | {kb.cast.__name__} | {default} "
                       f"| {kb.doc} |")
        out.append("")
    return "\n".join(out)


if __name__ == "__main__":
    print(generate_doc())
