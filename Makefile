# cake-tpu developer entry points (ref: the reference Makefile's build/test
# targets; mobile app targets have no analog here — see PARITY.md §2f).

.PHONY: install test lint knobs-doc metrics-doc obs-smoke trace-smoke serve-smoke qos-smoke paged-smoke chaos-smoke serve-chaos-smoke fleet-chaos-smoke partition-smoke fleet-soak kvshare-smoke telemetry-smoke spec-smoke spec-serve-smoke native clean docker

install:
	pip install -e . --no-build-isolation

# static-analysis gate (docs/static_analysis.md): AST checkers for the
# serving hot path — host-sync, recompile-hazard, use-after-donate,
# knob-registry, lock-discipline, hot-timing. Exits non-zero on any
# violation that lacks an in-line `# lint: disable=<rule> — <reason>`.
lint:
	python -m cake_tpu.analysis

# regenerate docs/knobs.md from the central registry (cake_tpu/knobs.py);
# tests/test_analysis.py pins the file to the registry
knobs-doc:
	python -m cake_tpu.knobs > docs/knobs.md

# regenerate docs/observability.md — the metric/span/timeline catalog —
# from cake_tpu/obs (catalog.py); tests/test_analysis.py pins the file,
# and the metric-registry lint checks instrument names against it
metrics-doc:
	python -m cake_tpu.obs > docs/observability.md

native:
	$(MAKE) -C csrc

test:
	python -m pytest tests/ -x -q

# request-tracing gate: one chat driven through a REAL router + replica
# (tiny CPU model) must yield a stitched timeline with events from BOTH
# tiers retrievable by its trace id from the router, and non-zero
# TTFT/ITL/e2e SLO histograms (with exemplars) in the replica's /metrics
trace-smoke: lint
	JAX_PLATFORMS=cpu python scripts/trace_smoke.py

# observability gate: the static-analysis pass (hot-timing absorbed
# check_hot_timing.py; the other six rules ride along), the cross-tier
# trace-smoke above, and a tiny traced CPU generation asserting /metrics
# histograms and the Chrome-trace export are live
obs-smoke: lint trace-smoke
	JAX_PLATFORMS=cpu python scripts/obs_smoke.py

# continuous-batching gate: concurrent chats 200 through the engine, a 429
# + Retry-After under queue saturation, non-zero serve-queue gauges in
# /metrics while saturated, and non-zero prefix-cache hits on repeated
# prompts (tiny CPU model, in-process aiohttp)
serve-smoke: lint
	JAX_PLATFORMS=cpu python scripts/serve_smoke.py

# QoS admission-plane gate: batch-image saturation (tiny diffusion stub
# through the job executor) with interleaved interactive chat — chat
# TTFT p50 must stay within 2x the idle baseline, every batch job must
# complete, and the class-labeled queue gauges must be live in /metrics
qos-smoke: lint
	JAX_PLATFORMS=cpu python scripts/qos_smoke.py

# fault-tolerance gate: master + 2 real workers on localhost, one worker
# killed mid-stream by a deterministic fault plan — the generation must
# complete bit-identical to the unfailed run with exactly one replay
# prefill, and the recovery counters must be non-zero in /metrics
chaos-smoke:
	JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

# serve-plane crash-only gate: engine under concurrent API load with one
# injected step crash — every client completes 200 bit-identical to an
# uninjected run, exactly one rebuild (non-zero
# cake_serve_engine_rebuilds_total in /metrics), /health back to 200
serve-chaos-smoke: lint
	JAX_PLATFORMS=cpu python scripts/serve_chaos_smoke.py

# fleet robustness gate: 3 real serve replicas behind the router, one
# killed mid-traffic — zero failed non-streamed requests (transparent
# failover), a visible eject -> readmit cycle in /fleet + /metrics, and
# saturation shed as router-level 429s (shed_by=router), never replica
# errors. Streamed phase (hard gate): the owning replica is killed
# MID-STREAM — the self-healed body must be byte-identical to an
# unbroken run with zero client-visible errors, and resume budget 0
# must preserve the typed error event (now with resume_token).
fleet-chaos-smoke: lint
	JAX_PLATFORMS=cpu python scripts/fleet_chaos_smoke.py

# partition-tolerance gate (tier-2): real serve replicas behind the
# router with a REAL network chaos layer (fleet/netem.ChaosProxy) on
# the victim's wire. Full partition, asymmetric probe-alive/data-dead
# (flipped via the proxy's control socket), and a delay brownout each
# eject within a bounded window with ZERO client-visible errors; the
# asymmetric eject carries evidence=data, probes alone never readmit
# it, the failed trial re-ejects with a doubled hold, and only the
# healed network's data-path trial readmits (docs/fleet.md)
partition-smoke: lint
	JAX_PLATFORMS=cpu python scripts/partition_smoke.py

# closed-loop elastic-fleet gate (tier-2: real multi-process soak, not
# part of the tier-1 pytest run): a real router with the autoscaler on
# bootstraps 0 -> min by spawning real serve child processes, a load
# ramp scales 2 -> 4 on starved headroom, the ramp's end scales 4 -> 2
# through graceful drains (every reap forced=False), a kill -9 victim
# is swept and replaced via below_min — zero client-visible errors and
# zero frozen-gauge contamination across all of it (docs/autoscaling.md)
fleet-soak: lint
	JAX_PLATFORMS=cpu python scripts/fleet_soak.py

# fleet-shared KV gate (tier-2): 3 real replicas behind the router with
# CAKE_KVSHARE=1 — a cordoned warm replica's prefix chain is fetched by
# a cache-cold peer purely off the router-injected X-Cake-KV-Peers
# directory (bit-identical greedy body, kv-fetch hit counter advancing,
# prefix_hit_tokens > 0 on the lander), and a mid-stream drain ships
# the live slot's swap blob to a peer which resumes the stream
# byte-identical with zero client-visible errors (docs/kv_sharing.md)
kvshare-smoke: lint
	JAX_PLATFORMS=cpu python scripts/kvshare_smoke.py

# fleet telemetry gate: 2 real engine-backed replicas behind the router,
# a traffic burst -> live rollup (merged fleet TTFT p95 from bucket-wise
# histogram sums, non-zero capacity headroom, burn-rate gauges on
# /metrics), flight ring readable on demand, then one replica killed ->
# stale + outlier(stale) within a probe window with the dead replica's
# mirrored gauges RETRACTED from the router's /metrics (stale-mirror
# rule; docs/telemetry.md)
telemetry-smoke: lint
	JAX_PLATFORMS=cpu python scripts/telemetry_smoke.py

# paged-KV gate: paged greedy bit-identical to the sequential path,
# prefix hit = refcount bump (shared-blocks gauge > 0, no KV copy),
# preempt-by-swap under an undersized pool with bit-identical
# continuation, kv-block gauges + preemption counter in /metrics
paged-smoke: lint
	JAX_PLATFORMS=cpu python scripts/paged_smoke.py

# speculative-decoding gate: serve engine + n-gram drafter on the tiny
# CPU model — greedy output bit-identical to a spec-off engine, >= 1
# multi-token accept, non-zero cake_serve_spec_{proposed,accepted}_total
spec-smoke:
	JAX_PLATFORMS=cpu python scripts/spec_smoke.py

# batched-speculation serve gate: concurrent API clients through the
# PAGED speculating engine (no stand-down) — bit-identical greedy
# outputs vs a spec-off engine, non-zero spec counters in /metrics,
# batched spec block in /health
spec-serve-smoke: lint
	JAX_PLATFORMS=cpu python scripts/spec_serve_smoke.py

dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	python -c "import jax; jax.config.update('jax_platforms','cpu'); \
	import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

docker:
	docker compose build

clean:
	$(MAKE) -C csrc clean
	find . -name __pycache__ -type d -exec rm -rf {} +
