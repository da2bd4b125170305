"""Inside one scheduler iteration (PR 26): the leaf children of a
`serve.step` span (`serve.sweep` ... `serve.fanout`, tied to it by
`args.parent`) say what the host was doing, and the device's idle gaps are
split over them with `trace_reduce.label_gaps`.

The two clocks first. `Trace.perf_to_prof` ties perf_counter to the
profiler's HOST plane (one annotation); the device plane's own stamps sit
a few milliseconds off that (3 ms early in the first traced run of PR 26:
every `_decode_slots` execution began before the host span that dispatched
it). A phase is 0.01-3 ms long, so the gaps would be put down to the phase
before the right one. `device_lead_ns` measures the offset in the run
itself, from causality, and the spans are shifted by it.

A program without these spans (the parent of the PR that brought them)
gives every reader here nothing to read: they return None.
"""
from __future__ import annotations

import bisect

import trace_reduce

# host phases by what they are to the device: work before anything can be
# dispatched, the dispatches themselves, the wait for the ids, the fan-out
GROUPS = {
    "admit": ("serve.sweep", "serve.admit", "serve.plan"),
    "dispatch": ("serve.decode_dispatch", "serve.prefill_chunk",
                 "serve.prefill_finish"),
    "fetch": ("serve.fetch",),
    "fanout": ("serve.fanout",),
}
UNNAMED = "unnamed"
DECODE = "_decode_slots"


def _cached(ctx, key: str, make):
    got = getattr(ctx, key, None)
    if got is None:
        got = make()
        setattr(ctx, key, got)
    return got


def device_lead_ns(ctx) -> int:
    """How far the device plane's clock runs ahead of the host spans as
    `perf_to_prof` places them. The sampled ids cannot be on the host
    before the program that made them has ended, so every `serve.fetch`
    ends after the `_decode_slots` execution it waited for: the smallest
    (fetch end - execution end) is the offset plus the fastest
    device->host hop of the window, which is taken as nothing. Steps are
    30 ms and more apart, so an execution pairs with the fetch that ends
    nearest to its own end. 0 when there is too little to pair."""
    def make():
        tr = ctx.trace
        ends = sorted(tr.perf_to_prof((e["ts"] + e["dur"]) * 1000)
                      for e in ctx.spans if e["name"] == "serve.fetch")
        leads = []
        for _, start, dur in tr.events("modules", DECODE):
            k = bisect.bisect_left(ends, start + dur)
            near = [ends[j] - (start + dur) for j in (k - 1, k)
                    if 0 <= j < len(ends)]
            if near and abs(min(near, key=abs)) < 10_000_000:
                leads.append(min(near, key=abs))
        return min(leads) if len(leads) >= 5 else 0
    return _cached(ctx, "_device_lead_ns", make)


def idle_seconds(ctx) -> dict | None:
    """Seconds of the first device plane's idle time in the traced window
    under each group of host phases, and under none (`unnamed`: between
    steps, or a hole in the cover of `serve.step`)."""
    def make():
        tr, lead = ctx.trace, device_lead_ns(ctx)
        steps = {e["args"]["id"] for e in ctx.spans
                 if e["name"] == "serve.step" and "id" in e.get("args", {})}
        spans = {g: [] for g in GROUPS}
        group_of = {n: g for g, names in GROUPS.items() for n in names}
        for e in ctx.spans:
            g = group_of.get(e["name"])
            if g and e.get("args", {}).get("parent") in steps:
                t0 = tr.perf_to_prof(e["ts"] * 1000) - lead
                spans[g].append((t0, t0 + e["dur"] * 1000))
        if not tr.devices or not any(spans.values()):
            return {}
        return trace_reduce.label_gaps(tr.idle_gaps(), spans, list(GROUPS),
                                       UNNAMED)
    return _cached(ctx, "_phase_idle_s", make) or None


def idle_share(ctx, group: str) -> float | None:
    """That group's part of `device.idle_share`, in points of the window."""
    secs = idle_seconds(ctx)
    if secs is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * secs[group] / ctx.trace.window_s


def step_host_ms(ctx) -> list[float]:
    """Per `serve.step` span inside the measured window: its duration minus
    its `serve.fetch` child, the time the scheduler was NOT blocked on the
    device."""
    fetch = {}
    for e in ctx.spans:
        if e["name"] == "serve.fetch" and "parent" in e.get("args", {}):
            fetch[e["args"]["parent"]] = e["dur"]
    if not fetch:
        return []
    return [(e["dur"] - fetch.get(e["args"]["id"], 0)) / 1e3
            for e in ctx.spans_named("serve.step") if "id" in e.get("args", {})]


def token_stamps(timeline: dict) -> list[float]:
    """The engine's stamp for each token of one request, in seconds on the
    perf_counter clock: the `first_token` event, then one stamp per token
    of every `decode` / `spec_verify` event (the iteration that fetched the
    first token also emitted the second, so its `decode` event, which
    precedes `first_token`, stamps token two)."""
    t0 = timeline["t0_us"]
    first, rest = [], []
    for e in timeline["events"]:
        t = (t0 + e["t_ms"] * 1e3) / 1e6
        if e["kind"] == "first_token":
            first.append(t)
        elif e["kind"] == "decode":
            rest.append(t)
        elif e["kind"] == "spec_verify":
            rest += [t] * (int(e.get("accepted", 0)) + 1)
    return first[:1] + rest if first else []
