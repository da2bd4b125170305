"""Run by hand on the CPU, like test_harness.py:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The readers PR 26 added, on a slice of a chip trace of qwen3-4b.chat taken
with that PR's program (data/cut_phases.py made it), and on what a program
without the new spans leaves them to read.
"""
from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(HERE, "data"))

import cut_phases  # noqa: E402
import manifest  # noqa: E402
import phases  # noqa: E402

IDLE = [f"engine.idle.{g}_share" for g in list(phases.GROUPS)
        + [phases.UNNAMED]]


def reader(name):
    return manifest.metric_reader(BENCH, name)


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "phases_trace.json")) as f:
        data = json.load(f)
    return cut_phases.Ctx(data), data["expected"]


def test_every_new_metric_is_in_the_manifest_for_every_cell():
    m = manifest.load()
    cells = [w["name"] for w in m["workloads"]]
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in IDLE + ["engine.step_host_p50_ms", "api.handoff_p95_ms"]:
        assert by_name[name]["workloads"] == cells
        assert by_name[name]["layer"] in ("engine", "api")


def test_idle_shares_sum_to_the_idle_share(recorded):
    ctx, expected = recorded
    shares = {name: reader(name)(ctx) for name in IDLE}
    idle = reader("device.idle_share")(ctx)
    assert sum(shares.values()) == pytest.approx(idle, abs=1e-6)
    assert all(v >= 0 for v in shares.values())
    assert shares["engine.idle.unnamed_share"] < idle / 10
    for name, value in shares.items():
        assert value == pytest.approx(expected[name])
    assert idle == pytest.approx(expected["device.idle_share"])
    assert reader("engine.step_host_p50_ms")(ctx) == pytest.approx(
        expected["engine.step_host_p50_ms"])


def test_spans_are_laid_on_the_devices_clock_by_causality(recorded):
    """As recorded every decode execution begins before the host span that
    dispatched it; shifted by the measured lead none does, and no fetch
    ends before the execution it waited for."""
    ctx, expected = recorded
    tr, lead = ctx.trace, phases.device_lead_ns(ctx)
    assert lead == expected["device_lead_ns"] and 1e6 < lead < 1e7

    def on_device(name, shift):
        return sorted((tr.perf_to_prof(e["ts"] * 1000) - shift,
                       tr.perf_to_prof((e["ts"] + e["dur"]) * 1000) - shift)
                      for e in ctx.spans if e["name"] == name)

    execs = sorted((s, s + d) for _, s, d in
                   tr.events("modules", phases.DECODE))
    assert len(execs) >= 5

    def begun_before_dispatch(shift):
        starts = [a for a, _ in on_device("serve.decode_dispatch", shift)]
        return sum(1 for s, _ in execs
                   if min(abs(s - a) for a in starts) < 5e6
                   and min(starts, key=lambda a: abs(s - a)) > s)

    assert begun_before_dispatch(0) == len(execs)
    assert begun_before_dispatch(lead) == 0
    for a, b in on_device("serve.fetch", lead):
        waited = [e for s, e in execs if a < e < b + 5e6]
        assert all(e <= b for e in waited)
    # without the shift the gaps go to the phase before the right one:
    # the time the dispatch call held the device back reads as a slow fetch
    ctx._phase_idle_s, ctx._device_lead_ns = None, 0
    assert reader("engine.idle.fetch_share")(ctx) > \
        3 * expected["engine.idle.fetch_share"]
    assert reader("engine.idle.dispatch_share")(ctx) < \
        expected["engine.idle.dispatch_share"]


def test_step_host_time_leaves_the_fetch_out():
    spans = [{"name": "serve.step", "ts": 0, "dur": 34_000,
              "args": {"id": 1, "step": 7}},
             {"name": "serve.fetch", "ts": 3_000, "dur": 30_000,
              "args": {"id": 2, "parent": 1, "step": 7}},
             {"name": "serve.step", "ts": 40_000, "dur": 6_000,
              "args": {"id": 3, "step": 8}}]      # a step that only prefilled
    ctx = SimpleNamespace(spans=spans, spans_named=lambda n: [
        e for e in spans if e["name"] == n])
    assert phases.step_host_ms(ctx) == [4.0, 6.0]
    assert reader("engine.step_host_p50_ms")(ctx) == 5.0


def test_a_program_without_the_spans_gives_nothing(recorded):
    """The parent of PR 26: `serve.step` and `serve.prefill_chunk` spans
    with no id, no parent and no siblings; timelines with no `t0_us`."""
    ctx, _ = recorded
    old = [{**e, "args": {k: v for k, v in e["args"].items()
                          if k not in ("id", "parent", "step")}}
           for e in ctx.spans
           if e["name"] in ("serve.step", "serve.prefill_chunk")]
    rec = SimpleNamespace(rid="b0", origin=1.0, tokens=[1.5, 1.6])
    parent = SimpleNamespace(
        trace=ctx.trace, spans=old, t0=0.0, seconds=40.0, records=[rec],
        spans_named=lambda n: [e for e in old if e["name"] == n],
        timelines={"b0": {"events": [{"kind": "first_token", "t_ms": 1.0}]}})
    for name in IDLE + ["engine.step_host_p50_ms", "api.handoff_p95_ms"]:
        assert reader(name)(parent) is None
    assert reader("device.idle_share")(parent) is not None


def _handoff_ctx(shift_s: float):
    """30 requests of 3 tokens: the engine stamps a token, the client gets
    it 2 ms later (the last one of each 10 ms later)."""
    t0_us = 5_000_000
    records, timelines = [], {}
    for i in range(30):
        base = 100.0 * i                      # ms into the timeline
        events = [{"kind": "enqueue", "t_ms": base},
                  {"kind": "decode", "t_ms": base + 50.0, "step": 3 * i},
                  {"kind": "first_token", "t_ms": base + 50.01,
                   "step": 3 * i},
                  {"kind": "decode", "t_ms": base + 80.0, "step": 3 * i + 1},
                  {"kind": "finish", "t_ms": base + 81.0}]
        stamps = [base + 50.01, base + 50.0, base + 80.0]
        got = [(t0_us / 1e3 + t + late) / 1e3 + shift_s
               for t, late in zip(stamps, (2.0, 2.0, 10.0))]
        records.append(SimpleNamespace(rid=f"b{i}", origin=1.0 + i,
                                       tokens=got))
        timelines[f"b{i}"] = {"t0_us": t0_us, "events": events}
    return SimpleNamespace(t0=0.0, seconds=40.0, records=records,
                           timelines=timelines)


def test_handoff_pairs_each_token_with_its_stamp():
    ctx = _handoff_ctx(0.0)
    assert phases.token_stamps(ctx.timelines["b0"]) == pytest.approx(
        [5.05001, 5.05, 5.08])
    # a third of the differences are 10 ms, so the 95th percentile is
    assert reader("api.handoff_p95_ms")(ctx) == pytest.approx(10.0, abs=1e-6)
    # the iteration that fetched the first token emitted the second too:
    # paired with the NEXT decode event it would read 30 ms early
    spec = {"t0_us": 0, "events": [
        {"kind": "spec_verify", "t_ms": 1.0, "accepted": 2},
        {"kind": "first_token", "t_ms": 1.0}]}
    assert len(phases.token_stamps(spec)) == 1 + 3


def test_handoff_is_left_out_when_the_clocks_are_not_one():
    assert reader("api.handoff_p95_ms")(_handoff_ctx(-0.005)) is None
