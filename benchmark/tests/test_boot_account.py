"""Run by hand on the CPU, like test_harness.py:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_boot_account.py -q

The five readers PR 60 added (they move `setup_s`), on the spans of a
recorded CPU rehearsal (data/boot_report.json says how it was made) and on
what a program without those spans leaves them to read; the manifest with
their entries; and run.py end to end at tiny widths with the five listed
for a tiny cell.
"""
from __future__ import annotations

import copy as copy_mod
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import manifest  # noqa: E402
import rehearsal  # noqa: E402

NEW = ("process.trace_lower_s", "process.cache_load_s", "process.compile_s",
       "programs.build_s", "engine.build_s")


def reader(name):
    return manifest.metric_reader(BENCH, name)


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "boot_report.json")) as f:
        data = json.load(f)
    return SimpleNamespace(spans=data["spans"],
                           window_perf=data["window_perf"]), data


def test_the_readers_agree_with_the_programs_own_account(recorded):
    ctx, data = recorded
    boot, meter = data["boot"], data["compile"]
    got = {name: reader(name)(ctx) for name in NEW}
    assert all(v is not None and v >= 0 for v in got.values())
    assert got["process.trace_lower_s"] == pytest.approx(
        boot["trace_s"] + boot["lower_s"], abs=2e-3)
    assert got["process.cache_load_s"] == pytest.approx(
        boot["cache_load_s"], abs=1e-3)
    assert got["process.compile_s"] == pytest.approx(
        boot["compile_s"], abs=1e-3)
    # the launcher's own meter counted the same backend stages
    assert boot["builds"] == meter["compilations"]
    assert got["process.cache_load_s"] + got["process.compile_s"] == \
        pytest.approx(meter["compile_s"], abs=6e-3)
    phases = {p["name"]: p["dur_s"] for p in boot["phases"]}
    assert got["programs.build_s"] == pytest.approx(phases["boot.model"],
                                                    abs=1e-3)
    assert got["engine.build_s"] == pytest.approx(phases["boot.engine"],
                                                  abs=1e-3)
    # all of it lies before the window
    assert sum(got[n] for n in NEW[:3]) < ctx.window_perf[0]


def test_a_span_that_ends_inside_the_window_is_not_the_start_ups(recorded):
    ctx, _ = recorded
    before = {name: reader(name)(ctx) for name in NEW}
    start_us = ctx.window_perf[0] * 1e6
    late = copy_mod.deepcopy([e for e in ctx.spans
                              if e["name"] in ("process.compile",
                                               "boot.model", "boot.engine")])
    for e in late:
        e["ts"] = int(start_us) - e["dur"] + 1      # ends 1 us inside
    ctx.spans = ctx.spans + late
    assert {name: reader(name)(ctx) for name in NEW} == before


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reads_nothing(recorded, name):
    ctx, _ = recorded
    ctx.spans = [e for e in ctx.spans if e["cat"] not in ("boot", "process")]
    assert ctx.spans                        # the parent's: serve.* alone
    assert reader(name)(ctx) is None
    ctx.spans = []
    assert reader(name)(ctx) is None


def test_the_manifest_holds_with_the_five_entries():
    assert manifest.validate() == []
    m = manifest.load()
    cells = [w["name"] for w in m["workloads"]]
    by = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert by[name]["moves"] == "setup_s"
        assert by[name]["workloads"] == cells
        assert by[name]["source"] == "program_span"
        assert by[name]["layer"] == name.split(".")[0]


def test_a_traced_rehearsal_prints_the_five(tmp_path):
    copy = rehearsal.make_copy(str(tmp_path))
    cell = "tiny-qwen3.tiny_chat"
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    for e in m["per_layer"]:
        if e["moves"] == "setup_s":
            e["workloads"] = e["workloads"] + [cell]
    with open(path, "w") as f:
        json.dump(m, f, indent=1)
    p = rehearsal.run_cell(copy, cell, 2 ** 31 + 60, 5, 1)
    assert p.returncode == 0, p.stderr[-2000:] + p.stdout[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    got = {n: line["metrics"][n]["value"] for n in NEW}
    assert all(line["metrics"][n]["unit"] == "s" for n in NEW)
    said = next(ln for ln in p.stdout.splitlines()
                if ln.startswith("[setup] "))
    setup_s = float(said.split()[1])
    meter = json.loads(said.split("; compiles ")[1])
    assert sum(got[n] for n in NEW[:3]) <= setup_s
    # (the meter's total is printed at `ready`; nothing compiles after it)
    assert got["process.cache_load_s"] + got["process.compile_s"] == \
        pytest.approx(meter["compile_s"], abs=0.02)
    with open(os.path.join(copy, "benchmark", "out",
                           f"{cell}-seed{2 ** 31 + 60}-trace1",
                           "child_report.json")) as f:
        report = json.load(f)
    boot = report["engine"]["boot"]
    assert boot["builds"] == report["compile"]["compilations"]
    assert report["compiles_in_window"] == 0
    # a run without the trace holds the account all the same
    p0 = rehearsal.run_cell(copy, cell, 2 ** 31 + 61, 3, 0)
    assert p0.returncode == 0, p0.stderr[-2000:]
    with open(os.path.join(copy, "benchmark", "out",
                           f"{cell}-seed{2 ** 31 + 61}-trace0",
                           "child_report.json")) as f:
        plain = json.load(f)["engine"]["boot"]
    assert plain["builds"] > 0 and plain["handed"]["spans"] == 0
    assert {p["name"] for p in plain["phases"]} >= {
        "boot.model", "boot.rope", "boot.engine", "boot.engine.pool"}
