"""Tokens per second in an open loop are the schedule's, not the server's
(ISSUE 58). Run by hand on the CPU with the rest of benchmark/tests. No JAX.

Three things: the arithmetic on the COMMITTED `longprompt.json` (what the
schedule offers, and that a faster server reads FEWER tokens in the window),
the twin reader `engine.stall_ms.open`, and the manifest ISSUE 58 asks for,
built from today's in a temporary root and held to `manifest.validate`.
PR 58 could not commit that manifest (PERF.md §7, first item): this test is
its specification.
"""
from __future__ import annotations

import json
import math
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import manifest  # noqa: E402
import traffic  # noqa: E402

SECONDS = 40.0
OPEN = ["qwen3-4b.chat", "qwen3-4b.longprompt"]
TWIN = "engine.stall_ms.open"
LONGPROMPT = manifest.Cell("qwen3-4b.longprompt").mix


def _kinds(m):
    return {w["name"]: manifest.Cell(w["name"]).mix["kind"]
            for w in m["workloads"]}


# -- what `longprompt` offers, and what the window's edges carry ---------------

def fcfs_tokens(reqs, step_ms: float, gap_ms: float) -> tuple[int, int, int]:
    """A plain first-come-first-served server over a schedule: a prompt
    advances one 256-token chunk a step of `step_ms` (a chunk and the decode
    step it shares), prompts one behind the other; the first token comes
    with the last chunk and the others `gap_ms` apart. Returns what
    `run.py: end_to_end` counts (tokens of ANY request stamped inside the
    window), the part of it carried IN by requests due before the window,
    and what the window's own requests carry OUT past its end."""
    free, seen, carried_in, carried_out = -math.inf, 0, 0, 0
    for r in reqs:
        free = max(r.due_s, free) + math.ceil(r.prompt_tokens / 256) \
            * step_ms / 1e3
        inside = sum(0 <= free + i * gap_ms / 1e3 < SECONDS
                     for i in range(r.max_tokens))
        seen += inside
        if r.due_s < 0:
            carried_in += inside
        else:
            carried_out += r.max_tokens - inside
    return seen, carried_in, carried_out


@pytest.fixture(scope="module")
def longprompt():
    return traffic.generate(LONGPROMPT, 58, SECONDS)


def test_longprompt_offers_56_tokens_a_second(longprompt):
    due = [r for r in longprompt if 0 <= r.due_s < SECONDS]
    assert (len(longprompt), len(due)) == (50, 35)
    assert {r.max_tokens for r in longprompt} == {64}
    assert sum(r.max_tokens for r in due) / SECONDS == 56.0
    # the three arrivals whose tokens a slow server carries over the
    # window's start, and the last, which gives the window nothing
    ramp = [round(r.due_s, 2) for r in longprompt if r.due_s < 0]
    assert ramp[-3:] == [-1.57, -1.57, -0.89]
    assert round(longprompt[-1].due_s, 2) == 39.58
    # `--seed` draws the text alone: every seed offers this schedule
    other = traffic.generate(LONGPROMPT, 2 ** 31 + 58, SECONDS)
    assert [(r.due_s, r.prompt_tokens) for r in other] == \
        [(r.due_s, r.prompt_tokens) for r in longprompt]


# (a step that carries a chunk, a gap between tokens) in ms, slowest first,
# and the tokens/s the window then reads. The first row is the cell's first
# ledger line (59.9; PR 25's sweep read 60.0), the fifth PR 57's change to
# the digit (2,221 tokens = 55.525 on the chip).
SPEEDS = [((90, 30), 60.0), ((70, 25), 57.625), ((48, 20), 57.05),
          ((40, 16), 56.1), ((35, 15), 55.55), ((25, 14), 55.2),
          ((20, 13), 55.125)]


@pytest.mark.parametrize("speed, tok_s", SPEEDS)
def test_a_speed_reads_these_tokens_a_second(longprompt, speed, tok_s):
    seen, carried_in, carried_out = fcfs_tokens(longprompt, *speed)
    assert seen / SECONDS == pytest.approx(tok_s)
    assert seen == 35 * 64 + carried_in - carried_out


def test_a_faster_server_reads_fewer_tokens_in_the_window(longprompt):
    read = [fcfs_tokens(longprompt, *speed) for speed, _ in SPEEDS]
    seen = [s for s, _, _ in read]
    assert seen == sorted(seen, reverse=True) and len(set(seen)) == len(seen)
    # the carried-in tokens fall 270 -> 15, the carried-out 110 -> 50
    assert (read[0][1:], read[-1][1:]) == ((270, 110), (15, 50))
    # the whole travel is 8 % of the slowest reading, and the step PR 57
    # took (the chip: 57.55 -> 55.525) is more than the 3 % bound
    assert 1 - seen[-1] / seen[0] == pytest.approx(0.08125)
    bound = [e["bound"] for e in manifest.load()["end_to_end"]
             if e["name"] == "out_tok_s"][0]
    assert 1 - 55.525 / 57.55 > bound


# -- the twin reader -------------------------------------------------------------

def _ctx(flight, stop_s=28.0, offset_ns=7_000_000_000):
    return SimpleNamespace(
        flight=list(flight),
        trace=SimpleNamespace(t1=int(stop_s * 1e9) + offset_ns,
                              offset_ns=offset_ns))


def test_the_twin_reads_what_engine_stall_ms_reads():
    one = manifest.metric_reader(BENCH, "engine.stall_ms")
    twin = manifest.metric_reader(BENCH, TWIN)
    steady = [{"t": 11.0 + 0.03 * i, "wall_ms": 30.0, "gap_ms": 0.1,
               "stall_ms": 0.0} for i in range(50)]
    stalled = steady + [
        {"t": 20.0, "wall_ms": 2000.0, "gap_ms": 0.1, "stall_ms": 1500.1},
        {"t": 40.0, "wall_ms": 20.0, "gap_ms": 900.0, "stall_ms": 420.0},
        # under way at the profiler's stop: the harness's own pause
        {"t": 29.3, "wall_ms": 1500.0, "gap_ms": 0.4, "stall_ms": 1000.4}]
    for flight, value in ((steady, 0), (stalled, pytest.approx(1920.1)),
                          ([{"t": 12.0, "host_ms": 2.0}] * 9, None),
                          ([], None)):
        assert twin(_ctx(flight)) == one(_ctx(flight)) == value
    assert twin(_ctx(steady)) is not None       # no stall reads 0, not nothing


def test_the_twin_moves_the_ttft_in_the_open_loops_alone():
    m = manifest.load()
    assert manifest.validate() == []
    by = {e["name"]: e for e in m["per_layer"]}
    one, twin = by["engine.stall_ms"], by[TWIN]
    assert {k: twin[k] for k in ("unit", "better", "source", "layer")} == \
        {k: one[k] for k in ("unit", "better", "source", "layer")}
    assert twin["moves"] == "ttft_p50_ms"
    assert twin["workloads"] == OPEN == \
        [c for c, kind in _kinds(m).items() if kind == "open_poisson"]
    for cell in OPEN:
        assert TWIN in [e["name"] for e in manifest.Cell(cell).per_layer]
        assert "ttft_p50_ms" in [e["name"]
                                 for e in manifest.Cell(cell).end_to_end]


# -- the manifest ISSUE 58 asks for -------------------------------------------------

def _root_with(tmp_path, m) -> str:
    root = str(tmp_path)
    if not os.path.exists(os.path.join(root, "benchmark")):
        os.symlink(BENCH, os.path.join(root, "benchmark"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f, indent=1)
    return root


def test_the_manifest_issue_58_asks_for_validates(tmp_path):
    m = manifest.load()
    kinds = _kinds(m)
    closed = [c for c, kind in kinds.items() if kind == "closed"]
    assert len(closed) == 7 and sorted(closed + OPEN) == sorted(kinds)
    by = {e["name"]: e for e in m["end_to_end"] + m["per_layer"]}
    # 1. tokens per second are judged where the system sets them
    by["out_tok_s"]["workloads"] = closed
    faults = manifest.validate(_root_with(tmp_path, m))
    assert sorted(faults) == sorted(
        f"per_layer {name}: cell {cell} does not report out_tok_s"
        for name in ("engine.occupancy", "engine.stall_ms") for cell in OPEN)
    # 2. and 3. the two readers that move them leave the open loops, where
    # the twin already stands for the second
    by["engine.occupancy"]["workloads"] = closed
    by["engine.stall_ms"]["workloads"] = closed
    root = _root_with(tmp_path, m)
    assert manifest.validate(root) == []
    for cell, kind in kinds.items():
        judged = [e["name"] for e in manifest.Cell(cell, root).end_to_end]
        assert ("out_tok_s" in judged) == (kind == "closed"), cell
        assert len(judged) >= 2 and "setup_s" in judged
    for e in m["per_layer"]:
        if e["moves"] == "out_tok_s":
            assert set(e["workloads"]) <= set(closed), e["name"]
    assert sorted(by["engine.stall_ms"]["workloads"]
                  + by[TWIN]["workloads"]) == sorted(kinds)
    # nothing else moved: no bound, no cell, no other list
    today = manifest.load()
    assert m["workloads"] == today["workloads"]
    pairs = list(zip(m["end_to_end"] + m["per_layer"],
                     today["end_to_end"] + today["per_layer"]))
    assert [a["name"] for a, b in pairs if a != b] == \
        ["out_tok_s", "engine.occupancy", "engine.stall_ms"]
    assert all({**a, "workloads": None} == {**b, "workloads": None}
               for a, b in pairs)
