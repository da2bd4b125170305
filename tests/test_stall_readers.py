"""A run that stood still says where (ISSUE 41), part four: the four
per-layer readers on hand-made contexts, and their manifest entries."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NEW = ("engine.stall_ms", "engine.between_steps_p50_ms",
       "api.handoff_inside_p95_ms", "api.handoff_inside_p95_ms.tail99")
INSIDE = NEW[2:]


@pytest.fixture(scope="module")
def manifest():
    sys.path.insert(0, BENCH)
    try:
        import manifest as m
        yield m
    finally:
        sys.path.remove(BENCH)


# launch_server.py's `trace_stop_ns` on the records' clock, and a tie to
# the profiler's that the reader has to undo
STOP_S, OFFSET_NS = 28.0, 7_000_000_000_123


def _ctx(flight=(), spans=(), window=(10.0, 50.0)):
    lo, hi = (t * 1e6 for t in window)
    spans = list(spans)
    return SimpleNamespace(
        flight=list(flight), spans=spans, window_perf=list(window),
        trace=SimpleNamespace(t1=int(STOP_S * 1e9) + OFFSET_NS,
                              offset_ns=OFFSET_NS),
        spans_named=lambda name: [
            e for e in spans if e["name"] == name
            and lo <= e["ts"] and e["ts"] + e["dur"] <= hi])


def _write(ts_s, wait_us, dur_us):
    return {"name": "api.sse_write", "cat": "api", "ph": "X",
            "ts": int(ts_s * 1e6), "dur": dur_us,
            "args": {"rid": "b1", "wait_us": wait_us}}


def test_stall_ms_sums_the_flagged_records_excess(manifest):
    read = manifest.metric_reader(BENCH, "engine.stall_ms")
    recs = [{"t": 11.0 + 0.03 * i, "wall_ms": 30.0, "gap_ms": 0.1,
             "stall_ms": 0.0} for i in range(50)]
    assert read(_ctx(recs)) == 0            # nothing stood still: 0, not None
    assert read(_ctx(recs)) is not None
    stalled = recs + [{"t": 20.0, "wall_ms": 2000.0, "gap_ms": 0.1,
                       "stall_ms": 1500.1},
                      {"t": 40.0, "wall_ms": 20.0, "gap_ms": 900.0,
                       "stall_ms": 420.0}]
    assert read(_ctx(stalled)) == pytest.approx(1920.1)
    # the parent's records: host_ms / fetch_ms and no wall_ms
    assert read(_ctx([{"t": 12.0, "host_ms": 2.0, "fetch_ms": 28.0}] * 9)) \
        is None
    assert read(_ctx([])) is None


@pytest.mark.parametrize("began, wall_ms, counted", [
    (STOP_S - 0.2, 1500.0, False),      # under way at the profiler's stop
    (STOP_S + 0.45, 4900.0, False),     # the stop took the GIL 0.45 s late
    (STOP_S + 1.9, 800.0, False),       # the edge of the shadow
    (STOP_S + 2.1, 800.0, True),        # past it: the program's own
    (STOP_S - 3.0, 2900.0, True),       # ended before the stop
])
def test_stall_ms_leaves_out_the_profilers_own_stop(manifest, began, wall_ms,
                                                    counted):
    read = manifest.metric_reader(BENCH, "engine.stall_ms")
    recs = [{"t": 12.0, "wall_ms": 30.0, "gap_ms": 0.1, "stall_ms": 0.0},
            {"t": began + wall_ms / 1e3 + 0.0004, "wall_ms": wall_ms,
             "gap_ms": 0.4, "stall_ms": wall_ms + 0.4 - 500.0}]
    assert read(_ctx(recs)) == (pytest.approx(wall_ms - 499.6) if counted
                                else 0)


def test_between_steps_is_the_median_gap_behind_a_busy_iteration(manifest):
    read = manifest.metric_reader(BENCH, "engine.between_steps_p50_ms")
    recs = [{"wall_ms": 30.0, "gap_ms": g}
            for g in (0.0, 0.08, 0.12, 0.10, 0.0, 40.0)]
    assert read(_ctx(recs)) == pytest.approx(0.11)      # of the four > 0
    assert read(_ctx([{"wall_ms": 1.0, "gap_ms": 0.0}])) is None
    assert read(_ctx([{"host_ms": 2.0, "fetch_ms": 28.0}] * 9)) is None


@pytest.mark.parametrize("name", INSIDE)
def test_handoff_inside_reads_wait_plus_write_in_the_window(manifest, name):
    read = manifest.metric_reader(BENCH, name)
    spans = [_write(11.0 + i, 1000 + 10 * i, 200) for i in range(30)]
    spans.append(_write(5.0, 900_000, 200))             # before the window
    spans.append({"name": "serve.step", "ts": int(12e6), "dur": 30_000,
                  "args": {"id": 1}})
    # 30 samples of 1.2 .. 1.49 ms: the 95th percentile, closest ranks
    assert read(_ctx(spans=spans)) == pytest.approx(1.4755)
    assert read(_ctx(spans=spans[:19])) is None         # under 20 tokens
    assert read(_ctx(spans=spans[-1:])) is None         # the parent's spans


def test_the_manifest_holds_with_the_new_entries(manifest):
    assert manifest.validate(ROOT) == []
    m = manifest.load(ROOT)
    cells = [w["name"] for w in m["workloads"]]
    # (PRs 44 and 48 appended two metrics each behind them, PRs 53 and 56
    # three each)
    assert [e["name"] for e in m["per_layer"][-14:-10]] == list(NEW)
    by = {e["name"]: e for e in m["per_layer"]}
    assert by[NEW[0]]["workloads"] == by[NEW[1]]["workloads"] == cells
    assert (by[NEW[0]]["moves"], by[NEW[1]]["moves"]) == ("out_tok_s",
                                                          "itl_p50_ms")
    assert by[NEW[0]]["source"] == by[NEW[1]]["source"] == "program_counter"
    # the inside hand-off and its twin cover between them every cell that
    # judges a tail of the gaps (PR 44's cell judges none), as the outside
    # hand-off and its twin do
    tails = {e["name"]: e.get("workloads", cells) for e in m["end_to_end"]}
    assert sorted(by[NEW[2]]["workloads"] + by[NEW[3]]["workloads"]) == \
        sorted(tails["itl_p95_ms"] + tails["itl_p99_ms"])
    assert set(cells) - set(tails["itl_p95_ms"] + tails["itl_p99_ms"]) == \
        {"laguna-s-2.1-l9-ep16.codeassist", "solar-open2-l8-ep32.agent",
         "brumby-14b-l8.continuation", "deepseek-v2-l5-ep8.longdoc"}
    for inside, outside in zip(INSIDE, ("api.handoff_p95_ms",
                                        "api.handoff_p95_ms.tail99")):
        assert by[inside]["workloads"] == by[outside]["workloads"]
        assert by[inside]["moves"] == by[outside]["moves"]
        assert by[inside]["layer"] == "api"
        assert by[inside]["source"] == "program_span"


def test_benchmark_json_only_gained_entries_at_the_end():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert len(m["workloads"]) == 9 and len(m["configs"]) == 8
    names = [e["name"] for e in m["per_layer"]]
    assert len(names) == len(set(names))
    assert names.index("engine.prefix_hit_share") == len(names) - 15
    assert names[-10:] == ["programs.decode.attn_full_ms",
                          "programs.decode.ffn_shared_ms",
                          "programs.decode.attn_linear_ms",
                          "programs.prefill.attn_linear_ms",
                          "programs.decode.attn_retention_ms",
                          "programs.prefill.attn_retention_ms",
                          "retention_state_roofline",
                          "programs.decode.attn_latent_ms",
                          "programs.prefill.attn_latent_ms",
                          "latent_read_roofline"]
