"""The `jamba2-3b.crowd` cell rehearsed on the CPU at tiny widths (run by
hand, as this suite is): the configuration's own keys and family file, the
`crowd` mix's own kind, the two per-layer readers this family brought; the
cell runs end to end and is `correct`; the int8 control AND the
dropped-state control (jamba_controls.py) read over the limit.
"""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import jamba_controls  # noqa: E402
import manifest  # noqa: E402
import rehearsal  # noqa: E402

CELL = "tiny-jamba.tiny_crowd"
# CPU readings at these widths, five seeds: served bf16 0.0121-0.0150, the
# int8 control 0.0402-0.0528, the dropped-state control 0.54-0.69
LIMIT = 0.025

# the crowd mix at a CPU's size: closed, as many callers as slots, prompts
# of one to three chunks
MIX = {
    "kind": "closed", "what": "test", "clients": 4, "ramp_seconds": 2,
    "stagger_seconds": 0.2, "shape_seed": 1, "shared_prefix_tokens": 0,
    "unique_tokens": {"dist": "uniform", "min": 51, "max": 90},
    "max_tokens": {"dist": "uniform", "min": 6, "max": 12},
    "sampling": {"temperature": 0.7, "top_p": 0.9},
    "check": {"prompt_tokens": [20, 90], "decode_steps": 3}}


def tiny_jamba() -> dict:
    """benchmark/configs/jamba2-3b.json with every width cut: two periods
    of four layers (Mamba, Mamba, attention on one K/V head, Mamba)."""
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        hf = json.load(f)
    hf.update(vocab_size=512, hidden_size=64, intermediate_size=128,
              num_hidden_layers=8, num_attention_heads=4,
              attn_layer_period=4, attn_layer_offset=2, mamba_dt_rank=8,
              max_position_embeddings=512)
    hf["benchmark"] = {
        **rehearsal._tiny("qwen3")["benchmark"], "family": "jamba",
        "correct": {"number": "as the real configuration", "limit": LIMIT,
                    "control": "int8"}}
    return hf


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with the tiny cell ADDED."""
    dst = rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))
    bench = os.path.join(dst, "benchmark")
    cfg, mix = CELL.split(".")
    with open(os.path.join(bench, "configs", cfg + ".json"), "w") as f:
        json.dump(tiny_jamba(), f, indent=1)
    with open(os.path.join(bench, "traffic", mix + ".json"), "w") as f:
        json.dump(MIX, f, indent=1)
    m = manifest.load(dst)
    m["configs"].append({"name": cfg, "source": "none: a test preset",
                         "file": f"benchmark/configs/{cfg}.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": CELL, "config": cfg, "traffic": mix,
                           "chips": 1, "why": "CPU rehearsal"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "jamba2-3b.crowd" in e.get("workloads", ()):
            e["workloads"].append(CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(m, f, indent=1)
    assert manifest.validate(dst) == []
    return dst


def test_the_cell_in_the_manifest_is_the_one_the_issue_names():
    m = manifest.load()
    cell = manifest.Cell("jamba2-3b.crowd")
    assert (cell.chips, cell.bench["family"], cell.bench["reduced"]) == \
        (1, "jamba", {})
    assert cell.mix["kind"] == "closed" and cell.mix["clients"] == \
        int(cell.bench["engine_env"]["CAKE_SERVE_SLOTS"])
    judged = {e["name"] for e in cell.end_to_end}
    assert {"itl_p50_ms", "itl_p95_ms", "out_tok_s", "setup_s"} <= judged
    assert not {"ttft_p50_ms", "ttft_p90_ms"} & judged
    mine = {e["name"] for e in cell.per_layer}
    assert {"programs.decode.ssm_ms", "programs.prefill.ssm_ms",
            "programs.decode.attn_ms", "engine.occupancy",
            "programs.prefill_chunk_ms.closed", "api.handoff_p95_ms"} <= mine
    assert "cake_flash_attention_roofline" not in mine
    for name in ("programs.decode.ssm_ms", "programs.prefill.ssm_ms"):
        entry = [e for e in m["per_layer"] if e["name"] == name][0]
        assert entry["workloads"] == ["jamba2-3b.crowd"]
        assert callable(manifest.metric_reader(BENCH, name))


def test_ssm_readers_find_nothing_where_the_program_has_no_such_scope():
    """On a parent commit (or a model without state-space layers) no op is
    traced under `cake.ssm`: the readers give None and do not raise."""
    ctx = types.SimpleNamespace(trace=types.SimpleNamespace(
        scope_ms=lambda program, scope: None))
    for name in ("programs.decode.ssm_ms", "programs.prefill.ssm_ms"):
        assert manifest.metric_reader(BENCH, name)(ctx) is None


def test_cell_runs_end_to_end_and_the_int8_control_fails(copy):
    p = rehearsal.run_cell(copy, CELL, 2 ** 31 + 36, 5, 1,
                           extra=("--control", "int8"))
    assert p.returncode == 0, p.stderr[-2000:] + p.stdout[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    compared = line["compared"]["logits_rel_rms_pooled"]
    assert compared["limit"] == LIMIT and compared["value"] <= LIMIT
    control = [json.loads(ln[len("[control] "):])
               for ln in p.stdout.splitlines() if ln.startswith("[control] ")]
    assert control and control[0]["pooled"] > LIMIT
    # spans and counters read on a CPU too; device-trace metrics (the ssm
    # readers among them) are left out of a rehearsal's line, not invented
    assert "engine.step_p50_ms" in line["metrics"]
    assert "programs.decode.ssm_ms" not in line["metrics"]
    report = [d for d in os.listdir(os.path.join(copy, "benchmark", "out"))
              if d.startswith(CELL)][0]
    with open(os.path.join(copy, "benchmark", "out", report,
                           "child_report.json")) as f:
        flight = json.load(f)["flight"]
    stepping = [r for r in flight if r["occupancy"]]
    per_row = 6 * (3 * 128 * 2 + 16 * 128 * 4)      # 6 Mamba layers, bf16
    assert stepping and all(
        r["state_bytes"] == r["occupancy"] * per_row for r in stepping)


def test_dropped_state_control_reads_over_the_limit():
    """The reference that loses the state at every chunk boundary, in the
    program's place: a check point a chunk behind the boundary still sees
    it, because A_log's spread leaves slow states."""
    cfg = tiny_jamba()
    cell = types.SimpleNamespace(
        bench=cfg["benchmark"], mix=MIX,
        hf={k: v for k, v in cfg.items() if k != "benchmark"})
    got = jamba_controls.readings(cell, [2 ** 31 + 36, 77], log=lambda s: 0)
    assert max(got["sound"]) <= LIMIT, got
    assert min(got["int8"]) > LIMIT, got
    assert min(got[jamba_controls.DROP]) > 3 * LIMIT, got
