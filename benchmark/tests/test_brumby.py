"""The `brumby-14b-l8.continuation` cell rehearsed on the CPU at tiny widths
(run by hand, as this suite is): the configuration's own keys and family
file against the catalog's row, the `continuation` mix's own kind (closed,
as many callers as slots, no shared prefix, the prefix cache off), the
three per-layer readers and the kernel's counts this family brought; the
cell runs end to end and is `correct`; the int8 control AND the three
mechanism controls (brumby_controls.py) read over the limit.
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

import brumby_controls  # noqa: E402
import manifest  # noqa: E402
import rehearsal  # noqa: E402

REAL = "brumby-14b-l8.continuation"
CELL = "tiny-brumby.tiny_continuation"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers"]
NEW_READERS = ("programs.decode.attn_retention_ms",
               "programs.prefill.attn_retention_ms",
               "retention_state_roofline")
# CPU readings at these widths, seeds 5 and 77 (every leaf at STD but the
# gate's: TINY_INIT): served bf16 0.0066 and 0.0105 (three layers of hidden
# 64), the int8 control 0.0172 and 0.0734, the gate off 0.79-0.87, the power
# 1 1.17-1.19: the limit is the geometric middle of 0.0105 and 0.0172 (at
# hidden 64 int8 is no decade below bfloat16, as it is at 5,120)
LIMIT = 0.0134

# (constant of reference/brumby.py, as the file has it, at hidden 64)
TINY_INIT = (("RESIDUAL_STD", "STD / (2 * 40) ** 0.5", "STD"),
             ("EMBED_SCALE", "0.75", "0.125"))

# the continuation mix at a CPU's size: closed, as many callers as slots,
# prompts of two to four chunks, no shared prefix
MIX = {
    "kind": "closed", "what": "test", "clients": 4, "ramp_seconds": 2,
    "stagger_seconds": 0.2, "shape_seed": 1, "shared_prefix_tokens": 0,
    "unique_tokens": {"dist": "uniform", "min": 40, "max": 120},
    "max_tokens": {"dist": "uniform", "min": 6, "max": 12},
    "sampling": {"temperature": 0.7, "top_p": 0.9},
    "check": {"prompt_tokens": [20, 90, 200], "decode_steps": 3}}


def tiny_brumby() -> dict:
    """benchmark/configs/brumby-14b-l8.json with every width cut: 4 query
    heads on 2 key/value heads of width 8 (a state of 8 x 36 a head), three
    layers, chunks of 32, the prefix cache off as in the cell."""
    with open(os.path.join(BENCH, "configs", "brumby-14b-l8.json")) as f:
        hf = json.load(f)
    hf.update(vocab_size=512, hidden_size=64, intermediate_size=128,
              num_attention_heads=4, num_key_value_heads=2, head_dim=8,
              num_hidden_layers=3, max_position_embeddings=512)
    tiny = rehearsal._tiny("qwen3")["benchmark"]
    hf["benchmark"] = {
        **tiny, "family": "brumby",
        "engine_env": {**tiny["engine_env"], "CAKE_PREFIX_CACHE_MB": "0"},
        "correct": {"number": "as the real configuration", "limit": LIMIT,
                    "control": "int8"}}
    return hf


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with the tiny cell ADDED."""
    dst = rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))
    bench = os.path.join(dst, "benchmark")
    cfg, mix = CELL.split(".")
    # the copy's family file draws the residual projections and the
    # embedding at STD like the rest: scaled for a stream of hidden 5,120
    # (STD / sqrt(80) under an embedding of 1.07) the layers add a
    # hundredth of the embedding at hidden 64 and no control shows
    ref_path = os.path.join(bench, "reference", "brumby.py")
    with open(ref_path) as f:
        text = f.read()
    for name, scaled, plain in TINY_INIT:
        assert text.count(f"{name} = {scaled}\n") == 1, name
        text = text.replace(f"{name} = {scaled}\n", f"{name} = {plain}\n")
    with open(ref_path, "w") as f:
        f.write(text)
    with open(os.path.join(bench, "configs", cfg + ".json"), "w") as f:
        json.dump(tiny_brumby(), f, indent=1)
    with open(os.path.join(bench, "traffic", mix + ".json"), "w") as f:
        json.dump(MIX, f, indent=1)
    m = manifest.load(dst)
    m["configs"].append({"name": cfg, "source": "none: a test preset",
                         "file": f"benchmark/configs/{cfg}.json",
                         "reduced": [], "why": "CPU rehearsal"})
    m["workloads"].append({"name": CELL, "config": cfg, "traffic": mix,
                           "chips": 1, "why": "CPU rehearsal"})
    for e in m["end_to_end"] + m["per_layer"]:
        if REAL in e.get("workloads", ()):
            e["workloads"].append(CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(m, f, indent=1)
    assert manifest.validate(dst) == []
    return dst


def test_the_cell_in_the_manifest_is_the_one_the_issue_names():
    m = manifest.load()
    assert manifest.validate() == []
    cell = manifest.Cell(REAL)
    entry = cell.config_entry
    assert (cell.chips, cell.bench["family"]) == (1, "brumby")
    assert entry["reduced"] == REDUCED == list(cell.bench["reduced"])
    for line in (entry["why"], entry["source"], cell.workload["why"]):
        assert len(line) <= 200 and "\n" not in line
    assert cell.hf["num_hidden_layers"] == 8
    env = cell.bench["engine_env"]
    assert env == {"CAKE_SERVE_SLOTS": "16", "CAKE_SERVE_CTX": "4096",
                   "CAKE_PREFILL_CHUNK": "256", "CAKE_PREFIX_CACHE_MB": "0",
                   "CAKE_MAX_QUEUE": "512"}
    # the traffic, letter for letter
    assert cell.workload["traffic"] == "continuation"
    mix = dict(cell.mix)
    mix.pop("what")
    assert mix == {
        "kind": "closed", "clients": 16, "ramp_seconds": 12,
        "stagger_seconds": 0.5, "shape_seed": 1, "shared_prefix_tokens": 0,
        "unique_tokens": {"dist": "uniform", "min": 256, "max": 2048},
        "max_tokens": {"dist": "uniform", "min": 384, "max": 1024},
        "sampling": {"temperature": 0.7, "top_p": 0.9},
        "check": {"prompt_tokens": [300, 1100], "decode_steps": 4}}
    assert mix["unique_tokens"]["max"] + mix["max_tokens"]["max"] \
        <= int(env["CAKE_SERVE_CTX"])
    # judged by the three metrics without a list; it joins no tail's
    assert {e["name"] for e in cell.end_to_end} == {
        "itl_p50_ms", "out_tok_s", "setup_s"}
    mine = {e["name"] for e in cell.per_layer}
    assert {*NEW_READERS, "programs.decode_ms", "programs.decode.attn_ms",
            "programs.decode.ffn_ms", "programs.decode.sample_ms",
            "programs.decode.lm_head_ms", "programs.decode.embed_ms",
            "programs.decode.unscoped_ms", "engine.step_p50_ms",
            "engine.step_host_p50_ms", "engine.occupancy",
            "engine.stall_ms", "engine.between_steps_p50_ms",
            "device.idle_share"} <= mine
    assert not {"cake_flash_attention_roofline", "engine.prefix_hit_share",
                "programs.decode.attn_linear_ms", "programs.decode.ssm_ms",
                "programs.decode.attn_full_ms"} & mine
    for name in NEW_READERS:
        entry = [e for e in m["per_layer"] if e["name"] == name][0]
        assert entry["workloads"][0] == REAL
        assert callable(manifest.metric_reader(BENCH, name))
    roof = [e for e in m["per_layer"]
            if e["name"] == "retention_state_roofline"][0]
    assert (roof["unit"], roof["layer"], roof["moves"]) == (
        "%", "kernel", "itl_p50_ms")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "Brumby-14B-Base"][0]
    cell = manifest.Cell(REAL)
    assert cell.config_entry["source"] == row["source_url"] == \
        cell.bench["source"]
    for key, published in row["config"].items():
        if key in REDUCED:
            assert cell.bench["reduced"][key]["published"] == published
            continue
        assert cell.hf[key] == published, key
    assert set(cell.hf) == set(row["config"])


def test_the_kernels_counts_are_the_issues_arithmetic():
    cell = manifest.Cell(REAL)
    flops, nbytes = manifest.kernel_counts(BENCH, "retention_state").counts(
        cell.hf, 16)
    row = 8 * 8 * (8256 * 128 + 8256) * 4
    assert row == 272_646_144 and nbytes == 16 * row * 2
    assert flops == 16 * 8 * 2 * 8256 * 128 * (8 + 40)
    # bound by bytes: 10.65 ms of memory beside 0.66 ms of arithmetic
    peaks = cell.peaks("TPU v5 lite")
    assert nbytes / peaks["hbm_bytes_per_s"] > \
        10 * flops / peaks["bf16_flops_per_s"]


def test_new_readers_find_nothing_where_the_program_gives_nothing():
    """On a parent commit no op is traced under the new scope: None, never
    a raise."""
    seen = []
    trace = types.SimpleNamespace(
        scope_ms=lambda program, scope: seen.append((program, scope)),
        perf_to_prof=lambda ns: ns,
        events=lambda key, name: [("jit__decode_slots", 10, 5)],
        executions=lambda program: [[("jit(_decode_slots)/cake.attn/x", 3)]])
    ctx = types.SimpleNamespace(
        trace=trace, spans=[], cell=manifest.Cell(REAL),
        kernel=lambda name: manifest.kernel_counts(BENCH, name),
        peaks=manifest.Cell(REAL).peaks("TPU v5 lite"))
    for name in NEW_READERS:
        assert manifest.metric_reader(BENCH, name)(ctx) is None
    assert seen == [("_decode_slots", "attn.retention"),
                    ("_prefill_slot", "attn.retention")]


def test_the_roofline_reader_divides_need_by_traced_time():
    """Two executions, 16 and 8 live rows, 20 ms and 15 ms under the scan
    scope (an op of the projections beside them is not counted)."""
    cell = manifest.Cell(REAL)
    scan = "jit(_decode_slots)/vmap(cake.attn)/cake.attn.retention/" \
           "cake.attn.retention.scan/mul"
    proj = "jit(_decode_slots)/vmap(cake.attn)/cake.attn.retention/" \
           "cake.attn.retention.proj/dot_general"
    trace = types.SimpleNamespace(
        perf_to_prof=lambda ns: ns,
        events=lambda key, name: [("m", 2_000_000, 25_000_000),
                                  ("m", 40_000_000, 25_000_000)],
        executions=lambda program: [
            [(scan, 12_000_000), (proj, 5_000_000), (scan, 8_000_000)],
            [(scan, 15_000_000)]])
    spans = [{"name": "serve.decode_dispatch", "ts": 1_000,
              "args": {"slots": 16}},
             {"name": "serve.decode_dispatch", "ts": 30_000,
              "args": {"slots": 8}},
             {"name": "serve.fetch", "ts": 31_000, "args": {}}]
    peaks = cell.peaks("TPU v5 lite")
    ctx = types.SimpleNamespace(
        trace=trace, spans=spans, cell=cell, peaks=peaks,
        kernel=lambda name: manifest.kernel_counts(BENCH, name))
    got = manifest.metric_reader(BENCH, "retention_state_roofline")(ctx)
    need = (16 + 8) * 272_646_144 * 2 / peaks["hbm_bytes_per_s"]
    assert got == pytest.approx(100 * need / 0.035)
    assert 0 < got < 100


def test_cell_runs_end_to_end_and_the_int8_control_fails(copy):
    p = rehearsal.run_cell(copy, CELL, 5, 5, 1,
                           extra=("--control", "int8"))
    assert p.returncode == 0, p.stderr[-2000:] + p.stdout[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    compared = line["compared"]
    assert compared["logits_rel_rms_pooled"]["limit"] == LIMIT
    assert compared["logits_rel_rms_pooled"]["value"] <= LIMIT
    control = [json.loads(ln[len("[control] "):])
               for ln in p.stdout.splitlines() if ln.startswith("[control] ")]
    assert control and control[0]["pooled"] > LIMIT
    # counters read on a CPU too; device-trace metrics are left out of a
    # rehearsal's line, not invented
    assert "engine.step_p50_ms" in line["metrics"]
    assert not set(NEW_READERS) & set(line["metrics"])
    report = [d for d in os.listdir(os.path.join(copy, "benchmark", "out"))
              if d.startswith(CELL)][0]
    with open(os.path.join(copy, "benchmark", "out", report,
                           "child_report.json")) as f:
        rep = json.load(f)
    # a full step reads and writes every row's state: 3 layers x 2 heads x
    # (128 x 8 + 128) float32 (36 products in one lane tile), and no row
    # holds a key or a value
    row = 3 * 2 * (128 * 8 + 128) * 4
    stepping = [r for r in rep["flight"] if r["occupancy"]]
    assert stepping and all(r["state_bytes"] == row * r["occupancy"]
                            for r in stepping)
    assert rep["engine"]["attention_kinds"] == [
        {"kind": "retention", "layers": 3, "power": 2, "heads": 4,
         "kv_heads": 2, "key_dim": 8, "state_width": 36,
         "padded_width": 128, "rotary_dim": 8, "rope_theta": 1000000.0,
         "state_bytes": row}]
    assert "prefix_cache" not in rep["engine"]


def test_every_mechanism_control_reads_over_the_limit(monkeypatch):
    """The reference in int8, without the gate, at power 1, with the keys
    before the last block boundary masked out, each in the program's
    place."""
    cfg = tiny_brumby()
    cell = types.SimpleNamespace(
        bench=cfg["benchmark"], mix=MIX,
        hf={k: v for k, v in cfg.items() if k != "benchmark"})
    import reference.brumby as ref
    for name, _, plain in TINY_INIT:                    # as in `copy`
        monkeypatch.setattr(ref, name, eval(plain, vars(ref)))
    got = brumby_controls.readings(cell, [5, 77], 2, log=lambda s: 0)
    assert max(got["sound"]) <= LIMIT, got
    for q in brumby_controls.MUST_FAIL:
        assert min(got[q]) > LIMIT, (q, got)
