"""The `longcat-flash-chat-l4-ep32.toolcall` cell rehearsed on the CPU at
tiny widths (run by hand, as this suite is): the configuration's own keys
and family file against the catalog's row, the `toolcall` mix's own kind
with a shared prefix of several blocks restored into the rows of four latent
sub-layers, the two per-layer readers this family brought; the cell runs end
to end and is `correct` with every prefix hit counted and every held expert
AND the identity path reached; the int8 control and the eight mechanism
controls (longcat_flash_controls.py) read over the limit.
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

import longcat_flash_controls  # noqa: E402
import manifest  # noqa: E402
import rehearsal  # noqa: E402

REAL = "longcat-flash-chat-l4-ep32.toolcall"
CELL = "tiny-longcat-flash.tiny_toolcall"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]
NEW_READERS = ("programs.decode.ffn_zero_ms", "programs.decode.ffn_dense_ms")
# CPU readings at these widths, seeds 5 and 77 (TINY_INIT): served bf16
# ~0.005-0.01, every control 0.03 or more (tests/test_longcat_flash.py)
LIMIT = 0.02

# (constant of reference/longcat_flash.py, as the file has it, at hidden
# 64): the numbers the published widths lead to (q, k_pe, k_nope, v and an
# FFN's gate and up ~1.6, an embedding of 2.35)
TINY_INIT = (("Q_B_STD", "STD", "0.2"), ("KV_A_STD", "STD", "0.2"),
             ("KV_B_STD", "STD", "0.2"), ("O_PROJ_STD", "STD / 8", "0.025"),
             ("FFN_IN_STD", "STD", "0.2"),
             ("DENSE_DOWN_STD", "STD / 6", "0.022"),
             ("EXPERT_DOWN_STD", "1.5 * STD", "0.04"),
             ("EMBED_SCALE", "1.5", "14.7"),
             ("SELECT_BIAS_STD", "0.001", "0.03"))

# the toolcall mix at a CPU's size: closed, as many callers as slots, a
# shared prefix of three blocks, a short turn, a short answer
MIX = {
    "kind": "closed", "what": "test", "clients": 4, "ramp_seconds": 2,
    "stagger_seconds": 0.2, "shape_seed": 1, "shared_prefix_tokens": 96,
    "unique_tokens": {"dist": "uniform", "min": 52, "max": 100},
    "max_tokens": {"dist": "uniform", "min": 6, "max": 12},
    "sampling": {"temperature": 0.7, "top_p": 0.9},
    "check": {"prompt_tokens": [20, 90], "decode_steps": 3}}


def tiny_longcat_flash() -> dict:
    """benchmark/configs/longcat-flash-chat-l4-ep32.json with every width
    cut: two layers = four latent sub-layers (4 heads of 16 + 8 with values
    of 16 through ranks 24 and 32), dense FFNs of 128, the second share of 4
    of 8 experts of 32 under a router of 8 + 4 identity outputs, top 3."""
    with open(os.path.join(BENCH, "configs",
                           "longcat-flash-chat-l4-ep32.json")) as f:
        hf = json.load(f)
    hf.update(vocab_size=512, hidden_size=64, ffn_hidden_size=128,
              expert_ffn_hidden_size=32, num_layers=2,
              num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              n_routed_experts=4, zero_expert_num=4, moe_topk=3,
              expert_parallel={"size": 2, "rank": 1},
              max_position_embeddings=512)
    hf["benchmark"] = {
        **rehearsal._tiny("qwen3")["benchmark"], "family": "longcat_flash",
        "correct": {"number": "as the real configuration", "limit": LIMIT,
                    "control": "int8"}}
    return hf


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with the tiny cell ADDED and the reference's
    initialisers set for its widths."""
    dst = rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))
    bench = os.path.join(dst, "benchmark")
    cfg, mix = CELL.split(".")
    ref_path = os.path.join(bench, "reference", "longcat_flash.py")
    with open(ref_path) as f:
        text = f.read()
    for name, scaled, plain in TINY_INIT:
        assert text.count(f"{name} = {scaled}\n") == 1, name
        text = text.replace(f"{name} = {scaled}\n", f"{name} = {plain}\n")
    with open(ref_path, "w") as f:
        f.write(text)
    with open(os.path.join(bench, "configs", cfg + ".json"), "w") as f:
        json.dump(tiny_longcat_flash(), f, indent=1)
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
    assert (len(m["configs"]), len(m["workloads"])) == (10, 11)
    cell = manifest.Cell(REAL)
    entry = cell.config_entry
    assert (cell.chips, cell.bench["family"]) == (1, "longcat_flash")
    assert entry["reduced"] == REDUCED
    assert set(cell.bench["reduced"]) == set(REDUCED)
    hf = cell.hf
    assert (hf["num_layers"], hf["n_routed_experts"], hf["zero_expert_num"],
            hf["vocab_size"], hf["expert_parallel"]) == (
        4, 16, 256, 16384, {"size": 32, "rank": 0})
    env, mix = cell.bench["engine_env"], cell.mix
    assert mix["kind"] == "closed" and mix["clients"] == \
        int(env["CAKE_SERVE_SLOTS"]) == 32
    assert (mix["ramp_seconds"], mix["stagger_seconds"],
            mix["shape_seed"]) == (12, 0.35, 1)
    assert mix["shared_prefix_tokens"] == 4096
    assert mix["unique_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 1024}
    assert mix["max_tokens"] == {"dist": "uniform", "min": 256, "max": 768}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert mix["check"] == {"prompt_tokens": [200, 650, 2400],
                            "decode_steps": 4}
    assert mix["shared_prefix_tokens"] + mix["unique_tokens"]["max"] \
        + mix["max_tokens"]["max"] <= int(env["CAKE_SERVE_CTX"]) == \
        cell.bench["max_cache_len"] == 6144
    # the shared prefix is whole blocks of the prefix cache, which holds it
    # (8 sub-layers of 640 lanes)
    assert mix["shared_prefix_tokens"] % int(env["CAKE_PREFILL_CHUNK"]) == 0
    assert mix["shared_prefix_tokens"] * 8 * 640 * 2 \
        < int(env["CAKE_PREFIX_CACHE_MB"]) * 2 ** 20
    # judged on the three metrics that list no cells, on no other
    assert {e["name"] for e in cell.end_to_end} == {
        "itl_p50_ms", "out_tok_s", "setup_s"}
    mine = {e["name"] for e in cell.per_layer}
    assert set(NEW_READERS) <= mine
    assert {"programs.decode.ffn_experts_ms", "programs.decode.ffn_route_ms",
            "programs.decode.attn_latent_ms",
            "programs.prefill.attn_latent_ms", "engine.prefix_hit_share",
            "programs.decode.attn_ms", "programs.decode.ffn_ms",
            "programs.decode_ms", "engine.occupancy",
            "device.idle_share"} <= mine
    assert not {"latent_read_roofline", "cake_flash_attention_roofline",
                "programs.decode.ffn_shared_ms", "programs.decode.ssm_ms",
                "engine.prefix_snapshot_share"} & mine
    for name in NEW_READERS:
        entry = [e for e in m["per_layer"] if e["name"] == name][0]
        assert entry["workloads"] == [REAL]
        assert entry["moves"] == "itl_p50_ms"
        assert callable(manifest.metric_reader(BENCH, name))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "LongCat-Flash-Chat"][0]
    cell = manifest.Cell(REAL)
    assert cell.config_entry["source"] == row["source_url"] == \
        cell.bench["source"]
    for key, published in row["config"].items():
        if key in REDUCED:
            assert cell.bench["reduced"][key]["published"] == published
            assert cell.bench["reduced"][key]["here"] == cell.hf[key]
            continue
        assert cell.hf[key] == published, key
    assert set(cell.hf) - set(row["config"]) == {"expert_parallel",
                                                 "model_type"}


def test_new_readers_find_nothing_where_the_program_gives_nothing():
    """On a parent commit no op is traced under the new scopes: None, never
    a raise."""
    seen = []
    trace = types.SimpleNamespace(
        scope_ms=lambda program, scope: seen.append((program, scope)),
        perf_to_prof=lambda ns: ns, events=lambda *a: [],
        executions=lambda program: [])
    ctx = types.SimpleNamespace(
        trace=trace, spans=[], peaks={}, cell=None,
        kernel=lambda name: manifest.kernel_counts(BENCH, name))
    for name in NEW_READERS:
        assert manifest.metric_reader(BENCH, name)(ctx) is None
    assert seen == [("_decode_slots", "ffn.zero"),
                    ("_decode_slots", "ffn.dense")]


def test_cell_runs_end_to_end_and_the_int8_control_is_read(copy):
    p = rehearsal.run_cell(copy, CELL, 5, 5, 1,
                           extra=("--control", "int8"))
    assert p.returncode == 0, p.stderr[-2000:] + p.stdout[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    compared = line["compared"]
    assert compared["logits_rel_rms_pooled"]["limit"] == LIMIT
    assert compared["logits_rel_rms_pooled"]["value"] <= LIMIT
    # 4 held experts and the identity path, in every sparse layer
    assert compared["experts_reached_min"] == {"value": -5, "limit": -5}
    control = [json.loads(ln[len("[control] "):])
               for ln in p.stdout.splitlines() if ln.startswith("[control] ")]
    assert control and control[0]["pooled"] > \
        compared["logits_rel_rms_pooled"]["value"]
    assert "engine.step_p50_ms" in line["metrics"]
    assert 35 < line["metrics"]["engine.prefix_hit_share"]["value"] < 95
    assert not set(NEW_READERS) & set(line["metrics"])
    report = [d for d in os.listdir(os.path.join(copy, "benchmark", "out"))
              if d.startswith(CELL)][0]
    with open(os.path.join(copy, "benchmark", "out", report,
                           "child_report.json")) as f:
        rep = json.load(f)
    kinds = rep["engine"]["attention_kinds"]
    assert [(k["kind"], k["layers"], k["heads"], k["row_width"],
             k["row_lanes"], k["row_bytes"]) for k in kinds] == [
        ("latent", 4, 4, 40, 128, 320)]
    assert rep["engine"]["sparse_layers"]["router_width"] == 12
    assert rep["engine"]["sparse_layers"]["shortcut_pairs"] == [[0, 1],
                                                                [2, 3]]


def test_every_mechanism_control_reads_over_the_limit(monkeypatch):
    """The reference in int8 beside the sound reading, and with each of the
    family's eight mechanisms got wrong, each in the program's place."""
    cfg = tiny_longcat_flash()
    cell = types.SimpleNamespace(
        bench=cfg["benchmark"], mix=MIX,
        hf={k: v for k, v in cfg.items() if k != "benchmark"})
    import reference.longcat_flash as ref
    for name, _, plain in TINY_INIT:                    # as in `copy`
        monkeypatch.setattr(ref, name, eval(plain, vars(ref)))
    got = longcat_flash_controls.readings(cell, [5, 77], 2, log=lambda s: 0)
    assert max(got["sound"]) <= LIMIT, got
    for q in longcat_flash_controls.WITHOUT:
        assert min(got[q]) > LIMIT, (q, got)
    assert min(got["int8"]) > 1.2 * min(got["sound"]), got
