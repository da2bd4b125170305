"""The `deepseek-v2-l5-ep8.longdoc` cell rehearsed on the CPU at tiny widths
(run by hand, as this suite is): the configuration's own keys and family
file against the catalog's row, the `longdoc` mix's own kind with a shared
prefix of several blocks restored into rows of latents, the three per-layer
readers this family brought; the cell runs end to end and is `correct` with
every prefix hit counted; the int8 control AND the five mechanism controls
(deepseek_v2_controls.py) read over the limit.
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

import deepseek_v2_controls  # noqa: E402
import manifest  # noqa: E402
import rehearsal  # noqa: E402

REAL = "deepseek-v2-l5-ep8.longdoc"
CELL = "tiny-deepseek-v2.tiny_longdoc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_READERS = ("programs.decode.attn_latent_ms",
               "programs.prefill.attn_latent_ms", "latent_read_roofline")
# CPU readings at these widths, seeds 5 and 77 (TINY_INIT): served bf16
# 0.012-0.032 (three layers of hidden 64; a group near-tie that bf16 flips
# moves a whole group's experts), the int8 control 0.028-0.051, the
# mechanism controls 0.08-0.55 (plain top-k the smallest)
LIMIT = 0.05

# (constant of reference/deepseek_v2.py, as the file has it, at hidden 64):
# the numbers the published widths lead to (q 0.78, k_pe and an FFN's gate
# and up 1.43, k_nope and v 1.8, an embedding of 1.07)
TINY_INIT = (("Q_B_STD", "STD", "0.16"), ("KV_A_STD", "STD", "0.18"),
             ("KV_B_STD", "4 * STD", "0.32"),
             ("O_PROJ_STD", "STD / 9", "0.04"),
             ("FFN_IN_STD", "STD", "0.18"),
             ("RESIDUAL_STD", "4 * STD / (2 * 60) ** 0.5", "STD"),
             ("EMBED_SCALE", "1.5", "6.7"),
             ("EXPERT_DOWN_STD", "STD / 5", "STD / 2"))

# the longdoc mix at a CPU's size: closed, as many callers as slots, one
# document of three blocks all ask of, a short question, a short answer
MIX = {
    "kind": "closed", "what": "test", "clients": 4, "ramp_seconds": 2,
    "stagger_seconds": 0.2, "shape_seed": 1, "shared_prefix_tokens": 96,
    "unique_tokens": {"dist": "uniform", "min": 52, "max": 100},
    "max_tokens": {"dist": "uniform", "min": 6, "max": 12},
    "sampling": {"temperature": 0.7, "top_p": 0.9},
    "check": {"prompt_tokens": [20, 90], "decode_steps": 3}}


def tiny_deepseek_v2() -> dict:
    """benchmark/configs/deepseek-v2-l5-ep8.json with every width cut: 4
    heads of 16 + 8 with values of 16 through ranks 24 and 32 (a row of 40
    numbers in 128 lanes), layer 0 dense, the second share of 4 of 8
    experts (2 of the router's 4 groups, of which a token keeps 2)."""
    with open(os.path.join(BENCH, "configs",
                           "deepseek-v2-l5-ep8.json")) as f:
        hf = json.load(f)
    hf.update(vocab_size=512, hidden_size=64, intermediate_size=128,
              moe_intermediate_size=32, num_hidden_layers=3,
              num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
              kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, n_routed_experts=4, num_experts_per_tok=3,
              n_group=4, topk_group=2,
              expert_parallel={"size": 2, "rank": 1},
              max_position_embeddings=512)
    hf["rope_scaling"] = {**hf["rope_scaling"],
                          "original_max_position_embeddings": 64}
    hf["benchmark"] = {
        **rehearsal._tiny("qwen3")["benchmark"], "family": "deepseek_v2",
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
    ref_path = os.path.join(bench, "reference", "deepseek_v2.py")
    with open(ref_path) as f:
        text = f.read()
    for name, scaled, plain in TINY_INIT:
        assert text.count(f"{name} = {scaled}\n") == 1, name
        text = text.replace(f"{name} = {scaled}\n", f"{name} = {plain}\n")
    with open(ref_path, "w") as f:
        f.write(text)
    with open(os.path.join(bench, "configs", cfg + ".json"), "w") as f:
        json.dump(tiny_deepseek_v2(), f, indent=1)
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
    assert (len(m["configs"]), len(m["workloads"])) == (8, 9)
    cell = manifest.Cell(REAL)
    entry = cell.config_entry
    assert (cell.chips, cell.bench["family"]) == (1, "deepseek_v2")
    assert entry["reduced"] == REDUCED
    assert set(cell.bench["reduced"]) == set(REDUCED)
    hf = cell.hf
    assert (hf["num_hidden_layers"], hf["n_routed_experts"],
            hf["vocab_size"], hf["expert_parallel"]) == (
        5, 20, 12800, {"size": 8, "rank": 0})
    env, mix = cell.bench["engine_env"], cell.mix
    assert mix["kind"] == "closed" and mix["clients"] == \
        int(env["CAKE_SERVE_SLOTS"]) == 32
    assert (mix["ramp_seconds"], mix["stagger_seconds"],
            mix["shape_seed"]) == (12, 0.35, 1)
    assert mix["shared_prefix_tokens"] == 20480
    assert mix["unique_tokens"] == {"dist": "uniform", "min": 64,
                                    "max": 512}
    assert mix["max_tokens"] == {"dist": "uniform", "min": 256, "max": 768}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert mix["check"] == {"prompt_tokens": [200, 650, 2400],
                            "decode_steps": 4}
    assert mix["shared_prefix_tokens"] + mix["unique_tokens"]["max"] \
        + mix["max_tokens"]["max"] <= int(env["CAKE_SERVE_CTX"]) == \
        cell.bench["max_cache_len"] == 24576
    # the document is whole blocks of the prefix cache, which holds it
    assert mix["shared_prefix_tokens"] % int(env["CAKE_PREFILL_CHUNK"]) == 0
    assert mix["shared_prefix_tokens"] * 5 * 640 * 2 \
        < int(env["CAKE_PREFIX_CACHE_MB"]) * 2 ** 20
    # judged on the three metrics that list no cells, on no other
    assert {e["name"] for e in cell.end_to_end} == {
        "itl_p50_ms", "out_tok_s", "setup_s"}
    mine = {e["name"] for e in cell.per_layer}
    assert set(NEW_READERS) <= mine and len(mine) == 24
    assert {"programs.decode.ffn_experts_ms", "programs.decode.ffn_shared_ms",
            "engine.prefix_hit_share", "programs.decode.attn_ms",
            "programs.decode_ms", "engine.occupancy",
            "device.idle_share"} <= mine
    assert not {"cake_flash_attention_roofline", "programs.decode.ssm_ms",
                "programs.decode.attn_full_ms",
                "programs.prefill_chunk_ms.closed"} & mine
    for name, moves in zip(NEW_READERS,
                           ("itl_p50_ms", "out_tok_s", "itl_p50_ms")):
        entry = [e for e in m["per_layer"] if e["name"] == name][0]
        assert entry["workloads"] == [REAL] and entry["moves"] == moves
        assert callable(manifest.metric_reader(BENCH, name))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_every_number_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2"][0]
    cell = manifest.Cell(REAL)
    assert cell.config_entry["source"] == row["source_url"] == \
        cell.bench["source"]
    for key, published in row["config"].items():
        if key in REDUCED:
            assert cell.bench["reduced"][key]["published"] == published
            assert cell.bench["reduced"][key]["here"] == cell.hf[key]
            continue
        assert cell.hf[key] == published, key
    assert set(cell.hf) - set(row["config"]) == {"expert_parallel"}


def test_the_kernels_counts_are_the_issues_arithmetic():
    """672,832 held tokens: 0.937 TFLOP = 4.76 ms at 197 TFLOP/s and
    3.88 GB = 4.73 ms at 819 GB/s: the read sits on the ridge."""
    counts = manifest.kernel_counts(BENCH, "latent_read").counts
    peaks = manifest.Cell(REAL).peaks("TPU v5 lite")
    flops, nbytes = counts(manifest.Cell(REAL).hf, 672832)
    assert flops == 672832 * 5 * 128 * (576 + 512) * 2
    assert nbytes == 672832 * 5 * 576 * 2
    assert round(flops / peaks["bf16_flops_per_s"] * 1e3, 2) == 4.76
    assert round(nbytes / peaks["hbm_bytes_per_s"] * 1e3, 2) == 4.73


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
    assert seen == [("_decode_slots", "attn.latent"),
                    ("_prefill_slot", "attn.latent")]


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
    assert compared["experts_reached_min"] == {"value": -4, "limit": -4}
    control = [json.loads(ln[len("[control] "):])
               for ln in p.stdout.splitlines() if ln.startswith("[control] ")]
    assert control and control[0]["pooled"] > \
        compared["logits_rel_rms_pooled"]["value"]
    # counters read on a CPU too: every judged request restored its three
    # shared blocks (96 of 148-196 tokens) into a row of latents;
    # device-trace metrics are left out of a rehearsal's line, not invented
    assert "engine.step_p50_ms" in line["metrics"]
    assert 35 < line["metrics"]["engine.prefix_hit_share"]["value"] < 95
    assert not set(NEW_READERS) & set(line["metrics"])
    report = [d for d in os.listdir(os.path.join(copy, "benchmark", "out"))
              if d.startswith(CELL)][0]
    with open(os.path.join(copy, "benchmark", "out", report,
                           "child_report.json")) as f:
        rep = json.load(f)
    stepping = [r for r in rep["flight"] if r["occupancy"]]
    assert stepping and all(r["kv_tokens"] >= 96 * r["occupancy"]
                            for r in stepping)
    dispatched = [e for e in rep["spans"]
                  if e["name"] == "serve.decode_dispatch"]
    assert dispatched and all(e["args"]["kv_tokens"] > 0 for e in dispatched)
    kinds = rep["engine"]["attention_kinds"]
    assert [(k["kind"], k["heads"], k["row_width"], k["row_lanes"],
             k["row_bytes"]) for k in kinds] == [("latent", 4, 40, 128, 240)]


def test_every_mechanism_control_reads_over_the_limit(monkeypatch):
    """The reference in int8 beside the sound reading, and without each of
    the family's five mechanisms, each in the program's place."""
    cfg = tiny_deepseek_v2()
    cell = types.SimpleNamespace(
        bench=cfg["benchmark"], mix=MIX,
        hf={k: v for k, v in cfg.items() if k != "benchmark"})
    import reference.deepseek_v2 as ref
    for name, _, plain in TINY_INIT:                    # as in `copy`
        monkeypatch.setattr(ref, name, eval(plain, vars(ref)))
    got = deepseek_v2_controls.readings(cell, [5, 77], 2, log=lambda s: 0)
    assert max(got["sound"]) <= LIMIT, got
    for q in deepseek_v2_controls.WITHOUT:
        assert min(got[q]) > LIMIT, (q, got)
    # (at hidden 64 int8 is no decade below bfloat16, as it is at 5,120)
    assert min(got["int8"]) > 1.2 * min(got["sound"]), got
