"""Run by hand on the CPU (not part of tests/):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The generators, the manifest, the trace reduction on a small recorded
trace, both references against the tiny model, the control, and the tiny
rehearsal of run.py end to end in a temporary copy that ADDS a
configuration, a mix, a cell, a per-layer metric and a kernel's counts as
files of their own.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
sys.path.insert(0, REPO)

import manifest  # noqa: E402
import rehearsal  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _mixes():
    d = os.path.join(BENCH, "traffic")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


# -- the generator -----------------------------------------------------------

@pytest.mark.parametrize("name", _mixes())
def test_same_seed_same_inputs_other_seed_same_work_other_text(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        mix = json.load(f)
    a, b = traffic.generate(mix, 5, 40), traffic.generate(mix, 5, 40)
    c = traffic.generate(mix, 2 ** 31 + 7, 40)
    assert a == b
    assert a != c
    # every seed offers the same work at the same instants; the text differs
    shape = [(r.due_s, r.client, r.prompt_tokens, r.max_tokens) for r in a]
    assert shape == [(r.due_s, r.client, r.prompt_tokens, r.max_tokens)
                     for r in c]
    assert traffic.messages(a[0], mix) == traffic.messages(b[0], mix)
    assert traffic.messages(a[0], mix)[-1] != traffic.messages(c[0], mix)[-1]
    if mix["kind"] == "open_poisson":
        assert all(-mix["ramp_seconds"] <= r.due_s < 40 for r in a)
        assert [r.due_s for r in a] == sorted(r.due_s for r in a)


@pytest.mark.parametrize("name", _mixes())
def test_rendered_prompt_has_exactly_the_tokens_asked_for(name):
    from cake_tpu.models.common.text_model import render_chat
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        mix = json.load(f)
    reqs = traffic.generate(mix, 9, 30)[:40]
    for r in reqs:
        assert len(render_chat(None, traffic.messages(r, mix))) == \
            r.prompt_tokens
    if mix.get("shared_prefix_tokens"):
        n = mix["shared_prefix_tokens"]
        heads = {render_chat(None, traffic.messages(r, mix))[:n]
                 for r in reqs}
        assert len(heads) == 1


# -- the manifest --------------------------------------------------------------

def test_manifest_keeps_the_drivers_rules():
    assert manifest.validate() == []


def test_every_per_layer_metric_has_its_reader():
    for e in manifest.load()["per_layer"]:
        assert callable(manifest.metric_reader(BENCH, e["name"])), e["name"]


def test_manifest_refuses_what_the_driver_would(tmp_path):
    copy = rehearsal.make_copy(str(tmp_path))
    assert manifest.validate(copy) == []
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["per_layer"][0]["unit"] = "tokens per second"
    m["per_layer"][1]["moves"] = "no_such_metric"
    m["workloads"][0]["chips"] = 4
    m["workloads"][1]["chips"] = 4
    m["configs"][1]["reduced"].append("head_dim")
    with open(path, "w") as f:
        json.dump(m, f)
    faults = "\n".join(manifest.validate(copy))
    for needle in ("unit", "moves", "quarter", "width",
                   "lays the deployment out on 1"):
        assert needle in faults, (needle, faults)


# -- the trace reduction -----------------------------------------------------------

def test_union_and_gap_labels():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    got = trace_reduce.label_gaps(
        [(0, 10), (20, 30)], {"a": [(0, 4)], "b": [(2, 8), (25, 26)]},
        ["a", "b"], "rest")
    assert got == {"a": 4e-9, "b": 5e-9, "rest": 11e-9}


def test_reduction_of_the_recorded_trace():
    """A few steps cut from a chip trace of qwen3-4b.chat (PR 25)."""
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        data = json.load(f)
    tr = trace_reduce.Trace(data["trace"])
    busy, window = tr.busy_s(), tr.window_s
    assert 0 < busy < window
    gaps = tr.idle_gaps()
    assert abs(sum(b - a for a, b in gaps) / 1e9 - (window - busy)) < 1e-9
    assert all(b > a for a, b in gaps)
    decodes = tr.events("modules", "_decode_slots")
    assert decodes and all(d > 0 for _, _, d in decodes)
    # kernel sums and the roofline share, through the metric's own reader
    expected = data["expected"]
    assert abs(busy - expected["busy_s"]) < 1e-9

    class Ctx:
        trace, spans = tr, data["spans"]
        peaks = manifest.Cell("qwen3-4b.chat").peaks("TPU v5 lite")
        cell = manifest.Cell("qwen3-4b.chat")

        @staticmethod
        def kernel(name):
            return manifest.kernel_counts(BENCH, name)

    share = manifest.metric_reader(BENCH, "cake_flash_attention_roofline")(
        Ctx)
    assert share == pytest.approx(expected["roofline_share"])
    assert 0 < share <= 100
    idle = manifest.metric_reader(BENCH, "device.idle_share")(Ctx)
    assert idle == pytest.approx(100 * (1 - busy / window))


def test_kernel_counts_are_the_needed_work():
    hf = manifest.Cell("qwen3-4b.chat").hf
    counts = manifest.kernel_counts(BENCH, "cake_flash_attention").counts
    f0, b0 = counts(hf, 0, 256)
    f1, b1 = counts(hf, 256, 256)
    # a fresh chunk is the causal triangle; an append chunk adds the
    # rectangle over what the row already holds
    assert f0 == 4 * 128 * 32 * (256 * 257 // 2)
    assert f1 - f0 == 4 * 128 * 32 * 256 * 256
    assert b1 > b0 > 0


# -- the references and the control -----------------------------------------------

@pytest.mark.parametrize("family", ["qwen3", "qwen3_moe"])
def test_reference_equals_the_program_in_float32_and_control_fails(family):
    import importlib

    import jax.numpy as jnp

    import check
    import weights as weights_mod
    from cake_tpu.models.common.config import config_from_hf_dict
    from cake_tpu.models.common.layers import make_rope
    from cake_tpu.models.common.text_model import TextModel

    hf = {k: v for k, v in rehearsal._tiny(family).items()
          if k != "benchmark"}
    reference = importlib.import_module(f"reference.{family}")
    cfg = config_from_hf_dict(hf)
    sound, control = [], []
    for seed in (3, 2 ** 31 + 11, 77):
        for dtype, sink in ((jnp.float32, None), (jnp.bfloat16, sound)):
            w = weights_mod.make_weights(reference, hf, seed, dtype)
            model = TextModel(cfg, {**w, "rope": make_rope(cfg)},
                              dtype=dtype, seed=1, max_cache_len=256)
            seqs = check.check_ids(seed, hf["vocab_size"], [20, 90])
            served = check.served_logits(
                model, 4, 256, 32, seqs, 3,
                {"temperature": 0.7, "top_p": 0.9})
            got = check.compare(reference, hf, w, served)
            if sink is None:
                # the same arithmetic in the same precision: equal to
                # rounding, through prefill chunks, decode and the tail
                assert got["worst"] < 2e-5, got
            else:
                sink.append(got["pooled"])
                control.append(check.control(reference, hf, w, served,
                                             "fp8")["pooled"])
    # the control (fp8 in the program's place) reads well above the served
    # bf16 model at this size too (a tiny MoE routes top-2 of 8, so one
    # flipped near-tie is a quarter of a layer: twice, not three times);
    # the limits of the real configurations are set from chip readings
    assert min(control) > 2 * max(sound), (sound, control)


# -- run.py end to end, at tiny widths, in a copy that only ADDS files ----------

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,trace", [
    ("tiny-qwen3.tiny_chat", 0), ("tiny-qwen3_moe.tiny_closed", 0),
    ("tiny-qwen3.tiny_chat", 1)])
def test_rehearsal_prints_the_contract_line(copy, cell, trace):
    p = rehearsal.run_cell(copy, cell, 2 ** 31 + 5, 5, trace)
    assert p.returncode == 0, p.stderr[-2000:] + p.stdout[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    want = CONTRACT_KEYS | {"rehearsal"} | ({"breakdown"} if trace else set())
    assert set(line) == want
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"      # never a device metric
    m = manifest.load(copy)
    if trace:
        # the added metric was found by name; metrics with nothing to read
        # on a CPU (device trace) are left out, not invented
        assert "test.prompt_tokens" in line["metrics"]
        assert "engine.step_p50_ms" in line["metrics"]
        assert "programs.decode_ms" not in line["metrics"]
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        names = {e["name"] for e in m["end_to_end"]
                 if cell in e.get("workloads", [cell])}
        assert set(line["metrics"]) == names
        assert ("ttft_p90_ms" in names) == cell.endswith(".tiny_chat")
        assert all(v["value"] > 0 for v in line["metrics"].values())
    if "moe" not in cell:
        assert line["correct"] is True


def test_four_chip_cell_of_an_added_family_runs_sharded(copy):
    """A `chips: 4` cell whose family came as a file: the mesh is built
    from the cell, the weights are born sharded, the check and the engine
    run under it (four virtual CPU devices)."""
    p = rehearsal.run_cell(copy, rehearsal.TP4_CELL, 2 ** 31 + 9, 4, 0,
                           devices=4)
    assert p.returncode == 0, p.stderr[-2000:] + p.stdout[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert '"mesh": {"tp": 4}' in p.stdout
    out = os.path.join(copy, "benchmark", "out")
    log = [d for d in os.listdir(out) if d.startswith(rehearsal.TP4_CELL)][0]
    with open(os.path.join(out, log, "server.log")) as f:
        assert "q_proj sharded PartitionSpec('tp', None)" in f.read()


def test_four_chip_cell_on_one_device_gives_no_result(copy):
    p = rehearsal.run_cell(copy, rehearsal.TP4_CELL, 3, 3, 0, devices=1)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_no_chip_no_result(copy):
    p = rehearsal.run_cell(copy, "tiny-qwen3.tiny_chat", 1, 3, 0, rehearse=0)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert '"correct"' not in p.stdout


def test_bare_directory_fails(tmp_path):
    """Only BENCHMARK.json and benchmark/: no program to serve."""
    copy = rehearsal.make_copy(str(tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "run.py"),
         "--workload", "tiny-qwen3.tiny_chat", "--seed", "1", "--seconds",
         "3", "--trace", "0", "--rehearse", "1"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
