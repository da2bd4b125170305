"""CPU rehearsal of chip_smoke.py: the serve phase at tiny_config("qwen3")
widths through the SAME code the chip runs — the real CLI server, real
HTTP, the server's own /health, /metrics and SIGTERM drain — plus the
output contract (PR 21 was lost on it). The platform here is "cpu", so the
script itself must refuse to print the ok line."""
import argparse
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def smoke_args():
    return argparse.Namespace(size="tiny", seed=0, chips=1)


@pytest.fixture(scope="module")
def serve_result(smoke_args):
    os.makedirs(os.path.join(chip_smoke.WORK, "logs"), exist_ok=True)
    os.makedirs(os.path.join(chip_smoke.WORK, "results"), exist_ok=True)
    prep = chip_smoke.run_child_phase("prepare", smoke_args,
                                      env={"JAX_PLATFORMS": "cpu"})
    assert prep["passed"] and prep["tensors"] == 2 + 11 * 4
    return chip_smoke.run_serve_phase(smoke_args)


def test_serve_phase_result_structure(serve_result):
    r = serve_result
    assert r["passed"] and r["warm_repeats_identical"]
    assert r["requests"] == 9
    assert all(h > 0 for h in r["prefix_hit_tokens"])
    assert r["metrics"]["engine_rebuilds"] == 0
    assert r["metrics"]["requests_ok"] == 9
    assert r["first_health_s"] > 0 and r["compilations"] > 0
    assert r["compilations_in_last_repeat"] == 0
    # the long prompt was chunked, and on the CPU no chunk claims the kernel
    assert len(r["long_prompt_chunks"]) >= 3
    assert {a for _, _, a in r["long_prompt_chunks"]} == {"masked"}
    assert r["stream"]["content"] and r["stream"]["content_chunks"] > 0


def test_last_line_has_exactly_the_contract_keys(serve_result):
    line = chip_smoke.final_line(serve_result["device"])
    assert "\n" not in line
    obj = json.loads(line)
    assert set(obj) == {"ok", "device"} and obj["ok"] is True
    assert set(obj["device"]) == {"platform", "kind", "count"}
    assert obj["device"]["platform"] == "cpu"       # not a pass: see below
    assert isinstance(obj["device"]["kind"], str)
    assert isinstance(obj["device"]["count"], int)


def test_script_refuses_without_an_accelerator():
    """`python chip_smoke.py` on the CPU: non-zero, and no ok line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--size", "tiny"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "not 'tpu'" in proc.stderr
