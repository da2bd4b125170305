"""A temporary copy of the benchmark with tiny configurations, mixes, a
per-layer metric and a kernel's counts ADDED AS FILES (no file that is
there is edited) — the CPU rehearsal of run.py, and the proof that a later
PR can add one of each the same way.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def _tiny(model_type: str, family: str | None = None, chips: int = 1
          ) -> dict:
    family = family or model_type
    hf = {
        "architectures": ["Qwen3ForCausalLM" if model_type == "qwen3"
                          else "Qwen3MoeForCausalLM"],
        "model_type": model_type, "vocab_size": 512, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2 if chips == 1 else chips, "head_dim": 16,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
        "max_position_embeddings": 512,
        "tie_word_embeddings": model_type == "qwen3",
    }
    if model_type == "qwen3_moe":
        hf.update(num_experts=8, num_experts_per_tok=2,
                  moe_intermediate_size=32, norm_topk_prob=True,
                  decoder_sparse_step=1, mlp_only_layers=[])
    hf["benchmark"] = {
        "source": "none: a test preset", "family": family, "chips": chips,
        "deployment": "CPU rehearsal", "reduced": {}, "assumed": {},
        "engine_env": {"CAKE_SERVE_SLOTS": "4", "CAKE_SERVE_CTX": "512",
                       "CAKE_PREFILL_CHUNK": "32",
                       "CAKE_PREFIX_CACHE_MB": "8",
                       "CAKE_MAX_QUEUE": "64"},
        "max_cache_len": 512,
        "correct": {"number": "as the real configurations", "limit": 0.05,
                    "control": "int8"},
    }
    return hf


TP4_CELL = "tiny-qwen3-tp4.tiny_chat"

MIXES = {
    "tiny_chat": {
        "kind": "open_poisson", "what": "test", "rate_rps": 3.0,
        "ramp_seconds": 2, "shape_seed": 1, "shared_prefix_tokens": 32,
        "unique_tokens": {"dist": "uniform", "min": 62, "max": 120},
        "max_tokens": {"dist": "uniform", "min": 4, "max": 10},
        "sampling": {"temperature": 0.7, "top_p": 0.9},
        "check": {"prompt_tokens": [20, 90], "decode_steps": 3}},
    "tiny_closed": {
        "kind": "closed", "what": "test", "clients": 4, "ramp_seconds": 2,
        "stagger_seconds": 0.2, "shape_seed": 1, "shared_prefix_tokens": 0,
        "unique_tokens": {"dist": "uniform", "min": 51, "max": 90},
        "max_tokens": {"dist": "uniform", "min": 6, "max": 12},
        "sampling": {"temperature": 0.7, "top_p": 0.9},
        "check": {"prompt_tokens": [20, 90], "decode_steps": 3}},
}

ADDED_METRIC = '''"""A metric a later PR adds: tokens the judged requests' prompts held."""


def read(ctx):
    return float(sum(r.req.prompt_tokens for r in ctx.records
                     if r.sent is not None))
'''

# a family a later PR adds: its reference and its leaf tree, one file
ADDED_FAMILY = '''\"\"\"A family added as a file (qwen3's, by another name).\"\"\"
from reference.qwen3 import forward_logits, layer_leaves  # noqa: F401
'''

ADDED_KERNEL = '''def counts(hf, pos0, tokens):
    return 1.0, 1.0
'''


def make_copy(dst: str) -> str:
    """Copy benchmark/ to dst and ADD tiny cells; returns dst."""
    bench = os.path.join(dst, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", ".pytest_cache"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    cells = []
    with open(os.path.join(bench, "reference", "tiny_added.py"), "w") as f:
        f.write(ADDED_FAMILY)
    for cfg, tiny, mix in (
            ("tiny-qwen3", _tiny("qwen3"), "tiny_chat"),
            ("tiny-qwen3_moe", _tiny("qwen3_moe"), "tiny_closed"),
            # four chips, tensor-parallel, of a family added as a file
            (TP4_CELL.split(".")[0], _tiny("qwen3", "tiny_added", chips=4),
             "tiny_chat")):
        with open(os.path.join(bench, "configs", cfg + ".json"), "w") as f:
            json.dump(tiny, f, indent=1)
        with open(os.path.join(bench, "traffic", mix + ".json"), "w") as f:
            json.dump(MIXES[mix], f, indent=1)
        m["configs"].append({"name": cfg, "source": "none: a test preset",
                             "file": f"benchmark/configs/{cfg}.json",
                             "reduced": [], "why": "CPU rehearsal"})
        m["workloads"].append({"name": f"{cfg}.{mix}", "config": cfg,
                               "traffic": mix,
                               "chips": tiny["benchmark"]["chips"],
                               "why": "CPU rehearsal"})
        cells.append(f"{cfg}.{mix}")
    with open(os.path.join(bench, "layer_metrics",
                           "test.prompt_tokens.py"), "w") as f:
        f.write(ADDED_METRIC)
    with open(os.path.join(bench, "kernels", "test_kernel.py"), "w") as f:
        f.write(ADDED_KERNEL)
    m["per_layer"].append({
        "name": "test.prompt_tokens", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "api", "moves": "out_tok_s",
        "workloads": cells})
    open_loop = [c for c in cells if c.endswith(".tiny_chat")]
    for e in m["end_to_end"]:           # TTFT is judged in the open loops
        if "workloads" in e:
            e["workloads"] = e["workloads"] + open_loop
    for e in m["per_layer"]:
        if e["name"] in ("engine.step_p50_ms", "engine.occupancy"):
            e["workloads"] = e["workloads"] + cells
        if e["name"] in ("api.overhead_ms", "admission.queue_wait_p90_ms"):
            e["workloads"] = e["workloads"] + open_loop
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(m, f, indent=1)
    return dst


def run_cell(copy: str, workload: str, seed: int, seconds: float,
             trace: int, rehearse: int = 1, timeout: float = 600.0,
             devices: int = 1):
    """`devices`: how many virtual CPU devices the child's JAX sees."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    return subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace), "--rehearse", str(rehearse)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=timeout)


if __name__ == "__main__":
    out = make_copy(sys.argv[1])
    p = run_cell(out, sys.argv[2], int(sys.argv[3]), float(sys.argv[4]),
                 int(sys.argv[5]))
    print(p.stdout[-6000:])
    print(p.stderr[-3000:], file=sys.stderr)
    sys.exit(p.returncode)
