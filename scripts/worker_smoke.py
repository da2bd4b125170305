"""Worker portability smoke: brings up a real two-process localhost
cluster — one `WorkerServer` child process + one master — on the JAX
**CPU** backend and checks greedy parity against a local single-process
run. This is the runnable form of the PARITY.md mobile-scope claim ("any
aarch64 JAX-CPU box joins via `cake-tpu worker`"); the CI workflow runs
it on an ARM runner (ref: the reference's Android aarch64 CI job,
/root/reference/.github/workflows/ci.yml).

Must live in a real file (not a heredoc): the worker child is spawned via
multiprocessing, which re-imports __main__ and cannot do so from stdin.

Usage: python scripts/worker_smoke.py  (or scripts/worker_smoke.sh)
Prints one JSON line {"worker_smoke": "ok", ...} and exits 0 on success.
"""
import json
import multiprocessing as mp
import os
import platform
import socket
import sys
import tempfile
import time

# repo root on sys.path: script lives in scripts/, package at the root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a CPU drill: force the platform before first device use
import jax

jax.config.update("jax_platforms", "cpu")


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def make_checkpoint(tmp):
    """Tiny qwen3-shaped synthetic checkpoint on disk (no egress here);
    mirrors tests/test_cluster.py cluster_model_dir."""
    import jax.numpy as jnp

    from cake_tpu.models import tiny_config
    from cake_tpu.models.common.layers import init_params
    from cake_tpu.utils.export import params_to_hf_tensors
    from cake_tpu.utils.safetensors_io import save_safetensors

    cfg = tiny_config("qwen3")
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    save_safetensors(os.path.join(tmp, "model.safetensors"),
                     params_to_hf_tensors(cfg, params))
    with open(os.path.join(tmp, "config.json"), "w") as f:
        json.dump({"architectures": ["Qwen3ForCausalLM"], "vocab_size": 256,
                   "hidden_size": 64, "intermediate_size": 128,
                   "num_hidden_layers": 4, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
                   "rope_theta": 10000.0, "max_position_embeddings": 128,
                   "eos_token_id": 2}, f)
    return cfg, params


def worker_main(port, cache_root):
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax as _jax
    _jax.config.update("jax_platforms", "cpu")
    from cake_tpu.cluster.worker import run_worker
    run_worker("smoke-w0", "smoke-key", port=port, cache_root=cache_root,
               advertise=False)


def main():
    tmp = tempfile.mkdtemp(prefix="cake-smoke-")
    cfg, params = make_checkpoint(tmp)
    port = free_port()
    proc = mp.get_context("spawn").Process(
        target=worker_main, args=(port, os.path.join(tmp, "wcache")),
        daemon=True)
    proc.start()
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), 0.2).close()
            break
        except OSError:
            time.sleep(0.2)
    else:
        print(json.dumps({"worker_smoke": "fail",
                          "error": "worker never listened"}))
        sys.exit(1)

    import jax.numpy as jnp

    from cake_tpu.cluster.master import DistributedTextModel, master_setup
    from cake_tpu.models import SamplingConfig, TextModel

    prompt = [11, 23, 5, 190, 77, 3]
    scfg = SamplingConfig(temperature=0.0)

    local = TextModel(cfg, params, dtype=jnp.float32, max_cache_len=64)
    want, _ = local.generate(prompt, max_new_tokens=12, sampling=scfg)

    workers = [{"name": "smoke-w0", "host": "127.0.0.1", "port": port,
                "caps": {"backend": "cpu", "device": "cpu",
                         "memory_bytes": 4 << 30, "tflops": 50.0}}]
    setup = master_setup(tmp, "smoke-key", cfg, workers,
                         assignments={"smoke-w0": (2, 4)},
                         dtype_str="f32", max_cache_len=64)
    dist = DistributedTextModel(cfg, setup.master_params, setup.stages,
                                dtype=jnp.float32, max_cache_len=64)
    got, _ = dist.generate(prompt, max_new_tokens=12, sampling=scfg)
    for c in setup.clients:
        c.close()
    proc.terminate()

    ok = list(got) == list(want)
    print(json.dumps({"worker_smoke": "ok" if ok else "fail",
                      "machine": platform.machine(),
                      "python": platform.python_version(),
                      "tokens": [int(t) for t in got]}))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
