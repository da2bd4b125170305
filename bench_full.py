"""Full benchmark matrix: the committed TPU numbers behind BASELINE.md's
non-decode rows (VERDICT r3 item 2 — "perf evidence is a single number").

Prints one JSON line per metric:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N|null, ...}

Baselines (BASELINE.md, RTX 3080 Laptop 16 GB):
  * FLUX.1-dev FP8 768x1024: 3.5 s/step        -> flux2_klein_step_s
    (klein-4B is the FLUX.2 family member that fits 16 GB HBM in bf16;
    FLUX.1-dev needs the fp8-native path and is benched separately)
  * VibeVoice TTS: 20 ms/frame                  -> vibevoice_ms_frame
  * prefill TTFT: no published reference number -> vs_baseline null
  * MoE decode: no published reference number   -> vs_baseline null

Timing discipline: every timed region ends in a real host fetch. One
process at a time holds the chip: the parent starts one child per bench
(so memory_stats peaks are per metric) and itself never initialises a JAX
backend. Without --cpu a platform other than "tpu" is an error; every row
names platform, device_kind and device_count. A failed bench fails the
run: there is no zero row.

Usage: python bench_full.py [--only m1,m2] [--cpu] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp


def _fetch(x):
    return np.asarray(x)


def device_mem_mb() -> dict:
    """HBM residency snapshot (verdict r4 item 3: the fp8-native story needs
    a device measurement, not host-side byte arithmetic)."""
    ms = jax.local_devices()[0].memory_stats() or {}
    out = {}
    if "bytes_in_use" in ms:
        out["hbm_in_use_mb"] = round(ms["bytes_in_use"] / 2**20)
    if "peak_bytes_in_use" in ms:
        out["hbm_peak_mb"] = round(ms["peak_bytes_in_use"] / 2**20)
    return out


def _build_fp8_tree(shape_tree, skip_substrings=("embed_tokens", "lm_head")):
    """Materialize a param tree directly from ShapeDtypeStructs, placing every
    128x128-divisible 2D matmul weight on device as an fp8-native marker dict
    ({"fp8", "scale_inv"}) and everything else in its declared dtype — the
    same in-HBM layout `load_mapped_params(fp8_native=True)` produces, but
    without ever materializing the bf16 model first (an 8B bf16 init would
    blow 16 GB-class HBM before the fp8 conversion could start)."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    leaves, treedef = tree_flatten_with_path(shape_tree)
    rng = np.random.default_rng(0)
    # weight VALUES are throughput-irrelevant (TPU matmul speed is
    # data-independent) — tile one modest random block instead of drawing
    # ~8e9 host-side gaussians for an 8B model
    block = rng.standard_normal(1 << 20, dtype=np.float32) * 0.02

    def _rand(shape, np_dtype):
        n = int(np.prod(shape)) if shape else 1
        reps = -(-n // block.size)
        flat = np.tile(block, reps)[:n] if reps > 1 else block[:n]
        return jnp.asarray(flat.reshape(shape), np_dtype)

    out = []
    for path, leaf in leaves:
        pstr = jax.tree_util.keystr(path)
        shape, dtype = leaf.shape, leaf.dtype
        if (len(shape) == 2 and shape[0] % 128 == 0 and shape[1] % 128 == 0
                and dtype == jnp.bfloat16
                and not any(s in pstr for s in skip_substrings)):
            f8 = _rand(shape, jnp.float8_e4m3fn)
            si = jnp.ones((shape[0] // 128, shape[1] // 128), jnp.float32)
            out.append({"fp8": f8, "scale_inv": si})
        else:
            out.append(_rand(shape, dtype))
    return tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# prefill TTFT at 512 / 2048-token prompts (flagship Qwen3-0.6B shape)
# ---------------------------------------------------------------------------


def bench_prefill(smoke: bool):
    from __graft_entry__ import FLAGSHIP

    from cake_tpu.models import SamplingConfig, TextModel, config_from_hf_dict
    from cake_tpu.models import tiny_config

    cfg = tiny_config("qwen3") if smoke else config_from_hf_dict(FLAGSHIP)
    model = TextModel(cfg, dtype=jnp.bfloat16,
                      max_cache_len=128 if smoke else 4096)
    scfg = SamplingConfig(temperature=0.0)
    out = []
    for n in ((16, 32) if smoke else (512, 2048)):
        prompt = list(np.random.default_rng(0).integers(
            0, cfg.vocab_size - 1, size=n))
        model.generate(prompt, max_new_tokens=1, sampling=scfg)   # compile
        ttfts = []
        for _ in range(5):
            _, stats = model.generate(prompt, max_new_tokens=1, sampling=scfg)
            ttfts.append(stats["ttft_s"])
        p50 = float(np.median(ttfts))
        out.append({
            "metric": f"prefill_ttft_{n}",
            "value": round(p50 * 1e3, 1), "unit": "ms",
            "vs_baseline": None,
        })
    return out


# ---------------------------------------------------------------------------
# FLUX.2-klein denoise step (768x1024, the reference's FLUX.1 geometry)
# ---------------------------------------------------------------------------


def bench_flux2(smoke: bool):
    from cake_tpu.models.image.flux2 import (Flux2ImageModel,
                                             Flux2PipelineConfig,
                                             tiny_flux2_config)
    cfg = tiny_flux2_config() if smoke else Flux2PipelineConfig()
    m = Flux2ImageModel(cfg, dtype=jnp.bfloat16)
    w, h = (64, 64) if smoke else (768, 1024)
    steps = 2 if smoke else 4
    m.generate_image("warmup", width=w, height=h, steps=1, seed=0)  # compile
    t0 = time.monotonic()
    img = m.generate_image("bench", width=w, height=h, steps=steps, seed=0)
    _fetch(img)        # generate already decodes+fetches; keep it explicit
    per_step = (time.monotonic() - t0) / steps
    return [{
        "metric": "flux2_klein_step_s",
        "value": round(per_step, 3), "unit": "s/step",
        # reference headline: FLUX.1-dev FP8 3.5 s/step at this geometry
        "vs_baseline": round(3.5 / per_step, 2),
        "note": "includes VAE decode amortized over steps; klein-4B bf16 "
                "vs reference flux1-dev-12B fp8 (the 16 GB-fitting member "
                "of each family)",
    }]


# ---------------------------------------------------------------------------
# VibeVoice-Realtime-0.5B speech frame rate
# ---------------------------------------------------------------------------


def bench_tts(smoke: bool):
    from cake_tpu.models.audio.vibevoice import (VibeVoiceConfig, VibeVoiceTTS,
                                                 tiny_tts_config)
    from cake_tpu.models.common.config import tiny_config

    if smoke:
        cfg = tiny_tts_config()
    else:
        # VibeVoice-Realtime-0.5B: Qwen2.5-0.5B backbone split 4 base +
        # 20 TTS layers (ref: vibevoice.rs model shape / BASELINE.md row)
        qwen05 = dict(vocab_size=151936, hidden_size=896,
                      intermediate_size=4864, num_attention_heads=14,
                      num_key_value_heads=2, rms_norm_eps=1e-6,
                      rope_theta=1e6, max_position_embeddings=4096,
                      eos_token_id=151645, tie_word_embeddings=True)
        base = tiny_config("qwen2", **{**qwen05, "num_hidden_layers": 4})
        tts = tiny_config("qwen2", **{**qwen05, "num_hidden_layers": 20})
        cfg = VibeVoiceConfig(lm_base=base, lm_tts=tts)
    m = VibeVoiceTTS(cfg, dtype=jnp.bfloat16, max_frames=16)
    text = "The quick brown fox jumps over the lazy dog."
    m.generate_speech(text, max_frames=2, seed=0)    # compile
    n_frames = 4 if smoke else 12
    t0 = time.monotonic()
    audio = m.generate_speech(text, max_frames=n_frames, seed=0)
    _fetch(audio.samples)
    frames = max(1, round(len(audio.samples) / (cfg.hop)))
    ms = (time.monotonic() - t0) / frames * 1e3
    return [{
        "metric": "vibevoice_ms_frame",
        "value": round(ms, 1), "unit": "ms/frame",
        "vs_baseline": round(20.0 / ms, 2),    # reference: 20 ms/frame
        "frames": frames,
    }]


# ---------------------------------------------------------------------------
# MoE decode (largest qwen3-moe-shaped config fitting 16 GB HBM)
# ---------------------------------------------------------------------------


def bench_moe(smoke: bool):
    from cake_tpu.models import SamplingConfig, TextModel, tiny_config
    if smoke:
        cfg = tiny_config("qwen3_moe")
    else:
        # ~11.5 GB bf16: 48 experts x (3 * 768 * 2048) x 24 layers
        cfg = tiny_config(
            "qwen3_moe", vocab_size=151936, hidden_size=2048,
            intermediate_size=6144, num_hidden_layers=24,
            num_attention_heads=16, num_key_value_heads=4, head_dim=128,
            num_experts=48, num_experts_per_tok=8, moe_intermediate_size=768,
            max_position_embeddings=4096)
    model = TextModel(cfg, dtype=jnp.bfloat16,
                      max_cache_len=128 if smoke else 1024)
    scfg = SamplingConfig(temperature=0.0)
    prompt = list(np.random.default_rng(0).integers(
        0, cfg.vocab_size - 1, size=32))
    tokens = 32 if smoke else 256
    model.generate(prompt, max_new_tokens=tokens, sampling=scfg)   # compile
    rates = []
    for _ in range(3):
        _, stats = model.generate(prompt, max_new_tokens=tokens, sampling=scfg)
        rates.append(stats["tok_per_s"])
    active = cfg.num_experts_per_tok / cfg.num_experts
    return [{
        "metric": "qwen3_moe_decode",
        "value": round(float(np.mean(rates)), 1), "unit": "tok/s",
        "vs_baseline": None,     # reference publishes no MoE numbers
        "config": f"{cfg.num_experts}e-top{cfg.num_experts_per_tok}"
                  f"-h{cfg.hidden_size}-L{cfg.num_hidden_layers}",
        "active_fraction": round(active, 3),
    }]


# ---------------------------------------------------------------------------
# Llama-3-8B fp8-native decode (the 16 GB "largest dense" config)
# ---------------------------------------------------------------------------


def bench_llama8b_fp8(smoke: bool):
    from cake_tpu.models import SamplingConfig, TextModel, tiny_config
    from cake_tpu.models.common.layers import init_params

    if smoke:
        cfg = tiny_config("llama")
    else:
        # Llama-3-8B geometry (ref BASELINE.json north star); bf16 needs
        # ~16 GB for weights alone, fp8-native halves it to ~8 GB resident
        cfg = tiny_config(
            "llama", vocab_size=128256, hidden_size=4096,
            intermediate_size=14336, num_hidden_layers=32,
            num_attention_heads=32, num_key_value_heads=8, head_dim=128,
            rope_theta=500000.0, max_position_embeddings=4096)

    # build the fp8-native pytree directly from shapes: every matmul weight
    # becomes a {"fp8", "scale_inv"} marker dict resolved inside the jitted
    # forward — never materializing the ~16 GB bf16 model first
    shapes = jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0))
    params = _build_fp8_tree(shapes)
    mem_resident = device_mem_mb()

    model = TextModel(cfg, params=params, dtype=jnp.bfloat16,
                      max_cache_len=128 if smoke else 1024)
    scfg = SamplingConfig(temperature=0.0)
    prompt = list(np.random.default_rng(0).integers(
        0, cfg.vocab_size - 1, size=32))
    tokens = 32 if smoke else 128
    model.generate(prompt, max_new_tokens=tokens, sampling=scfg)   # compile
    rates = []
    for _ in range(3):
        _, stats = model.generate(prompt, max_new_tokens=tokens, sampling=scfg)
        rates.append(stats["tok_per_s"])
    return [{
        "metric": "llama3_8b_fp8_decode",
        "value": round(float(np.mean(rates)), 1), "unit": "tok/s",
        "vs_baseline": None,    # reference cannot fit 8B on its 16 GB GPU
        "note": "fp8-native resident weights (~8 GB HBM), bf16 compute",
        "hbm_weights_mb": mem_resident.get("hbm_in_use_mb"),
        **device_mem_mb(),
    }]


# ---------------------------------------------------------------------------
# FLUX.1-dev fp8-native denoise step (the reference's actual headline row:
# 3.5 s/step at 768x1024, 13,317 MB resident — docs/benchmarks/README.md)
# ---------------------------------------------------------------------------


def bench_flux1_fp8(smoke: bool):
    from cake_tpu.models.image.flux import (FluxImageModel, FluxPipelineConfig,
                                            tiny_flux_config)
    from cake_tpu.models.image.mmdit import init_mmdit_params
    from cake_tpu.models.image.vae import init_vae_decoder_params

    cfg = tiny_flux_config() if smoke else FluxPipelineConfig()
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    shapes = jax.eval_shape(
        lambda a, b: {
            "transformer": init_mmdit_params(cfg.mmdit, a, jnp.bfloat16),
            "vae": init_vae_decoder_params(cfg.vae, b, jnp.bfloat16),
        }, k1, k2)
    # fp8 the transformer matmuls only; VAE convs + norms stay bf16
    params = _build_fp8_tree(shapes, skip_substrings=("vae",))
    mem_resident = device_mem_mb()
    m = FluxImageModel(cfg, params=params, dtype=jnp.bfloat16)
    w, h = (64, 64) if smoke else (768, 1024)
    steps = 2 if smoke else 4
    m.generate_image("warmup", width=w, height=h, steps=1, seed=0)   # compile
    t0 = time.monotonic()
    img = m.generate_image("bench", width=w, height=h, steps=steps, seed=0)
    _fetch(img)
    per_step = (time.monotonic() - t0) / steps
    return [{
        "metric": "flux1_fp8_step_s",
        "value": round(per_step, 3), "unit": "s/step",
        "vs_baseline": round(3.5 / per_step, 2),   # ref: 3.5 s/step fp8
        "note": "FLUX.1-dev geometry (19+38 blocks, h3072), fp8-native "
                "resident transformer weights, bf16 compute; includes VAE "
                "decode amortized over steps",
        "hbm_weights_mb": mem_resident.get("hbm_in_use_mb"),
        **device_mem_mb(),
    }]


BENCHES = {
    "prefill": bench_prefill,
    "flux2": bench_flux2,
    "flux1_fp8": bench_flux1_fp8,
    "tts": bench_tts,
    "moe": bench_moe,
    "llama8b_fp8": bench_llama8b_fp8,
}

# generous per-bench wall budgets (first compile of a 57-block MMDiT or a
# 32-layer 8B model is minutes on its own)
BENCH_TIMEOUT_S = {"flux2": 2400, "flux1_fp8": 2400, "llama8b_fp8": 1800}
DEFAULT_TIMEOUT_S = 1200


def _run_inproc(names, smoke, cpu):
    """Child mode: this process holds the chip. An exception in a bench
    is the child's non-zero exit."""
    from bench import device_fields
    from cake_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    device = device_fields(cpu_ok=cpu)
    for name in names:
        for row in BENCHES[name](smoke):
            print(json.dumps({**row, **device}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", help="comma-separated subset of "
                                   f"{sorted(BENCHES)}")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU platform (control-flow smoke)")
    ap.add_argument("--inproc", action="store_true",
                    help="run benches in this process (child mode; the "
                         "default parent spawns one subprocess per bench so "
                         "memory_stats peaks are per-metric)")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(BENCHES)
    if args.inproc:
        if args.cpu:
            jax.config.update("jax_platforms", "cpu")
        _run_inproc(names, args.smoke, args.cpu)
        return 0

    # parent mode: no jax call below this line — one process at a time
    # owns the chip, and it is the child. Children print their own rows.
    import subprocess
    failed = []
    for name in names:
        cmd = [sys.executable, __file__, "--only", name, "--inproc"]
        if args.smoke:
            cmd.append("--smoke")
        if args.cpu:
            cmd.append("--cpu")
        limit = BENCH_TIMEOUT_S.get(name, DEFAULT_TIMEOUT_S)
        try:
            rc = subprocess.run(cmd, timeout=limit).returncode
        except subprocess.TimeoutExpired:
            rc = f"timeout after {limit}s"
        if rc != 0:
            failed.append((name, rc))
            print(f"[bench_full] {name} FAILED: {rc}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
