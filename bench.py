"""Headline benchmark: Qwen3-0.6B-shaped single-chip decode throughput.

Prints ONE JSON line:
  {"metric": "qwen3_0.6b_decode", "value": <tok/s>, "unit": "tok/s",
   "vs_baseline": <value / 185.7>, "p50_ttft_ms": <ms>,
   "platform": ..., "device_kind": ..., "device_count": ...}
Any failure is an exception and a non-zero exit: there is no zero row.

This process is the one holder of the chip. Without --cpu, a platform
other than "tpu" is an error (a CPU run is a smoke of the control flow,
never a speed); every result names the device it ran on.

Baseline: the reference's best published small-model decode — Qwen2.5-0.5B
F16 at 185.7 tok/s on an RTX 3080 Laptop (BASELINE.md; the closest published
number to the BASELINE.json north-star config). Random weights: throughput
is weight-value independent, and the environment has no network egress.

Usage: python bench.py [--smoke] [--cpu] [--tokens N] [--runs N]
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_TOK_S = 185.7


def device_fields(cpu_ok: bool) -> dict:
    """platform / device_kind / device_count as JAX reports them; without
    --cpu anything but a TPU is an error, not a fallback."""
    d = jax.devices()[0]
    if d.platform != "tpu" and not cpu_ok:
        raise SystemExit(f"no TPU: jax reports platform {d.platform!r} "
                         "(pass --cpu for a control-flow smoke)")
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(jax.devices())}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny model quick check")
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU platform (control-flow smoke; "
                         "same as JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from cake_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    device = device_fields(cpu_ok=args.cpu)
    metric = "smoke_decode" if args.smoke else "qwen3_0.6b_decode"

    from cake_tpu.models import (SamplingConfig, TextModel, config_from_hf_dict,
                                 tiny_config)
    from __graft_entry__ import FLAGSHIP

    if args.smoke:
        cfg = tiny_config("qwen3")
        cache_len = 128
        args.tokens = min(args.tokens, 64)
    else:
        cfg = config_from_hf_dict(FLAGSHIP)
        cache_len = 2048

    model = TextModel(cfg, dtype=jnp.bfloat16, max_cache_len=cache_len)
    prompt = list(np.random.default_rng(0).integers(
        0, cfg.vocab_size - 1, size=args.prompt_len))
    scfg = SamplingConfig(temperature=0.0)   # greedy, seeded (ref bench: temp=0)

    # warmup / compile — full token count so every cache-length bucket the
    # timed runs will touch is compiled here, not inside the timed loop
    model.generate(prompt, max_new_tokens=args.tokens, sampling=scfg,
                   chunk=args.chunk)

    rates, ttfts = [], []
    for _ in range(args.runs):
        toks, stats = model.generate(prompt, max_new_tokens=args.tokens,
                                     sampling=scfg, chunk=args.chunk)
        rates.append(stats["tok_per_s"])
        ttfts.append(stats["ttft_s"])
    # extra TTFT-only samples: median over more draws than the full runs
    for _ in range(4):
        _, stats = model.generate(prompt, max_new_tokens=1, sampling=scfg,
                                  chunk=args.chunk)
        ttfts.append(stats["ttft_s"])

    value = float(np.mean(rates))
    result = {
        "metric": metric,
        "value": round(value, 2),
        "unit": "tok/s",
        # only the full-width model on the chip is comparable to it
        "vs_baseline": (round(value / BASELINE_TOK_S, 3)
                        if device["platform"] == "tpu" and not args.smoke
                        else None),
        "p50_ttft_ms": round(float(np.median(ttfts)) * 1e3, 1),
        **device,
    }
    print(json.dumps(result))
    print(json.dumps({"detail": {"runs": args.runs, "tokens": args.tokens,
                                 "dtype": "bfloat16"}}), file=sys.stderr)


if __name__ == "__main__":
    main()
