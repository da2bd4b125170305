#!/usr/bin/env python3
"""Readings for the limit of `correct`, in ONE process on the chip (set-up
is long, the comparison needs no timed window):

    python benchmark/calibrate.py --workload <cell> --seeds 12 --controls 3

For each seed: weights from the seed, the served programs' logits at the
cell's own sizes (check.py), the reference's, and for the first
`--controls` seeds the control's (the reference in int8 and in fp8, held
against the reference proper). Prints one JSON line per seed and, last, the
two numbers a limit is set from: the largest sound reading and the smallest
control reading.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import manifest  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args()
    cell = manifest.Cell(args.workload)
    bm, hf = cell.bench, cell.hf
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, manifest.ROOT)
    from cake_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    import check
    import weights as weights_mod
    from cake_tpu.models.common.config import config_from_hf_dict
    from cake_tpu.models.common.layers import make_rope
    from cake_tpu.models.common.text_model import TextModel

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    cfg = config_from_hf_dict(hf)
    rope = make_rope(cfg)
    mesh = weights_mod.cell_mesh(cell)
    env = bm["engine_env"]
    slots, ctx = int(env["CAKE_SERVE_SLOTS"]), int(env["CAKE_SERVE_CTX"])
    chunk = int(env["CAKE_PREFILL_CHUNK"])
    reference = importlib.import_module(f"reference.{bm['family']}")
    ck = cell.mix["check"]
    model, sound, controls = None, [], {"int8": [], "fp8": []}
    for k in range(args.seeds):
        seed = args.first_seed + k * 7919 + (2 ** 31 if k % 4 == 3 else 0)
        if model is not None:
            model.params = None             # free 8 GB before the next 8
        w = weights_mod.make_weights(reference, hf, seed, jnp.bfloat16,
                                     mesh=mesh)
        if model is None:
            model = TextModel(cfg, {**w, "rope": rope}, dtype=jnp.bfloat16,
                              seed=1, max_cache_len=int(bm["max_cache_len"]),
                              mesh=mesh)
        else:
            model.params = {**w, "rope": rope}
        seqs = check.check_ids(seed, hf["vocab_size"], ck["prompt_tokens"])
        served = check.served_logits(model, slots, ctx, chunk, seqs,
                                     ck["decode_steps"],
                                     cell.mix["sampling"])
        got = check.compare(reference, hf, w, served)
        row = {"seed": seed, "pooled": got["pooled"], "worst": got["worst"]}
        sound.append(got["pooled"])
        if k < args.controls:
            for q in controls:
                c = check.control(reference, hf, w, served, q)
                controls[q].append(c["pooled"])
                row[f"control_{q}"] = {"pooled": c["pooled"],
                                       "smallest_point":
                                       min(c["points"].values())}
        del served, w
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": args.seeds,
        "sound_largest": max(sound), "sound_smallest": min(sound),
        "control_smallest": {q: min(v) for q, v in controls.items() if v},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
