#!/usr/bin/env python3
"""The second control of the jamba family, by hand (one process, as
calibrate.py):

    python benchmark/tests/jamba_controls.py --workload jamba2-3b.crowd --seeds 3

For each seed: the served programs' logits at the cell's own sizes held
against the reference (the sound reading), and two controls put in the
program's place as `check.control` puts them: the reference in int8, and the
reference with the Mamba state and the conv tail ZEROED at every boundary of
a prefill chunk (`drop_state_at` of reference/jamba.py) — what a program
would serve that lost a row's recurrent state between two dispatches. Both
must read over the configuration's limit: the first proves the check sees
precision, the second that it sees the state. calibrate.py and check.py
stay as they are; this file only calls them.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

DROP = "drop_state"


class WithDroppedState:
    """reference/jamba.py behind the interface `check.control` calls: the
    control named DROP is the reference with the state dropped at every
    multiple of `chunk` tokens."""

    def __init__(self, reference, chunk: int):
        self.reference, self.chunk = reference, chunk

    def forward_logits(self, hf, weights, ids, positions, quant=None):
        if quant != DROP:
            return self.reference.forward_logits(hf, weights, ids, positions,
                                                 quant=quant)
        return self.reference.forward_logits(
            hf, weights, ids, positions,
            drop_state_at=range(self.chunk, len(ids), self.chunk))


def readings(cell, seeds: list[int], log=print) -> dict:
    """{"sound": [...], "int8": [...], DROP: [...]} pooled, one per seed."""
    import jax.numpy as jnp

    import check
    import weights as weights_mod
    from cake_tpu.models.common.config import config_from_hf_dict
    from cake_tpu.models.common.layers import make_rope
    from cake_tpu.models.common.text_model import TextModel

    bm, hf = cell.bench, cell.hf
    env = bm["engine_env"]
    slots, ctx = int(env["CAKE_SERVE_SLOTS"]), int(env["CAKE_SERVE_CTX"])
    chunk = int(env["CAKE_PREFILL_CHUNK"])
    reference = importlib.import_module(f"reference.{bm['family']}")
    dropped = WithDroppedState(reference, chunk)
    cfg, ck = config_from_hf_dict(hf), cell.mix["check"]
    rope = make_rope(cfg)
    model, out = None, {"sound": [], "int8": [], DROP: []}
    for seed in seeds:
        if model is not None:
            model.params = None         # free the weights before the next
        w = weights_mod.make_weights(reference, hf, seed, jnp.bfloat16)
        if model is None:
            model = TextModel(cfg, {**w, "rope": rope}, dtype=jnp.bfloat16,
                              seed=1, max_cache_len=int(bm["max_cache_len"]))
        else:
            model.params = {**w, "rope": rope}
        served = check.served_logits(
            model, slots, ctx, chunk,
            check.check_ids(seed, hf["vocab_size"], ck["prompt_tokens"]),
            ck["decode_steps"], cell.mix["sampling"])
        row = {"seed": seed,
               "sound": check.compare(reference, hf, w, served)["pooled"]}
        for q in ("int8", DROP):
            c = check.control(dropped, hf, w, served, q)
            row[q] = c["pooled"]
            row[q + "_smallest_point"] = min(c["points"].values())
        for k in out:
            out[k].append(row[k])
        del served, w
        log(json.dumps(row))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 3600)
    args = ap.parse_args()
    import manifest
    cell = manifest.Cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, manifest.ROOT)
    from cake_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    got = readings(cell, [args.first_seed + k * 7919
                          for k in range(args.seeds)],
                   log=lambda s: print(s, flush=True))
    print(json.dumps({"workload": args.workload,
                      "limit": cell.bench["correct"]["limit"],
                      "sound_largest": max(got["sound"]),
                      "int8_smallest": min(got["int8"]),
                      "drop_state_smallest": min(got[DROP])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
