#!/usr/bin/env python3
"""The controls of the longcat_flash family, by hand (one process, as
calibrate.py):

    python benchmark/tests/longcat_flash_controls.py --workload longcat-flash-chat-l4-ep32.toolcall --seeds 12 --controls 3

For each seed: the served programs' logits at the cell's own sizes held
against the reference (the sound reading), and for the first `--controls`
seeds nine controls put in the program's place as `check.control` puts
them: the reference in int8, and the reference with one of the family's
mechanisms got wrong (keywords of reference/longcat_flash.py's own
`forward_logits`): the identity experts' term dropped, the sparse layer's
output added a sub-layer early (the unshortcut block), the sparse layer
computed from the second sub-layer's post-attention norm, the query
latent's scale 1, the key/value latent's scale 1, selection without the
bias, the softmax over the 512 real outputs only, the routed scale 1. Each
must read over the configuration's limit on every seed, or the
configuration's file says which the pooled number cannot see. calibrate.py
and check.py stay as they are; this file only calls them.
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

# control name -> the keywords of forward_logits that take the mechanism out
WITHOUT = {"zero_off": {"zero": "off"},
           "shortcut_early": {"shortcut": "early"},
           "shortcut_post1": {"shortcut": "post1"},
           "q_scale_1": {"q_scale": 1.0},
           "kv_scale_1": {"kv_scale": 1.0},
           "select_bias_off": {"select_bias": "off"},
           "router_real": {"router": "real"},
           "routed_scale_1": {"routed_scale": 1.0}}
CONTROLS = ("int8",) + tuple(WITHOUT)
# the controls the check must call not correct on every seed: all of them
MUST_FAIL = CONTROLS


class Without:
    """reference/longcat_flash.py behind the interface `check.control` calls: a
    control named in WITHOUT is the reference without that mechanism."""

    def __init__(self, reference):
        self.reference = reference

    def forward_logits(self, hf, weights, ids, positions, quant=None):
        if quant in WITHOUT:
            return self.reference.forward_logits(
                hf, weights, ids, positions, **WITHOUT[quant])
        return self.reference.forward_logits(hf, weights, ids, positions,
                                             quant=quant)


def readings(cell, seeds: list[int], controls: int, log=print) -> dict:
    """{"sound": [...], "int8": [...], "zero_off": [...], ...} pooled: the
    sound reading of every seed, each control's of the first `controls`."""
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
    without = Without(reference)
    cfg, ck = config_from_hf_dict(hf), cell.mix["check"]
    rope = make_rope(cfg)
    model, out = None, {k: [] for k in ("sound",) + CONTROLS}
    for n, seed in enumerate(seeds):
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
        got = check.compare(reference, hf, w, served)
        row = {"seed": seed, "sound": got["pooled"], "worst": got["worst"],
               "modes": [o["modes"] for o in served]}
        row["experts_used"], row["experts_needed"] = \
            reference.experts_used(hf, w, served[-1]["ids"])
        out["sound"].append(row["sound"])
        for q in CONTROLS if n < controls else ():
            c = check.control(without, hf, w, served, q)
            row[q] = c["pooled"]
            row[q + "_smallest_point"] = min(c["points"].values())
            out[q].append(c["pooled"])
        del served, w
        log(json.dumps(row))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 6400)
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
    got = readings(cell, [args.first_seed + k * 7919 - (2 ** 31 if k % 3 == 2
                                                         else 0)
                          for k in range(args.seeds)], args.controls,
                   log=lambda s: print(s, flush=True))
    print(json.dumps({"workload": args.workload,
                      "limit": cell.bench["correct"]["limit"],
                      "sound_largest": max(got["sound"]),
                      "sound_smallest": min(got["sound"]),
                      **{q + "_smallest": min(got[q]) for q in CONTROLS
                         if got[q]},
                      **{q + "_largest": max(got[q]) for q in CONTROLS
                         if got[q]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
