#!/usr/bin/env python3
"""Run by hand, on the chip: a stall made on purpose, and what says so.

    python3 benchmark/tests/stall_drill.py --cell qwen3-4b.chat --on-step N
    python3 benchmark/tests/stall_drill.py --engine

`--cell`: one `--trace 0` run of the cell with `serve/faults.py`'s own plan
in the environment (`CAKE_SERVE_FAULT_PLAN=stall_on_step=N;stall_step_ms=
1000`: decode dispatch N sleeps one second on the scheduler thread; choose N
inside the window from an earlier run's `engine.steps_by_kind`). Prints the
client's `[steadiness]` line beside the report's `engine.stalls` that lie
inside the window. The product's path is untouched: the hook is the one the
chaos drills use.

`--engine`: a tiny random-weight engine in this process, on whatever device
JAX has; one decode dispatch collects a large cycle, compiles a function
nobody has compiled and sleeps 0.6 s. Prints that iteration's stall record:
`gc_ms` and `compiles` are the process's own witnesses (obs/process.py).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def cell(args) -> int:
    env = dict(os.environ, CAKE_SERVE_FAULT_PLAN=(
        f"stall_on_step={args.on_step};stall_step_ms={args.stall_ms}"))
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         args.cell, "--seed", str(args.seed), "--seconds", "40",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True)
    for line in p.stdout.splitlines():
        if line.startswith(("[steadiness]", "[client]", "[correct]")):
            print(line[:600])
    print(f"rc {p.returncode}", p.stderr[-400:] if p.returncode else "")
    path = os.path.join(BENCH, "out", f"{args.cell}-seed{args.seed}-trace0",
                        "child_report.json")
    with open(path) as f:
        rep = json.load(f)
    t0, t1 = rep["window_perf"]
    eng = rep["engine"]
    print("[stalls] count", eng["stalls"]["count"], "reference_ms",
          eng["stalls"]["reference_ms"], "loop_lag_ms",
          eng.get("loop_lag_ms"))
    for s in eng["stalls"]["worst"]:
        where = "window" if t0 <= s["t"] <= t1 else "outside"
        print(f"[stall] {where} at {s['t'] - t0:.2f} s of the window: "
              + json.dumps({k: s[k] for k in (
                  "seq", "kind", "phase", "wall_ms", "gap_ms", "ph",
                  "stall_ms", "gc_ms", "compiles", "compile_ms",
                  "loop_lag_ms", "of_step", "occupancy")}))
    print("[by kind]", json.dumps(eng["steps_by_kind"]), "occupancy_sum",
          eng["occupancy_sum"], "steps", eng["steps"])
    return p.returncode


def engine(args) -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from cake_tpu.models import TextModel, tiny_config
    from cake_tpu.obs import PROCESS
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve import ServeEngine, faults
    from cake_tpu.serve.faults import ServeFaultInjector

    class SlowOnce(ServeFaultInjector):
        seen: int = 0

        def on_decode(self, reqs):
            self.seen += 1
            if self.seen != 5:
                return
            junk = []
            for _ in range(400_000):
                a, b = [], []
                a.append(b)
                b.append(a)
                junk.append(a)
            del junk, a, b
            gc.collect()
            jax.jit(lambda x: jnp.tanh(x * 43.0) + 5)(
                jnp.ones((9, 7))).block_until_ready()
            time.sleep(0.6)

    PROCESS.install()
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind)
    # heads of 128 lanes: the chip's decode kernel refuses the preset's 16
    model = TextModel(tiny_config("qwen3", head_dim=128), dtype=jnp.bfloat16,
                      max_cache_len=256)
    eng = ServeEngine(model, slots=2, max_queue=4, ctx_len=256,
                      prefill_chunk=16)
    greedy = SamplingConfig(temperature=0.0)
    try:
        warm = eng.submit([3, 4, 5, 6], max_new_tokens=4, sampling=greedy)
        assert warm.wait(600) and "error" not in warm.result, warm.result
        time.sleep(0.5)
        seq0 = eng.flight.snapshot()[-1]["seq"]
        faults.install(SlowOnce())
        req = eng.submit([9, 8, 7, 6, 5], max_new_tokens=12, sampling=greedy)
        assert req.wait(600) and "error" not in req.result
        faults.clear()
        time.sleep(0.5)
        stalls = [s for s in eng.flight.stalls()["worst"]
                  if s["seq"] > seq0]
    finally:
        eng.close()
    for s in stalls:
        print("[stall] " + json.dumps(s))
    ok = len(stalls) == 1 and stalls[0]["phase"] == "decode_dispatch" \
        and stalls[0]["gc_ms"] >= 1 and stalls[0]["compiles"] >= 1
    print("drill", "ok" if ok else "NOT ok")
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="")
    ap.add_argument("--engine", action="store_true")
    ap.add_argument("--on-step", type=int, default=0)
    ap.add_argument("--stall-ms", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=2147441901)
    args = ap.parse_args()
    return engine(args) if args.engine else cell(args)


if __name__ == "__main__":
    sys.exit(main())
