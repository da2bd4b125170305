#!/usr/bin/env python3
"""How tests/data/small_trace.json was made: a slice of a traced run's
child_report.json around the first prefill execution that ran the flash
kernel, with the spans that lie in it and the numbers the reduction gives
for it (the test recomputes them).

    python benchmark/tests/data/cut_trace.py <child_report.json> <out.json> [ms]
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, BENCH)

import manifest  # noqa: E402
import trace_reduce  # noqa: E402


def main(src: str, dst: str, width_ms: float = 150.0):
    with open(src) as f:
        rep = json.load(f)
    tr, names = rep["trace"], rep["trace"]["names"]
    dev = tr["devices"][0]
    kernel_starts = [s for i, s, d in dev["ops"]
                     if "cake_flash_attention" in names[i]]
    mods = [(s, d) for i, s, d in dev["modules"]
            if "_prefill_slot" in names[i]
            and any(s <= k < s + d for k in kernel_starts)]
    t0 = mods[0][0] - int(20e6)
    t1 = t0 + int(width_ms * 1e6)
    offset = tr["sync"]["prof_ns"] - tr["sync"]["perf_ns"]
    keep: dict[int, int] = {}

    def cut(events):
        out = []
        for i, s, d in events:
            if s >= t0 and s + d <= t1:
                out.append([keep.setdefault(i, len(keep)), s, d])
        return out

    # the kernel's events as they are; every other op merged into the
    # intervals it covers (the busy union is unchanged, the file is small)
    kernel = [e for e in dev["ops"] if "cake_flash_attention" in names[e[0]]]
    rest = trace_reduce.union([(s, s + d) for i, s, d in dev["ops"]
                               if "cake_flash_attention" not in names[i]
                               and s >= t0 and s + d <= t1])
    names.append("(other ops, merged)")
    merged = [[len(names) - 1, s, e - s] for s, e in rest]
    small = {"devices": [{"plane": dev["plane"],
                          "ops": cut(kernel) + cut(merged),
                          "modules": cut(dev["modules"])}],
             "sync": tr["sync"], "window_perf_ns": [t0 - offset, t1 - offset]}
    small["names"] = [n[:120] for n, _ in sorted(
        ((names[i], j) for i, j in keep.items()), key=lambda x: x[1])]
    lo, hi = (t0 - offset) / 1e3 - 5e5, (t1 - offset) / 1e3
    spans = [e for e in rep["spans"] if lo <= e["ts"] <= hi]
    t = trace_reduce.Trace(small)

    class Ctx:
        trace = t
        cell = manifest.Cell("qwen3-4b.chat")
        peaks = cell.peaks("TPU v5 lite")

        @staticmethod
        def kernel(name):
            return manifest.kernel_counts(BENCH, name)

    Ctx.spans = spans
    share = manifest.metric_reader(BENCH, "cake_flash_attention_roofline")(
        Ctx)
    with open(dst, "w") as f:
        json.dump({"trace": small, "spans": spans,
                   "expected": {"busy_s": t.busy_s(),
                                "roofline_share": share}}, f)
    print(f"{dst}: {len(small['devices'][0]['ops'])} ops, "
          f"{len(small['devices'][0]['modules'])} modules, {len(spans)} "
          f"spans, busy {t.busy_s():.4f} of {t.window_s:.4f} s, roofline "
          f"share {share}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2],
         float(sys.argv[3]) if len(sys.argv) > 3 else 150.0)
