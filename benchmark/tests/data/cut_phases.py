#!/usr/bin/env python3
"""How tests/data/phases_trace.json was made: a slice of a traced run's
child_report.json (the program with PR 26's phase spans) a second into the
trace, with the device ops merged into the intervals they cover and gaps
under 2 us between ops closed (inside a program; the file is small, and
the expected numbers are read from the slice as it is), the module
executions, the spans that touch the slice, and the numbers the readers
give for it (the test recomputes them).

    python benchmark/tests/data/cut_phases.py <child_report.json> <out.json> [ms]
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, BENCH)

import manifest  # noqa: E402
import phases  # noqa: E402
import trace_reduce  # noqa: E402

READERS = ["device.idle_share", "engine.step_host_p50_ms"] + [
    f"engine.idle.{g}_share" for g in list(phases.GROUPS) + [phases.UNNAMED]]


class Ctx:
    """What the readers of this slice read."""

    def __init__(self, data: dict):
        self.trace = trace_reduce.Trace(data["trace"])
        self.spans = data["spans"]

    def spans_named(self, name):
        return [e for e in self.spans if e["name"] == name]


def main(src: str, dst: str, width_ms: float = 400.0):
    with open(src) as f:
        rep = json.load(f)
    tr, dev = rep["trace"], rep["trace"]["devices"][0]
    offset = tr["sync"]["prof_ns"] - tr["sync"]["perf_ns"]
    t0 = tr["window_perf_ns"][0] + offset + int(1e9)
    t1 = t0 + int(width_ms * 1e6)
    busy = []
    for a, b in trace_reduce.union([(max(s, t0), min(s + d, t1))
                                    for _, s, d in dev["ops"]
                                    if s < t1 and s + d > t0]):
        if busy and a - busy[-1][1] < 2000:
            busy[-1][1] = b
        else:
            busy.append([a, b])
    names = ["(ops, merged)"]
    modules = []
    for i, s, d in dev["modules"]:
        if s >= t0 and s + d <= t1:
            if tr["names"][i] not in names:
                names.append(tr["names"][i])
            modules.append([names.index(tr["names"][i]), s, d])
    small = {"names": names,
             "devices": [{"plane": dev["plane"], "modules": modules,
                          "ops": [[0, s, e - s] for s, e in busy]}],
             "sync": tr["sync"], "window_perf_ns": [t0 - offset, t1 - offset]}
    # the spans that can lie under the slice once shifted by the lead
    lo, hi = (t0 - offset) / 1e3 - 2e4, (t1 - offset) / 1e3 + 2e4
    data = {"trace": small,
            "spans": [e for e in rep["spans"]
                      if e["ts"] + e["dur"] >= lo and e["ts"] <= hi]}
    ctx = Ctx(data)
    data["expected"] = {name: manifest.metric_reader(BENCH, name)(ctx)
                        for name in READERS}
    data["expected"]["device_lead_ns"] = phases.device_lead_ns(ctx)
    with open(dst, "w") as f:
        json.dump(data, f)
    print(f"{dst}: {len(busy)} busy intervals, {len(modules)} modules, "
          f"{len(data['spans'])} spans; {json.dumps(data['expected'])}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2],
         float(sys.argv[3]) if len(sys.argv) > 3 else 400.0)
