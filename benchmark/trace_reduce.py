"""From a profiler trace to numbers. Two steps, so that the second can be
tested on a small recorded trace without a chip:

  compact(xplane_path, ...)   in the process that has JAX: reads the
      `.xplane.pb`, keeps the device planes' op and module events and the
      one host annotation that ties the profiler's clock to perf_counter.
  Trace(compact_dict)         pure Python: busy union, idle gaps, sums by
      name, events inside a module execution.

All times inside a Trace are nanoseconds on the PROFILER's clock;
`perf_to_prof` converts the recorder's perf_counter stamps.
"""
from __future__ import annotations

import bisect
import glob
import os

SYNC_NAME = "bench.sync"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def compact(xplane_path: str, sync_perf_ns: int, start_perf_ns: int,
            stop_perf_ns: int) -> dict:
    """Device events + the clock tie, as plain JSON-able data."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    names: dict[str, int] = {}

    def idx(name: str) -> int:
        return names.setdefault(name, len(names))

    devices, sync_prof_ns, layout = [], None, []
    for plane in pd.planes:
        lines = list(plane.lines)
        layout.append({"plane": plane.name,
                       "lines": [ln.name for ln in lines]})
        if plane.name.startswith("/device:"):
            dev = {"plane": plane.name, "ops": [], "modules": []}
            for ln in lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(ln.name)
                if key is None:
                    continue
                for ev in ln.events:
                    dev[key].append([idx(ev.name), int(ev.start_ns),
                                     int(ev.duration_ns)])
            if dev["ops"]:
                devices.append(dev)
        elif sync_prof_ns is None:
            for ln in lines:
                for ev in ln.events:
                    if ev.name == SYNC_NAME:
                        sync_prof_ns = int(ev.start_ns)
                        break
                if sync_prof_ns is not None:
                    break
    if sync_prof_ns is None:
        raise ValueError(f"no {SYNC_NAME!r} annotation in {xplane_path}")
    inv = [None] * len(names)
    for n, i in names.items():
        inv[i] = n
    return {"names": inv, "devices": devices, "layout": layout,
            "sync": {"prof_ns": sync_prof_ns, "perf_ns": int(sync_perf_ns)},
            "window_perf_ns": [int(start_perf_ns), int(stop_perf_ns)]}


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    def __init__(self, data: dict):
        self.names = data["names"]
        self.devices = data["devices"]
        self.offset_ns = data["sync"]["prof_ns"] - data["sync"]["perf_ns"]
        p0, p1 = data["window_perf_ns"]
        self.t0, self.t1 = self.perf_to_prof(p0), self.perf_to_prof(p1)

    def perf_to_prof(self, perf_ns: int) -> int:
        return int(perf_ns) + self.offset_ns

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _clip(self, events):
        for i, s, d in events:
            a, b = max(s, self.t0), min(s + d, self.t1)
            if b > a:
                yield i, a, b

    def busy(self, dev: dict) -> list[tuple[int, int]]:
        return union([(a, b) for _, a, b in self._clip(dev["ops"])])

    def busy_s(self) -> float:
        """Seconds with an op on the device, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(b - a for a, b in self.busy(d))
                   for d in self.devices) / len(self.devices) / 1e9

    def idle_gaps(self, dev_index: int = 0) -> list[tuple[int, int]]:
        """Idle intervals of one device inside the window."""
        if not self.devices:
            return []
        gaps, t = [], self.t0
        for a, b in self.busy(self.devices[dev_index]):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.t1 > t:
            gaps.append((t, self.t1))
        return gaps

    def op_seconds(self) -> dict[str, float]:
        """Summed device time by op name, first device."""
        out: dict[str, float] = {}
        for i, a, b in self._clip(self.devices[0]["ops"] if self.devices
                                  else []):
            out[self.names[i]] = out.get(self.names[i], 0.0) + (b - a) / 1e9
        return out

    def events(self, key: str, substr: str, dev_index: int = 0
               ) -> list[tuple[str, int, int]]:
        """(name, start, duration) of events whose name holds `substr`,
        whole ones only (both ends inside the window)."""
        out = []
        if not self.devices:
            return out
        for i, s, d in self.devices[dev_index][key]:
            if substr in self.names[i] and s >= self.t0 and s + d <= self.t1:
                out.append((self.names[i], s, d))
        return out

    def inside(self, key: str, substr: str, start: int, end: int,
               dev_index: int = 0) -> list[tuple[str, int, int]]:
        return [(n, s, d) for n, s, d in self.events(key, substr, dev_index)
                if s >= start and s + d <= end]


def label_gaps(gaps: list[tuple[int, int]],
               spans: dict[str, list[tuple[int, int]]],
               order: list[str], rest: str) -> dict[str, float]:
    """Seconds of idle time by what the host was doing: each gap is split
    over the first label in `order` whose spans cover it, the remainder
    going to `rest`."""
    merged = {k: union(v) for k, v in spans.items()}
    starts = {k: [s for s, _ in v] for k, v in merged.items()}
    out = {k: 0.0 for k in order}
    out[rest] = 0.0

    def covered(label: str, a: int, b: int) -> list[tuple[int, int]]:
        iv, st = merged.get(label, []), starts.get(label, [])
        i = max(bisect.bisect_right(st, a) - 1, 0)
        got = []
        while i < len(iv) and iv[i][0] < b:
            s, e = max(iv[i][0], a), min(iv[i][1], b)
            if e > s:
                got.append((s, e))
            i += 1
        return got

    for a, b in gaps:
        left = [(a, b)]
        for label in order:
            nxt = []
            for s, e in left:
                cut = covered(label, s, e)
                out[label] += sum(y - x for x, y in cut) / 1e9
                t = s
                for x, y in cut:
                    if x > t:
                        nxt.append((t, x))
                    t = y
                if e > t:
                    nxt.append((t, e))
            left = nxt
        out[rest] += sum(e - s for s, e in left) / 1e9
    return out
