#!/usr/bin/env python3
"""Device time by named scope, per execution of a program, from a profiler
trace (`jax.profiler` / `obs.jax_trace` / the benchmark's `--trace 1`).

    python scripts/xplane_scopes.py <trace_dir> [program ...]

The programs carry `jax.named_scope`s (obs.spans.SCOPE_CATALOG). In a TPU
xplane they reach the ops as the `tf_op` stat of the op's event METADATA
(`jit(_decode_slots)/vmap(cake.sample)/cake.sample.sort/gather:`), which
`jax.profiler.ProfileData` does not expose (it lists an event's own stats
only), so this reads the protobuf itself through TensorFlow's bindings.
Prints one JSON object: for each program (default `_decode_slots` and
`_prefill_slot`) the number of executions on the first device plane, the
median execution in ms, the median summed op time under every scope (a
nested scope counts under its parents too; `(none)` is what carries no
scope), and the largest unscoped ops.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import statistics
import sys

SCOPE = re.compile(r"[/(](cake\.[a-z_.]+)[/)]")
UNSCOPED = "(none)"


def scope_times(path: str, programs: list[str]) -> dict:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    plane = next(p for p in space.planes if p.name.startswith("/device:TPU"))
    stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
    name, scopes = {}, {}
    for mid, md in plane.event_metadata.items():
        name[mid] = md.name
        for st in md.stats:
            if stat_name.get(st.metadata_id) == "tf_op":
                # a string stat is stored inline or as a reference
                text = st.str_value or stat_name.get(st.ref_value, "")
                scopes[mid] = SCOPE.findall(text)
    lines = {ln.name: ln for ln in plane.lines}

    def events(line):
        base = line.timestamp_ns * 1000
        return [(e.metadata_id, base + e.offset_ps, e.duration_ps)
                for e in line.events]

    modules, ops = events(lines["XLA Modules"]), events(lines["XLA Ops"])
    out = {"plane": plane.name, "xplane": path}
    for prog in programs:
        runs = sorted((s, s + d) for m, s, d in modules if prog in name[m])
        if not runs:
            continue
        starts = [s for s, _ in runs]
        sums = [collections.Counter() for _ in runs]
        bare = collections.Counter()
        for m, s, d in ops:
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s + d > runs[k][1]:
                continue
            for scope in scopes.get(m) or [UNSCOPED]:
                sums[k][scope] += d
            if not scopes.get(m):
                bare[name[m][:100]] += d
        keys = sorted({k for c in sums for k in c})
        out[prog] = {
            "executions": len(runs),
            "execution_ms": statistics.median((e - s) / 1e9 for s, e in runs),
            "scope_ms": {k: statistics.median(c[k] / 1e9 for c in sums)
                         for k in keys},
            "largest_unscoped_ms": [[n, ps / 1e9 / len(runs)]
                                    for n, ps in bare.most_common(6)]}
    return out


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    found = sorted(glob.glob(os.path.join(
        argv[0], "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    print(json.dumps(scope_times(found[-1], argv[1:] or ["_decode_slots",
                                                    "_prefill_slot"]),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
