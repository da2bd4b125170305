"""BENCHMARK.json and the files it names, found by name. No JAX.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under benchmark/; the harness knows
none of them by name. `validate()` holds the manifest to the rules the
driver states (character sets, lengths, cross-references), so that a later
PR's added entry is refused here, on the CPU, before a chip run.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load(root: str | None = None) -> dict:
    with open(os.path.join(root or ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with everything it names, loaded."""

    def __init__(self, name: str, root: str | None = None):
        self.root = root or ROOT
        self.bench_dir = os.path.join(self.root, "benchmark")
        m = self.manifest = load(self.root)
        found = [w for w in m["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"known: {[w['name'] for w in m['workloads']]}")
        self.workload = found[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg = [c for c in m["configs"]
               if c["name"] == self.workload["config"]][0]
        self.config_entry = cfg
        self.config = _json(os.path.join(self.root, cfg["file"]))
        self.bench = self.config["benchmark"]
        self.hf = {k: v for k, v in self.config.items() if k != "benchmark"}
        self.mix = _json(os.path.join(self.bench_dir, "traffic",
                                      self.workload["traffic"] + ".json"))
        self.end_to_end = [e for e in m["end_to_end"] if self._applies(e)]
        self.per_layer = [e for e in m["per_layer"] if self._applies(e)]

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def peaks(self, device_kind: str) -> dict:
        table = _json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table:
            raise SystemExit(f"device kind {device_kind!r} is not in "
                             "benchmark/peaks.json; add it with its source")
        return table[device_kind]


def load_module(path: str):
    """A Python file by path (metric names hold dots, so no import name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", os.path.basename(path)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: str, name: str):
    return load_module(os.path.join(bench_dir, "layer_metrics",
                                    name + ".py")).read


def kernel_counts(bench_dir: str, kernel: str):
    return load_module(os.path.join(bench_dir, "kernels", kernel + ".py"))


def validate(root: str | None = None) -> list[str]:
    """Every rule a CPU can check; returns the faults found."""
    root = root or ROOT
    m, bad = load(root), []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(m) != keys:
        bad.append(f"top-level keys {sorted(m)} != {sorted(keys)}")
    bench_dir = os.path.join(root, "benchmark")

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME.match(n):
            bad.append(f"{what} name {n!r} is outside the name rules")

    def line_ok(s, what):
        if not (isinstance(s, str) and 1 <= len(s) <= 200
                and "\n" not in s and "\t" not in s):
            bad.append(f"{what} must be 1-200 characters on one line")

    for word in m["command"]:
        line_ok(word, "command word")
    if not 1 <= int(m["run_seconds"]) <= 51:
        bad.append("run_seconds outside 1..51")
    cfg_names, files, cfg_chips = set(), set(), {}
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
        name_ok(c["name"], "config")
        line_ok(c["source"], "source")
        line_ok(c["why"], "why")
        if c["name"] in cfg_names or c["file"] in files:
            bad.append(f"config {c['name']} or its file appears twice")
        cfg_names.add(c["name"])
        files.add(c["file"])
        if not any(c["file"].startswith(p + "/") for p in m["paths"]):
            bad.append(f"config file {c['file']} is outside paths")
        elif not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config file {c['file']} is missing")
        else:
            data = _json(os.path.join(root, c["file"]))
            listed = set(data.get("benchmark", {}).get("reduced", {}))
            if listed != set(c["reduced"]):
                bad.append(f"config {c['name']}: reduced {c['reduced']} != "
                           f"the file's {sorted(listed)}")
            cfg_chips[c["name"]] = data.get("benchmark", {}).get("chips")
            fam = data.get("benchmark", {}).get("family")
            if not os.path.exists(os.path.join(bench_dir, "reference",
                                               f"{fam}.py")):
                bad.append(f"config {c['name']}: no reference/{fam}.py")
        for k in c["reduced"]:
            name_ok(k, "reduced key")
            if re.search(r"(_dim|_rank|hidden_size|intermediate_size|"
                         r"head_dim|experts_per_tok)$", k):
                bad.append(f"config {c['name']}: reduced names a width {k}")
    cells, pairs, four = {}, set(), 0
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        line_ok(w["why"], "why")
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips must be 1 or 4")
        four += w["chips"] == 4
        if cfg_chips.get(w["config"], w["chips"]) != w["chips"]:
            bad.append(f"workload {w['name']}: chips {w['chips']} but the "
                       f"configuration's file lays the deployment out on "
                       f"{cfg_chips[w['config']]}")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']} appears twice")
        cells[w["name"]] = w
        pairs.add((w["config"], w["traffic"]))
        if not any(os.path.exists(os.path.join(
                bench_dir, "traffic", w["traffic"] + ext))
                for ext in TRAFFIC_EXT):
            bad.append(f"workload {w['name']}: no traffic file")
    if four > max(len(cells) // 4, 1):
        bad.append("more than a quarter of the cells ask for four chips")
    for c in cfg_names - {w["config"] for w in m["workloads"]}:
        bad.append(f"config {c} has no cell")
    metric_names, e2e = set(), {}
    for e in m["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        if not {"name", "unit", "better", "bound", "source"} <= set(e) \
                or not set(e) <= allowed:
            bad.append(f"end_to_end {e.get('name')}: keys {sorted(e)}")
        if e["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {e['name']}: source {e['source']}")
        if not 0 < e["bound"] <= 0.1:
            bad.append(f"end_to_end {e['name']}: bound {e['bound']}")
        e2e[e["name"]] = e
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    for e in m["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        if not allowed - {"workloads"} <= set(e) or not set(e) <= allowed:
            bad.append(f"per_layer {e.get('name')}: keys {sorted(e)}")
            continue
        line_ok(e["layer"], "layer")
        if e["source"] not in SOURCES:
            bad.append(f"per_layer {e['name']}: source {e['source']}")
        if e["moves"] not in e2e:
            bad.append(f"per_layer {e['name']}: moves {e['moves']!r} is "
                       "not an end-to-end metric")
            continue
        moved = e2e[e["moves"]]
        for w in e.get("workloads", cells):
            if w not in cells:
                bad.append(f"per_layer {e['name']}: unknown cell {w}")
            elif "workloads" in moved and w not in moved["workloads"]:
                bad.append(f"per_layer {e['name']}: cell {w} does not "
                           f"report {e['moves']}")
        if not os.path.exists(os.path.join(bench_dir, "layer_metrics",
                                           e["name"] + ".py")):
            bad.append(f"per_layer {e['name']}: no reader file")
        if e["name"].endswith("_roofline") and e["unit"] != "%":
            bad.append(f"per_layer {e['name']}: a roofline share is in %")
    for e in m["end_to_end"] + m["per_layer"]:
        name_ok(e["name"], "metric")
        if e["name"] in metric_names:
            bad.append(f"metric {e['name']} appears twice")
        metric_names.add(e["name"])
        if not UNIT.match(e["unit"]):
            bad.append(f"metric {e['name']}: unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            bad.append(f"metric {e['name']}: better {e['better']!r}")
        for w in e.get("workloads", []):
            if w not in cells:
                bad.append(f"metric {e['name']}: unknown cell {w}")
    for w in cells:
        reported = [e for e in m["end_to_end"]
                    if "workloads" not in e or w in e["workloads"]]
        if len([e for e in reported if e["name"] != "setup_s"]) < 1:
            bad.append(f"cell {w} reports no end-to-end metric but setup_s")
        if not [e for e in m["per_layer"]
                if "workloads" not in e or w in e["workloads"]]:
            bad.append(f"cell {w} reports no per-layer metric")
    if len(json.dumps(m)) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad


if __name__ == "__main__":
    faults = validate()
    print("\n".join(faults) or "BENCHMARK.json: no fault found")
    raise SystemExit(1 if faults else 0)
