#!/usr/bin/env python3
"""One run of one cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It is the load generator and the clock: it
starts the one child that holds the chip (launch_server.py), waits until
that has checked its outputs and warmed its programs (`setup_s` ends
there), makes the cell's traffic (the schedule from the mix's own
`shape_seed`, the same for every run; the prompt text from --seed: see
traffic.py), sends it over HTTP with streaming on, and times every
request from the instant it was DUE.

The last line of stdout is the one JSON object of the contract (`correct`,
`attempted`, `failed`, `metrics`, `device`, and `breakdown` when traced).
Everything else — set-up by phase, the numbers compared with their limits,
generator lateness, counts by status, samples behind each percentile — is
on earlier lines and under benchmark/out/.

--trace 0 reports the end-to-end metrics; --trace 1 takes a profiler trace
in the middle of the window and reports the per-layer metrics instead.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import itertools
import json
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import manifest  # noqa: E402
import traffic  # noqa: E402

READY_TIMEOUT_S = 1150.0     # a first run in a checkout compiles
DRAIN_TIMEOUT_S = 90.0
TRACE_AT, TRACE_SECONDS = 0.35, 4.0     # share of the window; length


def info(msg: str):
    print(msg, flush=True)


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (q in 0..100)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


class Child:
    """launch_server.py: JSON lines both ways, stderr to a log file."""

    def __init__(self, cell, args, out_dir: str):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.log_path = os.path.join(out_dir, "server.log")
        self._log = open(self.log_path, "w")
        cmd = [sys.executable, os.path.join(HERE, "launch_server.py"),
               "--workload", cell.name, "--seed", str(args.seed),
               "--trace", str(args.trace), "--port", str(self.port),
               "--out", out_dir, "--rehearse", str(args.rehearse)]
        if args.control:
            cmd += ["--control", args.control]
        self.proc = subprocess.Popen(
            cmd, cwd=cell.root, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self._log, text=True)
        self.lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self):
        for line in self.proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                try:
                    self.lines.put(json.loads(line))
                    continue
                except ValueError:
                    pass
            if line:
                self._log.write(f"[stdout] {line}\n")
        self.lines.put(None)

    def expect(self, event: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                msg = self.lines.get(timeout=max(deadline - time.monotonic(),
                                                 0.01))
            except queue.Empty:
                raise RuntimeError(f"no {event!r} from the server within "
                                   f"{timeout:.0f} s") from None
            if msg is None:
                raise RuntimeError(
                    f"the server exited ({self.proc.wait()}) before "
                    f"{event!r}; see {self.log_path}")
            if msg.get("event") == event:
                return msg
            if msg.get("event") == "error":
                raise RuntimeError(f"server: {msg}")

    def send(self, **cmd):
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def stop(self) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


class Record:
    """What the client saw of one request (monotonic seconds)."""

    def __init__(self, req: traffic.Request, judged_by_send: bool):
        self.req, self.rid = req, f"b{req.index}"
        self.due: float | None = None
        self.sent: float | None = None
        self.tokens: list[float] = []
        self.status: str = "pending"
        self.finish: str | None = None
        self.judged_by_send = judged_by_send

    @property
    def origin(self) -> float | None:
        return self.sent if self.judged_by_send else self.due


async def one_request(session, base: str, rec: Record, mix: dict):
    rec.sent = time.monotonic()
    try:
        async with session.post(
                base + "/v1/chat/completions", json=traffic.body(rec.req, mix),
                headers={"X-Cake-Request-Id": rec.rid}) as resp:
            if resp.status != 200:
                rec.status = f"http_{resp.status}"
                return
            done = False
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                payload = raw[5:].strip()
                if payload == b"[DONE]":
                    done = True
                    break
                choice = json.loads(payload)["choices"][0]
                if choice.get("delta", {}).get("content"):
                    rec.tokens.append(time.monotonic())
                if choice.get("finish_reason"):
                    rec.finish = choice["finish_reason"]
            if not done or rec.finish != "length" \
                    or len(rec.tokens) != rec.req.max_tokens:
                rec.status = (f"ended_early:{rec.finish}:"
                              f"{len(rec.tokens)}/{rec.req.max_tokens}")
            else:
                rec.status = "ok"
    except asyncio.CancelledError:
        rec.status = "cut_at_end" if rec.tokens else "no_token_by_end"
        raise
    except Exception as e:                  # a refused or broken connection
        rec.status = f"error:{type(e).__name__}"


async def drive(cell, schedule, seconds: float, port: int, on_mark,
                trace: bool):
    """Send the schedule; returns (records, window start, lateness list)."""
    import aiohttp
    mix = cell.mix
    base = f"http://127.0.0.1:{port}"
    ramp = float(mix.get("ramp_seconds", 0))
    closed = mix["kind"] == "closed"
    records = [] if closed else [Record(r, False) for r in schedule]
    tasks: list[asyncio.Task] = []
    late: list[float] = []
    timeout = aiohttp.ClientTimeout(total=None, sock_read=300)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout,
                                     connector=conn) as session:
        t0 = time.monotonic() + ramp
        t_end = t0 + seconds

        async def sleep_until(t):
            d = t - time.monotonic()
            if d > 0:
                await asyncio.sleep(d)

        async def marks():
            await sleep_until(t0)
            on_mark("mark", name="window_start")
            if trace:
                await sleep_until(t0 + TRACE_AT * seconds)
                on_mark("trace_start")
                await asyncio.sleep(min(TRACE_SECONDS, 0.5 * seconds))
                on_mark("trace_stop")
            await sleep_until(t_end)
            on_mark("mark", name="window_end")

        async def open_loop():
            for rec in records:
                rec.due = t0 + rec.req.due_s
                await sleep_until(rec.due)
                late.append(time.monotonic() - rec.due)
                tasks.append(asyncio.create_task(
                    one_request(session, base, rec, mix)))

        async def caller(i: int):
            await sleep_until(t0 - ramp + i * float(
                mix.get("stagger_seconds", 0)))
            own = [r for r in schedule if r.client == i]
            for n in itertools.count():     # round again if it runs dry
                if time.monotonic() >= t_end:
                    return
                req = dataclasses.replace(
                    own[n % len(own)], index=i + n * int(mix["clients"]))
                records.append(Record(req, True))
                await one_request(session, base, records[-1], mix)

        marker = asyncio.create_task(marks())
        if closed:
            tasks += [asyncio.create_task(caller(i))
                      for i in range(int(mix["clients"]))]
        else:
            await open_loop()
        await marker

        def judged(rec):
            o = rec.origin
            return o is not None and t0 <= o < t_end

        # after the window: every judged request still gets its first token
        deadline = time.monotonic() + DRAIN_TIMEOUT_S
        while time.monotonic() < deadline and any(
                judged(r) and not r.tokens and r.status == "pending"
                for r in records):
            await asyncio.sleep(0.05)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    for rec in records:                 # a caller cut between two requests
        if rec.status == "pending" and rec.sent is not None:
            rec.status = "cut_at_end" if rec.tokens else "no_token_by_end"
    return records, t0, late


def end_to_end(records, t0: float, seconds: float) -> tuple[dict, dict]:
    """The client-side numbers and the counts behind them."""
    t_end = t0 + seconds
    judged = [r for r in records
              if r.origin is not None and t0 <= r.origin < t_end]
    failed = [r for r in judged
              if r.status not in ("ok", "cut_at_end")]
    ttft = [(r.tokens[0] - r.origin) * 1e3 for r in judged if r.tokens]
    gaps, n_tok = [], 0
    for r in records:
        n_tok += sum(1 for t in r.tokens if t0 <= t < t_end)
        gaps += [(b - a) * 1e3 for a, b in zip(r.tokens, r.tokens[1:])
                 if t0 <= b < t_end]
    vals = {}
    if ttft:
        vals["ttft_p90_ms"] = percentile(ttft, 90)
        vals["ttft_p50_ms"] = percentile(ttft, 50)
    if gaps:
        vals["itl_p50_ms"] = percentile(gaps, 50)
        vals["itl_p95_ms"] = percentile(gaps, 95)
    vals["out_tok_s"] = n_tok / seconds
    by_status: dict[str, int] = {}
    for r in records:
        if r.sent is not None:
            key = r.status.split(":")[0]
            by_status[key] = by_status.get(key, 0) + 1
    counts = {"attempted": len(judged), "failed": len(failed),
              "sent": sum(by_status.values()), "by_status": by_status,
              "failed_examples": [r.status for r in failed[:5]],
              "ttft_samples": len(ttft), "gap_samples": len(gaps),
              "tokens_in_window": n_tok}
    return vals, counts


def steadiness(records, t0: float, seconds: float) -> dict:
    """Where the window's tokens fell: the tokens of each of its seconds and
    the longest time in which no stream got one. A pause of the whole
    server (a collection, a starved host) shows here and nowhere else."""
    times = sorted(t - t0 for r in records for t in r.tokens
                   if t0 <= t < t0 + seconds)
    per_s = [0] * int(seconds + 0.999)
    for t in times:
        per_s[int(t)] += 1
    edges = [0.0] + times + [seconds]
    gap, at = max((b - a, a) for a, b in zip(edges, edges[1:]))
    return {"longest_silence_ms": round(gap * 1e3, 1),
            "silence_began_s": round(at, 2), "tokens_by_second": per_s}


def sweep_stage(cell, args, port, on_mark, rate: float):
    """One window at one rate; the knee is where the backlog starts to
    grow (first tokens still owed at the window's end, TTFT rising from
    the window's first half to its second)."""
    schedule = traffic.generate(cell.mix, args.seed, args.seconds,
                                rate_rps=rate)
    records, t0, _ = asyncio.run(drive(cell, schedule, args.seconds, port,
                                       on_mark, False))
    vals, counts = end_to_end(records, t0, args.seconds)
    half = t0 + args.seconds / 2
    t_end = t0 + args.seconds
    first = [(r.tokens[0] - r.due) * 1e3 for r in records
             if r.tokens and t0 <= r.due < half]
    second = [(r.tokens[0] - r.due) * 1e3 for r in records
              if r.tokens and half <= r.due < t_end]
    owed = sum(1 for r in records if t0 <= r.due < t_end
               and (not r.tokens or r.tokens[0] > t_end))
    info("[sweep] " + json.dumps({
        "rate": rate, **{k: round(v, 2) for k, v in vals.items()},
        "ttft_p50_first_half": round(percentile(first, 50), 1)
        if first else None,
        "ttft_p50_second_half": round(percentile(second, 50), 1)
        if second else None,
        "first_tokens_owed_at_end": owed,
        "attempted": counts["attempted"], "failed": counts["failed"]}))


class Context:
    """What a per-layer metric's reader may read."""

    def __init__(self, cell, records, t0, seconds, report, device):
        import trace_reduce
        self.cell, self.records = cell, records
        self.t0, self.seconds = t0, seconds
        self.window_perf = report["window_perf"]
        self.spans = report.get("spans", [])
        self.flight = report.get("flight", [])
        self.timelines = report.get("timelines", {})
        self.trace = trace_reduce.Trace(report["trace"])
        self.device_kind = device["kind"]

    @property
    def peaks(self) -> dict:
        """This device's row of benchmark/peaks.json; an unknown kind is
        an error, never a default."""
        return self.cell.peaks(self.device_kind)

    def spans_named(self, name: str) -> list[dict]:
        """Recorder spans that lie inside the measured window."""
        lo, hi = (t * 1e6 for t in self.window_perf)
        return [e for e in self.spans if e["name"] == name
                and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]

    def timeline_events(self, kind: str) -> list[dict]:
        """Events of that kind from the judged requests' timelines."""
        t_end = self.t0 + self.seconds
        out = []
        for r in self.records:
            o = r.origin
            tl = self.timelines.get(r.rid)
            if tl and o is not None and self.t0 <= o < t_end:
                for e in tl.get("events", []):
                    if e.get("kind") == kind:
                        out.append({**e, "rid": r.rid})
        return out

    def kernel(self, name: str):
        return manifest.kernel_counts(self.cell.bench_dir, name)


def breakdown(ctx: Context) -> dict:
    import trace_reduce
    tr = ctx.trace
    ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:10]

    def iv(name):
        return [(tr.perf_to_prof(e["ts"] * 1000),
                 tr.perf_to_prof((e["ts"] + e["dur"]) * 1000))
                for e in ctx.spans if e["name"] == name]

    labels = trace_reduce.label_gaps(
        tr.idle_gaps(),
        {"serve.prefill_chunk: host dispatch of a chunk":
         iv("serve.prefill_chunk"),
         "serve.step: sweeps, admission, dispatch, fetch, fan-out":
         iv("serve.step")},
        ["serve.prefill_chunk: host dispatch of a chunk",
         "serve.step: sweeps, admission, dispatch, fetch, fan-out"],
        "between steps: scheduler loop, API thread, no request")
    gaps = sorted(labels.items(), key=lambda kv: -kv[1])
    # HLO text runs to hundreds of characters: the head names the op
    return {"device_ops": [[n[:160], s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps if s > 0][:10]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0,
                    help="1: allow the CPU (tests); prints no device metric")
    ap.add_argument("--sweep", default="",
                    help="tool: comma-separated rates, one window each, on "
                         "one server; prints a line per rate and no result")
    ap.add_argument("--control", default="",
                    help="tool: also read the control (int8 | fp8)")
    args = ap.parse_args()
    t_launch = time.monotonic()
    cell = manifest.Cell(args.workload)
    out_dir = os.path.join(cell.bench_dir, "out",
                           f"{cell.name}-seed{args.seed}-trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    child = Child(cell, args, out_dir)
    try:
        checked = child.expect("checked", READY_TIMEOUT_S)
        ready = child.expect("ready", READY_TIMEOUT_S)
        setup_s = time.monotonic() - t_launch
        device = ready["device"]
        info(f"[setup] {setup_s:.2f} s; phases (s from the child's start): "
             + json.dumps({k: round(v, 2) for k, v in ready["phases"].items()})
             + f"; compiles {json.dumps(ready['compile'])}")
        info(f"[engine] {json.dumps(checked['engine'])} warmed "
             f"{json.dumps(checked['warmed'])}")
        def on_mark(cmd, **kw):
            child.send(cmd=cmd, **kw)

        if args.sweep:
            for rate in map(float, args.sweep.split(",")):
                sweep_stage(cell, args, ready["port"], on_mark, rate)
            child.stop()
            return 0
        schedule = traffic.generate(cell.mix, args.seed, args.seconds)

        records, t0, late = asyncio.run(drive(
            cell, schedule, args.seconds, ready["port"], on_mark,
            bool(args.trace)))
        child.send(cmd="report")
        with open(child.expect("report", 300.0)["path"]) as f:
            report = json.load(f)
    except (RuntimeError, KeyboardInterrupt) as e:
        print(f"benchmark: FAILED — {e}", file=sys.stderr, flush=True)
        child.stop()
        return 3 if child.proc.returncode == 3 else 1
    rc = child.stop()

    vals, counts = end_to_end(records, t0, args.seconds)
    vals["setup_s"] = setup_s
    info(f"[requests] {json.dumps(counts)}")
    if late:
        info(f"[generator] lateness ms: max {max(late) * 1e3:.2f} "
             f"p99 {percentile(late, 99) * 1e3:.2f} over {len(late)} sends")
    info("[client] " + json.dumps({k: round(v, 3) for k, v in vals.items()}))
    info("[steadiness] " + json.dumps({
        **steadiness(records, t0, args.seconds),
        "server_cpu_s": round(report["cpu_s_in_window"], 2),
        "server_gc_pauses": report["gc_pauses_in_window"]}))

    # -- correct: every number compared beside its limit -------------------
    ck, limit = checked["check"], cell.bench["correct"]["limit"]
    compared = [("logits_rel_rms_pooled", ck["pooled"], limit),
                ("compilations_in_window", report["compiles_in_window"], 0),
                ("server_exit_code", rc, 0)]
    if "experts_used" in ck:
        need = int(cell.hf["num_experts"]) // 2
        compared.append(("experts_reached_min", -ck["experts_used"], -need))
    correct = True
    for name, got, lim in compared:
        ok = lim is not None and got <= lim
        correct &= ok
        info(f"[correct] {name}: {got} limit {lim} -> "
             f"{'ok' if ok else 'NOT ok'}")
    info(f"[check points] worst {ck['worst']} " + json.dumps(ck["points"])
         + " modes "
         + json.dumps(ck["modes"]))
    if "control" in ck:
        info("[control] " + json.dumps(ck["control"]))

    units = {e["name"]: e["unit"]
             for e in cell.end_to_end + cell.per_layer}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": report["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": counts["attempted"],
            "failed": counts["failed"]}
    if args.trace:
        ctx = Context(cell, records, t0, args.seconds, report, device)
        metrics = {}
        for e in cell.per_layer:
            value = manifest.metric_reader(cell.bench_dir, e["name"])(ctx)
            if value is not None:
                metrics[e["name"]] = {"value": value, "unit": e["unit"]}
        dev["busy_s"], dev["window_s"] = ctx.trace.busy_s(), \
            ctx.trace.window_s
        line["breakdown"] = breakdown(ctx)
        with open(os.path.join(out_dir, "trace_layout.json"), "w") as f:
            json.dump(report["trace"]["layout"], f, indent=1)
    else:
        metrics = {e["name"]: {"value": vals[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end if e["name"] in vals}
    line["metrics"], line["device"] = metrics, dev
    if args.rehearse:
        line["rehearsal"] = True
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump({"line": line, "client": vals, "counts": counts,
                   "check": ck, "phases": ready["phases"]}, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
