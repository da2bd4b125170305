#!/usr/bin/env python3
"""The one child that holds the chip(s): `cake-tpu serve`, built as
`cli.cmd_serve` builds it (ApiState -> serve.maybe_engine ->
serve.admission.get_plane -> api.serve), around a TextModel whose weights
the benchmark made on the device from --seed and a synthetic byte-level
tokenizer of the published vocabulary size. No checkpoint is written or
read: the load path stays chip_smoke.py's to prove.

Before it says ready it (1) checks the served programs' logits against the
plain reference (check.py), (2) warms every program this cell's traffic
can reach and nothing else. Then it serves; run.py talks to it over HTTP
for the traffic and over stdin/stdout (one JSON object per line) for the
window marks, the profiler and the final report.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import manifest  # noqa: E402
import traffic  # noqa: E402

T_START = time.perf_counter()


def say(obj: dict):
    """One JSON line to run.py (stdout is the control channel)."""
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def log(msg: str):
    print(f"[launch {time.perf_counter() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


class CompileMeter:
    """Every XLA compilation (cache retrievals too) with the perf_counter
    instant it ended, via jax.monitoring."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.events.append((time.perf_counter(), float(duration)))

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)

    def total(self) -> dict:
        durations = sorted((d for _, d in self.events), reverse=True)
        return {"compilations": len(durations),
                "compile_s": round(sum(durations), 3),
                "longest_s": [round(d, 2) for d in durations[:4]]}


class GcMeter:
    """Every garbage collection of 10 ms or more, with the perf_counter
    instant it began: a pause of the whole process, the scheduler too."""

    def __init__(self):
        import gc
        self.pauses: list[tuple[float, float, int]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        elif now - self._t0 >= 0.010:
            self.pauses.append((self._t0, now - self._t0,
                                info["generation"]))

    def between(self, t0: float, t1: float) -> list:
        return [[round(t - t0, 3), round(d * 1e3, 1), g]
                for t, d, g in self.pauses if t0 <= t <= t1]


def bytes_to_unicode() -> list[str]:
    """The byte-level BPE alphabet (GPT-2): 256 printable stand-ins."""
    bs = (list(range(ord("!"), ord("~") + 1))
          + list(range(0xA1, 0xAC + 1)) + list(range(0xAE, 0xFF + 1)))
    cs, n = bs[:], 0
    for b in range(256):
        if b not in bs:
            bs.append(b)
            cs.append(256 + n)
            n += 1
    table = dict(zip(bs, map(chr, cs)))
    return [table[b] for b in range(256)]


def write_tokenizer(path: str, vocab_size: int):
    """Byte-level BPE without merges: ids 0..255 are the bytes (one token
    per ASCII character), the ids above are fillers that decode to
    non-empty ASCII, since a random model emits any id."""
    vocab = {ch: i for i, ch in enumerate(bytes_to_unicode())}
    for i in range(256, vocab_size):
        vocab[f"<{i:x}>"] = i
    bl = {"type": "ByteLevel", "add_prefix_space": False,
          "trim_offsets": True, "use_regex": False}
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "tokenizer.json"), "w") as f:
        json.dump({"version": "1.0", "truncation": None, "padding": None,
                   "added_tokens": [], "normalizer": None,
                   "pre_tokenizer": bl, "post_processor": None,
                   "decoder": bl,
                   "model": {"type": "BPE", "dropout": None,
                             "unk_token": None,
                             "continuing_subword_prefix": None,
                             "end_of_word_suffix": None, "fuse_unk": False,
                             "byte_fallback": False, "ignore_merges": False,
                             "vocab": vocab, "merges": []}},
                  f, ensure_ascii=False)


def reachable_prefill_shapes(mix: dict, chunk: int, bucket_for) -> set:
    """(bucket, first-chunk?) of every prefill dispatch the mix can cause:
    full chunks, and the partial last chunk of every prompt length."""
    shared = int(mix.get("shared_prefix_tokens", 0))
    u = mix["unique_tokens"]
    lo = shared + max(int(u.get("min", u.get("value", 0))),
                      traffic.min_unique_tokens(mix))
    hi = shared + int(u.get("max", u.get("value", 0)))
    shapes = set()
    for n in range(lo, hi + 1):
        if n >= chunk:
            shapes.add((chunk, True))
            if n >= 2 * chunk:
                shapes.add((chunk, False))
        if n % chunk:
            shapes.add((bucket_for(n % chunk, chunk), n < chunk))
    return shapes


def warm_up(engine, model, tokenizer, cell, seed: int, log) -> dict:
    """Every program the window can dispatch, through the engine itself."""
    import numpy as np

    from cake_tpu.models.common.text_model import bucket_for, chat_prompt_ids
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.slots import slot_buckets

    mix, chunk, slots = cell.mix, engine.chunk, engine.slots
    s = mix["sampling"]
    scfg = SamplingConfig(temperature=s["temperature"], top_p=s["top_p"])
    rng = np.random.default_rng([seed, 0xBEEF])
    vocab = model.cfg.vocab_size

    def run(prompts: list, max_new: int):
        reqs = [engine.submit(list(map(int, p)), max_new_tokens=max_new,
                              sampling=scfg) for p in prompts]
        for r in reqs:
            if not r.wait(timeout=900.0) or "error" in r.result:
                raise SystemExit(f"warm-up request failed: "
                                 f"{r.result.get('error')!r}")

    shapes = sorted(reachable_prefill_shapes(mix, chunk, bucket_for))
    prompts = [rng.integers(0, vocab, b if first else chunk + b)
               for b, first in shapes]
    run(prompts, 2)
    log(f"warm-up: prefill shapes (bucket, fresh) {shapes}")
    # the slot buckets: `slots` callers at once pass through 1, 2, 4 ...
    # occupied rows as their prefills end one iteration apart
    probe = traffic.generate(mix, seed, 8.0)
    texts = [chat_prompt_ids(tokenizer, traffic.messages(r, mix))
             for r in sorted(probe, key=lambda r: r.prompt_tokens)[:slots]]
    while len(texts) < slots:
        texts += texts[:slots - len(texts)]
    run(texts, 3 * slots)
    want = len(slot_buckets(slots))
    have = getattr(model._decode_slots, "_cache_size", lambda: want)()
    if have < want:
        for nb in slot_buckets(slots):      # one rung at a time
            run(texts[:nb], 6)
        have = model._decode_slots._cache_size()
    log(f"warm-up: decode programs {have} of {want} slot buckets")
    if int(mix.get("shared_prefix_tokens", 0)) >= chunk:
        run(texts[:2], 2)                   # the shared prefix: stored, hit
    return {"prefill_shapes": [list(x) for x in shapes],
            "decode_programs": int(have)}


def control_loop(engine, meter, gc_meter, args, cell, device, phases):
    """Reads run.py's commands on stdin; answers on stdout."""
    import jax

    from cake_tpu.obs import RECORDER, TIMELINES

    base = f"http://127.0.0.1:{args.port}"
    for _ in range(600):
        try:
            urllib.request.urlopen(base + "/health", timeout=2).read()
            break
        except OSError:
            time.sleep(0.05)
    # one request through the HTTP path: handler, tokenizer, SSE writer
    req0 = traffic.generate(cell.mix, args.seed, 8.0)[0]
    body = traffic.body(req0, cell.mix)
    body["max_tokens"] = 4
    urllib.request.urlopen(urllib.request.Request(
        base + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}), timeout=600).read()
    phases["http_ready"] = time.perf_counter() - T_START
    say({"event": "ready", "port": args.port, "device": device, "phases": phases,
         "compile": meter.total()})
    marks: dict = {}
    trace_dir = os.path.join(args.out, "profile")
    for line in sys.stdin:
        cmd = json.loads(line)
        name = cmd["cmd"]
        if name == "mark":
            marks[cmd["name"]] = time.perf_counter()
            marks[cmd["name"] + "_cpu"] = time.process_time()
            if cmd["name"] == "window_start" and args.trace:
                RECORDER.clear()
            say({"event": "marked", "name": cmd["name"]})
        elif name == "trace_start":
            jax.profiler.start_trace(trace_dir)
            marks["trace_start_ns"] = time.perf_counter_ns()
            marks["sync_ns"] = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("bench.sync"):
                time.sleep(0.002)
            say({"event": "tracing"})
        elif name == "trace_stop":
            marks["trace_stop_ns"] = time.perf_counter_ns()
            jax.profiler.stop_trace()
            say({"event": "traced"})
        elif name == "report":
            t0, t1 = marks["window_start"], marks["window_end"]
            peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in jax.local_devices()]
            rep = {"compiles_in_window": meter.between(t0, t1),
                   "compile": meter.total(),
                   "memory_peak_bytes": max([p for p in peak if p] or [0]),
                   "window_perf": [t0, t1],
                   # the whole process's CPU seconds, and its collector's
                   # pauses [s from the window's start, ms, generation]
                   "cpu_s_in_window": marks["window_end_cpu"]
                   - marks["window_start_cpu"],
                   "gc_pauses_in_window": gc_meter.between(t0, t1),
                   "engine": engine.health()}
            if args.trace:
                import trace_reduce
                rep["spans"] = [e for e in RECORDER.events()
                                if e.get("ph") == "X"]
                rep["flight"] = [r for r in engine.flight.snapshot()
                                 if t0 <= r["t"] <= t1]
                rep["timelines"] = {rid: TIMELINES.get(rid)
                                    for rid in TIMELINES.ids()}
                rep["trace"] = trace_reduce.compact(
                    trace_reduce.find_xplane(trace_dir), marks["sync_ns"],
                    marks["trace_start_ns"], marks["trace_stop_ns"])
            path = os.path.join(args.out, "child_report.json")
            with open(path, "w") as f:
                json.dump(rep, f)
            say({"event": "report", "path": path})
        else:
            say({"event": "error", "what": f"unknown command {name!r}"})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--control", default="",
                    help="calibration only: also run the control")
    args = ap.parse_args()
    cell = manifest.Cell(args.workload)
    bm, hf = cell.bench, cell.hf
    os.makedirs(args.out, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.update(bm["engine_env"])
    if args.trace:              # rings large enough for a whole window
        os.environ["CAKE_FLIGHT_RECORDER"] = "65536"
        os.environ["CAKE_TRACE_REQUESTS"] = "4096"
        os.environ["CAKE_TRACE_EVENTS"] = "262144"
    sys.path.insert(0, manifest.ROOT)
    from cake_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    # every program into the cache, the small ones too (JAX's default keeps
    # only compiles of a second or more): set-up is paid by every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_log_compiles", True)     # names, in server.log
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if (device["platform"] != "tpu" and not args.rehearse) \
            or device["count"] < cell.chips:
        log(f"no accelerator for this cell: JAX reports {device}, the cell "
            f"asks for {cell.chips} TPU chip(s)")
        return 3
    meter = CompileMeter(jax)
    gc_meter = GcMeter()
    phases = {"imports": time.perf_counter() - T_START}
    log(f"device {device}; compile cache at {cache_dir}")

    import jax.numpy as jnp

    import check
    import weights as weights_mod
    from cake_tpu.api import ApiState, serve
    from cake_tpu.models.common.config import config_from_hf_dict
    from cake_tpu.models.common.layers import make_rope
    from cake_tpu.models.common.text_model import TextModel
    from cake_tpu.runtime import CakeTokenizer
    from cake_tpu.serve import maybe_engine
    from cake_tpu.serve.admission import get_plane

    cfg = config_from_hf_dict(hf)
    mesh = weights_mod.cell_mesh(cell)
    reference = importlib.import_module(f"reference.{bm['family']}")
    w = weights_mod.make_weights(reference, hf, args.seed, jnp.bfloat16,
                                 mesh=mesh)
    jax.block_until_ready(w)
    q = w["layers"][0]["self_attn"]["q_proj"]["weight"]
    log(f"mesh {mesh}; q_proj sharded "
        f"{getattr(q.sharding, 'spec', 'on one device')}")
    phases["weights"] = time.perf_counter() - T_START
    tok_dir = os.path.join(args.out, "tokenizer")
    write_tokenizer(tok_dir, hf["vocab_size"])
    tokenizer = CakeTokenizer(tok_dir)
    model = TextModel(cfg, {**w, "rope": make_rope(cfg)},
                      tokenizer=tokenizer, dtype=jnp.bfloat16,
                      seed=args.seed % (2 ** 31),
                      max_cache_len=int(bm["max_cache_len"]), mesh=mesh)
    phases["model"] = time.perf_counter() - T_START

    # -- the output check: served programs vs the plain reference ---------
    env = bm["engine_env"]
    slots, ctx = int(env["CAKE_SERVE_SLOTS"]), int(env["CAKE_SERVE_CTX"])
    chunk = int(env["CAKE_PREFILL_CHUNK"])
    ck = cell.mix["check"]
    seqs = check.check_ids(args.seed, hf["vocab_size"], ck["prompt_tokens"])
    from cake_tpu.models.common.text_model import bucket_for
    served = check.served_logits(
        model, slots, ctx, chunk, seqs, ck["decode_steps"],
        cell.mix["sampling"],
        also_load=sorted(reachable_prefill_shapes(cell.mix, chunk,
                                                  bucket_for)))
    phases["check_served"] = time.perf_counter() - T_START
    result = {**check.compare(reference, hf, w, served),
              "modes": [o["modes"] for o in served]}
    if hasattr(reference, "experts_used"):
        result["experts_used"] = reference.experts_used(
            hf, w, served[-1]["ids"])
    if args.control:
        result["control"] = check.control(reference, hf, w, served,
                                          args.control)
    del served
    phases["check_reference"] = time.perf_counter() - T_START
    log(f"check: pooled {result['pooled']:.5f}, worst point "
        f"{result['worst']:.5f} of {len(result['points'])}")

    # -- the server, as cli.cmd_serve builds it ----------------------------
    state = ApiState(model=model, tokenizer=tokenizer,
                     model_id=cell.config_entry["name"])
    state.engine = engine = maybe_engine(model)
    get_plane(state)
    phases["engine"] = time.perf_counter() - T_START
    warmed = warm_up(engine, model, tokenizer, cell, args.seed, log)
    phases["warm_up"] = time.perf_counter() - T_START
    say({"event": "checked", "check": result, "warmed": warmed,
         "engine": {"slots": engine.slots, "ctx": engine.ctx,
                    "chunk": engine.chunk,
                    "mesh": dict(mesh.shape) if mesh else None}})
    if args.trace:
        from cake_tpu.obs import RECORDER
        RECORDER.enable()
    threading.Thread(
        target=control_loop, daemon=True,
        args=(engine, meter, gc_meter, args, cell, device, phases)
    ).start()
    serve(state, host="127.0.0.1", port=args.port)
    return 0


if __name__ == "__main__":
    sys.exit(main())
