#!/usr/bin/env python3
"""chip_smoke.py — prove `cake-tpu serve` answers requests on the TPU.

    python chip_smoke.py              # one chip: kernel phase, serve phase
    python chip_smoke.py --chips 4    # the --tp 4 path and its comparison only

Drives the main path once — `python -m cake_tpu.cli serve <dir>` -> API ->
admission -> ServeEngine -> TextModel programs -> Pallas flash kernel — at
the published widths of Qwen3-0.6B (every layer, random weights from
--seed) and checks what comes out by the repo's own means. Exits 0 and
prints, as the LAST line of stdout,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

only when every phase passed on a TPU. Any failure — no accelerator
(JAX_PLATFORMS=cpu), a missing checkout, a phase that failed — exits
non-zero and prints no such line.

One process per chip: this process never initialises a JAX backend. Each
phase is a child that is the only holder of the chip while it lives; they
run one after another, their output goes to .chip_smoke/logs/, and each
leaves its result in .chip_smoke/results/<phase>.json. The device block
of the last line is the server's own /health account. All children share
one compile cache (cake_tpu/utils/compile_cache.py), so the serve phase
reuses what the kernel phase compiled and a second run starts warm.

Phases (children marked *):
  prepare*  writes config.json, one seeded model.safetensors (through
            cake_tpu/utils/safetensors_io.py, whose import chain reaches
            jax — hence a child, pinned to the CPU platform, numpy only)
            and a byte-level tokenizer.json covering the whole vocabulary.
  kernel*   asserts the platform is "tpu"; flash_attention COMPILED (never
            interpret) vs ops.attention in f32 at the model's widths;
            TextModel.prefill logits flash vs CAKE_TPU_FLASH=0, with the
            kernel asserted present in the compiled prefill program.
  serve*    the real CLI server at engine defaults; short / long / stream /
            concurrent / prefix-warm chats over HTTP; /health, /metrics,
            SIGTERM drain.
  --chips 4: ref* (unsharded prefill logits, first device) -> tp4* (the
            same logits from the --tp 4 model, per-device placement) ->
            serve* with --tp 4 (long prompt, per-device memory).

--size tiny is the CPU rehearsal of the same code (tests/test_chip_smoke.py);
it can never print the ok line, because the platform is not "tpu".
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")

# Qwen3-0.6B as published (Qwen/Qwen3-0.6B config.json): every layer
QWEN3_0_6B = dict(
    architectures=["Qwen3ForCausalLM"], vocab_size=151936, hidden_size=1024,
    intermediate_size=3072, num_hidden_layers=28, num_attention_heads=16,
    num_key_value_heads=8, head_dim=128, rms_norm_eps=1e-6,
    rope_theta=1000000.0, max_position_embeddings=40960,
    tie_word_embeddings=True, eos_token_id=151645,
)
# tiny_config("qwen3") widths (scripts/worker_smoke.py writes the same
# dict), with room for a chunked long prompt and a few filler vocab ids
QWEN3_TINY = dict(
    architectures=["Qwen3ForCausalLM"], vocab_size=320, hidden_size=64,
    intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-5,
    rope_theta=10000.0, max_position_embeddings=512,
    tie_word_embeddings=True, eos_token_id=319,
)
SIZES = {
    # engine defaults: 4 slots x 4096 ctx, chunk 256 (the CLI's own
    # --max-cache-len default of 2048 would cap the ctx below its default)
    "full": dict(config=QWEN3_0_6B, max_cache_len=4096, long_chars=640,
                 serve_env={}),
    "tiny": dict(config=QWEN3_TINY, max_cache_len=512, long_chars=150,
                 serve_env={"CAKE_PREFILL_CHUNK": "32"}),
}


class PhaseFailed(Exception):
    pass


def say(msg: str):
    """Phase lines: stdout, always BEFORE the final line."""
    print(msg, flush=True)


def final_line(device: dict) -> str:
    """The contract's last line, from the server's /health device block."""
    return json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": int(device["count"])}})


def model_dir(size: str, seed: int) -> str:
    return os.path.join(WORK, f"model-{size}-seed{seed}")


def long_prompt(size: str) -> str:
    """Deterministic prose; byte-level tokenizer: 1 char = 1 token."""
    words = ("the quick brown fox jumps over the lazy dog while seven "
             "wizards quietly mix a potion of jade and onyx ").split()
    text, i = "", 0
    while len(text) < SIZES[size]["long_chars"]:
        text += f"{words[i % len(words)]}{i % 7} "
        i += 1
    return text.rstrip()


# ---------------------------------------------------------------------------
# child processes
# ---------------------------------------------------------------------------

def _child_env(extra: dict | None = None) -> dict:
    """Children find the package without PYTHONPATH: phase children run
    this file (its directory is the checkout), the server runs `-m` from
    cwd=ROOT."""
    env = dict(os.environ)
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.update(extra or {})
    return env


def _result_path(phase: str) -> str:
    return os.path.join(WORK, "results", f"{phase}.json")


def _log_path(phase: str) -> str:
    return os.path.join(WORK, "logs", f"{phase}.log")


def run_child_phase(phase: str, args, env: dict | None = None,
                    timeout: float = 900.0) -> dict:
    """Run `chip_smoke.py --phase <phase>` to its end; the child is the
    only JAX process alive. Output -> its log; result <- its JSON file."""
    res = _result_path(phase)
    if os.path.exists(res):
        os.unlink(res)
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--size", args.size, "--seed", str(args.seed),
           "--chips", str(args.chips)]
    t0 = time.monotonic()
    with open(_log_path(phase), "w") as log:
        try:
            proc = subprocess.run(cmd, cwd=ROOT, env=_child_env(env),
                                  stdout=log, stderr=subprocess.STDOUT,
                                  timeout=timeout)
        except subprocess.TimeoutExpired:       # run() has killed the child
            raise PhaseFailed(f"{phase}: no end within {timeout:.0f} s:\n"
                              + _tail(_log_path(phase))) from None
    wall = time.monotonic() - t0
    if proc.returncode != 0 or not os.path.exists(res):
        raise PhaseFailed(f"{phase}: child exited {proc.returncode}; "
                          f"tail of {_log_path(phase)}:\n"
                          + _tail(_log_path(phase)))
    with open(res) as f:
        out = json.load(f)
    out["wall_s"] = round(wall, 1)
    return out


def _tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>"


def _write_result(phase: str, result: dict):
    with open(_result_path(phase), "w") as f:
        json.dump(result, f)


# ---------------------------------------------------------------------------
# phase: prepare (child, CPU-pinned, numpy only)
# ---------------------------------------------------------------------------

def _bytes_to_unicode() -> list[str]:
    """The byte-level BPE alphabet (GPT-2): 256 printable stand-ins."""
    bs = (list(range(ord("!"), ord("~") + 1))
          + list(range(ord("\u00a1"), ord("\u00ac") + 1))
          + list(range(ord("\u00ae"), ord("\u00ff") + 1)))
    cs, n = bs[:], 0
    for b in range(256):
        if b not in bs:
            bs.append(b)
            cs.append(256 + n)
            n += 1
    table = dict(zip(bs, map(chr, cs)))
    return [table[b] for b in range(256)]


def _tokenizer_json(vocab_size: int) -> dict:
    """Byte-level BPE, no merges: ids 0..255 are the byte alphabet (exact
    per-token round trip), ids above are filler entries that decode to
    non-empty ASCII — a random full-vocabulary model emits any id."""
    vocab = {ch: i for i, ch in enumerate(_bytes_to_unicode())}
    for i in range(256, vocab_size):
        vocab[f"<{i:x}>"] = i
    bl = {"type": "ByteLevel", "add_prefix_space": False,
          "trim_offsets": True, "use_regex": False}
    return {"version": "1.0", "truncation": None, "padding": None,
            "added_tokens": [], "normalizer": None, "pre_tokenizer": bl,
            "post_processor": None, "decoder": bl,
            "model": {"type": "BPE", "dropout": None, "unk_token": None,
                      "continuing_subword_prefix": None,
                      "end_of_word_suffix": None, "fuse_unk": False,
                      "byte_fallback": False, "ignore_merges": False,
                      "vocab": vocab, "merges": []}}


def phase_prepare(args) -> dict:
    import ml_dtypes
    import numpy as np

    from cake_tpu.utils.safetensors_io import save_safetensors

    cfg = SIZES[args.size]["config"]
    out = model_dir(args.size, args.seed)
    os.makedirs(out, exist_ok=True)
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg["head_dim"]
    sq, skv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    rng = np.random.default_rng(args.seed)

    def w(*shape):
        return (rng.standard_normal(shape, dtype=np.float32) * 0.02
                ).astype(ml_dtypes.bfloat16)

    def ones(n):
        return np.ones((n,), ml_dtypes.bfloat16)

    t = {"model.embed_tokens.weight": w(cfg["vocab_size"], h),
         "model.norm.weight": ones(h)}
    for i in range(cfg["num_hidden_layers"]):
        lp = f"model.layers.{i}"
        t[f"{lp}.input_layernorm.weight"] = ones(h)
        t[f"{lp}.post_attention_layernorm.weight"] = ones(h)
        t[f"{lp}.self_attn.q_proj.weight"] = w(sq, h)
        t[f"{lp}.self_attn.k_proj.weight"] = w(skv, h)
        t[f"{lp}.self_attn.v_proj.weight"] = w(skv, h)
        t[f"{lp}.self_attn.o_proj.weight"] = w(h, sq)
        t[f"{lp}.self_attn.q_norm.weight"] = ones(d)
        t[f"{lp}.self_attn.k_norm.weight"] = ones(d)
        t[f"{lp}.mlp.gate_proj.weight"] = w(inter, h)
        t[f"{lp}.mlp.up_proj.weight"] = w(inter, h)
        t[f"{lp}.mlp.down_proj.weight"] = w(h, inter)
    save_safetensors(os.path.join(out, "model.safetensors"), t)
    with open(os.path.join(out, "config.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(out, "tokenizer.json"), "w") as f:
        json.dump(_tokenizer_json(cfg["vocab_size"]), f, ensure_ascii=False)
    nbytes = sum(a.nbytes for a in t.values())
    return {"passed": True, "model_dir": out, "param_bytes": int(nbytes),
            "tensors": len(t)}


# ---------------------------------------------------------------------------
# in-process JAX children: shared helpers
# ---------------------------------------------------------------------------

class _CompileMeter:
    """Counts XLA compilations (and cache retrievals) and their seconds in
    this process, via jax.monitoring."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1
            self.seconds += float(duration)

    def snapshot(self) -> dict:
        return {"compilations": self.n,
                "compile_s": round(self.seconds, 2)}


def _jax_child_start(args, require_tpu: bool):
    """Every JAX child: place the compile cache, meter compiles, and
    refuse anything but the TPU before doing any work (the kernel phase
    always: the kernel is never interpreted; the others at full size)."""
    from cake_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    meter = _CompileMeter()
    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: platform is {dev.platform!r}, "
                         "not 'tpu' — no accelerator, nothing to prove")
    return jax, meter, cache_dir


def _peak_hbm(jax) -> list[int | None]:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def _load_model(args, mesh_tp: int | None = None, max_cache_len=1024):
    """The checkpoint through the same facade `cli serve` uses."""
    from cake_tpu.runtime import build_text_model
    gen, tok, _, _ = build_text_model(
        model_dir(args.size, args.seed), dtype="bf16",
        max_cache_len=max_cache_len, seed=args.seed, download=False,
        tp=mesh_tp)
    return gen, tok


def _chat_ids(tok, text: str) -> list[int]:
    return tok.encode_chat_prompt(
        tok.apply_chat([{"role": "user", "content": text}]))


def _prefill_logits(jax, model, ids):
    import numpy as np
    logits, _ = model.prefill(model.new_cache(), ids)
    return np.asarray(jax.device_get(logits), np.float32)[0]


def _prefill_program_text(model, n_ids: int) -> str:
    """Optimized HLO of the prefill program `model.prefill` runs for a
    fresh n_ids-token prompt."""
    import jax.numpy as jnp

    from cake_tpu.models.common.text_model import bucket_for
    bkt = bucket_for(n_ids, model.max_cache_len)
    i32 = jnp.asarray(0, jnp.int32)
    return model._prefill.lower(
        model.params, jnp.zeros((1, bkt), jnp.int32), model.new_cache(),
        i32, i32, flash_mode="fresh").compile().as_text()


def _logit_agreement(a, b) -> dict:
    import numpy as np
    scale = float(np.max(np.abs(b)))
    diff = float(np.max(np.abs(a - b)))
    cos = float(np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b)))
    return {"max_abs_diff": diff, "ref_max_abs": scale,
            "rel": diff / max(scale, 1e-30), "cosine": cos,
            "argmax_equal": bool(int(np.argmax(a)) == int(np.argmax(b)))}


# logits of two bf16 programs that differ only in the attention kernel:
# bf16 rounding through every layer, nothing structural
LOGIT_REL_TOL = 0.05
LOGIT_COS_MIN = 0.999


def _check_logits(tag: str, agree: dict):
    if not (agree["rel"] <= LOGIT_REL_TOL
            and agree["cosine"] >= LOGIT_COS_MIN):
        raise SystemExit(f"chip_smoke: {tag} logits disagree: {agree}")


# ---------------------------------------------------------------------------
# phase: kernel (child, holds the chip)
# ---------------------------------------------------------------------------

# max-abs error of a bf16-output kernel against an f32 reference on
# O(1) values: output rounding (2^-9 relative) plus accumulation order
KERNEL_ATOL = 3e-2


KERNEL_CASES = (
    dict(name="fresh512", sq=512, skv=512),
    dict(name="fresh512_valid300", sq=512, skv=512, valid_len=300),
    dict(name="append256over4096", sq=256, skv=4096, q_offset=1024,
         valid_len=200),
    dict(name="windowed512", sq=512, skv=512, window=128),
)


def _run_kernel_case(jax, cfg: dict, case: dict, seed: int) -> float:
    """flash_attention (compiled) vs ops.attention in f32; returns the
    max-abs error over the valid query rows."""
    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.ops.attention import (make_attention_mask,
                                        multi_head_attention)
    from cake_tpu.ops.flash import flash_attention

    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    sq, skv = case["sq"], case["skv"]
    off = case.get("q_offset")
    vl = case.get("valid_len", sq)
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal(s, dtype=np.float32),
                           jnp.bfloat16)
               for s in ((1, sq, hq, d), (1, skv, hkv, d), (1, skv, hkv, d)))
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, valid_len=vl, q_offset=off, window=case.get("window")))(
            q, k, v)

    pos0 = off or 0
    q_pos = pos0 + jnp.arange(sq, dtype=jnp.int32)[None]
    k_idx = jnp.arange(skv, dtype=jnp.int32)
    k_pos = jnp.where(k_idx < pos0 + vl, k_idx, -1)[None]
    mask = make_attention_mask(q_pos, k_pos, window=case.get("window"))
    with jax.default_matmul_precision("highest"):
        want = multi_head_attention(q.astype(jnp.float32),
                                    k.astype(jnp.float32),
                                    v.astype(jnp.float32), mask)
    got = np.asarray(got, np.float32)[:, :vl]
    want = np.asarray(want, np.float32)[:, :vl]
    if not np.all(np.isfinite(got)):
        raise SystemExit(f"chip_smoke: kernel case {case['name']}: "
                         "non-finite output")
    return float(np.max(np.abs(got - want)))


def phase_kernel(args) -> dict:
    jax, meter, cache_dir = _jax_child_start(args, require_tpu=True)
    import numpy as np
    cfg = SIZES[args.size]["config"]
    dev = jax.devices()[0]
    out: dict = {"platform": dev.platform, "device_kind": dev.device_kind,
                 "count": len(jax.devices()), "cache_dir": cache_dir}

    errs = {}
    for case in KERNEL_CASES:
        errs[case["name"]] = _run_kernel_case(jax, cfg, case, args.seed)
    out["kernel_max_abs_err"] = errs
    bad = {k: e for k, e in errs.items() if not e <= KERNEL_ATOL}
    if bad:
        raise SystemExit(f"chip_smoke: kernel error over {KERNEL_ATOL}: "
                         f"{bad}")

    # the model: flash prefill vs the masked path, same checkpoint
    from cake_tpu.utils import cakekit
    out["cakekit_native"] = bool(cakekit.available())
    ids = [int(x) for x in np.random.default_rng(args.seed + 1).integers(
        0, cfg["vocab_size"], 512)]
    model, _ = _load_model(args)
    text = _prefill_program_text(model, len(ids))
    if "tpu_custom_call" not in text:
        raise SystemExit("chip_smoke: the compiled prefill program holds "
                         "no tpu_custom_call — flash gave way to the "
                         "reference path")
    l_flash = _prefill_logits(jax, model, ids)
    del model
    os.environ["CAKE_TPU_FLASH"] = "0"
    masked, _ = _load_model(args)
    if "tpu_custom_call" in _prefill_program_text(masked, len(ids)):
        raise SystemExit("chip_smoke: CAKE_TPU_FLASH=0 still compiled the "
                         "kernel — nothing to compare against")
    l_mask = _prefill_logits(jax, masked, ids)
    if not (np.all(np.isfinite(l_flash)) and l_flash.shape ==
            (cfg["vocab_size"],)):
        raise SystemExit("chip_smoke: prefill logits malformed")
    out["prefill_flash_vs_masked"] = _logit_agreement(l_flash, l_mask)
    _check_logits("flash vs masked prefill", out["prefill_flash_vs_masked"])
    out["prefill_program_has_kernel"] = True
    out.update(meter.snapshot())
    out["peak_hbm_bytes"] = _peak_hbm(jax)
    out["passed"] = True
    return out


# ---------------------------------------------------------------------------
# phases: ref / tp4 (children, --chips 4 only)
# ---------------------------------------------------------------------------

def _ref_logits_path(args) -> str:
    return os.path.join(WORK, "results", f"ref_logits_seed{args.seed}.npy")


def phase_ref(args) -> dict:
    """Unsharded prefill logits of the long chat prompt (no mesh: first
    device only), saved for the tp4 child."""
    jax, meter, _ = _jax_child_start(args, args.size == "full")
    import numpy as np
    model, tok = _load_model(args)
    ids = _chat_ids(tok, long_prompt(args.size))
    logits = _prefill_logits(jax, model, ids)
    np.save(_ref_logits_path(args), logits)
    return {"passed": True, "prompt_tokens": len(ids),
            "devices_visible": len(jax.devices()),
            "peak_hbm_bytes": _peak_hbm(jax), **meter.snapshot()}


def phase_tp4(args) -> dict:
    """The same logits from the --tp 4 model (runtime.build_text_model,
    the CLI's facade), and where the loader put the weights."""
    jax, meter, _ = _jax_child_start(args, args.size == "full")
    import numpy as np
    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke: --chips 4 needs 4 devices, JAX "
                         f"reports {len(jax.devices())}")
    model, tok = _load_model(args, mesh_tp=4)
    # placement, read before any program runs: the peak of every device is
    # what the LOADER put there
    leaves = jax.tree_util.tree_leaves(model.params)
    total = sum(x.nbytes for x in leaves)
    per_dev = {d.id: 0 for d in jax.local_devices()[:4]}
    for x in leaves:
        for s in x.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    after_load = _peak_hbm(jax)[:4]
    out = {"param_bytes_total": int(total),
           "param_bytes_per_device": per_dev,
           "peak_hbm_after_load": after_load}
    if args.size == "full":
        # sharded projections are ~3/4 of the bytes; the embedding table
        # is replicated. No device may ever have held the whole model.
        for dev_id, peak in zip(per_dev, after_load):
            if peak is None or peak >= 0.75 * total:
                raise SystemExit(
                    f"chip_smoke: device {dev_id} peaked at {peak} bytes "
                    f"during load; the whole model is {total} — weights "
                    "landed whole before being sharded")
        spread = max(per_dev.values()) / min(per_dev.values())
        if spread > 1.05:
            raise SystemExit(f"chip_smoke: uneven placement {per_dev}")
    ids = _chat_ids(tok, long_prompt(args.size))
    if "tpu_custom_call" not in _prefill_program_text(model, len(ids)) \
            and args.size == "full":
        raise SystemExit("chip_smoke: the --tp 4 prefill program holds no "
                         "tpu_custom_call")
    logits = _prefill_logits(jax, model, ids)
    ref = np.load(_ref_logits_path(args))
    out["tp4_vs_unsharded"] = _logit_agreement(logits, ref)
    _check_logits("--tp 4 vs unsharded prefill", out["tp4_vs_unsharded"])
    out.update(meter.snapshot())
    out["peak_hbm_bytes"] = _peak_hbm(jax)
    out["passed"] = True
    return out


# ---------------------------------------------------------------------------
# phase: serve (parent drives the real CLI over HTTP; no JAX here)
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body: dict | None = None,
          headers: dict | None = None, timeout: float = 900.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _get_json(url: str, timeout: float = 30.0) -> dict:
    return json.loads(_http("GET", url, timeout=timeout)[1])


def _chat(base: str, content: str, rid: str | None = None,
          max_tokens: int = 16) -> dict:
    """One non-streamed greedy chat; checked for a non-empty completion."""
    status, raw = _http(
        "POST", base + "/v1/chat/completions",
        {"messages": [{"role": "user", "content": content}],
         "max_tokens": max_tokens, "temperature": 0},
        headers={"X-Cake-Request-Id": rid} if rid else None)
    body = json.loads(raw)
    text = body["choices"][0]["message"]["content"]
    if status != 200 or not text or \
            not body["usage"]["completion_tokens"] > 0:
        raise PhaseFailed(f"chat answered {status} with an empty "
                          f"completion: {raw[:400]!r}")
    return body


def _chat_stream(base: str, content: str, max_tokens: int = 16) -> dict:
    """stream:true read to [DONE]; returns the joined content."""
    req = urllib.request.Request(
        base + "/v1/chat/completions", method="POST",
        data=json.dumps({"messages": [{"role": "user", "content": content}],
                         "max_tokens": max_tokens, "temperature": 0,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    parts, chunks, done = [], 0, False
    with urllib.request.urlopen(req, timeout=900.0) as r:
        for line in r:
            line = line.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            ev = json.loads(payload)
            if "error" in ev:
                raise PhaseFailed(f"stream error event: {ev}")
            delta = ev["choices"][0].get("delta", {}).get("content")
            if delta:
                parts.append(delta)
                chunks += 1
    text = "".join(parts)
    if not done or not text:
        raise PhaseFailed(f"stream ended done={done} content={text!r}")
    return {"content": text, "content_chunks": chunks}


def _semantic(body: dict) -> dict:
    """What two runs of one greedy request must agree on (ids, timestamps
    and tokens_per_second differ by construction)."""
    c = body["choices"][0]
    u = body["usage"]
    return {"content": c["message"]["content"],
            "finish_reason": c["finish_reason"],
            "prompt_tokens": u["prompt_tokens"],
            "completion_tokens": u["completion_tokens"]}


def _common_prefix(a: str, b: str) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


# JAX_LOG_COMPILES=1: one line per compilation (or cache retrieval) from
# jax's own stderr handler; the CLI's root handler repeats it in another
# format, which the anchor leaves out
_COMPILED = re.compile(
    r"^WARNING:.*Finished XLA compilation of (\S+) in ([0-9.eE+-]+) sec",
    re.M)


def _compiles_in(log_path: str) -> tuple[int, float]:
    """(count, seconds) of the compilations a server has logged so far."""
    with open(log_path, errors="replace") as f:
        found = _COMPILED.findall(f.read())
    return len(found), round(sum(float(s) for _, s in found), 2)


def _metric_samples(text: str, name: str) -> list[tuple[str, float]]:
    out = []
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            labels, _, value = line[len(name):].rpartition(" ")
            out.append((labels, float(value)))
    return out


def _check_metrics(text: str, n_requests: int) -> dict:
    rebuilds = sum(v for _, v in _metric_samples(
        text, "cake_serve_engine_rebuilds_total"))
    step_failures = sum(v for _, v in _metric_samples(
        text, "cake_serve_step_failures_total"))
    failed_http = {lab: v for lab, v in _metric_samples(
        text, "cake_api_requests_total")
        if v and not re.search(r'status="[23]\d\d"', lab)}
    outcomes = {lab: v for lab, v in _metric_samples(
        text, "cake_serve_e2e_seconds_count") if v}
    not_ok = {lab: v for lab, v in outcomes.items()
              if 'outcome="ok"' not in lab}
    n_ok = sum(v for lab, v in outcomes.items() if 'outcome="ok"' in lab)
    res = {"engine_rebuilds": rebuilds, "step_failures": step_failures,
           "failed_http": failed_http, "requests_ok": n_ok,
           "requests_not_ok": not_ok}
    if rebuilds or step_failures or failed_http or not_ok \
            or n_ok != n_requests:
        raise PhaseFailed(f"/metrics does not show a clean run of "
                          f"{n_requests} requests: {res}")
    return res


def run_serve_phase(args, tp: int | None = None) -> dict:
    """Start `python -m cake_tpu.cli serve`, drive it over HTTP, read its
    own account, SIGTERM it. With tp: the four-chip variant — the long
    prompt only, and per-device memory from /health."""
    spec = SIZES[args.size]
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    log_path = _log_path("serve")
    cmd = [sys.executable, "-m", "cake_tpu.cli", "-v", "serve",
           model_dir(args.size, args.seed), "--port", str(port),
           "--max-cache-len", str(spec["max_cache_len"])]
    if tp:
        cmd += ["--tp", str(tp)]
    env = _child_env({"JAX_LOG_COMPILES": "1", **spec["serve_env"]})
    out: dict = {"cmd": " ".join(cmd[1:])}
    t0 = time.monotonic()
    log = open(log_path, "w")
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                            stderr=subprocess.STDOUT)
    try:
        # -- /health, with a deadline ---------------------------------
        deadline = t0 + 600.0
        while True:
            if proc.poll() is not None:
                raise PhaseFailed(f"server exited {proc.returncode} before "
                                  f"/health:\n{_tail(log_path)}")
            try:
                if _http("GET", base + "/health", timeout=5.0)[0] == 200:
                    break
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() > deadline:
                raise PhaseFailed("no /health within 600 s:\n"
                                  + _tail(log_path))
            time.sleep(0.25)
        out["first_health_s"] = round(time.monotonic() - t0, 1)

        long_text = long_prompt(args.size)
        n_req = 0
        if not tp:
            _chat(base, "hello 1")
            n_req += 1
        cold = _chat(base, long_text, rid="smoke-long-cold")
        n_req += 1
        tl = _get_json(base + "/api/v1/requests/smoke-long-cold")
        chunks = [(e["pos0"], e["tokens"], e.get("attn"))
                  for e in tl.get("events", [])
                  if e["kind"] == "prefill_chunk"]
        out["long_prompt_tokens"] = cold["usage"]["prompt_tokens"]
        out["long_prompt_chunks"] = chunks
        if args.size == "full":
            if cold["usage"]["prompt_tokens"] < 600:
                raise PhaseFailed("long prompt is under 600 tokens")
            attn = {a for _, _, a in chunks}
            if not {"flash-fresh", "flash-append"} <= attn:
                raise PhaseFailed("the long prompt did not take the Pallas "
                                  "kernel in both fresh and append modes: "
                                  f"{chunks}")
        if not tp:
            out["stream"] = _chat_stream(base, "hello 2")
            n_req += 1
            results: list = [None] * 4

            def one(i):
                try:
                    results[i] = _chat(base, f"story {i}")
                except Exception as e:      # re-raised on the main thread
                    results[i] = e
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for r in results:
                if isinstance(r, Exception):
                    raise PhaseFailed(f"concurrent chat failed: {r!r}")
            n_req += 4

        # -- the long chat twice more: prefix-warm, identical ---------
        warm, hits, n_compiled = [], [], []
        for _ in range(2):
            before = _compiles_in(log_path)[0]
            warm.append(_chat(base, long_text))
            n_req += 1
            stats = _get_json(base + "/api/v1/stats")["stats"]
            hits.append(int(stats.get("prefix_hit_tokens", 0)))
            n_compiled.append(_compiles_in(log_path)[0] - before)
        out["prefix_hit_tokens"] = hits
        if not all(h > 0 for h in hits):
            raise PhaseFailed(f"prefix-warm repeats show no prefix hit: "
                              f"{hits}")
        if _semantic(warm[0]) != _semantic(warm[1]):
            raise PhaseFailed("the two prefix-warm greedy repeats differ:\n"
                              f"{_semantic(warm[0])}\n{_semantic(warm[1])}")
        out["warm_repeats_identical"] = True
        c, w = (_semantic(cold)["content"], _semantic(warm[0])["content"])
        # cold took other prefill programs than the warm repeats: reported,
        # not gated (random weights have thin argmax margins)
        out["cold_vs_warm_common_prefix_chars"] = [_common_prefix(c, w),
                                                   len(c), len(w)]
        out["compilations_in_last_repeat"] = n_compiled[-1]

        # -- the server's own account ---------------------------------
        health = _get_json(base + "/health")
        dev = health["device"]
        out["device"] = {k: dev.get(k) for k in
                         ("platform", "device_kind", "count")}
        out["peak_hbm_bytes"] = dev.get("peak_bytes_in_use")
        out["engine"] = {k: health.get("engine", {}).get(k)
                         for k in ("slots", "ctx_len", "prefill_chunk",
                                   "rebuilds")}
        if tp:
            out["devices"] = dev.get("devices")
            _check_spread(out["devices"], tp)
        out["metrics"] = _check_metrics(
            _http("GET", base + "/metrics", timeout=30.0)[1].decode(), n_req)
        out["requests"] = n_req

        # -- SIGTERM: drain line, exit code 0 -------------------------
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120.0)
        with open(log_path, errors="replace") as f:
            drained = "draining serve engine" in f.read()
        if rc != 0 or not drained:
            raise PhaseFailed(f"SIGTERM: exit code {rc}, drain line "
                              f"{'present' if drained else 'absent'}:\n"
                              + _tail(log_path))
        out["compilations"], out["compile_s"] = _compiles_in(log_path)
        out["wall_s"] = round(time.monotonic() - t0, 1)
        out["passed"] = True
        return out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log.close()


def _check_spread(devices: list | None, tp: int):
    """Weights and KV are spread: every chip holds, and has at its peak
    held, about the same bytes — a pool or a model that landed whole on
    the first chip before being sharded shows in that chip's peak."""
    if not devices or len(devices) != tp:
        raise PhaseFailed(f"/health lists {devices!r}, wanted {tp} devices")
    for key in ("bytes_in_use", "peak_bytes_in_use"):
        vals = [d.get(key) for d in devices]
        if not all(vals) or max(vals) / min(vals) > 1.25:
            raise PhaseFailed(f"{key} is not spread over {tp} chips: "
                              f"{vals}")


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _remove_stale_native():
    """A build product outside git must not ride along: cakekit.py
    rebuilds csrc/libcakekit.so from csrc/cakekit.cpp on first import."""
    csrc = os.path.join(ROOT, "csrc")
    if not os.path.isdir(csrc):
        return
    for name in os.listdir(csrc):
        if name == "libcakekit.so" or name.endswith(".tmp"):
            os.unlink(os.path.join(csrc, name))


def run(args) -> dict:
    """All phases for this invocation; returns the device block when
    every phase passed."""
    os.makedirs(os.path.join(WORK, "logs"), exist_ok=True)
    os.makedirs(os.path.join(WORK, "results"), exist_ok=True)
    if args.size == "full":     # the rehearsal shares its checkout with
        _remove_stale_native()  # whatever else runs there (the tests)
    t0 = time.monotonic()
    try:
        return _run_phases(args, t0)
    finally:
        # 1.2 GB of weights, made anew by every run: not left in the tree
        shutil.rmtree(model_dir(args.size, args.seed), ignore_errors=True)


def _run_phases(args, t0: float) -> dict:
    prep = run_child_phase("prepare", args, env={"JAX_PLATFORMS": "cpu"})
    say(f"[prepare] {json.dumps(prep)}")
    if args.chips == 4:
        say(f"[ref] {json.dumps(run_child_phase('ref', args))}")
        say(f"[tp4] {json.dumps(run_child_phase('tp4', args))}")
        serve = run_serve_phase(args, tp=4)
    else:
        kern = run_child_phase("kernel", args)
        say(f"[kernel] {json.dumps(kern)}")
        say(f"[kernel] native checkpoint reader (cakekit): "
            f"{'native core' if kern['cakekit_native'] else 'pure-Python'}")
        serve = run_serve_phase(args)
    say(f"[serve] {json.dumps(serve)}")
    say(f"[serve] compilations in the last prefix-warm repeat (after "
        f"warm-up): {serve['compilations_in_last_repeat']}"
        + ("" if serve["compilations_in_last_repeat"] == 0
           else "  <-- NOT zero"))
    say(f"[total] wall {time.monotonic() - t0:.1f}s")
    return serve["device"]


PHASES = {"prepare": phase_prepare, "kernel": phase_kernel,
          "ref": phase_ref, "tp4": phase_tp4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--size", choices=sorted(SIZES), default="full")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", choices=sorted(PHASES), default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:                      # a child: one phase, result to disk
        _write_result(args.phase, PHASES[args.phase](args))
        return 0
    try:
        device = run(args)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr, flush=True)
        return 1
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: FAILED — the server reports {device}, wanted "
              f"platform 'tpu' x {args.chips}", file=sys.stderr, flush=True)
        return 1
    # the script's final act: every child has exited
    print(final_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
