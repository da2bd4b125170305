"""Micro-benchmark suite (ref: cake-core/benches/ — 23 divan modules).

Times the hot host-side and device-side primitives; prints one JSON object
per benchmark. Run: python benches/bench_micro.py [--filter NAME] [--cpu]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, warmup=3, iters=20) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def bench_crc32():
    from cake_tpu.cluster.proto import crc32
    data = np.random.default_rng(0).integers(0, 256, 8 << 20,
                                             dtype=np.uint32).astype(np.uint8).tobytes()
    dt = timeit(lambda: crc32(data))
    return {"gb_per_s": round(len(data) / dt / 1e9, 2)}


def bench_frame_roundtrip():
    from cake_tpu.cluster import proto
    x = np.random.default_rng(0).standard_normal((1, 64, 2048)).astype(np.float32)
    msg = proto.forward(x, 0, None)

    def run():
        frame = proto.encode_frame(msg)
        proto.decode_payload(frame[8:])
    dt = timeit(run)
    return {"ms": round(dt * 1000, 3), "mb": round(x.nbytes / 1e6, 1)}


def bench_auth():
    import asyncio

    from cake_tpu.cluster.auth import (authenticate_as_master,
                                       authenticate_as_worker)

    async def once():
        done = asyncio.Event()

        async def on_conn(r, w):
            await authenticate_as_worker(r, w, "k")
            w.close()
            done.set()
        server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        r, w = await asyncio.open_connection(
            "127.0.0.1", server.sockets[0].getsockname()[1])
        await authenticate_as_master(r, w, "k")
        await done.wait()
        w.close()
        server.close()
    dt = timeit(lambda: asyncio.run(once()), warmup=2, iters=10)
    return {"ms": round(dt * 1000, 2)}


def bench_pread():
    import os
    import tempfile

    from cake_tpu.utils import cakekit
    with tempfile.NamedTemporaryFile(delete=False) as f:
        f.write(os.urandom(32 << 20))
        path = f.name
    try:
        dt = timeit(lambda: cakekit.pread(path, 0, 32 << 20))
        return {"gb_per_s": round((32 << 20) / dt / 1e9, 2),
                "native": cakekit.available()}
    finally:
        os.unlink(path)


def bench_decode_step():
    import jax
    import jax.numpy as jnp

    from cake_tpu.models import TextModel, tiny_config
    from cake_tpu.ops.sampling import SamplingConfig
    m = TextModel(tiny_config("qwen3"), dtype=jnp.float32, max_cache_len=128)
    m.generate([1, 2, 3], max_new_tokens=8, chunk=8,
               sampling=SamplingConfig())          # compile
    dt = timeit(lambda: m.generate([1, 2, 3], max_new_tokens=32, chunk=32,
                                   sampling=SamplingConfig()),
                warmup=1, iters=5)
    return {"tiny_tok_per_s": round(32 / dt, 1)}


def bench_flash_attention():
    """The COMPILED Pallas kernel vs the XLA einsum path on a prefill-sized
    problem: parity and timing. TPU only — the kernel is never interpreted
    here (tests/test_flash.py is the interpret-mode coverage)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.ops.attention import causal_sdpa
    from cake_tpu.ops.flash import flash_attention

    if jax.default_backend() != "tpu":
        return {"skipped": "the compiled kernel needs a TPU"}
    b, s, hq, hkv, d = 1, 1024, 16, 8, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)

    flash = jax.jit(flash_attention)
    ref = jax.jit(causal_sdpa)
    got = np.asarray(flash(q, k, v), np.float32)
    want = np.asarray(ref(q, k, v), np.float32)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
    return {"seq": s,
            "parity_max_err": round(float(np.max(np.abs(got - want))), 5),
            "flash_ms": round(timeit(
                lambda: flash(q, k, v).block_until_ready()) * 1e3, 3),
            "xla_ms": round(timeit(
                lambda: ref(q, k, v).block_until_ready()) * 1e3, 3)}


def bench_moe_dispatch():
    """Ragged segment-GEMM dispatch vs the dense all-experts combine on a
    prefill-sized 128-expert problem (the k/E FLOP claim measured on
    hardware — ref: qwen3_moe/moe.rs top-8 over 128 experts; on CPU the
    ragged op densifies in lowering, so only parity is reported there).
    Timed with a host fetch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.ops.moe import combine_weights, moe_ffn, router_topk

    on_tpu = jax.default_backend() == "tpu"
    e, k = (128, 8)
    t, i, h = (1024, 768, 2048) if on_tpu else (64, 16, 32)
    rng = np.random.default_rng(0)
    router = jnp.asarray(rng.normal(0, .3, (e, h)), jnp.bfloat16)
    gp = jnp.asarray(rng.normal(0, .02, (e, i, h)), jnp.bfloat16)
    up = jnp.asarray(rng.normal(0, .02, (e, i, h)), jnp.bfloat16)
    dp = jnp.asarray(rng.normal(0, .02, (e, h, i)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(0, 1, (t, h)), jnp.bfloat16)

    def dense(x):
        logits = jnp.einsum("th,eh->te", x, router,
                            preferred_element_type=jnp.float32)
        w, idx = router_topk(logits, k, True, "softmax")
        w_te = combine_weights(w, idx, e).astype(x.dtype)
        a = jax.nn.silu(jnp.einsum("th,eih->tei", x, gp)) \
            * jnp.einsum("th,eih->tei", x, up)
        return jnp.einsum("te,teh->th", w_te,
                          jnp.einsum("tei,ehi->teh", a, dp))

    ragged = jax.jit(lambda x: moe_ffn(x, router, gp, up, dp, k, True))
    jdense = jax.jit(dense)
    got = np.asarray(ragged(x), np.float32)
    want = np.asarray(jdense(x), np.float32)
    err = float(np.max(np.abs(got - want)))
    out = {"tokens": t, "experts": e,
           "topk": k, "parity_max_err": round(err, 4)}
    if on_tpu:
        out["ragged_ms"] = round(timeit(
            lambda: np.asarray(ragged(x)), warmup=2, iters=5) * 1e3, 2)
        out["dense_ms"] = round(timeit(
            lambda: np.asarray(jdense(x)), warmup=2, iters=5) * 1e3, 2)
        out["speedup"] = round(out["dense_ms"] / max(out["ragged_ms"], 1e-9),
                               2)
    return out


def bench_moe_crossover():
    """Ragged-vs-dense crossover sweep: the token count where the sorted
    segment-GEMM dispatch starts beating the dense all-experts combine is
    what ops/moe.RAGGED_MIN_TOKENS should be set to (VERDICT r4 item 4:
    32 was a guess, measure it). TPU-only (the ragged op densifies in CPU
    lowering, so a CPU sweep measures nothing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.ops import moe as moe_mod
    from cake_tpu.ops.moe import combine_weights, moe_ffn, router_topk

    if jax.default_backend() != "tpu":
        return {"skipped": "crossover is only meaningful on TPU"}

    e, k, i, h = 128, 8, 768, 2048
    rng = np.random.default_rng(0)
    router = jnp.asarray(rng.normal(0, .3, (e, h)), jnp.bfloat16)
    gp = jnp.asarray(rng.normal(0, .02, (e, i, h)), jnp.bfloat16)
    up = jnp.asarray(rng.normal(0, .02, (e, i, h)), jnp.bfloat16)
    dp = jnp.asarray(rng.normal(0, .02, (e, h, i)), jnp.bfloat16)

    def dense(x):
        logits = jnp.einsum("th,eh->te", x, router,
                            preferred_element_type=jnp.float32)
        w, idx = router_topk(logits, k, True, "softmax")
        w_te = combine_weights(w, idx, e).astype(x.dtype)
        a = jax.nn.silu(jnp.einsum("th,eih->tei", x, gp)) \
            * jnp.einsum("th,eih->tei", x, up)
        return jnp.einsum("te,teh->th", w_te,
                          jnp.einsum("tei,ehi->teh", a, dp))

    # force both paths regardless of the RAGGED_MIN_TOKENS gate
    def ragged_full(x):
        logits = jnp.einsum("th,eh->te", x, router,
                            preferred_element_type=jnp.float32)
        w, idx = router_topk(logits, k, True, "softmax")
        return moe_mod._moe_ragged(x, w, idx, gp, up, dp, "silu")

    ragged = jax.jit(ragged_full)
    jdense = jax.jit(dense)
    rows = []
    crossover = None
    for t in (8, 16, 32, 64, 128, 256, 512):
        x = jnp.asarray(rng.normal(0, 1, (t, h)), jnp.bfloat16)
        r_ms = timeit(lambda: np.asarray(ragged(x)), warmup=2, iters=5) * 1e3
        d_ms = timeit(lambda: np.asarray(jdense(x)), warmup=2, iters=5) * 1e3
        rows.append({"tokens": t, "ragged_ms": round(r_ms, 3),
                     "dense_ms": round(d_ms, 3)})
        if crossover is None and r_ms < d_ms:
            crossover = t
    return {"experts": e, "topk": k, "sweep": rows,
            "crossover_tokens": crossover,
            "current_gate": moe_mod.RAGGED_MIN_TOKENS}


def bench_sampling():
    import jax
    import jax.numpy as jnp

    from cake_tpu.ops.sampling import SamplingConfig, sample
    logits = jax.random.normal(jax.random.PRNGKey(0), (151936,))
    cfg = SamplingConfig(temperature=0.8, top_k=40, top_p=0.9,
                         repeat_penalty=1.1)
    recent = jnp.full((64,), -1, jnp.int32)
    fn = jax.jit(lambda l, k: sample(l, k, cfg, recent))
    k = jax.random.PRNGKey(1)
    fn(logits, k).block_until_ready()
    dt = timeit(lambda: fn(logits, k).block_until_ready())
    return {"us": round(dt * 1e6, 1)}


def bench_gguf_dequant():
    from cake_tpu.utils.gguf import dequant_q4_k
    raw = np.random.default_rng(0).integers(
        0, 256, 144 * 4096, dtype=np.uint32).astype(np.uint8).tobytes()
    n = 256 * 4096
    dt = timeit(lambda: dequant_q4_k(raw, n))
    return {"m_weights_per_s": round(n / dt / 1e6, 1)}


BENCHES = {
    "crc32": bench_crc32,
    "frame_roundtrip": bench_frame_roundtrip,
    "auth_handshake": bench_auth,
    "pread_32mb": bench_pread,
    "decode_tiny": bench_decode_step,
    "flash_attention": bench_flash_attention,
    "moe_dispatch": bench_moe_dispatch,
    "moe_crossover": bench_moe_crossover,
    "sampling_151k_vocab": bench_sampling,
    "gguf_q4k_dequant": bench_gguf_dequant,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--filter", default="")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (host-side primitives and "
                         "parity only; device timings are skipped)")
    args = ap.parse_args()
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from bench import device_fields
    from cake_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    device = device_fields(cpu_ok=args.cpu)   # no TPU without --cpu: error
    failed = 0
    for name, fn in BENCHES.items():
        if args.filter and args.filter not in name:
            continue
        try:
            out = fn()
        except Exception as e:  # keep the suite running; fail the run
            out = {"error": str(e)[:120]}
            failed += 1
        print(json.dumps({"bench": name, **out, **device}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
