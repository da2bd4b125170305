"""The expert layer alone on the chip, every form, every token count: the
sweep behind `ops/moe.py: EXPERT_BLOCK_TOKENS` (PERF.md section 5, PR 55).

usage (through the chip tool; a form a process, T ascending):
    PYTHONPATH=. python3 scripts/moe_sweep.py <form>[,<form>...] [T,T,...]
forms: dense (the four einsums over every token at once) | walk<B> (the
same in blocks of B) | fold (the combine weights multiplied into the
activations, one contraction over E x I) | foldwalk<B> | ragged (the
sort-based dispatch over `lax.ragged_dot_general` that `ops/moe.py` held
until PR 55, kept HERE so its row can be read again) | pkg
(`cake_tpu.ops.moe.moe_ffn` as the tree has it).
Prints one JSON line a (form, T): the median of SWEEP_CALLS (20) calls each
waited for, the time a call of as many queued back to back, the program's
temporaries (`memory_analysis()`), the relative RMS difference to `dense`
on the same inputs (T up to SWEEP_REF_MAX_T, 256) and the rows that came
back all zero. SWEEP_SHAPE = E,k,H,I (default Qwen3-30B-A3B's 128,8,2048,768).
"""
import json
import os
import statistics as st
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

from cake_tpu.ops import moe

E, K, H, I = (int(v) for v in os.environ.get("SWEEP_SHAPE", "128,8,2048,768").split(","))
variants = sys.argv[1].split(",")
Ts = [int(t) for t in sys.argv[2].split(",")] if len(sys.argv) > 2 else [32, 64, 128, 256, 512, 1024, 2048, 4096]
CALLS = int(os.environ.get("SWEEP_CALLS", "20"))
REF_MAX_T = int(os.environ.get("SWEEP_REF_MAX_T", "256"))


def four(x, w_te, gp, up, dp):
    g = jnp.einsum("th,eih->tei", x, gp)
    u = jnp.einsum("th,eih->tei", x, up)
    a = jax.nn.silu(g) * u
    y_e = jnp.einsum("tei,ehi->teh", a, dp)
    return jnp.einsum("te,teh->th", w_te, y_e).astype(x.dtype)


def fold(x, w_te, gp, up, dp):
    g = jnp.einsum("th,eih->tei", x, gp)
    u = jnp.einsum("th,eih->tei", x, up)
    a = jax.nn.silu(g) * u * w_te[:, :, None]
    return jnp.einsum("tei,ehi->th", a, dp).astype(x.dtype)


def walk(body, block):
    def run(x, w_te, gp, up, dp):
        t = x.shape[0]
        if t <= block:
            return body(x, w_te, gp, up, dp)
        nb, rem = divmod(t, block)
        cut = nb * block
        out = lax.map(lambda xw: body(xw[0], xw[1], gp, up, dp),
                      (x[:cut].reshape(nb, block, -1), w_te[:cut].reshape(nb, block, -1))).reshape(cut, -1)
        if rem:
            out = jnp.concatenate([out, body(x[cut:], w_te[cut:], gp, up, dp)])
        return out
    return run


def ragged(x, weights, idx, gp, up, dp):
    from jax.lax import RaggedDotDimensionNumbers, ragged_dot_general
    dn = RaggedDotDimensionNumbers(dot_dimension_numbers=(((1,), (2,)), ((), ())),
                                   lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
    t, h = x.shape
    flat = idx.reshape(t * K)
    order = jnp.argsort(flat)
    tok = order // K
    xs = x[tok]
    gs = jnp.bincount(flat, length=E).astype(jnp.int32)
    g = ragged_dot_general(xs, gp, gs, dn)
    u = ragged_dot_general(xs, up, gs, dn)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    y = ragged_dot_general(a, dp, gs, dn)
    wf = weights.reshape(t * K)[order]
    out = jnp.zeros((t, h), jnp.float32).at[tok].add(y.astype(jnp.float32) * wf[:, None])
    return out.astype(x.dtype)


def make(variant):
    if variant == "pkg":
        return lambda x, r, gp, up, dp: moe.moe_ffn(x, r, gp, up, dp, K, True)

    def f(x, r, gp, up, dp):
        logits = jnp.einsum("th,eh->te", x, r, preferred_element_type=jnp.float32)
        weights, idx = moe.router_topk(logits, K, True)
        if variant == "ragged":
            return ragged(x, weights, idx, gp, up, dp)
        w_te = moe.combine_weights(weights, idx, E).astype(x.dtype)
        if variant == "dense":
            return four(x, w_te, gp, up, dp)
        if variant == "fold":
            return fold(x, w_te, gp, up, dp)
        if variant.startswith("foldwalk"):
            return walk(fold, int(variant[8:]))(x, w_te, gp, up, dp)
        if variant.startswith("walk"):
            return walk(four, int(variant[4:]))(x, w_te, gp, up, dp)
        raise SystemExit(f"unknown variant {variant}")
    return f


dev = jax.devices()[0]
ks = jax.random.split(jax.random.PRNGKey(55), 5)
bf = jnp.bfloat16
r = (jax.random.normal(ks[0], (E, H)) * 0.04).astype(bf)
gp = (jax.random.normal(ks[1], (E, I, H)) * 0.02).astype(bf)
up = (jax.random.normal(ks[2], (E, I, H)) * 0.02).astype(bf)
dp = (jax.random.normal(ks[3], (E, H, I)) * 0.02).astype(bf)
ref_fn = jax.jit(make("dense"))
for variant in variants:
    fn = jax.jit(make(variant))
    for t in Ts:
        x = jax.random.normal(jax.random.fold_in(ks[4], t), (t, H)).astype(bf)
        try:
            comp = fn.lower(x, r, gp, up, dp).compile()
            ma = comp.memory_analysis()
            for _ in range(3):
                comp(x, r, gp, up, dp).block_until_ready()
            ms = []
            for _ in range(CALLS):
                t0 = time.perf_counter()
                comp(x, r, gp, up, dp).block_until_ready()
                ms.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            outs = [comp(x, r, gp, up, dp) for _ in range(CALLS)]
            outs[-1].block_until_ready()
            piped = (time.perf_counter() - t0) * 1e3 / CALLS
            del outs
            got = comp(x, r, gp, up, dp).astype(jnp.float32)
            rel = None
            if variant != "dense" and t <= REF_MAX_T:
                want = ref_fn(x, r, gp, up, dp).astype(jnp.float32)
                rel = float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
            print("SWEEP", json.dumps({"variant": variant, "T": t, "median_ms": round(st.median(ms), 4),
                                       "min_ms": round(min(ms), 4), "pipelined_ms": round(piped, 4), "max_ms": round(max(ms), 4),
                                       "temp_bytes": ma.temp_size_in_bytes,
                                       "rel_rms_to_dense": rel, "zero_rows": int(jnp.sum(jnp.all(got == 0, axis=1))),
                                       "device": dev.device_kind}), flush=True)
        except Exception as ex:  # a form that does not fit or compile is a row of the table too
            print("SWEEP", json.dumps({"variant": variant, "T": t, "error": str(ex)[:300]}), flush=True)
