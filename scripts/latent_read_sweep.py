"""The latents' read alone on the chip, every candidate body of a decode
step, every block length: the sweep behind `ops/latent_attention.py`'s
decode body (PERF.md section 5, item 12; PR 63).

usage (through the chip tool):
    PYTHONPATH=. python3 scripts/latent_read_sweep.py [<body>[,<body>...]] [H,H,...]
bodies: pkg (`cake_tpu.ops.latent_attention.latent_attention` as the tree
has it) | pkg@<step> (the same with `DECODE_STEP_K` set to <step>) | chunk
(the tree's kernel at one full chunk: 256 queries behind 20.5 k) |
rows<step>[x<parts>][j][m][s] (the heads as the MXU's streamed rows, the
order the chunk keeps: a grid step of <step> latents as <parts> chains,
`d` the pipeline alone and `x` the two products alone (no result: what
bounds a body),
`j` one joint softmax over the step where the default is an independent
chain a part merged at the end, `m` the whole mask on every block where
the default masks a row's last visible block alone, `s` the scale on the
queries once a row where the default scales every block's scores) |
keys<step>[x<parts>][m][s]-<pv> (the LATENTS streamed, s^T = kv @ q^T, the
softmax down the sublanes; <pv> the weighted sum: dot0 = kv_v^T @ p^T
contracting dim 0 of both, tr = p^T transposed on the XLU then p @ kv_v,
eye = p^T transposed on the MXU by an identity). All of them have ONE grid
step a key step, as `ops/latent_attention.py` had until PR 63; the tree's
body loops over a row's key steps itself. The bodies that
`ops/latent_attention.py` did not take are kept HERE so their rows can be
read again.
Prints one JSON line a (body, H): the median over SWEEP_CALLS (30) calls
queued back to back, us a 512 latents walked, GB/s of 640-lane rows, the
share of `benchmark/kernels/latent_read.py`'s needed work (the larger of
operations over 197 TFLOP/s and bytes over 819 GB/s, over the time) and the
largest difference to XLA's masked read on SWEEP_CHECK_ROWS (2) rows.
SWEEP_POOL = rows,ctx (default the cells' 32,24576); frontiers are drawn
20.5-21.5 k at H 128 (`longdoc`) and 17-21 k at H 32 (`longreason`).
"""
import functools
import json
import os
import re
import statistics as st
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
from kernels import latent_read as needed  # noqa: E402

from cake_tpu.ops import latent_attention as la  # noqa: E402
from cake_tpu.ops.attention import NEG_INF  # noqa: E402

ROWS, CTX = (int(v) for v in os.environ.get("SWEEP_POOL", "32,24576").split(","))
CALLS = int(os.environ.get("SWEEP_CALLS", "30"))
CHECK_ROWS = int(os.environ.get("SWEEP_CHECK_ROWS", "2"))
# the CPU's rehearsal at a tiny pool: the bodies interpreted
INTERPRET = bool(os.environ.get("SWEEP_INTERPRET"))
D, DV, RANK, ROPE = 640, 512, 512, 64
SCALE = 0.1147
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
FRONTIERS = {128: (20500, 21500), 32: (17000, 21000)}
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _body(pos0_ref, limit_ref, q_ref, kv_ref, pos_ref, o_ref, m_ref, l_ref,
          acc_ref, *, step, parts, dv, n_k, order, pv, joint, mask_all,
          prescale, only=None):
    """One (row, key step) grid step of a decode step's read: q_ref [H, D],
    kv_ref [step, D], pos_ref [1, step]. rows order: m/l [H, 1], acc
    [H, dv]. keys order: m/l [1, H]; acc [dv, H] (dot0) or [H, dv]."""
    b, j = pl.program_id(0), pl.program_id(1)
    h = q_ref.shape[0]
    sub = step // parts
    q_pos = pos0_ref[b]
    end = jnp.minimum(limit_ref[b], q_pos + 1)
    n_vis = (jnp.maximum(end, 0) + step - 1) // step
    keys = order == "keys"
    red = 0 if keys else -1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def to_rows(x):
        """[1, H] lane vector -> [H, 1]."""
        return jnp.broadcast_to(x, (128, h)).T[:h, :1] if h == 128 else \
            jnp.broadcast_to(jnp.pad(x, ((0, 0), (0, 128 - h))),
                             (128, 128)).T[:h, :1]

    def walk(masked):
        if only == "dma":
            # the pipeline alone: the block arrives and one tile of it is
            # touched
            acc_ref[:8, :128] += kv_ref[:8, :128].astype(jnp.float32)
            return
        if only == "mxu":
            # the two products alone, no softmax between them
            acc = acc_ref[...]
            for c in range(parts):
                kv = kv_ref[c * sub:(c + 1) * sub, :]
                s = jax.lax.dot_general(q_ref[...], kv, _NT,
                                        preferred_element_type=jnp.float32)
                acc = acc + jnp.dot(s.astype(kv.dtype), kv[:, :dv],
                                    preferred_element_type=jnp.float32)
            acc_ref[...] = acc
            return
        q = q_ref[...]
        if prescale:
            q = (q.astype(jnp.float32) * SCALE).astype(q.dtype)
        ss, vis = [], []
        for c in range(parts):
            kv = kv_ref[c * sub:(c + 1) * sub, :]
            if keys:
                s = jax.lax.dot_general(kv, q, _NT,
                                        preferred_element_type=jnp.float32)
            else:
                s = jax.lax.dot_general(q, kv, _NT,
                                        preferred_element_type=jnp.float32)
            if not prescale:
                s = s * SCALE
            if masked:
                if keys:
                    # index == position below a row's frontier
                    k_idx = j * step + c * sub + jax.lax.broadcasted_iota(
                        jnp.int32, (sub, 1), 0)
                    v = k_idx <= q_pos
                else:
                    kv_pos = pos_ref[:, c * sub:(c + 1) * sub]
                    v = (kv_pos >= 0) & (kv_pos <= q_pos)
                s = jnp.where(v, s, NEG_INF)
                vis.append(v)
            ss.append(s)
        m_prev = m_ref[...]
        if joint:
            m_new = m_prev
            for s in ss:
                m_new = jnp.maximum(m_new, jnp.max(s, axis=red, keepdims=True))
            ms = [m_new] * parts
        else:
            ms = [jnp.max(s, axis=red, keepdims=True) for s in ss]
            m_new = functools.reduce(jnp.maximum, ms, m_prev)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[...] * alpha
        if keys and pv != "dot0":
            acc = acc_ref[...] * to_rows(alpha)
        else:
            acc = acc_ref[...] * alpha
        for c in range(parts):
            kv_v = kv_ref[c * sub:(c + 1) * sub, :dv]
            p = jnp.exp(ss[c] - ms[c])
            if masked:
                p = jnp.where(vis[c], p, 0.0)
            w = None if joint else jnp.exp(ms[c] - m_new)
            lc = jnp.sum(p, axis=red, keepdims=True)
            l_new = l_new + (lc if joint else lc * w)
            pb = p.astype(kv_v.dtype)
            if not keys:
                o = jnp.dot(pb, kv_v, preferred_element_type=jnp.float32)
            elif pv == "dot0":
                o = jax.lax.dot_general(kv_v, pb, _TN,
                                        preferred_element_type=jnp.float32)
            elif pv == "tr":
                o = jnp.dot(p.T.astype(kv_v.dtype), kv_v,
                            preferred_element_type=jnp.float32)
            elif pv == "eye":
                eye = (jax.lax.broadcasted_iota(jnp.int32, (h, h), 0)
                       == jax.lax.broadcasted_iota(jnp.int32, (h, h), 1)
                       ).astype(kv_v.dtype)
                pt = jax.lax.dot_general(eye, pb, _NT,
                                         preferred_element_type=jnp.float32)
                o = jnp.dot(pt.astype(kv_v.dtype), kv_v,
                            preferred_element_type=jnp.float32)
            if w is not None:
                o = o * (to_rows(w) if keys and pv != "dot0" else w)
            acc = acc + o
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc

    if mask_all:
        pl.when(j < n_vis)(lambda: walk(True))
    else:
        pl.when(j < n_vis - 1)(lambda: walk(False))
        pl.when(j == n_vis - 1)(lambda: walk(True))

    @pl.when(j == n_k - 1)
    def _store():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        if keys and pv == "dot0":
            o_ref[...] = (acc_ref[...] / l).T.astype(o_ref.dtype)
        elif keys:
            o_ref[...] = (acc_ref[...] / to_rows(l)).astype(o_ref.dtype)
        else:
            o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def candidate(name):
    """The call of one candidate body: (q [B, 1, H, D], kv, kv_pos, pos0,
    limit) -> [B, 1, H, dv]."""
    m = re.fullmatch(r"(rows|keys)(\d+)(?:x(\d+))?([jmsdx]*)(?:-(\w+))?", name)
    order, step, parts, flags, pv = m.groups()
    step, parts = int(step), int(parts or 1)
    kernel = functools.partial(
        _body, step=step, parts=parts, dv=DV, n_k=CTX // step, order=order,
        pv=pv, joint="j" in flags, mask_all="m" in flags,
        prescale="s" in flags,
        only="dma" if "d" in flags else "mxu" if "x" in flags else None)

    def block(bi, j, pos0_ref, limit_ref):
        end = jnp.minimum(limit_ref[bi], pos0_ref[bi] + 1)
        last = jnp.maximum((jnp.maximum(end, 0) + step - 1) // step - 1, 0)
        return jnp.minimum(j, last)

    @jax.jit
    def call(q, kv, kv_pos, pos0, limit):
        b, _, h, d = q.shape
        t = kv.shape[1]
        keys = order == "keys"
        ml = (1, h) if keys else (h, 1)
        acc = (DV, h) if keys and pv == "dot0" else (h, DV)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(b, t // step),
                in_specs=[
                    pl.BlockSpec((None, None, h, d),
                                 lambda bi, j, *_: (bi, 0, 0, 0)),
                    pl.BlockSpec((None, step, d),
                                 lambda bi, j, *r: (bi, block(bi, j, *r), 0)),
                    pl.BlockSpec((None, 1, step),
                                 lambda bi, j, *r: (bi, 0, block(bi, j, *r))),
                ],
                out_specs=pl.BlockSpec((None, None, h, DV),
                                       lambda bi, j, *_: (bi, 0, 0, 0)),
                scratch_shapes=[pltpu.VMEM(ml, jnp.float32),
                                pltpu.VMEM(ml, jnp.float32),
                                pltpu.VMEM(acc, jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((b, 1, h, DV), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name="sweep_" + re.sub(r"\W", "_", name),
            interpret=INTERPRET,
        )(pos0, limit, q, kv, kv_pos.reshape(b, 1, t))
        return out

    return call


@jax.jit
def pkg(q, kv, kv_pos, pos0, limit):
    return la.latent_attention(q, kv, kv_pos, pos0, limit, DV, SCALE,
                               interpret=INTERPRET)


@functools.lru_cache(maxsize=2)
def inputs(h, s, seed=0):
    lo, hi = FRONTIERS.get(h, FRONTIERS[128])
    rng = np.random.default_rng(seed)
    held = rng.integers(lo, hi, size=ROWS).astype(np.int32)
    held = np.minimum(held, CTX - s)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(ks[0], (ROWS, s, h, D), jnp.bfloat16)
    q = q.at[..., RANK + ROPE:].set(0)
    kv = jax.random.normal(ks[1], (ROWS, CTX, D), jnp.bfloat16)
    kv = kv.at[..., RANK + ROPE:].set(0)
    idx = jnp.arange(CTX, dtype=jnp.int32)[None, :]
    pos0 = jnp.asarray(held)
    kv_pos = jnp.where(idx < (pos0 + s)[:, None], idx, -1)
    return q, kv, kv_pos, pos0, pos0 + s


def measure(name, h):
    s = 256 if name == "chunk" else 1
    rows = 1 if name == "chunk" else ROWS
    q, kv, kv_pos, pos0, limit = inputs(h, s)
    if name == "chunk":
        q, pos0, limit = q[:1], pos0[:1], limit[:1]
        args = (q, kv[:1], kv_pos[:1], pos0, limit)
    else:
        args = (q, kv, kv_pos, pos0, limit)
    if name.startswith("pkg@"):
        # the tree's kernel at another key step than its constant
        la.DECODE_STEP_K = int(name[4:])
        la._entry.cache_clear()
        fn = jax.jit(lambda *a: pkg.__wrapped__(*a))
    else:
        fn = pkg if name in ("pkg", "chunk") else candidate(name)
    try:
        out = jax.block_until_ready(fn(*args))
    except Exception as e:  # a body Mosaic does not lower is a finding
        msg = str(e)
        keep = [ln for ln in msg.splitlines() if "rror" in ln or
                "not " in ln or "nsupported" in ln]
        return {"body": name, "H": h, "lowers": False,
                "error": " | ".join(keep)[:600] or msg[:600]}
    n = min(CHECK_ROWS, rows)
    want = la.latent_read(
        args[0][:n], args[1][:n], args[2][:n],
        pos0[:n, None] + jnp.arange(s)[None, :], DV, SCALE)
    diff = float(jnp.max(jnp.abs(out[:n].astype(jnp.float32)
                                 - want.astype(jnp.float32))))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / CALLS)
    sec = st.median(times)
    held = int(jnp.sum(limit)) if s == 1 else int(limit[0])
    walked = sum(-(-int(v) // 512) for v in np.asarray(limit)) * (
        1 if s == 1 else s // la.query_tokens(s, h))
    hf = {"num_hidden_layers": 1, "num_attention_heads": h,
          "kv_lora_rank": RANK, "qk_rope_head_dim": ROPE}
    line = {"body": name, "H": h, "lowers": True, "ms": sec * 1e3,
            "us_per_512": sec * 1e6 / walked,
            "GBps_640": walked * 512 * D * 2 / sec / 1e9, "max_diff": diff}
    if s == 1:
        flops, nbytes = needed.counts(hf, held)
        line["needed_share_pct"] = 100 * max(flops / PEAK_FLOPS,
                                             nbytes / PEAK_BYTES) / sec
    else:
        # a chunk's arithmetic: every query against its prefix
        flops = 2.0 * s * h * (held - s / 2) * (RANK + ROPE + RANK)
        line["mxu_share_pct"] = 100 * flops / PEAK_FLOPS / sec
    return line


def main():
    bodies = sys.argv[1].split(",") if len(sys.argv) > 1 else ["pkg"]
    heads = [int(v) for v in sys.argv[2].split(",")] if len(sys.argv) > 2 \
        else [128, 32]
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "pool": [ROWS, CTX], "calls": CALLS}), flush=True)
    for h in heads:
        for name in bodies:
            print(json.dumps(measure(name, h)), flush=True)


if __name__ == "__main__":
    main()
