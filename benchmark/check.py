"""The output check: the served programs against the plain reference.

Runs in the process that holds the chip, before the engine exists, on a
pool of the engine's own shape, through the very programs the engine calls
(`TextModel.prefill_chunk`, `sample_one`, `decode_slots`) — so every chunk
bucket x flash mode and the full-slot decode program are exercised, and the
compiled programs are the ones the engine then reuses.

For each check sequence (lengths from the traffic mix's `check` block, token
ids from --seed over the whole vocabulary):
  1. the prompt is prefilled chunk by chunk into a pool row; the logits the
     program returns at each chunk's end are kept;
  2. `decode_steps` tokens are sampled and decoded through the cache with
     the batched decode program, all check rows in one dispatch;
  3. a further chunk is prefilled behind them: its logits depend on every
     key and value the decode steps wrote.
The reference then makes ONE full forward pass over prompt + decoded tokens
+ that chunk (no cache) and gives logits at the same positions. At each
point the relative RMS difference
    rms(served - reference) / rms(reference)
over the whole vocabulary is printed; the number the limit in the
configuration's file holds is the same ratio POOLED over all the points
(root of summed squares over root of summed squares). The largest single
point was tried first and is no steady number for the MoE: a router
near-tie that bf16 rounding flips doubles ONE point in one seed of ten
(PERF.md, PR 25), while a fault in any one program still moves a third or
more of the points and so the pooled number.
"""
from __future__ import annotations

import numpy as np

# recent-token window of the engine's slot carries (serve/engine.py RECENT_N)
TAIL_TOKENS = 32


def rel_rms(got: np.ndarray, want: np.ndarray) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def check_ids(seed: int, vocab: int, lengths: list[int]) -> list[dict]:
    """Token ids of every check sequence, from the seed alone."""
    rng = np.random.default_rng([seed, 0xC0FFEE])
    return [{"prompt": rng.integers(0, vocab, n).astype(np.int32),
             "tail": rng.integers(0, vocab, TAIL_TOKENS).astype(np.int32)}
            for n in lengths]


def served_logits(model, slots: int, ctx: int, chunk: int, seqs: list[dict],
                  decode_steps: int, sampling: dict,
                  also_load: tuple = ()) -> list[dict]:
    """Steps 1-3 above. Returns per sequence the full id sequence and the
    served logits by position. `also_load`: further (bucket, first-chunk?)
    prefill shapes whose programs are run once here, with the decode
    program of every slot bucket — loaded in the main thread, before the
    engine exists (loaded later, from the scheduler thread under a full
    device, the same cache entries took 3-6 s instead of 1 s in half the
    runs: PERF.md, PR 25)."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.serve.engine import RECENT_N

    vocab = model.cfg.vocab_size
    layers = model.new_cache(slots, kv_len=ctx)["layers"]
    # rows spread over the pool: first, last, then the middle
    rows = [0, slots - 1, slots // 2][:len(seqs)]
    out = []
    toks = jnp.zeros((slots,), jnp.int32)
    pos = jnp.zeros((slots,), jnp.int32)
    act = jnp.zeros((slots,), jnp.bool_)
    rngs = jnp.stack([jax.random.PRNGKey(i) for i in range(slots)])
    recents = jnp.full((slots, RECENT_N), -1, jnp.int32)
    temp = jnp.float32(sampling["temperature"])
    top_k = jnp.int32(vocab)
    top_p = jnp.float32(sampling["top_p"])
    pen = jnp.float32(1.0)
    for row, seq in zip(rows, seqs):
        ids = seq["prompt"]
        got, modes = {}, []
        for p0 in range(0, len(ids), chunk):
            part = ids[p0:p0 + chunk]
            logits, layers = model.prefill_chunk(layers, row, part, p0)
            got[p0 + len(part) - 1] = logits[0]
            modes.append(model.last_chunk_attn)
        first = model.sample_one(logits[0], jax.random.PRNGKey(row), temp,
                                 top_k, top_p, pen,
                                 jnp.full((RECENT_N,), -1, jnp.int32))
        toks = toks.at[row].set(first)
        pos = pos.at[row].set(len(ids))
        act = act.at[row].set(True)
        out.append({"row": row, "n": len(ids), "got": got, "modes": modes,
                    "decoded": [], "tail": seq["tail"], "prompt": ids})
    temps = jnp.full((slots,), sampling["temperature"], jnp.float32)
    top_ks = jnp.full((slots,), vocab, jnp.int32)
    top_ps = jnp.full((slots,), sampling["top_p"], jnp.float32)
    pens = jnp.ones((slots,), jnp.float32)
    for _ in range(decode_steps):
        packed, layers, toks, pos, rngs, recents = model.decode_slots(
            layers, toks, pos, rngs, recents, temps, top_ks, top_ps, pens,
            act, nb=slots)
        arr = np.asarray(packed)            # [2, nb]: input ; sampled
        for o in out:
            o["decoded"].append(int(arr[0, o["row"]]))
    last = np.asarray(toks)
    for o in out:
        # the cache now holds prompt + decoded inputs; the token sampled
        # last leads the tail chunk
        tail = np.concatenate([[last[o["row"]]], o["tail"][1:]]
                              ).astype(np.int32)
        p0 = o["n"] + decode_steps
        logits, layers = model.prefill_chunk(layers, o["row"], tail, p0)
        o["modes"].append(model.last_chunk_attn)
        o["ids"] = np.concatenate([o["prompt"],
                                   np.asarray(o["decoded"], np.int32), tail])
        o["got"][len(o["ids"]) - 1] = logits[0]
        o["got"] = {p: np.asarray(v, np.float32) for p, v in o["got"].items()}
    idle = jnp.zeros((slots,), jnp.bool_)       # no row decodes: load only
    for bucket, first in also_load:
        _, layers = model.prefill_chunk(layers, 0, np.zeros(bucket, np.int32),
                                        0 if first else chunk)
    nb = 1
    while nb < slots:
        _, layers, toks, pos, rngs, recents = model.decode_slots(
            layers, toks, pos, rngs, recents, temps, top_ks, top_ps, pens,
            idle, nb=nb)
        nb *= 2
    jax.block_until_ready(layers)
    del layers
    return out


def _held_against(reference, hf, weights, served, got_of) -> dict:
    """{"points": one number per point, "pooled": the number compared}."""
    points, num, den = {}, 0.0, 0.0
    for i, o in enumerate(served):
        positions = sorted(o["got"])
        want = reference.forward_logits(hf, weights, o["ids"], positions)
        for p, w, g in zip(positions, want, got_of(o, positions)):
            kind = ("tail_after_decode" if p == len(o["ids"]) - 1
                    else "prefill")
            points[f"seq{i}.{kind}@{p}"] = rel_rms(g, w)
            w64 = np.asarray(w, np.float64)
            num += float(np.sum((np.asarray(g, np.float64) - w64) ** 2))
            den += float(np.sum(w64 ** 2))
    return {"points": points, "pooled": float(np.sqrt(num / max(den, 1e-30))),
            "worst": max(points.values())}


def compare(reference, hf: dict, weights: dict, served: list[dict]) -> dict:
    """The served logits held against the reference's."""
    return _held_against(reference, hf, weights, served,
                         lambda o, positions: [o["got"][p] for p in positions])


def control(reference, hf: dict, weights: dict, served: list[dict],
            quant: str) -> dict:
    """The control: the reference in the precision below, put in the
    program's place and held against the reference proper."""
    return _held_against(
        reference, hf, weights, served,
        lambda o, positions: reference.forward_logits(
            hf, weights, o["ids"], positions, quant=quant))
