"""Host-side speculative decode loop: draft -> verify -> emit.

The traced pieces live elsewhere — ops/sampling.spec_accept (the
Leviathan/Chen accept/reject rule, batched), TextModel._spec_verify /
._spec_slots / ._spec_slots_paged (one bucketed forward + acceptance +
rejected-suffix rollback per device call; the _slots variants serve the
engine's batched ragged-acceptance iteration) — this module owns what
must stay on the host: asking the drafter,
growing the KV bucket, truncating emission at EOS / budget, and the spec
metrics every path shares (cake_serve_spec_{proposed,accepted}_total +
the accepted-length histogram).
"""
from __future__ import annotations

import jax
import numpy as np

from ..obs import (RECORDER, SPEC_ACCEPTED, SPEC_ACCEPTED_LEN,
                   SPEC_BUCKET_ACCEPTED, SPEC_PROPOSED)


def record_step(n_proposed: int, n_acc: int, bucket: int | None = None) -> None:
    """Feed the shared spec instruments from one completed verify step
    (generate loop and serve engine both call this — one call-site shape,
    both paths). `bucket` is the row count the batched dispatch ran
    (engine path only; the whole pool on contiguous rows, the slot-count
    bucket on a paged pool): it labels the acceptance-x-occupancy
    histogram the serve bench reads."""
    SPEC_PROPOSED.inc(n_proposed)
    SPEC_ACCEPTED.inc(n_acc)
    SPEC_ACCEPTED_LEN.observe(n_acc)
    if bucket is not None:
        SPEC_BUCKET_ACCEPTED.observe(n_acc, bucket=str(bucket))


def spec_stats_dict(steps: int, proposed: int, accepted: int) -> dict:
    """Per-generation speculative stats block (stats dict / bench JSON)."""
    return {
        "spec_steps": steps,
        "spec_proposed": proposed,
        "spec_accepted": accepted,
        "spec_accept_rate": round(accepted / proposed, 4) if proposed else 0.0,
        # tokens emitted per device step (the speedup proxy: 1.0 == plain
        # decode; every verify emits its correction/bonus token + accepts)
        "spec_tokens_per_step": round((accepted + steps) / steps, 4)
        if steps else 0.0,
    }


def spec_decode_loop(model, drafter, k: int, prompt_ids: list[int],
                     out: list[int], cache, kv_len: int, rng, recent,
                     scfg, max_new_tokens: int, on_token, done: bool):
    """Speculative replacement for TextModel.generate's decode loop.

    `out` already holds the first sampled token (emitted by generate's
    shared prefill preamble); `done` is True when it was EOS. Each
    iteration: the drafter proposes up to k tokens from the host-side
    sequence, ONE verify call checks them all (and commits exactly the
    accepted prefix), and the host fans out n_acc + 1 tokens. Greedy
    output is bit-identical to the non-speculative path; EOS inside the
    accepted prefix truncates emission exactly where one-token-at-a-time
    decoding would have stopped.

    Returns (out, spec_stats).
    """
    cfg = model.cfg
    drafter.reset()
    pos = len(prompt_ids)               # next KV write position
    n_total = min(max_new_tokens - 1, model.max_cache_len - pos - 1)
    emitted = 0
    steps = proposed = accepted = 0
    while not done and emitted < n_total:
        # room for the widest verify (k drafts + the input token)
        if pos + k + 1 > kv_len and kv_len < model.max_cache_len:
            from ..models.common.text_model import bucket_for
            kv_len = bucket_for(pos + k + 1, model.max_cache_len)
            cache = model._grow_to(cache, new_len=kv_len)
        # never draft past the cache or the budget (a step emits at most
        # n_draft + 1 tokens; the +1 correction token always fits)
        n_draft = min(k, kv_len - pos - 1, max(n_total - emitted - 1, 0))
        draft = list(drafter.propose(prompt_ids + out, n_draft))[:n_draft] \
            if n_draft > 0 else []
        rng, sub = jax.random.split(rng)
        with RECORDER.span("spec.verify", cat="gen", drafts=len(draft),
                           pos=pos):
            packed, cache, recent = model.verify_tokens(
                cache, out[-1], draft, k, pos, sub, recent, scfg)
            # lint: disable=host-sync — the verify loop's one planned fetch per
            # step: [n_acc, next] in a single small transfer
            arr = np.asarray(packed)
        n_acc, nxt = int(arr[0]), int(arr[1])
        steps += 1
        proposed += len(draft)
        accepted += n_acc
        record_step(len(draft), n_acc)
        for t in draft[:n_acc] + [nxt]:
            out.append(t)
            emitted += 1
            if on_token is not None:
                on_token(model._mk_token(t))
            if cfg.is_eos(t) or emitted >= n_total:
                done = True
                break
        pos += n_acc + 1
    return out, spec_stats_dict(steps, proposed, accepted)
