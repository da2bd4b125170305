"""Drafters: proposal sources for speculative decoding.

A Drafter looks at the committed sequence (prompt + generated ids, host
side) and proposes up to k continuation tokens for ONE verify step to
check. Proposals are free to be wrong — the traced accept/reject rule
(ops/sampling.spec_accept) guarantees the emitted sequence keeps the
target model's semantics regardless — so a drafter's only job is to be
cheap and right often enough that accepted-tokens-per-step beats 1.0.

Two built-ins:

  * NGramDrafter — zero-weight prompt-lookup (Saxena 2023 "prompt lookup
    decoding"; the APD idea in Leviathan et al.'s framing with a
    copy-from-context q): match the last few tokens against the earlier
    sequence and propose whatever followed last time. Free, and strong
    exactly where decode is most wasteful — summarization, code editing,
    RAG, anything that restates its input.
  * DraftModelDrafter — classic two-model speculation: a smaller model
    with the SAME tokenizer greedily rolls out k tokens against its own
    small KV cache, rolling its speculative suffix back between calls
    with cache.truncate_cache.

Both are deterministic (point-mass q), which is what the acceptance rule
in ops/sampling.spec_accept assumes.
"""
from __future__ import annotations

from typing import Protocol, Sequence, runtime_checkable

import numpy as np

from .. import knobs

DEFAULT_SPEC_K = 6
MAX_SPEC_K = 32


@runtime_checkable
class Drafter(Protocol):
    """Proposal source for speculative decoding.

    `shareable` marks a drafter safe to share across concurrent sequences
    (stateless propose) — required by the serve engine, which calls one
    instance from every speculating slot.
    """

    name: str
    shareable: bool

    def propose(self, ids: Sequence[int], k: int) -> list[int]:
        """Up to k proposed continuation tokens for the sequence `ids`
        (prompt + generated so far). Return [] to abstain — the verify
        step then degenerates to a plain (distribution-preserving)
        decode step."""
        ...

    def reset(self) -> None:
        """Drop any per-sequence state before a new generation."""
        ...


class NGramDrafter:
    """Prompt-lookup drafter: no weights, no cache, no device work.

    Matches the last m tokens (m from max_ngram down to min_ngram)
    against the earlier sequence; on a hit, proposes the k tokens that
    followed the most recent earlier occurrence WITH A FULL k-token
    continuation (matches near the sequence end can only offer a stub —
    a 1-token proposal wastes the verify's amortized weight read, so a
    slightly older occurrence that fills the whole draft window beats a
    fresher one that cannot; when no occurrence fills it, the longest
    available continuation wins). Abstains when nothing repeats — a
    random prompt costs speculation nothing, a repetitive one (quote
    the context, fix this code, summarize) gets multi-token accepts for
    free. min_ngram >= 2 by default so single-token coincidences don't
    spray junk proposals.
    """

    name = "ngram"
    shareable = True

    def __init__(self, max_ngram: int = 3, min_ngram: int = 2):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"need 1 <= min_ngram <= max_ngram, got "
                             f"{min_ngram}..{max_ngram}")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose(self, ids: Sequence[int], k: int) -> list[int]:
        arr = np.asarray(list(ids), dtype=np.int64)
        n = int(arr.shape[0])
        if k <= 0 or n < self.min_ngram + 1:
            return []
        for m in range(min(self.max_ngram, n - 1), self.min_ngram - 1, -1):
            suffix = arr[n - m:]
            # candidate starts 0..n-m-1: the last window (the suffix
            # itself) is excluded, and every candidate has >= 1
            # continuation token
            windows = np.lib.stride_tricks.sliding_window_view(
                arr, m)[:n - m]
            hits = np.nonzero((windows == suffix).all(axis=1))[0]
            if hits.size == 0:
                continue
            # most recent occurrence whose continuation fills the whole
            # draft window; else the longest continuation on offer
            full = hits[hits + m + k <= n]
            j = int(full[-1]) if full.size else int(hits[0])
            cont = arr[j + m:j + m + k]
            if cont.size:
                return [int(t) for t in cont]
        return []

    def reset(self) -> None:
        pass


class DraftModelDrafter:
    """Greedy rollout from a smaller TextModel sharing the target's
    tokenizer (classic speculative sampling, Leviathan/Chen 2023).

    The drafter owns a small KV cache that always holds exactly the
    CONFIRMED prefix between calls: propose() forwards the unseen suffix
    (one bucketed prefill), greedily decodes k tokens, then rolls its own
    speculative suffix back out with cache.truncate_cache — the caller's
    sequence is append-only, so the prefix stays valid even when the
    target rejects every proposal. Attention-only draft models required:
    a linear-attention state cannot roll back (truncate_cache raises).

    Per-sequence state => NOT shareable across serve-engine slots; use it
    on the generate() path (or one engine slot pool per drafter).
    """

    name = "draft_model"
    shareable = False

    def __init__(self, model):
        if model.cfg.has_recurrent_state:
            raise ValueError(
                "draft model has linear-attention layers; their recurrent "
                "state cannot roll back between proposals — use an "
                "attention-only draft model or the n-gram drafter")
        self.model = model
        self.reset()

    def reset(self) -> None:
        self.cache = None
        self.kv_len = 0
        self.n_valid = 0        # cache holds exactly positions [0, n_valid)

    def propose(self, ids: Sequence[int], k: int) -> list[int]:
        from ..models.common.cache import truncate_cache
        from ..models.common.text_model import bucket_for
        m = self.model
        n = len(ids)
        if n == 0 or n >= m.max_cache_len:
            return []
        # greedy decode writes positions n .. n+k-2; stay inside the cache
        k = min(k, m.max_cache_len - n)
        if k <= 0:
            return []
        need = n + k
        if self.cache is None:
            self.kv_len = bucket_for(need, m.max_cache_len)
            self.cache = m.new_cache(1, kv_len=self.kv_len)
            self.n_valid = 0
        elif need > self.kv_len:
            self.kv_len = bucket_for(need, m.max_cache_len)
            self.cache = m._grow_to(self.cache, new_len=self.kv_len)
        # forward the unseen suffix (>= 1 token: re-forwarding the last
        # position on a no-delta call just rewrites identical KV)
        start = min(self.n_valid, n - 1)
        logits, self.cache = m.prefill(self.cache, list(ids[start:n]),
                                       pos0=start)
        self.n_valid = n
        # lint: disable=host-sync — draft proposals are host ints by contract
        # (the drafter feeds the verify program's host-built token block)
        props = [int(np.argmax(np.asarray(logits[0])))]
        for _ in range(k - 1):
            logits, self.cache = m.decode_logits(self.cache, props[-1])
            # lint: disable=host-sync — same: each draft id seeds the next draft
            # decode step on the host path
            props.append(int(np.argmax(np.asarray(logits[0]))))
        if len(props) > 1:
            # decode committed positions n .. n+k-2 — our own speculation;
            # drop it so the cache again holds exactly the confirmed prefix
            self.cache = truncate_cache(self.cache, n)
        return props


def resolve_drafter(spec, k: int | None = None):
    """(drafter | None, k) from a generate()/engine `spec` argument.

    spec: None reads env CAKE_SPEC ("" / unset = off, "ngram" = prompt
    lookup); False forces off; "ngram" / a Drafter instance / a draft
    TextModel are taken as-is. k defaults from CAKE_SPEC_K, clamped to
    [1, 32]; the n-gram drafter's match window comes from
    CAKE_SPEC_NGRAM (max match length, min stays 2).
    """
    if k is None:
        k = knobs.get("CAKE_SPEC_K")
    k = max(1, min(int(k), MAX_SPEC_K))
    if spec is None:
        spec = knobs.get("CAKE_SPEC")
    if spec is None or spec is False:
        return None, k
    if isinstance(spec, str):
        s = spec.strip().lower()
        if s in ("", "0", "off", "none", "false"):
            return None, k
        if s in ("ngram", "prompt", "prompt_lookup", "lookup"):
            # clamp to >= 2: min_ngram stays at the documented
            # junk-proposal guard (single-token coincidences must never
            # spray k-token drafts through the wider verify forward)
            mg = max(2, int(knobs.get("CAKE_SPEC_NGRAM")))
            return NGramDrafter(max_ngram=mg), k
        raise ValueError(
            f"unknown drafter {spec!r}: pass 'ngram', a Drafter instance, "
            "or a draft TextModel")
    if isinstance(spec, Drafter):
        return spec, k
    if hasattr(spec, "prefill") and hasattr(spec, "decode_logits"):
        return DraftModelDrafter(spec), k
    raise TypeError(f"cannot build a drafter from {type(spec).__name__}")
