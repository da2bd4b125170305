"""Generic decoder-only text model runtime.

TPU replacement for the reference's TextModelBase (ref: models/common/
text_model.rs): instead of a per-layer Forwarder loop with dynamic-shape KV
concat, the model compiles

  * one `prefill` program per (batch, padded-length-bucket) — the prompt is
    right-padded to a power-of-two bucket and padded slots are dropped from
    the KV scatter (ref hard-part #1: static shapes, bucketed prefill);
  * one `decode_step` program — embed -> all local layers -> head -> sampling
    entirely on device, only the 4-byte token id crosses the host boundary
    per token (ref: text_model.rs GPU sampling / repeat penalty);
  * one `decode_chunk` program — lax.scan over N decode steps for the
    streaming path, dispatched pipeline-deep off the device-side carry so
    the per-chunk host fetch overlaps the next chunk's compute;
  * one `decode_until` program — lax.while_loop to EOS/budget for the
    non-streaming path: a whole generation segment is ONE device call and
    ONE host fetch.

Distributed layer sharding plugs in through `stages`: an ordered list of
LocalStage (jit-compiled contiguous layer range) and remote stages (any
object with forward_hidden(x, pos0, valid_len) — the TCP Client in
cluster/client.py). This mirrors the reference's contiguous same-worker
batching (text_model.rs:298-331) with the whole local range as ONE device
program.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import (DECODE_TOKEN_SECONDS, GENERATED_TOKENS, PROCESS,
                    RECORDER, TTFT_SECONDS, now)
from ...ops.sampling import (SamplingConfig, config_has_filters,
                             push_recent_token, sample, sample_traced,
                             spec_accept)
from .cache import (grow_cache, kv_capacity, paged_block_of,
                    paged_block_window, paged_gather_layer,
                    paged_scatter_blocks, slot_assign_layers,
                    restore_reads, slot_extract_block_layers,
                    slot_reset_layers, slot_restore_chain_layers,
                    truncate_layers)
from .config import ModelConfig
from .layers import (cut_rope, embed_tokens, flash_kernel_mode,
                     forward_layers, lm_head_logits)

PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)

# decode tokens an initial KV bucket reserves beyond the prompt so the
# first growth realloc doesn't land within the opening tokens of decode
# (shared by the distributed master's sizing and the worker's warmup)
DECODE_HEADROOM = 16

# distributed pipelined prefill streams the prompt through the stage chain
# in chunks of this many tokens (stage s computes chunk c while stage s-1
# computes chunk c+1 — prefill has no sampling dependency, so unlike
# decode the chain CAN overlap); shared so the worker warm sweep compiles
# the exact chunk shapes the master will send
PREFILL_CHUNK = 512


def _observe_generation(stats: dict, n_out: int, path: str):
    """Feed the canonical TTFT / per-token-decode histograms and token
    counter from a completed generation's stats dict (shared by the local,
    offloaded and distributed models — one call site shape, three paths)."""
    TTFT_SECONDS.observe(stats["ttft_s"])
    ntok = stats.get("decode_tokens") or 0
    if ntok and stats.get("decode_s", 0) > 0:
        DECODE_TOKEN_SECONDS.observe(stats["decode_s"] / ntok)
    GENERATED_TOKENS.inc(n_out, path=path)


def bucket_for(n: int, max_len: int) -> int:
    for b in PREFILL_BUCKETS:
        if n <= b:
            return min(b, max_len)
    return max_len


def initial_kv_bucket(n_tokens: int, max_new: int, max_len: int) -> int:
    """KV bucket covering n_tokens of context + the first sampled token +
    a short run of decode, so the first growth realloc never lands within
    the opening tokens. Shared by the distributed master's fresh-
    generation sizing AND its mid-stream recovery replay: a replayed
    request must land on exactly the bucketing progression the unfailed
    run used."""
    span = 1 + min(max_new, DECODE_HEADROOM)
    return bucket_for(n_tokens + span, max_len)


def select_flash_mode(pos0: int, width: int, capacity: int | None) -> str:
    """Host-static flash dispatch shared by the local, master and worker
    prefill paths: "fresh" at position 0, scatter-then-flash "append" while
    the chunk stays inside the unwrapped buffer, else the masked path."""
    if pos0 == 0:
        return "fresh"
    if capacity is not None and pos0 + width <= capacity:
        return "append"
    return "off"


def _chunk_attn(flash_mode: str, width: int) -> str:
    """Attention path of a serve prefill chunk's full-attention layers, as
    the request timeline names it."""
    kernel = flash_kernel_mode(flash_mode, width)
    return f"flash-{kernel}" if kernel else "masked"


def check_prefill_bounds(n: int, pos0: int, capacity: int | None,
                         max_len: int) -> int:
    """Validate a prefill request against the cache; returns the prompt
    bucket. capacity = actual full-attention buffer length (kv_capacity),
    which may be a smaller growth bucket than max_len."""
    bkt = bucket_for(n, max_len)
    if n > bkt:
        raise ValueError(f"prompt length {n} exceeds cache {bkt}")
    limit = max_len if capacity is None else min(capacity, max_len)
    if pos0 + n > limit:
        raise ValueError(
            f"prefill past cache end: pos0={pos0} + {n} tokens > "
            f"cache capacity {limit}")
    return bkt


@dataclass
class Token:
    id: int
    text: str | None
    is_end_of_stream: bool


# -- what the compiled programs do -------------------------------------------
# One traced body for each thing TextModel's programs do to a batch-1 cache
# view; the jitted shells in TextModel._build keep only what differs between
# the KV layouts — how the view of a slot is made (a pool row / a gather
# through the block table) and what is written back.

def chunk_logits(cfg: ModelConfig, params: dict, cache: dict, tokens, pos0,
                 valid_len, flash_mode: str, mesh):
    """Forward one right-padded chunk of a prompt (tokens [1, S]) at
    absolute position pos0 and keep the logits at its last valid position.
    Returns (logits [1, V], the advanced cache)."""
    x = embed_tokens(cfg, params, tokens)
    x, cache = forward_layers(cfg, params, x, cache, pos0,
                              valid_len=valid_len, flash_mode=flash_mode,
                              mesh=mesh)
    idx = jnp.clip(valid_len - 1, 0, x.shape[1] - 1)
    x_last = jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=1)
    return lm_head_logits(cfg, params, x_last)[:, 0], cache


def slot_step(cfg: ModelConfig, params: dict, cache: dict, tok, p, rng,
              recent, temp, tk, tp, pen, act, mesh=None):
    """One slot's sampled decode step on its batch-1 cache view: embed ->
    layers -> head -> sample_traced -> recent-token push, the sampled_step
    pipeline with TRACED sampling parameters. `act` (traced bool) masks
    the slot OUT without changing the program: valid_len 0 leaves its
    KV/conv/recurrent state byte-identical (the scatter is dropped, the GDN
    scan masks the state advance) and its token/rng/recent carries pass
    through; for an active slot valid_len 1 is numerically the unmasked
    step. Returns (next token, the view's layers without their batch axis,
    rng, recent)."""
    x = embed_tokens(cfg, params, tok[None, None])
    x, cache = forward_layers(cfg, params, x, cache, p,
                              valid_len=act.astype(jnp.int32), mesh=mesh)
    logits = lm_head_logits(cfg, params, x)[0, -1]
    rng2, sk = jax.random.split(rng)
    nxt = sample_traced(logits, sk, temp, tk, tp, pen, recent)
    nxt = jnp.where(act, nxt, tok)
    return (nxt, jax.tree_util.tree_map(lambda a: a[0], cache["layers"]),
            jnp.where(act, rng2, rng),
            jnp.where(act, push_recent_token(recent, nxt), recent))


def slot_verify(cfg: ModelConfig, params: dict, cache: dict, tokens, p,
                n_input, draft, ndr, sk, recent, temp, tk, tp, pen,
                filt: bool, has_linear: bool):
    """One slot's speculative verify on its batch-1 cache view. tokens:
    [1, K+1] = [input token, d_0 .. d_{K-1}], entries >= n_input are
    padding (n_input 0 freezes the slot); draft: [K] with ndr valid
    entries; sk: the consumed key. Returns (n_acc, next token, commit,
    the view's layers with their batch axis, recent'), commit = n_acc + 1
    <= n_input the number of input positions that stay.

    Pass 1 forwards all n_input tokens (valid_len keeps padding out of the
    KV scatter and the GDN state scan), keeps logits at every position and
    runs the traced accept/reject rule. What `layers` holds splits on the
    model's layer mix, statically:
      * attention only: pass 1's layers, all n_input entries written — the
        caller rolls the rejected suffix back by position (truncate_layers
        on a row, a pos mask on the written blocks of a paged pool), zero
        extra compute;
      * any recurrent layer: a state cannot be truncated, so the commit
        re-runs the forward with valid_len = commit from the ORIGINAL
        cache — the masking that keeps bucketed-prefill padding out of the
        state keeps the rejected suffix out, bit-exactly. XLA dead-code-
        eliminates pass 1's unused cache outputs.
    """
    x = embed_tokens(cfg, params, tokens)
    x1, c1 = forward_layers(cfg, params, x, cache, p, valid_len=n_input)
    logits = lm_head_logits(cfg, params, x1)[0]                # [K+1, V]
    n_acc, nxt, recent = spec_accept(logits, draft, ndr, sk, temp, tk, tp,
                                     pen, recent, use_filters=filt)
    commit = jnp.minimum(n_acc + 1, n_input)
    if has_linear:
        _, c1 = forward_layers(cfg, params, x, cache, p, valid_len=commit)
    return n_acc, nxt, commit, c1["layers"], recent


class LocalStage:
    """A contiguous range of layers resident on this host's TPU(s).

    With a mesh, params are tp-sharded in place (GSPMD inserts the
    collectives inside the one compiled range) — the product-path
    replacement for the reference's intra-worker multi-GPU layer split
    (ref: worker.rs:126-229)."""

    def __init__(self, cfg: ModelConfig, params: dict, lo: int, hi: int,
                 mesh=None):
        from ...parallel.sharding import check_tp_divisibility, shard_params
        if mesh is not None:
            check_tp_divisibility(cfg, mesh)
        self.cfg, self.lo, self.hi = cfg, lo, hi
        self.params = shard_params(params, mesh)
        self.mesh = mesh

        @functools.partial(jax.jit,
                           static_argnames=("padded", "flash_mode"),
                           donate_argnums=(2,))
        def _fwd(params, x, cache, pos0, valid_len, padded, flash_mode):
            del padded  # static marker to separate prefill/decode programs
            return forward_layers(cfg, params, x, cache, pos0,
                                  layer_range=(lo, hi), valid_len=valid_len,
                                  flash_mode=flash_mode, mesh=mesh)

        self._fwd = _fwd

    def forward_hidden(self, x, cache, pos0, valid_len, flash_mode="off"):
        return self._fwd(self.params, x, cache, pos0, valid_len,
                         padded=x.shape[1], flash_mode=flash_mode)


class TextModel:
    """Single-process text model (all layers local). The distributed master
    variant lives in cluster/master.py and reuses the same compiled pieces."""

    # first non-streaming decode segment (and so the initial KV bucket) is
    # capped at this many tokens; later segments fill the growing buckets
    UNTIL_SEGMENT = 256
    # streaming decode keeps this many chunks in flight so each chunk's
    # host fetch overlaps the next chunk's device compute
    STREAM_DEPTH = 2

    @PROCESS.phase("boot.model")
    def __init__(self, cfg: ModelConfig, params: dict | None = None,
                 tokenizer=None, dtype=jnp.bfloat16, seed: int = 42,
                 max_cache_len: int | None = None, mesh=None):
        # a process that never enabled the compile cache still witnesses
        # its program builds from here on
        PROCESS.install()
        self.cfg = cfg
        self.dtype = dtype
        self.tokenizer = tokenizer
        self.mesh = mesh
        self.max_cache_len = min(max_cache_len or cfg.max_seq_len, cfg.max_seq_len)
        # in-host tensor parallelism on the product path: shard the weights
        # once, let GSPMD insert the psum after the row x col matmul pairs
        # in every compiled program below (no-op without a mesh)
        from ...parallel.sharding import (check_tp_divisibility,
                                          init_params_sharded, shard_params)
        if mesh is not None:
            check_tp_divisibility(cfg, mesh)
            sp = mesh.shape.get("sp", 1)
            if sp > 1 and (self.max_cache_len % sp or sp & (sp - 1)):
                # otherwise cache_shardings silently replicates the top KV
                # bucket — the context-memory scaling sp exists for
                # vanishes at exactly the size where it matters
                raise ValueError(
                    f"sp={sp} must be a power of two dividing "
                    f"max_cache_len {self.max_cache_len} so every KV "
                    "growth bucket shards over it")
        if params is None:
            params = init_params_sharded(mesh, cfg,
                                         jax.random.PRNGKey(seed), dtype)
        # the tables end where this model's caches do
        params = {**params,
                  "rope": cut_rope(params["rope"], self.max_cache_len)}
        self.params = shard_params(params, mesh)
        self._rng = jax.random.PRNGKey(seed)
        self.last_prefill_mode: str | None = None
        self.last_chunk_attn: str | None = None
        self._build()

    # -- compiled programs --------------------------------------------------

    def _build(self):
        cfg = self.cfg
        mesh = self.mesh     # static per instance: the ring branch's mesh
                             # is baked into this model's compiled prefill

        @functools.partial(jax.jit, donate_argnums=(2,),
                           static_argnames=("flash_mode",))
        def _prefill(params, tokens, cache, pos0, valid_len, flash_mode):
            return chunk_logits(cfg, params, cache, tokens, pos0, valid_len,
                                flash_mode, mesh)

        def sampled_step(params, tok, cache, rng, recent, scfg):
            """The one decode step shared by every sampling decode program
            (scan chunk, while_loop segment): embed -> all layers -> head ->
            on-device sample -> recent-token push. A single definition so a
            sampling/threading change cannot land in one compiled path and
            silently diverge the others (they are parity-tested, but keep
            the invariant structural)."""
            rng, sk = jax.random.split(rng)
            x = embed_tokens(cfg, params, tok[:, None])
            x, cache = forward_layers(cfg, params, x, cache, cache["pos"],
                                      mesh=mesh)
            logits = lm_head_logits(cfg, params, x)[:, -1]
            nxt = sample(logits[0], sk, scfg, recent)
            recent = push_recent_token(recent, nxt)
            return nxt, jnp.broadcast_to(nxt, tok.shape), cache, rng, recent

        @functools.partial(jax.jit, static_argnames=("scfg", "n"),
                           donate_argnums=(2,))
        def _decode_chunk(params, token, cache, rng, recent, scfg, n):
            """lax.scan over n decode steps, sampling on device."""
            def body(carry, _):
                tok, cache, rng, recent = carry
                nxt, tok, cache, rng, recent = sampled_step(
                    params, tok, cache, rng, recent, scfg)
                return (tok, cache, rng, recent), nxt

            (tok, cache, rng, recent), toks = jax.lax.scan(
                body, (token, cache, rng, recent), None, length=n)
            return toks, cache, rng, recent

        @functools.partial(jax.jit, static_argnames=("scfg", "nbuf"),
                           donate_argnums=(2,))
        def _decode_until(params, token, cache, rng, recent, n_limit, scfg,
                          nbuf):
            """Decode up to n_limit tokens on device, stopping at EOS
            (lax.while_loop): ONE host round trip per generation. Every host
            sync has a fixed cost that chunked decode pays per chunk
            (fetches are stream-ordered, so they cannot overlap queued
            compute), and the while_loop also removes past-EOS overshoot.
            Returns [count, tok0, tok1, ...] packed into one array so the
            host pays a single small fetch.

            (Measured dead end, kept for the record: an outer-while over
            inner fori_loop(k) variant — static inner trip count to let XLA
            pipeline weight prefetch — benched slower than this flat loop
            on v5e in an early round; nested loop carries appear to defeat
            in-place KV-cache aliasing. Not re-measured since: PERF.md
            carries what has been measured on the current machine.)"""
            eos = jnp.asarray(cfg.eos_token_ids or (-1,), jnp.int32)

            def cond(c):
                i, done = c[0], c[1]
                return jnp.logical_and(~done, i < n_limit)

            def body(c):
                i, done, tok, cache, rng, recent, buf = c
                nxt, tok, cache, rng, recent = sampled_step(
                    params, tok, cache, rng, recent, scfg)
                buf = jax.lax.dynamic_update_index_in_dim(buf, nxt, i, 0)
                return (i + 1, jnp.any(nxt == eos), tok, cache, rng, recent,
                        buf)

            init = (jnp.asarray(0, jnp.int32), jnp.asarray(False), token,
                    cache, rng, recent, jnp.zeros((nbuf,), jnp.int32))
            i, _, _, cache, rng, recent, buf = jax.lax.while_loop(
                cond, body, init)
            return jnp.concatenate([i[None], buf]), cache, rng, recent

        @functools.partial(jax.jit, donate_argnums=(2,))
        def _decode_step(params, token, cache):
            """One decode step returning raw logits (distributed master path +
            logit-parity tests)."""
            x = embed_tokens(cfg, params, token[:, None])
            x, cache = forward_layers(cfg, params, x, cache, cache["pos"],
                                      mesh=mesh)
            logits = lm_head_logits(cfg, params, x)[:, -1]
            return logits, cache

        # no donation: grown shapes differ, so donated buffers can't be
        # reused anyway and the warning is just noise
        @functools.partial(jax.jit, static_argnames=("new_len",))
        def _grow(cache, new_len):
            return grow_cache(cfg, cache, new_len)

        @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5))
        def _decode_slots(params, layers, toks, pos, rngs, recents,
                          temps, top_ks, top_ps, penalties, active):
            """One batched sampled decode step over EVERY pool row with
            per-slot positions, RNG keys, recent-token windows and TRACED
            sampling params (sample_traced): the continuous-batching
            engine's iteration unit. No static argument — ONE executable
            per pool shape at every occupancy, so a mixed bag of client
            sampling configs cannot grow the compile cache (the
            api/text.py quantization grid stays the only bound on the
            legacy static-SamplingConfig programs). The donated pool
            buffers update in place: a program that ran on a prefix of
            the rows would slice them out and write them back, and those
            copies of every layer's K and V cost more than the masked
            rows' work (PERF.md, PR 33).

            The per-slot step is slot_step, vmapped over the slot axis.
            `active` [B] bool masks rows OUT of the step without changing
            the executable: an inactive row (free, or mid-way through a
            CHUNKED admission prefill) keeps its state byte-identical and
            its token/pos/rng/recent carries. That is what lets a chunked
            prefill build a row IN PLACE across iterations while the
            surrounding slots keep decoding — decode can never smear a
            garbage KV entry into a half-built prefix — and greedy parity
            with the sequential path is untouched."""
            def one(tok, lcs, p, rng, recent, temp, tk, tp, pen, act):
                cache = {"layers": jax.tree_util.tree_map(
                    lambda a: a[None], lcs), "pos": p}
                return slot_step(cfg, params, cache, tok, p, rng, recent,
                                 temp, tk, tp, pen, act, mesh)

            # the whole per-slot carry advances ON DEVICE: the engine ships
            # nothing per iteration and fetches only the packed ids
            nxt, layers, rngs, recents = jax.vmap(one)(
                toks, layers, pos, rngs, recents, temps, top_ks, top_ps,
                penalties, active)
            # the fetch target packs [input token ; sampled token] per slot:
            # a freshly admitted slot's first token (sampled at admission,
            # never fetched — admission stays sync-free) rides the SAME
            # device->host transfer as this step's ids, so an iteration
            # costs exactly one fetch no matter how many slots joined
            return (jnp.stack([toks, nxt]), layers, nxt,
                    pos + active.astype(jnp.int32), rngs, recents)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _slot_assign(layers, src_layers, slot):
            return slot_assign_layers(layers, src_layers, slot)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _slot_reset(layers, slot):
            return slot_reset_layers(layers, slot)

        @functools.partial(jax.jit, donate_argnums=(2,),
                           static_argnames=("flash_mode",))
        def _prefill_slot(params, tokens, layers, slot, pos0, valid_len,
                          flash_mode):
            """Prefill one CHUNK of a prompt directly into pool row `slot`
            at absolute position pos0 — the serve engine's incremental
            admission unit. The row is gathered to a batch-1 view, run
            through the same forward_layers as every other prefill program
            (chunk queries attend over [row prefix ; in-pass chunk], so a
            prompt split into chunks reproduces the monolithic prefill
            exactly — the cluster's pipelined prefill pins the same
            invariant), then scattered back. One executable per
            (chunk-bucket, flash_mode); slot/pos0/valid_len are traced.
            Returns (logits at the last valid chunk position, layers)."""
            row = jax.tree_util.tree_map(lambda a: a[slot][None], layers)
            logits, rcache = chunk_logits(
                cfg, params, {"layers": row, "pos": pos0}, tokens, pos0,
                valid_len, flash_mode, mesh)
            layers = jax.tree_util.tree_map(
                lambda full, r: full.at[slot].set(r[0]), layers,
                rcache["layers"])
            return logits, layers

        # -- speculative verify: k drafted tokens in ONE bucketed step ------
        # A verify step is a prefill-chunk-shaped forward over
        # [last_token, d_0 .. d_{k-1}] at pos0 with logits kept at ALL
        # positions, followed by the traced accept/reject rule
        # (ops.sampling.spec_accept) and the rejected-suffix rollback —
        # everything inside one compiled program, so a verify costs one
        # device call exactly like a decode step.
        has_linear = cfg.has_recurrent_state

        @functools.partial(jax.jit, donate_argnums=(2,),
                           static_argnames=("filt",))
        def _spec_verify(params, tokens, cache, pos0, n_input, draft, rng,
                         recent, temp, top_k, top_p, penalty, filt):
            """Batch-1 verify (the generate() speculative loop): slot_verify
            on the cache itself. tokens: [1, K+1]; draft: [K]; n_input =
            n_draft + 1 (traced); rng is the consumed key. `filt` is the
            static no-vocab-filters escape hatch (one executable per value
            — two at most)."""
            n_acc, nxt, commit, layers, recent = slot_verify(
                cfg, params, cache, tokens, pos0, n_input, draft,
                n_input - 1, rng, recent, temp, top_k, top_p, penalty, filt,
                has_linear)
            if not has_linear:
                layers = truncate_layers(layers, pos0 + commit)
            return (jnp.stack([n_acc, nxt]),
                    {"layers": layers, "pos": pos0 + commit}, recent)

        @functools.partial(jax.jit, static_argnames=("filt",),
                           donate_argnums=(1, 2, 3, 4, 5))
        def _spec_slots(params, layers, toks, pos, rngs, recents, temps,
                        top_ks, top_ps, penalties, active, drafts,
                        n_drafts, filt):
            """Batched multi-token speculative verify over every pool
            row — the `_decode_slots` of the speculative path. Each
            slot forwards [input_token, d_0 .. d_{k-1}] at its OWN
            position in one vmapped program, runs the traced
            accept/reject rule with its own sampling params, commits
            exactly the accepted prefix, and advances its carries by
            n_acc + 1. Acceptance is RAGGED per slot: a slot that rejects
            at position 0 and a slot that accepts all k coexist in the
            same executable (the rejected-suffix rollback is a per-row
            pos truncation / valid_len-masked state commit, both traced).
            A slot whose drafter abstained (n_drafts == 0) degenerates to
            a plain decode step inside the same program, so mixed
            draft/no-draft iterations never fall back to a second
            dispatch. `filt` (False = no slot in the dispatch filters
            the vocabulary — the accept rule skips its per-row sorts) is
            the only static argument; the draft width k rides the
            drafts shape — one executable per (k, filt), zero recompiles
            in steady state.

            Inactive rows (free / mid-chunked-prefill) ride along frozen
            exactly like _decode_slots: valid_len 0 drops the KV scatter
            and freezes linear state, the truncate end sits past every
            real entry, and every carry passes through unchanged."""
            def one(tok, lcs, p, rng, recent, temp, tk, tp, pen, act,
                    draft, ndr):
                cache = {"layers": jax.tree_util.tree_map(
                    lambda a: a[None], lcs), "pos": p}
                tokens = jnp.concatenate([tok[None], draft])[None, :]
                rng2, sk = jax.random.split(rng)
                n_acc, nxt, commit, new_layers, recent2 = slot_verify(
                    cfg, params, cache, tokens, p,
                    jnp.where(act, ndr + 1, 0), draft, ndr, sk, recent,
                    temp, tk, tp, pen, filt, has_linear)
                if not has_linear:
                    new_layers = truncate_layers(
                        new_layers,
                        jnp.where(act, p + commit, jnp.int32(2**30)))
                new_lcs = jax.tree_util.tree_map(lambda a: a[0],
                                                 new_layers)
                return (jnp.where(act, nxt, tok),
                        jnp.where(act, n_acc, 0), commit, new_lcs,
                        jnp.where(act, rng2, rng),
                        jnp.where(act, recent2, recent))

            nxt, n_accs, adv, layers, rngs, recents = jax.vmap(one)(
                toks, layers, pos, rngs, recents, temps, top_ks, top_ps,
                penalties, active, drafts, n_drafts)
            return (jnp.stack([toks, n_accs, nxt]), layers, nxt, pos + adv,
                    rngs, recents)

        @functools.partial(jax.jit, static_argnames=("width",))
        def _slot_extract(layers, slot, start, width):
            return slot_extract_block_layers(layers, slot, start, width)

        @functools.partial(jax.jit, donate_argnums=(0,),
                           static_argnames=("block",))
        def _slot_restore(layers, chain, at, block):
            """at = [slot, first block, final] int32, one transfer: all
            traced, so ONE executable per piece length whatever they are."""
            return slot_restore_chain_layers(layers, chain, at[0], at[1],
                                             block, at[2] != 0)

        @functools.partial(jax.jit, donate_argnums=(2, 3, 4, 5))
        def _slot_join(logits, base_rng, toks, pos, rngs, recents, temps,
                       top_ks, top_ps, penalties, active, ints, floats):
            """A prompt's end as ONE program: derive the request's key from
            the engine's base key and its admission sequence number, sample
            the first token off the final chunk's `logits` [1, V] with the
            decode step's own sample_traced (empty recent window), and
            write every per-slot carry at `slot`. ints = [slot, seq, prompt
            length, top_k] int32, floats = [temperature, top_p, repeat
            penalty] f32: all traced, so ONE executable per pool shape
            whatever the slot and the sampling. Donates what _decode_slots
            donates; the engine keeps its own handles of the rest."""
            slot, seq, n, top_k = ints[0], ints[1], ints[2], ints[3]
            temp, top_p, pen = floats[0], floats[1], floats[2]
            rng, sk = jax.random.split(jax.random.fold_in(base_rng, seq))
            recent = jnp.full(recents.shape[1:], -1, jnp.int32)
            tid = sample_traced(logits[0], sk, temp, top_k, top_p, pen,
                                recent)
            return (toks.at[slot].set(tid), pos.at[slot].set(n),
                    rngs.at[slot].set(rng),
                    recents.at[slot].set(recent.at[-1].set(tid)),
                    temps.at[slot].set(temp), top_ks.at[slot].set(top_k),
                    top_ps.at[slot].set(top_p), penalties.at[slot].set(pen),
                    active.at[slot].set(True))

        # -- paged KV: decode/prefill through a block table ----------------
        # Full-attention KV lives in a shared physical block pool
        # ([num_blocks, block_tokens, ...] per layer); a slot addresses its
        # logical row through a TRACED [B, max_blocks] block table, so the
        # host-side allocator can remap/extend tables every iteration
        # without compiling anything new — `nb` (the slot-count bucket the
        # small per-slot `rows` are sliced to) is the only static
        # argument; the contiguous _decode_slots has none. SWA rings
        # and linear-attention state stay per-slot rows (`rows` pytree);
        # the gathered view reproduces the contiguous row's layout
        # byte-for-byte, so paged greedy decode is bit-identical to the
        # contiguous path (pinned in tests/test_paged.py).

        def _paged_row_cache(pool, rows_slot, table_row, p):
            """Batch-1 cache for one slot: pooled layers gathered through
            the table (masked to the row's frontier `p` — the write
            position, so the view holds exactly positions 0..p-1), row
            layers taken as-is (already the slot's rows)."""
            lcs = [paged_gather_layer(pl, table_row, p) if pl else rl
                   for pl, rl in zip(pool, rows_slot)]
            return {"layers": jax.tree_util.tree_map(
                lambda a: a[None], lcs), "pos": p}

        @functools.partial(jax.jit, static_argnames=("nb",),
                           donate_argnums=(1, 2, 4, 5, 6, 7))
        def _decode_slots_paged(params, pool, rows, tables, toks, pos, rngs,
                                recents, temps, top_ks, top_ps, penalties,
                                active, nb):
            """_decode_slots over a paged pool: per slot, gather the
            logical row view, run the same slot_step, then write back ONLY
            the block the step's KV landed in (position p lives in table
            entry p // bt). Inactive rows ride along with the write
            dropped (pid -> sentinel), so their pool bytes stay untouched
            just like the contiguous active-mask contract."""
            bt = next(pl["pos"].shape[1] for pl in pool if pl)
            nblocks = next(pl["pos"].shape[0] for pl in pool if pl)

            def one(table_row, rows_slot, tok, p, rng, recent, temp, tk,
                    tp, pen, act):
                cache = _paged_row_cache(pool, rows_slot, table_row, p)
                nxt, new_lcs, rng, recent = slot_step(
                    cfg, params, cache, tok, p, rng, recent, temp, tk, tp,
                    pen, act, mesh)
                wb = jnp.clip(p // bt, 0, table_row.shape[0] - 1)
                blks = [paged_block_of(lc, wb, bt) if pl else {}
                        for pl, lc in zip(pool, new_lcs)]
                new_rows = [{} if pl else lc
                            for pl, lc in zip(pool, new_lcs)]
                return nxt, blks, new_rows, wb, rng, recent

            step = active[:nb].astype(jnp.int32)
            rows_nb = jax.tree_util.tree_map(lambda a: a[:nb], rows)
            nxt, blks, new_rows, wbs, new_rngs, new_recents = jax.vmap(one)(
                tables[:nb], rows_nb, toks[:nb], pos[:nb], rngs[:nb],
                recents[:nb], temps[:nb], top_ks[:nb], top_ps[:nb],
                penalties[:nb], active[:nb])
            pids = jnp.take_along_axis(tables[:nb], wbs[:, None],
                                       axis=1)[:, 0]
            pids = jnp.where(active[:nb], pids, nblocks)   # inactive: drop
            pool = [paged_scatter_blocks(pl, pids, blk) if pl else pl
                    for pl, blk in zip(pool, blks)]
            rows = jax.tree_util.tree_map(
                lambda full, s: full.at[:nb].set(s), rows, new_rows)
            return (jnp.stack([toks[:nb], nxt]), pool, rows,
                    toks.at[:nb].set(nxt), pos.at[:nb].add(step),
                    rngs.at[:nb].set(new_rngs),
                    recents.at[:nb].set(new_recents))

        @functools.partial(jax.jit, donate_argnums=(2, 3),
                           static_argnames=("flash_mode",))
        def _prefill_slot_paged(params, tokens, pool, rows, tables, slot,
                                pos0, valid_len, flash_mode):
            """_prefill_slot over a paged pool: gather the slot's view,
            run the chunk forward, write back the blocks the chunk
            touched (a STATIC window of tokens.shape[1]//bt + 1 table
            entries, masked down to the traced [pos0 // bt, last written
            block] range), and update the slot's SWA/linear rows."""
            bt = next(pl["pos"].shape[1] for pl in pool if pl)
            nblocks = next(pl["pos"].shape[0] for pl in pool if pl)
            table_row = tables[slot]
            rows_slot = [jax.tree_util.tree_map(lambda a: a[slot], rl)
                         for rl in rows]
            logits, rcache = chunk_logits(
                cfg, params,
                _paged_row_cache(pool, rows_slot, table_row, pos0), tokens,
                pos0, valid_len, flash_mode, mesh)
            # the blocks the chunk wrote: a window sized statically by the
            # chunk bucket
            views = [jax.tree_util.tree_map(lambda a: a[0], nl) if pl else {}
                     for pl, nl in zip(pool, rcache["layers"])]
            pids, blks = paged_block_window(
                views, table_row, pos0, valid_len, tokens.shape[1] // bt + 1,
                bt, nblocks)
            new_pool = [paged_scatter_blocks(pl, pids, blk) if pl else pl
                        for pl, blk in zip(pool, blks)]
            new_rows = [rl if pl else jax.tree_util.tree_map(
                            lambda full, r: full.at[slot].set(r[0]), rl, nl)
                        for pl, rl, nl in zip(pool, rows, rcache["layers"])]
            return logits, new_pool, new_rows

        @functools.partial(jax.jit, static_argnames=("nb", "filt"),
                           donate_argnums=(1, 2, 4, 5, 6, 7))
        def _spec_slots_paged(params, pool, rows, tables, toks, pos, rngs,
                              recents, temps, top_ks, top_ps, penalties,
                              active, drafts, n_drafts, nb, filt):
            """_spec_slots over a paged pool: per slot, gather the logical
            row view through the block table, verify [input, drafts] at
            the slot's frontier, then write back ONLY the blocks holding
            the COMMITTED positions p .. p+n_acc — the block cursor moves
            by accepted length and speculative writes past it are dropped
            (rejected drafts' KV never reaches the pool: positions at or
            past the commit frontier are masked to -1 inside the written
            window, and blocks wholly past it fall outside the window).
            The engine must have reserved blocks for [p, p+n_drafts]
            before dispatch (speculative frontier reservation). Inactive
            rows ride along with every write dropped, exactly like
            _decode_slots_paged."""
            bt = next(pl["pos"].shape[1] for pl in pool if pl)
            nblocks = next(pl["pos"].shape[0] for pl in pool if pl)
            k = drafts.shape[1]

            def one(table_row, rows_slot, tok, p, rng, recent, temp, tk,
                    tp, pen, act, draft, ndr):
                cache = _paged_row_cache(pool, rows_slot, table_row, p)
                tokens = jnp.concatenate([tok[None], draft])[None, :]
                rng2, sk = jax.random.split(rng)
                n_acc, nxt, commit, new_layers, recent2 = slot_verify(
                    cfg, params, cache, tokens, p,
                    jnp.where(act, ndr + 1, 0), draft, ndr, sk, recent,
                    temp, tk, tp, pen, filt, has_linear)
                new_lcs = jax.tree_util.tree_map(lambda a: a[0], new_layers)
                # the blocks holding the committed positions: a window
                # sized statically by the draft width; an inactive slot
                # writes none
                pids, blks = paged_block_window(
                    [lc if pl else {} for pl, lc in zip(pool, new_lcs)],
                    table_row, p, commit, k // bt + 2, bt, nblocks)
                pids = jnp.where(act, pids, nblocks)
                # the speculative suffix never reaches the pool: a
                # swapped-out victim must not carry uncommitted KV
                blks = [{**blk, "pos": jnp.where(blk["pos"] >= p + commit,
                                                 -1, blk["pos"])}
                        if blk else blk for blk in blks]
                new_rows = [{} if pl else lc
                            for pl, lc in zip(pool, new_lcs)]
                return (jnp.where(act, nxt, tok),
                        jnp.where(act, n_acc, 0), commit, blks, new_rows,
                        pids, jnp.where(act, rng2, rng),
                        jnp.where(act, recent2, recent))

            rows_nb = jax.tree_util.tree_map(lambda a: a[:nb], rows)
            (nxt, n_accs, adv, blks, new_rows, pids, new_rngs,
             new_recents) = jax.vmap(one)(
                tables[:nb], rows_nb, toks[:nb], pos[:nb], rngs[:nb],
                recents[:nb], temps[:nb], top_ks[:nb], top_ps[:nb],
                penalties[:nb], active[:nb], drafts[:nb], n_drafts[:nb])
            flat_pids = pids.reshape(-1)        # [nb * nwb]
            pool = [paged_scatter_blocks(
                        pl, flat_pids, jax.tree_util.tree_map(
                            lambda a: a.reshape((-1,) + a.shape[2:]), blk))
                    if pl else pl
                    for pl, blk in zip(pool, blks)]
            rows = jax.tree_util.tree_map(
                lambda full, s: full.at[:nb].set(s), rows, new_rows)
            return (jnp.stack([toks[:nb], n_accs, nxt]), pool, rows,
                    toks.at[:nb].set(nxt), pos.at[:nb].add(adv),
                    rngs.at[:nb].set(new_rngs),
                    recents.at[:nb].set(new_recents))

        @jax.jit
        def _paged_row_snapshot(rows, slot):
            """Batch-1 copy of one slot's UNPOOLED state (SWA rings +
            linear conv/recurrent) — the boundary-exact snapshot the
            paged prefix cache stores per share unit (pooled layers
            share by block id instead and contribute no leaves here)."""
            return [jax.tree_util.tree_map(lambda a: a[slot][None], rl)
                    for rl in rows]

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _paged_row_install(rows, snap, slot):
            return [jax.tree_util.tree_map(
                lambda full, s: full.at[slot].set(s[0]), rl, sn)
                for rl, sn in zip(rows, snap)]

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _paged_row_reset(rows, slot):
            return slot_reset_layers(rows, slot)

        self._prefill = _prefill
        self._spec_verify = _spec_verify
        self._spec_slots = _spec_slots
        self._spec_slots_paged = _spec_slots_paged
        self._decode_slots = _decode_slots
        self._slot_assign = _slot_assign
        self._slot_reset = _slot_reset
        self._prefill_slot = _prefill_slot
        self._slot_extract = _slot_extract
        self._slot_restore = _slot_restore
        self._slot_join = _slot_join
        self._decode_slots_paged = _decode_slots_paged
        self._prefill_slot_paged = _prefill_slot_paged
        self._paged_row_snapshot = _paged_row_snapshot
        self._paged_row_install = _paged_row_install
        self._paged_row_reset = _paged_row_reset
        self._sample_traced = jax.jit(sample_traced)
        self._decode_chunk = _decode_chunk
        self._decode_until = _decode_until
        self._decode_step = _decode_step
        self._grow = _grow

    # -- cache / state ------------------------------------------------------

    def new_cache(self, batch: int = 1, kv_len: int | None = None):
        """kv_len bounds the KV buffers (cache-length bucket); defaults to
        the full max_cache_len (distributed master / parity-test paths)."""
        from ...parallel.sharding import init_cache_sharded
        return init_cache_sharded(self.mesh, self.cfg, batch,
                                  kv_len or self.max_cache_len, self.dtype)

    def _grow_to(self, cache, new_len: int):
        """Grow the KV bucket; re-pin shardings on the grown buffers (the
        jitted grow propagates input shardings, but pinning keeps the KV
        head axis split explicit rather than propagation-dependent)."""
        from ...parallel.sharding import shard_cache
        return shard_cache(self._grow(cache, new_len=new_len), self.mesh)

    # -- continuous-batching slot programs (serve engine) -------------------

    def decode_slots(self, layers, toks, pos, rngs, recents,
                     temps, top_ks, top_ps, penalties, active,
                     nb: int | None = None):
        """One batched sampled decode step over every pool row.

        layers: a pool cache's per-layer list (leaves [B, ...]); toks/pos:
        [B] int32; rngs: [B] PRNG keys; recents: [B, N] int32;
        temps/top_ps/penalties: [B] f32; top_ks: [B] int32 (>= vocab
        disables); active: [B] bool — False rows (free, or mid-chunked-
        prefill) are carried through untouched with their row state left
        byte-identical. All per-slot carries are device-resident and
        DONATED except `active` (the scheduler mutates it only at
        admission/release transitions and keeps its own handle). nb:
        accepted for the paged twin's callers and ignored — the program
        runs on all B rows in place at every occupancy, `active` alone
        says which rows step.
        Returns (packed_ids [2, B] = [input token ; sampled token] per
        slot — one fetch serves this step's ids AND any just-admitted
        slot's unfetched first token — then layers, toks, pos, rngs,
        recents).
        """
        del nb
        return self._decode_slots(self.params, layers, toks, pos, rngs,
                                  recents, temps, top_ks, top_ps, penalties,
                                  active)

    def prefill_chunk(self, layers, slot: int, token_ids, pos0: int):
        """Prefill one chunk of a prompt into pool row `slot` at absolute
        position pos0 (the serve engine's incremental admission step; the
        row must already hold exactly positions 0..pos0-1). The chunk is
        right-padded to a power-of-two bucket; flash dispatch follows the
        same host-static select_flash_mode as every other prefill path.
        Returns (logits [1, V] at the chunk's last valid position — only
        meaningful when this is the prompt's final chunk — and the updated
        pool layers)."""
        ids = np.asarray(list(token_ids), np.int32).ravel()
        n = int(ids.shape[0])
        cap = kv_capacity(self.cfg, {"layers": layers})
        bkt = check_prefill_bounds(n, pos0, cap, self.max_cache_len)
        padded = np.zeros((1, bkt), np.int32)
        padded[0, :n] = ids
        flash_mode = select_flash_mode(pos0, bkt, cap)
        self.last_chunk_attn = _chunk_attn(flash_mode, bkt)
        return self._prefill_slot(self.params, jnp.asarray(padded), layers,
                                  jnp.asarray(slot, jnp.int32),
                                  jnp.asarray(pos0, jnp.int32),
                                  jnp.asarray(n, jnp.int32),
                                  flash_mode=flash_mode)

    def slot_extract(self, layers, slot: int, start: int, width: int):
        """Copy the prefix block [start, start+width) out of pool row
        `slot` as a batch-1 layers pytree (prefix-cache insert). Static
        width: one executable per block size."""
        return self._slot_extract(layers, jnp.asarray(slot, jnp.int32),
                                  jnp.asarray(start, jnp.int32), width=width)

    def slot_restore(self, layers, chain: list, slot: int, first_block: int,
                     block: int, final: bool):
        """Restore a piece of a matched prefix chain (`chain`: consecutive
        `slot_extract` blocks of `block` tokens, the first one block
        `first_block` of the prompt) into pool row `slot` without resetting
        the rest of the row (prefix-cache hit; the pool is donated). ONE
        dispatch whatever the piece's length, and one executable per
        length: slot, first block and `final` are traced. `final` marks the
        chain's last piece, the only one whose last block installs its
        recurrent snapshot. The piece must be aligned (first_block a
        multiple of its length), which keeps its runs from wrapping in a
        ring (cache.restore_runs)."""
        if first_block % len(chain):
            raise ValueError(
                f"a piece of {len(chain)} blocks cannot start at block "
                f"{first_block}: pieces are aligned to their length")
        return self._slot_restore(
            layers, restore_reads(layers, chain, block),
            np.asarray([slot, first_block, final], np.int32), block=block)

    def slot_assign(self, layers, src_cache: dict, slot: int):
        """Re-home a batch-1 prefilled cache into pool row `slot` (row is
        reset first; pool is donated). One executable per source bucket."""
        return self._slot_assign(layers, src_cache["layers"],
                                 jnp.asarray(slot, jnp.int32))

    def slot_release(self, layers, slot: int):
        """Clear pool row `slot` (positions -1, state zeroed; donated)."""
        return self._slot_reset(layers, jnp.asarray(slot, jnp.int32))

    def sample_one(self, logits, rng, temp, top_k, top_p, penalty, recent):
        """Traced-parameter sampling of a single token (the engine's
        first-token sample off the prefill logits)."""
        return self._sample_traced(logits, rng, temp, top_k, top_p, penalty,
                                   recent)

    def slot_join(self, logits, base_rng, toks, pos, rngs, recents, temps,
                  top_ks, top_ps, penalties, active, *, slot: int, seq: int,
                  n: int, temp: float, top_k: int, top_p: float,
                  penalty: float):
        """Hand slot `slot` to the batched decode at a prompt's end: ONE
        dispatch and two small host arrays. `logits` [1, V] are the final
        chunk's; the key is fold_in(base_rng, seq); the first token, its
        recent window, position `n` and the sampling params (disabled
        values as sample_traced takes them: top_k >= vocab, top_p 1.0) are
        written at `slot`, which becomes active. toks, pos, rngs and
        recents are donated. Returns the nine carries in argument order."""
        return self._slot_join(
            logits, base_rng, toks, pos, rngs, recents, temps, top_ks,
            top_ps, penalties, active,
            jax.device_put(np.array([slot, seq, n, top_k], np.int32)),
            jax.device_put(np.array([temp, top_p, penalty], np.float32)))

    # -- paged-KV slot programs (serve engine, CAKE_KV_BLOCKS > 0) ----------

    def decode_slots_paged(self, pool, rows, tables, toks, pos, rngs,
                           recents, temps, top_ks, top_ps, penalties,
                           active, nb: int):
        """decode_slots over a paged pool: same carries and contract, but
        full-attention KV is read/written through `tables` ([B,
        max_blocks] int32 device array of physical block ids; entry ==
        num_blocks is unmapped). `pool`/`rows` come from
        cache.init_paged_layers and are donated; `tables` is NOT donated
        (the engine remaps entries between iterations and keeps its
        handle, like `active`). Returns (packed_ids [2, nb], pool, rows,
        toks, pos, rngs, recents)."""
        return self._decode_slots_paged(self.params, pool, rows, tables,
                                        toks, pos, rngs, recents, temps,
                                        top_ks, top_ps, penalties, active,
                                        nb=nb)

    def prefill_chunk_paged(self, pool, rows, tables, slot: int, token_ids,
                            pos0: int, ctx: int):
        """prefill_chunk over a paged pool: the chunk's KV scatters into
        the physical blocks `tables[slot]` maps for positions pos0..
        pos0+n-1 (the caller must have allocated them). `ctx` is the
        slot's logical row length (max_blocks * block_tokens) — the
        paged stand-in for the contiguous pool's buffer capacity.
        Returns (logits [1, V] at the last valid position, pool, rows)."""
        ids = np.asarray(list(token_ids), np.int32).ravel()
        n = int(ids.shape[0])
        bkt = check_prefill_bounds(n, pos0, ctx, self.max_cache_len)
        padded = np.zeros((1, bkt), np.int32)
        padded[0, :n] = ids
        flash_mode = select_flash_mode(pos0, bkt, ctx)
        self.last_chunk_attn = _chunk_attn(flash_mode, bkt)
        return self._prefill_slot_paged(self.params, jnp.asarray(padded),
                                        pool, rows, tables,
                                        jnp.asarray(slot, jnp.int32),
                                        jnp.asarray(pos0, jnp.int32),
                                        jnp.asarray(n, jnp.int32),
                                        flash_mode=flash_mode)

    def row_snapshot(self, rows, slot: int):
        """Batch-1 copy of slot `slot`'s unpooled state (SWA rings +
        linear conv/recurrent) — the paged prefix cache's boundary-exact
        share-unit snapshot (pooled layers share by block id instead)."""
        return self._paged_row_snapshot(rows, jnp.asarray(slot, jnp.int32))

    def row_install(self, rows, snap, slot: int):
        """Install a row_snapshot into slot `slot` (rows donated) — the
        final-block step of a paged prefix-cache hit."""
        return self._paged_row_install(rows, snap,
                                       jnp.asarray(slot, jnp.int32))

    def row_reset(self, rows, slot: int):
        """Clear slot `slot`'s unpooled state (rows donated) — the paged
        release/preempt wipe; pooled blocks need no wipe (the gather's
        stale-tenant pos guard makes freed blocks invisible)."""
        return self._paged_row_reset(rows, jnp.asarray(slot, jnp.int32))

    # -- speculative decoding ------------------------------------------------

    @staticmethod
    def _scfg_traced(scfg: SamplingConfig, vocab: int) -> tuple:
        """SamplingConfig -> the traced scalars the verify programs take
        (same disabled-value conventions as sample_traced)."""
        return (jnp.float32(scfg.temperature),
                jnp.int32(scfg.top_k or vocab),
                jnp.float32(scfg.top_p if scfg.top_p is not None else 1.0),
                jnp.float32(scfg.repeat_penalty))

    def verify_tokens(self, cache, last_token: int, draft_ids, k: int,
                      pos0: int, rng, recent, scfg: SamplingConfig):
        """One speculative verify step on a batch-1 cache: forward
        [last_token, draft...] (padded to a fixed k+1 width — ONE
        executable per k) at pos0, run the traced accept/reject rule, and
        commit exactly the accepted prefix (rejected-suffix KV rolled
        back in the same program). Returns (packed [2] = [n_acc,
        next_token], cache, recent') — one small fetch gives the host
        everything it needs to emit n_acc + 1 tokens."""
        draft = np.zeros((k,), np.int32)
        n_draft = min(len(draft_ids), k)
        draft[:n_draft] = np.asarray(list(draft_ids[:n_draft]), np.int32)
        cap = kv_capacity(self.cfg, cache)
        check_prefill_bounds(n_draft + 1, pos0, cap, self.max_cache_len)
        tokens = np.zeros((1, k + 1), np.int32)
        tokens[0, 0] = last_token
        tokens[0, 1:1 + n_draft] = draft[:n_draft]
        temp, top_k, top_p, pen = self._scfg_traced(scfg,
                                                    self.cfg.vocab_size)
        return self._spec_verify(self.params, jnp.asarray(tokens), cache,
                                 jnp.asarray(pos0, jnp.int32),
                                 jnp.asarray(n_draft + 1, jnp.int32),
                                 jnp.asarray(draft), rng, recent,
                                 temp, top_k, top_p, pen,
                                 filt=config_has_filters(scfg))

    def spec_slots(self, layers, toks, pos, rngs, recents, temps, top_ks,
                   top_ps, penalties, active, drafts, n_drafts,
                   nb: int | None = None, filt: bool = True):
        """Batched multi-token speculative verify over every pool row
        (the serve engine's speculative iteration unit — decode_slots'
        contract with a per-slot draft window, `nb` ignored as there:
        one program per (k, filt)). drafts: [B, k] int32
        (host-built proposals, right-padded); n_drafts: [B] int32 valid
        draft counts (0 = plain decode step for that slot). Acceptance is
        ragged per slot; each slot's carries advance by its own accepted
        length. `filt` (static): pass False when no slot in the dispatch
        uses top-k/top-p — the accept rule skips its per-row sorts.
        Returns (packed_ids [3, B] = [input token ; n_acc ; next token]
        per slot, layers, toks, pos, rngs, recents)."""
        del nb
        return self._spec_slots(self.params, layers, toks, pos, rngs,
                                recents, temps, top_ks, top_ps, penalties,
                                active, jnp.asarray(drafts, jnp.int32),
                                jnp.asarray(n_drafts, jnp.int32),
                                filt=bool(filt))

    def spec_slots_paged(self, pool, rows, tables, toks, pos, rngs,
                         recents, temps, top_ks, top_ps, penalties,
                         active, drafts, n_drafts, nb: int,
                         filt: bool = True):
        """spec_slots over a paged pool: same contract, KV read/written
        through `tables`. The caller must have reserved physical blocks
        covering each slot's speculative frontier [pos, pos + n_drafts]
        before dispatch; the program commits only the accepted prefix —
        the block cursor moves by accepted length and speculative writes
        past it are dropped. Returns (packed_ids [3, nb], pool, rows,
        toks, pos, rngs, recents)."""
        return self._spec_slots_paged(
            self.params, pool, rows, tables, toks, pos, rngs, recents,
            temps, top_ks, top_ps, penalties, active,
            jnp.asarray(drafts, jnp.int32),
            jnp.asarray(n_drafts, jnp.int32), nb=nb, filt=bool(filt))

    # -- inference ----------------------------------------------------------

    def _sp_size(self) -> int:
        m = self.mesh
        return (m.shape["sp"] if m is not None and "sp" in m.axis_names
                else 1)

    def _ring_ok(self) -> bool:
        """Ring prefill requires every layer full + windowless: SWA layers
        have no windowed flash under ring (their fallback is quadratic at
        exactly the lengths sp targets) and GDN scans would serialize over
        a sharded sequence."""
        return all(s.kind == "full" and s.window is None
                   for s in self.cfg.layer_specs())

    def prefill(self, cache, token_ids: Iterable[int], pos0: int = 0):
        ids = list(token_ids)
        n = len(ids)
        cap = kv_capacity(self.cfg, cache)
        bkt = check_prefill_bounds(n, pos0, cap, self.max_cache_len)
        padded = np.zeros((1, bkt), np.int32)
        padded[0, :n] = ids
        flash_mode = select_flash_mode(pos0, bkt, cap)
        # sequence-parallel prefill: with an sp mesh axis, fresh full-prompt
        # prefill runs ring attention (sequence sharded over sp, K/V blocks
        # rotating via collective permute) — the long-context path the
        # reference lacks. Decode is untouched: the cache scatter gathers
        # K/V back to the cache's own layout.
        if (flash_mode == "fresh" and self._sp_size() > 1
                and bkt % self._sp_size() == 0 and self._ring_ok()):
            flash_mode = "ring"
        self.last_prefill_mode = flash_mode
        logits, cache = self._prefill(self.params, jnp.asarray(padded), cache,
                                      jnp.asarray(pos0, jnp.int32),
                                      jnp.asarray(n, jnp.int32),
                                      flash_mode=flash_mode)
        return logits, cache

    def decode_logits(self, cache, token_id: int):
        """Single-token decode returning raw [B, V] logits."""
        return self._decode_step(self.params,
                                 jnp.asarray([token_id], jnp.int32), cache)

    def generate(self, prompt_ids: list[int], max_new_tokens: int = 256,
                 sampling: SamplingConfig | None = None,
                 on_token: Callable[[Token], None] | None = None,
                 chunk: int = 16, rng=None, spec=None,
                 spec_k: int | None = None) -> tuple[list[int], dict]:
        """Streamed generation. Returns (token_ids, stats).

        Without an `on_token` callback the whole decode runs as ONE device
        call (`_decode_until`: while_loop to EOS/budget, single fetch) —
        host syncs are stream-ordered, so their fixed cost is paid per
        call, not per token. With a callback,
        decode runs in on-device chunks of `chunk` tokens kept
        STREAM_DEPTH-deep in flight (the next chunk chains off the device
        carry, no host round trip), so tokens stream with bounded latency
        while fetch syncs overlap compute; EOS is checked between chunks.

        `spec` switches decode to SPECULATIVE mode (cake_tpu/spec/): a
        drafter proposes up to `spec_k` tokens per step (env CAKE_SPEC_K)
        and one bucketed verify step accepts a prefix of them — greedy
        output stays bit-identical, sampled output keeps the target
        distribution (see docs/speculative.md). Accepts a Drafter
        instance, "ngram", a draft TextModel, None (env CAKE_SPEC, off
        when unset) or False (force off, ignoring the env).
        """
        cfg = self.cfg
        scfg = sampling or SamplingConfig()
        rng = self._rng if rng is None else rng
        streaming = on_token is not None
        drafter = k_spec = None
        if spec is not False:
            from ...spec import resolve_drafter
            drafter, k_spec = resolve_drafter(spec, spec_k)
        # smallest bucket covering everything the first device call will
        # write — grown bucket-by-bucket below so decode never attends over
        # unused slots (the non-streaming path grows between segments)
        first_span = 1 + chunk if streaming else 1 + min(max_new_tokens,
                                                         self.UNTIL_SEGMENT)
        kv_len = bucket_for(len(prompt_ids) + first_span, self.max_cache_len)
        cache = self.new_cache(1, kv_len=kv_len)

        t0 = now()
        with RECORDER.span("prefill", cat="gen", tokens=len(prompt_ids)):
            logits, cache = self._prefill_start(prompt_ids, cache)
        rng, sk = jax.random.split(rng)
        recent = jnp.full((max(scfg.repeat_last_n, 1),), -1, jnp.int32)
        with RECORDER.span("sample", cat="phase"):
            first = sample(logits[0], sk, scfg, recent)
            recent = push_recent_token(recent, first)
            # lint: disable=host-sync — deliberate: TTFT is only honest if the
            # first token has actually reached the host
            tid = int(first)              # device sync: TTFT is honest
        ttft = now() - t0

        out: list[int] = [tid]
        tok_arr = first[None]
        if on_token:
            on_token(self._mk_token(tid))
        done = cfg.is_eos(tid)

        t1 = now()
        pos = len(prompt_ids)            # next write position (first token)
        spec_stats = None
        if drafter is not None:
            from ...spec.verify import spec_decode_loop
            out, spec_stats = spec_decode_loop(
                self, drafter, k_spec, prompt_ids, out, cache, kv_len,
                rng, recent, scfg, max_new_tokens, on_token, done)
        elif not streaming:
            # while_loop decode in cache-bucket-sized segments: each segment
            # is ONE device call filling the current KV bucket, then the
            # bucket grows — EOS waste stays bounded by the current bucket
            # and a long generation pays at most log2 extra syncs
            n_total = min(max_new_tokens - 1, self.max_cache_len - pos - 1)
            emitted = 0
            while not done and emitted < n_total:
                room = kv_len - pos - 1    # writes positions pos .. pos+n
                if room <= 0:
                    kv_len = bucket_for(pos + 2, self.max_cache_len)
                    cache = self._grow_to(cache, new_len=kv_len)
                    room = kv_len - pos - 1
                n_seg = min(n_total - emitted, room)
                with RECORDER.span("decode_segment", cat="gen",
                                   tokens=n_seg, pos=pos):
                    packed, cache, rng, recent = self._decode_until(
                        self.params, tok_arr, cache, rng, recent,
                        jnp.asarray(n_seg, jnp.int32), scfg,
                        bucket_for(n_seg, self.max_cache_len))
                    # lint: disable=host-sync — the non-streaming path's one fetch per
                    # SEGMENT (a whole while_loop decode burst), not per token
                    arr = np.asarray(packed)
                count = int(arr[0])
                seg = [int(t) for t in arr[1:1 + count]]
                out.extend(seg)
                emitted += count
                pos += count
                done = count < n_seg or (bool(seg) and cfg.is_eos(seg[-1]))
                if not done:
                    tok_arr = jnp.asarray([out[-1]], jnp.int32)
        else:
            # Pipelined streaming: chunk j+1 is dispatched off the DEVICE
            # carry (toks[-1:], cache, rng, recent) before chunk j's tokens
            # are fetched, so the fixed per-fetch sync latency overlaps the
            # next chunk's compute. Always run full chunks (one compiled
            # program); overshoot past EOS/max_new is discarded on the host
            # — wasted FLOPs bounded by STREAM_DEPTH chunks, zero recompiles.
            # Same total budget as the non-streaming path: full chunks while
            # they fit in the cache, then a sub-chunk cache-end remainder is
            # flushed through the while_loop program in one burst.
            n_rest = min(max_new_tokens - 1, self.max_cache_len - pos - 1)
            max_chunks = min(-(-n_rest // chunk),
                             (self.max_cache_len - pos) // chunk)
            budget = len(out) + n_rest
            inflight: deque = deque()
            disp = 0
            while not done:
                while len(inflight) < self.STREAM_DEPTH and disp < max_chunks:
                    if pos + chunk > kv_len:
                        kv_len = bucket_for(pos + chunk, self.max_cache_len)
                        cache = self._grow_to(cache, new_len=kv_len)
                    with RECORDER.span("decode_dispatch", cat="gen",
                                       tokens=chunk, pos=pos):
                        toks, cache, rng, recent = self._decode_chunk(
                            self.params, tok_arr, cache, rng, recent, scfg,
                            chunk)
                    tok_arr = toks[-1:]     # device-side chain, no fetch
                    pos += chunk
                    inflight.append(toks)
                    disp += 1
                if not inflight:
                    break
                with RECORDER.span("decode_wait", cat="gen"):
                    toks_np = np.asarray(inflight.popleft())
                for t in toks_np:
                    tid = int(t)
                    out.append(tid)
                    if on_token:
                        on_token(self._mk_token(tid))
                    if cfg.is_eos(tid) or len(out) >= budget:
                        done = True
                        break
            inflight.clear()                # EOS: drop overshoot chunks
            remainder = budget - len(out)
            if not done and remainder > 0:
                # cache-end tail smaller than a chunk: one while_loop call
                if pos + remainder > kv_len:
                    kv_len = bucket_for(pos + remainder, self.max_cache_len)
                    cache = self._grow_to(cache, new_len=kv_len)
                packed, cache, rng, recent = self._decode_until(
                    self.params, tok_arr, cache, rng, recent,
                    jnp.asarray(remainder, jnp.int32), scfg,
                    bucket_for(remainder, self.max_cache_len))
                # lint: disable=host-sync — cache-end remainder flush: one fetch for
                # the final sub-chunk burst
                arr = np.asarray(packed)
                for t in arr[1:1 + int(arr[0])]:
                    out.append(int(t))
                    if on_token:
                        on_token(self._mk_token(int(t)))
        dt = now() - t1
        stats = {
            "ttft_s": ttft,
            "decode_tokens": max(len(out) - 1, 0),
            "decode_s": dt,
            "tok_per_s": (len(out) - 1) / dt if dt > 0 and len(out) > 1 else 0.0,
        }
        if spec_stats is not None:
            stats.update(spec_stats)
        _observe_generation(stats, len(out), path="local")
        return out, stats

    def _prefill_start(self, prompt_ids, cache):
        return self.prefill(cache, prompt_ids)

    def _mk_token(self, tid: int) -> Token:
        text = None
        if self.tokenizer is not None:
            try:
                text = self.tokenizer.decode([tid])
            except Exception:
                text = None
        return Token(id=tid, text=text, is_end_of_stream=self.cfg.is_eos(tid))

    # -- chat ---------------------------------------------------------------

    def chat_generate(self, messages: list[dict], **kw):
        """Apply the tokenizer's chat template (fallback: ChatML —
        ref: models/common/chatml_history.rs) and generate."""
        return self.generate(chat_prompt_ids(self.tokenizer, messages), **kw)


def chat_prompt_ids(tokenizer, messages: list[dict]) -> list[int]:
    """messages -> token ids via the tokenizer's chat template when it has
    one (CakeTokenizer.apply_chat), else the ChatML fallback."""
    if hasattr(tokenizer, "apply_chat"):
        prompt = tokenizer.apply_chat(messages)
        if hasattr(tokenizer, "encode_chat_prompt"):
            return list(tokenizer.encode_chat_prompt(prompt))
    else:
        prompt = render_chat(tokenizer, messages)
    enc = tokenizer.encode(prompt)
    return list(enc.ids if hasattr(enc, "ids") else enc)


def render_chat(tokenizer, messages: list[dict]) -> str:
    """ChatML fallback template (ref: chatml_history.rs)."""
    parts = []
    for m in messages:
        parts.append(f"<|im_start|>{m['role']}\n{m['content']}<|im_end|>\n")
    parts.append("<|im_start|>assistant\n")
    return "".join(parts)


def continuation_prompt_ids(tokenizer, messages: list[dict]) -> list[int]:
    """Continuation-mode templating: the FINAL message is a partial
    assistant turn (role=assistant, `"continue": true`) and the prompt
    must end INSIDE it — the history is templated with its normal
    generation prompt (one assistant header) and the partial content is
    appended verbatim, with no second assistant header and no
    end-of-turn token. The engine then prefills prompt + partial and
    decode continues the same message: a greedy continuation is
    bit-identical to the stream that was never broken (the fleet
    router's mid-stream resume splice, and any client finishing a
    broken stream by hand, both ride this)."""
    head, partial = messages[:-1], str(messages[-1].get("content") or "")
    if hasattr(tokenizer, "apply_chat"):
        prompt = tokenizer.apply_chat(head) + partial
        if hasattr(tokenizer, "encode_chat_prompt"):
            return list(tokenizer.encode_chat_prompt(prompt))
    else:
        prompt = render_chat(tokenizer, head) + partial
    enc = tokenizer.encode(prompt)
    return list(enc.ids if hasattr(enc, "ids") else enc)
