"""Master node: cluster bring-up and distributed text generation.

Bring-up (ref: cake-core/src/cake/sharding/mod.rs master_setup:162-506):
discover workers -> estimate per-layer bytes from safetensors headers ->
TFLOPS-proportional assignment -> connect + authenticate + assign ->
stream the worker's layer-subset weights (zstd+CRC32, content-keyed cache)
-> await worker_ready. The master keeps unassigned layers, the embeddings
and the head (ref: Context VarBuilder excluding worker layers).

Generation (ref: master.rs:109-171 + text_model.rs forward loop): the stage
chain [local ranges | remote workers] runs per token; each local range is
one jit call, each remote range one TCP round trip; embeddings, head and
sampling stay on the master device.
"""
from __future__ import annotations

import functools
import logging
import os
import random
import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .. import knobs
from ..models.common.config import ModelConfig
from ..models.common.text_model import (PREFILL_BUCKETS, PREFILL_CHUNK,
                                        LocalStage, Token,
                                        _observe_generation, bucket_for,
                                        check_prefill_bounds,
                                        initial_kv_bucket,
                                        select_flash_mode)
from ..models.common.layers import (embed_tokens, forward_layers,
                                    lm_head_logits)
from ..obs import (CLUSTER_DEGRADED, CLUSTER_RECONNECTS, CLUSTER_REPLAYS,
                   RECORDER, now)
from ..ops.sampling import SamplingConfig, push_recent_token, sample
from .auth import cluster_hash
from .client import RemoteStage, StageFailure
from .strategy import DefaultStrategy, WorkerCapacity, estimate_layer_bytes
from .topology import Topology
from . import proto, transfer

log = logging.getLogger("cake_tpu.master")

# cap on the recovery reconnect backoff — failures past the first few
# retries are probed by the background restore loop instead
RECOVERY_BACKOFF_CAP_S = 10.0


class ClusterDegradedError(RuntimeError):
    """A worker is down and the recovery retry budget is exhausted: the
    request fails FAST (instead of hanging on reconnect loops), /health
    answers 503, and the background restore loop keeps probing the dead
    worker so a later request can succeed."""


@dataclass
class Stage:
    kind: str                  # "local" | "remote"
    start: int
    end: int
    runner: object             # LocalStage or RemoteStage
    cache: object = None       # local KV cache (remote keeps its own)


class DistributedTextModel:
    """TextModel over a stage chain. Single local stage == plain TextModel
    semantics; remote stages hop hidden states over the wire."""


    def __init__(self, cfg: ModelConfig, master_params: dict,
                 stages: list[Stage], tokenizer=None, dtype=jnp.bfloat16,
                 max_cache_len: int = 2048, seed: int = 42, mesh=None,
                 prefill_chunk: int | None = None,
                 recovery_retries: int | None = None,
                 recovery_backoff_s: float | None = None,
                 restore_interval_s: float | None = None):
        self.cfg = cfg
        self.stages = stages
        # mid-stream fault tolerance: how many quarantine->reconnect->
        # replay cycles one generation may spend before failing fast
        # (CAKE_RECOVERY_RETRIES), the base of the capped-exponential
        # jittered reconnect backoff (CAKE_RECOVERY_BACKOFF_S), and the
        # background restore loop's probe interval once degraded
        # (CAKE_RESTORE_INTERVAL_S)
        self.recovery_retries = recovery_retries if recovery_retries \
            is not None else knobs.get("CAKE_RECOVERY_RETRIES")
        self.recovery_backoff_s = recovery_backoff_s if recovery_backoff_s \
            is not None else knobs.get("CAKE_RECOVERY_BACKOFF_S")
        self.restore_interval_s = restore_interval_s if restore_interval_s \
            is not None else knobs.get("CAKE_RESTORE_INTERVAL_S")
        # serializes channel revival: _recover and the restore loop must
        # not reestablish() the same worker concurrently. NEVER guards
        # the flags below — reestablish() spans reconnect + weight
        # re-push + wait_ready (minutes), and a flag read blocking on it
        # would break generate()'s fail-fast contract
        self._revive_lock = threading.Lock()
        # guards the degraded flag + restore-thread handle (request
        # threads flip the flag, the restore loop clears it; the
        # lock-discipline lint enforces the guarded-by annotations).
        # Held only for flag reads/writes — always cheap, never across
        # network or device work
        self._degraded_lock = threading.Lock()
        # {worker, since, error} while a worker is quarantined with the
        # retry budget exhausted; /health 503s on it and generate() fails
        # fast until the restore loop revives the worker. Out-of-class
        # readers go through degraded_info()
        self.degraded: dict | None = None           # guarded-by: self._degraded_lock
        self._restore_thread: threading.Thread | None = None  # guarded-by: self._degraded_lock
        self._recoveries = 0            # per-generation, surfaced in stats
        self._replays = 0
        self._gen_prompt: list[int] = []   # recorded token sequence the
        self._gen_out: list[int] = []      # rebuild-by-replay replays
        self.tokenizer = tokenizer
        self.dtype = dtype
        # clamp like TextModel: positions past max_seq_len would silently
        # mis-index the rope tables (out-of-range gathers clamp, not raise)
        self.max_cache_len = min(max_cache_len, cfg.max_seq_len)
        self.mesh = mesh
        # pipelined-prefill chunk width; PREFILL_CHUNK is what workers
        # compile-warm, so overriding trades a first-request in-band
        # compile for the chosen width
        self.prefill_chunk = prefill_chunk or PREFILL_CHUNK
        self._last_prefill: dict = {}
        self._kv_len = self.max_cache_len   # reset()/generate() re-bucket
        # embed + head replicate over the in-host tp mesh so the hidden
        # state entering/leaving the sharded local stages is replicated
        from ..parallel.sharding import shard_params
        self.params = shard_params(master_params, mesh)  # embed + head
        self._rng = jax.random.PRNGKey(seed)

        @jax.jit
        def _embed(params, tokens):
            return embed_tokens(cfg, params, tokens)

        @jax.jit
        def _head(params, x_last):
            return lm_head_logits(cfg, params, x_last)[:, 0]

        self._embed = _embed
        self._head = _head
        self._sample = jax.jit(
            lambda l, k, rec, scfg: sample(l, k, scfg, rec),
            static_argnames=("scfg",))

    # -- lifecycle ----------------------------------------------------------

    def reset(self, kv_len: int | None = None):
        """Fresh caches everywhere; local stage caches start at the given
        cache-length bucket and grow bucket-by-bucket during decode (same
        lever as TextModel's growth bucketing — short generations never
        attend over max_cache_len of mostly-empty buffer)."""
        from ..parallel.sharding import init_cache_sharded
        self._kv_len = min(kv_len or self.max_cache_len, self.max_cache_len)
        for s in self.stages:
            if s.kind == "local":
                s.cache = init_cache_sharded(
                    self.mesh, self.cfg, 1, self._kv_len, self.dtype,
                    (s.start, s.end))
            else:
                s.runner.goodbye()

    def _grow_local(self, new_len: int):
        from ..models.common.cache import grow_cache
        from ..parallel.sharding import shard_cache
        new_len = min(new_len, self.max_cache_len)
        if new_len <= self._kv_len:
            return
        for s in self.stages:
            if s.kind == "local":
                s.cache = shard_cache(
                    grow_cache(self.cfg, s.cache, new_len,
                               (s.start, s.end)), self.mesh)
        self._kv_len = new_len

    # -- forward ------------------------------------------------------------

    def _stage_forward(self, s: Stage, x, pos0: int, valid_len: int | None):
        """One stage hop — the single definition of local/remote dispatch
        (dtype cast, flash-mode selection, kv hint) shared by the
        sequential chain and the pipelined prefill threads."""
        with RECORDER.span("layers", cat="phase", kind=s.kind,
                           start=s.start, end=s.end,
                           worker=getattr(s.runner, "name", "")):
            return self._stage_forward_inner(s, x, pos0, valid_len)

    def _stage_forward_inner(self, s: Stage, x, pos0: int,
                             valid_len: int | None):
        if s.kind == "local":
            # local prefill stages flash like TextModel.prefill
            # (full-length unwrapped caches)
            flash_mode = "off"
            if valid_len is not None:
                flash_mode = select_flash_mode(pos0, x.shape[1],
                                               self._kv_len)
            x, s.cache = s.runner.forward_hidden(
                jnp.asarray(x).astype(self.dtype), s.cache,
                jnp.asarray(pos0, jnp.int32),
                None if valid_len is None
                else jnp.asarray(valid_len, jnp.int32),
                flash_mode=flash_mode)
            return x
        # kv hint keeps the worker's per-connection cache bucket aligned
        # with the master's, so growth reallocs land on the same
        # (pre-warmed) bucket boundaries on every node
        # lint: disable=host-sync — remote hop: the hidden state must become
        # host bytes to cross the wire (this IS the pipeline's transfer point)
        x, _ = s.runner.forward_hidden(np.asarray(x), None, pos0, valid_len,
                                       kv_hint=self._kv_len)
        return x

    def _run_stages(self, x, pos0: int, valid_len: int | None):
        for s in self.stages:
            x = self._stage_forward(s, x, pos0, valid_len)
        return x

    def prefill_logits(self, token_ids: list[int], pos0: int = 0):
        n = len(token_ids)
        bkt = check_prefill_bounds(n, pos0, self._kv_len, self.max_cache_len)
        # pipelined chunked prefill when the chain has remote hops and the
        # prompt spans >= 2 chunks: decode is irreducibly sequential (token
        # t+1 needs token t's sample) but prefill is not — chunk c+1 runs
        # on stage s while chunk c is on stage s+1, hiding wire+compute of
        # every stage but the slowest
        cw = self.prefill_chunk
        if (pos0 == 0 and n > cw
                and (-(-n // cw)) * cw <= self._kv_len  # padded chunks fit
                and any(s.kind == "remote" for s in self.stages)):
            return self._prefill_pipelined(token_ids)
        self._last_prefill = {"pipelined": False, "chunks": 1, "width": bkt}
        padded = np.zeros((1, bkt), np.int32)
        padded[0, :n] = token_ids
        x = self._embed(self.params, jnp.asarray(padded))
        x = self._run_stages(x, pos0, n)
        x = jnp.asarray(x)[:, n - 1:n]
        return self._head(self.params, x.astype(self.dtype))

    def _prefill_pipelined(self, token_ids: list[int]):
        """Stream the prompt through the stage chain in PREFILL_CHUNK-token
        slices, one thread per stage (plus a feeder): the blocking remote
        round trips of different stages overlap, so long-prompt TTFT
        approaches max-stage time instead of sum-of-stages. Queues are
        unbounded — a failed stage can then never deadlock its upstream;
        in-flight memory is bounded by n_chunks hidden-state slices."""
        import queue as _queue
        import threading

        cw = self.prefill_chunk
        n = len(token_ids)
        n_chunks = -(-n // cw)
        self._last_prefill = {"pipelined": True, "chunks": n_chunks,
                              "width": cw}
        qs = [_queue.Queue() for _ in range(len(self.stages) + 1)]
        errs: list[Exception] = []

        def feed():
            try:
                for ci in range(n_chunks):
                    lo = ci * cw
                    ids = token_ids[lo:lo + cw]
                    padded = np.zeros((1, cw), np.int32)
                    padded[0, :len(ids)] = ids
                    x = self._embed(self.params, jnp.asarray(padded))
                    qs[0].put((x, lo, len(ids)))
            except Exception as e:     # noqa: BLE001 — surfaced below
                errs.append(e)
            finally:
                qs[0].put(None)

        def run_stage(i: int, s: Stage):
            try:
                while True:
                    item = qs[i].get()
                    if item is None:
                        break
                    x, p0, vl = item
                    qs[i + 1].put((self._stage_forward(s, x, p0, vl), p0, vl))
            except Exception as e:     # noqa: BLE001 — surfaced below
                errs.append(e)
            finally:
                qs[i + 1].put(None)

        threads = [threading.Thread(target=feed, daemon=True)] + [
            threading.Thread(target=run_stage, args=(i, s), daemon=True)
            for i, s in enumerate(self.stages)]
        for t in threads:
            t.start()
        last = None
        while True:
            item = qs[-1].get()
            if item is None:
                break
            last = item
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        if last is None:
            raise RuntimeError("pipelined prefill produced no output")
        x, _, vl = last
        x = jnp.asarray(x)[:, vl - 1:vl]
        return self._head(self.params, x.astype(self.dtype))

    def decode_logits(self, token_id: int, pos: int):
        with RECORDER.span("embed", cat="phase"):
            x = self._embed(self.params, jnp.asarray([[token_id]], jnp.int32))
        x = self._run_stages(x, pos, None)
        with RECORDER.span("lm_head", cat="phase"):
            return self._head(self.params,
                              jnp.asarray(x)[:, -1:].astype(self.dtype))

    # -- generation ---------------------------------------------------------

    def generate(self, prompt_ids: list[int], max_new_tokens: int = 256,
                 sampling: SamplingConfig | None = None, on_token=None,
                 rng=None, **_):
        # a degraded cluster fails FAST: the retry budget was already
        # spent, and the background restore loop owns the dead worker —
        # burning every request's latency on doomed reconnects would turn
        # one dead node into a full outage
        d = self.degraded_info()
        if d is not None:
            raise ClusterDegradedError(
                f"cluster degraded: worker {d['worker']} down for "
                f"{now() - d['since']:.0f}s ({d['error']}); "
                "restore loop is probing")
        scfg = sampling or SamplingConfig()
        rng = self._rng if rng is None else rng
        # initial bucket covers prompt + first sampled token + a short run
        # of decode (same sizing idea as TextModel's first_span): the first
        # growth — a realloc on master AND every worker — should not land
        # within the opening tokens of decode
        self.reset(kv_len=initial_kv_bucket(len(prompt_ids), max_new_tokens,
                                            self.max_cache_len))
        # per-generation RTT windows: the stats this generate returns (and
        # /api/v1/stats re-serves as "last generation") must not blend in
        # samples from earlier generations
        for s in self.stages:
            if s.kind == "remote":
                s.runner.rtts.clear()
        out: list[int] = []
        # recovery bookkeeping: the recorded token sequence is exactly
        # what rebuild-by-replay prefills after a worker loss (`out` is
        # aliased, so appends below keep the record current)
        self._gen_prompt = list(prompt_ids)
        self._gen_out = out
        self._recoveries = self._replays = 0
        recent = jnp.full((max(scfg.repeat_last_n, 1),), -1, jnp.int32)

        t0 = now()
        with RECORDER.span("prefill", cat="gen", tokens=len(prompt_ids)):
            try:
                logits = self.prefill_logits(prompt_ids)
            except StageFailure as e:
                logits = self._recover(e, max_new_tokens)
        with RECORDER.span("sample", cat="phase"):
            rng, sk = jax.random.split(rng)
            tok = self._sample(logits[0], sk, recent, scfg)
            recent = push_recent_token(recent, tok)
        ttft = now() - t0

        pos = len(prompt_ids)
        # lint: disable=host-sync — first-token fetch keeps TTFT honest (same
        # contract as TextModel.generate)
        tid = int(tok)
        out.append(tid)
        if on_token:
            on_token(self._mk_token(tid))

        t1 = now()
        budget = self.max_cache_len - len(prompt_ids) - 1
        max_new_tokens = min(max_new_tokens, max(budget, 1))
        while not self.cfg.is_eos(tid) and len(out) < max_new_tokens:
            if pos + 1 > self._kv_len:
                self._grow_local(bucket_for(pos + 2, self.max_cache_len))
            with RECORDER.span("decode_token", cat="gen", pos=pos):
                try:
                    logits = self.decode_logits(tid, pos)
                except StageFailure as e:
                    # replay leaves every cache holding positions
                    # 0..pos and returns exactly the logits this failed
                    # decode owed — the loop continues none the wiser
                    logits = self._recover(e, max_new_tokens - len(out))
                with RECORDER.span("sample", cat="phase"):
                    rng, sk = jax.random.split(rng)
                    tok = self._sample(logits[0], sk, recent, scfg)
                    recent = push_recent_token(recent, tok)
                    # lint: disable=host-sync — the distributed loop is host-driven by
                    # design: the sampled id must reach the host to feed the next hop's
                    # wire frame (one small fetch per token)
                    tid = int(tok)
            pos += 1
            out.append(tid)
            if on_token:
                on_token(self._mk_token(tid))
        dt = now() - t1
        stats = {"ttft_s": ttft, "decode_tokens": len(out) - 1,
                 "decode_s": dt, "prefill": dict(self._last_prefill),
                 "tok_per_s": (len(out) - 1) / dt if dt > 0 else 0.0,
                 "recoveries": self._recoveries, "replays": self._replays,
                 "stage_rtts": {
                     f"{s.runner.name}[{s.start}:{s.end}]":
                         s.runner.rtt_stats()
                     for s in self.stages if s.kind == "remote"}}
        _observe_generation(stats, len(out), path="cluster")
        return out, stats

    # -- mid-stream fault recovery ------------------------------------------

    def _remote_stage(self, worker: str) -> Stage | None:
        return next((s for s in self.stages
                     if s.kind == "remote" and s.runner.name == worker), None)

    def _recover(self, failure: StageFailure, remaining_new: int):
        """Quarantine the failed stage, reconnect with capped exponential
        backoff + jitter (re-auth + re-assign; weight push skipped while
        the worker acks its content-keyed cache), then rebuild ALL stage
        caches with one replay prefill. Returns the logits the failed op
        owed. Retry budget exhausted => mark the cluster degraded and
        raise ClusterDegradedError."""
        worker = failure.worker
        last: Exception = failure
        log.warning("stage failure (%s): %s — starting recovery",
                    failure.kind, failure)
        for attempt in range(self.recovery_retries):
            if attempt:
                wait = min(self.recovery_backoff_s * (2 ** (attempt - 1)),
                           RECOVERY_BACKOFF_CAP_S)
                # jitter so a fleet of masters doesn't reconnect-stampede
                # a worker that just came back
                time.sleep(wait * random.uniform(0.75, 1.25))
            if isinstance(last, StageFailure):
                worker = last.worker
            try:
                stage = self._remote_stage(worker)
                if stage is not None:
                    with RECORDER.span("recover", cat="gen", worker=worker,
                                       attempt=attempt):
                        with self._revive_lock:
                            stage.runner.reestablish()
                    CLUSTER_RECONNECTS.inc(worker=worker)
                    log.info("worker %s reconnected (attempt %d)", worker,
                             attempt + 1)
                logits = self._replay(remaining_new)
                self._recoveries += 1
                return logits
            except (StageFailure, OSError, RuntimeError,
                    proto.ProtocolError) as e:
                log.warning("recovery attempt %d/%d for %s failed: %s",
                            attempt + 1, self.recovery_retries, worker, e)
                last = e
        self._mark_degraded(worker, last)
        raise ClusterDegradedError(
            f"worker {worker} unrecoverable after "
            f"{self.recovery_retries} attempts: {last}") from last

    def _replay(self, remaining_new: int):
        """Rebuild-by-replay: worker KV is per-connection and died with
        the socket, so every stage cache is reset and the recorded token
        sequence (prompt + everything generated so far) is replayed
        through ONE pipeline prefill. The final position's logits are
        exactly what the failed op would have produced — greedy
        continuation is bit-identical to an unfailed run, and recovery
        costs one prefill no matter when the failure hit."""
        seq = self._gen_prompt + self._gen_out
        self.reset(kv_len=initial_kv_bucket(len(seq), remaining_new,
                                            self.max_cache_len))
        with RECORDER.span("replay_prefill", cat="gen", tokens=len(seq)):
            logits = self.prefill_logits(seq)
        self._replays += 1
        CLUSTER_REPLAYS.inc()
        return logits

    def degraded_info(self) -> dict | None:
        """Locked read of the degraded flag for out-of-class readers
        (/health, generate()'s fail-fast check) — the lock is only ever
        held for flag flips, so this never blocks on recovery work."""
        with self._degraded_lock:
            return self.degraded

    def _mark_degraded(self, worker: str, error: Exception):
        with self._degraded_lock:
            self.degraded = {"worker": worker, "since": now(),
                             "error": str(error)}
            if self._restore_thread is None \
                    or not self._restore_thread.is_alive():
                # started under the lock: the loop's first read blocks
                # until this block publishes the flag, never deadlocks
                self._restore_thread = threading.Thread(
                    target=self._restore_loop, daemon=True,
                    name="cake-restore")
                self._restore_thread.start()
        CLUSTER_DEGRADED.set(1.0)
        log.error("cluster degraded: worker %s unrecoverable (%s); "
                  "restore loop probing every %.1fs", worker, error,
                  self.restore_interval_s)

    def _restore_loop(self):
        """Background probe of the quarantined worker: on success the
        degraded flag clears and the NEXT request proceeds normally (its
        reset/prefill rebuilds all state — no replay needed between
        requests)."""
        while True:
            with self._degraded_lock:
                info = self.degraded
            if info is None:
                return
            time.sleep(self.restore_interval_s)
            with self._degraded_lock:
                info = self.degraded
            if info is None:
                return
            stage = self._remote_stage(info["worker"])
            if stage is None:
                with self._degraded_lock:
                    self.degraded = None
                CLUSTER_DEGRADED.set(0.0)
                return
            try:
                with self._revive_lock:
                    stage.runner.reestablish()
                CLUSTER_RECONNECTS.inc(worker=info["worker"])
                with self._degraded_lock:
                    self.degraded = None
                CLUSTER_DEGRADED.set(0.0)
                log.info("worker %s restored; cluster healthy again",
                         info["worker"])
                return
            except Exception as e:
                log.debug("restore probe for %s failed: %s",
                          info["worker"], e)

    def _mk_token(self, tid: int) -> Token:
        text = None
        if self.tokenizer is not None:
            try:
                text = self.tokenizer.decode([tid])
            except Exception:
                pass
        return Token(id=tid, text=text, is_end_of_stream=self.cfg.is_eos(tid))

    def chat_generate(self, messages: list[dict], **kw):
        from ..models.common.text_model import chat_prompt_ids
        return self.generate(chat_prompt_ids(self.tokenizer, messages), **kw)


# ---------------------------------------------------------------------------
# Cluster bring-up
# ---------------------------------------------------------------------------


@dataclass
class MasterSetup:
    cfg: ModelConfig
    topology: Topology
    stages: list[Stage]
    master_params: dict
    clients: list[RemoteStage] = field(default_factory=list)


def plan_assignments(cfg: ModelConfig, storage, workers: list[dict],
                     quant_factor: float = 1.0) -> dict[str, tuple[int, int]]:
    """TFLOPS-proportional contiguous ranges from discovery replies."""
    caps = [WorkerCapacity(name=w["name"],
                           memory_bytes=w["caps"]["memory_bytes"],
                           tflops=w["caps"]["tflops"],
                           backend=w["caps"].get("backend", "tpu"))
            for w in workers]
    layer_bytes = estimate_layer_bytes(storage, cfg.num_hidden_layers,
                                       quant_factor)
    plan = DefaultStrategy().assign_layers(
        caps, list(range(cfg.num_hidden_layers)), layer_bytes)
    out = {}
    for name, layers in plan.items():
        if layers:
            out[name] = (min(layers), max(layers) + 1)
    return out


def master_setup(model_dir: str, cluster_key: str, cfg: ModelConfig,
                 workers: list[dict],
                 assignments: dict[str, tuple[int, int]] | None = None,
                 dtype_str: str = "bf16", max_cache_len: int = 2048,
                 push_weights: bool = True,
                 master_device_fraction_reserved: float = 0.1,
                 fp8_native: bool = False, mesh=None,
                 warm: str = "full") -> MasterSetup:
    """Connect/auth/assign/push to each worker; build the stage chain.

    workers: discovery replies ({"name", "host", "port", "caps"}).
    fp8_native: stream the checkpoint's f8e4m3 tensors verbatim (the wire
    already carries raw safetensors bytes, so FP8 stays 1 byte/param in
    transit) and have every node keep them native in HBM with per-layer
    dequant fused into the matmuls (ref: native_dtype_backend.rs through
    sharding/mod.rs push_model_data).
    """
    import json
    import os

    from ..utils.loaders import load_model_params
    from ..utils.safetensors_io import TensorStorage

    storage = TensorStorage.from_model_dir(model_dir)
    if assignments is None:
        assignments = plan_assignments(cfg, storage, workers)
    with open(os.path.join(model_dir, "config.json")) as f:
        config_raw = json.load(f)
    mhash = transfer.model_hash(model_dir)
    ckey = transfer.cache_key(cluster_hash(cluster_key), mhash)

    # workers sorted by their range start -> stage order
    ordered = sorted(((name, rng) for name, rng in assignments.items()),
                     key=lambda kv: kv[1][0])
    clients: list[RemoteStage] = []
    worker_by_name = {w["name"]: w for w in workers}
    n = cfg.num_hidden_layers

    try:
        for name, (start, end) in ordered:
            w = worker_by_name[name]
            client = RemoteStage(w["host"], w["port"], cluster_key,
                                 name).connect()
            # registered immediately: a failure anywhere below (this worker
            # or a later one) must not leak the already-open sockets and
            # their per-connection server state
            clients.append(client)
            names = transfer.subset_tensor_names(storage, start, end, n,
                                                 include_embed=False,
                                                 include_head=False)
            # expected sizes always sent so the worker can validate its
            # cache even when pushing is disabled (header-only synthesis:
            # no data read)
            total, _ = transfer.synthesize_safetensors(storage, names)
            expected = {"model.safetensors": total}
            assignment = proto.layer_assignment(
                model_id=mhash, arch=cfg.arch, config=config_raw,
                start=start, end=end, dtype=dtype_str, cache_key=ckey,
                push_weights=push_weights, fp8_native=fp8_native)
            assignment["max_cache_len"] = max_cache_len
            assignment["expected_files"] = expected
            # "full": workers compile every growth bucket's decode + prefill
            # shape during setup so serving never pays an in-band compile;
            # "decode": smallest-bucket decode only (fast setup); "none"
            assignment["warm"] = warm
            # recovery memory: a mid-generation reconnect replays this
            # exact assignment (the worker's content-keyed weight cache
            # makes the push a no-op; the repush thunk covers a worker
            # that lost the cache too, e.g. a rebuilt host)
            client.assignment = assignment
            client.repush = functools.partial(_repush_weights, model_dir,
                                              names)
            resp = client.assign(assignment)
            if resp.get("t") == "worker_error":
                raise RuntimeError(f"worker {name}: {resp['error']}")
            if push_weights and not transfer_cached(resp):
                start_off = (resp.get("resume") or {}).get(
                    "model.safetensors", 0)
                total, chunks = transfer.synthesize_safetensors(storage,
                                                                names)
                client.push_weights(
                    transfer.encode_chunks("model.safetensors", total,
                                           chunks, start_offset=start_off))
            client.wait_ready()
            log.info("worker %s ready with layers [%d,%d)", name, start, end)

        # master keeps the unassigned layers
        assigned = set()
        for start, end in assignments.values():
            assigned |= set(range(start, end))
        master_layers = [i for i in range(n) if i not in assigned]

        # build the ordered stage chain
        stages: list[Stage] = []
        ranges: list[tuple[str, int, int, object]] = []
        for name, (start, end) in ordered:
            ranges.append(("remote", start, end,
                           clients[[nm for nm, _ in ordered].index(name)]))
        for lo, hi in _contiguous(master_layers):
            ranges.append(("local", lo, hi, None))
        ranges.sort(key=lambda r: r[1])

        dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32,
                 "f16": jnp.float16}.get(dtype_str, jnp.bfloat16)
        quant = None
        if fp8_native:
            from ..utils.quant import fp8_native_quant
            quant = fp8_native_quant()
        master_params = load_model_params(cfg, model_dir, dtype, quant=quant,
                                          layer_range=(0, 0),
                                          include_embed=True,
                                          include_head=True, mesh=mesh)
        for kind, lo, hi, runner in ranges:
            if kind == "local":
                p = load_model_params(cfg, model_dir, dtype, quant=quant,
                                      layer_range=(lo, hi),
                                      include_embed=False,
                                      include_head=False, mesh=mesh)
                from ..parallel.sharding import init_cache_sharded
                runner = LocalStage(cfg, p, lo, hi, mesh=mesh)
                cache = init_cache_sharded(mesh, cfg, 1, max_cache_len,
                                           dtype, (lo, hi))
                stages.append(Stage("local", lo, hi, runner, cache))
            else:
                stages.append(Stage("remote", lo, hi, runner))

        topo = Topology.from_dict({
            name: {"host": f"{worker_by_name[name]['host']}:"
                           f"{worker_by_name[name]['port']}",
                   "layers": [f"model.layers.{s}-{e - 1}"],
                   "memory_bytes": worker_by_name[name]["caps"]["memory_bytes"],
                   "tflops": worker_by_name[name]["caps"]["tflops"],
                   "backend": worker_by_name[name]["caps"].get("backend", "")}
            for name, (s, e) in assignments.items()})
        storage.close()
        return MasterSetup(cfg=cfg, topology=topo, stages=stages,
                           master_params=master_params, clients=clients)
    except BaseException:
        # a failure ANYWHERE in setup (worker connect/assign/push, master
        # local-stage load, cache init) must not leak the already-open
        # worker sockets or the checkpoint storage handles
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        try:
            storage.close()
        except Exception:
            pass
        raise


def transfer_cached(ack_msg: dict) -> bool:
    return bool(ack_msg.get("cached", False))


def _repush_weights(model_dir: str, names: list[str], client: RemoteStage,
                    ack: dict) -> None:
    """Recovery-path weight re-stream for a worker that lost its content-
    keyed cache: reopen the checkpoint and synthesize the client's layer
    subset again (master_setup's storage handle is long closed by the
    time a mid-generation reconnect needs this)."""
    from ..utils.safetensors_io import TensorStorage
    storage = TensorStorage.from_model_dir(model_dir)
    try:
        start_off = (ack.get("resume") or {}).get("model.safetensors", 0)
        total, chunks = transfer.synthesize_safetensors(storage, names)
        client.push_weights(transfer.encode_chunks(
            "model.safetensors", total, chunks, start_offset=start_off))
    finally:
        storage.close()


def _contiguous(layers: list[int]) -> list[tuple[int, int]]:
    if not layers:
        return []
    out = []
    lo = prev = layers[0]
    for i in layers[1:]:
        if i != prev + 1:
            out.append((lo, prev + 1))
            lo = i
        prev = i
    out.append((lo, prev + 1))
    return out
