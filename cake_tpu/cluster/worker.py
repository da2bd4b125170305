"""Worker node: advertises itself, authenticates the master, receives a layer
assignment (+ optionally streamed weights), then serves forward requests —
its whole contiguous layer range executing as ONE jit-compiled device call
per request (ref: cake-core/src/cake/sharding/worker.rs; the reference's
per-op dispatch loop :299-580 collapses into a single compiled range here).

Failure semantics match the reference: a failed forward answers
worker_error and keeps the connection loop alive (:425-431,477-516); a new
layer_assignment on a live socket re-runs setup (master restart, :316-330);
goodbye clears the per-connection cache (:364-384); each connection gets a
fresh KV cache (get_client_context :60-75).
"""
from __future__ import annotations

import asyncio
import json
import logging
import os

import jax.numpy as jnp
import numpy as np

from ..models.common.cache import cache_reset
from ..models.common.config import config_from_hf_dict
from ..models.common.text_model import LocalStage, select_flash_mode
from ..obs import PhaseTimer, WORKER_FWD_SECONDS, WORKER_HEARTBEAT, now
from ..utils.dtypes import parse_dtype
from ..utils.hub import cake_cache_dir
from . import faults, proto
from .auth import authenticate_as_worker, cluster_hash
from .discovery import WorkerAdvertiser, detect_capabilities
from .transfer import ModelReceiver, has_valid_model_cache

log = logging.getLogger("cake_tpu.worker")


class WorkerState:
    """Model state shared by all connections after a layer assignment."""

    def __init__(self):
        self.cfg = None
        self.stage: LocalStage | None = None
        self.start = 0
        self.end = 0
        self.dtype = jnp.bfloat16
        self.max_cache_len = 2048
        self.model_id = ""

    @property
    def loaded(self) -> bool:
        return self.stage is not None


class WorkerServer:
    def __init__(self, name: str, cluster_key: str, port: int = 10128,
                 model_dir: str | None = None, cache_root: str | None = None,
                 advertise: bool = True, discovery_port: int | None = None,
                 host: str = "0.0.0.0", tp: int | str | None = None):
        self.name = name
        self.cluster_key = cluster_key
        self.port = port
        self.host = host
        self.model_dir = model_dir          # pre-provisioned weights (cake split)
        self.cache_root = cache_root or os.path.join(cake_cache_dir(), "worker")
        self.advertise = advertise
        self.discovery_port = discovery_port
        self.caps = detect_capabilities()
        # in-host tensor parallelism over this worker's local devices — the
        # TPU-native replacement for the reference's intra-worker multi-GPU
        # layer split (ref: worker.rs:126-229): the assigned range still
        # compiles as ONE program, GSPMD splitting each layer over the mesh
        from ..parallel import serving_mesh
        self.mesh = serving_mesh(tp)
        self.state = WorkerState()
        self._advertiser = None
        self._server: asyncio.AbstractServer | None = None
        self._writers: set = set()       # live connections, closed on stop()
        self.stats = {"ops": 0, "tokens": 0, "fwd_s": 0.0}
        # monotonic liveness: bumped on every handled message, reported as
        # an AGE in worker_info (clocks aren't synchronized across nodes)
        # and exported/logged by the heartbeat loop so /health never has to
        # assume liveness
        self.started = now()
        self.last_heartbeat = now()
        # per-message phase accounting (read/deser/fwd/ser — the obs
        # replacement for the reference's worker.rs:533-543 breakdown);
        # phases also land in the span recorder when tracing is on
        self.phase = PhaseTimer()
        self._hb_task: asyncio.Task | None = None

    # -- lifecycle ---------------------------------------------------------

    async def start(self):
        self._server = await asyncio.start_server(
            self._on_connection, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        if self.advertise:
            kw = {}
            if self.discovery_port is not None:
                kw["discovery_port"] = self.discovery_port
            self._advertiser = WorkerAdvertiser(
                self.name, self.cluster_key, self.port, caps=self.caps,
                **kw).start()
        self._hb_task = asyncio.get_running_loop().create_task(
            self._heartbeat_loop())
        # chaos harness: lets a `@name:crash_after_ops=N` fault plan
        # hard-kill this worker mid-stream (no goodbye, no FIN-wait)
        faults.register_crash("@" + self.name, self._crash)
        log.info("worker %s listening on %s:%d", self.name, self.host, self.port)
        return self

    HEARTBEAT_INTERVAL = 15.0

    async def _heartbeat_loop(self):
        """Periodic liveness export: the gauge carries the monotonic
        last-activity timestamp, the log line the age + phase breakdown —
        a wedged worker is then visible as a growing age, not silence."""
        while True:
            await asyncio.sleep(self.HEARTBEAT_INTERVAL)
            WORKER_HEARTBEAT.set(now() - self.last_heartbeat,
                                 worker=self.name)
            log.debug("worker %s heartbeat: last activity %.1fs ago, "
                      "%d ops [%s]", self.name, now() - self.last_heartbeat,
                      self.stats["ops"], self.phase)

    async def serve_forever(self):
        async with self._server:
            await self._server.serve_forever()

    def _crash(self):
        """Injected hard death (cluster/faults.py crash_after_ops): stop
        accepting and abort every live connection with an RST — the
        ungraceful failure mode recovery must survive. Runs synchronously
        on the event loop thread from inside the fault hook."""
        log.warning("worker %s: injected crash", self.name)
        if self._hb_task:
            self._hb_task.cancel()
        if self._advertiser:
            self._advertiser.stop()
        if self._server:
            self._server.close()
        for w in list(self._writers):
            try:
                w.transport.abort()
            except Exception:
                w.close()

    async def stop(self):
        faults.unregister_crash("@" + self.name)
        if self._hb_task:
            self._hb_task.cancel()
        if self._advertiser:
            self._advertiser.stop()
        if self._server:
            self._server.close()
            # close LIVE connections too: Server.close() only stops
            # accepting, so without this a "stopped" worker keeps serving
            # forwards indefinitely (masters see a healthy worker that the
            # operator believes is down)
            for w in list(self._writers):
                try:
                    w.close()
                except Exception:
                    pass
            # bounded: py3.12 wait_closed blocks until all live master
            # connections drop, which may be never during teardown
            try:
                await asyncio.wait_for(self._server.wait_closed(), 2)
            except (TimeoutError, asyncio.TimeoutError):
                pass

    # -- connection handling -------------------------------------------------

    async def _on_connection(self, reader: asyncio.StreamReader,
                             writer: asyncio.StreamWriter):
        peer = writer.get_extra_info("peername")
        sock = writer.get_extra_info("socket")
        if sock is not None:
            # response frames are latency-critical (one per token): without
            # NODELAY, Nagle + delayed-ACK stalls alternate replies ~40 ms
            # (measured: p50 1 ms / mean 30 ms bimodal RTTs on localhost)
            import socket as _socket
            sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        # register BEFORE auth: a connection suspended mid-handshake when
        # stop() runs must be closed too, or it survives shutdown and
        # serves forwards on a worker the operator believes is down
        self._writers.add(writer)
        # label the streams so fault plans can target this worker's side
        # of the hop ("@name"; the master's side is plain "name")
        faults.tag(reader, "@" + self.name)
        faults.tag(writer, "@" + self.name)
        try:
            await authenticate_as_worker(reader, writer, self.cluster_key)
        except Exception as e:
            log.warning("auth failed from %s: %s", peer, e)
            self._writers.discard(writer)
            writer.close()
            return
        cache = None
        try:
            while True:
                msg, read_s, decode_s = await proto.read_frame_timed(reader)
                # bump liveness on EVERY received message, before any
                # branch can continue/raise past it; hello reports the age
                # before this message arrived
                prev_heartbeat = self.last_heartbeat
                self.last_heartbeat = now()
                t = msg.get("t")
                if t == "hello":
                    await proto.write_frame(writer, proto.worker_info(
                        self.name,
                        list(range(self.state.start, self.state.end)),
                        self.caps["backend"], self.caps["device"],
                        self.caps["memory_bytes"], self.caps["tflops"],
                        heartbeat_age_s=now() - prev_heartbeat,
                        ops=self.stats["ops"]))
                elif t == "layer_assignment":
                    cache = None
                    await self._handle_assignment(msg, reader, writer)
                elif t == "forward":
                    if not self.state.loaded:
                        await proto.write_frame(writer, proto.worker_error(
                            "no layer assignment"))
                        continue
                    cache = await self._handle_forward(msg, writer, cache,
                                                       read_s, decode_s)
                elif t == "goodbye":
                    # drop (not just zero) the cache: a grown buffer must
                    # not leak its size into the next generation — the next
                    # forward reallocates at the small bucket
                    cache = None
                    await proto.write_frame(writer, proto.ack())
                else:
                    await proto.write_frame(writer, proto.worker_error(
                        f"unexpected message {t!r}"))
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        except Exception as e:
            log.exception("connection error from %s: %s", peer, e)
        finally:
            self._writers.discard(writer)
            writer.close()

    # -- setup ---------------------------------------------------------------

    async def _handle_assignment(self, msg, reader, writer):
        st = self.state
        st.model_id = msg["model_id"]
        st.start, st.end = int(msg["start"]), int(msg["end"])
        st.dtype = parse_dtype(msg["dtype"])
        st.max_cache_len = int(msg.get("max_cache_len", 2048))
        cfg = config_from_hf_dict(msg["config"], msg.get("arch") or None)
        st.cfg = cfg
        key = msg["cache_key"]
        expected = msg.get("expected_files", {})

        # ack tells the master whether weights are already present so it can
        # skip the push (content-keyed cache, ref: has_valid_model_cache)
        model_dir = self.model_dir
        if model_dir is None:
            # empty `expected` cannot validate anything -> treat as uncached
            cached = bool(expected) and has_valid_model_cache(
                self.cache_root, key, expected)
            if not cached and msg["push_weights"]:
                a = proto.ack()
                a["cached"] = False
                # partial-transfer resume offsets (ref: ModelDataResume)
                recv = ModelReceiver(self.cache_root, key)
                a["resume"] = {f: recv.resume_offset(f) for f in expected}
                await proto.write_frame(writer, a)
                model_dir = await self._receive_weights(reader, key, msg, recv)
            elif cached:
                a = proto.ack()
                a["cached"] = True
                await proto.write_frame(writer, a)
                model_dir = os.path.join(self.cache_root, key)
            else:
                await proto.write_frame(writer, proto.worker_error(
                    "no weights: not cached and push disabled"))
                return
        else:
            a = proto.ack()
            a["cached"] = True
            await proto.write_frame(writer, a)

        try:
            t0 = now()
            from ..utils.loaders import load_model_params
            quant = None
            if msg.get("fp8_native"):
                from ..utils.quant import fp8_native_quant
                quant = fp8_native_quant()
            params = load_model_params(
                cfg, model_dir, st.dtype, quant=quant,
                layer_range=(st.start, st.end),
                include_embed=False, include_head=False, mesh=self.mesh)
            st.stage = LocalStage(cfg, params, st.start, st.end,
                                  mesh=self.mesh)
            # warm compiles during setup, not on first serve (ref hard-part
            # #7). "decode" warms the 1-token shape at the smallest bucket;
            # "full" (master default) additionally compiles every growth
            # bucket's decode AND fresh-prefill shape, so steady-state
            # serving never pays an in-band compile (VERDICT r4: in-band
            # compiles were the prime suspect for 8x RTT tail stalls)
            # off the event loop: a full warm sweep takes seconds-to-minutes
            # and other connections (another master mid-generation) must
            # keep being served while it runs
            await asyncio.get_running_loop().run_in_executor(
                None, self._warm, msg.get("warm", "decode"))
            log.info("worker %s loaded layers [%d,%d) in %.1fs", self.name,
                     st.start, st.end, now() - t0)
            await proto.write_frame(writer, proto.worker_ready())
        except Exception as e:
            log.exception("assignment failed")
            await proto.write_frame(writer, proto.worker_ready(
                ok=False, error=str(e)))
            st.stage = None

    def _warm(self, mode: str):
        """Compile-warm the shapes serving will hit. All jit caches are
        keyed on array shapes and persist across connections, so this runs
        once per assignment regardless of how many masters connect."""
        if mode == "none":
            return
        from ..models.common.text_model import (PREFILL_BUCKETS,
                                                PREFILL_CHUNK)
        st = self.state
        t0 = now()
        buckets = [b for b in PREFILL_BUCKETS if b <= st.max_cache_len]
        if not buckets or buckets[-1] != st.max_cache_len:
            buckets.append(st.max_cache_len)
        if mode != "full":
            buckets = buckets[:1]
        zero = jnp.asarray(0, jnp.int32)
        x1 = jnp.zeros((1, 1, st.cfg.hidden_size), st.dtype)
        n = 0
        for i, b in enumerate(buckets):
            cache = None     # free bucket i-1 before allocating bucket i
            cache, _ = self._sized_cache(None, b)
            # decode shape at this bucket; reuse the returned cache (same
            # buffers, contents irrelevant) for the prefill warms so the
            # largest bucket never holds two live caches at once
            _, cache = st.stage.forward_hidden(x1, cache, zero, None)
            n += 1
            if mode == "full":
                # fresh full-prompt prefill: the master pads prompts to
                # bucket width w and sends them whole, while the kv hint
                # sizes this cache to w's bucket OR the next one (prompt +
                # DECODE_HEADROOM may spill) — warm both combos
                for w in ([b, buckets[i - 1]] if i > 0 else [b]):
                    xb = jnp.zeros((1, w, st.cfg.hidden_size), st.dtype)
                    _, cache = st.stage.forward_hidden(
                        xb, cache, zero, jnp.asarray(w, jnp.int32),
                        flash_mode=select_flash_mode(0, w, b))
                    n += 1
                # pipelined-prefill chunk shapes: prompts longer than
                # PREFILL_CHUNK arrive as chunk-width slices — fresh for
                # chunk 0, append (pos0 traced, one compile covers all
                # later chunks) for the rest
                # (>= 2*chunk: the master only chunks prompts longer than
                # one chunk, and ceil-to-chunk must fit the bucket — a
                # bucket strictly between chunk and 2*chunk can never
                # receive chunked prefill)
                if b >= 2 * PREFILL_CHUNK:
                    xc = jnp.zeros((1, PREFILL_CHUNK, st.cfg.hidden_size),
                                   st.dtype)
                    vlc = jnp.asarray(PREFILL_CHUNK, jnp.int32)
                    for p0 in (0, PREFILL_CHUNK):
                        _, cache = st.stage.forward_hidden(
                            xc, cache, jnp.asarray(p0, jnp.int32), vlc,
                            flash_mode=select_flash_mode(
                                p0, PREFILL_CHUNK, b))
                        n += 1
        log.info("worker %s warmed %d shapes (%s) in %.1fs", self.name, n,
                 mode, now() - t0)

    async def _receive_weights(self, reader, key: str, assign_msg,
                               recv: ModelReceiver) -> str:
        while True:
            msg = await proto.read_frame(reader)
            if msg["t"] == "model_chunk":
                recv.on_chunk(msg)
            elif msg["t"] == "model_done":
                recv.finalize()
                recv.write_json("config.json", assign_msg["config_raw"]
                                if "config_raw" in assign_msg
                                else assign_msg["config"])
                break
            else:
                raise proto.ProtocolError(
                    f"unexpected {msg['t']!r} during weight transfer")
        return recv.dir

    # -- inference -----------------------------------------------------------

    def _fresh_cache(self, kv_len: int | None = None):
        from ..parallel.sharding import init_cache_sharded
        st = self.state
        return init_cache_sharded(
            self.mesh, st.cfg, 1, min(kv_len or st.max_cache_len,
                                      st.max_cache_len), st.dtype,
            (st.start, st.end))

    def _sized_cache(self, cache, needed: int):
        """Growth-bucketed per-connection cache (mirrors TextModel's
        cache-length bucketing): allocate at the smallest bucket covering
        the request, grow bucket-by-bucket as positions advance — decode
        attends over the allocated buffer, so short generations never pay
        max_cache_len of attention bandwidth per token on workers either."""
        from ..models.common.cache import grow_cache, kv_capacity
        from ..models.common.text_model import bucket_for
        from ..parallel.sharding import shard_cache
        st = self.state
        bkt = bucket_for(needed, st.max_cache_len)
        if cache is None:
            return self._fresh_cache(bkt), bkt
        cap = kv_capacity(st.cfg, cache, (st.start, st.end))
        if cap is None:            # pure SWA/linear range: wraps by design
            return cache, st.max_cache_len
        if needed > cap:
            cache = shard_cache(grow_cache(st.cfg, cache, bkt,
                                           (st.start, st.end)), self.mesh)
            cap = bkt
        return cache, cap

    async def _handle_forward(self, msg, writer, cache, read_s: float = 0.0,
                              decode_s: float = 0.0):
        st = self.state
        t0 = now()
        try:
            # deser: msgpack decode (timed by the framing layer) + raw-buffer
            # unpack + host->device transfer/cast
            t_d = now()
            x = jnp.asarray(proto.unpack_tensor(msg["x"])).astype(st.dtype)
            deser_s = decode_s + (now() - t_d)
            raw_pos0 = int(msg["pos0"])
            pos0 = jnp.asarray(raw_pos0, jnp.int32)
            vl = msg.get("valid_len")
            # kv hint: size the cache to the master's bucket so growth
            # reallocs stay bucket-aligned (and pre-warmed) on every node
            needed = max(raw_pos0 + x.shape[1], int(msg.get("kv") or 0))
            cache, capacity = self._sized_cache(cache, needed)
            # prefill chunks (valid_len present) take the flash path
            # (worker caches are unwrapped while inside the buffer)
            flash_mode = "off"
            if vl is not None:
                flash_mode = select_flash_mode(raw_pos0, x.shape[1],
                                               capacity)
            vl = None if vl is None else jnp.asarray(vl, jnp.int32)
            loop = asyncio.get_running_loop()

            def _run():
                # timing starts INSIDE the executor thread (queueing delay
                # belongs to wire_, not fwd_) and ends after a real fetch
                # (jax dispatch is async; only np.asarray syncs the device)
                t_fwd = now()
                yy, cc = st.stage.forward_hidden(x, cache, pos0, vl,
                                                 flash_mode=flash_mode)
                # lint: disable=host-sync — the stage result is serialized to the wire
                # next; fetching here also keeps fwd_ms honest (dispatch is async)
                yy = np.asarray(yy)
                return yy, cc, t_fwd, (now() - t_fwd) * 1e3

            y, cache, t_fwd0, fwd_ms = await loop.run_in_executor(None, _run)
            # ser timed separately so the echo attributes it: tobytes of
            # the hidden state dominates the response path
            t_s = now()
            packed = proto.pack_tensor(y)
            ser_s = now() - t_s
            # per-phase echo: lets the master split its observed RTT into
            # worker-side read/deser/fwd/ser and attribute the remainder
            # to the wire (ref: worker.rs:533-543)
            tm = {"read_ms": read_s * 1e3, "deser_ms": deser_s * 1e3,
                  "fwd_ms": fwd_ms, "ser_ms": ser_s * 1e3}
            await proto.write_frame(
                writer, proto.tensor_result(packed, msg.get("rid", 0),
                                            fwd_ms=fwd_ms, timing=tm))
        except Exception as e:
            log.exception("forward failed")
            await proto.write_frame(writer, proto.worker_error(str(e)))
            return cache
        dt = now() - t0
        self.stats["ops"] += 1
        self.stats["fwd_s"] += dt
        self.stats["tokens"] += int(np.prod(np.asarray(msg["x"]["sh"][:2])))
        WORKER_FWD_SECONDS.observe(fwd_ms / 1e3)
        # real start timestamps so the exported spans lay out sequentially
        # (read/decode finished just before the handler entered at t0)
        ph = self.phase
        ph.add("read", read_s, t0=t0 - decode_s - read_s)
        ph.add("deser", deser_s, t0=t0 - decode_s)
        ph.add("fwd", fwd_ms / 1e3, t0=t_fwd0)
        ph.add("ser", ser_s, t0=t_s)
        if self.stats["ops"] % 5 == 0:   # rolling stats (ref worker.rs:566-578)
            log.debug("worker %s: %d ops, avg %.1f ms [%s]", self.name,
                      self.stats["ops"],
                      1000 * self.stats["fwd_s"] / self.stats["ops"], ph)
        return cache


def run_worker(name: str, cluster_key: str, port: int = 10128,
               model_dir: str | None = None, tp: int | str | None = None,
               **kw):
    """Blocking entry point (ref: cake-cli run_as_worker)."""
    async def main():
        server = WorkerServer(name, cluster_key, port, model_dir, tp=tp, **kw)
        await server.start()
        await server.serve_forever()
    asyncio.run(main())
