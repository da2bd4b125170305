"""What the process saw beside the scheduler: the programs JAX built
(traced, lowered, compiled or loaded from the persistent cache), the
phases of its own start-up, collector pauses and how late the serving
event loop's timer fired.

A scheduler iteration that stood still says where from its own stamps
(serve/flight.py); whether the PROGRAM made it stand still or the process
or the machine did is told by the witnesses kept here, each paid for only
when its event happens:

  * `jax.monitoring` listeners, installed before the first program is
    built (`utils.compile_cache.enable_compile_cache`, `TextModel`,
    `serve.maybe_engine`: one idempotent `install()`): every program
    build is kept BY NAME AND STAGE in one ring, `{t_end, seconds, stage:
    trace | lower | backend, program, cache: hit | miss | off, phase}`.
    `trace` is Python's time to make the jaxpr, `lower` the MLIR module's,
    `backend` XLA's compile or, on a persistent-cache hit, the
    executable's retrieval and load. Functions traced INSIDE a program
    (`matmul`, `tanh`, an inner `jit`) emit trace events of their own,
    inside the outer one's time: a trace counts only where the next
    `lower` event of its thread names it, the others are dropped.
    Counters: `cake_compiles_total{cache}` / `cake_compile_seconds_total
    {cache}` (the backend stage), `cake_program_build_seconds_total{stage}`
    (trace, lower);
  * `phase(name)`: two clock reads and a list append around what the
    program does at start-up (`boot.model`, `boot.rope`, `boot.engine`,
    `boot.engine.pool`), and the process's own age when the watch was
    installed (the interpreter's start and the imports before it). A build
    carries the innermost phase open on its thread;
  * one `gc.callbacks` hook: two clock reads a collection and a return,
    unless the pause reached 1 ms — then it is observed in
    `cake_gc_pause_seconds` and kept as (start, ms, generation);
  * the serving event loop re-arms a 50 ms `call_later` tick
    (`LoopTick`, started by the API server) and records how late it
    fired: `cake_api_loop_lag_seconds` and a ring of (due, lag) the
    last minute long.

`PROCESS.boot()` is the account of all of it (`/health`'s engine block,
the flight dump's `static`). The span recorder, once enabled, is handed
every build (`process.compile`) and every phase (`cat="boot"`) since the
process began, with their past stamps, and each later one as it happens:
they are held beside its ring (`SpanRecorder.hold`), so a trace export
begins with the start-up however many spans followed.

`PROCESS.between(t0, t1)` is what a stall record reads: the pauses, the
compiles (with the programs' names) and the largest loop lag that overlap
that stretch of the recorder's clock (`obs.now()`). A lag as long as the
stall, on another thread, with no pause of ours, says the process or the
machine stood still; a pause or a compile of that size says which of ours
it was.
"""
from __future__ import annotations

import contextlib
import gc
import os
import threading
from collections import deque

from .spans import current_request_id, new_span_id
from .timing import now

__all__ = ["LoopTick", "ProcessWatch"]

_PREFIX = "/jax/core/compile/"
STAGES = {_PREFIX + "jaxpr_trace_duration": "trace",
          _PREFIX + "jaxpr_to_mlir_module_duration": "lower",
          _PREFIX + "backend_compile_duration": "backend"}
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}
BUILDS_KEPT = 4096          # three records a program: some 1,300 programs
PHASES_KEPT = 256
PROGRAMS_SHOWN = 32         # of `boot()`'s `programs`, the costliest
GC_KEEP_S = 0.001           # pauses under this are not kept, nor observed
TICK_S = 0.05               # the event loop's tick: 20 wake-ups a second
LAG_WINDOW_S = 60.0         # health's `max_60s`


def process_age_s() -> float | None:
    """Seconds since the kernel started this process (`/proc/self/stat`'s
    start time against `/proc/uptime`); None where there is no /proc."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command's closing bracket: state is the
            # third of the line, `starttime` the twenty-second
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return None


def _program(fun_name: str) -> str:
    """`jit(_decode_slots)` -> `_decode_slots`."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class _PerThread(threading.local):
    """What a thread's next event needs of its earlier ones."""

    def __init__(self):
        self.traces: dict = {}      # program -> (t_end, seconds), unclaimed
        self.cache = "off"          # the cache's verdict on the next backend
        self.phases: list = []      # (name, span id) open, innermost last


class ProcessWatch:
    """The witnesses' rings. Appends come from whichever thread the event
    fires on; readers copy first."""

    def __init__(self, compiles, compile_seconds, build_seconds,
                 gc_pause_seconds, loop_lag_seconds, recorder=None):
        # the five instruments and the span recorder the builds and the
        # phases are handed to (obs/__init__.py declares them and builds
        # the process's one watch)
        self._m_compiles, self._m_compile_s = compiles, compile_seconds
        self._m_build_s = build_seconds
        self._m_gc, self._m_lag = gc_pause_seconds, loop_lag_seconds
        self._recorder = recorder
        self._install_lock = threading.Lock()
        self._installed = False
        # one lock for the builds, the phases and their hand-over: a few
        # hundred events a process
        self._lock = threading.Lock()
        self._builds: deque = deque(maxlen=BUILDS_KEPT)     # build records
        self._phases: deque = deque(maxlen=PHASES_KEPT)     # closed phases
        self._programs: dict[str, dict] = {}    # name -> running totals
        self._cache_load_s = 0.0    # backend seconds that were cache hits
        self._n = 0                 # records made, builds and phases
        self._handed = 0            # the last `n` the recorder was handed
        # what the hand-overs cost: spans handed and the seconds it took
        # (the first, when the recorder is switched on, is most of it)
        self._handed_spans, self._handed_s = 0, 0.0
        # backend records made: what the engine compares around a dispatch
        self.backend_count = 0
        self._local = _PerThread()
        self.age_at_install_s: float | None = None
        self._t_process = 0.0       # the process's start on now()'s clock
        self._pauses: deque = deque(maxlen=4096)        # (t0, ms, generation)
        self._lags: deque = deque(
            maxlen=int(LAG_WINDOW_S / TICK_S))          # (due, lag seconds)
        self._gc_t0 = 0.0

    def install(self) -> None:
        """Register the build listeners and the collector hook, once a
        process, before its first program is built. jax.monitoring has no
        public way to take one listener off again, so there is no
        uninstall."""
        with self._install_lock:
            if self._installed:
                return
            self._installed = True
        self.age_at_install_s = process_age_s()
        self._t_process = now() - (self.age_at_install_s or 0.0)
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_build)
        jax.monitoring.register_event_listener(self._on_cache)
        gc.callbacks.append(self._on_gc)

    # -- program builds ------------------------------------------------------

    def _on_cache(self, event, **kw):
        verdict = CACHE_EVENTS.get(event)
        if verdict is not None:
            self._local.cache = verdict

    def _on_build(self, event, duration, fun_name="", **kw):
        stage = STAGES.get(event)
        if stage is None:
            return
        t_end, seconds, local = now(), float(duration), self._local
        name = _program(fun_name)
        if stage == "trace":
            # a program's own, or one of a function traced inside it: the
            # `lower` event that follows on this thread says which
            local.traces[name] = (t_end, seconds)
            return
        if stage == "lower":
            own = local.traces.get(name)
            local.traces = {}
            local.cache = "off"
            if own is not None:
                self._record(own[0], own[1], "trace", name, None)
            self._record(t_end, seconds, "lower", name, None)
            return
        self._record(t_end, seconds, "backend", name, local.cache)
        local.cache = "off"

    def _record(self, t_end, seconds, stage, program, cache):
        stack = self._local.phases
        rec = {"t_end": t_end, "seconds": seconds, "stage": stage,
               "program": program, "tid": threading.get_ident()}
        if cache is not None:
            rec["cache"] = cache
        if stack:
            rec["phase"], rec["parent"] = stack[-1]
        rid = current_request_id()
        if rid is not None:         # built in-band, under a request
            rec["request_id"] = rid
        if stage == "backend":
            self._m_compiles.inc(cache=cache)
            self._m_compile_s.inc(seconds, cache=cache)
        else:
            self._m_build_s.inc(seconds, stage=stage)
        with self._lock:
            self._n += 1
            rec["n"] = self._n
            self._builds.append(rec)
            tot = self._programs.get(program)
            if tot is None:
                tot = self._programs[program] = {
                    "builds": 0, "trace_s": 0.0, "lower_s": 0.0,
                    "backend_s": 0.0, "hits": 0, "misses": 0}
            tot[stage + "_s"] += seconds
            if stage == "backend":
                tot["builds"] += 1
                tot["hits"] += cache == "hit"
                tot["misses"] += cache == "miss"
                self._cache_load_s += seconds * (cache == "hit")
                self.backend_count += 1
        self.hand_over()

    def built_since(self, count: int) -> list[dict]:
        """The backend records this thread made after `backend_count` read
        `count`: the programs a dispatch compiled (or loaded) in-band."""
        with self._lock:
            fresh = self.backend_count - count
            if fresh <= 0:
                return []
            tid, out = threading.get_ident(), []
            for rec in reversed(self._builds):
                if rec["stage"] == "backend":
                    if rec["tid"] == tid:
                        out.append(dict(rec))
                    fresh -= 1
                    if fresh == 0:
                        break
        return out[::-1]

    # -- start-up phases -----------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        """One start-up phase of the program: kept with its start and
        length, nested by the phases open on this thread; the builds made
        inside carry its name."""
        stack = self._local.phases
        parent = stack[-1][1] if stack else None
        sid = new_span_id()
        stack.append((name, sid))
        t0 = now()
        try:
            yield
        finally:
            t1 = now()
            stack.pop()
            rec = {"name": name, "t0": t0, "dur_s": t1 - t0, "sid": sid,
                   "parent": parent, "tid": threading.get_ident()}
            with self._lock:
                self._n += 1
                rec["n"] = self._n
                self._phases.append(rec)
            self.hand_over()

    # -- the account, and the recorder's copy --------------------------------

    def boot(self) -> dict:
        """What the process did to become useful, from inside:
        `age_at_install_s` (the interpreter's start and the imports before
        the install: the backend's initialisation comes after), `phases`
        (`t_s` from the process's start), the programs built, the
        costliest PROGRAMS_SHOWN by name, and the totals over all of them:
        `trace_s` + `lower_s` (Python's part, paid warm and cold alike),
        `cache_load_s` (backend time of cache hits), `compile_s` (of misses
        and of builds with the cache off), `builds` / `hits` / `misses`;
        `handed` = the spans the recorder was handed and what that cost."""
        with self._lock:
            phases = [{"name": p["name"],
                       "t_s": round(p["t0"] - self._t_process, 3),
                       "dur_s": round(p["dur_s"], 3)} for p in self._phases]
            programs = [{"program": name, **tot}
                        for name, tot in self._programs.items()]
            load_s = self._cache_load_s
        programs.sort(key=lambda p: -(p["trace_s"] + p["lower_s"]
                                      + p["backend_s"]))
        total = {k: sum(p[k] for p in programs)
                 for k in ("builds", "hits", "misses", "trace_s", "lower_s",
                           "backend_s")}
        for p in programs:
            for k in ("trace_s", "lower_s", "backend_s"):
                p[k] = round(p[k], 4)
        return {"age_at_install_s": None if self.age_at_install_s is None
                else round(self.age_at_install_s, 3),
                "phases": phases, "programs": programs[:PROGRAMS_SHOWN],
                "builds": total["builds"], "hits": total["hits"],
                "misses": total["misses"],
                "trace_s": round(total["trace_s"], 4),
                "lower_s": round(total["lower_s"], 4),
                "cache_load_s": round(load_s, 4),
                "compile_s": round(total["backend_s"] - load_s, 4),
                "handed": {"spans": self._handed_spans,
                           "seconds": round(self._handed_s, 6)}}

    def hand_over(self) -> None:
        """Give the span recorder every build and closed phase it has not
        had yet, with their own stamps: all of them when it is switched on
        (`SpanRecorder.enable` calls this), then each as it happens."""
        rec = self._recorder
        if rec is None or not rec.enabled:
            return
        with self._lock:
            t0, fresh = now(), []
            for ring in (self._builds, self._phases):
                for r in reversed(ring):    # each ring is in `n`'s order
                    if r["n"] <= self._handed:
                        break
                    fresh.append(r)
            fresh.sort(key=lambda r: r["n"])
            for r in fresh:
                if "stage" in r:
                    args = {k: r[k] for k in ("program", "stage", "cache",
                                              "phase", "request_id")
                            if k in r}
                    rec.hold("process.compile",
                             (r["t_end"] - r["seconds"]) * 1e6,
                             r["seconds"] * 1e6, "process", r["tid"],
                             parent=r.get("parent"), **args)
                else:
                    rec.hold(r["name"], r["t0"] * 1e6, r["dur_s"] * 1e6,
                             "boot", r["tid"], parent=r["parent"],
                             sid=r["sid"])
            if fresh:
                self._handed = fresh[-1]["n"]
                self._handed_spans += len(fresh)
                self._handed_s += now() - t0

    def _on_gc(self, phase, info):
        t = now()
        if phase == "start":
            self._gc_t0 = t
            return
        pause = t - self._gc_t0
        if pause < GC_KEEP_S:
            return
        self._m_gc.observe(pause)
        self._pauses.append((self._gc_t0, pause * 1e3, info["generation"]))

    def note_loop_lag(self, due: float, lag: float) -> None:
        """One tick of the event loop: due at `due`, ran `lag` s late."""
        self._m_lag.observe(lag)
        self._lags.append((due, lag))

    def loop_lag(self) -> dict | None:
        """`{last, max_60s}` in ms; None where no loop ticks (an engine
        embedded without the API server)."""
        lags = list(self._lags)
        if not lags:
            return None
        horizon = now() - LAG_WINDOW_S
        recent = [lag for due, lag in lags if due + lag >= horizon]
        return {"last": round(lags[-1][1] * 1e3, 3),
                "max_60s": round(max(recent, default=lags[-1][1]) * 1e3, 3)}

    def between(self, t0: float, t1: float) -> dict:
        """What overlapped [t0, t1] on obs.now()'s clock: `gc_ms` (sum of
        the kept pauses), `compiles` / `compile_ms` / `compiled` (the
        backend stage of the builds that ended inside: how many, their ms,
        the programs' names), `loop_lag_ms` (the largest lag of a tick
        that was due before t1 and ran after t0)."""
        pauses = [ms for t, ms, _ in list(self._pauses)
                  if t <= t1 and t + ms / 1e3 >= t0]
        with self._lock:
            comp = [(b["seconds"], b["program"]) for b in self._builds
                    if b["stage"] == "backend" and t0 <= b["t_end"] <= t1]
        lags = [lag for due, lag in list(self._lags)
                if due <= t1 and due + lag >= t0]
        return {"gc_ms": round(sum(pauses), 3), "compiles": len(comp),
                "compile_ms": round(sum(s for s, _ in comp) * 1e3, 3),
                "compiled": [name for _, name in comp],
                "loop_lag_ms": round(max(lags, default=0.0) * 1e3, 3)}


class LoopTick:
    """The event loop's own lag, measured on the loop: a `call_later` of
    TICK_S re-armed from its own callback, which reports how far past its
    due instant it ran. Start and stop on the loop's thread."""

    def __init__(self, loop, watch: ProcessWatch):
        self._loop, self._watch = loop, watch
        self._handle = None
        self._due = 0.0

    def start(self) -> None:
        self._due = now() + TICK_S
        self._handle = self._loop.call_later(TICK_S, self._fire)

    def _fire(self):
        t = now()
        # call_later rounds to the loop's clock resolution: never negative
        self._watch.note_loop_lag(self._due, max(t - self._due, 0.0))
        self._due = t + TICK_S
        self._handle = self._loop.call_later(TICK_S, self._fire)

    def stop(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
