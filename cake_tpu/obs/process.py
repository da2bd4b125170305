"""What the process saw beside the scheduler: XLA compilations, collector
pauses and how late the serving event loop's timer fired.

A scheduler iteration that stood still says where from its own stamps
(serve/flight.py); whether the PROGRAM made it stand still or the process
or the machine did is told by three witnesses kept here, each paid for
only when its event happens:

  * one `jax.monitoring` listener: every backend compile (cache
    retrievals too) counts in `cake_compiles_total` /
    `cake_compile_seconds_total` and is kept as (end, seconds);
  * one `gc.callbacks` hook: two clock reads a collection and a return,
    unless the pause reached 1 ms — then it is observed in
    `cake_gc_pause_seconds` and kept as (start, ms, generation);
  * the serving event loop re-arms a 50 ms `call_later` tick
    (`LoopTick`, started by the API server) and records how late it
    fired: `cake_api_loop_lag_seconds` and a ring of (due, lag) the
    last minute long.

`PROCESS.between(t0, t1)` is what a stall record reads: the pauses, the
compiles and the largest loop lag that overlap that stretch of the
recorder's clock (`obs.now()`). A lag as long as the stall, on another
thread, with no pause of ours, says the process or the machine stood
still; a pause or a compile of that size says which of ours it was.
"""
from __future__ import annotations

import gc
import threading
from collections import deque

from .timing import now

__all__ = ["LoopTick", "ProcessWatch"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GC_KEEP_S = 0.001           # pauses under this are not kept, nor observed
TICK_S = 0.05               # the event loop's tick: 20 wake-ups a second
LAG_WINDOW_S = 60.0         # health's `max_60s`


class ProcessWatch:
    """The three witnesses' rings. Appends come from whichever thread the
    event fires on (a deque append is atomic); readers copy first."""

    def __init__(self, compiles, compile_seconds, gc_pause_seconds,
                 loop_lag_seconds):
        # the four instruments (obs/__init__.py declares them and builds
        # the process's one watch)
        self._m_compiles, self._m_compile_s = compiles, compile_seconds
        self._m_gc, self._m_lag = gc_pause_seconds, loop_lag_seconds
        self._install_lock = threading.Lock()
        self._installed = False
        self._compiles: deque = deque(maxlen=1024)      # (t_end, seconds)
        self._pauses: deque = deque(maxlen=4096)        # (t0, ms, generation)
        self._lags: deque = deque(
            maxlen=int(LAG_WINDOW_S / TICK_S))          # (due, lag seconds)
        self._gc_t0 = 0.0

    def install(self) -> None:
        """Register the compile listener and the collector hook, once a
        process (`serve.maybe_engine` calls it, so `cake serve` and every
        embedding of the engine have them). jax.monitoring has no public
        way to take one listener off again, so there is no uninstall."""
        with self._install_lock:
            if self._installed:
                return
            self._installed = True
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        gc.callbacks.append(self._on_gc)

    def _on_compile(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self._m_compiles.inc()
            self._m_compile_s.inc(float(duration))
            self._compiles.append((now(), float(duration)))

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
        the kept pauses), `compiles` / `compile_ms` (those that ended
        inside), `loop_lag_ms` (the largest lag of a tick that was due
        before t1 and ran after t0)."""
        pauses = [ms for t, ms, _ in list(self._pauses)
                  if t <= t1 and t + ms / 1e3 >= t0]
        comp = [s for t, s in list(self._compiles) if t0 <= t <= t1]
        lags = [lag for due, lag in list(self._lags)
                if due <= t1 and due + lag >= t0]
        return {"gc_ms": round(sum(pauses), 3), "compiles": len(comp),
                "compile_ms": round(sum(comp) * 1e3, 3),
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
