"""Engine flight recorder: a bounded ring of recent scheduler iterations.

The recurring failure mode on this project's hardware is the WEDGE — a
device dispatch that never returns (ROADMAP's TPU caveat, the watchdog in
supervisor.py). When it happens, a gauge flips and /health says wedged,
but the evidence of WHAT the engine was doing in the seconds before is
gone: the span recorder is off by default and metrics are aggregates.
This module is the black box: every completed scheduler iteration
appends one small record (occupancy, dispatch bucket, the tokens the
stepping rows hold and the bytes of recurrent state beside them, the step's wall
time split at the device fetch into `host_ms` and `fetch_ms`, `lag` = 1
when that fetch held the ids of the step an EARLIER iteration dispatched
and ran under this iteration's own, already queued — 0 when nothing was
queued behind it or nothing was fetched — `dropped` = ids fetched and not
delivered because their request had ended since the dispatch, spec accept
counts, queue depth, KV-pool occupancy) into a
ring of the last `CAKE_FLIGHT_RECORDER` iterations, and the supervisor
dumps the ring to `CAKE_TRACE_DIR` as JSON when the watchdog flags a
wedge or the rebuild budget puts the engine DOWN — the post-mortem an
operator (or the next session's bench triage) replays.

The record covers the whole iteration and the gap before it, from the
clock reads the step takes anyway: `wall_ms` (first stamp to last), `ph`
(its eight phases in ms, in PHASES' order: they add up to `wall_ms`),
`kind` (`decode` | `chunk` | `last_chunk` | `idle`: whether it carried a
prefill chunk, the prompt's last, or had no row to step), `joined` (the
slots it handed to the batched decode, one `_slot_join` program each: 1 on
a `last_chunk` record, else 0), `restores` / `restored` (the restore
programs its admissions' prefix hits dispatched in the admit phase, in front
of the decode step, and the cached blocks they restored: one program a
power-of-two piece of a matched chain, so 1 / 32 for a hit of 32 blocks, 2 /
33 for one of 33, 0 / 0 without a hit and in paged mode) and `gap_ms`
(from the previous iteration's last stamp to this one's first, when that
one left work behind: the `_run` loop's own time; else 0).

A STALL outlives the ring. An iteration whose `wall_ms + gap_ms` passes
max(10 x reference, STALL_FLOOR_MS) — reference = the median `wall_ms` of
the ring's last turn, recomputed once a turn — is flagged: its record's
`stall_ms` is the excess (0 on every other record), and a copy is kept
among `stalls` (the 64 newest, with a count and the total ms of all),
joined by what else the process saw in that stretch (obs/process.py:
`gc_ms`, `compiles` / `compile_ms` / `compiled` = the programs' names,
`loop_lag_ms`) and `phase`, the
largest entry of `ph` or `between` for the gap. That is the verdict:
`fetch` large and nothing else, the device or the runtime; a host phase
or `between` with `gc_ms`, the collector; with `compiles`, a recompile;
`loop_lag_ms` of the stall's size with no pause of ours, the process or
the machine stood still. One log line a stall, at most one a second: a
WARNING, or INFO where the iteration compiled (`compiled` on the counter).

Recording is a dict append under a lock per scheduler iteration — noise
next to the device dispatch the iteration just ran. Dumping is the slow
path and only happens on the two failure classifications.
"""
from __future__ import annotations

import json
import logging
import os
import threading
from collections import deque
from itertools import islice
from statistics import median

from .. import knobs
from ..obs import (PROCESS, SERVE_STEP_STALL_SECONDS, SERVE_STEP_STALLS,
                   now)

__all__ = ["FlightRecorder", "KINDS", "PHASES"]

log = logging.getLogger("cake_tpu.serve.flight")

# `ph`, in the order a step runs them (engine._step's stamps): the lagged
# landing is `fetch` + `fanout`; `prefill` is the chunk's dispatch and what
# follows it; `late_land` is a depth-0 engine's fetch and fan-out of its
# own step, behind the chunk
PHASES = ("sweep", "admit", "plan", "decode_dispatch", "fetch", "fanout",
          "prefill", "late_land")
KINDS = ("decode", "chunk", "last_chunk", "idle")
# above every legitimate step of the benchmark's cells: the slowest, the
# steps that end `longprompt`'s longest prompts, take 254-326 ms in every
# run, at the same instants of its schedule (chip runs, PR 41: ISSUE 41's
# 250 ms flagged six of them a run)
STALL_FLOOR_MS = 500.0
STALLS_KEPT = 64
# a ring larger than this (a traced run's) still takes a new reference
# every so many records, over the newest so many
REFERENCE_TURN = 256


class FlightRecorder:
    """Thread-safe iteration ring + dump-to-disk. The scheduler thread
    records; the watchdog thread and the supervisor dump."""

    def __init__(self, capacity: int | None = None, clock=now,
                 watch=PROCESS):
        # `clock` and `watch` are the tests' seams: the stamps' source and
        # the witnesses a stall is joined with
        self._clock, self._watch = clock, watch
        if capacity is None:
            capacity = knobs.get("CAKE_FLIGHT_RECORDER")
        self.capacity = max(int(capacity), 1)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._seq = 0
        # -- stalls: beside the ring, never cleared by its turning -------
        self._turn = min(self.capacity, REFERENCE_TURN)
        self._since_ref = 0
        self._reference_ms: float | None = None
        self._stalls: deque = deque(maxlen=STALLS_KEPT)
        self._stall_count = 0
        self._stall_total_ms = 0.0
        # the newest stall, not yet joined by the process's witnesses: the
        # event loop's late tick may only run once the scheduler lets go,
        # so it is finished by the next record (or the next reader)
        self._pending: dict | None = None
        self._last_warning = 0.0
        # cumulative, for health(): iterations and their ms by kind, and
        # the sum of occupancy — what tells two untraced runs apart
        self._by_kind = {k: [0, 0.0] for k in KINDS}
        self._occupancy_sum = 0
        # what holds for every iteration (the engine writes it once: the
        # layers whose keys lie joined in the pool); dumped and served
        # beside the ring, through `static_view`
        self.static: dict = {}

    def static_view(self) -> dict:
        """`static`, and under `boot` the process's account of its
        start-up and of every program built since, read now."""
        return {**self.static, "boot": self._watch.boot()}

    def begin(self) -> int:
        """Reserve the sequence number of the iteration that starts now:
        the step id its spans and timeline events carry. An iteration that
        fails or finds nothing to do writes no record, so its number is
        missing from the ring."""
        with self._lock:
            self._seq += 1
            return self._seq

    def record(self, seq: int | None = None, **fields) -> None:
        """Append one iteration record under the number begin() gave (a
        new one when none is passed); `t` (monotonic seconds) is stamped
        here."""
        if seq is None:
            seq = self.begin()
        rec = {"seq": seq, "t": round(self._clock(), 6)}
        rec.update(fields)
        wall = fields.get("wall_ms")
        with self._lock:
            self._ring.append(rec)
            stall = None if wall is None else self._account(rec, wall)
            done, self._pending = self._pending, stall
        if done is not None:
            self._finish(done)

    def _account(self, rec: dict, wall: float) -> dict | None:
        """Totals, the reference's turn and the stall flag of one record
        that carries `wall_ms`, just appended (lock held). Returns the
        stall's copy."""
        kind = self._by_kind.get(rec.get("kind"))
        if kind is not None:
            kind[0] += 1
            kind[1] += wall
        self._occupancy_sum += rec.get("occupancy", 0)
        total = wall + rec.get("gap_ms", 0.0)
        limit = max(10.0 * (self._reference_ms or 0.0), STALL_FLOOR_MS)
        self._since_ref += 1
        if self._since_ref >= self._turn:
            # once a turn, over the turn this record ends: the next turn's
            # records are judged by it
            self._reference_ms = median(
                r["wall_ms"] for r in islice(reversed(self._ring),
                                             self._turn)
                if "wall_ms" in r)
            self._since_ref = 0
        if total <= limit:
            rec["stall_ms"] = 0.0
            return None
        rec["stall_ms"] = round(total - limit, 3)
        self._stall_count += 1
        self._stall_total_ms += total
        stall = dict(rec)
        self._stalls.append(stall)
        return stall

    def _finish(self, stall: dict) -> None:
        """Join a kept stall with what the process saw in its stretch and
        say so once. Mutates the kept dict in place, under the lock."""
        total = stall["wall_ms"] + stall.get("gap_ms", 0.0)
        saw = self._watch.between(stall["t"] - total / 1e3, stall["t"])
        ph = stall.get("ph") or [0.0] * len(PHASES)
        top = max(range(len(PHASES)), key=ph.__getitem__)
        phase = "between" if stall.get("gap_ms", 0.0) > ph[top] \
            else PHASES[top]
        with self._lock:
            stall.update(saw, phase=phase)
            warn = stall["t"] - self._last_warning >= 1.0
            if warn:
                self._last_warning = stall["t"]
        # an iteration that compiled is a stall like any other (a start
        # without a warm-up has them, a recompile in service too), under a
        # label and a level of its own: every deploy makes some
        compiled = saw["compiles"] > 0
        SERVE_STEP_STALLS.inc(phase=phase,
                              compiled="yes" if compiled else "no")
        SERVE_STEP_STALL_SECONDS.inc(total / 1e3)
        if warn:
            log.log(
                logging.INFO if compiled else logging.WARNING,
                "scheduler iteration %s %s: %.0f ms (limit %.0f), "
                "mostly in %s; gc %.0f ms, %d compile(s) %.0f ms %s, event "
                "loop lag %.0f ms", stall["seq"],
                "compiled" if compiled else "stood still", total,
                total - stall["stall_ms"], phase, saw["gc_ms"],
                saw["compiles"], saw["compile_ms"],
                sorted(set(saw["compiled"])), saw["loop_lag_ms"])

    def _settle(self) -> None:
        """Finish the newest stall for a reader that came before the next
        record did."""
        with self._lock:
            done, self._pending = self._pending, None
        if done is not None:
            self._finish(done)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return [dict(r) for r in self._ring]

    def stalls(self) -> dict:
        """`{count, total_ms, reference_ms, worst}`: how many iterations
        were flagged since the process started and the ms they took (gap
        included), the reference the next one is judged by, and the kept
        stall records (the 64 newest: a cold start's compiles cannot push
        a later, shorter stall out), largest first. `t` is on obs.now()'s
        clock: a reader with a window drops the warm-up's compile stalls
        by it."""
        self._settle()
        with self._lock:
            kept = sorted((dict(s) for s in self._stalls), reverse=True,
                          key=lambda s: s["wall_ms"] + s.get("gap_ms", 0.0))
            return {"count": self._stall_count,
                    "total_ms": round(self._stall_total_ms, 3),
                    "reference_ms": self._reference_ms,
                    "worst": kept}

    def totals(self) -> dict:
        """Cumulative `steps_by_kind` ({n, ms} a kind) and
        `occupancy_sum` over every iteration recorded."""
        with self._lock:
            return {"steps_by_kind": {k: {"n": n, "ms": round(ms, 3)}
                                      for k, (n, ms) in
                                      self._by_kind.items()},
                    "occupancy_sum": self._occupancy_sum}

    def dump(self, reason: str, extra: dict | None = None) -> str | None:
        """Write the ring to CAKE_TRACE_DIR as JSON. Returns the path,
        or None when no trace dir is configured (the record still lives
        in memory for /health debugging via snapshot()). Never raises —
        the dump runs inside failure handling, and a full disk must not
        turn a wedge flag into a supervisor crash."""
        trace_dir = knobs.get_str("CAKE_TRACE_DIR")
        if not trace_dir:
            return None
        try:
            os.makedirs(trace_dir, exist_ok=True)
            stalls, static = self.stalls(), self.static_view()
            with self._lock:
                seq = self._seq
                body = {
                    "reason": reason,
                    "pid": os.getpid(),
                    "static": static,
                    "stalls": stalls,
                    "iterations": [dict(r) for r in self._ring],
                }
            if extra:
                body.update(extra)
            path = os.path.join(
                trace_dir, f"cake-flight-{os.getpid()}-{seq}-{reason}.json")
            with open(path, "w") as f:
                json.dump(body, f)
            log.warning("flight recorder dumped %d iteration(s) to %s "
                        "(%s)", len(body["iterations"]), path, reason)
            return path
        except Exception:
            log.exception("flight recorder dump failed (%s)", reason)
            return None
