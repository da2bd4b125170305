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

from .. import knobs
from ..obs import now

__all__ = ["FlightRecorder"]

log = logging.getLogger("cake_tpu.serve.flight")


class FlightRecorder:
    """Thread-safe iteration ring + dump-to-disk. The scheduler thread
    records; the watchdog thread and the supervisor dump."""

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            capacity = knobs.get("CAKE_FLIGHT_RECORDER")
        self.capacity = max(int(capacity), 1)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._seq = 0
        # what holds for every iteration (the engine writes it once: the
        # layers whose keys lie joined in the pool); dumped and served
        # beside the ring
        self.static: dict = {}

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
        rec = {"seq": seq, "t": round(now(), 6)}
        rec.update(fields)
        with self._lock:
            self._ring.append(rec)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return [dict(r) for r in self._ring]

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
            with self._lock:
                seq = self._seq
                body = {
                    "reason": reason,
                    "pid": os.getpid(),
                    "static": dict(self.static),
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
