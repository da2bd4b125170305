"""Before the window (PR 60): what the process did to become ready, read
from the spans the program's own watch (`cake_tpu/obs/process.py`) hands
the recorder when the launcher switches it on: one `process.compile` span
a stage of every program JAX built since the process began (`args`:
`program`, `stage` = trace | lower | backend, `cache` = hit | miss | off
on the backend stage, `phase`), and one span a boot phase under its own
name (`boot.model`, `boot.rope`, `boot.engine`, `boot.engine.pool`;
`cat` = boot). They carry their past stamps and are held beside the
recorder's ring, so the window's `RECORDER.clear()` leaves them.

Only spans that END before the window's start count: a program built
while serving is the window's (and `correct` holds that there is none).

A program without these spans (the parent of the PR that brought them)
gives every reader here nothing to read: they return None.
"""
from __future__ import annotations

COMPILE = "process.compile"


def _before_window(ctx, name: str) -> list[dict]:
    start_us = ctx.window_perf[0] * 1e6
    return [e for e in ctx.spans if e["name"] == name
            and e["ts"] + e["dur"] <= start_us]


def build_s(ctx, stages: tuple, caches: tuple | None = None):
    """Seconds of the `process.compile` spans of these stages (and, given
    `caches`, these cache verdicts); 0.0 where the program records builds
    and none is of that kind, None where it records none at all."""
    spans = _before_window(ctx, COMPILE)
    if not spans:
        return None
    return sum(e["dur"] for e in spans
               if e["args"]["stage"] in stages
               and (caches is None or e["args"].get("cache") in caches)) / 1e6


def phase_s(ctx, name: str):
    """Seconds of the boot phase `name` (every span of it: a process
    builds one model and one engine); None where there is none."""
    spans = _before_window(ctx, name)
    return sum(e["dur"] for e in spans) / 1e6 if spans else None
