"""The one traffic generator. A mix is a data file (benchmark/traffic/<name>.json);
this module turns mix + seed + window into a schedule of requests. No JAX.

Steadiness rule: prompt lengths, answer lengths, arrival instants AND their
order are drawn from the mix's own `shape_seed`, so every --seed offers the
same work at the same instants; --seed draws the prompt text (and, in the
server, the weights and the output check's token ids). For a queueing tail
the order IS the work: the same sizes in another order put another burst in
the window, and a TTFT percentile then moves by tens of percent between
seeds (PERF.md, PR 25). Runs with different seeds now differ like two runs
of one seed.

Kinds:
  open_poisson  arrivals on a schedule (exponential gaps scaled to the exact
                mean rate), from -ramp_seconds to the window's end. Requests
                due before 0 bring the server to its steady state and are not
                judged.
  closed        `clients` callers, each sending its next request when the
                last one ended; started `stagger_seconds` apart inside the
                ramp. A request is judged when it was SENT in the window.
"""
from __future__ import annotations

import dataclasses
import math
import random
import string

# ChatML, as the program's fallback template renders it (one byte-level token
# per ASCII character): the rendered prompt has exactly `prompt_tokens`.
_SYS_HEAD = "<|im_start|>system\n"
_SYS_TAIL = "<|im_end|>\n"
_USER_HEAD = "<|im_start|>user\n"
_USER_TAIL = "<|im_end|>\n<|im_start|>assistant\n"
_ALPHABET = string.ascii_lowercase + "     "


@dataclasses.dataclass
class Request:
    index: int
    due_s: float | None          # open loop: seconds from the window's start
    client: int | None           # closed loop: which caller sends it
    prompt_tokens: int
    shared_tokens: int
    max_tokens: int
    text_seed: int


def _draw(spec: dict, rng: random.Random) -> int:
    kind = spec["dist"]
    if kind == "fixed":
        return int(spec["value"])
    if kind == "uniform":
        return rng.randint(int(spec["min"]), int(spec["max"]))
    if kind == "lognormal":
        v = math.exp(rng.gauss(math.log(spec["median"]), spec["sigma"]))
        return int(min(max(v, spec["min"]), spec["max"]))
    raise ValueError(f"unknown distribution {kind!r}")


def min_unique_tokens(mix: dict) -> int:
    """Template characters that follow the shared part."""
    if mix.get("shared_prefix_tokens"):
        return len(_SYS_TAIL) + len(_USER_HEAD) + 1 + len(_USER_TAIL)
    return len(_USER_HEAD) + 1 + len(_USER_TAIL)


def generate(mix: dict, seed: int, seconds: float,
             rate_rps: float | None = None) -> list[Request]:
    shape = random.Random(mix.get("shape_seed", 0))
    text = random.Random(seed)
    ramp = float(mix.get("ramp_seconds", 0))
    shared = int(mix.get("shared_prefix_tokens", 0))
    if mix["kind"] == "open_poisson":
        rate = float(rate_rps if rate_rps is not None else mix["rate_rps"])
        span = ramp + seconds
        n = max(int(round(rate * span)), 1)
        # n arrivals and the gap that closes the span behind the last one
        gaps = [shape.expovariate(1.0) for _ in range(n + 1)]
        scale = span / sum(gaps)
        gaps = [g * scale for g in gaps]
    elif mix["kind"] == "closed":
        # more than the callers can finish: a caller never runs dry
        n = int(mix["clients"]) * max(int((ramp + seconds) / 2.0), 4)
        gaps = None
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    floor = min_unique_tokens(mix)
    sizes = [(max(_draw(mix["unique_tokens"], shape), floor),
              _draw(mix["max_tokens"], shape)) for _ in range(n)]
    out = []
    if gaps is not None:
        t = -ramp
        for i, ((uniq, mx), g) in enumerate(zip(sizes, gaps)):
            t += g
            out.append(Request(i, t, None, shared + uniq, shared, mx,
                               text.getrandbits(31)))
    else:
        for i, (uniq, mx) in enumerate(sizes):
            out.append(Request(i, None, i % int(mix["clients"]),
                               shared + uniq, shared, mx,
                               text.getrandbits(31)))
    return out


def _text(n: int, rng: random.Random) -> str:
    return "".join(rng.choices(_ALPHABET, k=n))


def messages(req: Request, mix: dict) -> list[dict]:
    """Chat messages whose ChatML rendering has req.prompt_tokens characters,
    the first req.shared_tokens of them the same for every request of the
    mix."""
    rng = random.Random(req.text_seed)
    if req.shared_tokens:
        shared = random.Random(mix.get("shape_seed", 0) + 17)
        sys_text = _text(req.shared_tokens - len(_SYS_HEAD), shared)
        rest = (req.prompt_tokens - req.shared_tokens - len(_SYS_TAIL)
                - len(_USER_HEAD) - len(_USER_TAIL))
        return [{"role": "system", "content": sys_text},
                {"role": "user", "content": _text(rest, rng)}]
    rest = req.prompt_tokens - len(_USER_HEAD) - len(_USER_TAIL)
    return [{"role": "user", "content": _text(rest, rng)}]


def body(req: Request, mix: dict) -> dict:
    s = mix["sampling"]
    return {"messages": messages(req, mix), "max_tokens": req.max_tokens,
            "temperature": s["temperature"], "top_p": s["top_p"],
            "stream": True}
