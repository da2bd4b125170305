"""Rotary position embeddings.

TPU-native RoPE: cos/sin tables are precomputed once per model in f32
(ref: models/common/cache.rs:49-99 — incl. llama3 frequency scaling) and
gathered by position index inside the jitted step, so decode (pos is a
traced scalar) and bucketed prefill reuse the same compiled code.

Layout note: the reference applies RoPE on [B, H, S, D] after transpose
(ref: attention.rs apply_rotary_emb). We keep activations in [B, S, H, D]
throughout — on TPU the einsum-based attention never needs the transpose.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Frequency scaling by `rope_type`: llama3 smoothing (ref: config.rs
    RopeScaling), linear interpolation, or YaRN (`beta_fast`, `beta_slow`,
    `attention_factor`; None = 0.1 ln(factor) + 1)."""
    factor: float = 8.0
    high_freq_factor: float = 4.0
    low_freq_factor: float = 1.0
    original_max_position_embeddings: int = 8192
    rope_type: str | None = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None


def yarn_attention_factor(scaling: RopeScaling | None) -> float:
    """What YaRN multiplies cos and sin by (so q.k grows by its square):
    the configuration's `attention_factor` or 0.1 ln(factor) + 1; 1.0 for
    every other rope_type."""
    if scaling is None or scaling.rope_type != "yarn":
        return 1.0
    if scaling.attention_factor is not None:
        return float(scaling.attention_factor)
    return 0.1 * float(np.log(scaling.factor)) + 1.0 \
        if scaling.factor > 1.0 else 1.0


def inv_frequencies(rotary_dim: int, theta: float,
                    scaling: RopeScaling | None = None) -> np.ndarray:
    """Per-pair inverse frequencies, with optional llama3 smoothing
    (ref: cache.rs:49-80), linear interpolation or YaRN's blend (HF
    `_compute_yarn_parameters`, truncated ramp)."""
    inv = 1.0 / (theta ** (np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim))
    if scaling is None or not scaling.factor or scaling.factor == 1.0:
        return inv.astype(np.float64)
    if scaling.rope_type == "linear":
        # uniform position interpolation (HF "linear"; Gemma3 global layers)
        inv = inv / scaling.factor
    elif scaling.rope_type == "default":
        pass                        # HF "default" ignores the factor
    elif scaling.rope_type in (None, "llama3"):
        low_wavelen = scaling.original_max_position_embeddings / scaling.low_freq_factor
        high_wavelen = scaling.original_max_position_embeddings / scaling.high_freq_factor
        wavelen = 2.0 * np.pi / inv
        scaled = np.where(wavelen > low_wavelen, inv / scaling.factor, inv)
        smooth = (scaling.original_max_position_embeddings / wavelen
                  - scaling.low_freq_factor) / (scaling.high_freq_factor
                                                - scaling.low_freq_factor)
        mid = (1.0 - smooth) * inv / scaling.factor + smooth * inv
        is_mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
        inv = np.where(is_mid, mid, scaled)
    elif scaling.rope_type == "yarn":
        # pairs that turn more than beta_fast times within the original
        # context keep their frequency, those that turn fewer than
        # beta_slow times are interpolated by the factor, a linear ramp
        # between the two pair indices in between
        n, orig = rotary_dim // 2, scaling.original_max_position_embeddings

        def pair_of(turns):
            return (rotary_dim * np.log(orig / (turns * 2.0 * np.pi))
                    / (2.0 * np.log(theta)))

        low = max(np.floor(pair_of(scaling.beta_fast)), 0.0)
        high = min(np.ceil(pair_of(scaling.beta_slow)), rotary_dim - 1.0)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(n, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        inv = inv / scaling.factor * ramp + inv * (1.0 - ramp)
    else:
        # the flavors still not implemented (dynamic, longrope) degrade to
        # unscaled RoPE with a warning — same tolerance posture as the
        # unknown-architecture fallback (config.py ARCH_ADAPTERS)
        import logging
        logging.getLogger(__name__).warning(
            "rope_type %r not implemented (dynamic and longrope are not); "
            "using unscaled RoPE", scaling.rope_type)
    return inv.astype(np.float64)


def rope_tables(max_seq_len: int, rotary_dim: int, theta: float,
                scaling: RopeScaling | None = None,
                dtype=jnp.float32):
    """Precompute (cos, sin) of shape [max_seq_len, rotary_dim // 2]; under
    YaRN both carry its attention factor."""
    inv = inv_frequencies(rotary_dim, theta, scaling)
    t = np.arange(max_seq_len, dtype=np.float64)
    freqs = np.outer(t, inv)
    cos, sin = np.cos(freqs), np.sin(freqs)
    mscale = yarn_attention_factor(scaling)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    return jnp.asarray(cos, dtype=dtype), jnp.asarray(sin, dtype=dtype)


def apply_rope(x, cos, sin, positions, rotary_dim: int | None = None,
               interleaved: bool = False):
    """Apply RoPE to x: [B, S, H, D] with positions: [B, S] or [S] (int32).

    rotary_dim < D applies partial RoPE to the first rotary_dim channels and
    passes the rest through (ref: attention.rs apply_rotary_emb; Phi-4
    partial_rotary_factor 0.25).
    """
    d = x.shape[-1]
    rd = d if rotary_dim is None else rotary_dim
    if positions.ndim == 1:
        positions = positions[None, :]
    c = cos[positions][:, :, None, :].astype(jnp.float32)   # [B, S, 1, rd/2]
    s = sin[positions][:, :, None, :].astype(jnp.float32)

    x_rot, x_pass = x[..., :rd], x[..., rd:]
    xf = x_rot.astype(jnp.float32)
    if interleaved:
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        o1 = x1 * c - x2 * s
        o2 = x1 * s + x2 * c
        out = jnp.stack([o1, o2], axis=-1).reshape(xf.shape)
    else:
        half = rd // 2
        x1 = xf[..., :half]
        x2 = xf[..., half:]
        o1 = x1 * c - x2 * s
        o2 = x1 * s + x2 * c
        out = jnp.concatenate([o1, o2], axis=-1)
    out = out.astype(x.dtype)
    if rd == d:
        return out
    return jnp.concatenate([out, x_pass], axis=-1)
