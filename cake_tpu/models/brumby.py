"""Power retention (arXiv:2507.04239, degree 2): the mixer of every layer of
Brumby-14B-Base (`model_type: brumby`), whose block is otherwise Qwen3's.

For the normed input h of a token, Hq query heads and Hkv key/value heads
of width d (Brumby: 40 / 8 x 128, 5 query heads a key/value head):
  1. q = rope(rms_norm_head(h Wq)), k = rope(rms_norm_head(h Wk)),
     v = h Wv: Qwen3's projections, per-head norms and rope, no bias
  2. the gate, one number a key/value head and token, in float32:
     log g = logsigmoid(h Wg + b_g), Wg [Hkv, hidden]
  3. phi(x), the symmetric square of x, width D = d (d + 1) / 2 (x_i x_j
     for i <= j, times sqrt 2 where i < j): phi(q) . phi(k) = (q . k)^2.
     A key/value head's state is S [D, d] and z [D], float32:
       S_t = g_t S_{t-1} + phi(k_t) v_t^T,   z_t = g_t z_{t-1} + phi(k_t)
       y_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)
     for each query head of the group (`retention_step`, the definition)
  4. out = y Wo

With an even power every weight (q . k)^2 is >= 0; the 1 / sqrt(d) scale
would cancel between numerator and denominator and is left out of both.

What runs is the CHUNK form (`retention_chunk`), made of matrix products:
with b_t the running sum of log g inside a chunk of C tokens,
  inside       A_ts = exp(b_t - b_s) (q_t . k_s)^2 for s <= t
  the carry    exp(b_t) phi(q_t)^T S_prev and exp(b_t) phi(q_t) . z_prev
  y_t = (A v + carry's numerator) / (A 1 + carry's denominator + eps)
  S_new = exp(b_C) S_prev + sum_s exp(b_C - b_s) phi(k_s) v_s^T, z alike.
A decode step is the chunk form at C = 1: y = (g S_prev^T phi(q) +
(q . k)^2 v) / (g z_prev . phi(q) + (q . k)^2 + eps) reads the OLD state
once for the read-out, and the update g S_prev + phi(k) v^T goes over the
donated leaf in place (no second copy of it): on the TPU both in ONE pass,
the kernel of ops/retention_state.py; elsewhere an einsum and one
elementwise pass. Padded steps (index >= valid_len) get log g = 0 and a
zero key, so they advance neither S nor z and no `where` runs over the
state.

A row holds {"state": [B, Hkv, d, D'], "norm": [B, Hkv, D']} in float32 and
no keys, values or positions. The state lies TRANSPOSED, its D axis on the
lanes and padded to whole 128-lane tiles (D' = 8,320 for D = 8,256; the pad
holds zeros and phi gives zeros there), so that a key's square is a row
broadcast down the sublanes, a value a column broadcast along the lanes,
and a tile of the state is read once for both the read-out and the update
(ops/retention_state.py, the decode step's kernel): 34.3 MB a layer at
Brumby's widths, whatever the row's length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import apply_rope, linear
from ..ops.norms import rms_norm
from ..ops.retention_state import (LANES, retention_state_step,
                                   state_kernel_enabled)
from .common.layers import init_attention_params
from .common.mixers import Mixer

EPS = 1e-6
# the products against the float32 state (phi(q)^T S, phi(q) . z): None is
# the TPU's default, one bfloat16 pass with float32 accumulation, the
# precision q and k themselves arrive in; the stored state stays float32,
# so nothing rounded is carried from step to step
STATE_PRECISION = None
HI = jax.lax.Precision.HIGHEST


def state_width(d: int) -> int:
    """D: how many products x_i x_j with i <= j a head of width d has."""
    return d * (d + 1) // 2


def padded_width(d: int) -> int:
    """D': D rounded up to whole lane tiles, the width a row holds."""
    return -(-state_width(d) // LANES) * LANES


@functools.lru_cache(maxsize=None)
def _pairs(d: int):
    """(i [D'], j [D'], scale [D']): pair n is x_i x_j, times sqrt 2 where
    i < j; the pad has index -1 (it picks nothing) and scale 0."""
    i, j = np.triu_indices(d)
    pad = padded_width(d) - len(i)
    scale = np.where(i == j, 1.0, np.sqrt(2.0)).astype(np.float32)
    return (np.pad(i, (0, pad), constant_values=-1).astype(np.int32),
            np.pad(j, (0, pad), constant_values=-1).astype(np.int32),
            np.pad(scale, (0, pad)))


def phi(x):
    """x [..., d] -> its symmetric square [..., D'], float32. The picks are
    products with 0/1 matrices (built in the program from the two index
    vectors: 33 KB each, where the matrices would be 4 MB of constants a
    use): exact for bfloat16 x in one pass (float32 accumulation), at the
    highest precision for float32 x."""
    d = x.shape[-1]
    first, second, scale = _pairs(d)
    exact = None if x.dtype == jnp.bfloat16 else HI

    def pick(idx):
        onehot = (jnp.arange(d, dtype=jnp.int32)[:, None]
                  == jnp.asarray(idx)[None, :]).astype(x.dtype)
        return jnp.einsum("...i,in->...n", x, onehot, precision=exact,
                          preferred_element_type=jnp.float32)

    return pick(first) * pick(second) * scale


def retention_step(state, norm, q, k, v, log_g):
    """ONE token, the recurrent form as written: state [B, Hkv, d, D'], norm
    [B, Hkv, D']; q [B, Hq, d], k, v [B, Hkv, d], log_g [B, Hkv]. Returns
    (state, norm, y [B, Hq, d] float32). The definition the chunk form is
    tested against; the programs run `retention_chunk`."""
    b, hq, d = q.shape
    hkv = k.shape[1]
    f32 = jnp.float32
    g = jnp.exp(log_g.astype(f32))
    pk = phi(k)                                           # [B, Hkv, D']
    state = g[..., None, None] * state \
        + v.astype(f32)[..., None] * pk[:, :, None, :]
    norm = g[..., None] * norm + pk
    pq = phi(q).reshape(b, hkv, hq // hkv, -1)            # [B, Hkv, G, D']
    num = jnp.einsum("bhgn,bhdn->bhgd", pq, state, precision=HI)
    den = jnp.einsum("bhgn,bhn->bhg", pq, norm, precision=HI)
    return state, norm, (num / (den[..., None] + EPS)).reshape(b, hq, d)


def retention_chunk(state, norm, q, k, v, log_g, valid_len=None):
    """C tokens at once: q [B, C, Hq, d], k, v [B, C, Hkv, d], log_g
    [B, C, Hkv] float32, on the state [B, Hkv, d, D'] and normaliser
    [B, Hkv, D'] the row carried in. Tokens at index >= valid_len advance
    nothing and are seen by nobody. Returns (state, norm, y [B, C, Hq, d]
    float32)."""
    b, c, hq, d = q.shape
    hkv = k.shape[2]
    f32 = jnp.float32
    exact = None if q.dtype == jnp.bfloat16 else HI
    idx = jnp.arange(c, dtype=jnp.int32)
    valid = jnp.ones((c,), bool) if valid_len is None else idx < valid_len
    with jax.named_scope("cake.attn.retention.expand"):
        pq = phi(q).reshape(b, c, hkv, hq // hkv, -1)     # [B, C, Hkv, G, D']
        pk = phi(k)                                       # [B, C, Hkv, D']
    with jax.named_scope("cake.attn.retention.scan"):
        lg = jnp.where(valid[None, :, None], log_g.astype(f32), 0.0)
        run = jnp.cumsum(lg, axis=1)                      # b_t  [B, C, Hkv]
        end = run[:, -1]                                  # b_C  [B, Hkv]
        qg = q.reshape(b, c, hkv, hq // hkv, d)
        vf = v.astype(f32)
        # inside the chunk: (q_t . k_s)^2 under the decay from s to t
        qk = jnp.einsum("bthgd,bshd->bhgts", qg, k, precision=exact,
                        preferred_element_type=f32)
        seen = (idx[None, :] <= idx[:, None]) & valid[None, :]   # [t, s]
        fade = run.transpose(0, 2, 1)[:, :, :, None] \
            - run.transpose(0, 2, 1)[:, :, None, :]       # b_t - b_s
        a = jnp.exp(jnp.where(seen, fade, -jnp.inf))[:, :, None] \
            * jnp.square(qk)                              # [B, Hkv, G, t, s]
        num = jnp.einsum("bhgts,bshd->bthgd", a, vf, precision=HI)
        den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)   # [B, C, Hkv, G]
        # against the carried state, read once as the row brought it in,
        # and the state behind the chunk: exp(b_C) S_prev + the keys' part
        carry = jnp.exp(run)[..., None]                   # [B, C, Hkv, 1]
        keep = jnp.exp(end)                               # [B, Hkv]
        w = jnp.where(valid[None, :, None],
                      jnp.exp(end[:, None] - run), 0.0)   # [B, C, Hkv]
        pkw = pk * w[..., None]
        if c == 1 and state_kernel_enabled():
            # a decode step: ONE pass over the leaf reads a tile for the
            # read-out and writes its update back in place
            read, state = retention_state_step(
                state, pq[:, 0], pkw[:, 0], vf[:, 0], keep)
            read = read[:, None]
        else:
            read = jnp.einsum("bthgn,bhdn->bthgd", pq, state,
                              precision=STATE_PRECISION)
            if c == 1:
                # an outer product, elementwise: fuses with the decay into
                # one read and write of the leaf
                add = vf[:, 0, :, :, None] * pkw[:, 0, :, None, :]
            else:
                add = jnp.einsum("bshn,bshd->bhdn", pkw, vf, precision=HI)
            state = keep[..., None, None] * state + add
        num = num + carry[..., None] * read
        den = den + carry * jnp.einsum(
            "bthgn,bhn->bthg", pq, norm, precision=STATE_PRECISION)
        y = (num / (den[..., None] + EPS)).reshape(b, c, hq, d)
        norm = keep[..., None] * norm + jnp.sum(pkw, axis=1)
    return state, norm, y


def init_retention_cache(cfg, spec, batch: int, max_seq_len: int,
                         dtype) -> dict:
    """A retention layer's rows: S and z a key/value head, float32 whatever
    the model's dtype. No `pos` leaf: recurrent rows."""
    a = cfg.attn_shape(spec)
    width = padded_width(a.head_dim)
    return {"state": jnp.zeros((batch, a.kv_heads, a.head_dim, width),
                               jnp.float32),
            "norm": jnp.zeros((batch, a.kv_heads, width), jnp.float32)}


def init_retention_params(cfg, spec, key, dtype):
    """Qwen3's attention leaves and the gate's projection: logits of std ~1
    from the token, a bias of std 4 a head so that half-lives spread from
    under a token to hundreds (a constant gate is one a norm hides)."""
    p = init_attention_params(cfg, spec, key, dtype)
    a, hid = cfg.attn_shape(spec), cfg.hidden_size
    kw, kb = jax.random.split(jax.random.fold_in(key, 7))
    p["g_proj"] = {
        "weight": jax.random.normal(kw, (a.kv_heads, hid), dtype)
        / hid ** 0.5,
        "bias": jax.random.normal(kb, (a.kv_heads,), dtype) * 4.0}
    return p


def retention_forward(cfg, spec, p, x, layer_cache, pos0, rope,
                      valid_len=None):
    """x: [B, S, hidden]. Returns (y [B, S, hidden], new_layer_cache).
    layer_cache None is the stateless path (a zero state, nothing kept)."""
    b, s, _ = x.shape
    a = cfg.attn_shape(spec)
    hq, hkv, d = a.heads, a.kv_heads, a.head_dim
    f32 = jnp.float32
    with jax.named_scope("cake.attn.retention.proj"):
        q = linear(x, p["q_proj"]["weight"]).reshape(b, s, hq, d)
        k = linear(x, p["k_proj"]["weight"]).reshape(b, s, hkv, d)
        v = linear(x, p["v_proj"]["weight"]).reshape(b, s, hkv, d)
        q = rms_norm(q, p["q_norm"]["weight"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm"]["weight"], cfg.rms_norm_eps)
        positions = pos0 + jnp.arange(s, dtype=jnp.int32)
        rd = cfg.rotary_dim_of(spec)
        q = apply_rope(q, rope["cos"], rope["sin"], positions, rd)
        k = apply_rope(k, rope["cos"], rope["sin"], positions, rd)
        log_g = jax.nn.log_sigmoid(
            linear(x, p["g_proj"]["weight"]).astype(f32)
            + p["g_proj"]["bias"].astype(f32))            # [B, S, Hkv]
    if layer_cache is None:
        width = padded_width(d)
        state = jnp.zeros((b, hkv, d, width), f32)
        norm = jnp.zeros((b, hkv, width), f32)
    else:
        state, norm = layer_cache["state"], layer_cache["norm"]
    state, norm, y = retention_chunk(state, norm, q, k, v, log_g, valid_len)
    with jax.named_scope("cake.attn.retention.proj"):
        out = linear(y.reshape(b, s, hq * d).astype(x.dtype),
                     p["o_proj"]["weight"])
    return out, (None if layer_cache is None
                 else {"state": state, "norm": norm})


def describe_retention(cfg, spec) -> dict:
    """A retention layer's entry of ModelConfig.attention_kinds(): the
    power, the heads, the state's width (D, and D' as it lies) and the
    float32 bytes a row holds (S and z of every key/value head)."""
    a = cfg.attn_shape(spec)
    width = padded_width(a.head_dim)
    return {"kind": spec.kind, "layers": 1, "power": cfg.retention.power,
            "heads": a.heads, "kv_heads": a.kv_heads, "key_dim": a.head_dim,
            "state_width": state_width(a.head_dim), "padded_width": width,
            "rotary_dim": cfg.rotary_dim_of(spec),
            "rope_theta": cfg.rope_theta,
            "state_bytes": 4 * a.kv_heads * width * (a.head_dim + 1)}


# -- checkpoint IO -----------------------------------------------------------
# Qwen3's names under `<layer>.self_attn.` and the gate's beside them as
# `g_proj.{weight,bias}` (assumed: no checkpoint is in the repository); a
# checkpoint without the bias loads with a zero one.


def load_retention_params(loader, lp: str, spec) -> dict:
    p = loader._attention(lp, spec)
    w = loader._dev(loader._get(f"{lp}.self_attn.g_proj.weight"))
    bias = f"{lp}.self_attn.g_proj.bias"
    p["g_proj"] = {
        "weight": w,
        "bias": (loader._dev(loader._get_dense(bias)) if loader._has(bias)
                 else jnp.zeros((w.shape[0],), w.dtype))}
    return p


def export_retention_params(cfg, p, lp: str) -> dict:
    from ..utils.export import export_attention_params
    out = export_attention_params(cfg, p, lp)
    out[f"{lp}.self_attn.g_proj.bias"] = np.asarray(p["g_proj"]["bias"])
    return out


MIXER = Mixer(
    param_key="self_attn", scopes=("cake.attn", "cake.attn.retention"),
    recurrent=True,
    init_params=init_retention_params,
    load_params=load_retention_params,
    export_params=export_retention_params,
    init_cache=init_retention_cache,
    forward=lambda cfg, spec, p, x, lc, pos0, rope, valid_len, flash_mode,
        mesh: retention_forward(cfg, spec, p, x, lc, pos0, rope, valid_len),
    describe=describe_retention)
