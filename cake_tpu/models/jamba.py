"""Jamba's Mamba-1 mixer: a selective state space with a diagonal state
per channel (HF `JambaMambaMixer`, slow path; Gu & Dao 2023), with Jamba's
RMS norms on dt, B and C. For token t of a row, u the normed input:

    [xr_t ; z_t]      = W_in u_t
    xc_t              = silu(sum_j w_conv[:, j] xr_{t-3+j} + b_conv)
    [dt_t; B_t; C_t]  = W_x xc_t
    dt_t              = softplus(W_dt RMSNorm_dt(dt_t) + b_dt)
    B_t, C_t          = RMSNorm_b(B_t), RMSNorm_c(C_t)
    h_t               = exp(dt_t A) h_{t-1} + (dt_t xc_t) B_t^T,  A = -exp(A_log)
    out_t             = W_out ((h_t C_t + D xc_t) silu(z_t))

The row's state lives in the cache pytree beside keys and values, with no
`pos` leaf (cache.is_positional is False: copied whole, never rolled back):
`conv` holds the last d_conv-1 conv inputs, `ssm` the state h. Both keep
d_inner as their LAST axis ([B, 3, 5120] and [B, 16, 5120]): the chip's
vector lanes run along an array's last axis, so every elementwise op of the
state update fills them whole, whatever layout the compiler would pick for
an axis of 16 or of 3 (it stores either order without padding).

The matrix products take their operands in the model dtype; those whose
result feeds the float32 arithmetic hand back the float32 they accumulate
in (`_proj`) and not its bf16 rounding, which is free on the chip and a
tenth of the served logits' distance from the float32 reference (CPU,
hidden 256, 28 layers: 0.151 -> 0.134). Everything between the products is
float32, as in gdn_forward. A one-token step takes the closed form
(no loop in the lowered decode program); a chunk scans its tokens one at a
time with the state as the carry: in the benchmark's traced run the 26
scans are 8.3 ms of op time in a 256-token chunk's 21 ms on a v5e, and
unrolling the loop 8 to 64 tokens an iteration read slower in a
micro-benchmark of one layer (CHANGES.md, PR 36).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.linear import linear
from ..ops.norms import rms_norm

# what the checkpoint calls the leaves the parameter tree names as every
# other family does (utils/loaders.py, utils/export.py)
CHECKPOINT_NAMES = {"mlp": "feed_forward",
                    "post_attention_layernorm": "pre_ff_layernorm",
                    "norm": "final_layernorm"}


def _proj(x, w):
    """x [..., in] @ w[out, in]^T, float32 out of operands as they are."""
    return jnp.einsum("...i,oi->...o", x, w,
                      preferred_element_type=jnp.float32)


def init_mamba_params(cfg, key, dtype):
    """Mamba's published initialisation: A = -(1..d_state) on every
    channel, dt's bias the inverse softplus of a step log-uniform in
    0.001-0.1, D ones."""
    m, h = cfg.mamba, cfg.hidden_size
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[5], (m.d_inner,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    p = {
        "in_proj": {"weight": jax.random.normal(
            ks[0], (2 * m.d_inner, h), dtype) * 0.02},
        "conv1d": {"weight": jax.random.normal(
            ks[1], (m.d_inner, 1, m.d_conv), dtype) * 0.2},
        "x_proj": {"weight": jax.random.normal(
            ks[2], (m.dt_rank + 2 * m.d_state, m.d_inner), dtype) * 0.02},
        "dt_proj": {
            "weight": jax.random.normal(ks[3], (m.d_inner, m.dt_rank), dtype)
            * m.dt_rank ** -0.5,
            "bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)},
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, m.d_state + 1, dtype=jnp.float32),
            (m.d_inner, m.d_state))),
        "D": jnp.ones((m.d_inner,), jnp.float32),
        "out_proj": {"weight": jax.random.normal(
            ks[4], (h, m.d_inner), dtype) * 0.02},
        "dt_layernorm": {"weight": jnp.ones((m.dt_rank,), dtype)},
        "b_layernorm": {"weight": jnp.ones((m.d_state,), dtype)},
        "c_layernorm": {"weight": jnp.ones((m.d_state,), dtype)},
    }
    if m.conv_bias:
        p["conv1d"]["bias"] = jnp.zeros((m.d_inner,), dtype)
    return p


def init_mamba_cache(cfg, batch: int, dtype) -> dict:
    m = cfg.mamba
    return {"conv": jnp.zeros((batch, m.d_conv - 1, m.d_inner), dtype),
            "ssm": jnp.zeros((batch, m.d_state, m.d_inner), jnp.float32)}


def mamba_forward(cfg, p, x, layer_cache, pos0, valid_len=None):
    """x: [B, S, H]. Returns (y [B, S, H], new_layer_cache).

    layer_cache: {"conv": [B, d_conv-1, d_inner] model dtype, "ssm":
    [B, d_state, d_inner] f32} or None (the stateless training path). A
    padded position (index >= valid_len) advances neither leaf."""
    m = cfg.mamba
    b, s, _ = x.shape
    di, n, r, k = m.d_inner, m.d_state, m.dt_rank, m.d_conv
    eps, f32, in_dtype = cfg.rms_norm_eps, jnp.float32, x.dtype
    vl = jnp.asarray(s, jnp.int32) if valid_len is None else valid_len

    with jax.named_scope("cake.ssm.proj"):
        xz = _proj(x, p["in_proj"]["weight"])
        xr, z = xz[..., :di], xz[..., di:]

    with jax.named_scope("cake.ssm.conv"):
        tail = (layer_cache["conv"].astype(f32) if layer_cache is not None
                else jnp.zeros((b, k - 1, di), f32))
        padded = jnp.concatenate([tail, xr], axis=1)        # [B, S+K-1, di]
        w = p["conv1d"]["weight"].astype(f32)[:, 0, :]      # [di, K]
        pre = sum(padded[:, j:j + s] * w[:, j] for j in range(k))
        if "bias" in p["conv1d"]:
            pre = pre + p["conv1d"]["bias"].astype(f32)
        xc = jax.nn.silu(pre)                               # [B, S, di]
        # the next tail: the last K-1 VALID inputs
        new_tail = (jnp.where(vl > 0, padded[:, 1:], tail) if s == 1 else
                    jax.lax.dynamic_slice_in_dim(padded, vl, k - 1, axis=1))

    with jax.named_scope("cake.ssm.proj"):
        par = _proj(xc.astype(in_dtype), p["x_proj"]["weight"])
        dt = rms_norm(par[..., :r], p["dt_layernorm"]["weight"], eps)
        bm = rms_norm(par[..., r:r + n], p["b_layernorm"]["weight"], eps)
        cm = rms_norm(par[..., r + n:], p["c_layernorm"]["weight"], eps)
        dt = jax.nn.softplus(
            _proj(dt.astype(in_dtype), p["dt_proj"]["weight"])
            + p["dt_proj"]["bias"].astype(f32))             # [B, S, di]

    with jax.named_scope("cake.ssm.scan"):
        a = -jnp.exp(p["A_log"].astype(f32)).T              # [n, di]
        h0 = (layer_cache["ssm"] if layer_cache is not None
              else jnp.zeros((b, n, di), f32))

        def step(h, inp):
            dt_t, xc_t, b_t, c_t, ok = inp                  # [B, di|n], []
            new = (jnp.exp(dt_t[:, None, :] * a) * h
                   + (dt_t * xc_t)[:, None, :] * b_t[:, :, None])
            y_t = jnp.sum(new * c_t[:, :, None], axis=1)    # [B, di]
            return jnp.where(ok, new, h), y_t               # pads: no advance

        if s == 1:
            h, y = step(h0, (dt[:, 0], xc[:, 0], bm[:, 0], cm[:, 0], vl > 0))
            y = y[:, None]
        else:
            tm = lambda t: jnp.moveaxis(t, 1, 0)            # time-major
            valid = jnp.arange(s, dtype=jnp.int32) < vl
            h, y = jax.lax.scan(step, h0,
                                (tm(dt), tm(xc), tm(bm), tm(cm), valid))
            y = jnp.moveaxis(y, 0, 1)                       # [B, S, di]
        y = (y + p["D"].astype(f32) * xc) * jax.nn.silu(z)

    with jax.named_scope("cake.ssm.proj"):
        out = linear(y.astype(in_dtype), p["out_proj"]["weight"])

    new_cache = None
    if layer_cache is not None:
        new_cache = {"conv": new_tail.astype(layer_cache["conv"].dtype),
                     "ssm": h}
    return out, new_cache


# -- checkpoint IO -----------------------------------------------------------

_MATRICES = ("in_proj", "x_proj", "out_proj")
_NORMS = ("dt_layernorm", "b_layernorm", "c_layernorm")


def load_mamba_params(loader, lp: str) -> dict:
    """lp = '<prefix>.layers.<i>'; weights under `.mamba.` (HF names)."""
    base, dev, dt = f"{lp}.mamba", loader._dev, loader.dtype
    g = loader._get_dense
    p = {name: {"weight": dev(g(f"{base}.{name}.weight"), dt)}
         for name in _MATRICES + _NORMS}
    p["conv1d"] = {"weight": dev(g(f"{base}.conv1d.weight"), dt)}
    if loader._has(f"{base}.conv1d.bias"):
        p["conv1d"]["bias"] = dev(g(f"{base}.conv1d.bias"), dt)
    p["dt_proj"] = {"weight": dev(g(f"{base}.dt_proj.weight"), dt),
                    "bias": dev(g(f"{base}.dt_proj.bias"), dt)}
    # what feeds exp() on the state every step stays F32 (as GDN's gates)
    p["A_log"] = dev(g(f"{base}.A_log"), jnp.float32)
    p["D"] = dev(g(f"{base}.D"), jnp.float32)
    return p


def export_mamba_params(p: dict, lp: str) -> dict:
    import numpy as np
    base = f"{lp}.mamba"
    out = {f"{base}.{name}.weight": np.asarray(p[name]["weight"])
           for name in _MATRICES + _NORMS + ("conv1d", "dt_proj")}
    out[f"{base}.dt_proj.bias"] = np.asarray(p["dt_proj"]["bias"])
    if "bias" in p["conv1d"]:
        out[f"{base}.conv1d.bias"] = np.asarray(p["conv1d"]["bias"])
    out[f"{base}.A_log"] = np.asarray(p["A_log"])
    out[f"{base}.D"] = np.asarray(p["D"])
    return out
