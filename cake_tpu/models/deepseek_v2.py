"""Multi-head latent attention (MLA, arXiv:2405.04434): the mixer of every
layer of DeepSeek-V2 (`model_type: deepseek_v2`), whose block is otherwise
the pre-norm one with a dense or a sparse FFN, of one layer in six of
Ling-3.0-flash (`ling3`), beside Kimi Delta Attention, and of both
sub-layers of a LongCat-Flash layer (`longcat_flash`, models/longcat_flash.py).

For the normed input x of a token, H heads, widths `qk_nope` + `qk_rope` a
query and key head, `v` a value head, ranks `q_lora` and `kv_lora`:
  queries  c_q = RMSNorm(W_qa x);  q = W_qb c_q as [H, nope + rope],
           each head [q_nope ; q_pe]; `q_lora_rank` None (Ling-3.0): q = W_q x,
           ONE matrix of full rank, no norm
  latent   [c ; k_pe] = W_kva x;  c_kv = RMSNorm(c);  k_pe is ONE key part
           all heads share. A position of a row holds [c_kv ; rope(k_pe)]
           (576 numbers for DeepSeek-V2) and no keys or values by head.
  expanded [k_nope^h ; v^h] = W_kvb^h c_kv;  k^h = [k_nope^h ; rope(k_pe)],
           q^h = [q_nope^h ; rope(q_pe^h)];
           o^h = softmax_causal(s q^h . k^h) v^h;  y = W_o [o^1 .. o^H]
  absorbed (the same numbers) with W_kvb^h = [W_uk^h ; W_uv^h]:
           q_lat^h = W_uk^h^T q_nope^h [kv_lora];
           score = s (q_lat^h . c_kv + rope(q_pe^h) . rope(k_pe));
           o_lat^h = sum p c_kv;  o^h = W_uv^h o_lat^h
  gated    `cfg.attn_head_gate` (Ling-3.0's `head_wise` gate): o^h times
           sigmoid(W_g x)[h], W_g [H, hidden], before W_o, in either form
  scaled   `la.q_scale`, `la.kv_scale` (LongCat-Flash's `mla_scale_q_lora`,
           `mla_scale_kv_lora`: (hidden / rank)^1/2): W_qb reads q_scale c_q
           and W_kvb reads kv_scale c_kv; rope(k_pe) is NOT scaled. A row
           still holds [c_kv ; rope(k_pe)], unscaled, as the other latent
           families store it, and the absorbed form carries kv_scale where
           W_uk and W_uv are absorbed: q_lat^h = kv_scale W_uk^h^T q_nope^h,
           o^h = kv_scale W_uv^h o_lat^h. So cache.py, both pools and the
           prefix cache see nothing new, and a prefix hit restores what a
           fresh prefill writes
The scale s is `cfg.attn_scale`: (nope + rope)^-1/2 times the SQUARE of
YaRN's mscale_all_dim factor, which DeepSeek-V2 puts there and not on cos
and sin (those carry mscale / mscale_all_dim; config._deepseek_v2).

ONE rule says which form runs, by what the step is given (`latent_forward`):
  * a cache (a decode step of one token, a chunk of a prompt, a verify
    step): the rows are written first, then the step reads the buffer
    ABSORBED: 128 query heads against one shared 576-wide key whose first
    512 columns are the value. A chunk absorbs too: expanded it would do
    less arithmetic past ~180 queries (1.13 against 1.48 TFLOP a layer at
    256 queries behind 20.7k latents) but would write and read back 1.36 GB
    of keys and values a layer, or need the expansion inside a kernel of
    its own, block by block; absorbed, the ONE kernel of
    ops/latent_attention.py serves both widths and nothing is expanded.
  * no cache (the stateless pass): the expanded form over the pass, the
    form the definition is written in.

Rope runs on `qk_rope` dims in HALVES (ops.rope.apply_rope); the published
checkpoint pairs them INTERLEAVED, (2i, 2i + 1), and de-interleaves at run
time. Here the rope rows of `q_b_proj` and `kv_a_proj_with_mqa` are
de-interleaved once, at load, and put back at export: q . k is a sum over
the pairs, whatever their order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import apply_rope, linear, make_attention_mask, \
    multi_head_attention
from ..ops.latent_attention import latent_attention, latent_block_k, \
    latent_read
from ..ops.norms import rms_norm
from .common.cache import init_attention_cache, latent_row_width, \
    write_entries
from .common.mixers import Mixer


def kernel_enabled() -> bool:
    """Does the absorbed read run the Pallas kernel
    (`cake_latent_decode_attention`): on TPU backends, with the other
    attention kernels' knob."""
    from ..ops.flash import flash_enabled
    return flash_enabled()


def init_latent_params(cfg, spec, key, dtype):
    la, hid, h = cfg.latent_attn, cfg.hidden_size, cfg.num_attention_heads
    ks = jax.random.split(key, 5)

    def w(k, shape):
        return {"weight": jax.random.normal(k, shape, dtype) * 0.02}

    if la.q_lora_rank is None:
        q = {"q_proj": w(ks[0], (h * la.qk_head_dim, hid))}
    else:
        q = {"q_a_proj": w(ks[0], (la.q_lora_rank, hid)),
             "q_a_layernorm": {"weight": jnp.ones((la.q_lora_rank,), dtype)},
             "q_b_proj": w(ks[1], (h * la.qk_head_dim, la.q_lora_rank))}
    if cfg.attn_head_gate:
        # gate logits of std ~1, as layers.init_attention_params draws them
        q["g_proj"] = {"weight": jax.random.normal(
            jax.random.fold_in(key, 5), (h, hid), dtype) / hid ** 0.5}
    return {
        **q,
        "kv_a_proj_with_mqa": w(ks[2], (la.row_width, hid)),
        "kv_a_layernorm": {"weight": jnp.ones((la.kv_lora_rank,), dtype)},
        "kv_b_proj": w(ks[3], (h * (la.qk_nope_head_dim + la.v_head_dim),
                               la.kv_lora_rank)),
        "o_proj": w(ks[4], (hid, h * la.v_head_dim)),
    }


def _in_lanes(parts: list, width: int):
    """The parts side by side along the last axis with zeros behind them up
    to `width`, as ONE concatenation (a pad behind a concatenation lays the
    narrower result out first: a copy of a chunk's queries more)."""
    pad = width - sum(p.shape[-1] for p in parts)
    if pad:
        parts = parts + [jnp.zeros(parts[0].shape[:-1] + (pad,),
                                   parts[0].dtype)]
    return jnp.concatenate(parts, axis=-1)


def latent_forward(cfg, spec, p, x, layer_cache, pos0, rope, valid_len=None,
                   flash_mode: str = "off", mesh=None):
    """x: [B, S, hidden]. Returns (y [B, S, hidden], new_layer_cache).
    layer_cache None is the stateless path: the expanded form."""
    b, s, _ = x.shape
    la, h, eps = cfg.latent_attn, cfg.num_attention_heads, cfg.rms_norm_eps
    dn, dr, dv, r = (la.qk_nope_head_dim, la.qk_rope_head_dim,
                     la.v_head_dim, la.kv_lora_rank)
    positions = pos0 + jnp.arange(s, dtype=jnp.int32)
    with jax.named_scope("cake.attn.latent.proj"):
        if la.q_lora_rank is None:
            q = linear(x, p["q_proj"]["weight"])
        else:
            c_q = rms_norm(linear(x, p["q_a_proj"]["weight"]),
                           p["q_a_layernorm"]["weight"], eps)
            if la.q_scale != 1.0:
                c_q = c_q * jnp.asarray(la.q_scale, c_q.dtype)
            q = linear(c_q, p["q_b_proj"]["weight"])
        q = q.reshape(b, s, h, dn + dr)
        ckv = linear(x, p["kv_a_proj_with_mqa"]["weight"])     # [B, S, r+dr]
        c_kv = rms_norm(ckv[..., :r], p["kv_a_layernorm"]["weight"], eps)
        cos, sin = rope["cos"], rope["sin"]
        q_nope = q[..., :dn]
        q_pe = apply_rope(q[..., dn:], cos, sin, positions)
        k_pe = apply_rope(ckv[:, :, None, r:], cos, sin, positions)[:, :, 0]
    w_kvb = p["kv_b_proj"]["weight"].reshape(h, dn + dv, r)
    q_pos = jnp.broadcast_to(positions[None, :], (b, s))
    if layer_cache is None:
        with jax.named_scope("cake.attn.latent.expand"):
            if la.kv_scale != 1.0:
                c_kv = c_kv * jnp.asarray(la.kv_scale, c_kv.dtype)
            kv = jnp.einsum("bsc,hnc->bshn", c_kv, w_kvb)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, dr))], -1)
            v = kv[..., dn:]
            qf = jnp.concatenate([q_nope, q_pe], -1)
        idx = jnp.arange(s, dtype=jnp.int32)
        kv_pos = q_pos if valid_len is None else jnp.where(
            idx[None, :] < valid_len, q_pos, -1)               # pads invisible
        with jax.named_scope("cake.attn.latent.read"):
            o = multi_head_attention(qf, k, v,
                                     make_attention_mask(q_pos, kv_pos),
                                     scale=cfg.attn_scale)
        new_cache = None
    else:
        # write the step's rows, then read the buffer as it lies: a latent
        # layer has no window, so buffer index == position
        width = layer_cache["kv"].shape[-1]
        row = _in_lanes([c_kv, k_pe], width)
        new_cache = write_entries(layer_cache, {"kv": row}, pos0, valid_len)
        with jax.named_scope("cake.attn.latent.absorb"):
            q_lat = jnp.einsum("bshn,hnc->bshc", q_nope, w_kvb[:, :dn])
            if la.kv_scale != 1.0:
                # k_nope = W_uk (kv_scale c_kv): the scale rides on the
                # absorbed query, the row keeps c_kv as it is
                q_lat = q_lat * jnp.asarray(la.kv_scale, q_lat.dtype)
            q_abs = _in_lanes([q_lat, q_pe], width)
        kv, kv_pos = new_cache["kv"], new_cache["pos"]
        with jax.named_scope("cake.attn.latent.read"):
            if (kernel_enabled() and mesh is None and kv.dtype == q_abs.dtype
                    and latent_block_k(kv.shape[1]) is not None):
                # each row to its own frontier; a row that valid_len 0
                # masks out of the step reads nothing
                n = s if valid_len is None else valid_len
                limit = jnp.where(n > 0, pos0 + n, 0)
                o_lat = latent_attention(q_abs, kv, kv_pos, pos0, limit, r,
                                         scale=cfg.attn_scale)
            else:
                o_lat = latent_read(q_abs, kv, kv_pos, q_pos, r,
                                    cfg.attn_scale)
        with jax.named_scope("cake.attn.latent.absorb"):
            o = jnp.einsum("bshc,hvc->bshv", o_lat, w_kvb[:, dn:])
            if la.kv_scale != 1.0:
                # v = W_uv (kv_scale c_kv), alike
                o = o * jnp.asarray(la.kv_scale, o.dtype)
    if cfg.attn_head_gate:
        # one sigmoid gate a head, from the layer's normed input
        with jax.named_scope("cake.attn.gate"):
            gate = jax.nn.sigmoid(
                linear(x, p["g_proj"]["weight"]).astype(jnp.float32))
            o = o * gate[..., None].astype(o.dtype)
    with jax.named_scope("cake.attn.latent.proj"):
        y = linear(o.reshape(b, s, h * dv).astype(x.dtype),
                   p["o_proj"]["weight"])
    return y, new_cache


def describe_latent(cfg, spec) -> dict:
    """A latent layer's entry of ModelConfig.attention_kinds(): the heads,
    the ranks and the three head widths, what a position of a row holds
    (`row_width` numbers, in `row_lanes` as it lies) and its bytes in
    bfloat16, the served dtype, summed over the layers; the latents' scales
    where a family has them (a row holds the unscaled latent)."""
    la = cfg.latent_attn
    scaled = {} if la.q_scale == la.kv_scale == 1.0 else {
        "q_scale": la.q_scale, "kv_scale": la.kv_scale}
    return {"kind": spec.kind, "layers": 1, "heads": cfg.num_attention_heads,
            "q_lora_rank": la.q_lora_rank, "kv_lora_rank": la.kv_lora_rank,
            "qk_nope_head_dim": la.qk_nope_head_dim,
            "qk_rope_head_dim": la.qk_rope_head_dim,
            "v_head_dim": la.v_head_dim, "row_width": la.row_width,
            "row_lanes": latent_row_width(la.row_width),
            "rotary_dim": la.qk_rope_head_dim, "rope_theta": cfg.rope_theta,
            "rope_scaling": (cfg.rope_scaling.rope_type
                             if cfg.rope_scaling is not None else None),
            "row_bytes": 2 * la.row_width, **scaled}


# -- checkpoint IO -----------------------------------------------------------
# The published names under `<layer>.self_attn.`; the rope rows of q_b_proj
# (the last qk_rope of every head; of q_proj where the queries are of full
# rank) and of kv_a_proj_with_mqa (the last qk_rope) change between the
# checkpoint's interleaved pairs and the halves apply_rope takes.

def _leaf_names(cfg) -> tuple:
    """(plain matrices, norms, the query matrix whose heads end in rope
    rows): by whether the queries pass a low-rank pair, and the head gate."""
    if cfg.latent_attn.q_lora_rank is None:
        plain, norms, q = ("kv_b_proj", "o_proj"), ("kv_a_layernorm",), \
            "q_proj"
    else:
        plain, norms, q = ("q_a_proj", "kv_b_proj", "o_proj"), \
            ("q_a_layernorm", "kv_a_layernorm"), "q_b_proj"
    if cfg.attn_head_gate:
        plain += ("g_proj",)
    return plain, norms, q


def _halves(dr: int) -> np.ndarray:
    """Interleaved -> halves: row i of the result is row _halves[i] of the
    checkpoint's rope rows (x0 x1 .. then y0 y1 ..)."""
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def _reorder_rope_rows(w: np.ndarray, heads: int, dr: int,
                       order: np.ndarray) -> np.ndarray:
    """w [heads * per, in]: the last dr rows of every head's `per` taken in
    `order`."""
    per = w.shape[0] // heads
    w3 = w.reshape(heads, per, -1)
    return np.concatenate(
        [w3[:, :per - dr], w3[:, per - dr:][:, order]], axis=1
    ).reshape(w.shape)


def load_latent_params(loader, lp: str, spec) -> dict:
    cfg = loader.cfg
    dr = cfg.latent_attn.qk_rope_head_dim
    sa = f"{lp}.self_attn"
    plain, norms, q = _leaf_names(cfg)
    p = {n: {"weight": loader._dev(loader._get(f"{sa}.{n}.weight"))}
         for n in plain}
    for n in norms:
        p[n] = {"weight": loader._norm(f"{sa}.{n}.weight")}
    for n, heads in ((q, cfg.num_attention_heads),
                     ("kv_a_proj_with_mqa", 1)):
        w = np.asarray(loader._get_dense(f"{sa}.{n}.weight"))
        p[n] = {"weight": loader._dev(
            _reorder_rope_rows(w, heads, dr, _halves(dr)))}
    return p


def export_latent_params(cfg, p, lp: str) -> dict:
    dr = cfg.latent_attn.qk_rope_head_dim
    sa = f"{lp}.self_attn"
    plain, norms, q = _leaf_names(cfg)
    out = {f"{sa}.{n}.weight": np.asarray(p[n]["weight"])
           for n in plain + norms}
    back = np.argsort(_halves(dr))
    for n, heads in ((q, cfg.num_attention_heads),
                     ("kv_a_proj_with_mqa", 1)):
        out[f"{sa}.{n}.weight"] = _reorder_rope_rows(
            np.asarray(p[n]["weight"]), heads, dr, back)
    return out


# what the checkpoint calls a leaf of the block's tree (utils/loaders.py,
# utils/export.py): the shared experts are `mlp.shared_experts`
CHECKPOINT_NAMES = {"shared_expert": "shared_experts"}

MIXER = Mixer(
    param_key="self_attn", scopes=("cake.attn", "cake.attn.latent"),
    recurrent=False,
    init_params=init_latent_params,
    load_params=load_latent_params,
    export_params=export_latent_params,
    init_cache=init_attention_cache,
    forward=latent_forward,
    describe=describe_latent)
