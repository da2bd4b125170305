"""Generic decoder-layer machinery: one config-driven block implementation
covers every dense text family (ref: models/common/{attention.rs,mlp.rs,
transformer.rs} + the per-family thin blocks).

Functional style: parameters are nested dicts (pytrees), forwards are pure
functions closed over the static ModelConfig/LayerSpec — jit compiles a
contiguous layer range into a single XLA program (the TPU replacement for
the reference's per-layer Box<dyn Forwarder> dispatch).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ...obs import PROCESS
from ...ops import (apply_rope, embedding, gelu_mul, linear,
                    make_attention_mask, multi_head_attention, rms_norm,
                    rope_tables, silu_mul)
from ...ops.moe import moe_ffn
from .cache import init_attention_cache, keys_joined, update_kv_cache
from .config import LayerSpec, ModelConfig
from .mixers import Mixer, mixer_of


# ---------------------------------------------------------------------------
# Parameter initialization (random weights; checkpoint loading lives in
# utils/loaders.py which produces the same pytree layout)
# ---------------------------------------------------------------------------


def _norm_shape(cfg: ModelConfig):
    return (cfg.hidden_size,)


def init_attention_params(cfg: ModelConfig, spec: LayerSpec, key, dtype):
    """Separate q/k/v/o projections (HF layout). The reference fuses QKV into
    one matmul (ref: attention.rs:90-115) — a GPU bandwidth trick; on TPU,
    separate tensors shard head-aligned over the tp axis and XLA fuses the
    three GEMMs' epilogues anyway, so fusion would only break TP alignment.
    Phi-4's pre-fused qkv_proj / gate_up_proj are split at load time."""
    ks = jax.random.split(key, 4)
    a, h = cfg.attn_shape(spec), cfg.hidden_size
    sq, sk, sv = a.size_q, a.size_k, a.size_v
    q_out = 2 * sq if (cfg.attn_output_gate and spec.kind == "full") else sq
    std = 0.02
    p = {
        "q_proj": {"weight": jax.random.normal(ks[0], (q_out, h), dtype) * std},
        "k_proj": {"weight": jax.random.normal(ks[1], (sk, h), dtype) * std},
        "v_proj": {"weight": jax.random.normal(ks[2], (sv, h), dtype) * std},
        "o_proj": {"weight": jax.random.normal(ks[3], (h, a.size_o), dtype)
                   * std},
    }
    if cfg.qkv_bias:
        p["q_proj"]["bias"] = jnp.zeros((q_out,), dtype)
        p["k_proj"]["bias"] = jnp.zeros((sk,), dtype)
        p["v_proj"]["bias"] = jnp.zeros((sv,), dtype)
    if cfg.qk_norm:
        if cfg.qk_norm_pre_reshape:
            p["q_norm"] = {"weight": jnp.ones((sq,), dtype)}
            p["k_norm"] = {"weight": jnp.ones((sk,), dtype)}
        else:
            p["q_norm"] = {"weight": jnp.ones((a.head_dim,), dtype)}
            p["k_norm"] = {"weight": jnp.ones((a.head_dim,), dtype)}
    if spec.sink:
        p["attention_sink_bias"] = jax.random.normal(
            jax.random.fold_in(key, 4), (a.heads,), dtype)
    if cfg.attn_head_gate:
        # gate logits of std ~1: the gates spread over (0.1, 0.9)
        p["g_proj"] = {"weight": jax.random.normal(
            jax.random.fold_in(key, 5), (a.heads, h), dtype) / h ** 0.5}
    return p


def init_mlp_params(cfg: ModelConfig, key, dtype, inter: int | None = None):
    k1, k2, k3 = jax.random.split(key, 3)
    h, i = cfg.hidden_size, inter or cfg.intermediate_size
    return {
        "gate_proj": {"weight": jax.random.normal(k1, (i, h), dtype) * 0.02},
        "up_proj": {"weight": jax.random.normal(k2, (i, h), dtype) * 0.02},
        "down_proj": {"weight": jax.random.normal(k3, (h, i), dtype) * 0.02},
    }


def init_moe_params(cfg: ModelConfig, key, dtype):
    """The router scores every expert of the model; `experts.*` hold the
    `num_experts` this process was told it holds (all of them, uncut)."""
    ks = jax.random.split(key, 6)
    h, e = cfg.hidden_size, cfg.num_experts
    i = cfg.moe_intermediate_size
    width = cfg.router_width
    p = {
        "gate": {"weight": jax.random.normal(ks[0], (width, h), dtype) * 0.02},
        "experts": {
            "gate_proj": jax.random.normal(ks[1], (e, i, h), dtype) * 0.02,
            "up_proj": jax.random.normal(ks[2], (e, i, h), dtype) * 0.02,
            "down_proj": jax.random.normal(ks[3], (e, h, i), dtype) * 0.02,
        },
    }
    if cfg.moe_select_bias:
        p["gate"]["e_score_correction_bias"] = jax.random.normal(
            jax.random.fold_in(key, 6), (width,), jnp.float32) * 0.1
    if cfg.shared_expert_intermediate_size:
        p["shared_expert"] = init_mlp_params(
            cfg, ks[4], dtype, inter=cfg.shared_expert_intermediate_size)
        if cfg.shared_expert_gated:
            p["shared_expert_gate"] = {
                "weight": jax.random.normal(ks[5], (1, h), dtype) * 0.02}
    return p


def init_layer_params(cfg: ModelConfig, spec: LayerSpec, key, dtype):
    ks = jax.random.split(key, 2)
    m = mixer_of(cfg, spec)
    p: dict = {m.param_key: m.init_params(cfg, spec, ks[0], dtype)}
    p["mlp"] = (init_moe_params(cfg, ks[1], dtype) if spec.is_moe
                else init_mlp_params(cfg, ks[1], dtype))
    if spec.shortcut == "open":
        # the pair's ONE sparse layer, beside this sub-layer's dense FFN
        p["moe"] = init_moe_params(cfg, jax.random.fold_in(key, 7), dtype)
    # fresh buffer per norm: donation/aliasing breaks if leaves share storage
    def ones():
        return jnp.ones(_norm_shape(cfg), dtype)
    if spec.norm_style == "pre":
        norm_names = ("input_layernorm", "post_attention_layernorm")
    elif spec.norm_style == "post":
        norm_names = ("post_attention_layernorm", "post_feedforward_layernorm")
    elif spec.norm_style == "sandwich":
        norm_names = ("input_layernorm", "post_attention_layernorm",
                      "pre_feedforward_layernorm", "post_feedforward_layernorm")
    else:
        norm_names = ()
    for name in norm_names:
        p[name] = {"weight": ones()}
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16,
                layer_range: tuple[int, int] | None = None,
                include_embed: bool | None = None,
                include_head: bool | None = None) -> dict:
    """Build the parameter pytree. layer_range selects a contiguous subset of
    layers (worker partial load — ref: utils/mod.rs:251-333); embed/head
    default to included iff the range touches the first/last layer."""
    lo, hi = layer_range or (0, cfg.num_hidden_layers)
    if include_embed is None:
        include_embed = lo == 0
    if include_head is None:
        include_head = hi == cfg.num_hidden_layers
    if include_head and cfg.tie_word_embeddings:
        include_embed = True  # tied head reads the embedding table
    keys = jax.random.split(key, cfg.num_hidden_layers + 2)
    params: dict = {"layers": [
        init_layer_params(cfg, cfg.layer_spec(i), keys[i], dtype)
        for i in range(lo, hi)
    ]}
    if include_embed:
        params["embed_tokens"] = {
            "weight": jax.random.normal(keys[-1], (cfg.vocab_size, cfg.hidden_size),
                                        dtype) * 0.02}
    if include_head:
        params["norm"] = {"weight": jnp.ones(_norm_shape(cfg), dtype)}
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {
                "weight": jax.random.normal(keys[-2],
                                            (cfg.vocab_size, cfg.hidden_size),
                                            dtype) * 0.02}
    params["rope"] = make_rope(cfg)
    return params


@PROCESS.phase("boot.rope")
def make_rope(cfg: ModelConfig) -> dict:
    if not any(spec.use_rope for spec in cfg.layer_specs()):
        return {}       # no layer rotates (Jamba): no table of max_seq_len
    cos, sin = rope_tables(cfg.max_seq_len, cfg.rotary_dim, cfg.rope_theta,
                           cfg.rope_scaling)
    rope = {"cos": cos, "sin": sin}
    if cfg.local_rope_theta is not None:
        # Gemma3 SWA layers: separate table at rope_local_base_freq, never
        # scaled (HF rotary_emb_local; pinned by tests/test_hf_parity.py);
        # MiMo-V2 window layers: swa_rope_theta; Laguna's: a rotary width
        # and a scaling of the table's own
        lcos, lsin = rope_tables(cfg.max_seq_len, cfg.local_rotary_dim,
                                 cfg.local_rope_theta,
                                 cfg.local_rope_scaling)
        rope["cos_local"], rope["sin_local"] = lcos, lsin
    return rope


@PROCESS.phase("boot.rope")
def cut_rope(rope: dict, rows: int) -> dict:
    """Every table's first `rows` rows: the positions a model whose caches
    end at `rows` can reach. A window layer's positions run past its ring
    to the row's frontier, so the local tables keep as many. The runtime
    keeps a table narrower than the lanes positions-minor, and XLA lays
    the WHOLE table out by rows in front of the gather of a step's few
    positions, in every execution: at 1,048,576 published positions that
    copy was 2.5 of a 13.5 ms decode step (PERF.md, PR 49). A position past
    the cut (a padded chunk's tail, a free pool row's carry) gathers the
    last row, as it gathered its own before: such rows are masked."""
    return {name: table if table.shape[0] <= rows else table[:rows]
            for name, table in rope.items()}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def flash_kernel_mode(flash_mode: str, s: int, window: int | None = None,
                      has_cache: bool = True, sink: bool = False
                      ) -> str | None:
    """"fresh" / "append" when a prefill of width s in this host-static
    flash_mode runs the Pallas kernel on a layer with this window, else
    None (the masked XLA path). A layer with a sink column in its softmax
    keeps the masked path: the kernel's softmax knows no sink. The one
    rule: attention_forward dispatches on it and the serve timeline
    reports it."""
    from ...ops.flash import FLASH_MIN_SEQ, flash_enabled
    if s < FLASH_MIN_SEQ or sink or not flash_enabled():
        return None
    if flash_mode == "fresh":
        return "fresh"
    if flash_mode == "append" and window is None and has_cache:
        return "append"
    return None


def decode_kernel_block(s: int, window: int | None, layer_cache, dtype,
                        mesh=None, sink: bool = False) -> int | None:
    """The block length the Pallas decode kernel walks this layer's cache
    in when a step of width s runs it, else None (the masked XLA path). It
    runs on a one-token step over a cache addressed by position (a `pos`
    leaf) that is unwrapped (no window: buffer index == position; SWA rings
    keep the masked path), at least one block long, in the queries' dtype
    and one whose rows the kernel can split (32-bit, or 16-bit with an even
    number of K/V heads a device), not sharded over its rows or its length (`dp`, `sp`:
    the kernel splits heads over `tp` alone), where the Pallas attention
    kernels are on, with no sink in its softmax and with keys that lie by
    head and are as wide as the values: the kernel takes one D for both
    and a rank-4 K. MiMo-V2's keys of 192 lie joined (cache.key_row_shape);
    at [.., Hkv, 192] the kernel did not compile either: it slices heads
    with a strided load, which Mosaic refuses on a buffer whose last dim is
    not 128 ("The last dim size is not 128 in original base memref",
    described v5e). Such a layer decodes masked. attention_forward alone
    dispatches on it."""
    from ...ops.decode_attention import decode_block_k
    from ...ops.flash import flash_enabled
    if (s != 1 or window is not None or sink or layer_cache is None
            or "pos" not in layer_cache or not flash_enabled()):
        return None
    if (keys_joined(layer_cache)
            or layer_cache["v"].shape[3] != layer_cache["k"].shape[3]):
        return None
    if mesh is not None and any(mesh.shape.get(a, 1) > 1
                                for a in ("dp", "sp")):
        return None
    k = layer_cache["k"]
    heads = k.shape[2] // (mesh.shape.get("tp", 1) if mesh is not None else 1)
    if k.dtype != dtype or (k.dtype.itemsize != 4 and
                            (k.dtype.itemsize != 2 or heads % 2)):
        return None
    return decode_block_k(k.shape[1])


def attention_forward(cfg: ModelConfig, spec: LayerSpec, p: dict, x,
                      layer_cache: dict, pos0, rope: dict, valid_len=None,
                      flash_mode: str = "off", mesh=None):
    """x: [B, S, H], pos0: traced scalar (first absolute position).
    Returns (y [B, S, H], new_layer_cache)."""
    b, s, _ = x.shape
    a = cfg.attn_shape(spec)
    hq, hkv, d, sq = a.heads, a.kv_heads, a.head_dim, a.size_q
    gated = cfg.attn_output_gate and spec.kind == "full"
    sink = p["attention_sink_bias"] if spec.sink else None

    q = linear(x, p["q_proj"]["weight"], p["q_proj"].get("bias"))
    k = linear(x, p["k_proj"]["weight"], p["k_proj"].get("bias"))
    v = linear(x, p["v_proj"]["weight"], p["v_proj"].get("bias"))

    head_gate = None
    if cfg.attn_head_gate:
        # one sigmoid gate a query head, from the layer's normed input
        with jax.named_scope("cake.attn.gate"):
            head_gate = jax.nn.sigmoid(
                linear(x, p["g_proj"]["weight"]).astype(jnp.float32))

    gate = None
    if gated:
        # q_proj emits 2x heads; per-head [q, gate] interleave -> sigmoid gate
        # on the attention output (ref: qwen3_5_moe attn_output_gate).
        qg = q.reshape(b, s, hq, 2 * d)
        q, gate = qg[..., :d].reshape(b, s, sq), qg[..., d:].reshape(b, s, sq)

    if cfg.qk_norm and cfg.qk_norm_pre_reshape:
        q = rms_norm(q, p["q_norm"]["weight"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm"]["weight"], cfg.rms_norm_eps)

    q = q.reshape(b, s, hq, d)
    k = k.reshape(b, s, hkv, d)
    v = v.reshape(b, s, hkv, a.v_head_dim)
    if cfg.attn_value_scale is not None:
        # scaled as projected, so the cache holds what attention sums
        v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)

    if cfg.qk_norm and not cfg.qk_norm_pre_reshape:
        q = rms_norm(q, p["q_norm"]["weight"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm"]["weight"], cfg.rms_norm_eps)

    positions = pos0 + jnp.arange(s, dtype=jnp.int32)
    if spec.use_rope:
        suf = "_local" if spec.local_rope_table else ""
        cos, sin = rope["cos" + suf], rope["sin" + suf]
        rd = cfg.rotary_dim_of(spec)
        q = apply_rope(q, cos, sin, positions, rd)
        k = apply_rope(k, cos, sin, positions, rd)

    # Attend over [previous cache ; in-pass K/V]. In-pass keys must be
    # presented in full (not through the ring): with a window-sized ring,
    # early prefill queries need keys the ring has already evicted.
    # layer_cache=None is the stateless path (training / no-cache prefill).
    idx = jnp.arange(s, dtype=jnp.int32)
    kv_pos_new = positions if valid_len is None else jnp.where(
        idx < valid_len, positions, -1)                    # pads invisible
    kv_pos_new = jnp.broadcast_to(kv_pos_new[None, :], (b, s))
    from ...ops.flash import flash_attention
    use_flash = flash_kernel_mode(flash_mode, s, spec.window,
                                  layer_cache is not None,
                                  spec.sink) is not None
    # the layer's read of its cache (the decode kernel's call or the
    # masked read), by kind
    read_scope = "cake.attn.full" if spec.window is None \
        else "cake.attn.window"
    if flash_mode == "ring" and mesh is not None and spec.window is None:
        # sp-sharded fresh prefill: sequence split over the mesh's sp axis,
        # K/V blocks rotate via collective permute (parallel/ring_attention)
        # so no device materializes the full sequence's scores. Exact for
        # padded prompts: pad KEYS sit at positions > every real query, so
        # the global causal mask hides them (pad query rows are garbage the
        # last-valid-position slice never reads — same as single-shot
        # padding). The KV cache itself is length-sharded over sp
        # (parallel/sharding.cache_shardings), so the scatter below writes
        # each device's sequence shard LOCALLY — context memory scales
        # with sp, and decode attends over the sharded length with GSPMD
        # inserting the softmax-reduction collectives. Only reached on
        # all-full-attention models (mode selection requires every layer
        # full + windowless: SWA layers have no windowed flash under ring,
        # and their masked fallback is quadratic at exactly the lengths sp
        # targets).
        from ...parallel.ring_attention import ring_attention
        y = ring_attention(q, k, v, mesh, scale=cfg.attn_scale)
        new_cache = (update_kv_cache(layer_cache, k, v, pos0, valid_len)
                     if layer_cache is not None else None)
        use_flash = True          # skip the masked fallback below
    elif use_flash and flash_mode == "fresh":
        # fresh-cache prefill: nothing in the cache is visible yet, so
        # causal flash over the in-pass K/V is exact, incl. SWA layers via
        # the kernel's window mask (Pallas; ref: flash-attn dispatch
        # attention.rs:270-277). Inference-only — the kernel has no VJP;
        # flash_mode stays "off" on the training path.
        y = flash_attention(q, k, v, scale=cfg.attn_scale, valid_len=valid_len,
                            window=spec.window, mesh=mesh)
        new_cache = (update_kv_cache(layer_cache, k, v, pos0, valid_len)
                     if layer_cache is not None else None)
        kv_pos = k_all = v_all = None
    elif use_flash:
        # continued prefill (cache append): scatter the chunk into the
        # cache, then flash over the buffer — valid because "append" is
        # only selected when the buffer is unwrapped (index == position)
        new_cache = update_kv_cache(layer_cache, k, v, pos0, valid_len)
        # (the kernel takes keys by head: joined keys are split, a copy
        # of this one row)
        y = flash_attention(q, new_cache["k"].reshape(b, -1, hkv, d),
                            new_cache["v"], scale=cfg.attn_scale,
                            valid_len=valid_len, q_offset=pos0, mesh=mesh)
        kv_pos = k_all = v_all = None
    elif layer_cache is None:
        kv_pos, k_all, v_all = kv_pos_new, k, v
        new_cache = None
    elif s == 1:
        # decode fast path: scatter the new entry first, attend over the
        # cache buffer directly — no [cache ; new] concat copy per layer
        # per token. Safe for SWA rings at s==1: the slot overwritten
        # (position p - W) is exactly the one the window mask excludes.
        new_cache = update_kv_cache(layer_cache, k, v, pos0, valid_len)
        kv_pos, k_all, v_all = (new_cache["pos"], new_cache["k"],
                                new_cache["v"])
        block_k = decode_kernel_block(s, spec.window, layer_cache, q.dtype,
                                      mesh, spec.sink)
        if block_k is not None:
            # the Pallas kernel reads the buffers in place, each row only
            # up to its frontier pos0 + 1, and no block of a row that
            # valid_len 0 masks out of the step
            from ...ops.decode_attention import decode_attention
            with jax.named_scope(read_scope):
                y = decode_attention(
                    q, k_all, v_all, kv_pos, pos0,
                    None if valid_len is None else valid_len > 0,
                    scale=cfg.attn_scale, block_k=block_k, mesh=mesh)
            use_flash = True      # skip the masked fallback below
    else:
        new_cache = None
        kv_pos = jnp.concatenate([layer_cache["pos"], kv_pos_new], axis=1)
        # the in-pass keys as the cache holds its own (joined or not)
        k_all = jnp.concatenate(
            [layer_cache["k"],
             k.reshape((b, s) + layer_cache["k"].shape[2:])], axis=1)
        v_all = jnp.concatenate([layer_cache["v"], v], axis=1)
    if not use_flash:
        q_pos = jnp.broadcast_to(positions[None, :], (b, s))
        mask = make_attention_mask(q_pos, kv_pos, window=spec.window)
        with jax.named_scope(read_scope):
            y = multi_head_attention(q, k_all, v_all, mask,
                                     scale=cfg.attn_scale, sink=sink)
        if layer_cache is not None and new_cache is None:
            new_cache = update_kv_cache(layer_cache, k, v, pos0, valid_len)
    if head_gate is not None:
        with jax.named_scope("cake.attn.gate"):
            y = y.reshape(b, s, hq, a.v_head_dim) \
                * head_gate[..., None].astype(y.dtype)
    y = y.reshape(b, s, a.size_o)
    if gate is not None:
        with jax.named_scope("cake.attn.gate"):
            y = y * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(y.dtype)
    return linear(y, p["o_proj"]["weight"]), new_cache


def describe_attention(cfg: ModelConfig, spec: LayerSpec) -> dict:
    """An attention layer's entry of ModelConfig.attention_kinds(): its
    heads, K/V heads, window, the dims it rotates and the table it reads."""
    a = cfg.attn_shape(spec)
    local = spec.local_rope_table
    sc = cfg.local_rope_scaling if local else cfg.rope_scaling
    return {
        "kind": spec.kind, "layers": 1, "heads": a.heads,
        "kv_heads": a.kv_heads, "window": spec.window,
        "rotary_dim": cfg.rotary_dim_of(spec) if spec.use_rope else 0,
        "rope_theta": ((cfg.local_rope_theta if local else cfg.rope_theta)
                       if spec.use_rope else None),
        "rope_scaling": ((sc.rope_type or "llama3")
                         if sc is not None and spec.use_rope else None)}


def _export_attention(cfg, p, lp):
    from ...utils.export import export_attention_params
    return export_attention_params(cfg, p, lp)


# attention, `full` and `swa` alike: a buffer or a ring addressed by position
MIXER = Mixer(
    param_key="self_attn", scopes=("cake.attn",), recurrent=False,
    init_params=init_attention_params,
    load_params=lambda loader, lp, spec: loader._attention(lp, spec),
    export_params=_export_attention, init_cache=init_attention_cache,
    forward=attention_forward, describe=describe_attention)


def mlp_forward(cfg: ModelConfig, p: dict, x):
    """gate/up matmuls -> silu_mul / gelu_mul -> down (ref: common/mlp.rs).
    Projections stay separate for tp-aligned sharding; XLA fuses the
    elementwise epilogue into the GEMMs."""
    gate = linear(x, p["gate_proj"]["weight"])
    up = linear(x, p["up_proj"]["weight"])
    h = gelu_mul(gate, up) if cfg.hidden_act == "gelu_tanh" else silu_mul(gate, up)
    return linear(h, p["down_proj"]["weight"])


def moe_forward(cfg: ModelConfig, p: dict, x):
    b, s, h = x.shape
    flat = x.reshape(b * s, h)
    act = "gelu" if cfg.hidden_act == "gelu_tanh" else "silu"
    if "_provider" in p:
        # disk-offloaded experts (--expert-offload): router on device,
        # selected experts streamed from storage — EAGER only (the host
        # round-trip on the routing indices cannot trace under jit)
        from .expert_provider import moe_ffn_offloaded
        if (cfg.moe_routed_scale != 1.0 or cfg.moe_n_group > 1
                or cfg.moe_zero_experts):
            raise NotImplementedError(
                "--expert-offload with a routed scaling factor, "
                "group-limited routing or identity experts")
        y = moe_ffn_offloaded(flat, p["gate"]["weight"], p["_provider"],
                              cfg.num_experts_per_tok, cfg.norm_topk_prob,
                              cfg.moe_gate_act, act)
    else:
        y = moe_ffn(flat, p["gate"]["weight"], p["experts"]["gate_proj"],
                    p["experts"]["up_proj"], p["experts"]["down_proj"],
                    cfg.num_experts_per_tok, cfg.norm_topk_prob,
                    cfg.moe_gate_act, act,
                    select_bias=p["gate"].get("e_score_correction_bias"),
                    first=cfg.expert_first,
                    routed_scale=cfg.moe_routed_scale,
                    n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group,
                    group_score_top=cfg.moe_group_score_top,
                    zero_experts=cfg.moe_zero_experts)
    if "shared_expert" in p:
        # always-active shared expert: sigmoid-gated where the checkpoint
        # has a `shared_expert_gate` (ref: qwen3_5_moe/moe.rs), else added
        # as it is (Laguna). In a share of an expert-parallel group it is
        # what every chip computes alike: counted once when shares add up
        with jax.named_scope("cake.ffn.shared"):
            sh = mlp_forward(cfg, p["shared_expert"], flat)
            if "shared_expert_gate" in p:
                g = jax.nn.sigmoid(linear(
                    flat, p["shared_expert_gate"]["weight"]
                ).astype(jnp.float32))
                sh = sh * g.astype(sh.dtype)
            y = y + sh
    return y.reshape(b, s, h)


# The jax.named_scope blocks below (and in ops/moe.py, ops/sampling.py)
# are metadata on the traced ops, nothing else: a device trace's reader
# sums time by scope. Their names are obs.spans.SCOPE_CATALOG.

def _ffn(cfg, spec, p, x):
    with jax.named_scope("cake.ffn"):
        return moe_forward(cfg, p["mlp"], x) if spec.is_moe \
            else mlp_forward(cfg, p["mlp"], x)


def _attn(cfg, spec, p, x, lc, pos0, rope, valid_len=None,
          flash_mode="off", mesh=None):
    """The layer's mixer (mixers.mixer_of), under its scopes."""
    m = mixer_of(cfg, spec)
    with contextlib.ExitStack() as scopes:
        for name in m.scopes:
            scopes.enter_context(jax.named_scope(name))
        return m.forward(cfg, spec, p[m.param_key], x, lc, pos0, rope,
                         valid_len, flash_mode, mesh)


def block_forward(cfg: ModelConfig, spec: LayerSpec, p: dict, x,
                  layer_cache: dict, pos0, rope: dict, valid_len=None,
                  flash_mode: str = "off", mesh=None):
    """One decoder block; norm placement per family
    (ref: common/transformer.rs pre-norm; olmo2/block.rs post-norm;
    gemma3/block.rs sandwich)."""
    eps = cfg.rms_norm_eps
    if spec.norm_style == "pre":
        h = rms_norm(x, p["input_layernorm"]["weight"], eps)
        attn_out, layer_cache = _attn(cfg, spec, p, h, layer_cache, pos0, rope, valid_len, flash_mode, mesh)
        x = x + attn_out
        h = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
        x = x + _ffn(cfg, spec, p, h)
    elif spec.norm_style == "post":
        attn_out, layer_cache = _attn(cfg, spec, p, x, layer_cache, pos0, rope, valid_len, flash_mode, mesh)
        x = x + rms_norm(attn_out, p["post_attention_layernorm"]["weight"], eps)
        x = x + rms_norm(_ffn(cfg, spec, p, x),
                         p["post_feedforward_layernorm"]["weight"], eps)
    elif spec.norm_style == "sandwich":
        h = rms_norm(x, p["input_layernorm"]["weight"], eps)
        attn_out, layer_cache = _attn(cfg, spec, p, h, layer_cache, pos0, rope, valid_len, flash_mode, mesh)
        attn_out = rms_norm(attn_out, p["post_attention_layernorm"]["weight"], eps)
        x = x + attn_out
        h = rms_norm(x, p["pre_feedforward_layernorm"]["weight"], eps)
        ffn_out = rms_norm(_ffn(cfg, spec, p, h),
                           p["post_feedforward_layernorm"]["weight"], eps)
        x = x + ffn_out
    else:
        raise ValueError(f"unknown norm style {spec.norm_style}")
    return x, layer_cache


def shortcut_forward(cfg: ModelConfig, spec: LayerSpec, p: dict, x, held,
                     layer_cache: dict, pos0, rope: dict, valid_len=None,
                     flash_mode: str = "off", mesh=None):
    """One SUB-layer of a shortcut-connected pair (LayerSpec.shortcut;
    models/longcat_flash.py has the block's equations): the pre-norm block
    of a mixer and a dense FFN, and
      'open'   the pair's sparse layer `moe` also reads this sub-layer's
               post-attention norm; its output is HELD BACK (returned, not
               added), so the next sub-layer's mixer and FFN never see it;
      'close'  `held` is added last, behind this sub-layer's own FFN.
    Returns (x, what is held after this sub-layer, layer_cache)."""
    eps = cfg.rms_norm_eps
    h = rms_norm(x, p["input_layernorm"]["weight"], eps)
    attn_out, layer_cache = _attn(cfg, spec, p, h, layer_cache, pos0, rope,
                                  valid_len, flash_mode, mesh)
    x = x + attn_out
    h = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
    with jax.named_scope("cake.ffn"):
        if spec.shortcut == "open":
            held = moe_forward(cfg, p["moe"], h)
        with jax.named_scope("cake.ffn.dense"):
            x = x + mlp_forward(cfg, p["mlp"], h)
            if spec.shortcut == "close":
                x, held = x + held, None
    return x, held, layer_cache


def _layer(cfg, spec, p, x, held, lc, pos0, rope, valid_len=None,
           flash_mode="off", mesh=None):
    """(x, held, layer cache) of one entry of the layer list: a block that
    stands alone, or one sub-layer of a shortcut pair with the value the
    pair carries."""
    if spec.shortcut is None:
        x, lc = block_forward(cfg, spec, p, x, lc, pos0, rope, valid_len,
                              flash_mode, mesh)
        return x, held, lc
    return shortcut_forward(cfg, spec, p, x, held, lc, pos0, rope, valid_len,
                            flash_mode, mesh)


def forward_layers(cfg: ModelConfig, params: dict, x, cache: dict, pos0,
                   layer_range: tuple[int, int] | None = None, valid_len=None,
                   flash_mode: str = "off", mesh=None):
    """Run a contiguous range of blocks over hidden states — the jit unit for
    both local stages and remote workers (ref: Forwarder.forward_batch /
    worker.rs op-batch execution, but compiled as ONE device program)."""
    lo, hi = layer_range or (0, len(params["layers"]))
    specs = cfg.layer_specs()[lo:hi]
    if specs and (specs[0].shortcut == "close"
                  or specs[-1].shortcut == "open"):
        raise ValueError(
            f"{cfg.arch}: layer_range ({lo}, {hi}) separates an opening "
            "sub-layer of a shortcut pair from its closing one: what the "
            "pair's sparse layer gives is carried between the two inside "
            "one program (a stage holds whole pairs)")
    rope = params["rope"]
    held = None     # what an open shortcut pair carries to its closing entry
    if cache is None:       # stateless (training / encoder use)
        for j, spec in enumerate(specs):
            x, held, _ = _layer(cfg, spec, params["layers"][j], x, held,
                                None, pos0, rope, valid_len)
        return x, None
    new_layers = list(cache["layers"])
    for j, spec in enumerate(specs):
        x, held, new_layers[j] = _layer(cfg, spec, params["layers"][j], x,
                                        held, cache["layers"][j], pos0, rope,
                                        valid_len, flash_mode, mesh=mesh)
    advance = x.shape[1] if valid_len is None else valid_len
    new_cache = {"layers": new_layers, "pos": pos0 + advance}
    return x, new_cache


def forward_train(cfg: ModelConfig, params: dict, tokens):
    """Stateless forward over all positions -> [B, S, V] f32 logits.

    Beyond-parity surface (the reference is inference-only): used by the
    training step in parallel/train.py and by logit-parity tests.
    """
    x = embed_tokens(cfg, params, tokens)
    x, _ = forward_layers(cfg, params, x, None, jnp.asarray(0, jnp.int32))
    h = rms_norm(x, params["norm"]["weight"], cfg.rms_norm_eps)
    w = (params["embed_tokens"]["weight"] if cfg.tie_word_embeddings
         else params["lm_head"]["weight"])
    return linear(h, w).astype(jnp.float32)


def embed_tokens(cfg: ModelConfig, params: dict, tokens):
    with jax.named_scope("cake.embed"):
        x = embedding(tokens, params["embed_tokens"]["weight"])
        if cfg.embed_scale is not None:
            # Gemma scales embeddings by sqrt(hidden) in the model dtype
            x = x * jnp.asarray(cfg.embed_scale, x.dtype)
        return x


def lm_head_logits(cfg: ModelConfig, params: dict, x_last):
    """Final norm + head on the last position only (ref: text_model.rs:336-352
    last-token lm_head)."""
    with jax.named_scope("cake.lm_head"):
        h = rms_norm(x_last, params["norm"]["weight"], cfg.rms_norm_eps)
        w = (params["embed_tokens"]["weight"] if cfg.tie_word_embeddings
             else params["lm_head"]["weight"])
        return linear(h, w).astype(jnp.float32)
