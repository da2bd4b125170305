"""Unified text-model configuration.

One generalized ModelConfig covers all text families, normalized from HF
config.json by per-architecture adapters (ref: models/common/config.rs:86-150
Config + per-family config.rs into_config()). Per-layer behavior (sliding
window / rope / linear-attention / MoE interleaves) is resolved here into
LayerSpec tuples so the model code is a single generic block driven by data.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any

from ...ops.rope import RopeScaling


@dataclasses.dataclass(frozen=True)
class LinearAttnConfig:
    """Delta-rule linear-attention hyperparameters: Gated DeltaNet
    (ref: config.rs LinearAttnConfig; qwen3_5/linear_attention.rs) and,
    with `kda`, Kimi Delta Attention (models/kda.py)."""
    layer_types: tuple[str, ...] = ()
    conv_kernel_dim: int = 4
    num_key_heads: int = 16
    key_head_dim: int = 128
    num_value_heads: int = 16
    value_head_dim: int = 128
    # Kimi Delta Attention (Solar-Open2's `kda_*` keys): q, k, v from
    # projections of their own, the decay one number a CHANNEL of a head's
    # keys from a low-rank pair, a sigmoid output gate from another, both of
    # rank `low_rank`. False: the gated delta net, a decay a head
    kda: bool = False
    low_rank: int = 0
    # beta = beta_scale x sigmoid(.): at 2 the state's transition
    # I - beta k k^T has an eigenvalue in (-1, 1) (`kda_allow_neg_eigval`)
    beta_scale: float = 1.0
    # the decay's and the output gate's projections are ONE matrix each of
    # full rank, `f_proj` / `g_proj` [H D, hidden] (Ling-3.0's `no_kda_lora`,
    # Solar-Open2's `kda_use_full_proj`); False: the low-rank pairs
    full_proj: bool = False
    # the log-decay is `decay_lower_bound` x sigmoid(exp(A_log) (. + dt_bias)),
    # inside (decay_lower_bound, 0) (Ling-3.0's `kda_safe_gate` /
    # `kda_lower_bound`); None: -exp(A_log) softplus(. + dt_bias), unbounded
    decay_lower_bound: float | None = None

    @property
    def decay(self) -> str:
        """What one decay number covers: a 'head' or a 'channel' of one."""
        return "channel" if self.kda else "head"

    @property
    def conv_channels(self) -> int:
        """Channels of the short conv (q, k and v side by side): what the
        row's conv tail holds of each of its last tokens."""
        return (2 * self.num_key_heads * self.key_head_dim
                + self.num_value_heads * self.value_head_dim)


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    """Mamba-1 selective-state-space hyperparameters (Jamba's mixer)."""
    d_inner: int = 5120            # mamba_expand x hidden_size
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    conv_bias: bool = True
    attn_layers: tuple[int, ...] = ()   # the layers that mix by attention


@dataclasses.dataclass(frozen=True)
class RetentionConfig:
    """Power retention (Brumby's mixer, models/brumby.py): the weight of a
    key is (q . k)^power under a gate a key/value head; the row's state is
    the symmetric power of its keys against its values, float32."""
    power: int = 2


@dataclasses.dataclass(frozen=True)
class LatentAttnConfig:
    """Multi-head latent attention (DeepSeek-V2's MLA, models/deepseek_v2.py):
    queries through a low-rank pair (`q_lora_rank` None: ONE full-rank
    `q_proj`, Ling-3.0's), keys and values expanded from ONE
    normed latent a token beside one roped key part all heads share. A
    head's queries and keys are `qk_nope_head_dim + qk_rope_head_dim` wide,
    its values `v_head_dim`; a position of a row holds `row_width` numbers."""
    q_lora_rank: int | None = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # the normed query latent and the normed key/value latent are multiplied
    # by these before `q_b_proj` / `kv_b_proj` (LongCat-Flash's
    # `mla_scale_q_lora` / `mla_scale_kv_lora`: (hidden / rank)^1/2); the
    # shared rope key part is not. A row still holds the UNSCALED normed
    # latent: `kv_scale` is folded where W_uk and W_uv are absorbed
    # (deepseek_v2.latent_forward). 1.0 traces nothing
    q_scale: float = 1.0
    kv_scale: float = 1.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """[normed latent ; roped shared key part]."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Resolved per-layer behavior, consumed by the generic decoder block."""
    # 'full' | 'swa' | 'linear' | 'mamba' | 'retention' | 'latent'
    kind: str = "full"
    use_rope: bool = True
    local_rope_table: bool = False  # Gemma3 SWA layers: rope_local_base_freq
    window: int | None = None     # sliding-window size when kind == 'swa'
    is_moe: bool = False
    norm_style: str = "pre"       # 'pre' | 'post' (OLMo2) | 'sandwich' (Gemma3)
    # a learned scalar a query head in the softmax's denominator, a column
    # that carries no value (MiMo-V2: `attention_sink_bias`)
    sink: bool = False
    # the layer carries a fixed-size state that only moves forward (no `pos`
    # leaf in its cache: cache.is_positional is False) in place of entries
    # addressed by position; what its mixer says (mixers.Mixer.recurrent),
    # set where layer_spec chooses the kind
    recurrent: bool = False
    # a shortcut-connected pair of entries of the layer list (LongCat-Flash:
    # ONE published layer is two sub-layers, each a mixer and a dense FFN):
    # 'open' also runs the sparse layer `moe` on its post-attention norm's
    # output and HOLDS the result back, 'close' adds it after its own dense
    # FFN; layers.forward_layers carries the value between the two. None:
    # the layer stands alone
    shortcut: str | None = None


@dataclasses.dataclass(frozen=True)
class AttnShape:
    """Head counts and widths of one attention layer: keys and queries
    `head_dim` wide, values `v_head_dim`."""
    heads: int
    kv_heads: int
    head_dim: int
    v_head_dim: int
    # a latent layer (LatentAttnConfig) as its ABSORBED read sees it: every
    # query head against one shared key `head_dim` = row_width wide whose
    # first `v_head_dim` = kv_lora_rank columns are the value; a position
    # holds that one vector and no K or V by head (cache.positional_leaves)
    latent: bool = False

    @property
    def size_q(self) -> int:
        return self.heads * self.head_dim

    @property
    def size_k(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def size_v(self) -> int:
        return self.kv_heads * self.v_head_dim

    @property
    def size_o(self) -> int:
        return self.heads * self.v_head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None
    partial_rotary_factor: float = 1.0
    max_seq_len: int = 4096
    bos_token_id: int | None = None
    eos_token_ids: tuple[int, ...] = ()
    tie_word_embeddings: bool = False
    qkv_bias: bool = False
    fused_qkv: bool = False        # Phi-3/4 pre-fused qkv_proj
    fused_gate_up: bool = False    # Phi-3/4 pre-fused gate_up_proj
    qk_norm: bool = False
    qk_norm_pre_reshape: bool = False  # OLMo2: norm full q/k before head split
    residual_rms_norm: bool = False    # (1+w) norms (Gemma3, Qwen3.5)
    norm_style: str = "pre"
    sliding_window: int | None = None
    global_layers: tuple[bool, ...] = ()   # per-layer global flag (Gemma3/EXAONE4)
    global_rope: bool = True       # EXAONE4 global layers: NoPE
    # Gemma3 SWA layers apply RoPE at rope_local_base_freq with no scaling,
    # while global layers use rope_theta + rope_scaling (HF ground truth,
    # pinned by tests/test_hf_parity.py; the reference skips RoPE on local
    # layers entirely — gemma3/block.rs:62 — which diverges from the HF
    # semantics real checkpoints were trained with, so we follow HF).
    local_rope_theta: float | None = None
    # the local table's own rotary width and scaling (Laguna: window layers
    # rotate all of a head under the plain table, full layers half of it
    # under YaRN); None = the global table's width, never scaled
    local_partial_rotary_factor: float | None = None
    local_rope_scaling: RopeScaling | None = None
    hidden_act: str = "silu"       # 'silu' | 'gelu_tanh'
    embed_scale: float | None = None
    model_prefix: str = "model"
    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int | None = None
    norm_topk_prob: bool = False
    shared_expert_intermediate_size: int | None = None
    # the shared expert's output passes a sigmoid gate of its own
    # (Qwen3.5 MoE's `shared_expert_gate`); False: it is added as it is
    shared_expert_gated: bool = True
    # the selected experts' weights are multiplied by this after their
    # normalisation (Laguna's `moe_routed_scaling_factor`)
    moe_routed_scale: float = 1.0
    moe_gate_act: str = "softmax"  # 'softmax' | 'sigmoid' (Qwen3.5 MoE shared gate)
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple[int, ...] = ()
    # Linear (recurrent) attention
    linear_attn: LinearAttnConfig | None = None
    attn_output_gate: bool = False
    # one sigmoid gate a query head, from a projection of its own of the
    # layer's normed input (`g_proj` [heads, hidden]), on every attention
    # layer at that layer's head count (Laguna's `gating: per-head`) and
    # on every latent layer (Ling-3.0's `head_wise` gate)
    attn_head_gate: bool = False
    # Mamba-1 state-space layers (Jamba); its attention layers are NoPE
    mamba: MambaConfig | None = None
    # power retention in every layer (Brumby): no layer keeps keys or values
    retention: RetentionConfig | None = None
    # latent attention in every layer that `linear_attn.layer_types` does
    # not call linear (DeepSeek-V2: all of them; Ling-3.0: one in six): a
    # position holds one latent, no keys or values by head
    latent_attn: LatentAttnConfig | None = None
    # Attention logit scale override (None = head_dim**-0.5); Gemma3 models
    # may set query_pre_attn_scalar.
    attn_scale: float | None = None
    # MiMo-V2: values narrower than keys, window layers of their own head
    # counts and widths (None = the full layers'), a sink column in the
    # softmax by layer kind, values scaled as they are projected
    v_head_dim: int | None = None
    swa_attn: AttnShape | None = None
    sink_full: bool = False
    sink_swa: bool = False
    attn_value_scale: float | None = None
    # the router selects its top-k by score + `e_score_correction_bias` and
    # weighs by the score alone (DeepSeek-V3's noaux_tc)
    moe_select_bias: bool = False
    # one share of an expert-parallel group: `num_experts` is what this
    # process HOLDS, contiguous from `expert_first`, of `router_experts`
    # the router scores (0 = it holds them all)
    router_experts: int = 0
    expert_first: int = 0
    # group-limited routing (DeepSeek-V2's `group_limited_greedy`): the
    # router's experts lie in `moe_n_group` contiguous groups, a token keeps
    # the `moe_topk_group` groups whose best expert scores highest and takes
    # its top-k among those alone; 1 / 1 is plain top-k
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # a group's score is the SUM of its `moe_group_score_top` best members
    # (Ling-3.0 and DeepSeek-V3's `noaux_tc`: 2, taken of score + bias);
    # 1: its best member alone
    moe_group_score_top: int = 1
    # the router's LAST `moe_zero_experts` outputs are identity experts
    # (LongCat-Flash's `zero_expert_num`, `zero_expert_type: identity`): no
    # bank backs them and no share holds them; a pick of one returns the
    # token itself times its weight
    moe_zero_experts: int = 0
    # the layer list is the SUB-layers of shortcut-connected layers, in
    # pairs (LayerSpec.shortcut): `num_hidden_layers` counts sub-layers
    shortcut_pairs: bool = False

    # ---- per-layer resolution ----

    def layer_spec(self, i: int) -> LayerSpec:
        if self.mamba is not None:
            attn = i in self.mamba.attn_layers
            return LayerSpec(kind="full" if attn else "mamba", use_rope=False,
                             norm_style=self.norm_style, recurrent=not attn)
        if self.retention is not None:
            return LayerSpec(kind="retention", use_rope=True,
                             norm_style=self.norm_style, recurrent=True)
        # the adapter's layer list first: a model may hold delta-rule
        # layers beside latent ones (Ling-3.0) as beside attention
        if self.linear_attn is not None and i < len(self.linear_attn.layer_types):
            if self.linear_attn.layer_types[i] == "linear_attention":
                return LayerSpec(kind="linear", use_rope=False,
                                 is_moe=self._layer_is_moe(i),
                                 norm_style=self.norm_style, recurrent=True)
        if self.latent_attn is not None and self.shortcut_pairs:
            return LayerSpec(kind="latent", use_rope=True,
                             norm_style=self.norm_style,
                             shortcut="close" if i % 2 else "open")
        if self.latent_attn is not None:
            return LayerSpec(kind="latent", use_rope=True,
                             is_moe=self._layer_is_moe(i),
                             norm_style=self.norm_style)
        if self.global_layers:
            is_global = self.global_layers[i] if i < len(self.global_layers) else True
            if is_global:
                return LayerSpec(kind="full", use_rope=self.global_rope,
                                 is_moe=self._layer_is_moe(i),
                                 norm_style=self.norm_style,
                                 sink=self.sink_full)
            return LayerSpec(kind="swa", use_rope=True,
                             local_rope_table=self.local_rope_theta is not None,
                             window=self.sliding_window,
                             is_moe=self._layer_is_moe(i),
                             norm_style=self.norm_style, sink=self.sink_swa)
        if self.sliding_window is not None:
            return LayerSpec(kind="swa", use_rope=True, window=self.sliding_window,
                             is_moe=self._layer_is_moe(i), norm_style=self.norm_style)
        return LayerSpec(kind="full", use_rope=True,
                         is_moe=self._layer_is_moe(i), norm_style=self.norm_style)

    def _layer_is_moe(self, i: int) -> bool:
        if self.num_experts == 0 or i in self.mlp_only_layers:
            return False
        return (i + 1) % max(self.decoder_sparse_step, 1) == 0

    def layer_specs(self) -> tuple[LayerSpec, ...]:
        return tuple(self.layer_spec(i) for i in range(self.num_hidden_layers))

    @property
    def has_recurrent_state(self) -> bool:
        """Some layer's state cannot be rolled back by position: a
        speculative commit re-forwards, and a draft model is refused."""
        return any(s.recurrent for s in self.layer_specs())

    def attn_shape(self, spec: LayerSpec) -> AttnShape:
        """Head counts and widths of a layer of this kind."""
        if spec.kind == "swa" and self.swa_attn is not None:
            return self.swa_attn
        if spec.kind == "latent":
            la = self.latent_attn
            return AttnShape(self.num_attention_heads, 1, la.row_width,
                             la.kv_lora_rank, latent=True)
        return AttnShape(self.num_attention_heads, self.num_key_value_heads,
                         self.head_dim, self.v_head_dim or self.head_dim)

    @property
    def router_width(self) -> int:
        """How many outputs the router scores: the experts of the whole
        expert-parallel group (an uncut model holds them all) and, behind
        them, the identity experts, which NO share holds."""
        return (self.router_experts or self.num_experts) \
            + self.moe_zero_experts

    def sparse_layers(self) -> dict | None:
        """The sparse layers' static description (health's static part and
        the flight record's, beside `attention_kinds`): how many there are,
        what the router scores and what of it this process holds. None: no
        layer is sparse."""
        specs = self.layer_specs()
        sparse = sum(s.is_moe or s.shortcut == "open" for s in specs)
        if not sparse:
            return None
        routed = self.router_experts or self.num_experts
        out = {"layers": sparse, "router_width": self.router_width,
               "routed_experts": routed,
               "identity_experts": self.moe_zero_experts,
               "held": self.num_experts, "held_from": self.expert_first,
               "top_k": self.num_experts_per_tok,
               "routed_scale": self.moe_routed_scale}
        if self.shortcut_pairs:
            out["shortcut_pairs"] = [[i, i + 1] for i, s in enumerate(specs)
                                     if s.shortcut == "open"]
        return out

    @property
    def rotary_dim(self) -> int:
        if self.latent_attn is not None:
            return self.latent_attn.qk_rope_head_dim
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def local_rotary_dim(self) -> int:
        """The local table's rotary width: its own where it has one."""
        if self.local_partial_rotary_factor is None:
            return self.rotary_dim
        hd = self.swa_attn.head_dim if self.swa_attn else self.head_dim
        return int(hd * self.local_partial_rotary_factor)

    def rotary_dim_of(self, spec: LayerSpec) -> int:
        """How many of a head's leading dims a layer of this kind
        rotates."""
        return (self.local_rotary_dim if spec.local_rope_table
                else self.rotary_dim)

    def attention_kinds(self) -> list[dict]:
        """One entry a distinct kind of attention layer, in layer order:
        how many layers, their heads, K/V heads, window, the dims they
        rotate and the table they read (health's static part and the
        flight record's say which table each kind read). The delta-rule
        layers have one entry of their own: heads, key and value widths,
        what a decay number covers, the conv kernel and the float32 state
        a row holds over all of them (the conv tails lie beside it); the
        latent layers theirs: ranks, head widths and the bytes a token
        holds in them (`row_bytes`, bfloat16). Each
        entry is its mixer's `describe` of one layer (mixers.py); layers
        whose entries differ in nothing else add up."""
        from .mixers import mixer_of
        summed = ("layers", "state_bytes", "row_bytes")
        kinds: dict = {}
        for spec in self.layer_specs():
            describe = mixer_of(self, spec).describe
            if describe is None:
                continue
            one = describe(self, spec)
            key = tuple(v for k, v in one.items() if k not in summed)
            if key not in kinds:
                kinds[key] = one
                continue
            for k in summed:
                if k in one:
                    kinds[key][k] += one[k]
        return list(kinds.values())

    def is_eos(self, token_id: int) -> bool:
        return token_id in self.eos_token_ids


def _eos_tuple(v) -> tuple[int, ...]:
    """eos_token_id is a single int or an array (ref: config.rs EosTokenId)."""
    if v is None:
        return ()
    if isinstance(v, int):
        return (v,)
    return tuple(int(x) for x in v)


def _rope_scaling(d: dict | None) -> RopeScaling | None:
    if not d:
        return None
    af = d.get("attention_factor")
    return RopeScaling(
        factor=float(d.get("factor", 1.0)),
        high_freq_factor=float(d.get("high_freq_factor", 4.0)),
        low_freq_factor=float(d.get("low_freq_factor", 1.0)),
        original_max_position_embeddings=int(
            d.get("original_max_position_embeddings", 8192)),
        rope_type=d.get("rope_type") or d.get("type"),
        beta_fast=float(d.get("beta_fast") or 32.0),
        beta_slow=float(d.get("beta_slow") or 1.0),
        attention_factor=None if af is None else float(af),
    )


def _base(d: dict, arch: str, **over) -> dict:
    """Common HF fields shared by every family."""
    heads = int(d["num_attention_heads"])
    hidden = int(d["hidden_size"])
    out = dict(
        arch=arch,
        vocab_size=int(d["vocab_size"]),
        hidden_size=hidden,
        intermediate_size=int(d["intermediate_size"]),
        num_hidden_layers=int(d["num_hidden_layers"]),
        num_attention_heads=heads,
        num_key_value_heads=int(d.get("num_key_value_heads") or heads),
        head_dim=int(d.get("head_dim") or hidden // heads),
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-5)),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rope_scaling=_rope_scaling(d.get("rope_scaling")),
        max_seq_len=int(d.get("max_position_embeddings", 4096)),
        bos_token_id=d.get("bos_token_id"),
        eos_token_ids=_eos_tuple(d.get("eos_token_id")),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
    )
    out.update(over)
    return out


def _llama(d):
    return ModelConfig(**_base(d, "llama"))


def _qwen2(d):
    return ModelConfig(**_base(d, "qwen2", qkv_bias=True))


def _qwen3(d):
    return ModelConfig(**_base(d, "qwen3", qk_norm=True))


def _qwen3_moe(d):
    return ModelConfig(**_base(
        d, "qwen3_moe", qk_norm=True,
        num_experts=int(d.get("num_experts", 128)),
        num_experts_per_tok=int(d.get("num_experts_per_tok", 8)),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        decoder_sparse_step=int(d.get("decoder_sparse_step", 1)),
        mlp_only_layers=tuple(d.get("mlp_only_layers", ())),
    ))


def _phi4(d):
    return ModelConfig(**_base(
        d, "phi4", fused_qkv=True, fused_gate_up=True,
        partial_rotary_factor=float(d.get("partial_rotary_factor", 1.0)),
    ))


def _mistral(d):
    return ModelConfig(**_base(
        d, "mistral",
        sliding_window=d.get("sliding_window"),
    ))


def _gemma3(d):
    """Gemma3: interleaved local(SWA, no RoPE)/global per 6 layers, sandwich
    norms with (1+w) weights, GELU-tanh MLP, embeddings scaled by sqrt(h),
    always-tied lm_head (ref: gemma3/config.rs into_config)."""
    n = int(d["num_hidden_layers"])
    pattern = int(d.get("sliding_window_pattern", 6))
    sched = d.get("sliding_window_attention_schedule") or []
    if sched:
        global_layers = tuple(bool(x) for x in sched[:n])
    else:
        global_layers = tuple((i + 1) % pattern == 0 for i in range(n))
    return ModelConfig(**_base(
        d, "gemma3",
        rope_theta=float(d.get("rope_theta", 10000.0)),
        qk_norm=True, residual_rms_norm=True, norm_style="sandwich",
        sliding_window=int(d.get("sliding_window", 1024)),
        global_layers=global_layers,
        local_rope_theta=float(d.get("rope_local_base_freq", 10000.0)),
        hidden_act="gelu_tanh",
        embed_scale=float(d["hidden_size"]) ** 0.5,
        tie_word_embeddings=True,
        attn_scale=(float(d["query_pre_attn_scalar"]) ** -0.5
                    if d.get("query_pre_attn_scalar") else None),
    ))


def _falcon3(d):
    return ModelConfig(**_base(d, "falcon3"))


def _olmo2(d):
    return ModelConfig(**_base(
        d, "olmo2", qk_norm=True, qk_norm_pre_reshape=True, norm_style="post",
    ))


def _exaone4(d):
    """EXAONE 4.0: 3 local(SWA+RoPE) : 1 global(full, NoPE), QK-norm,
    POST-norm residuals — post_attention_layernorm / post_feedforward_
    layernorm applied to the sublayer output before the residual add (HF
    Exaone4DecoderLayer ground truth, pinned by tests/test_hf_parity.py;
    the reference's exaone4/block.rs:55-67 uses pre-norm with an
    input_layernorm tensor real EXAONE4 checkpoints don't ship)."""
    n = int(d["num_hidden_layers"])
    pattern = d.get("sliding_window_pattern") or d.get("global_layer_period") or 4
    if isinstance(pattern, str):
        # HF documents the string form "LLLG" (L=local/sliding, G=global),
        # which released EXAONE-4.0 configs ship
        global_layers = tuple(pattern[i % len(pattern)].upper() == "G"
                              for i in range(n))
    else:
        global_layers = tuple((i + 1) % int(pattern) == 0 for i in range(n))
    return ModelConfig(**_base(
        d, "exaone4", qk_norm=True, norm_style="post",
        sliding_window=int(d.get("sliding_window", 4096)),
        global_layers=global_layers, global_rope=False,
    ))


def _qwen3_5_common(d, arch, **over):
    """Qwen3.5 wraps the text fields in text_config; hybrid GDN linear
    attention from layer_types (ref: qwen3_5/config.rs:95-160)."""
    tc = d.get("text_config", d)
    # Qwen3.5 nests rope fields in rope_parameters; Qwen3-Next ships them
    # flat at the top level (verified against transformers Qwen3NextConfig)
    rp = tc.get("rope_parameters") or {}
    rope_theta = float(rp.get("rope_theta", tc.get("rope_theta", 10000.0)))
    partial_rotary = float(rp.get(
        "partial_rotary_factor", tc.get("partial_rotary_factor", 0.25)))
    layer_types = tuple(tc.get("layer_types", ()))
    linear = None
    if layer_types:
        linear = LinearAttnConfig(
            layer_types=layer_types,
            conv_kernel_dim=int(tc.get("linear_conv_kernel_dim", 4)),
            num_key_heads=int(tc.get("linear_num_key_heads", 16)),
            key_head_dim=int(tc.get("linear_key_head_dim", 128)),
            num_value_heads=int(tc.get("linear_num_value_heads", 16)),
            value_head_dim=int(tc.get("linear_value_head_dim", 128)),
        )
    base = _base(
        tc, arch,
        rope_theta=rope_theta,
        partial_rotary_factor=partial_rotary,
        residual_rms_norm=True,
        model_prefix="model.language_model",
        linear_attn=linear,
        # full-attention layers: per-head QK-norm + sigmoid output gate
        # (ref: qwen3_5/full_attention.rs:22-46,155-162); the MoE variant
        # reads the flag from text_config (ref: qwen3_5_moe/config.rs)
        qk_norm=True,
        attn_output_gate=bool(tc.get("attn_output_gate", True)),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)
                                 or tc.get("tie_word_embeddings", False)),
    )
    base.update(over)
    return ModelConfig(**base)


def _qwen3_5(d):
    return _qwen3_5_common(d, "qwen3_5")


def _qwen3_next(d):
    """Qwen3-Next (HF Qwen3NextForCausalLM): same GDN-hybrid compute as
    Qwen3.5 but a flat config (no text_config wrapper) and plain `model.`
    prefix; MoE when num_experts > 0 (numerics pinned vs transformers in
    tests/test_hf_parity.py)."""
    arch = "qwen3_5_moe" if int(d.get("num_experts") or 0) > 0 else "qwen3_5"
    cfg = _qwen3_5_moe(d) if arch == "qwen3_5_moe" else _qwen3_5(d)
    return dataclasses.replace(cfg, model_prefix="model")


def _qwen3_5_moe(d):
    tc = d.get("text_config", d)
    return _qwen3_5_common(
        d, "qwen3_5_moe",
        num_experts=int(tc.get("num_experts", 256)),
        num_experts_per_tok=int(tc.get("num_experts_per_tok", 8)),
        moe_intermediate_size=int(tc["moe_intermediate_size"]),
        norm_topk_prob=bool(tc.get("norm_topk_prob", True)),
        shared_expert_intermediate_size=tc.get("shared_expert_intermediate_size"),
        # router is softmax like Qwen3-MoE; sigmoid gates only the shared
        # expert (ref: qwen3_5_moe/moe.rs:10-14; HF Qwen3NextSparseMoeBlock)
        moe_gate_act="softmax",
        decoder_sparse_step=int(tc.get("decoder_sparse_step", 1)),
        mlp_only_layers=tuple(tc.get("mlp_only_layers", ())),
    )


def _expert_share(d: dict, arch: str, held: int, n_group: int = 1,
                  topk_group: int = 1) -> tuple[int, int]:
    """(experts of the group, first held expert) of `expert_parallel: {size,
    rank}`, this repo's key for one share of an expert-parallel group:
    `held` experts of size x held, contiguous from rank x held. Under
    group-limited routing a share holds whole groups. The router may score
    further outputs that NO share holds (LongCat-Flash's identity experts,
    `ModelConfig.moe_zero_experts`, behind the group's experts): they are
    no part of the count returned here."""
    ep = d.get("expert_parallel") or {"size": 1, "rank": 0}
    size, rank = int(ep["size"]), int(ep["rank"])
    if not 0 <= rank < size:
        raise ValueError(f"{arch}: expert_parallel rank {rank} of {size}")
    width = held * size
    if n_group > 1 and (width % n_group or held % (width // n_group)
                        or not 1 <= topk_group <= n_group):
        raise ValueError(
            f"{arch}: {held} held of {width} experts in {n_group} groups "
            f"(top {topk_group}): a share holds whole groups")
    return width, held * rank


def _mimo_v2(d):
    """MiMo-V2 (XiaomiMiMo MiMo-V2-Flash / V2.5, `model_type: mimo_v2`):
    layer i attends in full iff `hybrid_layer_pattern[i] == 0`, else over a
    window of `sliding_window` with its own head counts, rope base and a
    learned sink; its FFN is dense iff `moe_layer_freq[i] == 0`, else 256
    sigmoid-scored experts selected by score + bias, weighed by score.
    Keys and queries are `head_dim` wide with rope on the first
    int(head_dim * partial_rotary_factor) dims, values `v_head_dim`.
    `expert_parallel: {size, rank}` says the checkpoint is one share of an
    expert-parallel group: `n_routed_experts` experts are held, the router
    scores size x as many. What this adapter cannot honour it refuses."""
    n = int(d["num_hidden_layers"])
    for key, want in (("n_group", 1), ("topk_group", 1)):
        if int(d.get(key) or 1) != want:
            raise ValueError(f"mimo_v2: {key} {d[key]} (group-limited "
                             "routing) is not implemented")
    if d.get("n_shared_experts") or d.get("routed_scaling_factor") not in (
            None, 1, 1.0):
        raise ValueError("mimo_v2: shared experts and a routed scaling "
                         "factor other than 1 are not implemented")
    if d.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"mimo_v2: scoring_func {d['scoring_func']!r}")
    pattern = list(d["hybrid_layer_pattern"])[:n]
    freq = list(d.get("moe_layer_freq") or [1] * n)[:n]
    held = int(d["n_routed_experts"])
    width, first = _expert_share(d, "mimo_v2", held)
    heads, hd = int(d["num_attention_heads"]), int(d["head_dim"])
    if int(d.get("swa_head_dim") or hd) != hd:
        raise ValueError("mimo_v2: swa_head_dim other than head_dim (two "
                         "rotary widths) is not implemented")
    return ModelConfig(**_base(
        d, "mimo_v2",
        rms_norm_eps=float(d.get("layernorm_epsilon",
                                 d.get("rms_norm_eps", 1e-5))),
        partial_rotary_factor=float(d.get("partial_rotary_factor", 1.0)),
        v_head_dim=int(d.get("v_head_dim") or hd),
        swa_attn=AttnShape(
            int(d.get("swa_num_attention_heads") or heads),
            int(d.get("swa_num_key_value_heads")
                or d.get("num_key_value_heads") or heads),
            int(d.get("swa_head_dim") or hd),
            int(d.get("swa_v_head_dim") or d.get("v_head_dim") or hd)),
        sliding_window=int(d.get("sliding_window")
                           or d["sliding_window_size"]),
        global_layers=tuple(p == 0 for p in pattern),
        local_rope_theta=float(d.get("swa_rope_theta", 10000.0)),
        sink_full=bool(d.get("add_full_attention_sink_bias", False)),
        sink_swa=bool(d.get("add_swa_attention_sink_bias", False)),
        attn_value_scale=d.get("attention_value_scale"),
        fused_qkv=d.get("attention_projection_layout") == "fused_qkv",
        num_experts=held, router_experts=width, expert_first=first,
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        moe_gate_act="sigmoid", moe_select_bias=True,
        mlp_only_layers=tuple(i for i, f in enumerate(freq) if f == 0),
    ))


# the rope types the laguna adapter takes for a layer kind; every other is
# refused (ops/rope.py would serve it as unscaled rope, with a warning)
_LAGUNA_ROPE_TYPES = ("default", "linear", "llama3", "yarn")


def _laguna(d):
    """Laguna (poolside Laguna-S-2.1, `model_type: laguna`): layer i
    attends in full iff `layer_types[i] == "full_attention"`, else over a
    window of `sliding_window`; the two kinds share K/V heads and head size
    and differ in query heads (`num_attention_heads_per_layer`, constant
    within a kind) and in rope (`rope_parameters` by kind: base, scaling
    and the part of a head that rotates). Every attention layer has a
    sigmoid gate a head from a projection of its own (`gating: per-head`);
    q and k are RMS-normed a head (assumed: the Qwen3-MoE lineage whose
    key names this config keeps). FFNs: dense iff `mlp_layer_types[i] ==
    "dense"` (or i in `mlp_only_layers`), else a softmax router over
    `num_experts`, top-k renormalised and multiplied by
    `moe_routed_scaling_factor`, plus one shared expert added ungated.
    `expert_parallel: {size, rank}` as `mimo_v2` reads it. What this
    adapter cannot honour it refuses."""
    n = int(d["num_hidden_layers"])
    kinds = list(d["layer_types"])[:n]
    if len(kinds) != n or set(kinds) - {"full_attention",
                                         "sliding_attention"}:
        raise ValueError(f"laguna: layer_types {sorted(set(kinds))} for "
                         f"{n} layers")
    is_full = tuple(k == "full_attention" for k in kinds)
    per_layer = list(d.get("num_attention_heads_per_layer")
                     or [d["num_attention_heads"]] * n)[:n]
    heads = {}
    for full in (True, False):
        counts = {int(h) for h, f in zip(per_layer, is_full) if f == full}
        if len(counts) > 1:
            raise ValueError(
                "laguna: num_attention_heads_per_layer varies within the "
                f"{'full' if full else 'sliding'} layers: {sorted(counts)}")
        heads[full] = counts.pop() if counts else int(
            d["num_attention_heads"])
    if d.get("gating", "per-head") != "per-head" or set(
            d.get("gating_types") or ["per_head"]) != {"per_head"}:
        raise ValueError(f"laguna: gating {d.get('gating')!r} / "
                         f"{sorted(set(d.get('gating_types') or []))} "
                         "(only a per-head gate is implemented)")
    if d.get("moe_apply_router_weight_on_input"):
        raise ValueError("laguna: moe_apply_router_weight_on_input true "
                         "is not implemented")
    if float(d.get("moe_router_logit_softcapping") or 0.0) != 0.0:
        raise ValueError("laguna: a router logit softcap other than 0 is "
                         "not implemented")
    if d.get("attention_bias"):
        raise ValueError("laguna: attention_bias true is not implemented")
    rp = d.get("rope_parameters") or {}

    def rope_of(kind):
        r = rp.get(kind) or {}
        rtype = r.get("rope_type") or r.get("type") or "default"
        if rtype not in _LAGUNA_ROPE_TYPES:
            raise ValueError(f"laguna: rope_type {rtype!r} of {kind} is "
                             "not implemented")
        return (float(r.get("rope_theta", d.get("rope_theta", 10000.0))),
                None if rtype == "default" else _rope_scaling(r),
                float(r.get("partial_rotary_factor", 1.0)))

    theta, scaling, partial = rope_of("full_attention")
    ltheta, lscaling, lpartial = rope_of("sliding_attention")
    mlp_kinds = list(d.get("mlp_layer_types") or [])[:n]
    dense = set(int(i) for i in d.get("mlp_only_layers") or ()) | {
        i for i, k in enumerate(mlp_kinds) if k == "dense"}
    held = int(d["num_experts"])
    width, first = _expert_share(d, "laguna", held)
    hd = int(d["head_dim"])
    kv = int(d["num_key_value_heads"])
    return ModelConfig(**_base(
        d, "laguna", num_attention_heads=heads[True],
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
        qk_norm=True, attn_head_gate=True,
        rope_theta=theta, rope_scaling=scaling,
        partial_rotary_factor=partial,
        local_rope_theta=ltheta, local_rope_scaling=lscaling,
        local_partial_rotary_factor=lpartial,
        swa_attn=AttnShape(heads[False], kv, hd, hd),
        sliding_window=int(d["sliding_window"]),
        global_layers=is_full,
        num_experts=held, router_experts=width, expert_first=first,
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        moe_routed_scale=float(d.get("moe_routed_scaling_factor") or 1.0),
        shared_expert_intermediate_size=(
            d.get("shared_expert_intermediate_size") or None),
        shared_expert_gated=False,
        decoder_sparse_step=int(d.get("decoder_sparse_step", 1)),
        mlp_only_layers=tuple(sorted(dense)),
    ))


def _solar_open2(d):
    """Solar Open 2 (upstage Solar-Open2-250B, `model_type: solar_open2`):
    layer i attends by softmax over grouped queries iff i is in
    `gqa_layers`, without rope where `use_rope` is false and with an
    elementwise sigmoid gate on its output where `use_gqa_gate` is true
    (this repo's `attn_output_gate`: the doubled `q_proj`); every other
    layer is Kimi Delta Attention by `linear_attn_config` (models/kda.py;
    `num_kv_heads: null` = as many as `num_heads`). Every FFN is sparse
    (`first_k_dense_replace` 0): a softmax router over `n_routed_experts`,
    top-k renormalised and multiplied by `routed_scaling_factor`, plus
    `n_shared_experts` shared ones as one ungated SwiGLU.
    `expert_parallel: {size, rank}` as `mimo_v2` reads it. What this
    adapter cannot honour it refuses."""
    n = int(d["num_hidden_layers"])
    if d.get("kda_use_full_proj"):
        raise ValueError("solar_open2: kda_use_full_proj true (full-rank "
                         "decay and gate projections) is not implemented "
                         "for this family, whose leaf names for them are "
                         "unknown (`ling3` has them: "
                         "LinearAttnConfig.full_proj)")
    if int(d.get("first_k_dense_replace") or 0) > 0:
        raise ValueError("solar_open2: first_k_dense_replace "
                         f"{d['first_k_dense_replace']} (leading dense "
                         "layers) is not implemented for this family, "
                         "which publishes no dense width (`ling3` has "
                         "delta-rule layers with a dense FFN)")
    if d.get("scoring_func", "softmax") != "softmax":
        raise ValueError(f"solar_open2: scoring_func {d['scoring_func']!r}")
    for key in ("n_group", "topk_group"):
        if int(d.get(key) or 1) != 1:
            raise ValueError(f"solar_open2: {key} {d[key]} (group-limited "
                             "routing) is not implemented")
    gqa = sorted(int(i) for i in d.get("gqa_layers") or ())
    if not gqa or gqa[0] < 0 or gqa[-1] >= n:
        raise ValueError(f"solar_open2: gqa_layers {gqa} for {n} layers")
    la = d["linear_attn_config"]
    heads, hd = int(la["num_heads"]), int(la["head_dim"])
    if int(la.get("num_kv_heads") or heads) != heads:
        raise ValueError("solar_open2: linear_attn_config.num_kv_heads "
                         f"{la['num_kv_heads']} other than num_heads is "
                         "not implemented")
    linear = LinearAttnConfig(
        layer_types=tuple("full_attention" if i in gqa
                          else "linear_attention" for i in range(n)),
        conv_kernel_dim=int(la.get("short_conv_kernel_size", 4)),
        num_key_heads=heads, key_head_dim=hd,
        num_value_heads=heads, value_head_dim=hd,
        kda=True, low_rank=hd,
        beta_scale=2.0 if d.get("kda_allow_neg_eigval") else 1.0)
    held = int(d["n_routed_experts"])
    width, first = _expert_share(d, "solar_open2", held)
    inter = int(d["moe_intermediate_size"])
    return ModelConfig(**_base(
        d, "solar_open2", linear_attn=linear,
        partial_rotary_factor=float(d.get("partial_rotary_factor", 1.0)),
        global_layers=(True,) * n, global_rope=bool(d.get("use_rope", True)),
        attn_output_gate=bool(d.get("use_gqa_gate", False)),
        num_experts=held, router_experts=width, expert_first=first,
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=inter,
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        moe_routed_scale=float(d.get("routed_scaling_factor") or 1.0),
        shared_expert_intermediate_size=(
            int(d.get("n_shared_experts") or 0) * inter or None),
        shared_expert_gated=False,
    ))


def _jamba(d):
    """Jamba / Jamba2 (HF JambaForCausalLM): Mamba-1 layers with attention
    at every `attn_layer_period`-th layer from `attn_layer_offset`
    (JambaConfig.layers_block_type), attention without rope, every FFN a
    dense SwiGLU when `num_experts` is 1. The sparse variants are another
    model: refused, never guessed at."""
    if int(d.get("num_experts") or 1) > 1:
        raise ValueError(
            "jamba: num_experts > 1 (the sparse Jamba variants) is not "
            "implemented; only dense-FFN checkpoints load")
    if bool(d.get("mamba_proj_bias", False)):
        raise ValueError("jamba: mamba_proj_bias true is not implemented")
    n, hidden = int(d["num_hidden_layers"]), int(d["hidden_size"])
    period = int(d.get("attn_layer_period", 8))
    offset = int(d.get("attn_layer_offset", 4))
    rank = d.get("mamba_dt_rank", "auto")
    mamba = MambaConfig(
        d_inner=int(d.get("mamba_expand", 2)) * hidden,
        d_state=int(d.get("mamba_d_state", 16)),
        d_conv=int(d.get("mamba_d_conv", 4)),
        dt_rank=-(-hidden // 16) if rank == "auto" else int(rank),
        conv_bias=bool(d.get("mamba_conv_bias", True)),
        attn_layers=tuple(i for i in range(n) if i % period == offset))
    return ModelConfig(**_base(
        d, "jamba", mamba=mamba,
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6))))


def _brumby(d):
    """Brumby (manifestai Brumby-14B-Base, `model_type: brumby`): Qwen3's
    block, key for key, with the softmax attention of EVERY layer replaced
    by power retention (arXiv:2507.04239; models/brumby.py). The published
    config has no key for the power: 2, the released model's, unless
    `retention_power` says otherwise. What this adapter cannot honour it
    refuses."""
    if d.get("use_sliding_window") or d.get("sliding_window") is not None:
        raise ValueError("brumby: a sliding window "
                         f"({d.get('sliding_window')}) is not implemented: "
                         "every layer is power retention over the whole row")
    if d.get("rope_scaling"):
        raise ValueError(f"brumby: rope_scaling {d['rope_scaling']} is not "
                         "implemented")
    if d.get("attention_bias"):
        raise ValueError("brumby: attention_bias true is not implemented")
    power = int(d.get("retention_power", 2))
    if power != 2:
        raise ValueError(f"brumby: retention power {power} is not "
                         "implemented (the state is the symmetric SQUARE "
                         "of the keys)")
    return ModelConfig(**_base(d, "brumby", qk_norm=True,
                               retention=RetentionConfig(power=power)))


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek's `yarn_get_mscale`: 0.1 mscale ln(factor) + 1 past a
    factor of 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _deepseek_v2(d):
    """DeepSeek-V2 (deepseek-ai DeepSeek-V2, `model_type: deepseek_v2`;
    arXiv:2405.04434): every layer attends through a latent (MLA,
    models/deepseek_v2.py): queries through `q_lora_rank`, keys and values
    expanded from ONE normed latent of `kv_lora_rank` a token beside one
    roped key part of `qk_rope_head_dim` that all heads share. Rope is YaRN
    on those dims; cos and sin carry mscale / mscale_all_dim and the
    softmax scale carries mscale_all_dim's factor SQUARED. The first
    `first_k_dense_replace` FFNs are dense, every other a softmax router over
    `n_routed_experts` in `n_group` contiguous groups (`group_limited_greedy`:
    the `topk_group` groups with the best expert, then the top-k among
    them), weights as scored (`norm_topk_prob` false) times
    `routed_scaling_factor`, plus `n_shared_experts` shared ones as one
    ungated SwiGLU. `expert_parallel: {size, rank}` as `mimo_v2` reads it; a
    share holds whole groups. What this adapter cannot honour it refuses."""
    if d.get("q_lora_rank") is None:
        raise ValueError("deepseek_v2: q_lora_rank null (a full-rank query "
                         "projection, V2-Lite's) is not implemented for "
                         "this family: V2-Lite differs in more than this "
                         "key (`ling3` has a full-rank query: "
                         "LatentAttnConfig.q_lora_rank None)")
    method = d.get("topk_method", "greedy")
    if method not in ("group_limited_greedy", "greedy"):
        raise ValueError(f"deepseek_v2: topk_method {method!r} is not "
                         "implemented")
    if d.get("scoring_func", "softmax") != "softmax":
        raise ValueError(f"deepseek_v2: scoring_func {d['scoring_func']!r}")
    if int(d.get("moe_layer_freq") or 1) != 1:
        raise ValueError(f"deepseek_v2: moe_layer_freq {d['moe_layer_freq']} "
                         "is not implemented")
    if d.get("attention_bias"):
        raise ValueError("deepseek_v2: attention_bias true is not "
                         "implemented")
    for key, has in (("mla_scale_q_lora", "LatentAttnConfig.q_scale"),
                     ("mla_scale_kv_lora", "LatentAttnConfig.kv_scale"),
                     ("zero_expert_num", "ModelConfig.moe_zero_experts")):
        if d.get(key):
            raise ValueError(f"deepseek_v2: {key} {d[key]} is not "
                             "implemented for this family (`longcat_flash` "
                             f"has it: {has})")
    if d.get("norm_topk_prob") and float(
            d.get("routed_scaling_factor") or 1.0) != 1.0:
        raise ValueError("deepseek_v2: norm_topk_prob true beside a "
                         "routed_scaling_factor (which the published gate "
                         "then leaves out) is not implemented")
    rs = d.get("rope_scaling") or None
    scaling, m = None, 1.0
    if rs is not None:
        if (rs.get("type") or rs.get("rope_type")) != "yarn":
            raise ValueError(f"deepseek_v2: rope_scaling {rs} (only yarn is "
                             "implemented)")
        factor = float(rs["factor"])
        all_dim = float(rs.get("mscale_all_dim") or 0.0)
        scaling = dataclasses.replace(
            _rope_scaling(rs), rope_type="yarn",
            attention_factor=yarn_mscale(factor, float(rs.get("mscale", 1)))
            / yarn_mscale(factor, all_dim))
        m = yarn_mscale(factor, all_dim) if all_dim else 1.0
    la = LatentAttnConfig(
        q_lora_rank=int(d["q_lora_rank"]),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]))
    held = int(d["n_routed_experts"])
    grouped = method == "group_limited_greedy"
    n_group = int(d.get("n_group") or 1) if grouped else 1
    topk_group = int(d.get("topk_group") or 1) if grouped else 1
    width, first = _expert_share(d, "deepseek_v2", held, n_group, topk_group)
    inter = int(d["moe_intermediate_size"])
    return ModelConfig(**_base(
        d, "deepseek_v2", head_dim=la.qk_head_dim, v_head_dim=la.v_head_dim,
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
        rope_scaling=scaling, latent_attn=la,
        attn_scale=la.qk_head_dim ** -0.5 * m * m,
        num_experts=held, router_experts=width, expert_first=first,
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=inter,
        norm_topk_prob=bool(d.get("norm_topk_prob", False)),
        moe_routed_scale=float(d.get("routed_scaling_factor") or 1.0),
        moe_n_group=n_group, moe_topk_group=topk_group,
        shared_expert_intermediate_size=(
            int(d.get("n_shared_experts") or 0) * inter or None),
        shared_expert_gated=False,
        mlp_only_layers=tuple(range(int(d.get("first_k_dense_replace")
                                        or 0))),
    ))


def _ling3(d):
    """Ling-3.0-flash (inclusionAI Ling-3.0-flash-VL's language model;
    `model_type: ling3` is this repo's name for it, the published string is
    not known offline): layer i attends through a latent (MLA,
    models/deepseek_v2.py) iff (i + 1) % `layer_group_size` == 0, else by
    Kimi Delta Attention (models/kda.py) at `num_attention_heads` heads of
    `head_dim`, with full-rank decay and gate projections (`no_kda_lora`)
    and a decay bounded below (`kda_safe_gate`, `kda_lower_bound`). The
    latent layer: ONE full-rank query projection (`q_lora_rank` null),
    plain rope at `rope_theta` on its `qk_rope_head_dim` dims, scale
    (nope + rope)^-1/2, a sigmoid gate a head on its output
    (`gated_attention_proj_granularity_type: head_wise`). The first
    `first_k_dense_replace` FFNs are dense, every other a sigmoid router
    over `num_experts` in `n_group` contiguous groups: selection on score +
    bias (`moe_router_enable_expert_bias`), a group scored by the sum of its
    two best, the `topk_group` best groups kept, top-k among them, weights
    the scores alone, normalised (`norm_topk_prob`) and times
    `routed_scaling_factor`, plus one shared SwiGLU of
    `moe_shared_expert_intermediate_size` added ungated.
    `expert_parallel: {size, rank}` as `mimo_v2` reads it; a share holds
    whole groups. What this adapter cannot honour it refuses."""
    n = int(d["num_hidden_layers"])
    for key in ("use_nGPT", "value_norm", "up_proj_norm",
                "scale_router_input", "use_kda_lora", "use_mla_nope",
                "attention_bias"):
        if d.get(key):
            raise ValueError(f"ling3: {key} true is not implemented")
    if not d.get("no_kda_lora", True):
        raise ValueError("ling3: no_kda_lora false (low-rank decay and gate "
                         "projections) is not implemented for this family")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = [x for x in list(d.get(key) or [])[:n] if x]
        if limits:
            raise ValueError(f"ling3: {key} holds {limits[0]} in a layer "
                             "kept (the clamp's form is not in the config) "
                             "and is not implemented")
    if d.get("score_function", "sigmoid") != "sigmoid":
        raise ValueError(f"ling3: score_function {d['score_function']!r}")
    if d.get("gated_attention_proj_granularity_type",
             "head_wise") != "head_wise":
        raise ValueError(
            "ling3: gated_attention_proj_granularity_type "
            f"{d['gated_attention_proj_granularity_type']!r} (only a gate a "
            "head is implemented)")
    if d.get("q_lora_rank") is not None:
        raise ValueError(f"ling3: q_lora_rank {d['q_lora_rank']} (a "
                         "low-rank query pair) is not implemented for this "
                         "family")
    if d.get("rope_scaling"):
        raise ValueError(f"ling3: rope_scaling {d['rope_scaling']} is not "
                         "implemented")
    heads, hd = int(d["num_attention_heads"]), int(d["head_dim"])
    if int(d.get("num_kv_heads_for_linear_attn") or heads) != heads:
        raise ValueError("ling3: num_kv_heads_for_linear_attn "
                         f"{d['num_kv_heads_for_linear_attn']} other than "
                         "num_attention_heads is not implemented")
    if int(d.get("group_norm_size") or 1) != 1:
        raise ValueError(f"ling3: group_norm_size {d['group_norm_size']} "
                         "(an output norm over more than a head) is not "
                         "implemented")
    if not d.get("linear_silu", True) or not d.get("use_qk_norm", True):
        raise ValueError("ling3: linear_silu / use_qk_norm false is not "
                         "implemented (KDA's conv has a SiLU behind it and "
                         "l2-norms q and k a head)")
    la = LatentAttnConfig(
        q_lora_rank=None, kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]))
    if int(d.get("rotary_dim") or la.qk_rope_head_dim) != la.qk_rope_head_dim:
        raise ValueError(f"ling3: rotary_dim {d['rotary_dim']} other than "
                         "qk_rope_head_dim is not implemented")
    period = int(d["layer_group_size"])
    if period < 1:
        raise ValueError(f"ling3: layer_group_size {period}")
    bounded = bool(d.get("kda_safe_gate", False))
    linear = LinearAttnConfig(
        layer_types=tuple("full_attention" if (i + 1) % period == 0
                          else "linear_attention" for i in range(n)),
        conv_kernel_dim=int(d.get("short_conv_kernel_size", 4)),
        num_key_heads=heads, key_head_dim=hd,
        num_value_heads=heads, value_head_dim=hd,
        kda=True, full_proj=True, beta_scale=1.0,
        decay_lower_bound=float(d["kda_lower_bound"]) if bounded else None)
    if bounded and not linear.decay_lower_bound < 0:
        raise ValueError(f"ling3: kda_lower_bound {d['kda_lower_bound']} is "
                         "not below 0")
    held = int(d["num_experts"])
    n_group, topk_group = int(d.get("n_group") or 1), int(
        d.get("topk_group") or 1)
    width, first = _expert_share(d, "ling3", held, n_group, topk_group)
    return ModelConfig(**_base(
        d, "ling3", linear_attn=linear, latent_attn=la,
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
        attn_scale=la.qk_head_dim ** -0.5, attn_head_gate=True,
        num_experts=held, router_experts=width, expert_first=first,
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        moe_routed_scale=float(d.get("routed_scaling_factor") or 1.0),
        moe_gate_act="sigmoid",
        moe_select_bias=bool(d.get("moe_router_enable_expert_bias", False)),
        moe_n_group=n_group, moe_topk_group=topk_group,
        moe_group_score_top=2 if n_group > 1 else 1,
        shared_expert_intermediate_size=(
            int(d.get("moe_shared_expert_intermediate_size") or 0) or None),
        shared_expert_gated=False,
        mlp_only_layers=tuple(range(int(d.get("first_k_dense_replace")
                                        or 0))),
    ))


def _longcat_flash(d):
    """LongCat-Flash (meituan-longcat LongCat-Flash-Chat; `model_type:
    longcat_flash` is the name its Transformers port goes by, unconfirmed
    offline). ONE of the `num_layers` published layers is a
    shortcut-connected block (models/longcat_flash.py has the equations):
    two latent-attention sub-layers (MLA, models/deepseek_v2.py, with the
    normed latents scaled by (hidden / rank)^1/2 where `mla_scale_q_lora` /
    `mla_scale_kv_lora` say so), each with a dense SwiGLU of
    `ffn_hidden_size`, and ONE sparse layer that reads the first
    sub-layer's post-attention norm and whose output joins the stream
    behind the second sub-layer's FFN. Here the layer list is the
    2 x `num_layers` SUB-layers (`shortcut_pairs`), each with a cache entry
    of its own. The router: softmax over `n_routed_experts` +
    `zero_expert_num` outputs, the top `moe_topk` of score +
    `e_score_correction_bias`, weights the scores alone, not normalised,
    times `routed_scaling_factor`; the last `zero_expert_num` outputs are
    identity experts. `expert_parallel: {size, rank}` as `mimo_v2` reads
    it: `n_routed_experts` is what this process holds. What this adapter
    cannot honour it refuses."""
    if d.get("attention_method", "MLA") != "MLA":
        raise ValueError(f"longcat_flash: attention_method "
                         f"{d['attention_method']!r} (only MLA is "
                         "implemented)")
    zeros = int(d.get("zero_expert_num") or 0)
    if zeros and d.get("zero_expert_type", "identity") != "identity":
        raise ValueError(f"longcat_flash: zero_expert_type "
                         f"{d['zero_expert_type']!r} (only identity "
                         "experts are implemented)")
    if d.get("attention_bias"):
        raise ValueError("longcat_flash: attention_bias true is not "
                         "implemented")
    if d.get("rope_scaling"):
        raise ValueError(f"longcat_flash: rope_scaling {d['rope_scaling']} "
                         "is not implemented (the published config has "
                         "plain rope)")
    scale = float(d.get("routed_scaling_factor") or 1.0)
    if d.get("norm_topk_prob") and scale != 1.0:
        raise ValueError("longcat_flash: norm_topk_prob true beside a "
                         "routed_scaling_factor is not implemented")
    if d.get("router_bias"):
        raise ValueError("longcat_flash: router_bias true (a bias on the "
                         "router's logits) is not implemented; the "
                         "selection bias e_score_correction_bias is")
    if d.get("q_lora_rank") is None:
        raise ValueError("longcat_flash: q_lora_rank null is not "
                         "implemented for this family")
    hidden, ql, kvl = (int(d["hidden_size"]), int(d["q_lora_rank"]),
                       int(d["kv_lora_rank"]))
    la = LatentAttnConfig(
        q_lora_rank=ql, kv_lora_rank=kvl,
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        q_scale=(hidden / ql) ** 0.5 if d.get("mla_scale_q_lora") else 1.0,
        kv_scale=((hidden / kvl) ** 0.5 if d.get("mla_scale_kv_lora")
                  else 1.0))
    held = int(d["n_routed_experts"])
    width, first = _expert_share(d, "longcat_flash", held)
    base = {**d, "num_hidden_layers": 2 * int(d["num_layers"]),
            "intermediate_size": int(d["ffn_hidden_size"])}
    return ModelConfig(**_base(
        base, "longcat_flash", head_dim=la.qk_head_dim,
        v_head_dim=la.v_head_dim, latent_attn=la, shortcut_pairs=True,
        attn_scale=la.qk_head_dim ** -0.5,
        num_experts=held, router_experts=width, expert_first=first,
        moe_zero_experts=zeros,
        num_experts_per_tok=int(d["moe_topk"]),
        moe_intermediate_size=int(d["expert_ffn_hidden_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", False)),
        moe_routed_scale=scale, moe_select_bias=True,
    ))


# HF architectures string -> adapter (ref: cake/mod.rs arch_str_to_text_model_arch;
# unknown strings fall back to llama, matching the reference)
ARCH_ADAPTERS = {
    "LlamaForCausalLM": _llama,
    "Qwen2ForCausalLM": _qwen2,
    "Qwen3ForCausalLM": _qwen3,
    "Qwen3MoeForCausalLM": _qwen3_moe,
    "Qwen3_5ForConditionalGeneration": _qwen3_5,
    "Qwen3_5MoeForConditionalGeneration": _qwen3_5_moe,
    "Qwen3NextForCausalLM": _qwen3_next,
    "Phi3ForCausalLM": _phi4,
    "Phi4ForCausalLM": _phi4,
    "MistralForCausalLM": _mistral,
    "Gemma3ForCausalLM": _gemma3,
    "FalconForCausalLM": _falcon3,
    "OLMo2ForCausalLM": _olmo2,
    "Olmo2ForCausalLM": _olmo2,
    "ExaoneForCausalLM": _exaone4,
    "Exaone4ForCausalLM": _exaone4,
    "JambaForCausalLM": _jamba,
    "MiMoV2ForCausalLM": _mimo_v2,
    "MiMoV2FlashForCausalLM": _mimo_v2,
    "LagunaForCausalLM": _laguna,
    "DeepseekV2ForCausalLM": _deepseek_v2,
    "LongcatFlashForCausalLM": _longcat_flash,
}

# short family names (CLI --arch overrides, tests)
FAMILY_ADAPTERS = {
    "llama": _llama, "llama3": _llama,
    "qwen2": _qwen2, "qwen3": _qwen3, "qwen3_moe": _qwen3_moe,
    "qwen3_5": _qwen3_5, "qwen3_5_moe": _qwen3_5_moe,
    "phi4": _phi4, "phi3": _phi4,
    "mistral": _mistral, "gemma3": _gemma3, "falcon3": _falcon3,
    "olmo2": _olmo2, "exaone4": _exaone4, "jamba": _jamba,
    "mimo_v2": _mimo_v2, "laguna": _laguna, "solar_open2": _solar_open2,
    "brumby": _brumby, "deepseek_v2": _deepseek_v2, "ling3": _ling3,
    "longcat_flash": _longcat_flash,
}


def detect_arch(config: dict) -> str:
    """First architectures entry (ref: config.rs detect_text_model_arch)."""
    archs = config.get("architectures") or []
    return archs[0] if archs else ""


def config_from_hf_dict(d: dict, arch: str | None = None) -> ModelConfig:
    name = arch or detect_arch(d)
    # an architectures string this table lacks still names its family in
    # `model_type` (MiMo-V2.5's published string is not known here)
    adapter = (ARCH_ADAPTERS.get(name) or FAMILY_ADAPTERS.get(name)
               or FAMILY_ADAPTERS.get(d.get("model_type"), _llama))
    return adapter(d)


def config_from_dir(model_dir: str, arch: str | None = None) -> ModelConfig:
    with open(os.path.join(model_dir, "config.json")) as f:
        d = json.load(f)
    return config_from_hf_dict(d, arch)


def tiny_config(arch: str = "llama", **over) -> ModelConfig:
    """Tiny synthetic config for tests (mirrors ref tests/unit_tests/helpers.rs:
    hidden=64, 4 layers, GQA 4/2)."""
    d: dict[str, Any] = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        rms_norm_eps=1e-5, rope_theta=10000.0, max_position_embeddings=128,
        eos_token_id=2,
    )
    if arch in ("qwen3_moe", "qwen3_5_moe"):
        d.update(num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32)
    if arch == "jamba":
        # both kinds occur: attention at layers 2 and 6 of a period of 4
        d.update(attn_layer_period=4, attn_layer_offset=2, num_experts=1,
                 mamba_expand=2, mamba_d_state=8, mamba_d_conv=4,
                 mamba_dt_rank=8, tie_word_embeddings=True,
                 rms_norm_eps=1e-6)
    if arch == "mimo_v2":
        # both kinds twice, a dense first layer, window layers of other
        # head counts, keys wider than values and, as the published 192,
        # no multiple of the lanes while a position's keys are (4 x 96,
        # 8 x 96: cache.key_row_shape joins them), a share of 4 of 8 experts
        d.update(num_hidden_layers=5, hybrid_layer_pattern=[0, 1, 1, 0, 1],
                 moe_layer_freq=[0, 1, 1, 1, 1], num_attention_heads=8,
                 num_key_value_heads=4, head_dim=96, v_head_dim=64,
                 swa_head_dim=96, swa_v_head_dim=64,
                 swa_num_attention_heads=8, swa_num_key_value_heads=8,
                 partial_rotary_factor=0.334, sliding_window=16,
                 swa_rope_theta=100.0, add_swa_attention_sink_bias=True,
                 add_full_attention_sink_bias=False,
                 attention_value_scale=0.707, layernorm_epsilon=1e-5,
                 n_routed_experts=4, num_experts_per_tok=2,
                 moe_intermediate_size=32, n_group=1, topk_group=1,
                 norm_topk_prob=True, scoring_func="sigmoid",
                 expert_parallel={"size": 2, "rank": 0})
    if arch == "laguna":
        # a dense first layer, both kinds twice in the published order,
        # window layers of 6 query heads beside full layers of 4 on the
        # same 2 K/V heads, YaRN over half of a head on the full layers,
        # a share of 4 of 8 experts beside a shared one
        d.update(num_hidden_layers=5, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
                 layer_types=["full_attention", "sliding_attention",
                              "sliding_attention", "full_attention",
                              "sliding_attention"],
                 num_attention_heads_per_layer=[4, 6, 6, 4, 6],
                 gating="per-head", sliding_window=16,
                 rope_parameters={
                     "full_attention": {
                         "rope_type": "yarn", "rope_theta": 500000,
                         "factor": 8, "beta_fast": 32, "beta_slow": 1,
                         "original_max_position_embeddings": 16,
                         "partial_rotary_factor": 0.5},
                     "sliding_attention": {
                         "rope_type": "default", "rope_theta": 10000,
                         "partial_rotary_factor": 1}},
                 mlp_layer_types=["dense"] + ["sparse"] * 4,
                 mlp_only_layers=[0], num_experts=4,
                 num_experts_per_tok=2, moe_intermediate_size=32,
                 shared_expert_intermediate_size=32, norm_topk_prob=True,
                 moe_routed_scaling_factor=2.5,
                 expert_parallel={"size": 2, "rank": 0})
    if arch == "solar_open2":
        # two periods of G, K, K, K: gated GQA without rope beside Kimi
        # Delta Attention (4 heads x 16, conv 4), every FFN a share of 4 of
        # 8 experts beside a shared one
        d.update(num_hidden_layers=8, gqa_layers=[0, 4], use_rope=False,
                 use_gqa_gate=True, head_dim=16,
                 linear_attn_config={"short_conv_kernel_size": 4,
                                     "head_dim": 16, "num_heads": 4,
                                     "num_kv_heads": None},
                 kda_use_full_proj=False, kda_allow_neg_eigval=True,
                 first_k_dense_replace=0, n_routed_experts=4,
                 n_shared_experts=1, num_experts_per_tok=2,
                 moe_intermediate_size=32, norm_topk_prob=True,
                 routed_scaling_factor=1,
                 expert_parallel={"size": 2, "rank": 0})
    if arch == "brumby":
        # power retention in every layer: 4 query heads on 2 key/value
        # heads of width 8, a state of 36 x 8 a head
        d.update(head_dim=8, rms_norm_eps=1e-6, rope_theta=1000000,
                 sliding_window=None, use_sliding_window=False,
                 rope_scaling=None, attention_bias=False)
    if arch == "deepseek_v2":
        # latent attention in every layer: 4 heads of 16 + 8 (nope + rope)
        # with values of 16, through ranks 24 and 32, so a row is 40 wide;
        # YaRN with the factor's square in the scale; a dense first layer,
        # then a share of 4 of 8 experts (2 of the router's 4 groups, of
        # which a token keeps 2) beside two shared ones
        d.update(num_hidden_layers=3, rms_norm_eps=1e-6, q_lora_rank=24,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, attention_bias=False,
                 rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                               "beta_slow": 1, "mscale": 0.707,
                               "mscale_all_dim": 0.707,
                               "original_max_position_embeddings": 16},
                 first_k_dense_replace=1, moe_layer_freq=1,
                 n_routed_experts=4, n_shared_experts=2,
                 num_experts_per_tok=3, moe_intermediate_size=32,
                 n_group=4, topk_group=2,
                 topk_method="group_limited_greedy", scoring_func="softmax",
                 norm_topk_prob=False, routed_scaling_factor=16,
                 expert_parallel={"size": 2, "rank": 0})
    if arch == "ling3":
        # a dense first layer, then a period of K, K, L: Kimi Delta
        # Attention (4 heads x 16, full-rank gates, decay in (-5, 0)) beside
        # a gated latent layer (4 heads of 16 + 8, values of 16, rank 32:
        # a row of 40), a share of 4 of 8 experts (2 of the router's 4
        # groups, of which a token keeps 2) beside a shared one
        d.update(num_hidden_layers=4, head_dim=16, rms_norm_eps=1e-6,
                 layer_group_size=3, first_k_dense_replace=1,
                 q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, rotary_dim=8,
                 short_conv_kernel_size=4, no_kda_lora=True,
                 kda_safe_gate=True, kda_lower_bound=-5,
                 gated_attention_proj_granularity_type="head_wise",
                 num_experts=4, num_experts_per_tok=3,
                 moe_intermediate_size=32,
                 moe_shared_expert_intermediate_size=32, n_group=4,
                 topk_group=2, score_function="sigmoid",
                 moe_router_enable_expert_bias=True, norm_topk_prob=True,
                 routed_scaling_factor=2.5,
                 expert_parallel={"size": 2, "rank": 0})
    if arch == "longcat_flash":
        # two shortcut layers = four latent sub-layers (4 heads of 16 + 8,
        # values of 16, ranks 24 and 32, both latents scaled), a dense FFN
        # each, a sparse layer a pair: a share of 4 of 8 experts under a
        # router of 8 + 4 identity outputs, top 3, unnormalised x 6
        for key in ("num_hidden_layers", "intermediate_size",
                    "num_key_value_heads"):
            d.pop(key)
        d.update(num_layers=2, ffn_hidden_size=128, expert_ffn_hidden_size=32,
                 q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, attention_bias=False,
                 attention_method="MLA", mla_scale_q_lora=True,
                 mla_scale_kv_lora=True, n_routed_experts=4,
                 zero_expert_num=4, zero_expert_type="identity", moe_topk=3,
                 routed_scaling_factor=6,
                 expert_parallel={"size": 2, "rank": 0})
    d.update(over)
    if arch in ("qwen3_5", "qwen3_5_moe"):
        d["text_config"] = dict(d)
        n = d["num_hidden_layers"]
        d["text_config"]["layer_types"] = [
            "linear_attention" if (i + 1) % 4 else "full_attention"
            for i in range(n)]
        d["text_config"].update(
            head_dim=16, linear_conv_kernel_dim=4, linear_num_key_heads=4,
            linear_key_head_dim=16, linear_num_value_heads=4,
            linear_value_head_dim=16)
        d["text_config"].update(over)
    return FAMILY_ADAPTERS[arch](d)
