"""Params pytree -> HF-named tensor dict (inverse of utils/loaders.py).

Used by the splitter (`cake split` — ref: utils/split.rs writes per-worker
safetensors bundles) and by round-trip tests.
"""
from __future__ import annotations

import numpy as np

from ..models.common.config import ModelConfig
from ..models.common.mixers import mixer_of


def _np(x) -> np.ndarray:
    return np.asarray(x)


def _norm(cfg: ModelConfig, w) -> np.ndarray:
    arr = _np(w).astype(np.float32)
    if cfg.residual_rms_norm:
        arr = arr - 1.0     # stored as delta from 0 (ref: config.rs)
    return arr.astype(_np(w).dtype)


def export_attention_params(cfg: ModelConfig, a: dict,
                            lp: str) -> dict[str, np.ndarray]:
    """An attention layer's tensors under `<lp>.self_attn.` (the inverse of
    ParamLoader._attention), its projections apart."""
    out = {f"{lp}.self_attn.o_proj.weight": _np(a["o_proj"]["weight"])}
    for proj in ("q_proj", "k_proj", "v_proj"):
        out[f"{lp}.self_attn.{proj}.weight"] = _np(a[proj]["weight"])
        if "bias" in a[proj]:
            out[f"{lp}.self_attn.{proj}.bias"] = _np(a[proj]["bias"])
    for qk in ("q_norm", "k_norm"):
        if qk in a:
            out[f"{lp}.self_attn.{qk}.weight"] = _norm(cfg, a[qk]["weight"])
    if "attention_sink_bias" in a:
        out[f"{lp}.self_attn.attention_sink_bias"] = \
            _np(a["attention_sink_bias"])
    if "g_proj" in a:
        out[f"{lp}.self_attn.g_proj.weight"] = _np(a["g_proj"]["weight"])
    return out


def params_to_hf_tensors(cfg: ModelConfig, params: dict,
                         layer_offset: int = 0,
                         fuse_phi: bool = False) -> dict[str, np.ndarray]:
    """fuse_phi: write Phi-style fused qkv_proj names, and gate_up_proj
    where the family fuses that too."""
    out: dict[str, np.ndarray] = {}
    pre = cfg.model_prefix
    if cfg.shortcut_pairs:
        from ..models.longcat_flash import refuse_checkpoint
        refuse_checkpoint("exporting")
    ckpt = {}
    if cfg.mamba is not None:
        from ..models.jamba import CHECKPOINT_NAMES as ckpt
    elif cfg.latent_attn is not None:
        from ..models.deepseek_v2 import CHECKPOINT_NAMES as ckpt
    ffn = ckpt.get("mlp", "mlp")

    if "embed_tokens" in params:
        out[f"{pre}.embed_tokens.weight"] = _np(params["embed_tokens"]["weight"])
    if "norm" in params:
        out[f"{pre}.{ckpt.get('norm', 'norm')}.weight"] = _norm(
            cfg, params["norm"]["weight"])
    if "lm_head" in params:
        out["lm_head.weight"] = _np(params["lm_head"]["weight"])

    for j, layer in enumerate(params["layers"]):
        i = layer_offset + j
        lp = f"{pre}.layers.{i}"
        for norm in ("input_layernorm", "post_attention_layernorm",
                     "pre_feedforward_layernorm", "post_feedforward_layernorm"):
            if norm in layer:
                out[f"{lp}.{ckpt.get(norm, norm)}.weight"] = _norm(
                    cfg, layer[norm]["weight"])
        m = mixer_of(cfg, cfg.layer_spec(i))
        out.update(m.export_params(cfg, layer[m.param_key], lp))
        if fuse_phi and f"{lp}.self_attn.q_proj.weight" in out:
            # Phi's checkpoint format, whatever the layer kind exported
            out[f"{lp}.self_attn.qkv_proj.weight"] = np.concatenate(
                [out.pop(f"{lp}.self_attn.{proj}.weight")
                 for proj in ("q_proj", "k_proj", "v_proj")], axis=0)
        mlp = layer["mlp"]
        if "experts" in mlp:    # MoE
            out[f"{lp}.mlp.gate.weight"] = _np(mlp["gate"]["weight"])
            if "e_score_correction_bias" in mlp["gate"]:
                out[f"{lp}.mlp.gate.e_score_correction_bias"] = \
                    _np(mlp["gate"]["e_score_correction_bias"])
            for e in range(cfg.num_experts):
                for proj in ("gate_proj", "up_proj", "down_proj"):
                    out[f"{lp}.mlp.experts.{e}.{proj}.weight"] = \
                        _np(mlp["experts"][proj][e])
            if "shared_expert" in mlp:
                shared = ckpt.get("shared_expert", "shared_expert")
                for proj in ("gate_proj", "up_proj", "down_proj"):
                    out[f"{lp}.mlp.{shared}.{proj}.weight"] = \
                        _np(mlp["shared_expert"][proj]["weight"])
                if "shared_expert_gate" in mlp:
                    out[f"{lp}.mlp.shared_expert_gate.weight"] = \
                        _np(mlp["shared_expert_gate"]["weight"])
        else:
            if fuse_phi and cfg.fused_gate_up:
                out[f"{lp}.mlp.gate_up_proj.weight"] = np.concatenate([
                    _np(mlp["gate_proj"]["weight"]),
                    _np(mlp["up_proj"]["weight"])], axis=0)
                out[f"{lp}.mlp.down_proj.weight"] = _np(mlp["down_proj"]["weight"])
            else:
                for proj in ("gate_proj", "up_proj", "down_proj"):
                    out[f"{lp}.{ffn}.{proj}.weight"] = _np(mlp[proj]["weight"])
    return out
