"""Model family registry.

Maps HF architecture strings / family names to ModelConfig adapters and
modality (ref: lib.rs dispatch_text_model! + cake/mod.rs
arch_str_to_text_model_arch). Every dense text family is a config-driven
variant of the generic block in models/common/layers.py — exactly the
reference's design where 13 families share one Config and block toolbox
(ref: models/common/config.rs:86-150).

Family notes (distinguishers, ref SURVEY §2e):
  llama3   - llama3 rope scaling, multi-EOS (models/llama3/)
  qwen2    - QKV bias (models/qwen2/)
  qwen3    - GQA + post-reshape QK-norm (models/qwen3/)
  qwen3_moe- 128-expert top-8 sparse FFN (models/qwen3_moe/)
  qwen3_5  - hybrid GDN linear attention 3:1 (models/qwen3_5/)
  qwen3_5_moe - GDN + 256-expert MoE + shared expert, attn_output_gate
  phi4     - pre-fused qkv/gate_up, partial RoPE 0.25 (models/phi4/)
  mistral  - sliding window (models/mistral/)
  gemma3   - 5:1 local(SWA,no-RoPE)/global, sandwich (1+w) norms, GELU,
             embed*sqrt(h) (models/gemma3/)
  falcon3  - vanilla GQA (models/falcon3/)
  olmo2    - post-norm, pre-reshape QK-norm (models/olmo2/)
  exaone4  - 3:1 local(SWA+RoPE)/global(NoPE) (models/exaone4/)
  jamba    - Mamba-1 state-space layers, NoPE attention every
             attn_layer_period-th layer, dense FFNs (models/jamba.py)
  mimo_v2  - window layers with a sink beside full layers of other head
             counts, keys 192 / values 128, bias-selecting sigmoid router
  laguna   - window layers of 72 query heads beside full layers of 48 on
             the same K/V heads, per-head sigmoid gate, YaRN on full
             layers / plain rope on window layers, scaled softmax router +
             ungated shared expert
"""
from __future__ import annotations

from .common.config import (ARCH_ADAPTERS, FAMILY_ADAPTERS, ModelConfig,
                            config_from_dir, config_from_hf_dict, detect_arch)

TEXT_FAMILIES = tuple(sorted(set(FAMILY_ADAPTERS) - {"llama", "phi3"}))

# modality dispatch (ref: cake-cli run_master -> text/image/audio paths)
IMAGE_ARCHS = {"FluxPipeline": "flux1", "Flux2Pipeline": "flux2",
               "StableDiffusionPipeline": "sd"}
AUDIO_ARCHS = {"VibeVoiceForConditionalGeneration": "vibevoice",
               "LuxTTSForTextToSpeech": "luxtts"}


def modality_for_arch(arch: str) -> str:
    if arch in IMAGE_ARCHS:
        return "image"
    if arch in AUDIO_ARCHS:
        return "audio"
    return "text"
