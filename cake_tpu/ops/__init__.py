"""TPU op/kernel library.

The reference's ComputeBackend trait (~35 methods over CUDA/Metal/Vulkan/
ROCm/CPU — ref: cake-core/src/backends/mod.rs) collapses on TPU into this
flat module of jit-fusable functions plus Pallas kernels for the few ops
where hand-scheduling beats XLA (flash attention for long prefill, decode
attention that walks each cache row to its frontier).
"""
from .activations import (add3, add_scaled, adaln_modulate, exp_mul, gelu,
                          gelu_mul, gelu_tanh, sigmoid, silu, silu_mul,
                          softmax, stable_softplus, sub_mul)
from .attention import (causal_sdpa, make_attention_mask,
                        multi_head_attention)
from .conv import (causal_depthwise_conv1d_update, conv1d, conv2d,
                   conv_transpose1d, depthwise_conv1d, depthwise_conv1d_silu)
from .fp8 import dequant_fp8_blockwise, quant_fp8_blockwise
from .linear import embedding, linear
from .norms import (add_rms_norm, group_norm, layer_norm,
                    load_rms_norm_weight, rms_norm, rms_norm_channel,
                    rms_norm_gated)
from .rope import RopeScaling, apply_rope, inv_frequencies, rope_tables
from .sampling import (SamplingConfig, apply_repeat_penalty,
                       push_recent_token, sample)

__all__ = [n for n in dir() if not n.startswith("_")]
