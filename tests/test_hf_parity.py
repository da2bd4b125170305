"""External numerics ground truth: every text family cross-checked against
the installed `transformers` implementation (CPU, f32, tiny random configs).

The golden fixtures (tests/golden) pin our own history; these tests pin the
*semantics* to an independent implementation — HF is what the real release
checkpoints were trained with, so divergence here means wrong-from-day-one
numerics, not a harmless style choice (BASELINE.json north star: identical
logits atol 1e-3; reference analog: cake-core/tests/unit_tests/
test_backend_ops.rs cross-checking ops against candle).

Weights flow OUR pytree -> utils/export.params_to_hf_tensors -> HF
state_dict, so the mapping layer is under test too (it is the inverse of
utils/loaders.py, which round-trip tests already pin against it).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from cake_tpu.models.common.config import ModelConfig, tiny_config
from cake_tpu.models.common.layers import (forward_train, init_params,
                                           make_rope)
from cake_tpu.models.common.text_model import TextModel
from cake_tpu.utils.export import params_to_hf_tensors

PROMPT = [11, 23, 5, 190, 77, 3, 149, 66, 20, 101, 7, 55]
ATOL = 1e-3


def randomize(cfg: ModelConfig, params: dict, seed: int) -> dict:
    """Replace every weight leaf with non-trivial random values so identity
    weights (norms at 1, zero biases) can't hide mapping or scaling bugs."""
    rng = np.random.default_rng(seed)
    rope = params.pop("rope")

    def rand(leaf):
        arr = rng.normal(0.0, 0.05, np.shape(leaf)).astype(np.float32)
        return jnp.asarray(arr)

    out = jax.tree.map(rand, params)
    out["rope"] = rope
    return out


def our_logits(cfg: ModelConfig, params: dict, prompt=PROMPT) -> np.ndarray:
    """[S, V] f32 logits from the stateless forward."""
    tokens = jnp.asarray([prompt], jnp.int32)
    return np.asarray(forward_train(cfg, params, tokens)[0], np.float32)


def our_cached_last_logits(cfg: ModelConfig, params: dict,
                           prompt=PROMPT) -> np.ndarray:
    """Last-token logits through the product prefill+decode cache path."""
    model = TextModel(cfg, params=params, dtype=jnp.float32, max_cache_len=64)
    cache = model.new_cache()
    _, cache = model.prefill(cache, prompt[:-1])
    logits, _ = model.decode_logits(cache, prompt[-1])
    return np.asarray(logits[0], np.float32)


def load_hf(model_cls, hf_config, tensors: dict[str, np.ndarray],
            allow_missing: tuple[str, ...] = ()):
    hf_config._attn_implementation = "eager"
    torch.manual_seed(0)
    model = model_cls(hf_config)
    sd = {k: torch.from_numpy(np.ascontiguousarray(v)) for k, v in tensors.items()}
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected, f"tensors HF does not expect: {unexpected}"
    bad = [k for k in missing
           if not any(k.startswith(p) or k.endswith(p) for p in allow_missing)]
    assert not bad, f"HF tensors our export did not provide: {bad}"
    if getattr(hf_config, "tie_word_embeddings", False):
        model.tie_weights()
    model.eval()
    return model


def hf_logits(model, prompt=PROMPT) -> np.ndarray:
    with torch.no_grad():
        out = model(input_ids=torch.tensor([prompt]), use_cache=False)
    return out.logits[0].float().numpy()


def assert_close(ours: np.ndarray, theirs: np.ndarray, what: str):
    err = np.max(np.abs(ours - theirs))
    assert err < ATOL, f"{what}: max |Δlogit| = {err:.2e} >= {ATOL}"


def check_family(cfg: ModelConfig, model_cls, hf_config, seed: int = 0,
                 fuse_phi: bool = False,
                 allow_missing: tuple[str, ...] = (),
                 extra_tensors=None, prompt=PROMPT):
    params = randomize(cfg, init_params(cfg, jax.random.PRNGKey(0),
                                        jnp.float32), seed)
    params["rope"] = make_rope(cfg)
    tensors = params_to_hf_tensors(cfg, params, fuse_phi=fuse_phi)
    if extra_tensors:
        tensors = extra_tensors(params, tensors)
    model = load_hf(model_cls, hf_config, tensors, allow_missing)
    ref = hf_logits(model, prompt)
    assert_close(our_logits(cfg, params, prompt), ref, "stateless forward")
    assert_close(our_cached_last_logits(cfg, params, prompt), ref[-1],
                 "cached prefill+decode last logit")


# ---------------------------------------------------------------------------
# dense llama-likes
# ---------------------------------------------------------------------------

_TINY_HF = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=10000.0,
                max_position_embeddings=128, eos_token_id=2,
                tie_word_embeddings=False)


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_llama():
    from transformers import LlamaConfig, LlamaForCausalLM
    check_family(tiny_config("llama"), LlamaForCausalLM,
                 LlamaConfig(attention_bias=False, **_TINY_HF))


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_llama3_rope_scaling():
    scaling = dict(rope_type="llama3", factor=8.0, high_freq_factor=4.0,
                   low_freq_factor=1.0, original_max_position_embeddings=32)
    from transformers import LlamaConfig, LlamaForCausalLM
    check_family(tiny_config("llama", rope_scaling=scaling),
                 LlamaForCausalLM,
                 LlamaConfig(rope_scaling=dict(scaling), **_TINY_HF))


def test_falcon3():
    # Falcon3 ships Llama-architecture checkpoints (ref: models/falcon3);
    # HF ground truth is therefore LlamaForCausalLM.
    from transformers import LlamaConfig, LlamaForCausalLM
    check_family(tiny_config("falcon3"), LlamaForCausalLM,
                 LlamaConfig(**_TINY_HF))


def test_qwen2():
    from transformers import Qwen2Config, Qwen2ForCausalLM
    check_family(tiny_config("qwen2"), Qwen2ForCausalLM,
                 Qwen2Config(**_TINY_HF))


def test_qwen3():
    from transformers import Qwen3Config, Qwen3ForCausalLM
    check_family(tiny_config("qwen3"), Qwen3ForCausalLM,
                 Qwen3Config(head_dim=16, **_TINY_HF))


def test_mistral_sliding_window():
    from transformers import MistralConfig, MistralForCausalLM
    check_family(tiny_config("mistral", sliding_window=4),
                 MistralForCausalLM,
                 MistralConfig(sliding_window=4, **_TINY_HF))


def test_phi4():
    from transformers import Phi3Config, Phi3ForCausalLM
    check_family(tiny_config("phi4", partial_rotary_factor=0.5),
                 Phi3ForCausalLM,
                 Phi3Config(partial_rotary_factor=0.5, pad_token_id=0,
                            **_TINY_HF),
                 fuse_phi=True)


def test_olmo2():
    from transformers import Olmo2Config, Olmo2ForCausalLM
    check_family(tiny_config("olmo2"), Olmo2ForCausalLM,
                 Olmo2Config(**_TINY_HF))


def test_exaone4():
    from transformers import Exaone4Config, Exaone4ForCausalLM
    check_family(tiny_config("exaone4", sliding_window=4),
                 Exaone4ForCausalLM,
                 Exaone4Config(sliding_window=4, sliding_window_pattern=4,
                               **_TINY_HF))


def test_gemma3():
    from transformers import Gemma3ForCausalLM, Gemma3TextConfig
    d = dict(_TINY_HF)
    d.update(rope_theta=1_000_000.0, tie_word_embeddings=True)
    cfg = tiny_config("gemma3", rope_theta=1_000_000.0,
                      query_pre_attn_scalar=32, sliding_window=4,
                      sliding_window_pattern=2, rope_local_base_freq=10000.0,
                      rope_scaling={"rope_type": "linear", "factor": 8.0})
    hf = Gemma3TextConfig(head_dim=16, sliding_window=4,
                          sliding_window_pattern=2, query_pre_attn_scalar=32,
                          rope_local_base_freq=10000.0,
                          rope_scaling={"rope_type": "linear", "factor": 8.0},
                          **d)
    check_family(cfg, Gemma3ForCausalLM, hf, allow_missing=("lm_head.weight",))


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def _qwen3_next_tensors(cfg):
    """Rewrite our exported GDN projections into HF Qwen3Next's interleaved
    in_proj_qkvz/in_proj_ba layout (inverse of the loader path)."""
    from cake_tpu.models.qwen3_5 import hf_qkvz_ba_from_flat

    def convert(params, tensors):
        out = {}
        for k, v in tensors.items():
            if k.endswith(".linear_attn.in_proj.weight"):
                qkvz, ba = hf_qkvz_ba_from_flat(cfg, v)
                base = k[:-len(".in_proj.weight")]
                out[base + ".in_proj_qkvz.weight"] = qkvz
                out[base + ".in_proj_ba.weight"] = ba
            else:
                out[k] = v
        return out
    return convert


def _qwen3_next_hf(**over):
    from transformers import Qwen3NextConfig
    layer_types = ["linear_attention" if (i + 1) % 4 else "full_attention"
                   for i in range(4)]
    d = dict(_TINY_HF)
    d.update(head_dim=16, partial_rotary_factor=0.25,
             linear_conv_kernel_dim=4, linear_num_key_heads=2,
             linear_key_head_dim=16, linear_num_value_heads=4,
             linear_value_head_dim=16, layer_types=layer_types,
             num_experts=0, mlp_only_layers=list(range(4)))
    d.update(over)
    return Qwen3NextConfig(**d)


def test_qwen3_5():
    """Gated-DeltaNet hybrid vs HF Qwen3Next (the released GDN family)."""
    import dataclasses

    from transformers import Qwen3NextForCausalLM
    cfg = tiny_config("qwen3_5", linear_num_key_heads=2)
    cfg = dataclasses.replace(cfg, model_prefix="model")
    check_family(cfg, Qwen3NextForCausalLM, _qwen3_next_hf(),
                 extra_tensors=_qwen3_next_tensors(cfg))


def test_qwen3_5_moe():
    import dataclasses

    from transformers import Qwen3NextForCausalLM
    cfg = tiny_config("qwen3_5_moe", linear_num_key_heads=2,
                      shared_expert_intermediate_size=48)
    cfg = dataclasses.replace(cfg, model_prefix="model")
    hf = _qwen3_next_hf(num_experts=8, num_experts_per_tok=2,
                        moe_intermediate_size=32, norm_topk_prob=True,
                        shared_expert_intermediate_size=48,
                        mlp_only_layers=[])
    check_family(cfg, Qwen3NextForCausalLM, hf,
                 extra_tensors=_qwen3_next_tensors(cfg))


def test_qwen3_moe():
    from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM
    check_family(tiny_config("qwen3_moe"), Qwen3MoeForCausalLM,
                 Qwen3MoeConfig(head_dim=16, num_experts=8,
                                num_experts_per_tok=2,
                                moe_intermediate_size=32, norm_topk_prob=True,
                                decoder_sparse_step=1, mlp_only_layers=[],
                                **_TINY_HF))


def test_jamba():
    """Mamba-1 layers with an attention layer without rope between them vs
    HF Jamba's slow path (no fused kernels), dense FFNs."""
    from transformers import JambaConfig, JambaForCausalLM
    cfg = tiny_config("jamba")
    assert [s.kind for s in cfg.layer_specs()] == ["mamba", "mamba", "full",
                                                   "mamba"]
    hf = JambaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        rms_norm_eps=1e-6, max_position_embeddings=128,
        tie_word_embeddings=True, attn_layer_period=4, attn_layer_offset=2,
        expert_layer_period=2, expert_layer_offset=1, num_experts=1,
        num_experts_per_tok=1, mamba_expand=2, mamba_d_state=8,
        mamba_d_conv=4, mamba_dt_rank=8, mamba_conv_bias=True,
        mamba_proj_bias=False, use_mamba_kernels=False, pad_token_id=0,
        bos_token_id=1, eos_token_id=2)
    check_family(cfg, JambaForCausalLM, hf, allow_missing=("lm_head.weight",))


# ---------------------------------------------------------------------------
# diffusion text encoders (FLUX.1 / SD / SDXL conditioning)
# ---------------------------------------------------------------------------


def _leaf(params, path: str):
    cur = params
    for part in path.split("."):
        cur = cur[int(part)] if part.isdigit() else cur[part]
    return np.asarray(cur, np.float32)


def _hf_tensors_from_mapping(params, mapping: dict) -> dict:
    return {hf_name: _leaf(params, path) for path, hf_name in mapping.items()}


def _rand_pytree(params, seed):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda l: jnp.asarray(rng.normal(0, 0.05, np.shape(l)),
                              jnp.float32), params)


@pytest.mark.parametrize("act,projection", [("quick_gelu", None),
                                            ("gelu", 24)])
def test_clip_text_encoder(act, projection):
    from transformers import CLIPTextConfig as HFCLIPConfig
    from transformers import CLIPTextModel, CLIPTextModelWithProjection

    from cake_tpu.models.text_encoders.clip import (clip_mapping,
                                                    clip_text_forward,
                                                    init_clip_params,
                                                    tiny_clip_config)
    import dataclasses
    cfg = dataclasses.replace(tiny_clip_config(), hidden_act=act,
                              projection_dim=projection)
    params = _rand_pytree(init_clip_params(cfg, jax.random.PRNGKey(0)), 3)
    tensors = _hf_tensors_from_mapping(params, clip_mapping(cfg))
    hf_cfg = HFCLIPConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        intermediate_size=cfg.intermediate_size,
        max_position_embeddings=cfg.max_positions, hidden_act=act,
        eos_token_id=cfg.eot_token_id, bos_token_id=0,
        projection_dim=projection or 512)
    cls = CLIPTextModelWithProjection if projection else CLIPTextModel
    model = load_hf(cls, hf_cfg, tensors,
                    allow_missing=("position_ids",))
    ids = [[5, 17, 2, 44, 80, cfg.eot_token_id, 0, 0]]
    with torch.no_grad():
        out = model(input_ids=torch.tensor(ids), output_hidden_states=True)
    hidden, pooled, penult = clip_text_forward(
        cfg, params, jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        hf_hidden = (out.last_hidden_state if projection is None
                     else model.text_model(torch.tensor(ids)).last_hidden_state)
    assert_close(np.asarray(hidden), hf_hidden.numpy(), "clip hidden")
    assert_close(np.asarray(penult), out.hidden_states[-2].numpy(),
                 "clip penultimate")
    hf_pooled = (out.pooler_output if projection is None
                 else out.text_embeds)
    assert_close(np.asarray(pooled), hf_pooled.detach().numpy(),
                 "clip pooled")


def test_t5_encoder():
    from transformers import T5Config as HFT5Config
    from transformers import T5EncoderModel

    from cake_tpu.models.text_encoders.t5 import (init_t5_params, t5_encode,
                                                  t5_mapping, tiny_t5_config)
    cfg = tiny_t5_config()
    params = _rand_pytree(init_t5_params(cfg, jax.random.PRNGKey(0)), 4)
    tensors = _hf_tensors_from_mapping(params, t5_mapping(cfg))
    hf_cfg = HFT5Config(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        num_layers=cfg.num_layers, num_heads=cfg.num_heads, d_kv=cfg.d_kv,
        d_ff=cfg.d_ff, relative_attention_num_buckets=cfg.relative_buckets,
        relative_attention_max_distance=cfg.relative_max_distance,
        layer_norm_epsilon=cfg.layer_norm_eps, feed_forward_proj="gated-gelu",
        is_encoder_decoder=False, use_cache=False, tie_word_embeddings=False)
    model = load_hf(T5EncoderModel, hf_cfg, tensors,
                    allow_missing=("encoder.embed_tokens.weight",))
    ids = [[5, 17, 2, 44, 80, 9, 1, 0]]
    with torch.no_grad():
        ref = model(input_ids=torch.tensor(ids)).last_hidden_state.numpy()
    ours = np.asarray(t5_encode(cfg, params, jnp.asarray(ids, jnp.int32)),
                      np.float32)
    assert_close(ours, ref, "t5 encoder hidden")
