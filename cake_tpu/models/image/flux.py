"""FLUX.1 image generation pipeline: text encode -> flow-matching denoise ->
VAE decode (ref: models/flux/{flux1.rs,flux1_model.rs};
call stack SURVEY §3.4). FLUX.2-klein lives in flux2.py (shared-modulation
transformer, Qwen3 encoder, 32-ch VAE).

Component sharding names mirror the reference's FluxShardable routing
("flux_text_encoder" | "flux_transformer" | "flux_vae" —
ref: flux/flux_shardable.rs:29-35): each component can be resident or a
RemoteStage-like forwarder, so image models shard at component granularity
over the cluster rather than per layer.

FLUX.1-dev uses CLIP-L pooled + T5-XXL sequence embeddings — text encoders
are pluggable callables here.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.diffusion import (flow_matching_euler_step, flow_matching_schedule)
from .mmdit import (MMDiTConfig, init_mmdit_params, make_img_ids,
                    make_txt_ids, mmdit_forward)
from .vae import (VaeConfig, init_vae_decoder_params, latents_to_patches,
                  patches_to_latents, vae_decode)

log = logging.getLogger("cake_tpu.flux")

COMPONENT_NAMES = ("flux_text_encoder", "flux_transformer", "flux_vae")


@dataclasses.dataclass(frozen=True)
class FluxPipelineConfig:
    mmdit: MMDiTConfig = MMDiTConfig()
    vae: VaeConfig = VaeConfig()
    guidance_default: float = 3.5
    shift_mu: float = 1.15           # resolution timestep shift


def tiny_flux_config() -> FluxPipelineConfig:
    """Test-scale config (mirrors the tiny text fixtures)."""
    return FluxPipelineConfig(
        # txt_dim/vec_dim line up with tiny_t5_config.d_model and
        # tiny_clip_config.hidden_size so the tiny encoder stack plugs in
        mmdit=MMDiTConfig(in_channels=16, hidden_size=64, num_heads=4,
                          head_dim=16, depth_double=2, depth_single=2,
                          txt_dim=32, vec_dim=32,
                          axes_dims=(4, 6, 6)),
        vae=VaeConfig(latent_channels=4, base_channels=32,
                      channel_mults=(1, 2), num_res_blocks=1),
    )


class DummyTextEncoder:
    """Deterministic hash-based embeddings — lets the full pipeline run
    without encoder weights (tests, random-weight smokes)."""

    def __init__(self, txt_dim: int, vec_dim: int, seq_len: int = 16):
        self.txt_dim, self.vec_dim, self.seq_len = txt_dim, vec_dim, seq_len

    def __call__(self, prompt: str):
        import zlib
        seed = zlib.crc32(prompt.encode())  # stable across processes
        k = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(k)
        txt = jax.random.normal(k1, (1, self.seq_len, self.txt_dim))
        vec = jax.random.normal(k2, (1, self.vec_dim))
        return txt, vec


class FluxImageModel:
    """ImageGenerator facade (ref: Generator/ImageGenerator traits,
    models/mod.rs:89-225). generate_image returns a PIL Image."""

    def __init__(self, cfg: FluxPipelineConfig, params: dict | None = None,
                 text_encoder=None, dtype=jnp.float32, seed: int = 42):
        self.cfg = cfg
        self.dtype = dtype
        if params is None:
            k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
            params = {
                "transformer": init_mmdit_params(cfg.mmdit, k1, dtype),
                "vae": init_vae_decoder_params(cfg.vae, k2, dtype),
            }
        self.params = params
        self.text_encoder = text_encoder or DummyTextEncoder(
            cfg.mmdit.txt_dim, cfg.mmdit.vec_dim)

        mmdit_cfg = cfg.mmdit

        @jax.jit
        def _velocity(tp, img, img_ids, txt, txt_ids, t, vec, guidance):
            return mmdit_forward(mmdit_cfg, tp, img, img_ids, txt, txt_ids,
                                 t, vec, guidance)

        vae_cfg = cfg.vae

        @jax.jit
        def _decode(vp, z):
            return vae_decode(vae_cfg, vp, z)

        self._velocity = _velocity
        self._decode = _decode

    # -- generation ---------------------------------------------------------

    def generate_image(self, prompt: str, width: int = 1024,
                       height: int = 1024, steps: int = 20,
                       guidance: float | None = None, seed: int | None = None,
                       negative_prompt: str | None = None,
                       on_step=None):
        del negative_prompt        # FLUX-dev: guidance-distilled, no negative
        cfg = self.cfg
        lc = cfg.vae.latent_channels
        # spatial factor = one 2x upsample per channel-mult step (8 for the
        # standard (1,2,4,4) decoder)
        factor = 2 ** (len(cfg.vae.channel_mults) - 1)
        # round latent dims UP (even, for 2x2 patching) and crop the decoded
        # image to the exact requested size — never return a smaller image
        lh = -(-height // factor)
        lw = -(-width // factor)
        lh += lh % 2
        lw += lw % 2
        rng = jax.random.PRNGKey(seed if seed is not None else 0)
        z = jax.random.normal(rng, (1, lc, lh, lw), self.dtype)

        txt, vec = self.text_encoder(prompt)
        txt = jnp.asarray(txt, self.dtype)
        vec = jnp.asarray(vec, self.dtype)
        img = latents_to_patches(z)
        img_ids = make_img_ids(lh // 2, lw // 2)
        txt_ids = make_txt_ids(txt.shape[1])
        g = jnp.asarray([cfg.guidance_default if guidance is None
                         else guidance], jnp.float32)

        ts = flow_matching_schedule(steps, cfg.shift_mu)
        t_start = time.monotonic()
        for i in range(steps):
            t = jnp.asarray([ts[i]], jnp.float32)
            v = self._velocity(self.params["transformer"], img, img_ids, txt,
                               txt_ids, t, vec, g)
            # python-float step sizes: np.float32 scalars would promote
            # bf16 latents to f32 mid-loop
            img = flow_matching_euler_step(img, v, float(ts[i]),
                                           float(ts[i + 1]))
            if on_step:
                on_step(i + 1, steps)
        log.info("denoise: %d steps in %.1fs", steps,
                 time.monotonic() - t_start)

        z = patches_to_latents(img, lh, lw)
        image = self._decode(self.params["vae"], z)
        return to_pil(np.asarray(image[0, :, :height, :width]))


def to_pil(chw: np.ndarray):
    """[-1,1] CHW float -> PIL Image."""
    from PIL import Image
    arr = np.clip((chw.transpose(1, 2, 0) + 1.0) * 127.5, 0, 255).astype(np.uint8)
    return Image.fromarray(arr)
