"""Image + audio pipeline tests on tiny configs: schedulers vs references,
MMDiT shape/semantics, VAE decode, full generate_image/generate_speech."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.audio import (LuxTTS, VibeVoiceTTS, tiny_luxtts_config,
                                   tiny_tts_config)
from cake_tpu.models.image import (FluxImageModel, tiny_flux_config)
from cake_tpu.models.image.mmdit import (init_mmdit_params, make_img_ids,
                                         make_txt_ids, mmdit_forward,
                                         timestep_embedding)
from cake_tpu.models.image.vae import (latents_to_patches, patches_to_latents)
from cake_tpu.ops.diffusion import (DpmSolverPP, cfg_combine,
                                    flow_matching_euler_step,
                                    flow_matching_schedule)
from cake_tpu.utils.wav import decode_wav, encode_wav


@pytest.fixture
def rng():
    """This file's own generator, fresh for every test: the session's
    (conftest.py) hands out draws that depend on which files ran before in
    the worker, and `test_mmdit_forward_shapes_and_conditioning` compares
    two random conditionings against a threshold some draws fall under."""
    return np.random.default_rng(42)


# ------------------------------------------------------------- schedulers

def test_flow_matching_schedule():
    ts = flow_matching_schedule(10)
    assert ts[0] == 1.0 and ts[-1] == 0.0 and len(ts) == 11
    assert np.all(np.diff(ts) < 0)
    shifted = flow_matching_schedule(10, shift_mu=1.15)
    assert shifted[0] > 0.99 and shifted[-1] == 0.0   # shift keeps endpoints
    # mid steps pushed toward 1 (more steps at high noise)
    assert shifted[5] > ts[5]


def test_euler_step_integrates_linear_flow():
    """With the exact constant velocity v = x1 - x0, Euler recovers x0."""
    rng = np.random.default_rng(0)
    x1 = jnp.asarray(rng.standard_normal((2, 8)))    # noise at t=1
    x0 = jnp.asarray(rng.standard_normal((2, 8)))    # data at t=0
    v = x1 - x0                                       # d x_t / dt for lerp path
    ts = flow_matching_schedule(5)
    x = x1
    for i in range(5):
        x = flow_matching_euler_step(x, v, ts[i], ts[i + 1])
    np.testing.assert_allclose(np.asarray(x), np.asarray(x0), atol=1e-5)


def test_dpm_solver_denoises_toward_x0():
    """v-prediction with the TRUE v at each step must recover x0 closely."""
    rng = np.random.default_rng(1)
    x0 = jnp.asarray(rng.standard_normal((4,)), jnp.float32)
    sch = DpmSolverPP.from_betas()
    ts = sch.timesteps(10)
    a0 = float(sch.alphas_cumprod[ts[0]])
    eps = jnp.asarray(rng.standard_normal(4), jnp.float32)
    x = (a0 ** 0.5) * x0 + ((1 - a0) ** 0.5) * eps
    for j, t in enumerate(ts):
        a = float(sch.alphas_cumprod[int(t)])
        alpha_t, sigma_t = a ** 0.5, (1 - a) ** 0.5
        # true eps for current x given x0: eps_t = (x - alpha*x0)/sigma
        eps_t = (x - alpha_t * x0) / max(sigma_t, 1e-8)
        # v-parameterization: v = alpha_t * eps - sigma_t * x0
        v_true = alpha_t * eps_t - sigma_t * x0
        t_next = int(ts[j + 1]) if j + 1 < len(ts) else 0
        x = sch.step(v_true, int(t), t_next, x)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x0), atol=0.05)


def test_cfg_combine():
    u, c = jnp.asarray([1.0]), jnp.asarray([2.0])
    assert float(cfg_combine(u, c, 1.0)[0]) == 2.0
    assert float(cfg_combine(u, c, 0.0)[0]) == 1.0
    assert float(cfg_combine(u, c, 2.0)[0]) == 3.0


# ------------------------------------------------------------------ mmdit

def test_patchify_roundtrip(rng):
    z = jnp.asarray(rng.standard_normal((2, 4, 8, 12)), jnp.float32)
    p = latents_to_patches(z)
    assert p.shape == (2, 4 * 6, 16)
    back = patches_to_latents(p, 8, 12)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(z))


def test_timestep_embedding_distinct():
    e = timestep_embedding(jnp.asarray([0.0, 0.5, 1.0]), 64)
    assert e.shape == (3, 64)
    assert not np.allclose(e[0], e[1])


def test_mmdit_forward_shapes_and_conditioning(rng):
    cfg = tiny_flux_config().mmdit
    params = init_mmdit_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    img = jnp.asarray(rng.standard_normal((1, 24, cfg.in_channels)), jnp.float32)
    txt = jnp.asarray(rng.standard_normal((1, 8, cfg.txt_dim)), jnp.float32)
    vec = jnp.asarray(rng.standard_normal((1, cfg.vec_dim)), jnp.float32)
    img_ids = make_img_ids(4, 6)
    txt_ids = make_txt_ids(8)
    t = jnp.asarray([0.5], jnp.float32)
    g = jnp.asarray([3.5], jnp.float32)
    v1 = mmdit_forward(cfg, params, img, img_ids, txt, txt_ids, t, vec, g)
    assert v1.shape == img.shape
    assert bool(jnp.all(jnp.isfinite(v1)))
    # conditioning matters: different text -> different velocity
    # (NB: scaling txt is ~invisible — FLUX LayerNorms are affine-free and
    # scale-invariant — so perturb direction, not magnitude)
    txt_b = jnp.asarray(rng.standard_normal((1, 8, cfg.txt_dim)), jnp.float32)
    v2 = mmdit_forward(cfg, params, img, img_ids, txt_b, txt_ids,
                       t, vec, g)
    assert not np.allclose(np.asarray(v1), np.asarray(v2), atol=1e-4)
    # timestep matters
    v3 = mmdit_forward(cfg, params, img, img_ids, txt, txt_ids,
                       jnp.asarray([0.9], jnp.float32), vec, g)
    assert not np.allclose(np.asarray(v1), np.asarray(v3), atol=1e-4)


# --------------------------------------------------------------- pipelines

def test_flux_generate_image():
    model = FluxImageModel(tiny_flux_config(), dtype=jnp.float32)
    steps_seen = []
    img = model.generate_image("a tiny cake", width=64, height=64, steps=3,
                               seed=1, on_step=lambda i, n: steps_seen.append(i))
    assert img.size == (64, 64)
    assert steps_seen == [1, 2, 3]
    # determinism
    img2 = model.generate_image("a tiny cake", width=64, height=64, steps=3,
                                seed=1)
    np.testing.assert_array_equal(np.asarray(img), np.asarray(img2))
    # different prompt -> different image (text conditioning reaches output)
    img3 = model.generate_image("a dragon", width=64, height=64, steps=3,
                                seed=1)
    assert not np.array_equal(np.asarray(img), np.asarray(img3))


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_vibevoice_generate_speech():
    tts = VibeVoiceTTS(tiny_tts_config(), dtype=jnp.float32, max_frames=6)
    frames = []
    audio = tts.generate_speech("hello there", max_frames=4,
                                on_frame=frames.append)
    hop = 16  # 4*4 upsample
    assert len(audio.samples) == len(frames) * hop
    assert np.all(np.abs(audio.samples) <= 1.0)
    wav = audio.wav_bytes()
    assert wav[:4] == b"RIFF"
    samples, rate = decode_wav(wav)
    assert rate == tts.cfg.sample_rate
    np.testing.assert_allclose(samples, audio.samples, atol=1e-3)
    assert len(audio.pcm_bytes()) == 2 * len(audio.samples)


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_vibevoice_voice_prompt_changes_output():
    tts = VibeVoiceTTS(tiny_tts_config(), dtype=jnp.float32, max_frames=4)
    a = tts.generate_speech("hi", max_frames=3)
    voice = encode_wav(np.sin(np.linspace(0, 100, 4000)).astype(np.float32))
    b = tts.generate_speech("hi", voice_wav=voice, max_frames=3)
    assert not np.allclose(a.samples, b.samples)


def test_luxtts_generate_speech():
    tts = LuxTTS(tiny_luxtts_config(), dtype=jnp.float32)
    audio = tts.generate_speech("hello world")
    assert len(audio.samples) > 0
    assert np.all(np.abs(audio.samples) <= 1.0)
    # deterministic per (text, seed)
    audio2 = tts.generate_speech("hello world")
    np.testing.assert_array_equal(audio.samples, audio2.samples)


def test_wav_roundtrip(rng):
    s = np.clip(rng.standard_normal(1000) * 0.3, -1, 1).astype(np.float32)
    wav = encode_wav(s, 16000)
    back, rate = decode_wav(wav)
    assert rate == 16000
    np.testing.assert_allclose(back, s, atol=1e-4)


# ------------------------------------------------------------------- sd

@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_sd_unet_shapes_and_conditioning(rng):
    from cake_tpu.models.image.sd import (init_unet_params, tiny_sd_config,
                                          unet_forward)
    cfg = tiny_sd_config().unet
    p = init_unet_params(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(rng.standard_normal((1, 4, 16, 16)), jnp.float32)
    ctx = jnp.asarray(rng.standard_normal((1, 8, cfg.context_dim)), jnp.float32)
    t = jnp.asarray([0.5], jnp.float32)
    e1 = unet_forward(cfg, p, x, t, ctx)
    assert e1.shape == x.shape and bool(jnp.all(jnp.isfinite(e1)))
    ctx2 = jnp.asarray(rng.standard_normal((1, 8, cfg.context_dim)), jnp.float32)
    e2 = unet_forward(cfg, p, x, t, ctx2)
    assert not np.allclose(np.asarray(e1), np.asarray(e2), atol=1e-5)
    e3 = unet_forward(cfg, p, x, jnp.asarray([0.9], jnp.float32), ctx)
    assert not np.allclose(np.asarray(e1), np.asarray(e3), atol=1e-5)


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_sd_generate_and_img2img():
    from cake_tpu.models.image.sd import SDImageModel, tiny_sd_config
    model = SDImageModel(tiny_sd_config())
    img = model.generate_image("a fox", width=32, height=32, steps=3, seed=4)
    assert img.size == (32, 32)
    img_b = model.generate_image("a fox", width=32, height=32, steps=3, seed=4)
    np.testing.assert_array_equal(np.asarray(img), np.asarray(img_b))
    # negative prompt changes the output (CFG path)
    img_n = model.generate_image("a fox", width=32, height=32, steps=3, seed=4,
                                 negative_prompt="blurry")
    assert not np.array_equal(np.asarray(img), np.asarray(img_n))
    # img2img from a given latent differs from txt2img
    z0 = np.random.default_rng(0).standard_normal((1, 4, 16, 16)).astype("f")
    img_i = model.generate_image("a fox", width=32, height=32, steps=4, seed=4,
                                 init_image=z0, strength=0.5)
    assert not np.array_equal(np.asarray(img), np.asarray(img_i))


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_sd_intermediate_images_and_trace(tmp_path):
    """intermediate_every decodes in-progress images through on_image
    (ref: sd.rs:526-529 intermediary_images) and trace_dir writes a JAX
    profiler trace (the --sd-tracing analog)."""
    from cake_tpu.models.image.sd import SDImageModel, tiny_sd_config
    model = SDImageModel(tiny_sd_config())
    seen = []
    img = model.generate_image("a fox", width=32, height=32, steps=4, seed=1,
                               intermediate_every=2,
                               on_image=lambda step, pil: seen.append(
                                   (step, pil.size)),
                               trace_dir=str(tmp_path / "trace"))
    assert seen == [(2, (32, 32))]       # step 4 is the final image
    assert img.size == (32, 32)
    trace_files = list((tmp_path / "trace").rglob("*"))
    assert trace_files, "profiler trace directory is empty"
    # final image identical to a run without intermediates
    img_plain = model.generate_image("a fox", width=32, height=32, steps=4,
                                     seed=1)
    np.testing.assert_array_equal(np.asarray(img), np.asarray(img_plain))


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_vibevoice_clone_prefill_bucketed():
    """Voice-clone conditioning pads the reference to 8-frame buckets so
    the jitted LM prefill compiles per bucket, not per clip length — and
    two different clip lengths inside one bucket produce caches advanced
    by their true frame counts."""
    import jax.numpy as jnp

    from cake_tpu.models.audio.vibevoice import (VibeVoiceTTS,
                                                 tiny_tts_config)
    from cake_tpu.utils.wav import encode_wav

    cfg = tiny_tts_config()
    m = VibeVoiceTTS(cfg, dtype=jnp.float32, max_frames=4)
    sr = cfg.sample_rate
    rng = np.random.default_rng(0)
    for n_hops in (3, 5):    # both inside the same 8-hop encoder bucket
        wav = encode_wav(rng.standard_normal(cfg.hop * n_hops)
                         .astype(np.float32) * 0.1, sr)
        audio = m.generate_speech("hi there", voice_wav=wav, seed=0,
                                  max_frames=2)
        assert np.isfinite(audio.samples).all()


def test_resample_antialias_removes_above_band():
    """48kHz reference with a 20kHz tone: after the low-pass + decimate to
    24kHz, the aliased image (4kHz) must be strongly attenuated vs naive
    linear decimation."""
    import jax.numpy as jnp

    from cake_tpu.models.audio.vibevoice import VibeVoiceTTS, tiny_tts_config
    from cake_tpu.utils.wav import encode_wav

    cfg = tiny_tts_config()
    m = VibeVoiceTTS(cfg, dtype=jnp.float32, max_frames=2)
    sr_in = 48000
    t = np.arange(sr_in) / sr_in
    tone = np.sin(2 * np.pi * 20000 * t).astype(np.float32)

    captured = {}
    orig = m.encode_voice_reference

    def spy(samples):
        captured["samples"] = np.asarray(samples)
        return orig(samples)

    m.encode_voice_reference = spy
    m._voice_embeds(encode_wav(tone, sr_in))
    res = captured["samples"]
    # alias image of 20kHz at 24kHz output = 4kHz; measure its energy
    spec = np.abs(np.fft.rfft(res))
    freqs = np.fft.rfftfreq(len(res), 1 / cfg.sample_rate)
    band = spec[(freqs > 3500) & (freqs < 4500)].max()
    assert band < 0.05 * len(res) / 2, band


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_pipelines_run_in_bf16():
    """serve default dtype: the whole image path must not promote to f32
    (regression: np-scalar coefficients promoted bf16 latents)."""
    from cake_tpu.models.image import FluxImageModel, tiny_flux_config
    from cake_tpu.models.image.sd import SDImageModel, tiny_sd_config
    img = FluxImageModel(tiny_flux_config(), dtype=jnp.bfloat16).generate_image(
        "x", width=32, height=32, steps=2)
    assert img.size == (32, 32)
    img2 = SDImageModel(tiny_sd_config(), dtype=jnp.bfloat16).generate_image(
        "x", width=32, height=32, steps=2)
    assert img2.size == (32, 32)
    # the actual promotion guard: scheduler steps must PRESERVE bf16
    from cake_tpu.ops.diffusion import (DpmSolverPP,
                                        flow_matching_euler_step,
                                        flow_matching_schedule)
    x = jnp.ones((2, 4), jnp.bfloat16)
    sch = DpmSolverPP.from_betas(prediction_type="epsilon")
    ts = sch.timesteps(4)
    out = sch.step(jnp.zeros_like(x), int(ts[0]), int(ts[1]), x)
    assert out.dtype == jnp.bfloat16
    fm = flow_matching_schedule(4)
    out2 = flow_matching_euler_step(x, jnp.zeros_like(x),
                                    float(fm[0]), float(fm[1]))
    assert out2.dtype == jnp.bfloat16


def test_vae_encoder_img2img_from_pixels():
    """vae_encode: [H,W,3] pixels -> scheduler-space latent at H/8 with
    finite values, and the full img2img pipeline runs from it (the CLI
    --init-image path); posterior sampling differs from the mode."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from cake_tpu.models.image.sd import SDImageModel, tiny_sd_config

    m = SDImageModel(tiny_sd_config(), dtype=jnp.float32)
    px = np.random.default_rng(0).integers(0, 256, (64, 64, 3),
                                           dtype=np.uint8)
    z0 = m.encode_image(px)
    lc = m.cfg.vae.latent_channels
    f = 2 ** (len(m.cfg.vae.channel_mults) - 1)   # /8 on real SD (4 levels)
    assert z0.shape == (1, lc, 64 // f, 64 // f)
    assert np.isfinite(np.asarray(z0)).all()

    zs = m.encode_image(px, rng=jax.random.PRNGKey(1))
    assert not np.allclose(np.asarray(zs), np.asarray(z0))

    img = m.generate_image("x", width=64, height=64, steps=2,
                           init_image=z0, strength=0.5, seed=3)
    assert img.size == (64, 64)
