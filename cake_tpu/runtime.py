"""Runtime facade: turn a model name/dir + flags into a ready generator.

This is the Python analog of the reference's Context bring-up
(ref: cake/mod.rs Context::from_args:112-507 — device pick, HF download,
GGUF/safetensors/quant detection, topology load + auto-shard, partial
weight loading) without the God-object: the facade returns plain objects.
"""
from __future__ import annotations

import json
import logging
import os

import jax
import jax.numpy as jnp

from .models import TextModel, config_from_hf_dict
from .models.common.config import detect_arch
from .utils.dtypes import parse_dtype
from .utils.hub import resolve_model

log = logging.getLogger("cake_tpu.runtime")


class CakeTokenizer:
    """Thin tokenizer wrapper: encode/decode + chat templating with the
    HF chat_template when present, ChatML fallback otherwise
    (ref: models/common/chatml_history.rs)."""

    def __init__(self, model_dir: str):
        self._tok = None
        self._hf = None
        tok_json = os.path.join(model_dir, "tokenizer.json")
        if os.path.exists(tok_json):
            from tokenizers import Tokenizer
            self._tok = Tokenizer.from_file(tok_json)
        cfg_path = os.path.join(model_dir, "tokenizer_config.json")
        self.chat_template = None
        if os.path.exists(cfg_path):
            with open(cfg_path) as f:
                self.chat_template = json.load(f).get("chat_template")
        if self.chat_template:
            try:
                from transformers import AutoTokenizer
                self._hf = AutoTokenizer.from_pretrained(model_dir)
            except Exception as e:
                log.warning("chat template present but AutoTokenizer failed "
                            "(%s); using ChatML fallback", e)

    def encode(self, text: str, add_special_tokens: bool = True) -> list[int]:
        if self._tok is not None:
            return self._tok.encode(
                text, add_special_tokens=add_special_tokens).ids
        if self._hf is not None:
            return self._hf.encode(text,
                                   add_special_tokens=add_special_tokens)
        # tokenizer-less model dir (synthetic checkpoints, smoke drives):
        # accept a whitespace-separated raw token-id prompt
        parts = text.split()
        if parts and all(p.isdigit() for p in parts):
            return [int(p) for p in parts]
        raise RuntimeError(
            "no tokenizer available (pass raw token ids, e.g. '11 23 5')")

    def encode_chat_prompt(self, prompt: str) -> list[int]:
        """Templated chat strings already contain their special tokens —
        don't let the tokenizer post-processor prepend BOS again."""
        return self.encode(prompt,
                           add_special_tokens=not bool(self.chat_template))

    def decode(self, ids) -> str:
        if self._tok is not None:
            return self._tok.decode(list(ids), skip_special_tokens=False)
        if self._hf is not None:
            return self._hf.decode(list(ids))
        return " ".join(str(int(i)) for i in ids)   # tokenizer-less fallback

    def apply_chat(self, messages: list[dict]) -> str:
        if self._hf is not None and self.chat_template:
            return self._hf.apply_chat_template(
                messages, tokenize=False, add_generation_prompt=True)
        from .models.common.text_model import render_chat
        return render_chat(self, messages)


def load_config_and_quant(model_dir: str, arch: str | None = None):
    from .utils.quant import detect_quantization
    gguf_files = [f for f in os.listdir(model_dir) if f.endswith(".gguf")] \
        if os.path.isdir(model_dir) else []
    cfg_path = os.path.join(model_dir, "config.json")
    if os.path.exists(cfg_path):
        with open(cfg_path) as f:
            raw = json.load(f)
        return config_from_hf_dict(raw, arch), detect_quantization(raw), raw
    if gguf_files:
        from .utils.gguf import GgufReader, gguf_config_dict
        raw = gguf_config_dict(GgufReader(os.path.join(model_dir,
                                                       gguf_files[0])))
        from .utils.quant import NoQuantization
        return config_from_hf_dict(raw, arch), NoQuantization(), raw
    raise FileNotFoundError(f"no config.json or .gguf in {model_dir}")


def build_image_model(model: str, dtype: str = "bf16",
                      fp8_native: bool = False):
    """Image generator for the serve path: 'demo:flux' / 'demo:sd' run the
    full pipelines on random weights (zero-egress environments); any other
    value is a release-checkpoint path (FLUX.1 ComfyUI bundle / BFL split
    layout — see models/image/flux_loader; ref: flux1.rs load path)."""
    from .models.image import (Flux2ImageModel, FluxImageModel, SDImageModel,
                               detect_flux2_checkpoint, detect_sd_checkpoint,
                               load_flux2_image_model, load_flux_image_model,
                               load_sd_image_model, tiny_flux2_config,
                               tiny_flux_config, tiny_sd_config)
    if model == "demo:sd":
        return SDImageModel(tiny_sd_config(), dtype=parse_dtype(dtype))
    if model == "demo:flux2":
        return Flux2ImageModel(tiny_flux2_config(), dtype=parse_dtype(dtype))
    if model.startswith("demo:"):
        return FluxImageModel(tiny_flux_config(), dtype=parse_dtype(dtype))
    # local path (dir or single bundle file) passes through; otherwise
    # resolve like text models (hub id -> cached snapshot)
    path = os.path.expanduser(model)
    if not os.path.exists(path):
        path = resolve_model(model)
    flux2_ckpt = detect_flux2_checkpoint(path)
    if flux2_ckpt is not None:
        return load_flux2_image_model(flux2_ckpt, dtype=parse_dtype(dtype))
    if detect_sd_checkpoint(path):
        return load_sd_image_model(path, dtype=parse_dtype(dtype))
    return load_flux_image_model(path, dtype=parse_dtype(dtype),
                                 fp8_native=fp8_native)


def build_audio_model(model: str, dtype: str = "bf16"):
    """TTS generator for the serve path: 'demo:vibevoice' / 'demo:luxtts'
    run on random weights; any other value is a release-checkpoint path
    (VibeVoice HF layout — models/audio/vibevoice_loader)."""
    from .models.audio import (LuxTTS, VibeVoiceTTS,
                               detect_luxtts_checkpoint,
                               detect_vibevoice_checkpoint, load_luxtts,
                               load_vibevoice, tiny_luxtts_config,
                               tiny_tts_config)
    dt = parse_dtype(dtype)
    if model == "demo:luxtts":
        return LuxTTS(tiny_luxtts_config(), dtype=dt)
    if model.startswith("demo"):
        return VibeVoiceTTS(tiny_tts_config(), dtype=dt)
    path = os.path.expanduser(model)
    if not os.path.exists(path):
        path = resolve_model(model)
    if detect_vibevoice_checkpoint(path):
        return load_vibevoice(path, dtype=dt)
    if detect_luxtts_checkpoint(path):
        return load_luxtts(path, dtype=dt)
    raise ValueError(
        f"audio model {model!r}: not a demo: alias and not a recognizable "
        f"VibeVoice or LuxTTS checkpoint directory")


def build_text_model(model: str, dtype: str = "bf16", arch: str | None = None,
                     max_cache_len: int = 2048, seed: int = 42,
                     cluster_key: str | None = None,
                     topology_path: str | None = None,
                     discovery_timeout: float = 3.0,
                     download: bool = True, fp8_native: bool = False,
                     tp: int | str | None = None, sp: int | None = None,
                     min_workers: int = 0, expert_offload: bool = False):
    """Returns (generator, tokenizer, model_id, topology|None).

    With a cluster key: discover workers (or use the topology file), run
    master_setup, return a DistributedTextModel. Otherwise a fully-local
    TextModel (ref: cake-cli run_as_master / all-local fallback
    sharding/mod.rs:209-212).

    tp: in-host tensor parallelism — "auto" uses every local device, an int
    uses that many; weights/KV shard over a {"tp": N} mesh and GSPMD inserts
    the collectives inside the same compiled programs the single-chip path
    runs (the product wiring for parallel/sharding.py; the reference's
    analog is the intra-worker multi-GPU layer split, worker.rs:126-229).
    Applies to the local model and to the master's local stages alike.
    """
    from .parallel import serving_mesh
    if sp and int(sp) > 1 and cluster_key:
        # ring prefill is selected only by the local TextModel; the
        # distributed master's stages would just replicate over the sp
        # axis — sp-times the devices doing redundant work, silently
        log.warning("--sp applies to local serving only; ignoring it for "
                    "the cluster path")
        sp = None
    mesh = serving_mesh(tp, sp=sp)
    model_dir = resolve_model(model, download=download)
    cfg, quant, raw = load_config_and_quant(model_dir, arch)
    if mesh is not None:
        # fail on tp/head indivisibility now, from the config alone —
        # before any multi-GB weight load or worker weight streaming
        from .parallel import check_tp_divisibility
        check_tp_divisibility(cfg, mesh)
    if fp8_native:
        from .utils.quant import Fp8Quantization, fp8_native_quant
        if not isinstance(quant, Fp8Quantization):
            raise ValueError("--fp8-native requires an FP8 checkpoint "
                             f"(detected quantization: {quant.name})")
        quant = fp8_native_quant()
    dt = parse_dtype(dtype)
    tokenizer = CakeTokenizer(model_dir)
    model_id = os.path.basename(model.rstrip("/"))

    workers = []
    if cluster_key:
        from .cluster import discover_workers
        from .cluster.topology import Topology
        if topology_path:
            topo = Topology.from_path(topology_path)
            workers = [{"name": n.name, "host": n.addr[0], "port": n.addr[1],
                        "caps": {"backend": n.backend or "cpu",
                                 "device": n.backend or "cpu",
                                 "memory_bytes": n.memory_bytes,
                                 "tflops": n.tflops}}
                       for n in topo.nodes.values()]
        else:
            workers = discover_workers(cluster_key, timeout=discovery_timeout,
                                       expected=min_workers or None)
        if not workers:
            log.warning("no workers found; running all-local")

    if expert_offload and cluster_key and workers:
        log.warning("--expert-offload applies to local serving only; "
                    "ignoring it for the cluster path")
        expert_offload = False
    if cluster_key and workers:
        from .cluster.master import DistributedTextModel, master_setup
        assignments = None
        if topology_path:
            topo = Topology.from_path(topology_path)
            assignments = {name: n.layer_range
                           for name, n in topo.nodes.items() if n.layer_range}
        setup = master_setup(model_dir, cluster_key, cfg, workers,
                             assignments=assignments, dtype_str=dtype,
                             max_cache_len=max_cache_len,
                             fp8_native=fp8_native, mesh=mesh)
        gen = DistributedTextModel(cfg, setup.master_params, setup.stages,
                                   tokenizer=tokenizer, dtype=dt,
                                   max_cache_len=max_cache_len, seed=seed,
                                   mesh=mesh)
        return gen, tokenizer, model_id, setup.topology

    # fully local
    if expert_offload:
        if not cfg.num_experts:
            raise ValueError("--expert-offload needs an MoE model "
                             f"(arch {cfg.arch} has no experts)")
        if fp8_native:
            # DiskExpertProvider dequants on read; the keep-native fp8
            # marker dicts the resident path streams into fused matmuls
            # have no offloaded consumer
            raise ValueError("--expert-offload and --fp8-native cannot "
                             "combine (offloaded experts dequant on read)")
        if mesh is not None:
            log.warning("--tp/--sp apply to the resident path only; "
                        "ignoring them for --expert-offload serving")
    # with a mesh each tensor goes from the host straight into its shards
    # (a model that needs every chip never fits whole on the first one)
    load_mesh = None if expert_offload else mesh
    gguf_files = [f for f in os.listdir(model_dir) if f.endswith(".gguf")]
    if gguf_files and not any(f.endswith(".safetensors")
                              for f in os.listdir(model_dir)):
        from .utils.gguf import GgufStorage
        from .utils.loaders import ParamLoader
        storage = GgufStorage(os.path.join(model_dir, gguf_files[0]),
                              cfg.model_prefix)
        params = ParamLoader(cfg, storage, dt, quant,
                             expert_offload=expert_offload,
                             mesh=load_mesh).load()
    else:
        from .utils.loaders import load_model_params
        params = load_model_params(cfg, model_dir, dt, quant=quant,
                                   expert_offload=expert_offload,
                                   mesh=load_mesh)
    if expert_offload:
        from .models.common.offload_model import OffloadedTextModel
        gen = OffloadedTextModel(cfg, params, tokenizer=tokenizer, dtype=dt,
                                 seed=seed, max_cache_len=max_cache_len)
        log.info("expert offload: %d experts/layer stream from disk, "
                 "dense trunk resident", cfg.num_experts)
        return gen, tokenizer, model_id, None
    gen = TextModel(cfg, params, tokenizer=tokenizer, dtype=dt, seed=seed,
                    max_cache_len=max_cache_len, mesh=mesh)
    return gen, tokenizer, model_id, None
