"""Command-line interface (ref: cake-cli/src/main.rs:23-93 — subcommands
run | serve | pull | list | chat | rm | split | worker).

    cake-tpu run Qwen/Qwen3-0.6B "hello"          one-shot generation
    cake-tpu run MODEL --cluster-key K            distributed master
    cake-tpu worker --name w0 --cluster-key K     worker node
    cake-tpu serve MODEL [--port 8000]            OpenAI-compatible API + UI
    cake-tpu chat MODEL | --api URL               terminal chat
    cake-tpu top [--api URL]                      live fleet dashboard
    cake-tpu pull/list/rm                          model cache management
    cake-tpu split MODEL TOPOLOGY OUT             per-worker weight bundles
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

from . import knobs


def _add_common_model_args(p: argparse.ArgumentParser):
    p.add_argument("model", help="model dir or HF repo id")
    p.add_argument("--dtype", default="bf16", help="bf16|f16|f32")
    p.add_argument("--arch", default=None,
                   help="force architecture (e.g. qwen3, llama3)")
    p.add_argument("--max-cache-len", type=int, default=2048)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--cluster-key", default=knobs.get("CAKE_CLUSTER_KEY"),
                   help="enable distributed mode (env: CAKE_CLUSTER_KEY)")
    p.add_argument("--topology", default=None, help="topology YAML path")
    p.add_argument("--no-download", action="store_true")
    p.add_argument("--fp8-native", action="store_true",
                   help="keep FP8 weights 1 byte/param in HBM, dequant "
                        "per layer (FP8 checkpoints only)")
    p.add_argument("--tp", default=None,
                   help="in-host tensor parallelism: 'auto' shards over all "
                        "local devices, N over the first N (default: 1 chip)")
    p.add_argument("--sp", type=int, default=None,
                   help="in-host sequence parallelism: shard long-prompt "
                        "prefill over N devices via ring attention "
                        "(composes with --tp; tp*sp devices are used)")
    p.add_argument("--expert-offload", action="store_true",
                   help="MoE: stream experts from disk instead of holding "
                        "them in HBM (capacity over throughput; serves "
                        "models whose expert banks exceed device memory)")
    p.add_argument("--discovery-timeout", type=float, default=3.0,
                   help="seconds to wait for UDP worker discovery")
    p.add_argument("--min-workers", type=int, default=0,
                   help="stop discovery as soon as this many workers "
                        "replied (0 = wait the full timeout)")


def _add_sampling_args(p: argparse.ArgumentParser):
    p.add_argument("--max-tokens", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.7)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--repeat-penalty", type=float, default=1.0)
    p.add_argument("--repeat-last-n", type=int, default=64,
                   help="window the repeat penalty looks back over")
    p.add_argument("--system-prompt", default=None,
                   help="system message for the chat template (the "
                        "reference defaults to 'You are a helpful AI "
                        "assistant.'; here omitted unless given)")


def _sampling(args):
    from .ops.sampling import SamplingConfig
    return SamplingConfig(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p,
                          repeat_penalty=args.repeat_penalty,
                          repeat_last_n=args.repeat_last_n)


def _messages(args, prompt: str) -> list[dict]:
    msgs = []
    if getattr(args, "system_prompt", None):
        msgs.append({"role": "system", "content": args.system_prompt})
    msgs.append({"role": "user", "content": prompt})
    return msgs


def _build(args):
    from .runtime import build_text_model
    return build_text_model(
        args.model, dtype=args.dtype, arch=args.arch,
        max_cache_len=args.max_cache_len, seed=args.seed,
        cluster_key=args.cluster_key, topology_path=args.topology,
        download=not args.no_download,
        fp8_native=getattr(args, "fp8_native", False),
        tp=getattr(args, "tp", None), sp=getattr(args, "sp", None),
        discovery_timeout=getattr(args, "discovery_timeout", 3.0),
        min_workers=getattr(args, "min_workers", 0),
        expert_offload=getattr(args, "expert_offload", False))


def cmd_run(args) -> int:
    gen, tokenizer, model_id, _ = _build(args)
    prompt = args.prompt or "Hello"
    if args.raw:
        ids = tokenizer.encode(prompt)
        _, stats = gen.generate(ids, max_new_tokens=args.max_tokens,
                                sampling=_sampling(args),
                                on_token=_print_token)
    else:
        _, stats = gen.chat_generate(
            _messages(args, prompt),
            max_new_tokens=args.max_tokens, sampling=_sampling(args),
            on_token=_print_token)
    print()
    print(f"[{stats['decode_tokens']} tokens, {stats['tok_per_s']:.1f} tok/s, "
          f"ttft {stats['ttft_s'] * 1000:.0f} ms]", file=sys.stderr)
    return 0


def _print_token(tok):
    if tok.text and not tok.is_end_of_stream:
        print(tok.text, end="", flush=True)


def cmd_image(args) -> int:
    """One-shot image generation to a PNG (ref: `cake run --model-type
    image-model --image-output out.png`; here a dedicated subcommand)."""
    from .runtime import build_image_model
    model = build_image_model(args.model, dtype=args.dtype,
                              fp8_native=getattr(args, "fp8_native", False))
    kwargs = dict(width=args.width, height=args.height, seed=args.seed)
    if args.steps is not None:
        kwargs["steps"] = args.steps
    if args.guidance is not None:
        kwargs["guidance"] = args.guidance
    if args.negative_prompt is not None:
        kwargs["negative_prompt"] = args.negative_prompt
    if args.init_image:
        # img2img (ref: --sd-img2img FILE + --sd-img2img-strength)
        if not hasattr(model, "init_latent_from"):
            raise SystemExit("--init-image needs an SD model (FLUX is "
                             "guidance-distilled text-to-image only)")
        from PIL import Image
        try:
            kwargs["init_image"] = model.init_latent_from(
                Image.open(args.init_image), args.width, args.height)
        except ValueError as e:
            raise SystemExit(str(e))
        kwargs["strength"] = args.strength
    t0 = time.monotonic()
    image = model.generate_image(args.prompt, **kwargs)
    image.save(args.out, format="PNG")
    print(f"[{args.out}: {args.width}x{args.height} in "
          f"{time.monotonic() - t0:.1f}s]", file=sys.stderr)
    return 0


def cmd_tts(args) -> int:
    """One-shot TTS to a WAV (ref: `cake run --model-type audio-model
    --audio-output output.wav`; here a dedicated subcommand)."""
    from .runtime import build_audio_model
    model = build_audio_model(args.model, dtype=args.dtype)
    voice_wav = None
    if args.voice_wav:
        with open(args.voice_wav, "rb") as f:
            voice_wav = f.read()
    kwargs = dict(voice=args.voice, voice_wav=voice_wav, seed=args.seed)
    if args.frames is not None:
        kwargs["max_frames"] = args.frames
    if args.steps is not None:
        kwargs["steps"] = args.steps
    if args.cfg_scale is not None:
        kwargs["cfg_scale"] = args.cfg_scale
    t0 = time.monotonic()
    audio = model.generate_speech(args.text, **kwargs)
    with open(args.out, "wb") as f:
        f.write(audio.wav_bytes())
    print(f"[{args.out}: {time.monotonic() - t0:.1f}s]", file=sys.stderr)
    return 0


def cmd_serve(args) -> int:
    from .api import ApiState, serve
    gen, tokenizer, model_id, topo = _build(args)
    image_model = audio_model = None
    if args.image_model:
        from .runtime import build_image_model
        image_model = build_image_model(
            args.image_model, dtype=args.dtype,
            fp8_native=getattr(args, "fp8_native", False))
    if args.audio_model:
        from .runtime import build_audio_model
        audio_model = build_audio_model(args.audio_model, dtype=args.dtype)
    layer_tensors = None
    try:
        # resolve the same way _build did (repo id -> cached snapshot dir)
        from .api.ui import layer_tensor_details
        from .utils.hub import resolve_model
        layer_tensors = layer_tensor_details(
            resolve_model(os.path.expanduser(args.model), download=False))
    except Exception:
        pass        # GGUF-only dirs / unresolved ids: UI shows no detail
    state = ApiState(model=gen, tokenizer=tokenizer, model_id=model_id,
                     topology=topo, image_model=image_model,
                     audio_model=audio_model, voices_dir=args.voices_dir,
                     layer_tensors=layer_tensors,
                     sd_intermediate_every=args.sd_intermediate_every,
                     sd_trace_dir=args.sd_trace_dir)
    # continuous batching for plain local TextModels (CAKE_SERVE_SLOTS
    # slots, CAKE_MAX_QUEUE admission bound, CAKE_SERVE_CTX per-slot
    # context; CAKE_SERVE_SLOTS=0 disables). Distributed/offload models
    # return None here and keep the locked one-at-a-time path.
    from .serve import maybe_engine
    state.engine = maybe_engine(gen)
    if state.engine is not None:
        print(f"[serve engine: {state.engine.slots} slots x "
              f"{state.engine.ctx} ctx, queue {state.engine.queue.maxsize}]",
              file=sys.stderr)
    # unified admission plane: QoS classes + tenant quotas for every
    # endpoint, heavy-job executor for images/audio (worker threads
    # start on the first job). Created eagerly so /health carries the
    # admission block from boot and SIGTERM drain covers job lanes.
    from .serve.admission import get_plane
    plane = get_plane(state)
    print(f"[admission plane: {plane.jobs.workers} job worker(s), "
          f"tenants={'on' if plane.tenants.policies else 'open'}]",
          file=sys.stderr)
    advertiser = None
    if args.announce:
        # announce this replica over the cluster discovery/PSK plumbing
        # so a fleet router (`cake route --cluster-key K`) finds it: same
        # UDP protocol as workers, caps tagged role=serve so routers and
        # masters never confuse the two populations
        key = args.cluster_key or knobs.get("CAKE_CLUSTER_KEY")
        if not key:
            print("error: --announce needs --cluster-key "
                  "(or CAKE_CLUSTER_KEY)", file=sys.stderr)
            return 2
        from .cluster.discovery import WorkerAdvertiser, detect_capabilities
        caps = {**detect_capabilities(), "role": "serve"}
        advertiser = WorkerAdvertiser(args.announce_name or os.uname().nodename,
                                      key, args.port, caps=caps).start()
        print(f"[announcing replica {advertiser.name} on UDP discovery]",
              file=sys.stderr)
    try:
        serve(state, host=args.host, port=args.port,
              basic_auth=args.basic_auth)
    finally:
        if advertiser is not None:
            advertiser.stop()
    return 0


def cmd_route(args) -> int:
    """Fleet router: front N `cake serve` replicas with health-driven
    membership, prefix-affinity failover and router-level 429s."""
    replicas = []
    for spec in args.replica or []:
        name, sep, url = spec.partition("=")
        if not sep:
            url = spec
            name = spec.split("//")[-1].replace(":", "-").replace("/", "")
        if "://" not in url:
            url = "http://" + url
        replicas.append((name, url))
    key = args.cluster_key or knobs.get("CAKE_CLUSTER_KEY")
    scaling = bool(args.autoscale or knobs.get("CAKE_SCALE"))
    if not replicas and not key and not (scaling and
                                         knobs.get_str("CAKE_SCALE_SPAWN_CMD")):
        print("error: need --replica host:port entries, --cluster-key "
              "for UDP discovery, or --autoscale with CAKE_SCALE_SPAWN_CMD "
              "to bootstrap an empty fleet", file=sys.stderr)
        return 2
    from .fleet import serve_router
    serve_router(replicas, host=args.host, port=args.port, cluster_key=key,
                 autoscale=True if args.autoscale else None)
    return 0


def cmd_top(args) -> int:
    """Live fleet dashboard: render the router's telemetry rollup
    (burn rates, headroom, per-replica SLO rows) in the terminal."""
    from .fleet.top import run_top
    url = args.api
    if "://" not in url:
        url = "http://" + url
    return run_top(url, interval_s=args.interval, once=args.once,
                   plain=args.plain, timeout_s=args.timeout)


def cmd_worker(args) -> int:
    from .cluster import run_worker
    if not args.cluster_key:
        print("error: --cluster-key (or CAKE_CLUSTER_KEY) required",
              file=sys.stderr)
        return 2
    run_worker(args.name, args.cluster_key, port=args.port,
               model_dir=args.model_dir, tp=args.tp)
    return 0


def cmd_pull(args) -> int:
    from .utils.hub import pull
    path = pull(args.repo)
    print(path)
    return 0


def cmd_list(args) -> int:
    from .utils.models import list_models
    rows = list_models()
    if not rows:
        print("no cached models")
        return 0
    w = max(len(m.repo_id) for m in rows) + 2
    for m in rows:
        status = "complete" if m.complete else "PARTIAL"
        print(f"{m.repo_id:<{w}} {m.source:<5} {m.size_bytes / 1e9:7.2f} GB  "
              f"{status}")
    return 0


def cmd_rm(args) -> int:
    from .utils.models import delete_model
    if delete_model(args.repo):
        print(f"removed {args.repo}")
        return 0
    print(f"{args.repo} not found", file=sys.stderr)
    return 1


def cmd_split(args) -> int:
    from .cluster.topology import Topology
    from .runtime import load_config_and_quant
    from .utils.hub import resolve_model
    from .utils.split import split_model
    model_dir = resolve_model(args.model, download=not args.no_download)
    cfg, _, _ = load_config_and_quant(model_dir)
    topo = Topology.from_path(args.topology)
    assignments = {name: n.layer_range for name, n in topo.nodes.items()
                   if n.layer_range}
    out = split_model(model_dir, assignments, args.out,
                      cfg.num_hidden_layers,
                      tie_word_embeddings=cfg.tie_word_embeddings)
    for worker, path in out.items():
        print(f"{worker}: {path}")
    return 0


def cmd_chat(args) -> int:
    sys_p = getattr(args, "system_prompt", None)
    if args.tui:
        from .tui import ChatSession, run_tui
        if args.api:
            session = ChatSession(api_url=args.api, api_key=args.api_key,
                                  system_prompt=sys_p)
        else:
            gen, tokenizer, model_id, _ = _build(args)
            session = ChatSession(gen=gen, sampling=_sampling(args),
                                  max_tokens=args.max_tokens,
                                  model_id=model_id, system_prompt=sys_p)
        return run_tui(session)
    from .chat import chat_local, chat_remote
    if args.api:
        return chat_remote(args.api, args.api_key, system_prompt=sys_p)
    gen, tokenizer, model_id, _ = _build(args)
    return chat_local(gen, model_id, _sampling(args), args.max_tokens,
                      system_prompt=sys_p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cake-tpu",
                                 description="TPU-native distributed "
                                             "multimodal inference")
    ap.add_argument("-v", "--verbose", action="count", default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU platform (tests and drills; same "
                         "as JAX_PLATFORMS=cpu)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("run", help="generate text for a prompt")
    _add_common_model_args(p)
    _add_sampling_args(p)
    p.add_argument("prompt", nargs="?", default=None)
    p.add_argument("--raw", action="store_true",
                   help="no chat template, raw completion")
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("image", help="generate an image to a PNG file")
    p.add_argument("model", help="image model dir ('demo:flux'/'demo:sd' "
                                 "for random weights)")
    p.add_argument("prompt")
    p.add_argument("--out", default="output.png")
    p.add_argument("--width", type=int, default=1024)
    p.add_argument("--height", type=int, default=1024)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--guidance", type=float, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--negative-prompt", default=None)
    p.add_argument("--init-image", default=None,
                   help="img2img: start from this image (SD; ref "
                        "--sd-img2img)")
    p.add_argument("--strength", type=float, default=0.8,
                   help="img2img denoise depth (ref --sd-img2img-strength)")
    p.add_argument("--dtype", default="bf16")
    p.add_argument("--fp8-native", action="store_true",
                   help="FLUX.1 fp8 checkpoints stay 1 byte/param in HBM")
    p.set_defaults(fn=cmd_image)

    p = sub.add_parser("tts", help="synthesize speech to a WAV file")
    p.add_argument("model", help="TTS model dir ('demo:vibevoice' | "
                                 "'demo:luxtts')")
    p.add_argument("text")
    p.add_argument("--out", default="output.wav")
    p.add_argument("--frames", type=int, default=None,
                   help="max speech frames (~133ms each for VibeVoice)")
    p.add_argument("--steps", type=int, default=None,
                   help="diffusion steps per frame")
    p.add_argument("--cfg-scale", type=float, default=None)
    p.add_argument("--voice", default=None,
                   help="voice-prompt .safetensors path (VibeVoice)")
    p.add_argument("--voice-wav", default=None,
                   help="clone the voice from this reference WAV")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", default="bf16")
    p.set_defaults(fn=cmd_tts)

    p = sub.add_parser("serve", help="OpenAI-compatible API server")
    _add_common_model_args(p)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--basic-auth", default=None, help="user:pass")
    p.add_argument("--image-model", default=None,
                   help="image model dir ('demo:flux' for random weights)")
    p.add_argument("--voices-dir", default=None,
                   help="directory of voice-prompt .safetensors files "
                        "served by name via the API")
    p.add_argument("--audio-model", default=None,
                   help="TTS model dir ('demo:vibevoice' | 'demo:luxtts')")
    p.add_argument("--sd-intermediate-every", type=int, default=0,
                   help="save the in-progress SD image every N denoise "
                        "steps (ref: intermediary_images)")
    p.add_argument("--sd-trace-dir", default=None,
                   help="write a JAX profiler trace of SD generation here "
                        "(ref: --sd-tracing)")
    p.add_argument("--announce", action="store_true",
                   help="advertise this replica on UDP discovery so a "
                        "fleet router (`cake-tpu route`) can find it "
                        "(needs --cluster-key / CAKE_CLUSTER_KEY)")
    p.add_argument("--announce-name", default=None,
                   help="replica name for discovery (default: hostname)")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("route", help="fleet router over N serve replicas")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8100)
    p.add_argument("--replica", action="append", default=[],
                   help="replica as NAME=URL or host:port "
                        "(repeatable; e.g. r0=http://10.0.0.5:8000)")
    p.add_argument("--cluster-key", default=None,
                   help="PSK for UDP discovery of `cake serve --announce` "
                        "replicas (CAKE_CLUSTER_KEY also works)")
    p.add_argument("--autoscale", action="store_true",
                   help="run the closed-loop autoscaler (scale replicas "
                        "out/in from telemetry; needs "
                        "CAKE_SCALE_SPAWN_CMD to scale out — same as "
                        "CAKE_SCALE=1, see docs/autoscaling.md)")
    p.set_defaults(fn=cmd_route)

    p = sub.add_parser("top", help="live fleet dashboard (telemetry "
                                   "rollup from a `route` process)")
    p.add_argument("--api", default="127.0.0.1:8100",
                   help="fleet router base URL (default 127.0.0.1:8100)")
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh period in seconds")
    p.add_argument("--once", action="store_true",
                   help="print one plain-text snapshot and exit")
    p.add_argument("--plain", action="store_true",
                   help="plain text instead of curses (implied when "
                        "stdout is not a tty)")
    p.add_argument("--timeout", type=float, default=3.0,
                   help="per-fetch HTTP timeout in seconds")
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser("worker", help="run as a cluster worker")
    p.add_argument("--name", default=os.uname().nodename)
    p.add_argument("--cluster-key", default=knobs.get("CAKE_CLUSTER_KEY"))
    p.add_argument("--port", type=int, default=10128)
    p.add_argument("--model-dir", default=None,
                   help="pre-provisioned weights (from `cake-tpu split`)")
    p.add_argument("--tp", default=None,
                   help="in-host tensor parallelism over this worker's "
                        "local devices ('auto' = all)")
    p.set_defaults(fn=cmd_worker)

    p = sub.add_parser("pull", help="download a model")
    p.add_argument("repo")
    p.set_defaults(fn=cmd_pull)

    p = sub.add_parser("list", help="list cached models")
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("rm", help="delete a cached model")
    p.add_argument("repo")
    p.set_defaults(fn=cmd_rm)

    p = sub.add_parser("split", help="write per-worker weight bundles")
    p.add_argument("model")
    p.add_argument("topology")
    p.add_argument("out")
    p.add_argument("--no-download", action="store_true")
    p.set_defaults(fn=cmd_split)

    p = sub.add_parser("chat", help="interactive terminal chat")
    _add_common_model_args(p)
    _add_sampling_args(p)
    p.add_argument("--api", default=None,
                   help="chat against a remote cake-tpu API URL instead")
    p.add_argument("--api-key", default=None)
    p.add_argument("--tui", action="store_true",
                   help="full-screen 2-tab interface (Chat + Cluster)")
    p.set_defaults(fn=cmd_chat)

    args = ap.parse_args(argv)
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    from .utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    logging.basicConfig(
        level=[logging.WARNING, logging.INFO, logging.DEBUG][min(args.verbose, 2)],
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
