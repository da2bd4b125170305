"""Parameter / cache sharding rules.

GSPMD style: annotate the pytrees with NamedSharding and let XLA insert the
collectives (psum after the row×col sharded matmul pair) — the TPU-idiomatic
replacement for hand-written NCCL calls the reference never had (SURVEY §2g:
TP is a "natural TPU win the reference cannot do").

Megatron-style layout per decoder layer (projections kept separate so row
chunks stay head-aligned — see layers.py init_attention_params):
  q/k/v_proj [out, H] : rows over tp (head-parallel)
  o_proj   [H, q]     : cols over tp -> XLA inserts the psum
  gate/up_proj [I, H] : rows over tp
  down_proj [H, I]    : cols over tp
  MoE expert banks    : leading E axis over ep (+ inner tp)
  KV cache            : heads over tp, batch over dp, LENGTH over sp
                        (context memory scales across the sp devices;
                        ring prefill writes each sequence shard locally,
                        decode attends over the sharded length with GSPMD
                        inserting the softmax-reduction collectives)
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.common.cache import init_cache
from ..models.common.config import ModelConfig


def _ax(mesh: Mesh, name: str):
    return name if name in mesh.axis_names and mesh.shape[name] > 1 else None


def param_pspec(path: tuple[str, ...], mesh: Mesh) -> P:
    """PartitionSpec for a parameter identified by its pytree path."""
    tp, ep = _ax(mesh, "tp"), _ax(mesh, "ep")
    name = path[-1]
    parent = path[-2] if len(path) >= 2 else ""
    if parent == "experts":
        # stacked expert banks [E, I, H] / [E, H, I]: experts over ep,
        # FFN channels over tp
        if name in ("gate_proj", "up_proj"):
            return P(ep, tp, None)
        if name == "down_proj":
            return P(ep, None, tp)
    if name == "weight" or name == "bias":
        if parent in ("q_proj", "k_proj", "v_proj", "g_proj", "q_b_proj",
                      "kv_b_proj"):
            # (g_proj: Laguna's gate a query head, head-parallel like q;
            # q_b_proj, kv_b_proj: a latent layer's expansions, rows by
            # head; its q_a, kv_a and their norms replicate, as its rows
            # of latents do: every device reads the one shared key)
            return P(tp, None) if name == "weight" else P(tp)
        if parent == "o_proj":
            return P(None, tp)
        if parent in ("gate_proj", "up_proj"):
            return P(tp, None)
        if parent == "down_proj":
            return P(None, tp)
        if parent in ("embed_tokens", "lm_head", "gate",
                      "shared_expert_gate"):
            return P(None, None)
    if parent == "rope":
        return P(None, None)
    return P(None)      # norms and other vectors


def _dense_pspec_for(leaf, spec: P) -> P:
    """Trim a spec to the leaf's rank (MoE dense tensors are 3D, rest 2D)."""
    ndim = getattr(leaf, "ndim", 0)
    parts = list(spec)
    if len(parts) > ndim:
        parts = parts[-ndim:] if ndim else []
    while len(parts) < ndim:
        parts.append(None)
    return P(*parts)


def params_shardings(params, mesh: Mesh):
    """Pytree of NamedSharding matching `params`."""
    def f(path, leaf):
        keys = tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
        keys = tuple(str(k) for k in keys if k is not None)
        spec = _dense_pspec_for(leaf, param_pspec(keys, mesh))
        # fail with the tensor name, not a deep GSPMD error, on indivisibility
        for dim, ax in enumerate(spec):
            if ax is not None and leaf.shape[dim] % mesh.shape[ax]:
                raise ValueError(
                    f"{'.'.join(keys)}: dim {dim} of shape {leaf.shape} not "
                    f"divisible by mesh axis {ax}={mesh.shape[ax]}")
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(f, params)


def cache_shardings(cache, mesh: Mesh):
    dp, tp = _ax(mesh, "dp"), _ax(mesh, "tp")
    sp = _ax(mesh, "sp")

    def _fit(leaf, spec: P) -> P:
        """Drop axes the leaf's dims can't be divided by (batch=1 under dp,
        GDN conv channels not a tp multiple): replicate rather than fail —
        these states are small relative to the weights."""
        parts = []
        for dim, ax in enumerate(spec):
            if ax is not None and leaf.shape[dim] % mesh.shape[ax]:
                ax = None
            parts.append(ax)
        return P(*parts)

    def f(path, leaf):
        name = str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
        ndim = getattr(leaf, "ndim", 0)
        spec = P()
        # sp: KV buffers shard over the LENGTH axis, so context memory
        # scales across the sp devices (ring prefill writes each shard
        # locally; decode attention over the sharded length is partial
        # per device with GSPMD inserting the softmax-reduction
        # collectives). _fit drops sp when the capacity (e.g. an SWA
        # window) is not divisible.
        if ndim == 4 and name in ("k", "v"):
            spec = P(dp, sp, tp, None)
        elif ndim == 3 and name == "k":
            # keys joined [B, T, Hkv * D] (cache.key_row_shape): a `tp`-th
            # of the joined width is whole heads, as Hkv % tp == 0
            spec = P(dp, sp, tp)
        elif ndim == 4 and name == "state":     # GDN [B, Hv, Dk, Dv]
            spec = P(dp, tp, None, None)
        elif ndim == 3 and name == "conv":      # GDN conv state [B, C, K-1]
            spec = P(dp, tp, None)
        elif ndim == 2 and name == "pos":
            spec = P(dp, sp)
        return NamedSharding(mesh, _fit(leaf, spec))
    return jax.tree_util.tree_map_with_path(f, cache)


def shard_params(params, mesh: Mesh | None):
    """No-op without a mesh so product call sites need no guard. Host
    (numpy) leaves go straight into their shards — only each device's
    slice crosses to it — and leaves already placed are left alone."""
    if mesh is None:
        return params
    return jax.device_put(params, params_shardings(params, mesh))


def init_params_sharded(mesh: Mesh | None, cfg: ModelConfig, key, dtype):
    """init_params with every leaf created where it lives: under a mesh
    the init runs as one jit with out_shardings, so a model that needs
    every chip is never materialized whole on the first one."""
    from ..models.common.layers import init_params
    fn = functools.partial(init_params, cfg, dtype=dtype)
    if mesh is None:
        return fn(key)
    shardings = params_shardings(jax.eval_shape(fn, key), mesh)
    return jax.jit(fn, out_shardings=shardings)(key)


def shard_cache(cache, mesh: Mesh | None):
    if mesh is None:
        return cache
    return jax.device_put(cache, cache_shardings(cache, mesh))


@functools.lru_cache(maxsize=None)
def _cache_maker(mesh: Mesh, *init_args):
    make = functools.partial(init_cache, *init_args)
    return jax.jit(make, out_shardings=cache_shardings(jax.eval_shape(make),
                                                       mesh))


def init_cache_sharded(mesh: Mesh | None, cfg: ModelConfig, batch: int,
                       kv_len: int, dtype, layer_range=None):
    """init_cache with every buffer created where it lives. Under a mesh
    the zeros are born sharded (one jitted maker per shape, memoized), so
    a KV pool sized for every chip never lands whole on the first one —
    which is what shard_cache(init_cache(...)) did."""
    if mesh is None:
        return init_cache(cfg, batch, kv_len, dtype, layer_range)
    return _cache_maker(mesh, cfg, batch, kv_len, dtype, layer_range)()


def check_tp_divisibility(cfg: ModelConfig, mesh: Mesh):
    tp = mesh.shape.get("tp", 1)
    if cfg.mamba is not None and tp > 1:
        raise ValueError(
            f"--tp {tp} is not supported for {cfg.arch}: its attention has "
            "one KV head, which cannot be split, and its Mamba projections "
            "and state have no placement over tp (they replicate)")
    if cfg.retention is not None and tp > 1:
        raise ValueError(
            f"--tp {tp} is not supported for {cfg.arch}: its key/value "
            "heads would split, but no placement of a power-retention "
            "row's state over tp is written or measured")
    for a in {cfg.attn_shape(s) for s in cfg.layer_specs()
              if not s.recurrent}:
        # (a latent layer's one shared key is held whole by every device)
        if (not a.latent and a.kv_heads % tp) or a.heads % tp:
            raise ValueError(
                f"tp={tp} must divide heads {a.heads}/{a.kv_heads}")
    if cfg.intermediate_size % tp:
        raise ValueError(f"tp={tp} must divide intermediate {cfg.intermediate_size}")
