"""Ring attention: causal attention with the sequence sharded over a mesh
axis ('sp'), K/V blocks rotating around the ring via collective permute.

This is long-context capability the reference does NOT have (SURVEY §5:
"no ring attention, no context parallelism") — on TPU it is the idiomatic
way to scale sequence length across ICI: each device holds S/N queries and
streams all N K/V blocks through, merging partial results with the online
(flash-style) log-sum-exp accumulation so the full [S, S] score matrix is
never materialized.

Written with shard_map + jax.lax.ppermute (XLA overlaps the permute with
the block computation); runs identically on the CPU test mesh and on ICI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, k_pos, scale):
    """Partial attention of a Q block against one K/V block.

    q: [B, Sq, H, D]; k/v: [B, Skv, Hkv, D].
    Returns (acc [B, Sq, H, D] f32 — unnormalized, m [B, Sq, H] rowmax,
    l [B, Sq, H] rowsum) for online-softmax merging.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.reshape(b, sq, hkv, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qf, k,
                        preferred_element_type=jnp.float32) * scale
    mask = (k_pos[:, None, :] <= q_pos[:, :, None])          # causal
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                             # [B,Hkv,G,Sq]
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgst,btkd->bskgd", p.astype(v.dtype), v)
    acc = acc.reshape(b, sq, hq, d).astype(jnp.float32)
    m = m.transpose(0, 3, 1, 2).reshape(b, sq, hq)
    l = l.transpose(0, 3, 1, 2).reshape(b, sq, hq)
    return acc, m, l


def _merge(acc1, m1, l1, acc2, m2, l2):
    """Merge two partial softmax accumulations (flash-attention algebra)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return acc, m, l


def ring_attention_sharded(q, k, v, axis_name: str, scale: float | None = None,
                           vary_axes: tuple[str, ...] = (),
                           axis_size: int | None = None):
    """Body run per-device under shard_map: q/k/v are the local sequence
    shards [B, S_local, H(.kv), D]; global sequence = concat over the axis.
    vary_axes: additional manual mesh axes the inputs vary over (e.g. the
    tp head axis) — the accumulators must be cast varying over them too or
    the fori_loop carry type mismatches. axis_size: static ring size from
    the mesh — the ppermute schedule below needs the concrete value."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n = axis_size if axis_size is not None else jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    b, s_local = q.shape[0], q.shape[1]

    q_pos = (me * s_local + jnp.arange(s_local, dtype=jnp.int32))[None, :]
    q_pos = jnp.broadcast_to(q_pos, (b, s_local))

    # pvary: accumulators start device-varying over the ring axis (and any
    # extra manual axes) so the fori_loop carry type matches (shard_map
    # manual-axes typing rule). Older jax has no varying-type system (and
    # no pcast) — there the shard_map is built with check_rep=False and
    # the plain accumulators are already well-typed.
    pcast = getattr(jax.lax, "pcast", None)
    vary = (axis_name, *vary_axes)
    cast = ((lambda a: pcast(a, vary, to='varying')) if pcast is not None
            else (lambda a: a))
    acc = cast(jnp.zeros(q.shape, jnp.float32))
    m = cast(jnp.full(q.shape[:3], -jnp.inf, jnp.float32))
    l = cast(jnp.zeros(q.shape[:3], jnp.float32))

    def step(i, carry):
        acc, m, l, k_blk, v_blk = carry
        src = (me - i) % n                    # whose K/V block we hold now
        k_pos = (src * s_local + jnp.arange(s_local, dtype=jnp.int32))[None, :]
        k_pos = jnp.broadcast_to(k_pos, (b, s_local))
        a2, m2, l2 = _block_attend(q, k_blk, v_blk, q_pos, k_pos, scale)
        acc, m, l = _merge(acc, m, l, a2, m2, l2)
        # rotate K/V to the right neighbor (overlaps with next compute)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return acc, m, l, k_blk, v_blk

    acc, m, l, _, _ = jax.lax.fori_loop(0, n, step, (acc, m, l, k, v))
    # fully-masked rows (never for causal q_pos>=0) guarded by l=0 -> 0
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   scale: float | None = None, head_axis: str = "tp"):
    """q/k/v: [B, S, H(.kv), D] global tensors; S must divide by mesh[axis].

    Composes with tensor parallelism: when the mesh also has a >1
    `head_axis`, heads stay sharded over it inside the ring (head blocks
    are aligned between q and kv, so local GQA grouping is preserved) —
    otherwise the shard_map region would silently all-gather the heads
    and compute the full attention redundantly on every tp member."""
    h = (head_axis if head_axis in mesh.axis_names
         and mesh.shape[head_axis] > 1 else None)
    fn = functools.partial(ring_attention_sharded, axis_name=axis, scale=scale,
                           vary_axes=(h,) if h else (),
                           axis_size=mesh.shape[axis])
    spec = P(None, axis, h, None)
    # jax.shard_map is the promoted name (jax >= 0.5); older releases only
    # ship jax.experimental.shard_map.shard_map, whose replication checker
    # predates the varying-type annotations the body would need — disable
    # it there (the out_specs still pin the result layout)
    smap = getattr(jax, "shard_map", None)
    if smap is not None:
        mapped = smap(fn, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)
    else:
        from jax.experimental.shard_map import shard_map as _shard_map
        mapped = _shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_rep=False)
    return mapped(q, k, v)
