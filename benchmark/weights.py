"""Weights from --seed, born on the device(s) in the type they are served in.

The benchmark makes the weights and hands the SAME arrays to the program
(`TextModel(cfg, params=...)`) and to the plain reference, so the reference
takes nothing the program has made. The layout is the published checkpoint's
(Hugging Face names, `[out, in]` matrices), which is also the program's
parameter tree.

Which leaves a layer has is the FAMILY's to say: `reference/<family>.py`
gives `layer_leaves(hf)`, a tree of `(shape, std)` (std None: a norm
weight), and may give `top_leaves(hf)` for what lies outside the layers.
This module only fills such trees with numbers. One jitted maker per tree:
every layer has the same shapes, so one compile serves all of them and the
dispatches run back to back on the device — never leaf by leaf from the
host, never through a file.

Under a mesh (a cell of four chips) every leaf is created where it lives:
the maker's `out_shardings` are the program's own placement rules
(`cake_tpu.parallel.sharding.params_shardings`), so a model that no single
chip holds is never whole on one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# init std of the projections (the family's initializer_range)
STD = 0.02
# norm weights are spread around 1 so that a dropped norm weight shows
NORM_SPREAD = 0.1


def seed_key(seed: int, stream: int):
    """A key for any whole number a driver may pass (over 2**31 too)."""
    key = jax.random.key(stream, impl="rbg")
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple)


def _fill(key, leaves, dtype):
    """A tree of (shape, std) filled with numbers of `dtype`."""
    flat, tree = jax.tree_util.tree_flatten(leaves, is_leaf=_is_leaf)
    out = []
    for k, (shape, std) in zip(jax.random.split(key, len(flat)), flat):
        x = jax.random.normal(k, shape, jnp.float32)
        out.append((1.0 + NORM_SPREAD * x if std is None else x * std
                    ).astype(dtype))
    return tree.unflatten(out)


def _freeze(leaves):
    """Hashable form of a (shape, std) tree, for the maker's cache."""
    flat, tree = jax.tree_util.tree_flatten(leaves, is_leaf=_is_leaf)
    return tuple(flat), tree


@functools.lru_cache(maxsize=None)
def _maker(flat: tuple, tree, dtype, mesh):
    leaves = tree.unflatten(flat)
    fn = functools.partial(_fill, leaves=leaves, dtype=dtype)
    if mesh is None:
        return jax.jit(fn)
    from cake_tpu.parallel.sharding import params_shardings
    shapes = jax.eval_shape(fn, jax.random.key(0, impl="rbg"))
    return jax.jit(fn, out_shardings=params_shardings(shapes, mesh))


def cell_mesh(cell):
    """The mesh `cake-tpu serve --tp <chips>` builds, over exactly the
    chips the cell asks for; None on one chip. A cell of several chips
    that would run on one is an error, never a quiet fallback."""
    if cell.chips == 1:
        return None
    from cake_tpu.parallel import serving_mesh
    mesh = serving_mesh(cell.chips)
    if mesh is None or mesh.size != cell.chips:
        raise SystemExit(f"{cell.name}: asked for {cell.chips} chips, the "
                         f"mesh built is {mesh}")
    return mesh


def top_leaves(hf: dict) -> dict:
    """What a decoder-only checkpoint holds outside its layers."""
    v, h = hf["vocab_size"], hf["hidden_size"]
    top = {"embed_tokens": {"weight": ((v, h), STD)},
           "norm": {"weight": ((h,), None)}}
    if not hf.get("tie_word_embeddings"):
        top["lm_head"] = {"weight": ((v, h), STD)}
    return top


def make_weights(family, hf: dict, seed: int, dtype=jnp.bfloat16,
                 mesh=None) -> dict:
    """The whole tree for config dict `hf`; `family` is the module
    `reference/<family>.py`."""
    # the tables first: their float32 temporaries want the room
    top = getattr(family, "top_leaves", top_leaves)(hf)
    w = _maker(*_freeze(top), dtype, mesh)(seed_key(seed, 2))
    n = hf["num_hidden_layers"]
    layer = _maker(*_freeze(family.layer_leaves(hf)), dtype, mesh)
    keys = jax.random.split(seed_key(seed, 1), n)
    w["layers"] = [layer(keys[i]) for i in range(n)]
    return w
