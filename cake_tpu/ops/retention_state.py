"""The state pass of a power-retention decode step (models/brumby.py), as
one Pallas kernel: every tile of a row's state is read ONCE, used for the
read-out of the group's query heads and written back decayed and updated,
in place.

For one key/value head of one row, S [d, D'] float32 (the state
transposed: D' on the lanes, a multiple of 128), phi(q) [G, D'] of the
group's G query heads, w phi(k) [D'], v [d] and the decay `keep`:
    read   = phi(q) S^T                       [G, d]   (the OLD state)
    S_new  = keep S + v phi(k)^T              [d, D']
XLA lowers the two as a convolution that reads S and an elementwise fusion
that reads and writes it: three passes of the 4.4 GB pool a step where two
are needed (PERF.md section 6, PR 53). Here the grid walks (row x head,
lane tiles); a tile [d, T] is loaded, multiplied into the [G, d] read-out
(one bfloat16 pass with float32 accumulation, as the XLA path's default)
and stored as its own update; the output aliases the input, so the donated
pool is updated where it lies.

Off the TPU the callers keep the XLA path (`state_kernel_enabled`); the
tests run the kernel interpreted against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# query heads of a group are padded to whole sublane tiles
ROWS = 8


def state_kernel_enabled() -> bool:
    """On for TPU backends unless CAKE_TPU_FLASH=0 (the switch of the
    other Pallas kernels, ops/flash.py)."""
    from .flash import flash_enabled
    return flash_enabled()


def lane_tile(width: int, limit: int = 2048) -> int:
    """The widest divisor of `width` that is a whole number of lane tiles
    and at most `limit` lanes: 1,664 for 8,320 (five tiles of 852 KB at
    d = 128)."""
    n = width // LANES
    best = max(k for k in range(1, n + 1)
               if n % k == 0 and k * LANES <= max(limit, LANES))
    return best * LANES


def _kernel(pq_ref, pk_ref, v_ref, keep_ref, s_ref, read_ref, out_ref):
    s = s_ref[0]                                          # [d, T] f32

    @pl.when(pl.program_id(1) == 0)
    def _():
        read_ref[...] = jnp.zeros_like(read_ref)

    read_ref[0] += jax.lax.dot_general(
        pq_ref[0].astype(jnp.bfloat16), s.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    out_ref[0] = keep_ref[0] * s + v_ref[0] * pk_ref[0]


def _step_local(state, pq, pkw, v, keep, *, interpret):
    b, h, d, width = state.shape
    g = pq.shape[2]
    tile = lane_tile(width)
    n = b * h
    pq = jnp.pad(pq.reshape(n, g, width).astype(jnp.float32),
                 ((0, 0), (0, -g % ROWS), (0, 0)))
    rows = pq.shape[1]
    col = lambda a: jnp.broadcast_to(                     # noqa: E731
        a.astype(jnp.float32).reshape(n, -1, 1), (n, d, 1))
    read, new = pl.pallas_call(
        _kernel,
        grid=(n, width // tile),
        in_specs=[
            pl.BlockSpec((1, rows, tile), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, tile), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, d, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, d, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, d, tile), lambda i, j: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, d, tile), lambda i, j: (i, 0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((n, rows, d), jnp.float32),
                   jax.ShapeDtypeStruct((n, d, width), jnp.float32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="cake_retention_state",
        interpret=interpret,
    )(pq, pkw.reshape(n, 1, width).astype(jnp.float32), col(v),
      col(keep), state.reshape(n, d, width))
    return (read[:, :g].reshape(b, h, g, d),
            new.reshape(b, h, d, width))


@functools.lru_cache(maxsize=None)
def _entry(interpret: bool):
    """The call for one static configuration, with the rule that keeps
    `vmap` (the decode program maps its rows) from looping: a mapped axis
    is merged into the kernel's own row axis, a reshape of leading dims."""
    local = jax.jit(functools.partial(_step_local, interpret=interpret))

    @jax.custom_batching.custom_vmap
    def call(state, pq, pkw, v, keep):
        return local(state, pq, pkw, v, keep)

    @call.def_vmap
    def _merge(axis_size, in_batched, *args):
        merged = []
        for a, batched in zip(args, in_batched):
            if not batched:
                a = jnp.broadcast_to(a[None], (axis_size,) + a.shape)
            merged.append(a.reshape((axis_size * a.shape[1],) + a.shape[2:]))
        read, new = call(*merged)
        split = lambda a: a.reshape((axis_size, -1) + a.shape[1:])  # noqa: E731
        return (split(read), split(new)), (True, True)

    return call


def retention_state_step(state, pq, pkw, v, keep, interpret: bool = False):
    """state [B, Hkv, d, D'] float32; pq [B, Hkv, G, D'] the query heads'
    squares; pkw [B, Hkv, D'] the key's square times its weight (zero for a
    masked row); v [B, Hkv, d]; keep [B, Hkv] the decay (one for a masked
    row). Returns (read [B, Hkv, G, d] = phi(q) against the OLD state,
    the new state, in the input's place)."""
    return _entry(bool(interpret))(state, pq, pkw, v, keep)
