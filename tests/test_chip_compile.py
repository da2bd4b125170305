"""Compile the Pallas attention kernels (prefill flash, decode) for a
DESCRIBED TPU v5e (nothing runs).

The TPU compiler is installed without a chip: it lowers for a topology
description and refuses what the chip would refuse — a kernel that does
not fit VMEM, a misaligned slice, a Mosaic call GSPMD cannot partition.
Interpret mode (tests/test_flash.py) sees none of that. This is the only
file that describes a chip: the description loads the TPU library, which
one process holds until it exits, so it happens inside a fixture, never at
import, and every compile runs in this test's own process.
"""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from cake_tpu.ops.decode_attention import decode_attention
from cake_tpu.ops.flash import flash_attention

# Qwen3-0.6B attention widths (the chip_smoke.py model)
HQ, HKV, D = 16, 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else: logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("tp",))


@contextlib.contextmanager
def _no_compile_cache():
    """A described-device compile can be written to the persistent cache
    but never read back without a chip — keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(sharding, sq, skv, *, d=D, append=False, window=None,
             mesh=None, scalar_sharding=None, hq=HQ, hkv=HKV):
    scalar_sharding = scalar_sharding or sharding
    q = jax.ShapeDtypeStruct((1, sq, hq, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, skv, hkv, d), jnp.bfloat16,
                              sharding=sharding)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar_sharding)

    def f(q, k, v, valid_len, pos0):
        return flash_attention(q, k, v, valid_len=valid_len,
                               q_offset=pos0 if append else None,
                               window=window, mesh=mesh)

    with _no_compile_cache():
        return jax.jit(f).lower(q, kv, kv, scalar, scalar).compile()


@pytest.mark.parametrize("sq,skv,kw", [
    (512, 512, {}),                                  # fresh S=512
    (4096, 4096, {}),                                # fresh S=4096
    (256, 4096, {"append": True}),                   # serve default chunk/ctx
    (256, 32768, {"append": True}),                  # whole-K/V VMEM wall
    (512, 512, {"d": 64}),
    (512, 512, {"d": 256}),
    (512, 512, {"window": 128}),                     # SWA layers
    # Jamba2-3B's attention: a group of 20 query heads on ONE K/V head
    (256, 256, {"hq": 20, "hkv": 1}),
    (256, 4096, {"hq": 20, "hkv": 1, "append": True}),
], ids=["fresh512", "fresh4096", "append256x4096", "append256x32768",
        "d64", "d256", "windowed", "group20-fresh", "group20-append"])
def test_flash_compiles_on_one_chip(one_chip, sq, skv, kw):
    compiled = _compile(one_chip, sq, skv, **kw)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("append", [False, True], ids=["fresh", "append"])
def test_flash_compiles_head_sharded_under_tp_mesh(tp_mesh, append):
    """GSPMD cannot partition a Mosaic kernel: under a mesh the call must
    be a shard_map over `tp` with the kernel still in the program."""
    heads = NamedSharding(tp_mesh, P(None, None, "tp", None))
    compiled = _compile(heads, 256 if append else 512,
                        4096 if append else 512, append=append,
                        mesh=tp_mesh,
                        scalar_sharding=NamedSharding(tp_mesh, P()))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # heads stay where they are: no collective moves q/k/v/out
    assert "all-gather" not in text and "all-to-all" not in text


# -- the decode kernel over a pool as the benchmark's cells hold it ----------

def _compile_decode(sharding, rows, hq, hkv, *, ctx=4096, mesh=None,
                    row_sharding=None):
    row_sharding = row_sharding or sharding
    q = jax.ShapeDtypeStruct((rows, 1, hq, D), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((rows, ctx, hkv, D), jnp.bfloat16,
                              sharding=sharding)
    pos = jax.ShapeDtypeStruct((rows, ctx), jnp.int32, sharding=row_sharding)
    q_pos = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=row_sharding)
    act = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=row_sharding)

    def f(q, k, v, kv_pos, q_pos, act):
        return decode_attention(q, k, v, kv_pos, q_pos, act, mesh=mesh)

    with _no_compile_cache():
        return jax.jit(f).lower(q, kv, kv, pos, q_pos, act).compile()


@pytest.mark.parametrize("rows,hq,hkv", [
    (8, 32, 8),                      # qwen3-4b's pool: 8 slots x 4096
    (16, 32, 4),                     # qwen3-30b-a3b's: 16 slots, 4 K/V heads
    (1, 16, 8),                      # a sequential generate's batch-1 cache
], ids=["4b-pool", "moe-pool", "batch1"])
def test_decode_kernel_compiles_on_one_chip(one_chip, rows, hq, hkv):
    """The K and V buffers reach the kernel as they lie: viewing
    [rows, T, Hkv, D] as [rows, T * Hkv, D] is a bitcast in the tiled
    layout, so no copy, transpose or slice of a pool-shaped operand
    stands around the call."""
    text = _compile_decode(one_chip, rows, hq, hkv).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    pool = re.compile(r"= bf16\[%d,(4096,%d|%d),128\]\S* (\w[\w-]*)\("
                      % (rows, hkv, 4096 * hkv))
    ops = {m.group(2) for m in pool.finditer(text)}
    assert ops <= {"parameter", "bitcast"}, ops


def test_decode_kernel_compiles_head_sharded_under_tp_mesh(tp_mesh):
    heads = NamedSharding(tp_mesh, P(None, None, "tp", None))
    text = _compile_decode(heads, 8, 32, 8, mesh=tp_mesh,
                           row_sharding=NamedSharding(tp_mesh, P())
                           ).as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-to-all" not in text
