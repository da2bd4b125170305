"""Compile the flash kernel for a DESCRIBED TPU v5e (nothing runs).

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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

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
             mesh=None, scalar_sharding=None):
    scalar_sharding = scalar_sharding or sharding
    q = jax.ShapeDtypeStruct((1, sq, HQ, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, skv, HKV, d), jnp.bfloat16,
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
], ids=["fresh512", "fresh4096", "append256x4096", "append256x32768",
        "d64", "d256", "windowed"])
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
