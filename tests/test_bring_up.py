"""Bring-up plumbing (PR 22): where the compile cache goes, what a host
advertises about its chip, where the loader puts weights under a mesh,
and what /health says about the device."""
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


@pytest.fixture
def config_updates(monkeypatch):
    """Capture jax.config.update instead of applying it: the tests never
    turn the persistent cache on for their own process."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_cache_helper_leaves_a_placed_cache_alone(monkeypatch,
                                                  config_updates):
    monkeypatch.setenv(compile_cache.ENV, "/placed/from/outside")
    assert compile_cache.enable_compile_cache() == "/placed/from/outside"
    # JAX reads the variable itself: no directory is set in code, only
    # that the key covers the ops' metadata (the named scopes, PR 26)
    assert config_updates == [METADATA_IN_KEY]


def test_cache_helper_fixed_path_inside_the_checkout(monkeypatch,
                                                     config_updates):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert config_updates == [METADATA_IN_KEY,
                              ("jax_compilation_cache_dir", want)] * 2
    # another pid derives the same path: nothing of the process is in it
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV}
    out = subprocess.run(
        [sys.executable, "-c",
         "from cake_tpu.utils.compile_cache import default_cache_dir; "
         "print(default_cache_dir())"],
        cwd=ROOT, env=env, capture_output=True, text=True, check=True,
        timeout=300)
    assert out.stdout.strip() == want


def _fake_tpu(kind, n=1):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind)] * n


def test_detect_capabilities_unknown_tpu_kind_raises(monkeypatch):
    from cake_tpu.cluster.discovery import detect_capabilities
    monkeypatch.setattr(jax, "devices", lambda: _fake_tpu("TPU v9 mega"))
    with pytest.raises(ValueError, match="TPU v9 mega"):
        detect_capabilities()


def test_detect_capabilities_v5e_is_the_bf16_peak(monkeypatch):
    from cake_tpu.cluster.discovery import detect_capabilities
    monkeypatch.setattr(jax, "devices", lambda: _fake_tpu("TPU v5 lite", 4))
    caps = detect_capabilities()
    assert caps["backend"] == "tpu" and caps["n_devices"] == 4
    assert caps["tflops"] == 4 * 197.0
    assert caps["memory_bytes"] == 4 * (16 << 30)


def test_detect_capabilities_broken_backend_is_not_a_cpu(monkeypatch):
    from cake_tpu.cluster.discovery import detect_capabilities

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError):
        detect_capabilities()


@pytest.fixture(scope="module")
def tp4_checkpoint(tmp_path_factory):
    from cake_tpu.models import tiny_config
    from cake_tpu.models.common.layers import init_params
    from cake_tpu.parallel import make_mesh
    from cake_tpu.utils.export import params_to_hf_tensors
    from cake_tpu.utils.safetensors_io import save_safetensors
    cfg = tiny_config("qwen3", num_key_value_heads=4)
    d = tmp_path_factory.mktemp("tp4ckpt")
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    save_safetensors(str(d / "model.safetensors"),
                     params_to_hf_tensors(cfg, params))
    (d / "config.json").write_text("{}")
    mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4])
    return cfg, str(d), mesh


def _assert_placed(params, mesh):
    from cake_tpu.parallel import params_shardings
    want = params_shardings(params, mesh)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    wants = jax.tree_util.tree_leaves(want)
    assert len(leaves) == len(wants) > 10
    split = 0
    for (path, leaf), sh in zip(leaves, wants):
        name = jax.tree_util.keystr(path)
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), name
        # never committed whole to one device
        assert leaf.sharding.device_set == set(mesh.devices.flat), name
        split += any(s.data.shape != leaf.shape
                     for s in leaf.addressable_shards)
    assert split >= 7 * 2       # every projection of every layer is split


def test_loader_places_each_leaf_where_it_lives(tp4_checkpoint, monkeypatch):
    from cake_tpu.utils import loaders
    from cake_tpu.utils.quant import NoQuantization
    cfg, model_dir, mesh = tp4_checkpoint
    # under a mesh no checkpoint tensor may go through jnp.asarray (which
    # lands it whole on device 0): leaves stay numpy until device_put
    host_flags = []
    real = loaders._to_dev
    monkeypatch.setattr(loaders, "_to_dev",
                        lambda a, dt, host=False: (host_flags.append(host),
                                                   real(a, dt, host))[1])
    placed = loaders.load_model_params(cfg, model_dir, jnp.float32,
                                       quant=NoQuantization(), mesh=mesh)
    assert host_flags and all(host_flags)
    _assert_placed(placed, mesh)
    plain = loaders.load_model_params(cfg, model_dir, jnp.float32,
                                      quant=NoQuantization())
    for a, b in zip(jax.tree_util.tree_leaves(placed),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_init_params_under_a_mesh_is_born_sharded(tp4_checkpoint):
    from cake_tpu.parallel.sharding import init_params_sharded
    cfg, _, mesh = tp4_checkpoint
    key = jax.random.PRNGKey(0)
    params = init_params_sharded(mesh, cfg, key, jnp.float32)
    _assert_placed(params, mesh)
    plain = init_params_sharded(None, cfg, key, jnp.float32)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_kv_cache_under_a_mesh_is_born_sharded(tp4_checkpoint):
    """The 4-chip smoke found the serve pool landing whole on device 0
    (shard_cache(init_cache(...))): the zeros are now created sharded."""
    from cake_tpu.parallel import cache_shardings
    from cake_tpu.parallel.sharding import init_cache_sharded
    cfg, _, mesh = tp4_checkpoint
    cache = init_cache_sharded(mesh, cfg, 4, 64, jnp.float32)
    want = cache_shardings(cache, mesh)
    for (path, leaf), sh in zip(jax.tree_util.tree_leaves_with_path(cache),
                                jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), name
        assert leaf.sharding.device_set == set(mesh.devices.flat), name
        if name.endswith("['k']") or name.endswith("['v']"):
            assert leaf.addressable_shards[0].data.shape[2] == \
                cfg.num_key_value_heads // 4, name
    # same maker for the same shape: no compile per request
    from cake_tpu.parallel import sharding
    before = sharding._cache_maker.cache_info().misses
    init_cache_sharded(mesh, cfg, 4, 64, jnp.float32)
    assert sharding._cache_maker.cache_info().misses == before
    plain = init_cache_sharded(None, cfg, 4, 64, jnp.float32)
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_health_names_the_device():
    from cake_tpu.api.obs_routes import _device_health
    h = _device_health()
    assert h["platform"] == "cpu" and isinstance(h["device_kind"], str)
    assert h["count"] == len(jax.devices()) == len(h["devices"])


def test_device_health_does_not_hide_a_broken_backend(monkeypatch):
    from cake_tpu.api.obs_routes import _device_health

    def boom():
        raise RuntimeError("device lost")
    monkeypatch.setattr(jax, "local_devices", boom)
    with pytest.raises(RuntimeError):
        _device_health()
