"""A layer kind is one row (models/common/mixers.py): every registered
family resolves, a row's params go out and come back leaf for leaf, its
cache leaves say what its `recurrent` says, its forward runs under the
scopes the trace readers look for, and nothing outside the table branches
on a layer kind."""
import ast
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.common import cache as cache_mod
from cake_tpu.models.common import layers as layers_mod
from cake_tpu.models.common.config import (FAMILY_ADAPTERS,
                                           config_from_hf_dict, tiny_config)
from cake_tpu.models.common.mixers import Mixer, mixer_of
from cake_tpu.utils.loaders import ParamLoader
from cake_tpu.utils.safetensors_io import TensorStorage, save_safetensors

ROOT = pathlib.Path(__file__).resolve().parents[1]
PACKAGE = ROOT / "cake_tpu"
FAMILY_MODULES = ("jamba", "kda", "qwen3_5", "brumby", "deepseek_v2")


def _rows(cfg):
    """(layer index, spec, mixer) of the config's distinct layer kinds."""
    seen, out = set(), []
    for i, spec in enumerate(cfg.layer_specs()):
        m = mixer_of(cfg, spec)
        assert isinstance(m, Mixer)
        if (m.param_key, spec) not in seen:
            seen.add((m.param_key, spec))
            out.append((i, spec, m))
    return out


@pytest.mark.parametrize("arch", sorted(FAMILY_ADAPTERS))
def test_every_layer_has_a_row_that_round_trips(tmp_path, arch):
    cfg = tiny_config(arch)
    for i, spec, m in _rows(cfg):
        lc = m.init_cache(cfg, spec, 2, 32, jnp.float32)
        assert cache_mod.is_positional(lc) == (not m.recurrent), (i, spec)
        assert spec.recurrent == m.recurrent, (i, spec)
        assert all(leaf.shape[0] == 2 for leaf in lc.values())

        lp = f"{cfg.model_prefix}.layers.{i}"
        p = m.init_params(cfg, spec, jax.random.PRNGKey(i), jnp.float32)
        tensors = m.export_params(cfg, p, lp)
        assert tensors and all(
            name.startswith(f"{lp}.{m.param_key}.") for name in tensors)
        path = tmp_path / f"layer{i}.safetensors"
        save_safetensors(str(path), tensors)
        loader = ParamLoader(cfg, TensorStorage.from_model_dir(str(tmp_path)),
                             dtype=jnp.float32)
        back = m.load_params(loader, lp, spec)
        path.unlink()
        want = dict(jax.tree_util.tree_leaves_with_path(p))
        got = dict(jax.tree_util.tree_leaves_with_path(back))
        assert want.keys() == got.keys()
        for k, leaf in want.items():
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(leaf), atol=1e-6,
                err_msg=f"layer {i} {jax.tree_util.keystr(k)}")


# (arch, a layer of the kind, the scopes its mixer runs under, outermost
# first): what benchmark/layer_metrics/programs.*.attn_ms, .attn_linear_ms
# and .ssm_ms read by name
SCOPES = [
    ("qwen3", 0, ("cake.attn",)),
    ("qwen3_5", 0, ("cake.attn", "cake.attn.linear")),
    ("solar_open2", 1, ("cake.attn", "cake.attn.linear")),
    ("jamba", 0, ("cake.ssm",)),
    ("brumby", 0, ("cake.attn", "cake.attn.retention")),
    ("deepseek_v2", 0, ("cake.attn", "cake.attn.latent")),
]


@pytest.mark.parametrize("arch,layer,scopes", SCOPES,
                         ids=["attention", "gdn", "kda", "mamba", "retention",
                              "latent"])
def test_a_rows_forward_runs_under_exactly_its_scopes(arch, layer, scopes):
    cfg = tiny_config(arch)
    spec = cfg.layer_spec(layer)
    m = mixer_of(cfg, spec)
    assert m.scopes == scopes
    p = jax.eval_shape(lambda: layers_mod.init_layer_params(
        cfg, spec, jax.random.PRNGKey(0), jnp.float32))
    lc = jax.eval_shape(lambda: cache_mod.init_layer_cache(
        cfg, spec, 1, 32, jnp.float32))
    rope = jax.eval_shape(lambda: layers_mod.make_rope(cfg))
    text = jax.jit(lambda p, x, lc, pos0, rope: layers_mod._attn(
        cfg, spec, p, x, lc, pos0, rope)).lower(
        p, jax.ShapeDtypeStruct((1, 4, cfg.hidden_size), jnp.float32), lc,
        jax.ShapeDtypeStruct((), jnp.int32), rope).as_text(debug_info=True)
    # every op's name stack (an inner jit's own location is its name alone)
    stacks = [name.split("/") for name in
              re.findall(r'loc\("(jit\(<lambda>\)/[^"]*)"\(', text)]
    assert len(stacks) > 20
    for stack in stacks:
        # [jit(<lambda>), the row's scopes ..., inner scopes, the primitive]
        assert tuple(stack[1:1 + len(scopes)]) == scopes, stack
    inner = {part for stack in stacks for part in stack[1 + len(scopes):-1]
             if part.startswith("cake.")}
    assert all(part.startswith(scopes[-1] + ".") for part in inner), inner
    second = {s[2] for s in stacks if len(s) > 2}
    for nested in ("cake.attn.linear", "cake.attn.retention",
                   "cake.attn.latent"):
        assert (nested in second) == (scopes[-1] == nested)


def _sources():
    return sorted(PACKAGE.rglob("*.py"))


def test_no_file_but_the_table_compares_a_layer_kind():
    """The next arm cannot be added in the old places unnoticed: outside
    mixers.py and config.py (which builds the specs) nothing compares
    `spec.kind` with a recurrent kind's name, tests a params tree for a
    mixer's key or indexes it by one."""
    kind = re.compile(
        r"""\.kind\s*(==|!=|in|not\s+in)\s*[(\[]?\s*["'](linear|mamba|retention|latent)["']"""
        r"""|["'](linear|mamba|retention|latent)["']\s*(==|!=)\s*\w+\.kind""")
    key = re.compile(
        r"""["'](mamba|linear_attn)["']\s+(not\s+)?in\s+\w"""
        r"""|\w\[["'](mamba|linear_attn)["']\]""")
    found = []
    for path in _sources():
        if path.name in ("mixers.py", "config.py"):
            continue
        for n, line in enumerate(path.read_text().splitlines(), 1):
            if kind.search(line) or key.search(line):
                found.append(f"{path.relative_to(ROOT)}:{n}: {line.strip()}")
    assert not found, "\n".join(found)


def test_only_the_table_imports_a_familys_row_or_its_functions():
    row_fn = re.compile(r"^(init_\w+_params|\w+_forward)$")
    found = []
    for path in _sources():
        for node in ast.walk(ast.parse(path.read_text())):
            if not (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.split(".")[-1] in FAMILY_MODULES):
                continue
            for alias in node.names:
                if alias.name == "MIXER" and path.name != "mixers.py" \
                        or row_fn.match(alias.name):
                    found.append(f"{path.relative_to(ROOT)}:{node.lineno}: "
                                 f"{alias.name} from {node.module}")
    assert not found, "\n".join(found)


# ModelConfig.attention_kinds() of the benchmark configurations and of the
# tiny configs that have a delta-rule, a Mamba or a retention layer: the six
# of PR 51 as its parent returned them, Brumby's since PR 53 (what /health
# and the flight record print)
def _attn(kind, layers, heads, kv_heads, window, rotary_dim, rope_theta,
          rope_scaling):
    return {"kind": kind, "layers": layers, "heads": heads,
            "kv_heads": kv_heads, "window": window, "rotary_dim": rotary_dim,
            "rope_theta": rope_theta, "rope_scaling": rope_scaling}


def _linear(layers, heads, key_dim, value_dim, decay, conv_kernel,
            state_bytes):
    return {"kind": "linear", "layers": layers, "heads": heads,
            "key_dim": key_dim, "value_dim": value_dim, "decay": decay,
            "conv_kernel": conv_kernel, "state_bytes": state_bytes}


KINDS = {
    "jamba2-3b": [_attn("full", 2, 20, 1, None, 0, None, None)],
    "laguna-s-2.1-l9-ep16": [
        _attn("full", 3, 48, 8, None, 64, 500000.0, "yarn"),
        _attn("swa", 6, 72, 8, 512, 128, 10000.0, None)],
    "mimo-v2.5-l7-ep16": [
        _attn("full", 2, 64, 4, None, 64, 10000000.0, "default"),
        _attn("swa", 5, 64, 8, 128, 64, 10000.0, None)],
    "qwen3-30b-a3b-l8": [_attn("full", 8, 32, 4, None, 128, 1000000.0, None)],
    "qwen3-4b": [_attn("full", 36, 32, 8, None, 128, 1000000.0, None)],
    "solar-open2-l8-ep32": [
        _attn("full", 2, 64, 8, None, 0, None, None),
        _linear(6, 64, 128, 128, "channel", 4, 25165824)],
    "brumby-14b-l8": [
        {"kind": "retention", "layers": 8, "power": 2, "heads": 40,
         "kv_heads": 8, "key_dim": 128, "state_width": 8256,
         "padded_width": 8320, "rotary_dim": 128, "rope_theta": 1000000.0,
         "state_bytes": 274759680}],
    "deepseek-v2-l5-ep8": [
        {"kind": "latent", "layers": 5, "heads": 128, "q_lora_rank": 1536,
         "kv_lora_rank": 512, "qk_nope_head_dim": 128,
         "qk_rope_head_dim": 64, "v_head_dim": 128, "row_width": 576,
         "row_lanes": 640, "rotary_dim": 64, "rope_theta": 10000.0,
         "rope_scaling": "yarn", "row_bytes": 5760}],
    "tiny:deepseek_v2": [
        {"kind": "latent", "layers": 3, "heads": 4, "q_lora_rank": 24,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "row_width": 40, "row_lanes": 128,
         "rotary_dim": 8, "rope_theta": 10000.0, "rope_scaling": "yarn",
         "row_bytes": 240}],
    "tiny:brumby": [
        {"kind": "retention", "layers": 4, "power": 2, "heads": 4,
         "kv_heads": 2, "key_dim": 8, "state_width": 36,
         "padded_width": 128, "rotary_dim": 8, "rope_theta": 1000000.0,
         "state_bytes": 36864}],
    "tiny:jamba": [_attn("full", 1, 4, 2, None, 0, None, None)],
    "tiny:qwen3_5": [_linear(3, 4, 16, 16, "head", 4, 12288),
                     _attn("full", 1, 4, 2, None, 4, 10000.0, None)],
    "tiny:solar_open2": [_attn("full", 2, 4, 2, None, 0, None, None),
                         _linear(6, 4, 16, 16, "channel", 4, 24576)],
}


@pytest.mark.parametrize("name", sorted(KINDS))
def test_attention_kinds_reports_what_it_reported(name):
    if name.startswith("tiny:"):
        cfg = tiny_config(name[5:])
    else:
        with open(ROOT / "benchmark" / "configs" / f"{name}.json") as f:
            cfg = config_from_hf_dict(json.load(f))
    got = cfg.attention_kinds()
    assert json.dumps(got) == json.dumps(KINDS[name])


@pytest.mark.parametrize("arch", ["qwen3", "mimo_v2", "deepseek_v2"])
def test_a_pools_blocks_and_a_rows_buffer_hold_the_same_leaves(arch):
    """cache.positional_leaves is the one spelling: the paged pool's blocks
    and a row's buffer agree past the two axes in front of a position
    (MiMo-V2: keys joined in both; DeepSeek-V2: one latent a position)."""
    cfg = tiny_config(arch)
    pool, rows = cache_mod.init_paged_layers(cfg, 6, 8, 2, 32, jnp.float32)
    for i, spec in enumerate(cfg.layer_specs()):
        row = cache_mod.init_layer_cache(cfg, spec, 2, 32, jnp.float32)
        blocks = pool[i] or rows[i]
        assert bool(pool[i]) == cache_mod.layer_is_pooled(spec)
        assert blocks.keys() == row.keys() == (
            {"kv", "pos"} if arch == "deepseek_v2" else {"k", "v", "pos"})
        for name in row:
            assert blocks[name].shape[2:] == row[name].shape[2:]
            assert blocks[name].dtype == row[name].dtype
        if pool[i]:
            assert pool[i]["pos"].shape == (6, 8)
            assert int(pool[i]["pos"].max()) == -1
