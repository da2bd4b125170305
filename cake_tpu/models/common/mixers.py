"""What a layer kind is, in one place: the mixer a layer runs between its
norms (attention over a cache addressed by position, or a recurrence over a
state of fixed size), named as one record of the functions its family
module already has. The block (layers.py), the cache (cache.py), the
loader and the exporter (utils/) ask `mixer_of` and branch on nothing else,
so a new layer kind is a row in its own module and an arm here.

Rows: attention, `full` and `swa` (layers.MIXER), the gated delta net
(qwen3_5.MIXER), Kimi Delta Attention (kda.MIXER), Mamba (jamba.MIXER), power
retention (brumby.MIXER), latent attention (deepseek_v2.MIXER).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from .config import LayerSpec, ModelConfig


@dataclasses.dataclass(frozen=True)
class Mixer:
    # the layer's params leaf and its checkpoint sub-key
    param_key: str
    # the named scopes its forward runs under, outermost first: a device
    # trace's readers sum time by them (obs.spans.SCOPE_CATALOG)
    scopes: tuple[str, ...]
    # its cache has no `pos` leaf (cache.is_positional is False): what
    # config.LayerSpec.recurrent says of the layers that run it
    recurrent: bool
    init_params: Callable       # (cfg, spec, key, dtype) -> dict
    load_params: Callable       # (loader, layer_prefix, spec) -> dict
    export_params: Callable     # (cfg, p, layer_prefix) -> {name: ndarray}
    init_cache: Callable        # (cfg, spec, batch, max_seq_len, dtype) -> dict
    # (cfg, spec, p, x, lc, pos0, rope, valid_len, flash_mode, mesh)
    # -> (y, lc)
    forward: Callable
    # (cfg, spec) -> ONE layer's entry of ModelConfig.attention_kinds(),
    # which adds up the layers whose entries are otherwise equal; None: the
    # kind is not reported there
    describe: Callable | None = None


def mixer_of(cfg: ModelConfig, spec: LayerSpec) -> Mixer:
    """The one place that reads `spec.kind` (and what a config says of its
    linear layers) to choose a mixer. The imports are lazy: a family's code
    stays out of the others' import path."""
    if spec.kind == "mamba":
        from ..jamba import MIXER
    elif spec.kind == "retention":
        from ..brumby import MIXER
    elif spec.kind == "latent":
        from ..deepseek_v2 import MIXER
    elif spec.kind == "linear" and cfg.linear_attn.kda:
        from ..kda import MIXER
    elif spec.kind == "linear":
        from ..qwen3_5 import MIXER
    else:
        from .layers import MIXER
    return MIXER
