"""Sharded training step (beyond-parity: the reference is inference-only).

A full next-token-prediction step — forward, cross-entropy, grads, AdamW —
jitted over the mesh with the same GSPMD param shardings the inference path
uses (tp for matmuls, dp for the batch). Exists so the framework's sharding
layout is exercised under both dispatch directions (forward + backward
collectives) and validated by dryrun_multichip on a virtual mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.common.config import ModelConfig
from ..models.common.layers import forward_train
from .sharding import params_shardings


def loss_fn(cfg: ModelConfig, params, tokens):
    """Causal LM loss: predict tokens[:, 1:] from tokens[:, :-1]."""
    logits = forward_train(cfg, params, tokens[:, :-1])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def make_train_step(cfg: ModelConfig, mesh: Mesh, params,
                    learning_rate: float = 1e-4):
    """Returns (train_step, opt_state). train_step(params, opt_state, tokens)
    -> (params, opt_state, loss), jitted with sharded in/out."""
    tx = optax.adamw(learning_rate)

    # params arrive already committed to params_shardings layouts
    # (shard_params) — leave their in_shardings UNSPECIFIED so the step
    # follows the committed layout instead of re-declaring it: with an
    # explicit respec, GSPMD may hand back a propagated layout for a
    # donated buffer (e.g. a tied embed row-sharded by the lm_head
    # matmul) and the second step either raises an in_shardings/arg
    # mismatch or breaks donation aliasing. Committing the
    # params here keeps the first/steady-state layouts identical.
    params = jax.device_put(params, params_shardings(params, mesh))
    opt_state = tx.init(params)
    tok_shard = NamedSharding(mesh, P("dp" if "dp" in mesh.axis_names else None,
                                      None))

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       in_shardings=(None, None, tok_shard))
    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step, opt_state
