"""Loss and the train-step factory.

``make_train_step(cfg, ctx, ...)`` closes over a *static* FCDA schedule —
the global chunk count, or the full per-layer ``ScheduleSpec`` vector under
adaptive MACT (XLA requires it); the trainer keeps one compiled step per
schedule key and switches between them from the router-load feedback
(docs/DESIGN.md §2, §Adaptive).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.moe import DistContext
from repro.core.router import update_bias
from repro.models import transformer
from repro.optim.adamw import AdamWState, adamw_init, adamw_update


class TrainState(NamedTuple):
    params: dict
    opt: AdamWState
    step: jax.Array


def init_train_state(key: jax.Array, cfg: ModelConfig, dtype=None, *,
                     mesh=None) -> TrainState:
    """Parameters in ``dtype`` (default ``cfg.dtype``) plus zeroed AdamW
    moments, built under one jit; with ``mesh`` every leaf is born in its
    ``param_shardings`` layout (moments shard like their parameter)."""
    init = functools.partial(_init_train_state, cfg=cfg,
                             dtype=jnp.dtype(cfg.dtype if dtype is None
                                             else dtype))
    out_shardings = None
    if mesh is not None:
        out_shardings = train_state_shardings(jax.eval_shape(init, key), mesh,
                                              cfg)
    return jax.jit(init, out_shardings=out_shardings)(key)


def train_state_shardings(state: TrainState, mesh, cfg: ModelConfig):
    """NamedShardings for a ``TrainState`` (of arrays or shapes): parameters
    by ``param_shardings``, AdamW moments like their parameter, counters
    replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import param_shardings
    p_sh = param_shardings(state.params, mesh, cfg)
    rep = NamedSharding(mesh, P())
    return TrainState(params=p_sh, opt=AdamWState(step=rep, mu=p_sh, nu=p_sh),
                      step=rep)


def _init_train_state(key: jax.Array, cfg: ModelConfig, dtype) -> TrainState:
    params = transformer.init_params(key, cfg, dtype)
    return TrainState(params=params, opt=adamw_init(params), step=jnp.int32(0))


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over valid positions (labels < 0 are masked out)."""
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = (logz - gold) * valid
    return ce.sum() / jnp.maximum(valid.sum(), 1)


def loss_fn(params: dict, cfg: ModelConfig, ctx: DistContext, batch: dict):
    logits, stats = transformer.forward(params, cfg, ctx, batch)
    ce = cross_entropy(logits, batch["labels"])
    aux_coef = cfg.moe.router_aux_coef if cfg.moe else 0.0
    n_moe = max(1, transformer.num_moe_layers(cfg))
    aux = stats["aux_loss"] / n_moe
    loss = ce + aux_coef * aux
    m = {"ce": ce, "aux": aux, "load": stats["load"],
         "drops": stats["drops"]}
    if "load_per_layer" in stats:
        # (L_moe, E) per-layer routed-token histograms — the adaptive MACT
        # telemetry stream (core/telemetry.py)
        m["load_per_layer"] = stats["load_per_layer"]
    return loss, m


def make_train_step(cfg: ModelConfig, ctx: DistContext, *, lr=3e-4):
    """Returns step(state, batch) -> (state, metrics).  Jit separately with
    the desired in/out shardings."""

    def train_step(state: TrainState, batch: dict):
        (loss, m), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, cfg, ctx, batch)
        lr_val = lr if not callable(lr) else lr(state.step)
        params, opt, om = adamw_update(grads, state.opt, state.params, lr=lr_val)
        # DeepSeek-style loss-free bias balancing runs outside the gradient
        if cfg.moe is not None and cfg.moe.loss_free_bias:
            params = _update_router_biases(params, m["load"], cfg)
        metrics = {"loss": loss, **{k: v for k, v in m.items() if k != "load"},
                   "load": m["load"], **om, "lr": jnp.float32(lr_val)}
        return TrainState(params, opt, state.step + 1), metrics

    return train_step


def _update_router_biases(params: dict, load: jax.Array, cfg: ModelConfig):
    """Apply the loss-free bias update to every router in the tree (the summed
    global load is a shared signal — per-layer loads would need per-layer
    stats; adequate for balancing and matches the paper's 'untouched routing'
    constraint since biases only affect selection)."""

    def upd(path, leaf):
        keys = tuple(str(p) for p in path)
        if any("router" in k for k in keys) and any("bias" in k for k in keys):
            return update_bias(leaf, load, cfg.moe)
        return leaf

    return jax.tree_util.tree_map_with_path(upd, params)
