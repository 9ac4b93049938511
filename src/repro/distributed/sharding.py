"""Logical sharding rules -> NamedShardings, divisibility-guarded.

Rules (docs/DESIGN.md §5): vocab/heads/d_ff/experts shard over ``model``;
batch over ``("pod","data")``; long-context decode caches shard their
*sequence* dim over the data axes instead (batch=1).  Any dim that does not
divide its axis is replicated — exercised per arch by
tests/test_sharding_rules.py (docs/DESIGN.md §5) so the roofline table can
call out the fallbacks (e.g. mixtral's 8 experts on a 16-wide axis,
whisper's 51865 vocab).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
    return n


def guarded(mesh: Mesh, dim: int, axes) -> Optional[object]:
    """Return ``axes`` if ``dim`` divides their product, else None (replicate)."""
    return axes if dim % axis_size(mesh, axes) == 0 else None


def batch_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _leaf_spec(path: str, shape: tuple, mesh: Mesh, cfg: ModelConfig) -> P:
    """Sharding rule for one parameter leaf, keyed on its tree path."""
    m = "model"

    def g(dim_idx: int, axes):
        return guarded(mesh, shape[dim_idx], axes)

    if "embed" in path or "pos_embed" in path:
        return P(g(0, m), None)
    if path.endswith("head"):
        return P(None, g(1, m))
    # MoE experts: (E, d, f) / (E, f, d) — expert dim over model when possible,
    # else fall back to sharding the ffn dim (tp_gspmd strategy).
    if any(f"'{w}'" in path for w in ("w1", "w2", "w3")) and len(shape) == 3:
        if shape[0] % axis_size(mesh, m) == 0:
            return P(m, None, None)
        big = 1 if shape[1] > shape[2] else 2
        return P(None, *((g(1, m), None) if big == 1 else (None, g(2, m))))
    if "router" in path:
        return P(None) if len(shape) == 1 else P(None, None)
    if "conv" in path:
        return P(*([None] * len(shape)))
    # attention / dense mlp / shared expert / ssm 2-D weights: shard the big dim
    if len(shape) == 2:
        if "wo" in path or "out_proj" in path or path.endswith("'w2'"):
            return P(g(0, m), None)            # row-parallel (input sharded)
        return P(None, g(1, m))                # column-parallel
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    return "/".join(str(p) for p in path)


def param_shardings(params, mesh: Mesh, cfg: ModelConfig):
    """NamedShardings for a parameter pytree (stacked period dims handled:
    leaves under 'periods' have a leading stack dim that stays replicated)."""

    def spec_for(path, leaf):
        p = _path_str(path)
        shape = leaf.shape
        if "periods" in p and len(shape) >= 1:
            inner = _leaf_spec(p, shape[1:], mesh, cfg)
            return P(None, *inner)
        return _leaf_spec(p, shape, mesh, cfg)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for(path, leaf)), params)


def act_pspec(mesh: Mesh, batch: int) -> P:
    ba = batch_axes(mesh)
    ba = guarded(mesh, batch, ba)
    return P(ba, None, None)


def logits_pspec(mesh: Mesh, batch: int, vocab: int) -> P:
    ba = guarded(mesh, batch, batch_axes(mesh))
    return P(ba, None, guarded(mesh, vocab, "model"))


def context_shardings(mesh: Mesh, cfg: ModelConfig, batch: int,
                      seq_axis=None) -> dict:
    """The ``DistContext`` fields that place a step's activations on
    ``mesh``: batch axes, (B, S, d) activations, (B, S, V) logits and
    (B, S, H, hd) attention heads.  ``seq_axis`` additionally shards the
    activations' sequence dim (sequence parallelism)."""
    ba = guarded(mesh, batch, batch_axes(mesh))
    return dict(
        batch_axes=batch_axes(mesh),
        act_pspec=NamedSharding(mesh, P(ba, seq_axis, None)),
        logits_pspec=NamedSharding(mesh, logits_pspec(mesh, batch,
                                                      cfg.padded_vocab)),
        heads_pspec=NamedSharding(mesh, P(ba, None, "model", None)))


def batch_pspec(mesh: Mesh, batch: int) -> P:
    ba = guarded(mesh, batch, batch_axes(mesh))
    return P(ba, None)


def cache_pspec(mesh: Mesh, leaf_shape: tuple, batch: int) -> P:
    """Decode caches: shard the batch dim over the data axes when divisible
    (handling the leading period-stack dim of scanned layers), else shard the
    largest (sequence) dim — the single-sequence long-context case."""
    ba = batch_axes(mesh)
    n = axis_size(mesh, ba)
    dims: list = [None] * len(leaf_shape)
    if n <= 1 or not leaf_shape:
        return P(*dims)
    for i, d in enumerate(leaf_shape[:2]):        # batch is dim 0, or dim 1
        if d == batch and batch % n == 0:         # after a period-stack dim
            dims[i] = ba
            return P(*dims)
    big = max(range(len(leaf_shape)), key=lambda i: leaf_shape[i])
    if leaf_shape[big] % n == 0 and leaf_shape[big] >= n:
        dims[big] = ba                             # long_500k: shard sequence
    return P(*dims)
