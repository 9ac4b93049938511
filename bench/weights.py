"""Seeded random weights, made on the device in one jitted call, in the
layout and dtype the program serves them in.

The benchmark makes the weights and hands them to the program and to the
plain reference alike, so the reference takes nothing the program made.
Each leaf is keyed by its path, so a leaf's values do not depend on which
other leaves exist; leaves stacked over scanned periods draw one period per
``lax.map`` step, which keeps the draw from holding a second copy of the
largest leaf.  Scales follow the usual fan-in rule, the embedding's is the
configuration's ``bench.embed_std``; norm scales are drawn around 1 so that
a path that skips a norm's scale shows in the comparison.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import seed_sequence


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that uses every bit of ``seed`` (PRNGKey keeps only 32)."""
    words = seed_sequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _path_str(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def _scale(path: str, shape: tuple, embed_std: float) -> float:
    """``embed_std`` for the embedding, fan-in ** -0.5 for an (in, out)
    matrix."""
    return embed_std if path == "embed" else shape[-2] ** -0.5


def _draw(key, path: str, shape: tuple, dtype, embed_std: float):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "bias":
        x = jnp.zeros(shape, jnp.float32)
    else:
        x = jax.random.normal(key, shape, jnp.float32) * _scale(
            path, shape, embed_std)
    return x.astype(dtype)


def make(shapes, seed: int, embed_std: float):
    """A tree like ``shapes`` (a pytree of ``ShapeDtypeStruct``, e.g. from
    ``jax.eval_shape`` of the program's initialiser) filled from ``seed``.
    Leaves under ``periods/`` have a leading period axis (the program scans
    over stacked periods) and are drawn one period at a time."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = []
        for path, sds in flat:
            p = _path_str(path)
            k = jax.random.fold_in(key, zlib.crc32(p.encode()))
            if p.startswith("periods/"):
                n = sds.shape[0]
                leaves.append(jax.lax.map(
                    lambda i, k=k, p=p, sds=sds: _draw(
                        jax.random.fold_in(k, i), p, sds.shape[1:],
                        sds.dtype, embed_std),
                    jnp.arange(n)))
            else:
                leaves.append(_draw(k, p, sds.shape, sds.dtype, embed_std))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
