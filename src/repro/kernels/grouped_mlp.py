"""Pallas TPU kernels: grouped (per-expert) blocked matmul and fused SwiGLU.

TPU adaptation of the expert-FFN hot spot (docs/DESIGN.md §6): the dispatched
buffer (E, C, d) is contracted against stacked expert weights with a
(E, C/bm, N/bn, K/bk) grid.  The K loop is innermost so the (bm, bn) output
tile stays resident in VMEM (revisited across k steps) and accumulates in
fp32 scratch; tiles are MXU-aligned multiples of 128 where shapes allow.

Tile sizes resolve through the measured autotuner cache (docs/DESIGN.md
§Autotune) with heuristic defaults as the cold-cache fallback; operands are
zero-padded to the chosen block multiples (exact under contraction, padded
output rows/cols sliced off), so ANY block size is legal — no sub-lane tiles
on prime dims, and the autotuner searches a free grid.

On the CPU (``JAX_PLATFORMS=cpu``) the tests validate the kernels with
``interpret=True`` against ``ref.py`` (Pallas does not lower to the CPU
backend otherwise); on a TPU they compile for the chip
(tests/test_tpu_compile.py, ``chip_smoke.py``).  ``ops.py`` selects the jnp
reference path unless ``use_pallas`` is set.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import choose_block, resolve_tiles

_DEFAULTS = {"bm": 128, "bn": 128, "bk": 512}


def _padded_operands(op, x, w_list, block_m, block_n, block_k):
    """Resolve tiles and zero-pad (E, M, K) x and (E, K, N) weights."""
    E, M, K = x.shape
    N = w_list[0].shape[2]
    tiles = resolve_tiles(op, (E, M, K, N), x.dtype, _DEFAULTS,
                          {"bm": block_m, "bn": block_n, "bk": block_k})
    cm = choose_block(M, tiles["bm"])
    cn = choose_block(N, tiles["bn"])
    ck = choose_block(K, tiles["bk"])
    if (cm.padded, ck.padded) != (M, K):
        x = jnp.pad(x, ((0, 0), (0, cm.padded - M), (0, ck.padded - K)))
    if (ck.padded, cn.padded) != (K, N):
        w_list = [jnp.pad(w, ((0, 0), (0, ck.padded - K), (0, cn.padded - N)))
                  for w in w_list]
    return x, w_list, cm, cn, ck


def _matmul_kernel(x_ref, w_ref, o_ref, acc, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(x_ref[0], w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _swiglu_kernel(x_ref, w1_ref, w3_ref, o_ref, acc1, acc3, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc1[...] = jnp.zeros_like(acc1)
        acc3[...] = jnp.zeros_like(acc3)

    acc1[...] += jnp.dot(x_ref[0], w1_ref[0], preferred_element_type=jnp.float32)
    acc3[...] += jnp.dot(x_ref[0], w3_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[0] = (jax.nn.silu(acc1[...]) * acc3[...]).astype(o_ref.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, *, block_m: int | None = None,
                   block_n: int | None = None, block_k: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """x: (E, M, K) @ w: (E, K, N) -> (E, M, N), one expert per grid row."""
    E, M, K = x.shape
    _, _, N = w.shape
    xp, (wp,), cm, cn, ck = _padded_operands(
        "grouped_matmul", x, [w], block_m, block_n, block_k)
    bm, bn, bk = cm.block, cn.block, ck.block
    grid = (E, cm.grid, cn.grid, ck.grid)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=ck.grid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, cm.padded, cn.padded), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp)
    return out[:, :M, :N]


def grouped_swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, *,
                   block_m: int | None = None, block_n: int | None = None,
                   block_k: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """Fused silu(x@w1) * (x@w3) per expert: (E, M, K) -> (E, M, N)."""
    E, M, K = x.shape
    _, _, N = w1.shape
    xp, (w1p, w3p), cm, cn, ck = _padded_operands(
        "grouped_swiglu", x, [w1, w3], block_m, block_n, block_k)
    bm, bn, bk = cm.block, cn.block, ck.block
    grid = (E, cm.grid, cn.grid, ck.grid)
    out = pl.pallas_call(
        functools.partial(_swiglu_kernel, n_k=ck.grid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, cm.padded, cn.padded), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=interpret,
    )(xp, w1p, w3p)
    return out[:, :M, :N]
