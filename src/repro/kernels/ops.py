"""Jit-friendly wrappers that select the Pallas kernel or the jnp reference.

``use_pallas`` defaults to False: the CPU backend (the tests, under
``JAX_PLATFORMS=cpu``, and the dry-run) runs Pallas only in interpret
mode, which the tests request explicitly.  On a TPU the kernels compile
for the chip (``--use-pallas`` in the launchers; ``chip_smoke.py`` runs the
grouped expert FFN compiled), and nothing there passes ``interpret=True``.
``tests/test_tpu_compile.py`` records which kernels the v5e compiler
accepts at mixtral widths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch_pallas as dp
from repro.kernels import ref
from repro.kernels.grouped_mlp import grouped_matmul, grouped_swiglu
from repro.kernels.ragged_mlp import ragged_matmul, ragged_swiglu


def _f0(v):
    return np.zeros(v.shape, jax.dtypes.float0)


def expert_ffn(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array, *,
               use_pallas: bool = False, interpret: bool = False) -> jax.Array:
    """Per-expert SwiGLU FFN over dispatched buffers.

    x: (..., E, C, d); w1, w3: (E, d, f); w2: (E, f, d) -> (..., E, C, d).
    Leading batch dims are vmapped over for the kernel path.
    """
    if not use_pallas:
        return ref.expert_ffn_ref(x, w1, w3, w2)

    def one(xb):
        h = grouped_swiglu(xb, w1, w3, interpret=interpret)
        return grouped_matmul(h, w2, interpret=interpret)

    fn = one
    for _ in range(x.ndim - 3):
        fn = jax.vmap(fn)
    return fn(x)


# ---------------------------------------------------------------------------
# dispatch / combine with a transpose-symmetric custom VJP
#
# Combine is the exact transpose of dispatch, so instead of letting autodiff
# transpose a scatter (serialized scatter HLO + a (G, cap, d) residual graph),
# dispatch-backward *calls the combine kernel* and combine-backward *calls the
# dispatch kernel*; the router-weight grad is a segment dot.  The only arrays
# saved for backward are the int32 index maps (and, for combine, its own
# primal inputs) — no dispatch buffer survives autodiff.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch_k(x, slots, src, block_m, interpret):
    # slots is residual-only (consumed by the backward gather)
    return dp.scatter_rows(x, src, src.shape[0], block_m=block_m,
                           interpret=interpret)


def _dispatch_fwd(x, slots, src, block_m, interpret):
    return _dispatch_k(x, slots, src, block_m, interpret), (slots, src)


def _dispatch_bwd(block_m, interpret, res, g):
    slots, src = res
    # transpose of scatter = gather: dx[t] = sum_k g[slot[t, k]]
    dx = dp.gather_combine(g, slots, None, block_t=block_m,
                           interpret=interpret)
    return dx, _f0(slots), _f0(src)


_dispatch_k.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combine_k(buf, slots, weights, total_rows, block_t, interpret):
    return dp.gather_combine(buf, slots, weights, block_t=block_t,
                             interpret=interpret)


def _combine_fwd(buf, slots, weights, total_rows, block_t, interpret):
    y = _combine_k(buf, slots, weights, total_rows, block_t, interpret)
    return y, (buf, slots, weights, total_rows)


def _combine_bwd(block_t, interpret, res, g):
    buf, slots, weights, total_rows = res
    T, K = slots.shape
    R = buf.shape[0]
    from repro.core.dispatch import invert_slots
    # transpose of gather = scatter, with the combine weight riding along:
    # dbuf[r] = w_flat[pos(r)] * g[token(r)].  With a prefix layout
    # (ragged), total_rows predicates off the dead row-blocks.
    pos = invert_slots(slots, R)                           # (R,) flat (t*K+k)
    src_tok = jnp.where(pos >= 0, pos // K, -1)
    wslot = jnp.where(
        pos >= 0, jnp.take(weights.reshape(-1), jnp.maximum(pos, 0)), 0)
    dbuf = dp.scatter_rows(g, src_tok, total_rows, wslot, block_m=block_t,
                           interpret=interpret)
    # weight grad via a segment dot: dw[t,k] = <g[t], buf[slot[t,k]]>
    rows = jnp.take(buf, jnp.maximum(slots, 0), axis=0)    # (T, K, d)
    dw = jnp.einsum("td,tkd->tk", g.astype(jnp.float32),
                    rows.astype(jnp.float32))
    dw = jnp.where(slots >= 0, dw, 0.0).astype(weights.dtype)
    return dbuf, _f0(slots), dw, _f0(total_rows)


_combine_k.defvjp(_combine_fwd, _combine_bwd)


def dispatch_rows(x: jax.Array, slots: jax.Array, rows: int,
                  total_rows=None, *, use_pallas: bool = False,
                  interpret: bool = False, block_m: int = 8) -> jax.Array:
    """Build the (rows, d) dispatch buffer from x (T, d) and the planner's
    slot map (T, K).  Pallas path: scalar-prefetched gather-formulated
    scatter with row-block predication past ``total_rows`` and a custom VJP
    whose backward is the combine kernel."""
    if not use_pallas:
        from repro.core.dispatch import scatter_rows_flat
        return scatter_rows_flat(x, slots, rows)
    from repro.core.dispatch import invert_slots
    K = slots.shape[1]
    pos = invert_slots(slots, rows)
    src_tok = jnp.where(pos >= 0, pos // K, -1)
    if total_rows is not None:
        # predication hint: with a prefix layout, blocks past the routed load
        # are skipped entirely (issued copies track the ACTUAL load)
        src_tok = jnp.where(jnp.arange(rows) < jnp.asarray(total_rows),
                            src_tok, -1)
    return _dispatch_k(x, slots, src_tok, block_m, interpret)


def combine_rows(buf: jax.Array, slots: jax.Array,
                 weights: jax.Array | None = None, total_rows=None, *,
                 use_pallas: bool = False, interpret: bool = False,
                 block_t: int = 8) -> jax.Array:
    """Inverse of dispatch_rows: (rows, d) -> (T, d), each token the weighted
    sum of its K slot rows.  Pallas path: gather kernel with a custom VJP
    whose backward is the dispatch kernel (+ segment dot for the weights);
    pass ``total_rows`` for prefix (ragged) layouts so the backward scatter
    predicates off dead row-blocks."""
    if not use_pallas:
        from repro.core.dispatch import gather_rows_flat
        return gather_rows_flat(buf, slots, weights)
    T, K = slots.shape
    if weights is None:
        weights = jnp.ones((T, K), buf.dtype)
    total = jnp.asarray(buf.shape[0] if total_rows is None else total_rows,
                        jnp.int32)
    return _combine_k(buf, slots, weights, total, block_t, interpret)


def _segment_outer(a: jax.Array, b: jax.Array, b2e: jax.Array,
                   num_experts: int) -> jax.Array:
    """Per-expert sum of block outer products: dw[e] = sum_{blocks of e}
    a_block^T @ b_block.  A scan over blocks — never materialises a
    (n_blocks, d, f) tensor (the weight-gather trap of the jnp fallback)."""
    nb = b2e.shape[0]
    R = a.shape[0]
    ab = a.reshape(nb, R // nb, a.shape[1])
    bb = b.reshape(nb, R // nb, b.shape[1])
    acc0 = jnp.zeros((num_experts, a.shape[1], b.shape[1]), jnp.float32)

    def body(acc, inp):
        ai, bi, e = inp
        contrib = jnp.dot(ai.T, bi, preferred_element_type=jnp.float32)
        return acc.at[e].add(contrib), None

    acc, _ = jax.lax.scan(body, acc0, (ab, bb, b2e))
    return acc


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ragged_ffn_kernel(x, w1, w3, w2, b2e, rows, block_m, interpret):
    h = ragged_swiglu(x, w1, w3, b2e, rows, block_m=block_m,
                      interpret=interpret)
    return ragged_matmul(h, w2, b2e, rows, block_m=block_m,
                         interpret=interpret)


def _ragged_ffn_fwd(x, w1, w3, w2, b2e, rows, block_m, interpret):
    y = _ragged_ffn_kernel(x, w1, w3, w2, b2e, rows, block_m, interpret)
    return y, (x, w1, w3, w2, b2e, rows)


def _ragged_ffn_bwd(block_m, interpret, res, gy):
    x, w1, w3, w2, b2e, rows = res
    E = w1.shape[0]
    mm = functools.partial(ragged_matmul, block_to_expert=b2e,
                           total_rows=rows, block_m=block_m,
                           interpret=interpret)
    # recompute the two up-projections (chunk-recompute discipline: no (R, f)
    # residuals are ever stored)
    h1 = mm(x, w1).astype(jnp.float32)
    h3 = mm(x, w3).astype(jnp.float32)
    s = jax.nn.sigmoid(h1)
    silu_h1 = h1 * s
    a = (silu_h1 * h3).astype(x.dtype)
    da = mm(gy, jnp.swapaxes(w2, 1, 2)).astype(jnp.float32)
    dh3 = (da * silu_h1).astype(x.dtype)
    dh1 = (da * h3 * (s + silu_h1 * (1 - s))).astype(x.dtype)
    dx = (mm(dh1, jnp.swapaxes(w1, 1, 2))
          + mm(dh3, jnp.swapaxes(w3, 1, 2))).astype(x.dtype)
    dw1 = _segment_outer(x, dh1, b2e, E).astype(w1.dtype)
    dw3 = _segment_outer(x, dh3, b2e, E).astype(w3.dtype)
    dw2 = _segment_outer(a, gy, b2e, E).astype(w2.dtype)
    return dx, dw1, dw3, dw2, _f0(b2e), _f0(rows)


_ragged_ffn_kernel.defvjp(_ragged_ffn_fwd, _ragged_ffn_bwd)


def ragged_expert_ffn(x: jax.Array, w1: jax.Array, w3: jax.Array,
                      w2: jax.Array, block_to_expert: jax.Array,
                      total_rows, *, block_m: int = 128,
                      use_pallas: bool = False,
                      interpret: bool = False) -> jax.Array:
    """SwiGLU FFN over the MegaBlocks-style flat layout (kernels/ragged_mlp).

    x: (R, d) expert-grouped bm-aligned rows -> (R, d).  On TPU the kernel
    predicates off blocks past ``total_rows``, so issued MXU work scales with
    the ACTUAL routed load instead of the dropless worst case.  The Pallas
    path carries a custom VJP (pallas_call has no autodiff rule): backward
    recomputes the up-projections with the same kernels and accumulates
    weight grads with a per-block scan.
    """
    if not use_pallas:
        return ref.ragged_expert_ffn_ref(x, w1, w3, w2, block_to_expert,
                                         total_rows)
    rows = jnp.asarray(total_rows, jnp.int32)
    return _ragged_ffn_kernel(x, w1, w3, w2,
                              block_to_expert.astype(jnp.int32), rows,
                              block_m, interpret)


# ---------------------------------------------------------------------------
# fully fused MoE leg: dispatch -> SwiGLU -> down-proj -> combine in ONE
# kernel launch (kernels/fused_moe.py) — the (R, d) dispatch buffer never
# exists in HBM on the forward pass.  The custom VJP composes the transpose
# symmetry with chunk-recompute: combine-backward IS the dispatch kernel
# (scatter token grads, combine weight riding along), dispatch-backward IS
# the combine kernel (gather per-token sums), and the FFN interior is
# recomputed with the ragged kernels — so the buffer exists only transiently
# inside the backward, exactly as the three-launch path's VJP already does,
# and no (R, ·) residual is saved.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12))
def _fused_moe_k(x, w1, w3, w2, src, wslot, slots, b2e, rows,
                 has_weights, block_m, block_k, interpret):
    from repro.kernels.fused_moe import fused_moe
    return fused_moe(x, w1, w3, w2, src, wslot, rows, b2e,
                     block_k=block_k, interpret=interpret)


def _fused_moe_fwd(x, w1, w3, w2, src, wslot, slots, b2e, rows,
                   has_weights, block_m, block_k, interpret):
    y = _fused_moe_k(x, w1, w3, w2, src, wslot, slots, b2e, rows,
                     has_weights, block_m, block_k, interpret)
    # residuals: primal inputs + int32 maps only — no (R, ·) intermediate
    return y, (x, w1, w3, w2, src, wslot, slots, b2e, rows)


def _fused_moe_bwd(has_weights, block_m, block_k, interpret, res, gy):
    x, w1, w3, w2, src, wslot, slots, b2e, rows = res
    E = w1.shape[0]
    # combine-bwd = dispatch kernel: dL/dy[r] = wslot[r] * gy[token(r)]
    g_buf = dp.scatter_rows(gy, src, rows, wslot, block_m=block_m,
                            interpret=interpret)
    # dispatch recompute — the buffer exists only inside this backward
    buf = dp.scatter_rows(x, src, rows, block_m=block_m, interpret=interpret)
    mm = functools.partial(ragged_matmul, block_to_expert=b2e,
                           total_rows=rows, block_m=block_m,
                           interpret=interpret)
    h1 = mm(buf, w1).astype(jnp.float32)
    h3 = mm(buf, w3).astype(jnp.float32)
    s = jax.nn.sigmoid(h1)
    silu_h1 = h1 * s
    a = (silu_h1 * h3).astype(x.dtype)
    da = mm(g_buf, jnp.swapaxes(w2, 1, 2)).astype(jnp.float32)
    dh3 = (da * silu_h1).astype(x.dtype)
    dh1 = (da * h3 * (s + silu_h1 * (1 - s))).astype(x.dtype)
    dbuf = (mm(dh1, jnp.swapaxes(w1, 1, 2))
            + mm(dh3, jnp.swapaxes(w3, 1, 2))).astype(x.dtype)
    # dispatch-bwd = combine kernel: dx[t] = sum_k dbuf[slot[t, k]]
    dx = dp.gather_combine(dbuf, slots, None, interpret=interpret)
    dw1 = _segment_outer(buf, dh1, b2e, E).astype(w1.dtype)
    dw3 = _segment_outer(buf, dh3, b2e, E).astype(w3.dtype)
    dw2 = _segment_outer(a, g_buf, b2e, E).astype(w2.dtype)
    if has_weights:
        # d wslot[r] = <gy[token(r)], y[r]> — needs the FFN output, one
        # extra ragged matmul; skipped entirely when the combine is unweighted
        # (the EP local leg, where the router weight is applied later).
        # Evaluated in the SAME (T, K)-shaped einsum as _combine_bwd and then
        # permuted to rows, so the (T, K) router grad the outer transpose
        # reassembles is bit-identical to the three-launch path's.
        from repro.core.dispatch import invert_slots
        y_buf = mm(a, w2)                                  # == combine's buf
        rows_y = jnp.take(y_buf, jnp.maximum(slots, 0), axis=0)   # (T, K, d)
        dwtk = jnp.einsum("td,tkd->tk", gy.astype(jnp.float32),
                          rows_y.astype(jnp.float32))
        dwtk = jnp.where(slots >= 0, dwtk, 0.0).astype(wslot.dtype)
        pos = invert_slots(slots, wslot.shape[0])
        d_wslot = jnp.where(
            pos >= 0, jnp.take(dwtk.reshape(-1), jnp.maximum(pos, 0)),
            jnp.zeros((), wslot.dtype))
    else:
        d_wslot = jnp.zeros_like(wslot)
    return (dx, dw1, dw3, dw2, _f0(src), d_wslot, _f0(slots), _f0(b2e),
            _f0(rows))


_fused_moe_k.defvjp(_fused_moe_fwd, _fused_moe_bwd)


def moe_ffn(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array,
            slots: jax.Array, block_to_expert: jax.Array, total_rows,
            weights: jax.Array | None = None, *, block_m: int = 128,
            block_k: int | None = None, use_pallas: bool = False,
            interpret: bool = False) -> jax.Array:
    """The whole per-chunk expert leg in one launch: x (T, d) + slot map
    (T, K) -> (T, d) weighted expert-FFN combine, over the MegaBlocks-style
    flat layout described by ``block_to_expert``/``total_rows`` (buffer size
    R = len(block_to_expert) * block_m).

    Pallas path: kernels/fused_moe.py (persistent single launch; the (R, d)
    dispatch buffer never touches HBM on forward) with the transpose-
    symmetric chunk-recompute VJP above.  jnp path: the composed reference
    (scatter -> ragged FFN ref -> gather), autodiff'd as-is."""
    R = block_to_expert.shape[0] * block_m
    if not use_pallas:
        from repro.core.dispatch import scatter_rows_flat, gather_rows_flat
        buf = scatter_rows_flat(x, slots, R)
        y = ref.ragged_expert_ffn_ref(buf, w1, w3, w2, block_to_expert,
                                      total_rows)
        return gather_rows_flat(y, slots, weights)
    from repro.core.dispatch import invert_slots
    T, K = slots.shape
    # derive the row-side maps OUTSIDE the custom_vjp: wslot is a
    # differentiable gather of the router weights, so its cotangent
    # transposes back to (T, K) automatically
    pos = invert_slots(slots, R)
    src = jnp.where(pos >= 0, pos // K, -1)
    if weights is None:
        w_flat = jnp.ones((T * K,), x.dtype)
    else:
        w_flat = weights.reshape(-1)
    wslot = jnp.where(pos >= 0, jnp.take(w_flat, jnp.maximum(pos, 0)),
                      jnp.zeros((), x.dtype))
    return _fused_moe_k(x, w1, w3, w2, src, wslot,
                        slots.astype(jnp.int32),
                        block_to_expert.astype(jnp.int32),
                        jnp.asarray(total_rows, jnp.int32),
                        weights is not None, block_m, block_k, interpret)
