"""Pallas dispatch/combine kernels vs the jnp references (interpret mode):
forward bit-for-bit under exact arithmetic, VJP vs autodiff'd jnp path, and
gradient parity of the full MoE layer (ragged custom VJP + dispatch/combine
custom VJP) against the dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core import dispatch as dsp
from repro.core import moe as M
from repro.kernels import dispatch_pallas as dp
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh


def _exact_case(seed, T=24, K=2, E=4, d=16, bm=8):
    """Inputs whose products/sums are exact in float32, so parity between
    kernel and reference is bit-for-bit regardless of FMA contraction."""
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.permutation(E)[:K] for _ in range(T)]).astype(np.int32)
    x = jnp.asarray(rng.integers(-8, 8, (T, d)), jnp.float32)
    w = jnp.asarray(2.0 ** rng.integers(-2, 2, (T, K)), jnp.float32)
    R = T * K + E * bm
    R = -(-R // bm) * bm
    plan = dsp.make_ragged_plan(jnp.asarray(idx), E, R, bm)
    return x, w, plan, R


@pytest.mark.parametrize("seed", range(4))
def test_scatter_kernel_bitexact(seed):
    x, w, plan, R = _exact_case(seed)
    K = plan.slots.shape[1]
    pos = dsp.invert_slots(plan.slots, R)
    src = jnp.where(pos >= 0, pos // K, -1)
    out_k = dp.scatter_rows(x, src, plan.total_rows, interpret=True)
    out_r = ref.scatter_rows_ref(x, src, plan.total_rows)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    # and matches the production jnp scatter path
    np.testing.assert_array_equal(
        np.asarray(out_k), np.asarray(dsp.scatter_rows_flat(x, plan.slots, R)))


@pytest.mark.parametrize("seed", range(4))
def test_gather_kernel_bitexact(seed):
    x, w, plan, R = _exact_case(seed)
    K = plan.slots.shape[1]
    pos = dsp.invert_slots(plan.slots, R)
    src = jnp.where(pos >= 0, pos // K, -1)
    buf = dp.scatter_rows(x, src, plan.total_rows, interpret=True)
    out_k = dp.gather_combine(buf, plan.slots, w, interpret=True)
    out_r = ref.gather_combine_ref(buf, plan.slots, w)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    np.testing.assert_array_equal(
        np.asarray(out_k),
        np.asarray(dsp.gather_rows_flat(buf, plan.slots, w)))


def test_scatter_predication_skips_blocks_past_total_rows():
    """Garbage in src past total_rows must not leak into the buffer."""
    x, w, plan, R = _exact_case(0)
    K = plan.slots.shape[1]
    pos = dsp.invert_slots(plan.slots, R)
    src = jnp.where(pos >= 0, pos // K, -1)
    tr = int(plan.total_rows)
    bm = 8
    # poison src in the dead region ON a block boundary past total_rows
    dead_start = -(-tr // bm) * bm
    if dead_start < R:
        src = src.at[dead_start:].set(0)
        out = dp.scatter_rows(x, src, tr, interpret=True)
        assert (np.asarray(out)[dead_start:] == 0).all()


@pytest.mark.parametrize("seed", range(3))
def test_dispatch_combine_vjp_matches_jnp(seed):
    """grad through the Pallas custom-VJP pair == grad through the plain
    jnp scatter/gather (autodiff) for x AND combine weights."""
    rng = np.random.default_rng(seed)
    T, K, E, d, bm = 16, 2, 4, 8, 4
    idx = np.stack([rng.permutation(E)[:K] for _ in range(T)]).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    w = jnp.asarray(rng.random((T, K)), jnp.float32)
    R = T * K + E * bm
    R = -(-R // bm) * bm
    plan = dsp.make_ragged_plan(jnp.asarray(idx), E, R, bm)

    def loss(x, w, use_pallas):
        buf = ops.dispatch_rows(x, plan.slots, R, total_rows=plan.total_rows,
                                use_pallas=use_pallas, interpret=use_pallas,
                                block_m=bm)
        y = ops.combine_rows(buf * 2.0, plan.slots, w,
                             use_pallas=use_pallas, interpret=use_pallas,
                             block_t=bm)
        return (y ** 2).sum()

    gp = jax.grad(lambda x, w: loss(x, w, True), argnums=(0, 1))(x, w)
    gj = jax.grad(lambda x, w: loss(x, w, False), argnums=(0, 1))(x, w)
    for a, b in zip(gp, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _uneven_router(params, E):
    """Bias the router so expert loads are strongly uneven."""
    w = np.array(params["router"]["w"])
    w[:, 0] += 2.0  # expert 0 hoovers up most tokens
    params["router"]["w"] = jnp.asarray(w)
    return params


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_grad_parity_vs_dense_oracle(top_k):
    """grad of the full layer through the ragged custom VJP + the new
    dispatch/combine custom VJP (EP on a 1x1 mesh, Pallas interpret) matches
    the dense oracle, under deliberately uneven expert loads."""
    cfg = MoEConfig(num_experts=4, top_k=top_k, d_ff_expert=32)
    params = M.init_moe(jax.random.PRNGKey(0), 16, cfg)
    params = _uneven_router(params, cfg.num_experts)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx_pallas = M.DistContext(mesh=mesh, moe_chunks=2,
                               moe_strategy="ep_shardmap", moe_ragged=True,
                               use_pallas=True, pallas_interpret=True)
    ctx_dense = M.DistContext(moe_strategy="dense")

    def loss(p, ctx):
        y, _ = M.moe_ffn(p, x, cfg, ctx)
        return (y ** 2).sum()

    g1 = jax.grad(lambda p: loss(p, ctx_pallas))(params)
    g2 = jax.grad(lambda p: loss(p, ctx_dense))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("top_k", [1, 2])
def test_ragged_ffn_vjp_vs_dense_oracle(top_k):
    """grad of the ragged custom VJP (_ragged_ffn_kernel) alone vs the dense
    einsum oracle on the same routed layout, uneven loads, interpret mode."""
    rng = np.random.default_rng(0)
    T, E, d, f, bm = 32, 4, 16, 32, 8
    K = top_k
    # uneven: most tokens on expert 0
    idx = np.where(rng.random((T, K)) < 0.7, 0,
                   rng.integers(0, E, (T, K))).astype(np.int32)
    if K == 2:  # keep the two picks distinct
        idx[:, 1] = (idx[:, 0] + 1 + idx[:, 1] % (E - 1)) % E
    R = T * K + E * bm
    R = -(-R // bm) * bm
    plan = dsp.make_ragged_plan(jnp.asarray(idx), E, R, bm)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    w3 = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32)

    def loss(x, w1, w3, w2, use_pallas):
        buf = ops.dispatch_rows(x, plan.slots, R, total_rows=plan.total_rows,
                                use_pallas=use_pallas, interpret=use_pallas)
        h = ops.ragged_expert_ffn(buf, w1, w3, w2, plan.block_to_expert,
                                  plan.total_rows, block_m=bm,
                                  use_pallas=use_pallas, interpret=use_pallas)
        y = ops.combine_rows(h, plan.slots, use_pallas=use_pallas,
                             interpret=use_pallas)
        return (y ** 2).sum()

    gp = jax.grad(lambda *a: loss(*a, True), argnums=(0, 1, 2, 3))(
        x, w1, w3, w2)
    gd = jax.grad(lambda *a: loss(*a, False), argnums=(0, 1, 2, 3))(
        x, w1, w3, w2)
    for a, b in zip(gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-4)
