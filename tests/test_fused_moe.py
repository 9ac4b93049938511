"""Fused persistent MoE kernel + measured autotuner validation.

Interpret-mode parity of kernels/fused_moe.py against (a) the three-launch
Pallas path (dispatch_rows -> ragged_expert_ffn -> combine_rows) and (b) the
jnp references, forward AND grads, across dropless/skewed loads and the
ring-of-experts edge cases (empty expert, all-to-one routing).  Exact cases
use integer-valued inputs and power-of-two router weights so parity is
bit-for-bit (np.testing.assert_array_equal); see kernels/ref.py::
fused_moe_ref for the accumulation-order contract that makes this hold.

Also covers the autotuner cache round-trip: record -> lookup -> kernels
honor the winner; a corrupt or missing cache file silently falls back to the
heuristic defaults.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core import dispatch as dsp
from repro.core import moe as M
from repro.kernels import autotune, ref
from repro.kernels.fused_moe import fused_moe
from repro.kernels.ops import (combine_rows, dispatch_rows, moe_ffn,
                               ragged_expert_ffn)
from repro.kernels.tiling import resolve_tiles
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# case builders
# ---------------------------------------------------------------------------

def _plan(topk, E, bm):
    T, K = np.asarray(topk).shape
    R = -(-(T * K + E * bm) // bm) * bm
    return dsp.make_ragged_plan(jnp.asarray(topk, jnp.int32), E, R, bm), R


def _exact_case(T=24, K=2, E=4, d=16, f=16, bm=8, seed=0, topk=None):
    """Integer-valued inputs + power-of-two router weights: every product
    and sum is exactly representable, so any correct evaluation order gives
    bitwise-identical results."""
    rng = np.random.default_rng(seed)
    if topk is None:
        topk = np.stack([rng.choice(E, K, replace=False) for _ in range(T)])
    plan, R = _plan(topk, E, bm)
    x = jnp.asarray(rng.integers(-8, 8, (T, d)), jnp.float32)
    w1 = jnp.asarray(rng.integers(-2, 2, (E, d, f)), jnp.float32)
    w3 = jnp.asarray(rng.integers(-2, 2, (E, d, f)), jnp.float32)
    w2 = jnp.asarray(rng.integers(-2, 2, (E, f, d)), jnp.float32)
    wtk = jnp.asarray(2.0 ** rng.integers(-2, 2, (T, K)), jnp.float32)
    return plan, R, x, w1, w3, w2, wtk


def _row_maps(plan, weights, K, R):
    """Invert the (T, K) slot map into the fused kernel's row-side view."""
    pos = dsp.invert_slots(plan.slots, R)
    src = jnp.where(pos >= 0, pos // K, -1)
    wslot = None
    if weights is not None:
        wslot = jnp.where(pos >= 0,
                          jnp.take(weights.reshape(-1), jnp.maximum(pos, 0)),
                          0.0)
    return src, wslot


def _three_launch(x, w1, w3, w2, plan, wtk, R, bm):
    buf = dispatch_rows(x, plan.slots, R, plan.total_rows,
                        use_pallas=True, interpret=True, block_m=bm)
    y = ragged_expert_ffn(buf, w1, w3, w2, plan.block_to_expert,
                          plan.total_rows, block_m=bm,
                          use_pallas=True, interpret=True)
    return combine_rows(y, plan.slots, wtk, plan.total_rows,
                        use_pallas=True, interpret=True)


# ---------------------------------------------------------------------------
# forward parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [16, 17])   # 17: padded-contraction path
def test_fused_kernel_bitwise_vs_ref(d):
    plan, R, x, w1, w3, w2, wtk = _exact_case(d=d, seed=1)
    src, wslot = _row_maps(plan, wtk, wtk.shape[1], R)
    got = fused_moe(x, w1, w3, w2, src, wslot, plan.total_rows,
                    plan.block_to_expert, interpret=True)
    want = ref.fused_moe_ref(x, w1, w3, w2, src, plan.slots,
                             plan.block_to_expert, plan.total_rows, wtk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed,skew", [(2, False), (3, True)])
def test_moe_ffn_forward_vs_three_launch_and_jnp(seed, skew):
    """Fused single-launch forward == three-launch Pallas == jnp reference,
    bitwise, on both balanced (dropless) and skewed routing."""
    T, K, E, bm = 24, 2, 4, 8
    topk = None
    if skew:        # 3/4 of tokens hammer expert 0 (second slot varies)
        rng = np.random.default_rng(seed)
        topk = np.stack([(0 if t % 4 else rng.integers(1, E),
                          rng.integers(1, E)) for t in range(T)])
    plan, R, x, w1, w3, w2, wtk = _exact_case(T=T, K=K, E=E, bm=bm,
                                              seed=seed, topk=topk)
    fused = moe_ffn(x, w1, w3, w2, plan.slots, plan.block_to_expert,
                    plan.total_rows, wtk, block_m=bm,
                    use_pallas=True, interpret=True)
    three = _three_launch(x, w1, w3, w2, plan, wtk, R, bm)
    ref_np = moe_ffn(x, w1, w3, w2, plan.slots, plan.block_to_expert,
                     plan.total_rows, wtk, block_m=bm, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(three))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref_np))


def test_moe_ffn_float_allclose_vs_jnp():
    """Non-exact (gaussian) inputs: fused vs jnp agree to fp32 tolerance."""
    rng = np.random.default_rng(7)
    T, K, E, d, f, bm = 37, 2, 4, 16, 24, 8
    topk = np.stack([rng.choice(E, K, replace=False) for _ in range(T)])
    plan, R = _plan(topk, E, bm)
    x = jnp.asarray(rng.standard_normal((T, d)) * 0.5, jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    w3 = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32)
    wtk = jnp.asarray(rng.random((T, K)), jnp.float32)
    fused = moe_ffn(x, w1, w3, w2, plan.slots, plan.block_to_expert,
                    plan.total_rows, wtk, block_m=bm,
                    use_pallas=True, interpret=True)
    want = moe_ffn(x, w1, w3, w2, plan.slots, plan.block_to_expert,
                   plan.total_rows, wtk, block_m=bm, use_pallas=False)
    np.testing.assert_allclose(fused, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# grad parity
# ---------------------------------------------------------------------------

def _grads(fn, x, w1, w3, w2, wtk, gy):
    def loss(x, w1, w3, w2, wtk):
        return jnp.sum(fn(x, w1, w3, w2, wtk) * gy)
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, w1, w3, w2, wtk)


def test_moe_ffn_grads_bitwise_vs_three_launch():
    """All five grads (x, w1, w3, w2, router weights) of the fused VJP match
    the three-launch Pallas path bit-for-bit under exact arithmetic."""
    T, K, E, bm = 24, 2, 4, 8
    plan, R, x, w1, w3, w2, wtk = _exact_case(T=T, K=K, E=E, bm=bm, seed=4)
    gy = jnp.asarray(np.random.default_rng(5).integers(-2, 2, x.shape),
                     jnp.float32)

    fused = lambda x, w1, w3, w2, wtk: moe_ffn(
        x, w1, w3, w2, plan.slots, plan.block_to_expert, plan.total_rows,
        wtk, block_m=bm, use_pallas=True, interpret=True)
    three = lambda x, w1, w3, w2, wtk: _three_launch(
        x, w1, w3, w2, plan, wtk, R, bm)

    gf = _grads(fused, x, w1, w3, w2, wtk, gy)
    gt = _grads(three, x, w1, w3, w2, wtk, gy)
    for name, a, b in zip("x w1 w3 w2 wtk".split(), gf, gt):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"grad {name}")


def test_moe_ffn_grads_unweighted_vs_jnp():
    """EP-leg shape (weights applied outside): fused VJP grads match the
    autodiff of the jnp reference path.  Not bitwise — jnp's backward
    evaluates the silu-derivative chain with different HLO than the
    chunk-recompute VJP — so this pins a tight relative tolerance; the
    bitwise contract vs the three-launch VJP is the test above."""
    T, K, E, bm = 24, 2, 4, 8
    plan, R, x, w1, w3, w2, _ = _exact_case(T=T, K=K, E=E, bm=bm, seed=6)
    gy = jnp.asarray(np.random.default_rng(8).integers(-2, 2, x.shape),
                     jnp.float32)

    def run(use_pallas):
        def loss(x, w1, w3, w2):
            out = moe_ffn(x, w1, w3, w2, plan.slots, plan.block_to_expert,
                          plan.total_rows, None, block_m=bm,
                          use_pallas=use_pallas, interpret=use_pallas)
            return jnp.sum(out * gy)
        return jax.grad(loss, argnums=(0, 1, 2, 3))(x, w1, w3, w2)

    for name, a, b in zip("x w1 w3 w2".split(), run(True), run(False)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"grad {name}")


# ---------------------------------------------------------------------------
# routing edge cases
# ---------------------------------------------------------------------------

def test_all_to_one_routing():
    """Every token routed to expert 0 (K=1): experts 1..E-1 fully empty,
    expert 0 carries the whole load.  Forward bitwise; empty experts get
    exactly-zero weight grads."""
    T, E, bm = 16, 4, 8
    topk = np.zeros((T, 1), np.int32)
    plan, R, x, w1, w3, w2, wtk = _exact_case(T=T, K=1, E=E, bm=bm, seed=9,
                                              topk=topk)
    fused = moe_ffn(x, w1, w3, w2, plan.slots, plan.block_to_expert,
                    plan.total_rows, wtk, block_m=bm,
                    use_pallas=True, interpret=True)
    want = moe_ffn(x, w1, w3, w2, plan.slots, plan.block_to_expert,
                   plan.total_rows, wtk, block_m=bm, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(want))

    gy = jnp.ones_like(x)
    fn = lambda x, w1, w3, w2, wtk: moe_ffn(
        x, w1, w3, w2, plan.slots, plan.block_to_expert, plan.total_rows,
        wtk, block_m=bm, use_pallas=True, interpret=True)
    _, dw1, _, dw2, _ = _grads(fn, x, w1, w3, w2, wtk, gy)
    np.testing.assert_array_equal(np.asarray(dw1[1:]),
                                  np.zeros_like(np.asarray(dw1[1:])))
    np.testing.assert_array_equal(np.asarray(dw2[1:]),
                                  np.zeros_like(np.asarray(dw2[1:])))


def test_empty_expert():
    """Routing avoids expert 2 entirely: its row range is dead, the fused
    kernel predicates those blocks off, and parity still holds."""
    rng = np.random.default_rng(11)
    T, K, E, bm = 24, 2, 4, 8
    live = np.asarray([0, 1, 3])
    topk = np.stack([rng.choice(live, K, replace=False) for _ in range(T)])
    plan, R, x, w1, w3, w2, wtk = _exact_case(T=T, K=K, E=E, bm=bm, seed=12,
                                              topk=topk)
    fused = moe_ffn(x, w1, w3, w2, plan.slots, plan.block_to_expert,
                    plan.total_rows, wtk, block_m=bm,
                    use_pallas=True, interpret=True)
    three = _three_launch(x, w1, w3, w2, plan, wtk, R, bm)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(three))


# ---------------------------------------------------------------------------
# MoE layer integration: ctx.moe_fused over the EP strategy
# ---------------------------------------------------------------------------

def test_moe_layer_fused_matches_ragged():
    """DistContext(moe_fused=True) over ep_shardmap reproduces the ragged
    three-launch layer output (same routing, same stats)."""
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=32)
    params = M.init_moe(jax.random.PRNGKey(0), 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    mesh = make_mesh((1, 1), ("data", "model"))
    y_rg, s_rg = M.moe_ffn(params, x, cfg, M.DistContext(
        mesh=mesh, moe_strategy="ep_shardmap", moe_chunks=2,
        moe_ragged=True))
    y_fu, s_fu = M.moe_ffn(params, x, cfg, M.DistContext(
        mesh=mesh, moe_strategy="ep_shardmap", moe_chunks=2,
        moe_fused=True))
    np.testing.assert_allclose(y_fu, y_rg, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(s_fu["load"]),
                                  np.asarray(s_rg["load"]))


# ---------------------------------------------------------------------------
# autotuner cache
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_file(tmp_path):
    path = str(tmp_path / "autotune.json")
    autotune.set_cache_path(path)
    yield path
    autotune.set_cache_path(None)


def test_cache_round_trip(cache_file):
    shape, dtype = (24, 16, 16, 4, 8), jnp.float32
    assert autotune.lookup("fused_moe", shape, dtype) is None
    autotune.record("fused_moe", shape, dtype, {"bk": 64}, time_ms=1.0)
    assert autotune.lookup("fused_moe", shape, dtype) == {"bk": 64}
    # a fresh load from disk (not the in-process view) sees the entry too
    autotune.set_cache_path(cache_file)
    assert autotune.lookup("fused_moe", shape, dtype) == {"bk": 64}
    # resolve_tiles prefers the cached winner over defaults,
    # and the explicit call-site value over both
    assert resolve_tiles("fused_moe", shape, dtype,
                         {"bk": 512}) == {"bk": 64}
    assert resolve_tiles("fused_moe", shape, dtype, {"bk": 512},
                         {"bk": 32}) == {"bk": 32}


def test_corrupt_cache_falls_back(cache_file):
    with open(cache_file, "w") as f:
        f.write("{not json !!")
    assert autotune.load_cache(cache_file) == {}
    assert autotune.lookup("fused_moe", (1, 2), jnp.float32) is None
    assert resolve_tiles("fused_moe", (1, 2), jnp.float32,
                         {"bk": 512}) == {"bk": 512}
    # recording over a corrupt file heals it
    autotune.record("op", (1, 2), jnp.float32, {"bk": 8})
    with open(cache_file) as f:
        assert "op|1x2" in json.dumps(json.load(f))


def test_missing_cache_is_empty(tmp_path):
    autotune.set_cache_path(str(tmp_path / "nope" / "autotune.json"))
    try:
        assert autotune.lookup("x", (1,), jnp.float32) is None
        assert resolve_tiles("x", (1,), jnp.float32, {"bm": 8}) == {"bm": 8}
    finally:
        autotune.set_cache_path(None)


def test_kernel_honors_cached_tiles(cache_file):
    """A recorded winner changes the tile the fused kernel traces with —
    and the result is still exact (padding keeps any block legal)."""
    plan, R, x, w1, w3, w2, wtk = _exact_case(d=16, seed=13)
    src, wslot = _row_maps(plan, wtk, wtk.shape[1], R)
    T, d = x.shape
    E, _, f = w1.shape
    bm = R // plan.block_to_expert.shape[0]
    autotune.record("fused_moe", (T, d, f, E, bm), x.dtype, {"bk": 8})
    got = fused_moe(x, w1, w3, w2, src, wslot, plan.total_rows,
                    plan.block_to_expert, interpret=True)
    want = ref.fused_moe_ref(x, w1, w3, w2, src, plan.slots,
                             plan.block_to_expert, plan.total_rows, wtk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_autotune_search_never_loses_to_baseline(cache_file):
    """The measured search prepends the heuristic baseline, so the winner's
    median is <= every candidate's (including the baseline's); failing
    candidates are skipped, not fatal."""
    a = jnp.ones((64, 64))

    def make_fn(bk):
        if bk == 13:                     # poisoned candidate: must be skipped
            raise ValueError("does not compile")
        def run():
            jnp.dot(a, a).block_until_ready()
        return run

    res = autotune.autotune("toy", (64,), jnp.float32, make_fn,
                            [{"bk": 13}, {"bk": 32}, {"bk": 64}],
                            baseline={"bk": 128}, blocks=2, repeats=2)
    assert res.baseline_ms is not None
    assert res.winner_ms <= res.baseline_ms
    assert {"bk": 13} in res.skipped
    # winner persisted for resolve_tiles
    assert autotune.lookup("toy", (64,), jnp.float32) == res.winner
