"""Pipelined FCDA schedule (docs/DESIGN.md §Pipeline): chunked_pipeline ≡
chunked_map (values, grads, stats contract), the extended memory model's
pipeline-depth term, and MACT's joint (chunk bin, depth) selection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import GPU_64G, get_config
from repro.configs.base import MoEConfig
from repro.core import memory_model as mm
from repro.core import moe as M
from repro.core.chunking import ChunkStages, chunked_map, chunked_pipeline, compose
from repro.core.mact import MACTController
from repro.core.moe import DistContext
from repro.launch.mesh import make_mesh

CFG = MoEConfig(num_experts=4, top_k=2, d_ff_expert=64)
CAP_CFG = MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                    capacity_mode="capacity", capacity_factor=0.5)


# ---------------------------------------------------------------------------
# chunking-level: synthetic stages
# ---------------------------------------------------------------------------

def _toy_stages(w1, w2):
    """Stage split with a permutation through the middle (order-sensitive:
    any chunk mis-sequencing scrambles the output)."""
    def dispatch(xc):
        idx = jnp.argsort(xc[:, 0])
        return {"x": xc[idx] * 2.0, "idx": idx,
                "load": jnp.histogram(xc[:, 0], bins=4, range=(-3, 3))[0]}

    def compute(st):
        return {"h": jax.nn.silu(st["x"] @ w1), "idx": st["idx"],
                "load": st["load"]}

    def combine(st):
        y = (st["h"] @ w2)[jnp.argsort(st["idx"])]
        return y, {"load": st["load"].astype(jnp.float32),
                   "aux": (st["h"] ** 2).mean()}

    return ChunkStages(dispatch, compute, combine)


@pytest.fixture(scope="module")
def toy():
    k1, k2, kx = jax.random.split(jax.random.PRNGKey(0), 3)
    w1 = jax.random.normal(k1, (8, 16)) * 0.3
    w2 = jax.random.normal(k2, (16, 8)) * 0.3
    x = jax.random.normal(kx, (64, 8))
    return _toy_stages(w1, w2), x, (w1, w2)


@pytest.mark.parametrize("c", [2, 4, 8])
@pytest.mark.parametrize("remat", [True, False])
def test_pipeline_matches_map(toy, c, remat):
    stages, x, _ = toy
    y0, s0 = chunked_map(compose(stages), x, c, remat=remat)
    for depth in (2, c):
        y1, s1 = chunked_pipeline(stages, x, c, depth=depth, remat=remat)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-6)
        np.testing.assert_allclose(np.asarray(s0["load"]),
                                   np.asarray(s1["load"]))
        np.testing.assert_allclose(float(s0["aux"]), float(s1["aux"]),
                                   rtol=1e-6)


def test_pipeline_gradients_match_map(toy):
    stages, x, (w1, w2) = toy

    def loss_map(x):
        y, s = chunked_map(compose(stages), x, 4, remat=True)
        return (y ** 2).sum() + s["aux"]

    def loss_pipe(x):
        y, s = chunked_pipeline(stages, x, 4, depth=2, remat=True)
        return (y ** 2).sum() + s["aux"]

    g0, g1 = jax.grad(loss_map)(x), jax.grad(loss_pipe)(x)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), atol=1e-5)


def test_pipeline_depth_fallbacks(toy):
    stages, x, _ = toy
    y0, _ = chunked_map(compose(stages), x, 4)
    # depth 1 and depth-not-dividing fall back to the sequential schedule
    for depth in (1, 3):
        y1, _ = chunked_pipeline(stages, x, 4, depth=depth)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-6)
    # depth > chunks clamps to chunks
    y2, _ = chunked_pipeline(stages, x, 2, depth=8)
    y3, _ = chunked_map(compose(stages), x, 2)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y3), atol=1e-6)
    with pytest.raises(ValueError):
        chunked_pipeline(stages, x, 4, depth=0)
    with pytest.raises(ValueError):
        chunked_pipeline(stages, jnp.zeros((10, 3)), 3)


# ---------------------------------------------------------------------------
# EP path on a 1-device mesh: the real stage split, in-process
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ep_setup():
    mesh = make_mesh((1, 1), ("data", "model"))
    params = M.init_moe(jax.random.PRNGKey(0), 32, CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    return mesh, params, x


def _run(mesh, params, x, cfg, **ctx_kw):
    ctx = DistContext(mesh=mesh, moe_strategy="ep_shardmap", **ctx_kw)
    with jax.set_mesh(mesh):
        return jax.jit(lambda p, x: M.moe_ffn(p, x, cfg, ctx))(params, x)


@pytest.mark.parametrize("c", [2, 4, 8])
@pytest.mark.parametrize("remat", [True, False])
def test_ep_pipeline_parity(ep_setup, c, remat):
    mesh, params, x = ep_setup
    y0, s0 = _run(mesh, params, x, CFG, moe_chunks=c, remat_chunks=remat)
    y1, s1 = _run(mesh, params, x, CFG, moe_chunks=c, remat_chunks=remat,
                  pipeline_chunks=2)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s0["load"]),
                                  np.asarray(s1["load"]))
    assert float(s0["drops"]) == float(s1["drops"]) == 0.0
    np.testing.assert_allclose(float(s0["aux_loss"]), float(s1["aux_loss"]),
                               rtol=1e-6)


def test_ep_pipeline_parity_capacity_mode(ep_setup):
    mesh, params, x = ep_setup
    y0, s0 = _run(mesh, params, x, CAP_CFG, moe_chunks=4)
    y1, s1 = _run(mesh, params, x, CAP_CFG, moe_chunks=4, pipeline_chunks=2)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s0["load"]),
                                  np.asarray(s1["load"]))
    assert float(s0["drops"]) == float(s1["drops"]) > 0   # baseline drops
    np.testing.assert_allclose(float(s0["aux_loss"]), float(s1["aux_loss"]),
                               rtol=1e-6)


@pytest.mark.parametrize("c", [2, 8])
def test_ep_pipeline_gradient_parity(ep_setup, c):
    mesh, params, x = ep_setup

    def loss(p, ctx):
        return M.moe_ffn(p, x, CFG, ctx)[0].sum()

    ctx0 = DistContext(mesh=mesh, moe_strategy="ep_shardmap", moe_chunks=c)
    ctx1 = DistContext(mesh=mesh, moe_strategy="ep_shardmap", moe_chunks=c,
                       pipeline_chunks=2)
    with jax.set_mesh(mesh):
        g0 = jax.jit(jax.grad(lambda p: loss(p, ctx0)))(params)
        g1 = jax.jit(jax.grad(lambda p: loss(p, ctx1)))(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_pipeline_matches_dense_oracle(ep_setup):
    mesh, params, x = ep_setup
    y, _ = _run(mesh, params, x, CFG, moe_chunks=4, pipeline_chunks=2)
    yd, _ = M.moe_ffn(params, x, CFG, DistContext(moe_strategy="dense"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yd), atol=1e-5)


# ---------------------------------------------------------------------------
# memory model: the pipeline-depth term
# ---------------------------------------------------------------------------

def test_activation_bytes_pipeline_term():
    cfg = get_config("deepseek-mini-16l")
    dims = mm.LayerDims.from_config(cfg)
    par = mm.Parallelism(t=1, p=4, c=1, e=32, d=1, b=1)
    base = mm.activation_bytes(dims, 4096, 6e5, par, chunks=8)
    two = mm.activation_bytes(dims, 4096, 6e5, par, chunks=8,
                              pipeline_depth=2)
    # depth-2 at c chunks keeps exactly the memory of depth-1 at c/2 chunks
    half = mm.activation_bytes(dims, 4096, 6e5, par, chunks=4)
    assert two > base
    assert np.isclose(two, half, rtol=1e-12)
    # live chunks cap at the chunk count (depth > c adds nothing more)
    capped = mm.activation_bytes(dims, 4096, 6e5, par, chunks=2,
                                 pipeline_depth=8)
    flat = mm.activation_bytes(dims, 4096, 6e5, par, chunks=2,
                               pipeline_depth=2)
    assert np.isclose(capped, flat, rtol=1e-12)


def test_optimal_chunks_with_depth():
    assert mm.optimal_chunks(1000, 600) == 2
    assert mm.optimal_chunks(1000, 600, pipeline_depth=2) == 4
    # never fewer chunks than the depth (all-live degenerate case)
    assert mm.optimal_chunks(10, 600, pipeline_depth=2) == 2
    assert mm.optimal_chunks(1000, 0, pipeline_depth=2) == 1 << 30


# ---------------------------------------------------------------------------
# MACT: joint (chunk bin, pipeline depth) selection
# ---------------------------------------------------------------------------

PAPER_PAR = mm.Parallelism(t=1, p=4, c=1, e=32, d=1, b=1)


@pytest.fixture(scope="module")
def mact():
    return MACTController(get_config("deepseek-mini-16l"), PAPER_PAR, GPU_64G,
                          seq_len=4096, static_override=43e9)


def test_mact_picks_depth2_when_extra_copy_fits(mact):
    # paper's observed distribution: c*=2 sequential; the depth-2 schedule
    # needs twice the chunks — a bin covers that, so MACT pipelines
    s_pp = 5.97e5
    assert mact.optimal_c(s_pp) == 2
    load = np.zeros(32)
    load[0] = s_pp                    # hottest device sees s_pp
    b, depth = mact.choose_schedule(load, ep_size=32)
    assert depth == 2
    assert b >= mm.optimal_chunks(s_pp, mact.s_prime_max(), pipeline_depth=2)
    assert mact.history[-1]["depth"] == 2


def test_mact_refuses_depth2_when_extra_copy_does_not_fit(mact):
    # s'' at 5x s'_max: sequential needs c=5 (bin 8 covers), but depth-2
    # needs c=10 > max bin — MACT must fall back to the sequential schedule
    s_pp = 5.0 * mact.s_prime_max()
    load = np.zeros(32)
    load[0] = s_pp
    b2, depth = mact.choose_schedule(load, ep_size=32)
    assert depth == 1
    assert b2 == 8
    # and the fallback is exactly what the sequential-only API picks
    assert mact.choose(load, ep_size=32) == b2


def test_mact_cold_start_is_admissible(mact):
    # cold start plans for the worst case s' -> e*s*k; whatever (bin, depth)
    # it picks must satisfy the extended Eq. 9 bound at that depth
    b, depth = mact.choose_schedule()
    wc = mm.worst_case_s_prime(4096, PAPER_PAR, mact.dims.topk)
    assert b >= mm.optimal_chunks(wc, mact.s_prime_max(),
                                  pipeline_depth=depth)


def test_memory_report_depth_term(mact):
    seq = mact.memory_report(5.97e5, chunks=4)
    pipe = mact.memory_report(5.97e5, chunks=4, pipeline_depth=2)
    assert pipe["activation_gb"] > seq["activation_gb"]
    assert pipe["pipeline_depth"] == 2


def test_observed_s_pp_rejects_indivisible_load(mact):
    with pytest.raises(ValueError, match="does not divide"):
        mact.observed_s_pp(np.ones(33), ep_size=32)
    # divisible load reshapes to per-device sums
    load = np.arange(64, dtype=np.float64)
    got = mact.observed_s_pp(load, ep_size=32)
    assert got == load.reshape(32, 2).sum(axis=1).max()


def test_trainer_schedule_is_sequential_without_mesh():
    from repro.training.trainer import Trainer
    cfg = get_config("deepseek-mini-8l").reduced()
    tr = Trainer(cfg, DistContext(), seq_len=64, global_batch=2, lr=1e-3)
    chunks, depth = tr.choose_schedule()
    assert depth == 1                 # local path has no all-to-all to overlap
    assert chunks in tr.mact_bins
