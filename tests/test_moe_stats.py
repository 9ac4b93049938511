"""The moe_ffn stats contract (see its docstring): load/drops are per-step
TOTALS with identical values across all three strategies, drops == 0 under
dropless capacity for every strategy, and > 0 for an undersized
balanced_capacity baseline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core import dispatch as dsp
from repro.core import moe as M
from repro.launch.mesh import make_mesh

CFG = MoEConfig(num_experts=4, top_k=2, d_ff_expert=32)


@pytest.fixture(scope="module")
def setup():
    params = M.init_moe(jax.random.PRNGKey(0), 16, CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    return params, x


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _ctx(strategy, **kw):
    if strategy == "ep_shardmap":
        return M.DistContext(mesh=_mesh11(), moe_strategy=strategy,
                             moe_chunks=2, **kw)
    return M.DistContext(moe_strategy=strategy, moe_chunks=2, **kw)


STRATEGIES = ["ep_shardmap", "tp_gspmd", "dense"]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_dropless_invariant(setup, strategy):
    params, x = setup
    _, stats = M.moe_ffn(params, x, CFG, _ctx(strategy))
    assert float(stats["drops"]) == 0.0


@pytest.mark.parametrize("strategy", ["ep_shardmap", "tp_gspmd"])
def test_undersized_capacity_drops(setup, strategy):
    params, x = setup
    cap_cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
                        capacity_mode="capacity", capacity_factor=0.5)
    _, stats = M.moe_ffn(params, x, cap_cfg, _ctx(strategy))
    assert float(stats["drops"]) > 0.0


def test_load_and_drops_are_per_step_totals(setup):
    """load sums to B*S*K token-slots (totals, not means) and is IDENTICAL
    across strategies; drops likewise."""
    params, x = setup
    B, S, _ = x.shape
    loads, drops = {}, {}
    for s in STRATEGIES:
        _, stats = M.moe_ffn(params, x, CFG, _ctx(s))
        loads[s] = np.asarray(stats["load"])
        drops[s] = float(stats["drops"])
        assert stats["load"].dtype == jnp.float32
    for s in STRATEGIES:
        assert loads[s].sum() == B * S * CFG.top_k, s
        np.testing.assert_array_equal(loads[s], loads["dense"], err_msg=s)
        assert drops[s] == 0.0


def test_ragged_ep_same_stats(setup):
    params, x = setup
    _, s_ep = M.moe_ffn(params, x, CFG, _ctx("ep_shardmap"))
    _, s_rg = M.moe_ffn(params, x, CFG, _ctx("ep_shardmap", moe_ragged=True))
    np.testing.assert_array_equal(np.asarray(s_ep["load"]),
                                  np.asarray(s_rg["load"]))
    assert float(s_rg["drops"]) == 0.0
