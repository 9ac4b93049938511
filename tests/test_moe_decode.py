"""The decode MoE path (core/moe.py::moe_ffn_decode, docs/DESIGN.md §Decode
MoE): one token per row, each expert read once and only where a live token
routes to it, with the row path's outputs and stats."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import MoEConfig
from repro.core import moe as M
from repro.models import transformer
from repro.serving.paged_cache import PagedCachePool

E, D = 8, 16
CFG = MoEConfig(num_experts=E, top_k=2, d_ff_expert=32)
CTX = M.DistContext()

#: each token's two experts, the first preferred
ROUTINGS = {
    "distinct": [(0, 1), (2, 3), (4, 5), (6, 7)],
    "one_pair": [(2, 5), (5, 2), (2, 5), (5, 2)],
    "mixed": [(0, 1), (1, 2), (0, 1), (5, 7)],
}


@pytest.fixture(scope="module")
def params():
    """Random experts behind a rigged router: logits = 10 * x[:E], so a
    token's routing is set by where its input peaks."""
    p = M.init_moe(jax.random.PRNGKey(0), D, CFG)
    w = jnp.zeros((D, E)).at[jnp.arange(E), jnp.arange(E)].set(10.0)
    return {**p, "router": {**p["router"], "w": w}}


def _tokens(pairs, seed=1):
    """(T, 1, D) inputs routed to ``pairs``: small noise plus peaks."""
    x = 0.05 * jax.random.normal(jax.random.PRNGKey(seed), (len(pairs), D))
    for t, (a, b) in enumerate(pairs):
        x = x.at[t, a].set(1.0).at[t, b].set(0.8)
    return x[:, None]


def _routed(pairs) -> set:
    return {e for pair in pairs for e in pair}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
@pytest.mark.parametrize("T", [1, 2, 3, 4])
def test_decode_matches_rows_and_dense(params, routing, T):
    pairs = ROUTINGS[routing][:T]
    x = _tokens(pairs)
    y, stats, reads = M.moe_ffn_decode(params, x, CFG, CTX)
    y_rows, s_rows = M._moe_ffn_rows(params, x, CFG, CTX)
    y_dense, _ = M._moe_ffn_dense(params, x, CFG, CTX)
    np.testing.assert_allclose(y, y_rows, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y, y_dense, rtol=1e-5, atol=1e-6)
    assert int(reads) == len(_routed(pairs))
    # the stats contract: the row path's numbers
    np.testing.assert_array_equal(stats["load"], s_rows["load"])
    assert stats["load"].dtype == jnp.float32
    assert float(stats["drops"]) == float(s_rows["drops"]) == 0.0
    np.testing.assert_allclose(stats["aux_loss"], s_rows["aux_loss"],
                               rtol=1e-6)


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_vmapped_over_slots_equals_one_call(params, routing):
    """``decode_wave`` vmaps each slot's (1, 1, d) step: the fold rule must
    give the one-call results, per-slot stats and one count for the wave."""
    pairs = ROUTINGS[routing]
    x = _tokens(pairs)
    y, stats, reads = M.moe_ffn_decode(params, x, CFG, CTX)
    y_v, s_v, r_v = jax.jit(jax.vmap(
        lambda xs: M.moe_ffn_decode(params, xs, CFG, CTX)))(x[:, None])
    np.testing.assert_array_equal(y_v[:, 0], y)
    np.testing.assert_array_equal(s_v["load"].sum(0), stats["load"])
    for t, pair in enumerate(pairs):
        np.testing.assert_array_equal(
            np.nonzero(np.asarray(s_v["load"][t]))[0], sorted(pair))
    np.testing.assert_array_equal(r_v, int(reads))


@pytest.mark.parametrize("dead", [0, 3])
def test_dead_slot_adds_no_expert(params, dead):
    """A dead row's experts are not read: the counter is the live tokens'
    union, live outputs keep their values, the stats still count it."""
    pairs = [(0, 1), (0, 1), (2, 3), (0, 1)]
    pairs[dead] = (6, 7)                  # only the dead token goes there
    x = _tokens(pairs)
    live = jnp.arange(4) != dead
    y_all, s_all, r_all = M.moe_ffn_decode(params, x, CFG, CTX)
    y, stats, reads = jax.jit(jax.vmap(
        lambda xs, lv: M.moe_ffn_decode(params, xs, CFG, CTX, live=lv)))(
        x[:, None], live[:, None])
    assert int(r_all) == len(_routed(pairs))
    want = _routed(p for t, p in enumerate(pairs) if t != dead)
    np.testing.assert_array_equal(reads, len(want))
    np.testing.assert_array_equal(np.delete(np.asarray(y[:, 0]), dead, 0),
                                  np.delete(np.asarray(y_all), dead, 0))
    np.testing.assert_array_equal(y[dead], 0.0)
    np.testing.assert_array_equal(stats["load"].sum(0), s_all["load"])


def _consumers(jaxpr, targets: list) -> set:
    """Primitives that read any of ``targets`` (vars of ``jaxpr``), looking
    through call-like equations into their bodies; a ``cond`` is reported
    as consuming whatever its branches read."""
    out = set()
    for eqn in jaxpr.eqns:
        hit = [i for i, v in enumerate(eqn.invars)
               if any(v is t for t in targets)]
        if not hit:
            continue
        name = eqn.primitive.name
        inner = next((eqn.params[k] for k in ("jaxpr", "call_jaxpr", "call")
                      if k in eqn.params), None)
        if name == "cond":
            out.add("cond")
        elif inner is not None:
            inner = getattr(inner, "jaxpr", inner)
            assert len(inner.invars) == len(eqn.invars), name
            out |= _consumers(inner, [inner.invars[i] for i in hit])
        else:
            out.add(name)
    return out


@pytest.mark.parametrize("layers", [2, 4])
def test_decode_wave_reads_experts_only_in_branches(layers):
    """The compiled serving wave (vmapped over slots; 2 layers unrolled, 4
    scanned as 2 periods) reads the expert weights only inside the
    per-expert ``cond``: a plain vmap of the branch would turn it into a
    select that reads every expert."""
    cfg = dataclasses.replace(registry()["mixtral-8x7b"].reduced(),
                              num_layers=layers)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    pool = PagedCachePool(params, cfg, CTX, 3, 32, 8)
    n = pool.n_slots
    inputs = pool._wave_inputs([None] * n, np.zeros((n,), np.int32),
                               np.zeros((n, 1, 1), np.int32))
    args = (params, pool.pools) + inputs
    closed = jax.make_jaxpr(pool._decode)(*args)
    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    invars = closed.jaxpr.invars
    assert len(invars) == len(leaves)
    experts = [v for v, (path, _) in zip(invars, leaves)
               if getattr(path[-1], "key", None) in M.EXPERT_WEIGHTS]
    # two MoE layers: their own leaves, or one stack over the periods
    assert len(experts) == 2 * len(M.EXPERT_WEIGHTS)
    assert {v.aval.ndim for v in experts} == {3 if layers == 2 else 4}
    assert _consumers(closed.jaxpr, experts) == {"cond"}


def test_prefill_and_extend_take_rows_path(monkeypatch):
    """Engagement is by shape: S > 1 (prefill, extend, moe_ffn_decode given
    a chunk) runs ``_moe_ffn_rows``; the decode step never does."""
    cfg = registry()["mixtral-8x7b"].reduced()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    calls = []
    rows = M._moe_ffn_rows

    def counted(*a, **kw):
        calls.append(a[1].shape)
        return rows(*a, **kw)

    monkeypatch.setattr(M, "_moe_ffn_rows", counted)
    toks = jnp.ones((1, 8), jnp.int32)
    _, _, cache = transformer.forward(params, cfg, CTX, {"tokens": toks},
                                      return_cache=True, cache_len=32)
    assert calls and all(s[1] == 8 for s in calls)
    calls.clear()
    transformer.extend_step(params, cfg, CTX, cache, toks[:, :4])
    assert calls and all(s[1] == 4 for s in calls)
    calls.clear()
    transformer.decode_step(params, cfg, CTX, cache, toks[:, :1])
    assert calls == []
    p = M.init_moe(jax.random.PRNGKey(0), D, CFG)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, D))
    y, _, reads = M.moe_ffn_decode(p, x, CFG, CTX)
    assert calls == [(2, 3, D)] and int(reads) == E
    np.testing.assert_array_equal(y, rows(p, x, CFG, CTX)[0])
