"""Telemetry-driven expert placement + hot-expert replication
(docs/DESIGN.md §Placement): solver invariants, hysteresis, the replica
memory term, EP bit-parity on a mesh, and migration/checkpoint round-trips.

Multi-device tests run in a SUBPROCESS that sets
--xla_force_host_platform_device_count (same rule as test_distributed.py)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import HardwareProfile
from repro.core import memory_model as mm
from repro.core import placement as plc
from repro.core.mact import MACTController
from repro.core.memory_model import Parallelism
from repro.core.moe import DistContext
from repro.core.placement import PlacementSpec
from repro.core.telemetry import LoadTelemetry
from repro.training.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 4, timeout: int = 600) -> str:
    src = (f"import os\n"
           f"os.environ['XLA_FLAGS'] = "
           f"'--xla_force_host_platform_device_count={devices}'\n"
           + textwrap.dedent(body))
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# telemetry: restore guard + imbalance signal
# ---------------------------------------------------------------------------

def test_bad_restore_leaves_live_ema_untouched():
    # regression: load_state_dict used to assign steps/ema before validating,
    # so a bad checkpoint clobbered the warm EMA it then refused to replace
    t = LoadTelemetry(num_layers=2, num_experts=3)
    warm = np.arange(6, dtype=np.float64).reshape(2, 3)
    t.update(warm)
    with pytest.raises(ValueError):
        t.load_state_dict({"steps": 99, "ema": np.ones((4, 4)).tolist()})
    assert t.steps == 1
    assert np.array_equal(t.loads, warm)
    # a valid restore still lands
    t.load_state_dict({"steps": 7, "ema": (warm * 2).tolist()})
    assert t.steps == 7 and np.array_equal(t.loads, warm * 2)


def test_imbalance_peak_over_mean():
    t = LoadTelemetry(num_layers=3, num_experts=4)
    assert t.imbalance() is None
    t.update([[1, 1, 1, 1], [8, 0, 0, 0], [0, 0, 0, 0]])
    imb = t.imbalance()
    assert np.allclose(imb, [1.0, 4.0, 1.0])   # all-zero layer reports 1.0


# ---------------------------------------------------------------------------
# PlacementSpec: shape, validation, derived tables
# ---------------------------------------------------------------------------

def test_identity_spec_properties():
    s = PlacementSpec.identity(8, 4)
    assert s.total_slots == 8 and s.slots_per_peer == 2
    assert s.replica_slots == 0 and s.is_identity
    s.validate()
    assert np.array_equal(s.replica_counts(), np.ones(8))
    with pytest.raises(ValueError):
        PlacementSpec.identity(6, 4)


def test_validate_rejects_malformed_specs():
    with pytest.raises(ValueError):   # slots not divisible by peers
        PlacementSpec(4, 2, (0, 1, 2, 3, 0)).validate()
    with pytest.raises(ValueError):   # duplicate expert on one peer
        PlacementSpec(4, 2, (0, 0, 2, 3)).validate()
    with pytest.raises(ValueError):   # expert 3 unplaced
        PlacementSpec(4, 2, (0, 1, 2, 0)).validate()
    with pytest.raises(ValueError):   # fewer slots than e_local
        PlacementSpec(8, 2, (0, 1)).validate()


def test_peer_loads_identity_matches_reshape_sum():
    s = PlacementSpec.identity(8, 4)
    load = np.arange(8, dtype=np.float64) + 1
    assert np.array_equal(s.peer_loads(load), load.reshape(4, 2).sum(1))
    with pytest.raises(ValueError):
        s.peer_loads(np.ones(5))


HOT = [100, 1, 1, 1, 1, 1, 1, 1]          # one dominant expert, E=8


def test_expert_slot_table_splits_replicas_evenly():
    s = plc.plan_placement(HOT, 4, replicas=1)
    assert s.replica_counts()[0] >= 2          # hot expert got replicated
    table = s.expert_slot_table()
    E, R = table.shape
    for e in range(E):
        slots, counts = np.unique(table[e], return_counts=True)
        assert np.all(np.asarray(s.slot_to_expert)[slots] == e)
        assert counts.max() - counts.min() == 0    # exact round-robin
    # predicted per-peer load splits the hot expert's column
    assert plc.bottleneck(s, HOT) < 100


def test_place_expert_idx_identity_and_even_split():
    import jax.numpy as jnp
    ident = PlacementSpec.identity(4, 2)
    idx = jnp.zeros((16, 2), jnp.int32)
    assert plc.place_expert_idx(idx, None) is idx
    assert plc.place_expert_idx(idx, ident) is idx
    s = plc.plan_placement(HOT, 4, replicas=1)
    slots = np.asarray(plc.place_expert_idx(idx, s))       # all route expert 0
    hosts = [i for i, e in enumerate(s.slot_to_expert) if e == 0]
    counts = np.bincount(slots.reshape(-1), minlength=s.total_slots)
    assert sorted(np.nonzero(counts)[0]) == sorted(hosts)
    assert counts[hosts].max() - counts[hosts].min() <= 1  # even up to T%R
    # same input -> same mapping (pure function of flat position)
    assert np.array_equal(slots, np.asarray(plc.place_expert_idx(idx, s)))


# ---------------------------------------------------------------------------
# solver: LPT, replication, hysteresis
# ---------------------------------------------------------------------------

def test_lpt_beats_identity_when_hot_experts_collide():
    # identity co-locates experts 0 and 1 on peer 0 -> bottleneck 150
    load = [100, 50, 1, 1, 1, 1, 1, 1]
    ident = PlacementSpec.identity(8, 4)
    s = plc.plan_placement(load, 4)
    s.validate()
    assert s.total_slots == 8                  # pure permutation
    assert plc.bottleneck(s, load) < plc.bottleneck(ident, load)
    assert plc.bottleneck(s, load) <= 101 + 1e-9   # LPT optimum here


def test_replication_cuts_below_single_expert_floor():
    # one expert dominates: no permutation helps (floor = 100), only replicas
    load = [100, 1, 1, 1, 1, 1, 1, 1]
    perm = plc.plan_placement(load, 4)
    rep = plc.plan_placement(load, 4, replicas=1)
    rep.validate()
    assert rep.total_slots == 8 + 4
    assert rep.replica_counts()[0] >= 2        # replicas went to the hot expert
    assert plc.bottleneck(perm, load) >= 100
    assert plc.bottleneck(rep, load) < 100
    with pytest.raises(ValueError):
        plc.plan_placement(load, 4, replicas=-1)
    with pytest.raises(ValueError):
        plc.plan_placement(load, 3)            # E % P != 0


def test_hysteresis_keeps_identity_on_balanced_load():
    loads = np.ones((3, 8))
    out = plc.choose_placements(loads, 3, 4)
    assert all(p.is_identity for p in out)


def test_hysteresis_holds_incumbent_within_band():
    ident = PlacementSpec.identity(8, 4)
    skew = np.asarray([[100, 50, 1, 1, 1, 1, 1, 1]])
    # big win: adopted
    adopted = plc.choose_placements(skew, 1, 4, current=(ident,))
    assert not adopted[0].is_identity
    # marginal win (within 10% band): incumbent survives
    mild = np.asarray([[10, 9.8, 10, 9.9, 10, 9.7, 10, 9.9]])
    held = plc.choose_placements(mild, 1, 4, current=(ident,))
    assert held[0] == ident
    # re-planning the adopted layout under the same load is a fixed point
    again = plc.choose_placements(skew, 1, 4, current=adopted)
    assert again == adopted


def test_choose_placements_cold_start_and_shape_guard():
    out = plc.choose_placements(None, 2, 4, num_experts=8)
    assert all(p.is_identity for p in out) and len(out) == 2
    cur = (plc.plan_placement([100, 50, 1, 1, 1, 1, 1, 1], 4),) * 2
    assert plc.choose_placements(None, 2, 4, num_experts=8, current=cur) == cur
    with pytest.raises(ValueError):
        plc.choose_placements(np.ones((3, 8)), 2, 4)
    with pytest.raises(ValueError):
        plc.choose_placements(None, 2, 4)      # num_experts required


def test_migrated_slots_accounting():
    ident = PlacementSpec.identity(8, 4)
    assert plc.migrated_slots(None, ident) == 0        # cold start: no moves
    assert plc.migrated_slots(ident, ident) == 0
    perm = PlacementSpec(8, 4, (1, 0, 2, 3, 4, 5, 6, 7))
    assert plc.migrated_slots(ident, perm) == 2
    rep = plc.plan_placement([100, 1, 1, 1, 1, 1, 1, 1], 4, replicas=1)
    # every fresh replica slot counts as moved (it receives a weight copy)
    assert plc.migrated_slots(rep, rep) == 0
    assert plc.migrated_slots(None, rep) >= rep.num_peers * rep.replica_slots


# ---------------------------------------------------------------------------
# MACT + memory model pricing
# ---------------------------------------------------------------------------

def _mact(**kw) -> MACTController:
    hw = HardwareProfile("test", hbm_bytes=1e8, peak_flops=1, hbm_bw=1,
                        ici_bw=1, alpha=0.9)
    return MACTController(get_config("deepseek-mini-8l").reduced(),
                          Parallelism(e=1, b=1), hw, seq_len=128,
                          bins=(1, 2, 4, 8), static_override=0.0, **kw)


def test_observed_s_pp_through_placement_map():
    mact = _mact()
    load = np.asarray([10.0, 10.0, 0.1, 0.1])
    ident = PlacementSpec.identity(4, 2)
    assert mact.observed_s_pp(load, ep_size=2) == \
        mact.observed_s_pp(load, placement=ident) == 20.0
    balanced = plc.plan_placement(load, 2)     # pairs a hot with a cold expert
    assert mact.observed_s_pp(load, placement=balanced) == pytest.approx(10.1)


def test_replica_weight_bytes_monotone_and_prices_budget():
    cfg = get_config("deepseek-mini-8l").reduced()
    par = Parallelism(e=2, b=1)
    assert mm.replica_weight_bytes(cfg, 0, par) == 0.0
    b1 = mm.replica_weight_bytes(cfg, 1, par)
    b2 = mm.replica_weight_bytes(cfg, 2, par)
    assert 0 < b1 < b2 and b2 == pytest.approx(2 * b1)
    # the replica term comes off the Eq. 8 budget...
    m0, m1 = _mact(), _mact(replica_slots=1)
    assert m1.s_prime_max() < m0.s_prime_max()
    # ...and onto the serving peak
    base = dict(requests=2, cache_len=64, decode_tokens=2)
    assert (mm.serving_peak_bytes(cfg, **base, replica_weight_bytes=1e6)
            == pytest.approx(mm.serving_peak_bytes(cfg, **base) + 1e6))


def test_placed_layer_gets_cheaper_or_equal_schedule():
    mact = _mact()
    E = mact.cfg.moe.num_experts
    # hot pair on one peer under identity; balanced placement splits them
    load = np.zeros((1, E))
    load[0, :2] = mact.s_prime_max() * 0.9
    balanced = plc.plan_placement(load[0], 2)
    plain = mact.choose_layer_schedules(load, 1, ep_size=2)
    placed = mact.choose_layer_schedules(load, 1, ep_size=2,
                                         placements=(balanced,))
    assert placed[0].chunks <= plain[0].chunks
    # identity placement vector must not change the plan at all
    ident = (PlacementSpec.identity(E, 2),)
    assert mact.choose_layer_schedules(load, 1, ep_size=2,
                                       placements=ident) == plain


# ---------------------------------------------------------------------------
# trainer: replan cadence, cache keys, checkpoint round-trip
# ---------------------------------------------------------------------------

def _trainer(**kw) -> Trainer:
    kw.setdefault("mact_ep_view", 2)
    return Trainer(get_config("deepseek-mini-8l").reduced(), DistContext(),
                   seq_len=32, global_batch=2, lr=1e-3,
                   use_placement=True, **kw)


def test_trainer_adopts_placement_and_composite_key():
    tr = _trainer(placement_replicas=1)
    E = tr.cfg.moe.num_experts
    key0 = tr._next_schedule_key()
    assert tr._with_placements(key0) == key0       # cold start: identity, bare
    skew = np.tile([100.0, 50.0, 1.0, 1.0][:E], (tr._n_moe, 1))
    tr.telemetry.update(skew)
    key1 = tr._next_schedule_key()
    full = tr._with_placements(key1)
    assert full != key1 and full[0] == key1
    assert all(isinstance(p, PlacementSpec) for p in full[1])
    assert any(not p.is_identity for p in full[1])
    rec = tr.placement_trace[-1]
    assert rec["migrated_slots"] > 0 and rec["migrated_bytes"] > 0
    assert max(rec["imbalance"]) > 1.0
    # identical compiled step reused for the same composite key
    fn = tr._compiled(full)
    assert tr._compiled(tr._with_placements(tr._next_schedule_key())) is fn


def test_trainer_respects_replan_interval():
    tr = _trainer(replan_interval=2)
    E = tr.cfg.moe.num_experts
    tr._next_schedule_key()                        # cold start plan (age 1)
    tr.telemetry.update(np.tile([100.0, 50.0] + [1.0] * (E - 2),
                                (tr._n_moe, 1)))
    tr._next_schedule_key()                        # age 1 < 2: no replan yet
    assert all(p.is_identity for p in tr._placements)
    assert len(tr.placement_trace) == 1
    tr._next_schedule_key()                        # age 2: replan fires
    assert len(tr.placement_trace) == 2
    assert any(not p.is_identity for p in tr._placements)


def test_trainer_disabled_or_indivisible_is_none():
    tr = _trainer()
    tr.use_placement = False
    assert tr.choose_placements() is None
    tr2 = _trainer(mact_ep_view=3)                 # E=4 not divisible by 3
    assert tr2.choose_placements() is None
    assert tr2._with_placements((1, 1)) == (1, 1)


def test_placement_checkpoint_round_trip():
    tr = _trainer(placement_replicas=1)
    E = tr.cfg.moe.num_experts
    tr.telemetry.update(np.tile([100.0, 50.0] + [1.0] * (E - 2),
                                (tr._n_moe, 1)))
    tr._next_schedule_key()
    assert any(not p.is_identity for p in tr._placements)
    extra = tr._runtime_extra()
    tr2 = _trainer(placement_replicas=1)
    tr2._apply_extra(extra)
    assert tr2._placements == tr._placements
    assert tr2._placement_age == tr._placement_age
    # a resumed replan from the warm state is a no-op (stable fixed point)
    tr2._placement_age = tr2.replan_interval
    tr2._next_schedule_key()
    assert tr2._placements == tr._placements


# ---------------------------------------------------------------------------
# EP numerics on a mesh (subprocess)
# ---------------------------------------------------------------------------

def test_ep_placement_bit_parity_forward_and_grads():
    """Identity and permutation placements are bitwise-identical to the
    unplaced EP path — output, loss, and every grad leaf — because per-row
    expert math is unchanged; only which peer runs it moves.  Replication
    keeps forward/loss/router/x grads bitwise too; expert WEIGHT grads
    accumulate replica partial-sums in a different order, so those three
    leaves are equal only to float-reassociation tolerance."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import moe as M
        from repro.core import placement as plc
        from repro.core.placement import PlacementSpec
        from repro.configs.base import MoEConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 4), ("data", "model"))
        cfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=64)
        params = M.init_moe(jax.random.PRNGKey(0), 32, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        def run(placement):
            ctx = M.DistContext(mesh=mesh, moe_chunks=2,
                                moe_strategy="ep_shardmap",
                                placement=placement)
            def loss(p, xx):
                y, s = M.moe_ffn(p, xx, cfg, ctx)
                return (y ** 2).sum(), (y, s)
            with jax.set_mesh(mesh):
                (l, (y, s)), g = jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1), has_aux=True))(params, x)
            return l, y, s, g
        l0, y0, s0, g0 = run(None)
        specs = {
          "identity": PlacementSpec.identity(8, 4),
          "permutation": PlacementSpec(8, 4, (3, 5, 0, 6, 1, 7, 2, 4)),
          "replicated": plc.plan_placement(
              [100, 50, 1, 1, 1, 1, 1, 1], 4, replicas=1),
        }
        flat0 = jax.tree_util.tree_flatten_with_path(g0)[0]
        for name, spec in specs.items():
            l1, y1, s1, g1 = run(spec)
            np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1),
                                          err_msg=name)
            assert float(l0) == float(l1), name
            assert float(s1["drops"]) == 0.0, name
            np.testing.assert_array_equal(np.asarray(s0["load"]),
                                          np.asarray(s1["load"]), err_msg=name)
            replicated = spec.replica_slots > 0
            for (path, a), b in zip(flat0, jax.tree.leaves(g1)):
                leaf = jax.tree_util.keystr(path)
                reassoc = replicated and any(w in leaf
                                             for w in ("w1", "w2", "w3")) \
                    and "router" not in leaf
                if reassoc:   # replica partial-sums re-ordered the reduction
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=1e-6, atol=1e-5,
                                               err_msg=f"{name} {leaf}")
                else:
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                                  err_msg=f"{name} {leaf}")
        print("PLACEMENT-PARITY OK")
    """, devices=4)
    assert "PLACEMENT-PARITY OK" in out


def test_ep_placement_all_to_one_routing_round_trip():
    """Worst-case skew: every token routes to experts {0, 1}, which identity
    co-locates on peer 0.  A planned placement separates and replicates them;
    the result must still be bitwise-identical with zero drops, and repeat
    runs identical (the replica split is deterministic)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import moe as M
        from repro.core import placement as plc
        from repro.configs.base import MoEConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 4), ("data", "model"))
        cfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=32)
        params = M.init_moe(jax.random.PRNGKey(0), 16, cfg)
        # force the router: zero weights -> uniform scores -> top-k
        # tie-breaks to experts (0, 1) for EVERY token
        params["router"]["w"] = jnp.zeros((16, 8), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
        T = 2 * 16
        load = np.zeros(8); load[0] = load[1] = T
        spec = plc.plan_placement(load, 4, replicas=1)
        assert not spec.is_identity
        assert plc.bottleneck(spec, load) < plc.bottleneck(
            plc.PlacementSpec.identity(8, 4), load)
        def run(placement):
            ctx = M.DistContext(mesh=mesh, moe_chunks=2,
                                moe_strategy="ep_shardmap",
                                placement=placement)
            with jax.set_mesh(mesh):
                y, s = jax.jit(lambda p, xx: M.moe_ffn(p, xx, cfg, ctx))(params, x)
            return np.asarray(y), s
        y0, s0 = run(None)
        assert np.asarray(s0["load"])[0] == T     # the skew really happened
        y1, s1 = run(spec)
        y2, _ = run(spec)
        np.testing.assert_array_equal(y0, y1)
        np.testing.assert_array_equal(y1, y2)     # deterministic split
        assert float(s1["drops"]) == 0.0
        np.testing.assert_array_equal(np.asarray(s0["load"]),
                                      np.asarray(s1["load"]))
        print("ALL-TO-ONE OK")
    """, devices=4)
    assert "ALL-TO-ONE OK" in out


def test_migration_then_step_equals_cold_start_on_mesh():
    """A trainer that replans mid-run (identity -> placed, i.e. after a
    weight migration) must produce the same compiled step as a fresh trainer
    cold-started directly at the new placement: stepping identical state on
    identical data is bitwise-equal."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from dataclasses import replace
        from repro.configs import get_config
        from repro.core.moe import DistContext
        from repro.training.step import init_train_state
        from repro.training.trainer import Trainer
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 4), ("data", "model"))
        cfg = get_config("mixtral-8x7b").reduced()
        cfg = replace(cfg, moe=replace(cfg.moe, num_experts=8))
        ctx = DistContext(mesh=mesh, moe_chunks=2, moe_strategy="ep_shardmap")
        kw = dict(seq_len=32, global_batch=4, lr=1e-3, use_mact=False,
                  use_placement=True, placement_replicas=1)
        skew = None
        def make():
            tr = Trainer(cfg, ctx, **kw)
            return tr, np.tile([100.0, 50.0] + [1.0] * 6, (tr._n_moe, 1))
        # trainer A: one step at identity, then replan + migrate
        trA, skew = make()
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        batch = trA.data.batch_at(0)
        with jax.set_mesh(mesh):
            k0 = trA._with_placements(trA._next_schedule_key())
            s0, m0 = trA._compiled(k0)(state, batch)
            trA.telemetry.update(skew)
            kA = trA._with_placements(trA._next_schedule_key())
            assert kA != k0 and any(not p.is_identity for p in trA._placements)
            sA, mA = trA._compiled(kA)(s0, batch)
        # trainer B: cold start straight at the same placement
        trB, _ = make()
        trB.telemetry.update(skew)
        with jax.set_mesh(mesh):
            kB = trB._with_placements(trB._next_schedule_key())
            assert trB._placements == trA._placements
            sB, mB = trB._compiled(kB)(s0, batch)
        assert float(mA["loss"]) == float(mB["loss"])
        for a, b in zip(jax.tree.leaves(sA), jax.tree.leaves(sB)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("MIGRATE==COLD OK", float(mA["loss"]))
    """, devices=4)
    assert "MIGRATE==COLD OK" in out
