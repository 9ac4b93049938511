"""Training loop with the MACT dynamic chunk controller in the driver seat.

Each step:
  1. MACT chooses the FCDA schedule from the previous step's router load
     (s''), via the theoretical memory model (Eq. 8-9, extended with the
     pipeline's extra live chunk) — cold-starting from the worst case
     `s' -> e*s*k`.  Global mode picks one (chunk bin, pipeline depth);
     adaptive mode (``adaptive_mact=True``, docs/DESIGN.md §Adaptive)
     resolves a *per-layer* ScheduleSpec vector from the telemetry EMA of
     per-layer expert loads, re-planned every ``replan_interval`` steps with
     load-margin hysteresis.
  2. The step function compiled for that schedule key runs.  Compiled
     variants live in a bounded LRU cache keyed by the schedule — the
     global (bin, depth) pair, or the full per-layer vector (uniform
     vectors collapse to the global key, so the adaptive path reuses the
     static compilations bit-for-bit).
  3. Router loads feed back to MACT/telemetry; metrics/chunk trace are
     recorded (benchmarks/fig5 reads the trace).

Resilience (docs/DESIGN.md §Resilience): compiled-step execution runs under
the ``OOMGuard`` degradation ladder — an out-of-memory failure (real
RESOURCE_EXHAUSTED or injected) rolls back to the pre-step state and
retries strictly more conservative schedules (deeper chunking -> depth 1 ->
full recompute) with bounded retries, then audits the memory model
(modeled vs HLO-derived bytes via launch/hlo_analysis.py) and widens
``mact_headroom`` when the model under-predicted.  ``resume=True`` makes
``fit`` self-healing: it restores the newest *valid* checkpoint (corrupt or
torn saves are skipped by the manifest checksum) along with the warm
telemetry EMA and MACT hysteresis state, and trains on to the target step —
bit-identical to a run that never died.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import HardwareProfile, ModelConfig, device_profile
from repro.core.chunking import ScheduleSpec
from repro.core.mact import MACTController
from repro.core.memory_model import Parallelism
from repro.core.moe import DistContext
from repro.core import placement as plc
from repro.core.placement import PlacementSpec
from repro.core.telemetry import LoadTelemetry
from repro.data.pipeline import SyntheticLMData
from repro.distributed import sharding as shd
from repro.models.transformer import num_moe_layers
from repro.runtime.faults import FaultInjector
from repro.runtime.guard import FULL_REMAT, DegradationLadder, OOMGuard
from repro.training.step import TrainState, init_train_state, make_train_step
from repro import checkpointing


@dataclass
class Trainer:
    cfg: ModelConfig
    ctx: DistContext
    seq_len: int
    global_batch: int
    lr: float = 3e-4
    seed: int = 0
    hw: Optional[HardwareProfile] = None   # None: the device's own profile
    par: Optional[Parallelism] = None
    mact_bins: tuple = (1, 2, 4, 8)
    use_mact: bool = True
    max_pipeline_depth: int = 2          # MACT may pick depth in [1, this]
    mact_ep_view: Optional[int] = None   # group experts per hypothetical device
    static_override: Optional[float] = None
    adaptive_mact: bool = False          # per-layer schedules from telemetry
    replan_interval: int = 1             # steps between adaptive re-plans
    mact_hysteresis: float = 0.1         # load-margin band for schedule moves
    mact_headroom: float = 0.2           # plan for (1+this)*EMA: covers the
                                         # drift a plan must survive between
                                         # re-plans (EMA lag + replan_interval)
    telemetry_decay: float = 0.6         # per-layer load EMA retention
    use_placement: bool = False          # telemetry-driven expert placement:
                                         # re-home/replicate experts at replan
                                         # boundaries (docs/DESIGN.md
                                         # §Placement)
    placement_replicas: int = 0          # extra hot-expert weight slots per
                                         # EP peer (0 = pure permutation)
    placement_hysteresis: float = 0.1    # min fractional bottleneck gain
                                         # before a layer's placement moves
    max_compiled_steps: int = 8          # LRU bound on cached compiled steps
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    resume: bool = False                 # fit() restores the newest valid
                                         # checkpoint and treats `steps` as
                                         # the TARGET step count
    injector: Optional[FaultInjector] = None   # chaos hooks (runtime/faults)
    max_oom_retries: int = 4             # ladder bound per step
    headroom_widen: float = 1.5          # audit: multiply mact_headroom by
                                         # this when the model under-predicts
    log: list = field(default_factory=list)
    chunk_trace: list = field(default_factory=list)
    pipeline_trace: list = field(default_factory=list)
    schedule_trace: list = field(default_factory=list)  # adaptive: full vectors
    placement_trace: list = field(default_factory=list)  # per-replan records:
                                         # imbalance, slots migrated, bytes

    def __post_init__(self):
        if self.hw is None:
            self.hw = device_profile()
        mesh = self.ctx.mesh
        if mesh is not None and self.ctx.act_pspec is None:
            # place activations, logits and heads the way the dry-run does
            self.ctx = dataclasses.replace(self.ctx, **shd.context_shardings(
                mesh, self.cfg, self.global_batch))
        if self.par is None:
            ep = data = 1
            if self.ctx.mesh is not None:
                shape = dict(zip(self.ctx.mesh.axis_names,
                                 self.ctx.mesh.devices.shape))
                if self.cfg.moe is not None:
                    ep = shape.get(self.ctx.ep_axis, 1)
                data = shape.get("data", 1) * shape.get("pod", 1)
            self.par = Parallelism(e=max(ep, 1),
                                   b=max(1, self.global_batch // data))
        self.mact = MACTController(
            self.cfg, self.par, self.hw, self.seq_len, bins=self.mact_bins,
            static_override=self.static_override, fused=self.ctx.moe_fused,
            replica_slots=(self.placement_replicas if self.use_placement
                           else 0))
        self.data = SyntheticLMData(self.cfg, self.seq_len, self.global_batch,
                                    self.seed)
        self._steps: OrderedDict[tuple, object] = OrderedDict()
        self._last_load: Optional[np.ndarray] = None
        self._n_moe = num_moe_layers(self.cfg)
        self.telemetry = LoadTelemetry(
            self._n_moe, self.cfg.moe.num_experts if self.cfg.moe else 1,
            decay=self.telemetry_decay)
        self._layer_schedules: Optional[tuple] = None
        self._plan_age = 0
        self._placements: Optional[tuple] = None
        self._placement_age = 0
        self.compile_count = 0
        self.evicted_recompile_count = 0
        self._evicted_keys: set = set()
        self.guard = OOMGuard(
            DegradationLadder(self.mact.schedule_space(self.max_pipeline_depth)),
            max_retries=self.max_oom_retries, on_oom=self._oom_audit)
        self._audit_args: Optional[tuple] = None   # (state, batch) of the
        self.headroom_widenings: list = []         # attempt being audited
        self.resumed_from: Optional[int] = None

    # -- bounded compiled-step cache -------------------------------------------
    # Keyed by the schedule: a global (chunk bin, pipeline depth) pair of
    # ints, or the full per-layer ScheduleSpec vector (adaptive MACT).  Every
    # vector component comes from MACTController.schedule_space, so the key
    # space is bucketed and finite; the LRU cap bounds resident compilations
    # regardless (docs/DESIGN.md §Adaptive).
    def _step_for(self, chunks: int, pipeline: int = 1):
        return self._compiled((chunks, pipeline))

    def _compiled(self, key: tuple):
        if key in self._steps:
            self._steps.move_to_end(key)
            return self._steps[key]
        # placement-composite key: (schedule_key, placements vector).  The
        # schedule half keeps its exact historical form so placement-off runs
        # reuse the same cache keys (and the same compiled steps) as before.
        sched_key, placements = key, None
        if (len(key) == 2 and isinstance(key[1], tuple) and key[1]
                and isinstance(key[1][0], PlacementSpec)):
            sched_key, placements = key
        cfg = self.cfg
        if sched_key and sched_key[0] == FULL_REMAT:  # ladder floor: largest
            cfg = dataclasses.replace(self.cfg, remat_policy="full")
            ctx = dataclasses.replace(self.ctx, moe_chunks=sched_key[1],
                                      pipeline_chunks=1,
                                      layer_schedules=None)
        elif sched_key and isinstance(sched_key[0], tuple):  # per-layer vector
            ctx = dataclasses.replace(
                self.ctx,
                layer_schedules=tuple(ScheduleSpec(*s) for s in sched_key))
        else:
            # clear any caller-supplied vector: the global key IS the schedule
            ctx = dataclasses.replace(self.ctx, moe_chunks=sched_key[0],
                                      pipeline_chunks=sched_key[1],
                                      layer_schedules=None)
        if placements is not None:
            ctx = dataclasses.replace(ctx, placements=placements)
        fn = jax.jit(make_train_step(cfg, ctx, lr=self.lr))
        self._steps[key] = fn
        self.compile_count += 1
        if key in self._evicted_keys:
            # the schedule working set exceeds the cache: every round trip
            # re-traces the step graph — raise max_compiled_steps (or the
            # hysteresis) if this fires often
            self.evicted_recompile_count += 1
            warnings.warn(
                f"recompiling previously-evicted schedule key {key}; "
                f"{self.evicted_recompile_count} evict-recompiles so far "
                f"(max_compiled_steps={self.max_compiled_steps})")
        while len(self._steps) > self.max_compiled_steps:
            evicted, _ = self._steps.popitem(last=False)
            self._evicted_keys.add(evicted)
        return fn

    def _plan_params(self) -> tuple:
        """(ep_view, max_depth) both planning modes share."""
        ep_view = self.mact_ep_view or max(self.par.e, 1)
        # local path has no all-to-all to overlap: plan sequential-only so
        # the bin is not sized for a depth that will never run
        max_depth = self.max_pipeline_depth if self.ctx.mesh is not None else 1
        return ep_view, max_depth

    def choose_schedule(self) -> tuple:
        """(chunks, pipeline depth) for the next step — MACT-selected.

        Note the feedback scale: the global path plans from ``stats["load"]``
        summed over every MoE layer, so its s'' overestimates the per-layer
        received-token count by up to L_moe — conservative on memory (more
        chunks than strictly needed), and the historical behavior fig5/
        table4 track.  The adaptive path (``adaptive_mact=True``) plans from
        the per-layer telemetry rows, which is the memory model's native
        granularity.
        """
        if not self.use_mact or self.cfg.moe is None:
            return self.ctx.moe_chunks, self.ctx.pipeline_chunks
        ep_view, max_depth = self._plan_params()
        return self.mact.choose_schedule(self._last_load, ep_size=ep_view,
                                         max_depth=max_depth)

    def choose_chunks(self) -> int:
        return self.choose_schedule()[0]

    def choose_layer_schedules(self) -> tuple:
        """Per-layer ScheduleSpec vector for the next step (adaptive MACT).

        Re-plans from the telemetry EMA every ``replan_interval`` steps (and
        at cold start, from the worst case); between re-plans the vector in
        force is reused, so the compiled step does not even change identity.
        """
        if self._layer_schedules is None or self._plan_age >= self.replan_interval:
            ep_view, max_depth = self._plan_params()
            self._layer_schedules = self.mact.choose_layer_schedules(
                self.telemetry.loads, self._n_moe, ep_size=ep_view,
                max_depth=max_depth, current=self._layer_schedules,
                hysteresis=self.mact_hysteresis,
                headroom=self.mact_headroom,
                placements=self._placements)
            self._plan_age = 0
        self._plan_age += 1
        return self._layer_schedules

    # -- expert placement (docs/DESIGN.md §Placement) --------------------------
    def _placement_peers(self) -> int:
        """EP peers the placement maps over: the real mesh group when one
        exists, else the MACT planning view (lets single-device runs plan —
        and price — placements the same way they plan schedules)."""
        if self.ctx.mesh is not None:
            return max(self.par.e, 1)
        return self.mact_ep_view or max(self.par.e, 1)

    def choose_placements(self) -> Optional[tuple]:
        """Per-MoE-layer PlacementSpec vector, re-planned from the telemetry
        EMA at the same ``replan_interval`` cadence as the schedules (the
        placement replan runs FIRST so MACT prices schedules through the new
        map).  Each replan appends a record to ``placement_trace`` with the
        per-layer imbalance it acted on and the migration volume (weight
        slots + bytes the replan boundary's all-to-all moves)."""
        peers = self._placement_peers()
        E = self.cfg.moe.num_experts if self.cfg.moe else 0
        if (not self.use_placement or self._n_moe == 0 or peers <= 1
                or E % peers):
            return None
        if self._placements is None or self._placement_age >= self.replan_interval:
            old = self._placements
            self._placements = plc.choose_placements(
                self.telemetry.loads, self._n_moe, peers, num_experts=E,
                replicas=self.placement_replicas, current=old,
                hysteresis=self.placement_hysteresis)
            self._placement_age = 0
            moved = sum(
                plc.migrated_slots(old[j] if old is not None else None,
                                   self._placements[j])
                for j in range(self._n_moe)) if old != self._placements else 0
            imb = self.telemetry.imbalance()
            slot_bytes = (3 * self.cfg.d_model * self.cfg.moe.d_ff_expert
                          / self.par.t * 4)          # fp32 training weights
            self.placement_trace.append({
                "step": len(self.log),
                "imbalance": None if imb is None else [float(v) for v in imb],
                "migrated_slots": int(moved),
                "migrated_bytes": float(moved * slot_bytes),
                "identity": all(p.is_identity for p in self._placements),
            })
        self._placement_age += 1
        return self._placements

    def _with_placements(self, sched_key: tuple) -> tuple:
        """Attach the placement vector to a schedule cache key.  Identity
        (or disabled) placement keeps the bare schedule key, so those runs
        share compiled steps with the pre-placement path bit-for-bit."""
        p = self._placements
        if p is None or all(s.is_identity for s in p):
            return sched_key
        return (sched_key, p)

    @staticmethod
    def _vector_key(vec: tuple) -> tuple:
        vec = tuple(ScheduleSpec(*s) for s in vec)
        if len(set(vec)) == 1:           # uniform: collapse to the global
            return (vec[0].chunks, vec[0].depth)   # path (scan + reuse)
        return vec

    def _next_schedule_key(self) -> tuple:
        """The SCHEDULE half of the compiled-step cache key for the next
        step (the placement half is attached by ``_with_placements`` inside
        the attempt, so the OOM ladder escalates over pure schedule keys).
        The placement replan runs first: MACT then prices each layer's s''
        through the placement map it will actually run under."""
        self.choose_placements()
        if (self.adaptive_mact and self.use_mact and self.cfg.moe is not None
                and self._n_moe > 0):
            return self._vector_key(self.choose_layer_schedules())
        if self.ctx.layer_schedules and not self.use_mact:
            # hand-picked per-layer schedule, no controller: honor it
            return self._vector_key(self.ctx.layer_schedules)
        return tuple(self.choose_schedule())

    # -- resilience (docs/DESIGN.md §Resilience) -------------------------------

    @staticmethod
    def _key_summary(key: tuple) -> tuple:
        """(chunks, pipeline) actually run for a compiled-step cache key."""
        if key and key[0] == FULL_REMAT:
            return key[1], 1
        if key and isinstance(key[0], tuple):          # per-layer vector
            return (max(s[0] for s in key),            # memory-binding layer
                    max(s[1] for s in key))
        return key

    def _oom_audit(self, key: tuple, exc: Exception, step: int) -> dict:
        """Post-hoc memory-model audit after an OOM: log modeled-vs-actual
        bytes and widen the planning headroom when the model said the
        failed schedule fit — i.e. it under-predicted the peak."""
        chunks, depth = self._key_summary(key)
        if self._last_load is not None:
            s_pp = self.mact.observed_s_pp(self._last_load,
                                           self._plan_params()[0])
        else:
            import repro.core.memory_model as mm
            s_pp = mm.worst_case_s_prime(self.seq_len, self.par,
                                         self.mact.dims.topk)
        report = self.mact.memory_report(s_pp, chunks, depth)
        audit = {"step": step, "key": key, "s_pp": float(s_pp),
                 "modeled_total_gb": report["total_gb"],
                 "modeled_fits": bool(report["fits"]), "error": str(exc)}
        if self._audit_args is not None:               # HLO-derived actuals
            try:                                       # (best-effort: the
                from repro.launch import hlo_analysis  # failed step may not
                fn = self._compiled(self._with_placements(key))  # even lower)
                text = fn.lower(*self._audit_args).compile().as_text()
                audit["hlo_hbm_gb"] = (
                    hlo_analysis.analyse_module(text)["hbm_bytes"] / 2**30)
            except Exception:                          # noqa: BLE001
                audit["hlo_hbm_gb"] = None
        if report["fits"]:
            # the model admitted a schedule that OOMed: plan with more margin
            before = self.mact_headroom
            self.mact_headroom = before * self.headroom_widen + 1e-2
            self._layer_schedules = None               # force a fresh plan
            self._plan_age = 0
            audit["headroom"] = (before, self.mact_headroom)
            self.headroom_widenings.append(audit["headroom"])
        return audit

    def _runtime_extra(self) -> dict:
        """Host-side planner state a checkpoint must carry for a resumed
        run to replan warm (and bit-identically)."""
        return {
            "telemetry": self.telemetry.state_dict(),
            "last_load": (None if self._last_load is None
                          else np.asarray(self._last_load).tolist()),
            "layer_schedules": (None if self._layer_schedules is None
                                else [list(s) for s in self._layer_schedules]),
            "plan_age": self._plan_age,
            "mact_headroom": self.mact_headroom,
            "placements": (None if self._placements is None
                           else [[p.num_experts, p.num_peers,
                                  list(p.slot_to_expert)]
                                 for p in self._placements]),
            "placement_age": self._placement_age,
        }

    def _apply_extra(self, extra: dict) -> None:
        if not extra:
            return
        if extra.get("telemetry"):
            self.telemetry.load_state_dict(extra["telemetry"])
        if extra.get("last_load") is not None:
            self._last_load = np.asarray(extra["last_load"])
        if extra.get("layer_schedules") is not None:
            self._layer_schedules = tuple(
                ScheduleSpec(*s) for s in extra["layer_schedules"])
        self._plan_age = int(extra.get("plan_age", 0))
        self.mact_headroom = float(extra.get("mact_headroom",
                                             self.mact_headroom))
        if extra.get("placements") is not None:
            self._placements = tuple(
                PlacementSpec(int(e), int(p), tuple(int(s) for s in slots))
                for e, p, slots in extra["placements"])
        self._placement_age = int(extra.get("placement_age", 0))

    def _resume_state(self) -> Optional[TrainState]:
        """Restore the newest VALID checkpoint (corrupt ones are skipped by
        the manifest checksum) plus the warm planner state; None if the
        directory holds nothing restorable."""
        step = checkpointing.latest_step(self.checkpoint_dir)
        if step is None:
            return None
        like = self._init_state()
        state = checkpointing.restore(self.checkpoint_dir, step, like)
        self._apply_extra(checkpointing.load_extra(self.checkpoint_dir, step))
        self.resumed_from = step
        return state

    # -- main loop ---------------------------------------------------------------
    def _init_state(self) -> TrainState:
        """float32 master weights (and moments), each leaf born in its
        ``param_shardings`` layout when the ctx carries a mesh."""
        return init_train_state(jax.random.PRNGKey(self.seed), self.cfg,
                                jnp.float32, mesh=self.ctx.mesh)

    def _place_batch(self, batch: dict) -> dict:
        mesh = self.ctx.mesh
        if mesh is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        return {k: jax.device_put(v, NamedSharding(
                    mesh, shd.batch_pspec(mesh, self.global_batch)
                    if v.ndim == 2 else
                    shd.act_pspec(mesh, self.global_batch)))
                for k, v in batch.items()}

    def fit(self, steps: int, state: Optional[TrainState] = None,
            verbose: bool = False) -> TrainState:
        """Run the training loop.

        ``steps`` counts iterations from the given state — except under
        ``resume=True``, where it is the TARGET total step count: fit
        restores the newest valid checkpoint and trains the remainder, so
        crash + re-run converges on the same final step as an uninterrupted
        run.
        """
        mesh = self.ctx.mesh
        with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
            return self._fit(steps, state, verbose)

    def _fit(self, steps: int, state: Optional[TrainState],
             verbose: bool) -> TrainState:
        if state is None and self.resume and self.checkpoint_dir:
            state = self._resume_state()
        if state is None:
            state = self._init_state()
        n = steps - int(state.step) if self.resume else steps
        for i in range(max(n, 0)):
            step_idx = int(state.step)
            key = self._next_schedule_key()
            batch = self._place_batch(self.data.batch_at(step_idx))

            def attempt(k, _state=state, _batch=batch, _step=step_idx):
                if self.injector is not None:
                    self.injector.maybe_fail_step(_step)   # oom/crash hooks
                    self.injector.maybe_stall(_step)
                new_state, metrics = self._compiled(
                    self._with_placements(k))(_state, _batch)
                loss = float(metrics["loss"])          # sync point: a real
                return new_state, metrics, loss        # OOM surfaces here

            t0 = time.perf_counter()
            self._audit_args = (state, batch)
            n_esc = len(self.guard.escalations)
            (state, metrics, loss), used = self.guard.run(key, attempt,
                                                          step_idx)
            self._audit_args = None
            dt = time.perf_counter() - t0
            chunks, pipeline = self._key_summary(used)
            burst = (self.injector.burst_factor(step_idx)
                     if self.injector is not None else 1.0)
            load = np.asarray(metrics["load"]) * burst
            self._last_load = load
            if (self.adaptive_mact and self._n_moe
                    and "load_per_layer" in metrics):
                self.telemetry.update(
                    np.asarray(metrics["load_per_layer"]) * burst)
            tgs = self.global_batch * self.seq_len / max(dt, 1e-9)
            rec = {"step": int(state.step), "loss": loss,
                   "ce": float(metrics["ce"]), "aux": float(metrics["aux"]),
                   "grad_norm": float(metrics["grad_norm"]),
                   "chunks": chunks, "pipeline": pipeline, "time_s": dt,
                   "tgs": tgs, "max_load": float(load.max()),
                   "drops": float(metrics["drops"]),
                   "oom_retries": len(self.guard.escalations) - n_esc}
            imb = self.telemetry.imbalance()
            if imb is not None:
                rec["imbalance"] = float(imb.max())
            self.log.append(rec)
            self.chunk_trace.append(chunks)
            self.pipeline_trace.append(pipeline)
            if self.adaptive_mact and self._layer_schedules is not None:
                self.schedule_trace.append(self._layer_schedules)
            if verbose:
                imb_s = (f" imb={rec['imbalance']:.2f}"
                         if "imbalance" in rec else "")
                plc_s = ""
                if (self.placement_trace
                        and self.placement_trace[-1]["step"] == len(self.log) - 1):
                    last = self.placement_trace[-1]
                    plc_s = (f" replan[moved={last['migrated_slots']} slots,"
                             f" {last['migrated_bytes'] / 2**20:.1f} MiB]")
                print(f"step {rec['step']:4d} loss {rec['loss']:.4f} "
                      f"c={chunks} tgs={tgs:,.0f}{imb_s}{plc_s}")
            if (self.checkpoint_dir and self.checkpoint_every
                    and int(state.step) % self.checkpoint_every == 0):
                checkpointing.save(self.checkpoint_dir, int(state.step),
                                   state, extra=self._runtime_extra())
                if self.injector is not None:
                    self.injector.maybe_truncate_checkpoint(
                        step_idx, self.checkpoint_dir)
        return state
