"""Continuous-batching scheduler on the MemFine serving memory model.

MemFine's core move — decompose MoE work into chunks and plan them against a
theoretical memory model — applied to serving (docs/DESIGN.md §Serving):

* **Slot map.**  The decode batch is a fixed-capacity pool of ``max_slots``
  per-request cache slots; the compiled decode step is the single-token
  ``transformer.decode_step`` vmapped over slots, so every slot carries its
  own position (ring write cursors included) and requests join/leave at step
  boundaries without retracing.
* **Admission control.**  A queued request starts only when the serving
  memory model (core/memory_model.py::serving_fits — weights + per-request
  caches + the worse of a decode wave and a prefill chunk) says the modeled
  peak still fits ``alpha * M_GPU``.  Occupancy, not allocation, is what the
  model bounds: the pool is allocated once at ``max_slots``, and a budget
  below the full pool simply admits fewer concurrent requests.
* **Chunked prefill interleave.**  Long prompts are split by
  ``core/chunking.py::chunk_spans`` and prefilled one chunk per scheduler
  step between decode waves — the FCDA idea at the request level: bounded
  prefill activations, bounded decode-latency impact.  The first chunk runs
  the single-pass prefill (``transformer.forward(return_cache=True)``), the
  rest the compiled extend step.

Request lifecycle: WAITING -> PREFILL -> ACTIVE -> FINISHED, plus the
overload exit WAITING -> SHED (docs/DESIGN.md §Resilience): a request whose
admission deadline lapses, or that arrives past the WAITING-queue overload
bound, is shed with a client-visible ``retry_after`` quote.  Shedding
applies ONLY to requests never admitted; once accepted (PREFILL/ACTIVE) a
request survives even a faulted decode wave — the fault handler evicts the
wave's slots and *requeues* each accepted request at the head of the queue
(its generated tokens ride along and prefill re-derives the cache), so an
injected or real RESOURCE_EXHAUSTED never loses accepted work.  One request
prefills at a time; its slot is reserved at admission so installation can
never fail.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import HardwareProfile, ModelConfig, device_profile
from repro.core import memory_model as mm
from repro.core.chunking import chunk_spans
from repro.core.moe import DistContext
from repro.core.telemetry import ExpertTelemetry
from repro.models import transformer
from repro.runtime.faults import FaultInjector
from repro.runtime.guard import ServingGuard, is_oom_error
from repro.serving import engine, residency

WAITING, PREFILL, ACTIVE, FINISHED, SHED = ("waiting", "prefill", "active",
                                            "finished", "shed")


@dataclass
class Request:
    rid: int
    tokens: np.ndarray                  # (S,) int32 prompt (grows on requeue:
                                        # prompt + generated-so-far)
    max_new_tokens: int
    arrival: float = 0.0                # seconds after scheduler start
    deadline_s: Optional[float] = None  # admission deadline (None = guard's)
    priority: int = 0                   # preemption rank (paged scheduler):
                                        # higher may preempt strictly lower
    # -- runtime (scheduler-owned) -----------------------------------------
    state: str = WAITING
    slot: int = -1
    chunks_done: int = 0
    cache: object = None                # private (B=1) cache while prefilling
    next_token: int = -1
    out: list = field(default_factory=list)
    t_first: Optional[float] = None     # first-token time (s after start)
    t_done: Optional[float] = None
    accepted: bool = False              # ever admitted — shed-exempt
    prompt: Optional[np.ndarray] = None # original prompt (set at submit)
    pending_token: int = -1             # requeue: already-sampled token the
                                        # re-prefill must NOT resample
    requeues: int = 0
    retry_after: Optional[float] = None # quote handed back when shed
    wave_wait: int = 0                  # consecutive decode waves skipped
                                        # while ACTIVE (starvation guard)
    # -- paged scheduler runtime (docs/DESIGN.md §Paging) -------------------
    rp: object = None                   # RequestPages while resident
    pos: int = 0                        # decode write position (host-side)
    spill: object = None                # host-spilled pages while preempted
    preemptions: int = 0


@dataclass(frozen=True)
class ServeConfig:
    max_slots: int = 4
    cache_len: int = 128
    prefill_chunk: int = 32
    hw: Optional[HardwareProfile] = None  # None: the device's own profile
    dtype_bytes: int = 2                # modeled cache/act bytes (bf16 target;
                                        # the CPU dry-run holds f32, the model
                                        # describes the production target)
    weight_bytes: float = mm.WEIGHT_ONLY_BYTES
    temperature: float = 0.0
    deadline_s: Optional[float] = None  # default admission deadline; a
                                        # WAITING request older than this is
                                        # shed with retry-after
    max_waiting: int = 0                # overload bound on the queue (0 = off)
    # -- paging (docs/DESIGN.md §Paging; 0/False = monolithic slot map) -----
    page_size: int = 0                  # tokens per cache page
    prefix_cache: bool = False          # trie-shared prompt prefixes
    preemption: bool = False            # spill low-priority residents under
                                        # admission pressure
    replica_weight_bytes: float = 0.0   # static cost of the engine-build
                                        # expert placement's replica slots
                                        # (docs/DESIGN.md §Placement); priced
                                        # by admission like any weight bytes
    # -- expert-aware decode + residency (docs/DESIGN.md §Residency) --------
    expert_batching: bool = False       # group waves by predicted expert
                                        # overlap instead of FIFO age order
    wave_size: int = 0                  # max members per decode wave (0 =
                                        # every resident; >0 engages the
                                        # masked subset step, FIFO-ordered
                                        # unless expert_batching)
    max_wave_wait: int = 4              # starvation guard: a resident that
                                        # skipped this many waves is force-
                                        # included in the next one
    resident_experts: int = 0           # per-MoE-layer resident expert
                                        # capacity (0 = all resident, tier
                                        # off); cold experts host-offloaded
    prefetch_experts: int = 1           # modeled in-flight prefetch buffer
                                        # (per-expert-layer weight rows the
                                        # memory model prices on top of the
                                        # resident set)
    probe_router: bool = False          # router-only probe on prompt tokens
                                        # seeds the prefetch prediction for
                                        # requests with no telemetry yet


class ContinuousBatchingScheduler:
    def __init__(self, params: dict, cfg: ModelConfig, ctx: DistContext,
                 scfg: ServeConfig, key: Optional[jax.Array] = None,
                 injector: Optional[FaultInjector] = None):
        if cfg.encoder_layers or cfg.num_patch_tokens:
            raise ValueError("continuous batching serves token-only decoders; "
                             f"{cfg.name!r} needs per-request encoder state")
        if scfg.hw is None:
            scfg = dataclasses.replace(scfg, hw=device_profile())
        self.params, self.cfg, self.ctx, self.scfg = params, cfg, ctx, scfg
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.queue: deque[Request] = deque()
        self.active: dict[int, Request] = {}          # slot -> request
        self.free_slots = list(range(scfg.max_slots))
        self._prefilling: Optional[Request] = None
        one = transformer.init_cache(params, cfg, 1, scfg.cache_len,
                                     jnp.float32)
        self.cache = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (scfg.max_slots,) + l.shape),
            one)
        # donate the slot-pool cache off-CPU (engine._jit), same rationale
        # as the engine's decode step: waves rewrite every ring in place
        self._decode = engine._jit(jax.vmap(
            lambda p, c, t: transformer.decode_step(p, cfg, ctx, c, t),
            in_axes=(None, 0, 0)), donate_cache_arg=1)
        # expert-aware decode + weight-residency tier (§Residency): any of
        # the three knobs engages the masked subset step, which also reports
        # per-slot routed loads (the telemetry feed)
        self._expert_aware = (scfg.expert_batching or scfg.wave_size > 0
                              or scfg.resident_experts > 0)
        self.telemetry: Optional[ExpertTelemetry] = None
        self.residency = None
        self._probe = None
        if self._expert_aware:
            if cfg.moe is None:
                raise ValueError("expert-aware serving (expert_batching / "
                                 "wave_size / resident_experts) needs a MoE "
                                 f"config; {cfg.name!r} is dense")
            n_moe = transformer.num_moe_layers(cfg)
            self.telemetry = ExpertTelemetry(n_moe, cfg.moe.num_experts)
            self._decode_masked = engine.get_decode_step_masked(cfg, ctx)
            if scfg.probe_router:
                self._probe = engine.get_router_probe(cfg, ctx)
            if scfg.resident_experts > 0:
                always = residency.always_resident_sets(
                    ctx.placements, n_moe, cfg.moe.num_experts)
                self.residency = residency.ExpertResidency(
                    params, cfg, scfg.resident_experts,
                    always_resident=always)
                self.params = self.residency.offload_cold(self.params)
        self.injector = injector
        self.guard = ServingGuard(deadline_s=scfg.deadline_s,
                                  max_waiting=scfg.max_waiting)
        # telemetry / invariants
        self.steps = 0
        self.decode_waves = 0
        self.prefill_chunks = 0
        self.max_occupancy = 0
        self.modeled_peak = 0.0
        self.admission_order: list[int] = []
        self.finished: list[Request] = []
        self.shed: list[Request] = []
        self.requeued: int = 0
        self.faults: int = 0
        self._reset_wave_stats()

    def _reset_wave_stats(self) -> None:
        self.expert_waves = 0          # waves run through the masked step
        self.wave_distinct_sum = 0     # sum over waves of distinct activated
        self.wave_members_sum = 0      # experts / of member count
        self.forced_includes = 0       # starvation-guard force-inclusions
        self.prefetch_hits = 0         # activated expert-layer pairs already
        self.prefetch_misses = 0       # resident / demand-restored mid-wave
        self.demand_reruns = 0         # wave/chunk re-runs after a restore

    def reset(self) -> None:
        """Clear all request state and telemetry but keep the compiled
        steps and the allocated slot pool — benchmarks warm the compile
        caches with a throwaway trace, reset, then time steady-state."""
        self.queue.clear()
        self.active.clear()
        self.free_slots = list(range(self.scfg.max_slots))
        self._prefilling = None
        self.steps = self.decode_waves = self.prefill_chunks = 0
        self.max_occupancy = 0
        self.modeled_peak = 0.0
        self.admission_order = []
        self.finished = []
        self.shed = []
        self.requeued = 0
        self.faults = 0
        self._reset_wave_stats()
        if self.telemetry is not None:
            self.telemetry.clear()
        if self.residency is not None:
            self.residency.reset_stats()

    # -- memory model -------------------------------------------------------

    def occupancy(self) -> int:
        """Requests currently holding cache memory (installed + prefilling)."""
        return len(self.active) + (1 if self._prefilling is not None else 0)

    def _resident_kw(self) -> dict:
        """Memory-model kwargs for the residency tier: with a capacity set,
        admission prices only the resident experts plus the in-flight
        prefetch buffer instead of the full expert table (§Residency)."""
        s = self.scfg
        if s.resident_experts <= 0:
            return {}
        return {"resident_experts": s.resident_experts,
                "prefetch_experts": s.prefetch_experts}

    def modeled_bytes(self, requests: Optional[int] = None) -> float:
        s = self.scfg
        return mm.serving_peak_bytes(
            self.cfg, requests=self.occupancy() if requests is None else requests,
            cache_len=s.cache_len, decode_tokens=s.max_slots,
            prefill_tokens=s.prefill_chunk, dtype_bytes=s.dtype_bytes,
            weight_bytes=s.weight_bytes,
            replica_weight_bytes=s.replica_weight_bytes,
            **self._resident_kw())

    def _admissible(self, requests: int) -> bool:
        s = self.scfg
        return mm.serving_fits(
            self.cfg, s.hw, requests=requests, cache_len=s.cache_len,
            decode_tokens=s.max_slots, prefill_tokens=s.prefill_chunk,
            dtype_bytes=s.dtype_bytes, weight_bytes=s.weight_bytes,
            replica_weight_bytes=s.replica_weight_bytes,
            **self._resident_kw())

    # -- request intake -----------------------------------------------------

    def submit(self, req: Request, now: float = 0.0) -> None:
        s = self.scfg
        if len(req.tokens) + req.max_new_tokens > s.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.tokens)} + gen "
                f"{req.max_new_tokens} exceeds cache_len {s.cache_len}")
        if not self._admissible(1):
            raise ValueError(
                f"request {req.rid} can never be admitted: modeled bytes for "
                f"one request ({self.modeled_bytes(1) / 1e9:.2f} GB) exceed "
                f"{s.hw.alpha:.2f} * {s.hw.hbm_bytes / 1e9:.0f} GB")
        req.prompt = np.asarray(req.tokens)
        if self.guard.overloaded(len(self.queue)):     # overload shedding
            self._shed(req, now)
            return
        req.state = WAITING
        self.queue.append(req)

    # -- shedding / fault recovery (docs/DESIGN.md §Resilience) --------------

    def _service_rate(self, now: float) -> float:
        return len(self.finished) / now if now > 0 else 0.0

    def _shed(self, req: Request, now: float) -> None:
        """Refuse a never-accepted request with a client-visible retry-after
        (the backlog drained at the observed service rate)."""
        assert not req.accepted, "accepted requests are never shed"
        req.state = SHED
        req.t_done = now
        backlog = len(self.queue) + self.occupancy()
        req.retry_after = self.guard.retry_after(backlog + 1,
                                                 self._service_rate(now))
        self.shed.append(req)

    def _expire_deadlines(self, now: float) -> None:
        """Shed WAITING requests whose admission deadline lapsed.  Accepted
        requeued requests are deadline-exempt: their work is already paid
        for, and dropping them would violate the no-accepted-loss
        invariant."""
        kept = deque()
        for req in self.queue:
            if not req.accepted and self.guard.expired(req, now):
                self._shed(req, now)
            else:
                kept.append(req)
        self.queue = kept

    def _requeue_active(self, now: float) -> None:
        """A faulted decode wave lost the slot pool's forward progress, not
        the requests: evict every ACTIVE slot and requeue its request at
        the head of the queue.  The request keeps its sampled tokens —
        ``tokens`` becomes prompt + generated-so-far minus the pending one,
        re-prefill rebuilds the cache, and ``pending_token`` re-arms the
        decode feed, so greedy output matches an unfaulted run exactly."""
        for slot in sorted(self.active.keys(), reverse=True):
            req = self.active.pop(slot)
            self.free_slots.append(slot)
            req.tokens = np.concatenate(
                [req.prompt, np.asarray(req.out[:-1], np.int32)])
            req.pending_token = req.out[-1]
            req.chunks_done = 0
            req.cache = None
            req.state = WAITING
            req.requeues += 1
            self.requeued += 1
            self.queue.appendleft(req)     # reverse slot order: slot 0 first

    def _admit(self) -> None:
        """FIFO admission at step boundaries: a slot must be free, at most
        one request prefills at a time, and the serving memory model must
        accept one more resident cache (Eq. 3, serving form)."""
        while (self.queue and self.free_slots and self._prefilling is None
               and self._admissible(self.occupancy() + 1)):
            req = self.queue.popleft()
            req.state = PREFILL
            req.accepted = True
            req.slot = self.free_slots.pop(0)
            self._prefilling = req
            self.admission_order.append(req.rid)
        # occupancy peaks at admission and only falls at evictions, so
        # measuring here (not at end-of-step, after same-step finishes
        # retired) is what makes the reported peak honest
        self.max_occupancy = max(self.max_occupancy, self.occupancy())
        self.modeled_peak = max(self.modeled_peak, self.modeled_bytes())

    # -- prefill interleave -------------------------------------------------

    def _prefill_step(self, now: float) -> None:
        req = self._prefilling
        spans = chunk_spans(len(req.tokens), self.scfg.prefill_chunk)
        start, stop = spans[req.chunks_done]
        seg = jnp.asarray(req.tokens[None, start:stop], jnp.int32)
        logits, req.cache = self._prefill_compute(req, seg)
        req.chunks_done += 1
        self.prefill_chunks += 1
        if req.chunks_done == len(spans):
            self._install(req, logits, now)

    def _prefill_compute(self, req: Request, seg):
        """One prefill/extend chunk for ``req``.  Expert-aware mode uses the
        loads variants (non-donating) so the chunk both feeds the request's
        expert telemetry and, under residency, can re-run from the SAME
        base cache after demand-restoring any cold expert it activated —
        the installed cache is therefore bitwise the all-resident one."""
        if not self._expert_aware:
            return engine.prefill_chunk(self.params, self.cfg, self.ctx,
                                        req.cache, seg, self.scfg.cache_len)
        if (self.residency is not None and self._probe is not None
                and req.chunks_done == 0):
            # no telemetry yet: probe the prompt's routing on embeddings and
            # prefetch the predicted experts before the first chunk
            counts = np.asarray(self._probe(
                self.params, jnp.asarray(np.asarray(seg[0], np.int32))))
            self.params = self.residency.prefetch(self.params, counts.sum(0) > 0)
        out = {}

        def once():
            logits, cache, load = engine.prefill_chunk(
                self.params, self.cfg, self.ctx, req.cache, seg,
                self.scfg.cache_len, return_load=True)
            out["logits"], out["cache"] = logits, cache
            out["load"] = np.asarray(load)
            return out["load"] > 0, lambda: None

        self._demand_fixpoint(once)
        self.telemetry.update(req.rid, out["load"])
        if self.residency is not None:
            self.residency.note(out["load"])
            self.params = self.residency.evict_to_capacity(self.params)
        return out["logits"], out["cache"]

    def _install(self, req: Request, logits, now: float) -> None:
        """Join at a step boundary: copy the private prefill cache into the
        reserved slot and sample the first token from the prefill logits."""
        self.cache = jax.tree.map(
            lambda full, one: full.at[req.slot].set(one),
            self.cache, req.cache)
        req.cache = None
        req.state = ACTIVE
        if req.t_first is None:
            req.t_first = now
        self.active[req.slot] = req
        self._prefilling = None
        if req.pending_token >= 0:
            # requeued after a faulted wave: the next decode token was
            # already sampled before the fault — feed it, don't resample
            req.next_token = req.pending_token
            req.pending_token = -1
        else:
            self._append_token(req, np.asarray(logits[0, -1]), now)

    # -- decode -------------------------------------------------------------

    def _sample(self, req: Request, logits_v: np.ndarray) -> int:
        if self.scfg.temperature > 0:
            k = jax.random.fold_in(jax.random.fold_in(self.key, req.rid),
                                   len(req.out))
            return int(jax.random.categorical(
                k, jnp.asarray(logits_v) / self.scfg.temperature))
        return int(np.argmax(logits_v))

    def _append_token(self, req: Request, logits_v: np.ndarray,
                      now: float) -> None:
        tok = self._sample(req, logits_v)
        req.out.append(tok)
        req.next_token = tok
        if len(req.out) >= req.max_new_tokens:
            self._evict(req, now)

    def _evict(self, req: Request, now: float) -> None:
        """Leave at a step boundary: release the slot (contents are dead
        weight until the next install overwrites them)."""
        req.state = FINISHED
        req.t_done = now
        self.active.pop(req.slot, None)
        self.free_slots.append(req.slot)
        self.finished.append(req)
        if self.telemetry is not None:
            self.telemetry.forget(req.rid)

    def _wave_fault_reset(self, now: float) -> None:
        """Faulted wave: no token was appended, the slot pool may hold
        garbage — requeue every accepted request and rebuild the (possibly
        donated/torn) pool; the requeued requests' re-prefills repopulate
        their slots."""
        self.faults += 1
        self._requeue_active(now)
        one = transformer.init_cache(self.params, self.cfg,
                                     1, self.scfg.cache_len, jnp.float32)
        self.cache = jax.tree.map(
            lambda l: jnp.broadcast_to(
                l[None], (self.scfg.max_slots,) + l.shape), one)

    # -- expert-aware wave formation (docs/DESIGN.md §Residency) -------------

    def _predicted_support(self, req: Request) -> Optional[np.ndarray]:
        """(L_moe, E) bool predicted-activation mask for ``req``: telemetry
        EMA support when seen, router probe as the cold-start fallback."""
        sup = self.telemetry.support(req.rid)
        if sup is not None:
            return sup
        if self._probe is not None:
            toks = np.asarray(req.tokens[-8:], np.int32)
            counts = np.asarray(self._probe(self.params, jnp.asarray(toks)))
            return counts.sum(axis=0) > 0
        return None

    def _expert_set(self, req: Request) -> frozenset:
        sup = self._predicted_support(req)
        if sup is None:
            return frozenset()
        return frozenset(int(e) for e in np.flatnonzero(sup.any(axis=0)))

    def _form_wave(self) -> list:
        """Choose this wave's member slots.

        Everyone decodes when the residents fit ``wave_size``.  Over
        capacity, FIFO mode takes the longest-waiting residents; expert
        mode seeds with the starvation-guard force-includes (wave_wait >=
        max_wave_wait) and the longest-waiting request, then greedily adds
        the resident whose predicted expert set grows the wave's union the
        least — minimizing distinct activated experts per wave, which is
        what the residency tier streams and decode bandwidth pays for."""
        s = self.scfg
        items = sorted(self.active.items())
        cap = s.wave_size if s.wave_size > 0 else len(items)
        if len(items) <= cap:
            return [slot for slot, _ in items]
        by_age = sorted(items, key=lambda kv: (-kv[1].wave_wait, kv[1].rid))
        if not s.expert_batching:
            return [slot for slot, _ in by_age[:cap]]
        chosen = [kv for kv in by_age
                  if kv[1].wave_wait >= s.max_wave_wait][:cap]
        self.forced_includes += len(chosen)
        taken = {slot for slot, _ in chosen}
        pool = [kv for kv in by_age if kv[0] not in taken]
        if not chosen and pool:
            chosen.append(pool.pop(0))            # seed: longest-waiting
        union = set()
        for _, req in chosen:
            union |= self._expert_set(req)
        while len(chosen) < cap and pool:
            best = min(pool, key=lambda kv: (
                len(self._expert_set(kv[1]) - union),
                -kv[1].wave_wait, kv[1].rid))
            pool.remove(best)
            chosen.append(best)
            union |= self._expert_set(best[1])
        return [slot for slot, _ in chosen]

    def _demand_fixpoint(self, run_once):
        """Drive one compute (decode wave or prefill chunk) to the residency
        fixpoint.  ``run_once() -> (act, commit)``: ``act`` the (L_moe, E)
        bool matrix of experts the run's MEMBERS routed through, ``commit``
        a closure applying that run's state effects.  A run that touched a
        cold expert is discarded, the expert demand-restored, and the run
        re-issued from the same inputs — only the clean run commits, so
        committed state is bitwise the all-resident run's.  Convergence:
        layer-0 routing depends only on dense (always-resident) weights, so
        each re-run trues a strictly longer prefix of MoE layers
        (§Residency).  Returns the clean run's activation matrix."""
        demand: set = set()
        for _ in range(residency.RERUN_LIMIT):
            act, commit = run_once()
            if self.residency is None:
                break
            missing = self.residency.missing(act)
            if not missing:
                break
            self.demand_reruns += 1
            demand.update(missing)
            self.params = self.residency.ensure(self.params, missing,
                                                demand=True)
        else:
            raise RuntimeError("residency demand loop did not converge "
                               f"within {residency.RERUN_LIMIT} re-runs")
        commit()
        if self.residency is not None:
            pairs = {(int(j), int(e)) for j, e in zip(*np.nonzero(act))}
            self.prefetch_misses += len(demand)
            self.prefetch_hits += len(pairs - demand)
        return act

    # hook points the paged subclass overrides ------------------------------

    def _wave_fault_ok(self, exc: Exception) -> bool:
        return is_oom_error(exc)

    def _wave_recover(self, now: float) -> None:
        self._wave_fault_reset(now)

    def _advance_member(self, req: Request) -> None:
        pass                                    # paged: decode cursor bump

    def _run_wave(self, members: list, mask: np.ndarray):
        """Run one member wave to the fixpoint and commit its cache.
        Returns (logits, load) host arrays over ALL slots; non-member load
        rows are zero."""
        toks = np.zeros((self.scfg.max_slots, 1, 1), np.int32)
        for slot, req in self.active.items():
            toks[slot, 0, 0] = req.next_token
        if self.injector is not None:
            self.injector.maybe_fail_step(self.steps, "decode_wave")
        toks_j, mask_j = jnp.asarray(toks), jnp.asarray(mask)
        out = {}

        def once():
            logits, new_cache, load = self._decode_masked(
                self.params, self.cache, toks_j, mask_j)
            out["logits"], out["cache"] = logits, new_cache
            out["load"] = np.asarray(load)
            return out["load"].sum(0) > 0, \
                lambda: setattr(self, "cache", out["cache"])

        self._demand_fixpoint(once)
        return np.asarray(out["logits"]), out["load"]

    def _decode_wave_expert(self, now: float) -> None:
        members = self._form_wave()
        if not members:
            return
        mask = np.zeros((self.scfg.max_slots,), bool)
        mask[members] = True
        if self.residency is not None:
            predicted = np.zeros((self.residency.num_layers,
                                  self.residency.num_experts), bool)
            for slot in members:
                sup = self._predicted_support(self.active[slot])
                if sup is not None:
                    predicted |= sup
            self.params = self.residency.prefetch(self.params, predicted)
        try:
            logits, load = self._run_wave(members, mask)
        except Exception as exc:
            if not self._wave_fault_ok(exc):
                raise
            self._wave_recover(now)
            return
        self.decode_waves += 1
        self.expert_waves += 1
        self.wave_members_sum += len(members)
        self.wave_distinct_sum += int(
            np.count_nonzero(load.sum(axis=(0, 1)) > 0))
        member_set = set(members)
        for slot, req in list(self.active.items()):
            if slot not in member_set:
                req.wave_wait += 1
                continue
            req.wave_wait = 0
            self.telemetry.update(req.rid, load[slot])
            self._advance_member(req)
            self._append_token(req, logits[slot, 0, -1], now)
        if self.residency is not None:
            self.residency.note(load.sum(axis=0))

    def _decode_wave(self, now: float) -> None:
        if self._expert_aware:
            self._decode_wave_expert(now)
            return
        toks = np.zeros((self.scfg.max_slots, 1, 1), np.int32)
        for slot, req in self.active.items():
            toks[slot, 0, 0] = req.next_token
        try:
            if self.injector is not None:
                self.injector.maybe_fail_step(self.steps, "decode_wave")
            logits, self.cache = self._decode(self.params, self.cache,
                                              jnp.asarray(toks))
            logits = np.asarray(logits)   # (slots, 1, 1, V): the host fetch
        except Exception as exc:          # is where a real OOM surfaces
            if not is_oom_error(exc):
                raise
            # the wave's donated slot pool may be torn — rebuild it
            self._wave_fault_reset(now)
            return
        self.decode_waves += 1
        for slot, req in list(self.active.items()):
            self._append_token(req, logits[slot, 0, -1], now)

    # -- main loop ----------------------------------------------------------

    def step(self, now: float = 0.0) -> bool:
        """One scheduler step: expire lapsed deadlines, admit, run one
        prefill chunk, run one decode wave.  Returns False when there was
        nothing to do."""
        if self.injector is not None:
            self.injector.maybe_stall(self.steps)      # stalled-prefill chaos
        self._expire_deadlines(now)
        self._admit()
        busy = False
        if self._prefilling is not None:
            self._prefill_step(now)
            busy = True
        if self.active:
            self._decode_wave(now)
            busy = True
        self.steps += 1
        return busy

    def run(self, requests: list[Request]) -> dict:
        """Drive a trace of requests (``arrival`` = seconds after start) to
        completion against the wall clock; returns the metrics dict."""
        pending = sorted(requests, key=lambda r: r.arrival)
        t0 = time.perf_counter()
        i = 0
        while (i < len(pending) or self.queue or self.active
               or self._prefilling is not None):
            now = time.perf_counter() - t0
            while i < len(pending) and pending[i].arrival <= now:
                self.submit(pending[i], now)
                i += 1
            if not self.step(now) and i < len(pending):
                time.sleep(min(pending[i].arrival - now, 0.01))
        return self.metrics(time.perf_counter() - t0)

    def metrics(self, elapsed: float) -> dict:
        lat = [r.t_done - r.arrival for r in self.finished]
        gen = sum(len(r.out) for r in self.finished)
        return {
            "requests": len(self.finished),
            "generated_tokens": gen,
            "elapsed_s": elapsed,
            "tok_per_s": gen / elapsed if elapsed > 0 else 0.0,
            "latency_p50_s": float(np.percentile(lat, 50)) if lat else 0.0,
            "latency_p99_s": float(np.percentile(lat, 99)) if lat else 0.0,
            "decode_waves": self.decode_waves,
            "prefill_chunks": self.prefill_chunks,
            "max_occupancy": self.max_occupancy,
            "modeled_peak_bytes": self.modeled_peak,
            "budget_bytes": self.scfg.hw.alpha * self.scfg.hw.hbm_bytes,
            "shed": len(self.shed),
            "retry_after_p50_s": (float(np.percentile(
                [r.retry_after for r in self.shed], 50))
                if self.shed else 0.0),
            "requeues": self.requeued,
            "faults": self.faults,
            # -- expert-aware wave + residency counters (§Residency) --------
            "expert_waves": self.expert_waves,
            "mean_distinct_experts": (self.wave_distinct_sum
                                      / self.expert_waves
                                      if self.expert_waves else 0.0),
            "mean_wave_occupancy": (self.wave_members_sum / self.expert_waves
                                    if self.expert_waves else 0.0),
            "forced_includes": self.forced_includes,
            "prefetch_hits": self.prefetch_hits,
            "prefetch_misses": self.prefetch_misses,
            "demand_reruns": self.demand_reruns,
            **({"residency": self.residency.stats()}
               if self.residency is not None else {}),
        }
