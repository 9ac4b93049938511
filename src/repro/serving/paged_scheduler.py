"""Paged continuous batching: block allocation, prefix reuse, preemption.

``PagedScheduler`` keeps the parent's control flow (FIFO admission at step
boundaries, chunked-prefill interleave, requeue-on-fault) and swaps the
memory substrate (docs/DESIGN.md §Paging):

* **Paged residency.**  The per-slot monolithic cache pool becomes page
  pools (serving/paged_cache.py); a request holds pages for the blocks it
  has actually filled, and admission charges the *paged* memory model
  (core/memory_model.py::serving_paged_fits) with allocated bytes plus
  each resident's outstanding worst-case reservation — so a short request
  no longer reserves a full max-length ring, which is where the admitted
  concurrency headroom comes from.
* **Prefix reuse.**  With ``prefix_cache`` on, finished prefills register
  whole aligned blocks of their prompt in a token-id trie; a later request
  sharing the prefix adopts those pages (CoW-shared), resumes its chunked
  prefill from the matched boundary, and pays pages only for the tail.
* **Preemption.**  With ``preemption`` on, a refused head-of-queue request
  walks the ServingGuard escalation ladder: reclaim prefix pages, then
  spill the lowest-priority (strictly below the incoming) active request
  to host; the victim re-enters the queue head, ``accepted`` and
  deadline-exempt, and restores bit-exactly once pages free up.

The decode wave gathers per-slot dense caches from the page tables and
runs the unchanged vmapped ``transformer.decode_step``, so paged decode is
token-identical to the slot-map path — pinned against the monolithic
scheduler and the prefill_replay / greedy-vs-generate oracles in
tests/test_paging.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import memory_model as mm
from repro.core.chunking import chunk_spans
from repro.core.moe import DistContext
from repro.models import transformer
from repro.runtime.faults import FaultInjector
from repro.runtime.guard import is_oom_error
from repro.serving import engine
from repro.serving.paged_cache import PagedCachePool
from repro.serving.paging import (PagesExhausted, PrefixTrie, RequestPages,
                                  prefix_align)
from repro.serving.scheduler import (ACTIVE, PREFILL, WAITING,
                                     ContinuousBatchingScheduler, Request,
                                     ServeConfig)


class PagedScheduler(ContinuousBatchingScheduler):
    def __init__(self, params: dict, cfg: ModelConfig, ctx: DistContext,
                 scfg: ServeConfig, key: Optional[jax.Array] = None,
                 injector: Optional[FaultInjector] = None,
                 token_pages: Optional[int] = None,
                 state_blocks: Optional[int] = None):
        if scfg.page_size < 1:
            raise ValueError("PagedScheduler needs ServeConfig.page_size >= 1")
        super().__init__(params, cfg, ctx, scfg, key=key, injector=injector)
        self.cache = None               # the monolithic slot pool is unused
        self.pool = PagedCachePool(
            params, cfg, ctx, scfg.max_slots, scfg.cache_len, scfg.page_size,
            dtype_bytes=scfg.dtype_bytes, token_pages=token_pages,
            state_blocks=state_blocks)
        self.align = prefix_align(scfg.page_size, scfg.prefill_chunk)
        self.trie = (PrefixTrie(self.pool.ops, self.align)
                     if scfg.prefix_cache else None)
        if injector is not None:
            self.pool.ops.fault_hook = (
                lambda where: injector.maybe_fail_step(self.steps, where))
        self.preemptions = 0
        self.prefix_evictions = 0
        # experts the decode waves read, of waves x MoE layers x E
        self.decode_experts_read = self.decode_experts_total = 0
        self._snapshots: dict[int, dict] = {}   # rid -> {boundary: state}
        self._shared_len: dict[int, int] = {}   # rid -> adopted prefix len

    def reset(self) -> None:
        for req in list(self.active.values()):
            self.pool.release(req.rp)
        if self._prefilling is not None and self._prefilling.rp is not None:
            self.pool.release(self._prefilling.rp)
        if self.trie is not None:
            self.trie.clear()
        super().reset()
        self.preemptions = 0
        self.prefix_evictions = 0
        self.decode_experts_read = self.decode_experts_total = 0
        self._snapshots.clear()
        self._shared_len.clear()

    # -- paged memory model --------------------------------------------------

    def _outstanding_reservations(self) -> float:
        """Bytes residents may still allocate: each request's worst case
        minus what it privately owns already.  Admission charges allocated
        + outstanding so later on-demand allocations can never push the
        modeled peak past the budget."""
        residents = list(self.active.values())
        if self._prefilling is not None:
            residents.append(self._prefilling)
        total = 0.0
        for req in residents:
            if req.rp is None:
                continue
            wc = self._worst_case(req, self._shared_len.get(req.rid, 0))
            total += max(0.0, wc - req.rp.private_bytes)
        return total

    def _worst_case(self, req: Request, shared_len: int) -> float:
        return self.pool.ops.worst_case_bytes(
            len(req.prompt) + req.max_new_tokens, shared_len)

    def _page_bytes_now(self, extra: float = 0.0) -> float:
        return (self.pool.alloc.allocated_bytes()
                + self._outstanding_reservations() + extra)

    def modeled_bytes(self, requests: Optional[int] = None) -> float:
        s = self.scfg
        occ = self.occupancy() if requests is None else requests
        return mm.serving_paged_peak_bytes(
            self.cfg, page_bytes=self._page_bytes_now(),
            decode_tokens=min(s.max_slots, occ),
            prefill_tokens=s.prefill_chunk, dtype_bytes=s.dtype_bytes,
            weight_bytes=s.weight_bytes,
            replica_weight_bytes=s.replica_weight_bytes,
            **self._resident_kw())

    def _fits_extra(self, extra_bytes: float, occ_after: int) -> bool:
        s = self.scfg
        return mm.serving_paged_fits(
            self.cfg, s.hw, page_bytes=self._page_bytes_now(extra_bytes),
            decode_tokens=min(s.max_slots, occ_after),
            prefill_tokens=s.prefill_chunk, dtype_bytes=s.dtype_bytes,
            weight_bytes=s.weight_bytes,
            replica_weight_bytes=s.replica_weight_bytes,
            **self._resident_kw())

    # -- intake --------------------------------------------------------------

    def submit(self, req: Request, now: float = 0.0) -> None:
        s = self.scfg
        if len(req.tokens) + req.max_new_tokens > s.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.tokens)} + gen "
                f"{req.max_new_tokens} exceeds cache_len {s.cache_len}")
        req.prompt = np.asarray(req.tokens)
        wc = self._worst_case(req, 0)
        if not mm.serving_paged_fits(
                self.cfg, s.hw, page_bytes=wc, decode_tokens=1,
                prefill_tokens=s.prefill_chunk, dtype_bytes=s.dtype_bytes,
                weight_bytes=s.weight_bytes,
                replica_weight_bytes=s.replica_weight_bytes,
                **self._resident_kw()):
            raise ValueError(
                f"request {req.rid} can never be admitted: its worst-case "
                f"pages ({wc / 1e9:.2f} GB) plus weights exceed "
                f"{s.hw.alpha:.2f} * {s.hw.hbm_bytes / 1e9:.0f} GB")
        if self.guard.overloaded(len(self.queue)):
            self._shed(req, now)
            return
        req.state = WAITING
        self.queue.append(req)

    # -- admission: prefix reuse + escalation ladder -------------------------

    def _admit(self, now: float) -> None:
        while self.queue and self.free_slots:
            req = self.queue[0]
            if req.spill is not None:
                if not self._readmit_preempted(req):
                    break
                continue
            if self._prefilling is not None:
                break
            matched, nodes = (self.trie.lookup(req.tokens)
                              if self.trie is not None else (0, []))
            while matched >= len(req.tokens):   # keep >=1 token to prefill
                nodes.pop()
                matched -= self.align
            wc = self._worst_case(req, matched)
            if not self._fits_extra(wc, self.occupancy() + 1):
                if not self._relieve_pressure(req):
                    break
                continue
            self.queue.popleft()
            with TraceAnnotation("serve.admit_request", rid=req.rid,
                                 matched=matched):
                req.state = PREFILL
                req.accepted = True
                if req.t_admit is None:
                    req.t_admit = now
                req.slot = self.free_slots.pop(0)
                req.rp = self.pool.ops.new_request()
                self._shared_len[req.rid] = matched
                self._snapshots[req.rid] = {}
                if matched:
                    self.trie.adopt(req.rp, nodes)
                    req.cache = self.pool.gather_dense(
                        req.rp.tables, nodes[-1].snapshot, matched)
                    req.chunks_done = matched // self.scfg.prefill_chunk
                self._prefilling = req
                self.admission_order.append(req.rid)
        self.max_occupancy = max(self.max_occupancy, self.occupancy())
        self.modeled_peak = max(self.modeled_peak, self.modeled_bytes())

    def _readmit_preempted(self, req: Request) -> bool:
        """A spilled request at the queue head: restore its pages (fully
        private — sharing does not survive a spill) straight into ACTIVE;
        its position, sampled tokens and decode feed are exactly where the
        preemption left them."""
        wc = self._worst_case(req, 0)
        if not self._fits_extra(wc, self.occupancy() + 1):
            if self.trie is not None and self.trie.evict_lru_leaf():
                self.prefix_evictions += 1
                return True
            return False
        try:
            rp = self.pool.restore(req.spill)
        except PagesExhausted:
            return False
        self.queue.popleft()
        req.spill = None
        req.rp = rp
        self._shared_len[req.rid] = 0
        req.slot = self.free_slots.pop(0)
        req.state = ACTIVE
        self.active[req.slot] = req
        return True

    def _relieve_pressure(self, incoming: Request) -> bool:
        """Walk the guard's escalation ladder for a refused admission:
        evict a prefix-cache leaf, then preempt the lowest-priority active
        request strictly below the incoming one.  Returns True if any rung
        freed memory (the caller re-checks admission)."""
        for rung in self.guard.admission_escalation(
                self.trie is not None, self.scfg.preemption):
            if rung == "evict_prefix":
                if self.trie.evict_lru_leaf():
                    self.prefix_evictions += 1
                    return True
            elif rung == "preempt":
                victim = self._pick_victim(incoming.priority)
                if victim is not None and self._preempt(victim):
                    return True
        return False

    def _pick_victim(self, above: int) -> Optional[Request]:
        cands = [r for r in self.active.values() if r.priority < above]
        if not cands:
            return None
        # lowest priority first; among ties, the most recently admitted
        # (its lost batching time is smallest)
        return max(cands, key=lambda r: (-r.priority, r.t_first or 0.0))

    def _preempt(self, victim: Request) -> bool:
        hook = None
        if self.injector is not None:
            hook = lambda where: self.injector.maybe_fail_step(  # noqa: E731
                self.steps, where)
        try:
            saved = self.pool.spill(victim.rp, fault_hook=hook)
        except Exception as exc:
            if not is_oom_error(exc):
                raise
            # fault mid-preemption: the spill aborted before any reference
            # dropped — the victim stays resident, nothing is lost
            self.faults += 1
            return False
        victim.rp = None
        victim.spill = saved
        victim.preemptions += 1
        self.preemptions += 1
        self._shared_len.pop(victim.rid, None)
        self.active.pop(victim.slot)
        self.free_slots.append(victim.slot)
        victim.state = WAITING               # accepted: deadline-exempt
        # behind the incoming head it was evicted for — putting it in front
        # would readmit it into the pages just freed and preempt it again,
        # forever; behind everything would starve an accepted request
        self.queue.insert(min(1, len(self.queue)), victim)
        return True

    # -- prefill: snapshot capture + paged install ---------------------------

    def _prefill_step(self, now: float) -> None:
        req = self._prefilling
        spans = chunk_spans(len(req.tokens), self.scfg.prefill_chunk)
        start, stop = spans[req.chunks_done]
        with TraceAnnotation("serve.prefill_chunk", rid=req.rid, start=start,
                             stop=stop, length=len(req.tokens)):
            seg = jnp.asarray(req.tokens[None, start:stop], jnp.int32)
            logits, req.cache = self._prefill_compute(req, seg)
            req.chunks_done += 1
            self.prefill_chunks += 1
            if (self.trie is not None and stop % self.align == 0
                    and stop <= self._registrable_len(len(req.tokens))):
                # state at an aligned boundary: what a prefix-hit resume
                # needs
                self._snapshots[req.rid][stop] = self.pool.state_snapshot(
                    req.cache)
        if req.chunks_done == len(spans):
            self._install(req, logits, now)

    def _registrable_len(self, prompt_len: int) -> int:
        """Prefix blocks are only stable while no ring has wrapped: a
        prompt longer than a ring overwrote its earliest blocks during
        prefill, so nothing registers for it."""
        for g in self.pool.groups:
            if g.ring and prompt_len > g.length:
                return 0
        return prompt_len

    def _install(self, req: Request, logits, now: float) -> None:
        S = len(req.tokens)
        with TraceAnnotation("serve.install", rid=req.rid):
            try:
                self.pool.install(req.rp, req.cache, S,
                                  shared_len=self._shared_len.get(req.rid, 0))
            except Exception as exc:
                if not (is_oom_error(exc) or isinstance(exc, PagesExhausted)):
                    raise
                # physical pages ran out mid-install (or an injected CoW
                # fault): requeue this request; nothing accepted is lost
                self.faults += 1
                self._requeue_prefilling(req)
                return
            if self.trie is not None:
                upto = self._registrable_len(S) // self.align * self.align
                if upto:
                    self.trie.register(req.tokens, upto, req.rp,
                                       self._snapshots.get(req.rid, {}))
        req.cache = None
        req.pos = S
        req.state = ACTIVE
        if req.t_first is None:
            req.t_first = now
        self.active[req.slot] = req
        self._prefilling = None
        if req.pending_token >= 0:
            req.next_token = req.pending_token
            req.pending_token = -1
        else:
            self._first_token(req, logits, now)

    def _requeue_prefilling(self, req: Request) -> None:
        self.pool.release(req.rp)
        req.rp = None
        self._shared_len.pop(req.rid, None)
        req.cache = None
        req.chunks_done = 0
        req.state = WAITING
        req.requeues += 1
        self.requeued += 1
        self.free_slots.append(req.slot)
        self._prefilling = None
        self.queue.appendleft(req)

    # -- decode: paged wave --------------------------------------------------

    def _requeue_active(self, now: float) -> None:
        for req in self.active.values():
            self.pool.release(req.rp)
            req.rp = None
            self._shared_len.pop(req.rid, None)
        super()._requeue_active(now)

    def _decode_wave(self, now: float) -> None:
        if self._expert_aware:
            self._decode_wave_expert(now)
            return
        s = self.scfg
        try:
            with TraceAnnotation("serve.decode_prepare"):
                toks = np.zeros((s.max_slots, 1, 1), np.int32)
                pos = np.zeros((s.max_slots,), np.int32)
                for slot, req in self.active.items():
                    toks[slot, 0, 0] = req.next_token
                    pos[slot] = req.pos
                    # the write block must be exclusively owned before the
                    # wave (CoW fires here on ring wrap into a shared prefix
                    # page); runs before the generic wave fault point so an
                    # armed kind@step spec lands mid-CoW-fork when one is
                    # pending
                    self.pool.prepare_decode_write(req.rp, req.pos)
                if self.injector is not None:
                    self.injector.maybe_fail_step(self.steps, "decode_wave")
                slot_rps = [self.active[i].rp if i in self.active else None
                            for i in range(s.max_slots)]
            logits, reads = self.pool.decode_wave(self.params, slot_rps, pos,
                                                  toks)
            with TraceAnnotation("serve.fetch"):
                logits, reads = jax.device_get((logits, reads))
        except Exception as exc:
            if not (is_oom_error(exc) or isinstance(exc, PagesExhausted)):
                raise
            self.faults += 1
            self._requeue_active(now)
            if jax.default_backend() != "cpu":
                # the donated pools may be torn mid-wave: rebuild them and
                # drop the trie's now-dangling pins (prefixes recompute)
                self._rebuild_pools()
            return
        self.decode_waves += 1
        self._count_reads(reads)
        with TraceAnnotation("serve.sample"):
            for slot, req in list(self.active.items()):
                req.pos += 1
                self._append_token(req, logits[slot, 0, -1], now)

    def _count_reads(self, reads: np.ndarray) -> None:
        """Add one wave's per-MoE-layer expert reads to the counters."""
        self.decode_experts_read += int(reads.sum())
        self.decode_experts_total += reads.size * (
            self.cfg.moe.num_experts if self.cfg.moe is not None else 0)

    @property
    def decode_expert_read_share(self) -> Optional[float]:
        """Experts the decode waves read over all they could have read
        (docs/DESIGN.md §Decode MoE); None before a wave with an MoE."""
        if not self.decode_experts_total:
            return None
        return self.decode_experts_read / self.decode_experts_total

    # -- expert-aware wave hooks (docs/DESIGN.md §Residency) -----------------

    def _wave_fault_ok(self, exc: Exception) -> bool:
        return is_oom_error(exc) or isinstance(exc, PagesExhausted)

    def _wave_recover(self, now: float) -> None:
        self.faults += 1
        self._requeue_active(now)
        if jax.default_backend() != "cpu":
            self._rebuild_pools()

    def _advance_member(self, req: Request) -> None:
        req.pos += 1

    def _run_wave(self, members: list, mask: np.ndarray):
        """Paged member wave to the residency fixpoint.  Membership rides
        the page tables — non-member slots get ``rp=None`` (zero-page
        reads, scratch-page writes), so even the committed clean run never
        touches a non-member's pages; discarded demand re-runs reuse the
        unchanged input pools."""
        s = self.scfg
        with TraceAnnotation("serve.decode_prepare"):
            toks = np.zeros((s.max_slots, 1, 1), np.int32)
            pos = np.zeros((s.max_slots,), np.int32)
            for slot in members:
                req = self.active[slot]
                toks[slot, 0, 0] = req.next_token
                pos[slot] = req.pos
                # CoW before the wave, as in the FIFO path; ensure_writable
                # is idempotent, so demand re-runs see the same owned block
                self.pool.prepare_decode_write(req.rp, req.pos)
            if self.injector is not None:
                self.injector.maybe_fail_step(self.steps, "decode_wave")
            slot_rps = [self.active[i].rp if mask[i] and i in self.active
                        else None for i in range(s.max_slots)]
        out = {}

        def once():
            logits, load, reads, new_pools = self.pool.decode_wave_loads(
                self.params, slot_rps, pos, toks)
            out["logits"], out["reads"], out["pools"] = logits, reads, new_pools
            # non-member slots decoded garbage from the zero page: zero
            # their load rows so unions/telemetry only see members
            with TraceAnnotation("serve.fetch"):
                load = np.asarray(load)
            out["load"] = load * mask[:, None, None]
            return out["load"].sum(0) > 0, \
                lambda: setattr(self.pool, "pools", out["pools"])

        self._demand_fixpoint(once)
        with TraceAnnotation("serve.fetch"):
            logits, reads = jax.device_get((out["logits"], out["reads"]))
        self._count_reads(reads)
        return logits, out["load"]

    def _rebuild_pools(self) -> None:
        if self.trie is not None:
            self.trie.clear()
        self.pool.pools = tuple(
            None if p is None else jnp.zeros_like(p)
            for p in self.pool.pools)

    def _evict(self, req: Request, now: float) -> None:
        if req.rp is not None:
            self.pool.release(req.rp)
            req.rp = None
        self._shared_len.pop(req.rid, None)
        self._snapshots.pop(req.rid, None)
        super()._evict(req, now)

    # -- telemetry -----------------------------------------------------------

    def metrics(self, elapsed: float) -> dict:
        m = super().metrics(elapsed)
        m["preemptions"] = self.preemptions
        m["prefix_evictions"] = self.prefix_evictions
        m["decode_experts_read"] = self.decode_experts_read
        m["decode_experts_total"] = self.decode_experts_total
        m["decode_expert_read_share"] = self.decode_expert_read_share
        m["page_hwm_bytes"] = self.pool.alloc.hwm_bytes()
        m["page_allocated_bytes"] = self.pool.alloc.allocated_bytes()
        if self.trie is not None:
            m.update({f"prefix_{k}": v for k, v in self.trie.stats().items()})
        return m
