"""Paged cache pools: the array side of docs/DESIGN.md §Paging.

``serving/paging.py`` owns the pure bookkeeping (allocator, page tables,
prefix trie); this module owns the device arrays and the compiled steps:

* **CacheLayout** — classifies every leaf of the per-request decode cache
  pytree (``transformer.init_cache``) by diffing a batch=1 against a
  batch=2 template: the axis whose size differs is the batch axis.  Leaves
  split into *token* leaves (attention K/V — paged along their token axis,
  grouped by (cache length, ring-ness)), *state* leaves (SSM state, conv
  tail, cross K/V — one constant-size state block per request) and the
  scalar ``pos`` (kept host-side per slot).
* **PagedCachePool** — one pool array per leaf, ``(pages,) + leaf_shape``
  with the token axis cut to ``page_size``.  A decode wave gathers each
  slot's page table into the dense per-slot cache
  (``blocks.gather_paged_tokens``), runs the *unchanged* vmapped
  ``transformer.decode_step``, and scatters the written rows back
  (``blocks.scatter_paged_tokens``) — which is what makes paged decode
  bit-identical to the monolithic slot pool: the reconstructed dense cache
  carries the exact same live values (the zero page stands in for
  never-filled blocks, and rows past a request's filled length are masked
  to exactly-zero attention weight either way), so the compiled step
  computes bitwise-equal logits.

Pool pages that were freed and reallocated may hold stale finite values in
their not-yet-written rows; those rows are unreachable by construction
(gather points never-filled *blocks* at the zero page, and decode/extend
masks unfilled *rows* inside a live block to -inf scores before softmax),
so outputs stay bit-identical without per-allocation zeroing.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.moe import DistContext
from repro.models import blocks, transformer
from repro.serving import engine
from repro.serving.paging import (RESERVED_PAGES, SCRATCH_PAGE, ZERO_PAGE,
                                  Group, PageAllocator, PageTableOps,
                                  RequestPages, space_key, STATE_SPACE)


@dataclass(frozen=True)
class LeafInfo:
    path: tuple                 # normalized key path into the cache pytree
    kind: str                   # "token" | "state" | "pos"
    batch_axis: int             # axis of the per-request batch dim (size 1)
    token_axis: Optional[int]   # token axis in the batchless shape
    group: Optional[Group]      # token leaves: which page space
    bshape: tuple               # per-request shape with the batch axis removed


def _norm_path(path) -> tuple:
    out = []
    for k in path:
        out.append(k.key if hasattr(k, "key") else k.idx)
    return tuple(out)


def _leaf_spec(path: tuple, cfg: ModelConfig):
    """LayerSpec of the layer owning an attention-cache leaf, recovered from
    its position in the cache pytree (pre / scanned periods / remainder)."""
    head, idx = path[0], path[1]
    if head == "pre":
        return cfg.prefix[idx]
    if head == "periods":
        return cfg.pattern[idx]
    assert head == "rem", f"unexpected cache leaf path {path}"
    return cfg.pattern[idx % len(cfg.pattern)]


class CacheLayout:
    """Leaf classification + treedef for one (params, cfg, cache_len)."""

    def __init__(self, params: dict, cfg: ModelConfig, cache_len: int,
                 dtype=jnp.float32, enc_out: Optional[jax.Array] = None):
        eo1 = eo2 = None
        if enc_out is not None:
            eo1 = jnp.zeros_like(enc_out[:1])
            eo2 = jnp.zeros((2,) + enc_out.shape[1:], enc_out.dtype)
        t1 = transformer.init_cache(params, cfg, 1, cache_len, dtype,
                                    enc_out=eo1)
        t2 = transformer.init_cache(params, cfg, 2, cache_len, dtype,
                                    enc_out=eo2)
        l1, self.treedef = jax.tree_util.tree_flatten_with_path(t1)
        l2, _ = jax.tree_util.tree_flatten_with_path(t2)
        self.cache_len = cache_len
        self.dtype = dtype
        self.leaves: list[LeafInfo] = []
        for (p1, a1), (_p2, a2) in zip(l1, l2):
            path = _norm_path(p1)
            if path == ("pos",):
                self.leaves.append(LeafInfo(path, "pos", -1, None, None, ()))
                continue
            diff = [ax for ax, (s1, s2) in enumerate(zip(a1.shape, a2.shape))
                    if s1 != s2]
            assert len(diff) == 1 and a1.shape[diff[0]] == 1, (
                f"cannot locate batch axis of cache leaf {path}: "
                f"{a1.shape} vs {a2.shape}")
            b = diff[0]
            bshape = a1.shape[:b] + a1.shape[b + 1:]
            if "attn" in path and path[-1] in ("k", "v"):
                spec = _leaf_spec(path, cfg)
                t = len(bshape) - 3           # (..., Sc, KH, hd)
                Sc = bshape[t]
                assert Sc == blocks.cache_len(spec, cache_len), path
                group = Group(length=Sc, ring=blocks._is_ring(spec, Sc))
                self.leaves.append(LeafInfo(path, "token", b, t, group,
                                            bshape))
            else:
                self.leaves.append(LeafInfo(path, "state", b, None, None,
                                            bshape))
        self.groups: list[Group] = sorted(
            {i.group for i in self.leaves if i.kind == "token"},
            key=lambda g: (g.length, g.ring))

    # -- modeled sizes (production dtype, not the CPU-dry-run f32) -----------

    def page_bytes(self, group: Group, page: int, dtype_bytes: int) -> float:
        per_token = sum(math.prod(i.bshape) // group.length
                       for i in self.leaves
                       if i.kind == "token" and i.group == group)
        return float(page * per_token * dtype_bytes)

    def state_bytes(self, dtype_bytes: int) -> float:
        return float(sum(math.prod(i.bshape) for i in self.leaves
                         if i.kind == "state") * dtype_bytes)


class PagedCachePool:
    """Page pools + compiled paged decode / install / gather / spill.

    ``n_slots`` bounds the decode-wave width (same role as the monolithic
    slot map); pages, not slots, bound memory.  ``token_pages`` /
    ``state_blocks`` size the physical pools — the *byte* budget is
    enforced by the scheduler through the paged memory model, so the
    physical pools only need to cover what admission can ever grant.
    """

    def __init__(self, params: dict, cfg: ModelConfig, ctx: DistContext,
                 n_slots: int, cache_len: int, page_size: int, *,
                 dtype=jnp.float32, dtype_bytes: int = 2,
                 token_pages: Optional[int] = None,
                 state_blocks: Optional[int] = None,
                 enc_out: Optional[jax.Array] = None):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.cfg, self.ctx = cfg, ctx
        self.n_slots = n_slots
        self.page = page_size
        self.layout = CacheLayout(params, cfg, cache_len, dtype,
                                  enc_out=enc_out)
        self.groups = self.layout.groups
        self._gidx = {g: i for i, g in enumerate(self.groups)}

        self.alloc = PageAllocator()
        for g in self.groups:
            pages = token_pages if token_pages is not None else (
                (n_slots + 2) * g.blocks(page_size) + 8)
            self.alloc.add_space(space_key(g), pages,
                                 self.layout.page_bytes(g, page_size,
                                                        dtype_bytes))
        n_state = state_blocks if state_blocks is not None else n_slots + 2
        self.alloc.add_space(STATE_SPACE, n_state,
                             self.layout.state_bytes(dtype_bytes))
        self.ops = PageTableOps(self.alloc, self.groups, page_size,
                                state_bytes=self.layout.state_bytes(
                                    dtype_bytes),
                                copy_page_fn=self._copy_page)

        # one pool per leaf: (pages,) + batchless shape, token axis -> page
        pools = []
        for info in self.layout.leaves:
            if info.kind == "pos":
                pools.append(None)
            elif info.kind == "token":
                rows = RESERVED_PAGES + self.alloc.spaces[
                    space_key(info.group)].total
                sh = list(info.bshape)
                sh[info.token_axis] = page_size
                pools.append(jnp.zeros((rows, *sh), dtype))
            else:
                rows = RESERVED_PAGES + n_state
                pools.append(jnp.zeros((rows, *info.bshape), dtype))
        self.pools = tuple(pools)
        self._decode_loads = None        # built lazily (expert-aware only)
        self._decode = self._build_decode()
        self._install = self._build_install()
        self._gather = self._build_gather()
        self._restore = self._build_restore()
        self._copy = {g: self._build_copy(g) for g in self.groups}

    # -- table assembly (host) ----------------------------------------------

    def _tables(self, slot_rps: list, for_scatter: bool) -> tuple:
        """(n_slots, n_blocks_g) int32 per group.  Gather points missing
        blocks at the zero page; scatter points them (and inactive slots)
        at the scratch page."""
        hole = SCRATCH_PAGE if for_scatter else ZERO_PAGE
        out = []
        for g in self.groups:
            nb = g.blocks(self.page)
            t = np.full((self.n_slots, nb), hole, np.int32)
            for s, rp in enumerate(slot_rps):
                if rp is None:
                    continue
                for b, pg in enumerate(rp.tables[g]):
                    if pg is not None:
                        t[s, b] = pg
            out.append(jnp.asarray(t))
        return tuple(out)

    def _state_ids(self, slot_rps: list, for_scatter: bool) -> jax.Array:
        hole = SCRATCH_PAGE if for_scatter else ZERO_PAGE
        ids = [hole if rp is None or rp.state_block is None else rp.state_block
               for rp in slot_rps]
        return jnp.asarray(np.asarray(ids, np.int32))

    def _full_tables(self, rp: RequestPages, for_scatter: bool) -> tuple:
        hole = SCRATCH_PAGE if for_scatter else ZERO_PAGE
        return tuple(
            jnp.asarray(np.asarray(
                [hole if p is None else p for p in rp.tables[g]], np.int32))
            for g in self.groups)

    # -- compiled steps ------------------------------------------------------

    def _gather_slots(self, pools, gt, sg, pos):
        """Dense per-slot caches (batch axis leading) read through the page
        tables ``gt`` and state ids ``sg``: a decode wave's input."""
        leaves = []
        with jax.named_scope("paged_gather"):
            for i, info in enumerate(self.layout.leaves):
                if info.kind == "pos":
                    leaves.append(pos)
                elif info.kind == "token":
                    x = blocks.gather_paged_tokens(
                        pools[i], gt[self._gidx[info.group]], info.token_axis,
                        info.group.length)
                    leaves.append(jnp.expand_dims(x, 1 + info.batch_axis))
                else:
                    leaves.append(jnp.expand_dims(pools[i][sg],
                                                  1 + info.batch_axis))
        return jax.tree_util.tree_unflatten(self.layout.treedef, leaves)

    def _scatter_slots(self, pools, new_cache, st, ss) -> tuple:
        """The pools with a wave's per-slot caches written back through the
        scatter tables ``st`` and state ids ``ss``."""
        new_leaves = jax.tree_util.tree_flatten(new_cache)[0]
        new_pools = []
        with jax.named_scope("paged_scatter"):
            for i, info in enumerate(self.layout.leaves):
                if info.kind == "pos":
                    new_pools.append(None)
                    continue
                x = jnp.squeeze(new_leaves[i], 1 + info.batch_axis)
                if info.kind == "token":
                    new_pools.append(blocks.scatter_paged_tokens(
                        pools[i], st[self._gidx[info.group]], x,
                        info.token_axis, self.page))
                else:
                    new_pools.append(pools[i].at[ss].set(x))
        return tuple(new_pools)

    def _build_decode(self):
        cfg, ctx = self.cfg, self.ctx

        def serve_decode_wave(params, pools, gt, st, sg, ss, pos, toks, live):
            cache = self._gather_slots(pools, gt, sg, pos)
            logits, new_cache, reads = jax.vmap(
                lambda c, t, v: transformer.decode_step(
                    params, cfg, ctx, c, t, return_reads=True, live=v))(
                cache, toks, live)
            return (logits, reads[0],
                    self._scatter_slots(pools, new_cache, st, ss))

        return engine._jit(serve_decode_wave, donate_cache_arg=1)

    def _build_decode_loads(self):
        """Loads-reporting twin of ``_build_decode`` for the expert-aware
        scheduler (docs/DESIGN.md §Residency): same gather -> decode ->
        scatter, but the decode also reports per-slot routed loads, pools
        are NOT donated, and the new pools are returned instead of being
        committed — the residency demand loop may discard a run that
        touched an offloaded expert and re-run it against the same input
        pools after restoring the weights."""
        cfg, ctx = self.cfg, self.ctx

        def serve_decode_wave_loads(params, pools, gt, st, sg, ss, pos, toks,
                                    live):
            cache = self._gather_slots(pools, gt, sg, pos)
            logits, new_cache, load, reads = jax.vmap(
                lambda c, t, v: transformer.decode_step(
                    params, cfg, ctx, c, t, return_load=True,
                    return_reads=True, live=v))(cache, toks, live)
            return (logits, load, reads[0],
                    self._scatter_slots(pools, new_cache, st, ss))

        return engine._jit(serve_decode_wave_loads)

    def _build_install(self):
        infos, gidx, page = self.layout.leaves, self._gidx, self.page

        def serve_install(pools, dense, tables, state_id):
            dl = jax.tree_util.tree_flatten(dense)[0]
            new_pools = []
            for i, info in enumerate(infos):
                if info.kind == "pos":
                    new_pools.append(None)
                    continue
                x = jnp.squeeze(dl[i], info.batch_axis)
                if info.kind == "token":
                    new_pools.append(blocks.scatter_paged_tokens(
                        pools[i], tables[gidx[info.group]], x,
                        info.token_axis, page))
                else:
                    new_pools.append(pools[i].at[state_id].set(x))
            return tuple(new_pools)

        return engine._jit(serve_install, donate_cache_arg=0)

    def _build_gather(self):
        infos, treedef = self.layout.leaves, self.layout.treedef
        gidx = self._gidx

        def serve_gather(pools, tables, state_vals, pos):
            leaves = []
            for i, info in enumerate(infos):
                if info.kind == "pos":
                    leaves.append(pos)
                elif info.kind == "token":
                    x = blocks.gather_paged_tokens(
                        pools[i], tables[gidx[info.group]], info.token_axis,
                        info.group.length)
                    leaves.append(jnp.expand_dims(x, info.batch_axis))
                else:
                    leaves.append(jnp.expand_dims(state_vals[i],
                                                  info.batch_axis))
            return jax.tree_util.tree_unflatten(treedef, leaves)

        return engine._jit(serve_gather)

    def _build_restore(self):
        infos, gidx = self.layout.leaves, self._gidx

        def serve_restore(pools, rows, tables, state_id):
            new_pools = []
            for i, info in enumerate(infos):
                if info.kind == "pos":
                    new_pools.append(None)
                elif info.kind == "token":
                    new_pools.append(pools[i].at[tables[gidx[info.group]]]
                                     .set(rows[i]))
                else:
                    new_pools.append(pools[i].at[state_id].set(rows[i]))
            return tuple(new_pools)

        return engine._jit(serve_restore, donate_cache_arg=0)

    def _build_copy(self, group: Group):
        idxs = [i for i, info in enumerate(self.layout.leaves)
                if info.kind == "token" and info.group == group]

        def serve_copy_page(pools, src, dst):
            out = list(pools)
            for i in idxs:
                out[i] = pools[i].at[dst].set(pools[i][src])
            return tuple(out)

        return engine._jit(serve_copy_page, donate_cache_arg=0)

    def _copy_page(self, group: Group, src: int, dst: int) -> None:
        self.pools = self._copy[group](self.pools, jnp.int32(src),
                                       jnp.int32(dst))

    # -- high-level ops ------------------------------------------------------

    def prepare_decode_write(self, rp: RequestPages, pos: int) -> None:
        """Before a wave: the block receiving position ``pos`` must exist
        and be exclusively owned (CoW fires here when a ring write cursor
        re-enters a prefix-shared or trie-pinned page)."""
        for g in self.groups:
            self.ops.ensure_writable(rp, g, g.block_of(pos, self.page))

    def _wave_inputs(self, slot_rps: list, pos: np.ndarray,
                     toks: np.ndarray) -> tuple:
        """A wave's device inputs after the params and pools: gather and
        scatter tables, state ids, positions, tokens and the (slots, 1)
        live mask (a slot holding a request)."""
        with TraceAnnotation("serve.page_tables"):
            live = np.asarray([[rp is not None] for rp in slot_rps])
            return (self._tables(slot_rps, for_scatter=False),
                    self._tables(slot_rps, for_scatter=True),
                    self._state_ids(slot_rps, for_scatter=False),
                    self._state_ids(slot_rps, for_scatter=True),
                    jnp.asarray(pos.astype(np.int32)), jnp.asarray(toks),
                    jnp.asarray(live))

    def decode_wave(self, params, slot_rps: list, pos: np.ndarray,
                    toks: np.ndarray):
        """One vmapped decode step over the slot map, paged: gather tables
        -> dense per-slot caches -> unchanged decode_step -> scatter back.
        ``slot_rps[s]`` is the RequestPages of the request in slot s (None =
        empty slot: reads the zero page, writes the scratch page, and adds
        no expert to the wave).  Returns (logits, reads): reads is the
        (L_moe,) int32 count of experts each MoE layer read."""
        inputs = self._wave_inputs(slot_rps, pos, toks)
        with TraceAnnotation("serve.decode_dispatch"):
            logits, reads, self.pools = self._decode(params, self.pools,
                                                     *inputs)
        return logits, reads

    def decode_wave_loads(self, params, slot_rps: list, pos: np.ndarray,
                          toks: np.ndarray):
        """Non-committing, loads-reporting wave for the expert-aware
        scheduler.  Membership is ``slot_rps[s] is not None``: a None slot
        reads the zero page and scatters to the scratch page, so committing
        the returned pools never perturbs a non-member's state — the paged
        form of the monolithic masked step's tree-select.  Returns
        (logits, load (slots, L_moe, E), reads (L_moe,), new_pools); the
        caller assigns ``pool.pools = new_pools`` only after a
        residency-clean run."""
        if self._decode_loads is None:
            self._decode_loads = self._build_decode_loads()
        inputs = self._wave_inputs(slot_rps, pos, toks)
        with TraceAnnotation("serve.decode_dispatch"):
            return self._decode_loads(params, self.pools, *inputs)

    def install(self, rp: RequestPages, dense, filled: int,
                shared_len: int = 0) -> None:
        """Scatter a finished (B=1) prefill cache into the request's pages.

        Allocates every block holding live rows; prefix-shared blocks stay
        shared when their content provably matches the dense cache (linear
        groups, and rings the prefill did not wrap — the scatter then
        rewrites them with bit-identical rows), otherwise they CoW first.
        Blocks wholly past ``filled`` stay unallocated (the concurrency
        win) and their scatter rows land on the scratch page."""
        for g in self.groups:
            live = min(filled, g.length)
            n_live = math.ceil(live / self.page) if live else 0
            if g.ring and filled > g.length:
                n_live = g.blocks(self.page)
                for b in range(n_live):           # wrap rewrote every block
                    self.ops.ensure_writable(rp, g, b)
            else:
                for b in range(n_live):
                    self.ops.ensure_block(rp, g, b)
        self.ops.alloc_state(rp)
        self.pools = self._install(self.pools, dense,
                                   self._full_tables(rp, for_scatter=True),
                                   jnp.int32(rp.state_block))

    def gather_dense(self, rp_tables: dict, state_vals: list, pos: int):
        """Dense (B=1) cache from explicit per-group block->page lists (a
        prefix-trie match) plus host state leaves — the resume point for a
        prefix-hit prefill.  Missing blocks read the zero page, exactly the
        cold cache's zeros."""
        tables = []
        for g in self.groups:
            t = [ZERO_PAGE if p is None else p for p in rp_tables[g]]
            tables.append(jnp.asarray(np.asarray(t, np.int32)))
        vals = [None if v is None else jnp.asarray(v) for v in state_vals]
        return self._gather(self.pools, tuple(tables), vals, jnp.int32(pos))

    def state_snapshot(self, dense) -> list:
        """Host copies of a dense (B=1) cache's state leaves (aligned with
        the layout's leaf order; None elsewhere) — what a prefix-trie node
        stores so an SSM/hybrid resume is bit-exact."""
        dl = jax.tree_util.tree_flatten(dense)[0]
        with TraceAnnotation("serve.fetch"):
            return [np.asarray(jnp.squeeze(dl[i], info.batch_axis))
                    if info.kind == "state" else None
                    for i, info in enumerate(self.layout.leaves)]

    # -- preemption: spill to host / restore --------------------------------

    def spill(self, rp: RequestPages, fault_hook=None) -> dict:
        """Copy the request's page contents to host memory and release every
        page reference (trie pins survive — they hold their own refs).

        ``fault_hook`` fires mid-preemption — after the host copy, before
        any reference is dropped — so an injected fault aborts the spill
        with the resident request and the allocator fully intact."""
        rows = []
        with TraceAnnotation("serve.fetch"):
            for i, info in enumerate(self.layout.leaves):
                if info.kind == "pos":
                    rows.append(None)
                elif info.kind == "token":
                    t = [ZERO_PAGE if p is None else p
                         for p in rp.tables[info.group]]
                    rows.append(np.asarray(self.pools[i][np.asarray(t)]))
                else:
                    blk = rp.state_block
                    rows.append(np.asarray(self.pools[i][blk])
                                if blk is not None else None)
        if fault_hook is not None:
            fault_hook("preempt_spill")
        saved = {
            "rows": rows,
            "mask": {g: [p is not None for p in rp.tables[g]]
                     for g in self.groups},
        }
        self.ops.release(rp)
        return saved

    def restore(self, saved: dict) -> RequestPages:
        """Re-admission after a spill: fresh fully-private pages, contents
        scattered back from host — the resumed decode is bit-identical to
        one that was never preempted."""
        rp = self.ops.new_request()
        for g in self.groups:
            for b, had in enumerate(saved["mask"][g]):
                if had:
                    self.ops.ensure_block(rp, g, b)
        self.ops.alloc_state(rp)
        rows = [r if r is None else jnp.asarray(r) for r in saved["rows"]]
        self.pools = self._restore(self.pools, rows,
                                   self._full_tables(rp, for_scatter=True),
                                   jnp.int32(rp.state_block))
        return rp

    def release(self, rp: RequestPages) -> None:
        self.ops.release(rp)
