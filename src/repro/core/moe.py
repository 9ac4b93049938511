"""The MemFine MoE layer: router + FCDA chunking + selectable expert strategy.

Strategies (docs/DESIGN.md §2):
  * ``ep_shardmap`` — experts sharded over the model axis, explicit
    all-to-all dispatch/combine per chunk (core/ep.py).  Requires the expert
    count, batch and sequence to divide the mesh axes.
  * ``tp_gspmd``    — experts replicated, expert FFN tensor-parallel on d_ff
    via GSPMD; dispatch is per-sequence-row (vmapped), so the sort never
    crosses devices.  Works for any expert count and for tiny decode batches.
  * ``dense``       — every expert on every token, masked combine.  O(E)
    compute; only used as a numerical oracle in tests.

Decode (one token per row, ``moe_ffn_decode``) takes its own local path:
each expert's weights are read once per wave, and only where a live token
routes to it.

All strategies share the same router and the same FCDA chunk loop, so Method
1/2/3 comparisons (paper §5) are pure config switches.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.configs.base import MoEConfig
from repro.core import dispatch as dsp
from repro.core.chunking import chunked_map
from repro.core.ep import moe_ffn_ep
from repro.core.router import init_router, route
from repro.kernels.ops import expert_ffn
from repro.kernels.ref import expert_ffn_ref


@dataclass(frozen=True)
class DistContext:
    """How the current step is distributed; plumbed through the model."""
    mesh: Optional[object] = None          # jax.sharding.Mesh or None (local)
    batch_axes: tuple = ("data",)
    ep_axis: str = "model"
    moe_chunks: int = 1                    # FCDA chunk count (MACT-selected)
    pipeline_chunks: int = 1               # FCDA schedule depth: 1 = sequential
                                           # loop, >= 2 = overlapped chunks with
                                           # that many live at once (EP path,
                                           # docs/DESIGN.md §Pipeline)
    remat_chunks: bool = True              # Eq. (7) per-chunk recomputation
    use_pallas: bool = False
    pallas_interpret: bool = False         # lower kernels in interpret mode
                                           # (CPU dry-run of the kernel path)
    moe_strategy: str = "auto"             # overrides MoEConfig.strategy
    moe_ragged: bool = False               # MegaBlocks-style flat expert buffers
    moe_fused: bool = False                # single-launch fused expert leg over
                                           # the ragged layout (implies it):
                                           # kernels/fused_moe.py; Eq. 2 loses
                                           # the dispatch-buffer term
    ragged_block: int = 128                # ragged-layout row-block size
    layer_schedules: Optional[tuple] = None  # adaptive MACT: one ScheduleSpec
                                           # (chunks, depth) per MoE layer, in
                                           # layer order; overrides moe_chunks/
                                           # pipeline_chunks per layer
                                           # (docs/DESIGN.md §Adaptive)
    placement: Optional[object] = None     # PlacementSpec for THIS layer's EP
                                           # expert->peer map + replicas
                                           # (core/placement.py); None =
                                           # identity contiguous mapping
    placements: Optional[tuple] = None     # one PlacementSpec per MoE layer,
                                           # resolved to ``placement`` by
                                           # blocks.layer_ctx
                                           # (docs/DESIGN.md §Placement)
    act_pspec: Optional[object] = None     # PartitionSpec for (B, S, d) activations
    logits_pspec: Optional[object] = None  # PartitionSpec for (B, S, V) logits
    heads_pspec: Optional[object] = None   # PartitionSpec for (B, S, H, hd) q/k/v


def init_moe(key: jax.Array, d_model: int, cfg: MoEConfig,
             dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 7)
    E, f = cfg.num_experts, cfg.d_ff_expert
    scale_in = d_model ** -0.5
    scale_out = f ** -0.5
    params = {
        "router": init_router(ks[0], d_model, E),
        "w1": jax.random.normal(ks[1], (E, d_model, f), dtype) * scale_in,
        "w3": jax.random.normal(ks[2], (E, d_model, f), dtype) * scale_in,
        "w2": jax.random.normal(ks[3], (E, f, d_model), dtype) * scale_out,
    }
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * f
        params["shared"] = {
            "w1": jax.random.normal(ks[4], (d_model, fs), dtype) * scale_in,
            "w3": jax.random.normal(ks[5], (d_model, fs), dtype) * scale_in,
            "w2": jax.random.normal(ks[6], (fs, d_model), dtype) * scale_out,
        }
    return params


def resolve_strategy(cfg: MoEConfig, x_shape: tuple, ctx: DistContext) -> str:
    """Pick the expert strategy for this (config, shape, mesh)."""
    want = ctx.moe_strategy if ctx.moe_strategy != "auto" else cfg.strategy
    if want not in ("auto", "ep_shardmap"):
        return want
    if ctx.mesh is None:
        return "tp_gspmd"
    shape = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    P = shape.get(ctx.ep_axis, 1)
    batch_div = 1
    for a in ctx.batch_axes:
        batch_div *= shape.get(a, 1)
    B, S = x_shape[0], x_shape[1]
    ok = (cfg.num_experts % P == 0 and B % batch_div == 0 and S % P == 0
          and (B // batch_div) * (S // P) % ctx.moe_chunks == 0
          and (B // batch_div) * (S // P) >= ctx.moe_chunks)
    if ok:
        return "ep_shardmap"
    if want == "ep_shardmap":
        raise ValueError(
            f"ep_shardmap requested but E={cfg.num_experts}, B={B}, S={S} "
            f"do not divide mesh axes {shape}")
    return "tp_gspmd"


# ---------------------------------------------------------------------------
# tp_gspmd / local path: per-row dispatch, replicated experts, TP FFN
# ---------------------------------------------------------------------------

def _moe_ffn_rows(params: dict, x: jax.Array, cfg: MoEConfig,
                  ctx: DistContext):
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k

    def row_fn(xrow):
        def chunk_fn(xc):
            t_c = xc.shape[0]
            with jax.named_scope("router"):
                r = route(params["router"], xc, cfg)
            if cfg.capacity_mode == "dropless":
                cap = dsp.dropless_capacity(t_c)
            else:
                cap = dsp.balanced_capacity(t_c, k, E, cfg.capacity_factor)
            # single-sort planner (num_peers=1: the expert layout IS the
            # device layout) — same plan the EP path derives per chunk.
            # Dispatch stays on the jnp scatter here: this path is vmapped
            # over batch rows and the Pallas dispatch kernels want the
            # un-vmapped flat layout (the EP path is where chunked dispatch
            # overhead actually bites); the expert FFN honors use_pallas.
            with jax.named_scope("dispatch"):
                uplan = dsp.make_unified_plan(r.expert_idx, E, 1,
                                              cap_expert=cap)
                plan = dsp.DispatchPlan(uplan.expert_slots,
                                        uplan.expert_load, uplan.drops_expert)
                buf = dsp.scatter_rows(xc, plan, E, cap)
            with jax.named_scope("experts"):
                h = expert_ffn(buf, params["w1"], params["w3"], params["w2"],
                               use_pallas=ctx.use_pallas)
            with jax.named_scope("combine"):
                y = dsp.gather_rows(h, plan, r.weights)
            stats = {"aux_loss": r.aux_loss,
                     "load": r.load.astype(jnp.float32),
                     "drops": plan.drops.astype(jnp.float32)}
            return y, stats

        return chunked_map(chunk_fn, xrow, ctx.moe_chunks, remat=ctx.remat_chunks)

    y, stats = jax.vmap(row_fn)(x)
    stats = {
        "aux_loss": stats["aux_loss"].mean() / ctx.moe_chunks,
        "load": stats["load"].sum(0),
        "drops": stats["drops"].sum(),
    }
    return y, stats


# ---------------------------------------------------------------------------
# decode path: one token per row, only the experts live tokens route to
# (docs/DESIGN.md §Decode MoE)
# ---------------------------------------------------------------------------

EXPERT_WEIGHTS = ("w1", "w3", "w2")


def _routed_experts(router: dict, experts: dict, layer, x: jax.Array,
                    live: jax.Array, cfg: MoEConfig):
    """The decode MoE over a wave's tokens: x (T, d), live (T,) bool.

    Each expert's weights are read once, inside a ``lax.cond`` taken only
    when some live token routes to it, for all T tokens at once.
    ``experts`` holds the (E, ...) weights, or with ``layer`` the weights
    stacked over scanned layers, (L, E, ...): the expert is indexed inside
    the branch, where the slice fuses into the dot, so no layer's weights
    are copied.  A dead token adds no expert and gets y = 0.

    Returns (y (T, d), per-token aux (T,), per-token load (T, E) float32,
    experts read () int32).
    """
    E = cfg.num_experts
    with jax.named_scope("router"):
        # each token routed on its own, as the row path routes each row
        r = jax.vmap(lambda t: route(router, t[None], cfg))(x)
    with jax.named_scope("dispatch"):
        hit = r.expert_idx[:, 0, :, None] == jnp.arange(E)     # (T, K, E)
        comb = jnp.where(hit, r.weights[:, 0, :, None], 0).sum(1)
        comb = jnp.where(live[:, None], comb, 0)               # (T, E)
        used = (hit.any(1) & live[:, None]).any(0)             # (E,)
    at = () if layer is None else (layer,)

    def read(e, y):
        w1, w3, w2 = (experts[k][at + (e,)][None] for k in EXPERT_WEIGHTS)
        return y + comb[:, e, None] * expert_ffn_ref(x[None], w1, w3, w2)[0]

    y = jnp.zeros_like(x)
    with jax.named_scope("experts"):
        for e in range(E):
            y = jax.lax.cond(used[e], functools.partial(read, e),
                             lambda y: y, y)
    return (y, r.aux_loss, r.load.astype(jnp.float32),
            used.sum(dtype=jnp.int32))


@functools.lru_cache(maxsize=None)
def _decode_call(cfg: MoEConfig):
    """``_routed_experts`` for ``cfg``, with a vmap rule that folds the
    mapped axis (the serving slots) into the tokens.  Under plain vmap each
    ``cond`` would become a select running every branch, once per slot."""
    fn = functools.partial(_routed_experts, cfg=cfg)
    call = custom_vmap(fn)

    @call.def_vmap
    def fold(n, in_batched, router, experts, layer, x, live):
        if any(jax.tree.leaves(in_batched[:3])):
            # weights differ per instance: nothing to share between them
            axes = jax.tree.map(lambda b: 0 if b else None, in_batched)
            return (jax.vmap(fn, in_axes=axes)(router, experts, layer, x,
                                               live), (True,) * 4)
        if not in_batched[3]:
            x = jnp.broadcast_to(x, (n,) + x.shape)
        if not in_batched[4]:
            live = jnp.broadcast_to(live, (n,) + live.shape)
        T = x.shape[1]
        y, aux, load, reads = call(router, experts, layer,
                                   x.reshape((n * T,) + x.shape[2:]),
                                   live.reshape(n * T))
        return ((y.reshape(x.shape), aux.reshape(n, T),
                 load.reshape(n, T, -1), reads),
                (True, True, True, False))

    return call


def moe_ffn_decode(params: dict, x: jax.Array, cfg: MoEConfig,
                   ctx: DistContext, live: Optional[jax.Array] = None,
                   layer=None):
    """Decode MoE, x: (B, 1, d) -> (y, stats, experts read () int32).

    On the local path (``tp_gspmd``) with one token per row, it reads each
    expert's w1, w3 and w2 once, and only if a live token routes to it;
    under vmap the mapped axis joins the tokens.  ``live`` (B,) bool marks
    rows holding a request (default: all); a dead row adds no expert.
    ``layer`` says the expert weights are stacked over scanned layers and
    picks this one.  ``stats`` are the row path's numbers: ``load`` of every
    row, dead ones included, ``drops`` 0 (one token cannot overflow an
    expert) and ``aux_loss`` the mean per-row aux.  Other strategies and
    shapes run ``moe_ffn``, and read all E experts.
    """
    B, S, _ = x.shape
    if S != 1 or resolve_strategy(cfg, x.shape, ctx) != "tp_gspmd":
        if layer is not None:
            params = {**params,
                      **{k: params[k][layer] for k in EXPERT_WEIGHTS}}
        y, stats = moe_ffn(params, x, cfg, ctx)
        return y, stats, jnp.int32(cfg.num_experts)
    if live is None:
        live = jnp.ones((B,), bool)
    y, aux, load, reads = _decode_call(cfg)(
        params["router"], {k: params[k] for k in EXPERT_WEIGHTS}, layer,
        x[:, 0], live)
    stats = {"aux_loss": aux.mean() / ctx.moe_chunks, "load": load.sum(0),
             "drops": jnp.float32(0)}
    y = y[:, None]
    if "shared" in params:
        with jax.named_scope("experts"):
            y = y + _shared_expert(params, x)
    return y, stats, reads


# ---------------------------------------------------------------------------
# dense oracle: compute every expert on every token (tests only)
# ---------------------------------------------------------------------------

def _moe_ffn_dense(params: dict, x: jax.Array, cfg: MoEConfig,
                   ctx: DistContext):
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    with jax.named_scope("router"):
        r = route(params["router"], x2, cfg)
    with jax.named_scope("experts"):
        xe = jnp.broadcast_to(x2[None], (cfg.num_experts,) + x2.shape)
        h = expert_ffn(xe, params["w1"], params["w3"], params["w2"],
                       use_pallas=False)                   # (E, T, d)
    with jax.named_scope("combine"):
        onehot = jax.nn.one_hot(r.expert_idx, cfg.num_experts, dtype=h.dtype)
        w = (onehot * r.weights[..., None].astype(h.dtype)).sum(1)  # (T, E)
        y = jnp.einsum("te,etd->td", w, h)
    stats = {"aux_loss": r.aux_loss, "load": r.load.astype(jnp.float32),
             "drops": jnp.float32(0)}
    return y.reshape(B, S, d), stats


# ---------------------------------------------------------------------------
# public layer
# ---------------------------------------------------------------------------

def _shared_expert(params: dict, x: jax.Array) -> jax.Array:
    s = params["shared"]
    h = jax.nn.silu(x @ s["w1"]) * (x @ s["w3"])
    return h @ s["w2"]


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig, ctx: DistContext):
    """x: (B, S, d) -> (y, stats).

    Stats contract (identical across strategies, asserted by
    tests/test_moe_stats.py):

    * ``load``  — (E,) float32, the TOTAL routed token-slot demand per expert
      for the whole step (pre-capacity-clip), summed over batch rows, chunks
      and devices — never a per-row or per-chunk mean.
    * ``drops`` — float32 scalar, the TOTAL token-slots dropped this step
      (send-side peer-capacity + receive-side expert-capacity on the EP
      path); exactly 0.0 under ``capacity_mode="dropless"``.
    * ``aux_loss`` — float32 scalar, the MEAN per-chunk Switch auxiliary
      loss (averaged over chunks and over whatever granularity routed
      independently: EP devices for ep_shardmap, batch rows for tp_gspmd —
      aux is nonlinear, so these can differ across strategies even though
      load/drops match exactly).
    """
    strategy = resolve_strategy(cfg, x.shape, ctx)
    if strategy == "ep_shardmap":
        y, stats = moe_ffn_ep(params, x, cfg, ctx.mesh,
                              batch_axes=ctx.batch_axes, ep_axis=ctx.ep_axis,
                              chunks=ctx.moe_chunks, remat=ctx.remat_chunks,
                              use_pallas=ctx.use_pallas,
                              interpret=ctx.pallas_interpret,
                              ragged=ctx.moe_ragged,
                              pipeline=ctx.pipeline_chunks,
                              ragged_block=ctx.ragged_block,
                              fused=ctx.moe_fused,
                              placement=ctx.placement)
        stats = dict(stats)
        stats["aux_loss"] = stats["aux_loss"] / ctx.moe_chunks
    elif strategy == "tp_gspmd":
        y, stats = _moe_ffn_rows(params, x, cfg, ctx)
    elif strategy == "dense":
        y, stats = _moe_ffn_dense(params, x, cfg, ctx)
    else:
        raise ValueError(f"unknown MoE strategy {strategy!r}")
    if "shared" in params:
        with jax.named_scope("experts"):
            y = y + _shared_expert(params, x)
    return y, stats
