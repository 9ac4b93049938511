"""Composable decoder / encoder-decoder transformer over LayerSpec patterns.

The stack is organised as ``num_periods`` repetitions of the config's layer
pattern (jamba 8-layer interleave, gemma3 6-layer 5:1, plain archs period=1)
plus unrolled remainder layers.  Parameters for the repeated period are
*stacked* on a leading axis and the stack is applied with ``lax.scan`` —
keeping HLO size O(period) rather than O(layers), which is what makes the
512-device dry-run compiles of 80-layer configs tractable.

Decode scans the same periods while threading per-period cache slices.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionSpec, LayerSpec, ModelConfig
from repro.core.moe import EXPERT_WEIGHTS, DistContext
from repro.models import blocks
from repro.models.layers import apply_norm, init_norm

_ENC_SPEC = LayerSpec(mixer="attn", ffn="dense",
                      attn=AttentionSpec(kind="full", rope=False))


def _constrain(x, pspec):
    if pspec is None:
        return x
    return jax.lax.with_sharding_constraint(x, pspec)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: ModelConfig, dtype=None, *,
                mesh=None) -> dict:
    """Random weights in ``dtype`` (default ``cfg.dtype``), built under one
    jit: no eager per-period copies stacked after the fact.  With ``mesh``
    every leaf is born in its ``param_shardings`` layout, so each device
    materialises only its own shard."""
    init = functools.partial(_init_params, cfg=cfg,
                             dtype=jnp.dtype(cfg.dtype if dtype is None
                                             else dtype))
    out_shardings = None
    if mesh is not None:
        from repro.distributed.sharding import param_shardings
        out_shardings = param_shardings(jax.eval_shape(init, key), mesh, cfg)
    return jax.jit(init, out_shardings=out_shardings)(key)


def _init_params(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    keys = iter(jax.random.split(key, cfg.num_layers + cfg.encoder_layers + 8))
    cross = cfg.encoder_layers > 0
    pattern = cfg.pattern
    np_, rem = cfg.num_periods, cfg.remainder_layers

    params: dict = {
        "embed": jax.random.normal(next(keys), (cfg.padded_vocab, cfg.d_model),
                                   dtype) * 0.02,
        "final_norm": init_norm(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(
            next(keys), (cfg.d_model, cfg.padded_vocab), dtype) * (cfg.d_model ** -0.5)
    if cfg.learned_pos:
        params["pos_embed"] = jax.random.normal(
            next(keys), (cfg.learned_pos, cfg.d_model), dtype) * 0.02

    params["pre"] = [blocks.init_layer(next(keys), spec, cfg, cross, dtype)
                     for spec in cfg.prefix]
    if np_ > 1:
        # one period per lax.map iteration, written in place into the
        # stacked leaves: stacking per-period copies (or vmapping the draw)
        # holds a second full-size copy of the largest leaf
        def period(k):
            return [blocks.init_layer(kk, spec, cfg, cross, dtype)
                    for kk, spec in zip(jax.random.split(k, len(pattern)),
                                        pattern)]
        params["periods"] = jax.lax.map(period,
                                        jax.random.split(next(keys), np_))
    else:
        params["periods"] = None
        rem = cfg.num_layers - len(cfg.prefix)
    params["rem"] = [
        blocks.init_layer(next(keys), pattern[i % len(pattern)], cfg, cross, dtype)
        for i in range(rem)
    ]

    if cfg.encoder_layers:
        ek = iter(jax.random.split(next(keys), cfg.encoder_layers + 2))
        enc_layers = [blocks.init_layer(next(ek), _ENC_SPEC, cfg, False, dtype)
                      for _ in range(cfg.encoder_layers)]
        params["encoder"] = {
            "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_layers),
            "final_norm": init_norm(cfg.d_model, cfg.norm),
            "pos_embed": jax.random.normal(next(ek), (cfg.encoder_seq, cfg.d_model),
                                           dtype) * 0.02,
        }
    return params


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def embed_inputs(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.num_patch_tokens and "patches" in batch:
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    if cfg.learned_pos:
        S = x.shape[1]
        x = x + params["pos_embed"][:S][None]
    return x


def unembed(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        return (x @ head).astype(jnp.float32)


# ---------------------------------------------------------------------------
# encoder (whisper): frames are precomputed conv-frontend embeddings (stub)
# ---------------------------------------------------------------------------

def encode(params: dict, cfg: ModelConfig, frames: jax.Array,
           ctx: DistContext) -> jax.Array:
    enc = params["encoder"]
    x = frames.astype(enc["pos_embed"].dtype) + enc["pos_embed"][None]
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def body(x, layer_params):
        x, _ = blocks.apply_layer(layer_params, x, _ENC_SPEC, cfg, ctx,
                                  positions, causal=False)
        return x, None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return apply_norm(enc["final_norm"], x, cfg.norm)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def num_moe_layers(cfg: ModelConfig) -> int:
    """Length of the per-layer schedule vector (adaptive MACT) and of the
    ``load_per_layer`` telemetry matrix's leading axis."""
    return sum(1 for s in cfg.layer_specs() if s.ffn == "moe")


def forward(params: dict, cfg: ModelConfig, ctx: DistContext, batch: dict, *,
            return_cache: bool = False, cache_len: Optional[int] = None,
            cache_dtype=jnp.float32):
    """Returns (logits: (B, S, V) f32, stats: summed MoE stats).

    For MoE configs ``stats`` additionally carries ``load_per_layer``, the
    (L_moe, E) matrix of per-MoE-layer routed-token histograms in layer
    order — the telemetry source for adaptive MACT (core/telemetry.py,
    docs/DESIGN.md §Adaptive).  ``ctx.layer_schedules`` (one ScheduleSpec
    per MoE layer) overrides the global (moe_chunks, pipeline_chunks) per
    layer; when the vector differs *across* scanned periods the period scan
    is unrolled (per-layer schedules are static, and a scan body is one
    trace), while a vector uniform across periods keeps the O(period) HLO —
    and reproduces the global path bit-for-bit.

    ``return_cache=True`` is the single-pass serving prefill
    (docs/DESIGN.md §Serving): every layer additionally emits its decode
    cache (K/V rings, SSM state, cross K/V), laid out exactly as
    ``init_cache`` + token-by-token replay would have produced, and the
    return becomes (logits, stats, cache).  ``cache_len`` sizes the caches
    (default: the prompt length); linear caches require cache_len >= S.
    """
    for name in ("layer_schedules", "placements"):
        vec = getattr(ctx, name)
        if vec is not None:
            want = num_moe_layers(cfg)
            if len(vec) != want:
                raise ValueError(
                    f"{name} has {len(vec)} entries, "
                    f"config {cfg.name!r} has {want} MoE layers")
    enc_out = None
    if cfg.encoder_layers:
        enc_out = encode(params, cfg, batch["frames"], ctx)
    x = embed_inputs(params, cfg, batch)
    x = _constrain(x, ctx.act_pspec)
    B, S, _ = x.shape
    total_len = (cache_len if cache_len is not None else S) if return_cache else None
    cache_kw = (dict(cache_len=total_len, cache_dtype=cache_dtype)
                if return_cache else {})
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    pattern = cfg.pattern
    stats_total = blocks.zero_stats(cfg)
    E = cfg.moe.num_experts if cfg.moe else 1
    layer_loads: list = []        # (n, E) pieces, MoE-layer order
    moe_idx = 0                   # position in the per-layer schedule vector
    cache: dict = {"pos": jnp.int32(S)}

    def run_layer(layer_params, x, spec, moe_idx):
        lctx = blocks.layer_ctx(ctx, moe_idx if spec.ffn == "moe" else None)
        out = blocks.apply_layer(layer_params, x, spec, cfg, lctx,
                                 positions, enc_out=enc_out, **cache_kw)
        x, st = out[0], out[1]
        lc = out[2] if return_cache else None
        return _constrain(x, ctx.act_pspec), st, lc

    cache["pre"] = []
    for i, layer_params in enumerate(params.get("pre", [])):
        spec = cfg.prefix[i]
        x, st, lc = run_layer(layer_params, x, spec, moe_idx)
        cache["pre"].append(lc)
        stats_total = jax.tree.map(jnp.add, stats_total, st)
        if spec.ffn == "moe":
            layer_loads.append(st["load"][None])
            moe_idx += 1

    cache["periods"] = None
    if params["periods"] is not None:
        np_ = cfg.num_periods
        n_moe_pat = sum(1 for s in pattern if s.ffn == "moe")
        sched = ctx.layer_schedules
        plac = ctx.placements
        uniform = (sched is None or all(
            len({tuple(sched[moe_idx + p * n_moe_pat + m])
                 for p in range(np_)}) == 1
            for m in range(n_moe_pat))) and (plac is None or all(
                len({plac[moe_idx + p * n_moe_pat + m]
                     for p in range(np_)}) == 1
                for m in range(n_moe_pat)))

        if uniform:
            # one trace serves every period: resolve each pattern position's
            # ctx from period 0's schedule and keep the O(period) scan
            pat_ctx, m = {}, 0
            for i, spec in enumerate(pattern):
                if spec.ffn == "moe":
                    pat_ctx[i] = blocks.layer_ctx(ctx, moe_idx + m)
                    m += 1
                else:
                    pat_ctx[i] = ctx

            def body(x, period_params):
                stats_p = blocks.zero_stats(cfg)
                loads_p = []
                caches_p = []
                for i, spec in enumerate(pattern):
                    out = blocks.apply_layer(period_params[i], x, spec, cfg,
                                             pat_ctx[i], positions,
                                             enc_out=enc_out, **cache_kw)
                    x, st = out[0], out[1]
                    caches_p.append(out[2] if return_cache else None)
                    stats_p = jax.tree.map(jnp.add, stats_p, st)
                    if spec.ffn == "moe":
                        loads_p.append(st["load"])
                x = _constrain(x, ctx.act_pspec)
                loads_p = (jnp.stack(loads_p) if loads_p
                           else jnp.zeros((0, E), jnp.float32))
                return x, (stats_p, loads_p, caches_p)

            x, (stats_stack, loads_stack, caches_stack) = jax.lax.scan(
                body, x, params["periods"])
            stats_total = jax.tree.map(lambda a, s: a + s.sum(0), stats_total,
                                       stats_stack)
            if return_cache:
                cache["periods"] = caches_stack   # scan stacks over periods
            if n_moe_pat:
                layer_loads.append(loads_stack.reshape(np_ * n_moe_pat, E))
        else:
            # heterogeneous schedules inside the scanned region: unroll the
            # periods so each layer compiles under its own (bin, depth)
            period_caches = []
            for p in range(np_):
                period_params = jax.tree.map(lambda a, p=p: a[p],
                                             params["periods"])
                caches_p = []
                for i, spec in enumerate(pattern):
                    x, st, lc = run_layer(period_params[i], x, spec, moe_idx)
                    caches_p.append(lc)
                    stats_total = jax.tree.map(jnp.add, stats_total, st)
                    if spec.ffn == "moe":
                        layer_loads.append(st["load"][None])
                        moe_idx += 1
                period_caches.append(caches_p)
            if return_cache:
                cache["periods"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                                *period_caches)
        if uniform:
            moe_idx += np_ * n_moe_pat

    cache["rem"] = []
    for i, layer_params in enumerate(params["rem"]):
        spec = pattern[i % len(pattern)]
        x, st, lc = run_layer(layer_params, x, spec, moe_idx)
        cache["rem"].append(lc)
        stats_total = jax.tree.map(jnp.add, stats_total, st)
        if spec.ffn == "moe":
            layer_loads.append(st["load"][None])
            moe_idx += 1

    if cfg.moe is not None:
        stats_total["load_per_layer"] = (
            jnp.concatenate(layer_loads, axis=0) if layer_loads
            else jnp.zeros((0, E), jnp.float32))

    logits = unembed(params, cfg, x)
    logits = _constrain(logits, ctx.logits_pspec)
    if return_cache:
        return logits, stats_total, cache
    return logits, stats_total


# ---------------------------------------------------------------------------
# decode: single-token step with per-layer caches
# ---------------------------------------------------------------------------

def init_cache(params: dict, cfg: ModelConfig, batch_size: int, seq_len: int,
               dtype, enc_out: Optional[jax.Array] = None) -> dict:
    pattern = cfg.pattern
    cache: dict = {"pos": jnp.int32(0)}

    def layer_cache(spec: LayerSpec, layer_params, period=None):
        cross = layer_params.get("cross") if isinstance(layer_params, dict) else None
        if cross is not None and period is not None:
            # slice only the cross weights out of the stacked periods: an
            # eager slice of the whole layer copies its expert weights
            cross = jax.tree.map(lambda a: a[period], cross)
        return blocks.init_layer_cache(spec, cfg, batch_size, seq_len, dtype,
                                       enc_out=enc_out, cross_params=cross)

    cache["pre"] = [layer_cache(spec, params["pre"][i])
                    for i, spec in enumerate(cfg.prefix)]
    if params["periods"] is not None:
        n = cfg.num_periods
        per_period = [
            [layer_cache(spec, params["periods"][i], p)
             for i, spec in enumerate(pattern)]
            for p in range(n)
        ]
        cache["periods"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_period)
    else:
        cache["periods"] = None
    cache["rem"] = [
        layer_cache(pattern[i % len(pattern)], params["rem"][i])
        for i in range(len(params["rem"]))
    ]
    return cache


def decode_step(params: dict, cfg: ModelConfig, ctx: DistContext,
                cache: dict, tokens: jax.Array, *, return_load: bool = False,
                return_reads: bool = False, live: Optional[jax.Array] = None):
    """tokens: (B, 1) -> (logits (B, 1, V), new cache).  Position from cache.

    ``return_load=True`` appends the (L_moe, E) per-MoE-layer routed-load
    matrix to the return — same layer order as ``forward``'s
    ``load_per_layer`` (pre, scanned periods period-major, remainder) — the
    per-step telemetry source of the expert-aware serving path
    (docs/DESIGN.md §Residency).  ``return_reads=True`` appends the (L_moe,)
    int32 count of experts each MoE layer read.  ``live`` (B,) bool marks
    the rows that hold a request (default: all); a dead row adds no expert
    to the MoE's reads (docs/DESIGN.md §Decode MoE)."""
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.learned_pos:
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], jnp.minimum(pos, cfg.learned_pos - 1), 1, 0)[None]
    x = x.astype(params["embed"].dtype)
    pattern = cfg.pattern
    E = cfg.moe.num_experts if cfg.moe is not None else 1
    loads: list = []          # per MoE layer, in layer order: (n, E)
    reads: list = []          # (n,)
    new_cache: dict = {"pos": pos + 1}

    def run(layer_params, x, layer_cache, spec, layer=None):
        x, c, load, read = blocks.apply_layer_decode(
            layer_params, x, layer_cache, spec, cfg, ctx, pos, live=live,
            layer=layer)
        if spec.ffn != "moe":
            return x, c, jnp.zeros((0, E), jnp.float32), jnp.zeros((0,),
                                                                   jnp.int32)
        return x, c, load[None], read[None]

    new_pre = []
    for i, layer_params in enumerate(params.get("pre", [])):
        x, c, load, read = run(layer_params, x, cache["pre"][i], cfg.prefix[i])
        new_pre.append(c)
        loads.append(load)
        reads.append(read)
    new_cache["pre"] = new_pre

    if params["periods"] is not None:
        periods = params["periods"]
        # MoE expert weights stay stacked over the periods: the decode MoE
        # indexes its layer inside the branch that reads an expert, so no
        # period's expert weights are sliced out (a copy) by the scan
        scanned = [
            {**pp, "ffn": {k: v for k, v in pp["ffn"].items()
                           if k not in EXPERT_WEIGHTS}}
            if spec.ffn == "moe" else pp
            for pp, spec in zip(periods, pattern)]

        def body(x, inp):
            period_params, period_cache, p = inp
            new_pc, loads_p, reads_p = [], [], []
            for i, spec in enumerate(pattern):
                lp, layer = period_params[i], None
                if spec.ffn == "moe":
                    lp = {**lp, "ffn": {**lp["ffn"], **{
                        k: periods[i]["ffn"][k] for k in EXPERT_WEIGHTS}}}
                    layer = p
                x, c, load, read = run(lp, x, period_cache[i], spec, layer)
                new_pc.append(c)
                loads_p.append(load)
                reads_p.append(read)
            return x, (new_pc, jnp.concatenate(loads_p),
                       jnp.concatenate(reads_p))

        x, (new_periods, loads_stack, reads_stack) = jax.lax.scan(
            body, x, (scanned, cache["periods"],
                      jnp.arange(cfg.num_periods)))
        loads.append(loads_stack.reshape(-1, E))
        reads.append(reads_stack.reshape(-1))
        new_cache["periods"] = new_periods
    else:
        new_cache["periods"] = None

    new_rem = []
    for i, layer_params in enumerate(params["rem"]):
        x, c, load, read = run(layer_params, x, cache["rem"][i],
                               pattern[i % len(pattern)])
        new_rem.append(c)
        loads.append(load)
        reads.append(read)
    new_cache["rem"] = new_rem

    out = (unembed(params, cfg, x), new_cache)
    if return_load:
        out += (jnp.concatenate(loads, axis=0),)
    if return_reads:
        out += (jnp.concatenate(reads, axis=0),)
    return out


def extend_step(params: dict, cfg: ModelConfig, ctx: DistContext,
                cache: dict, tokens: jax.Array, *, return_load: bool = False):
    """tokens: (B, C) -> (logits (B, C, V), new cache).  Multi-token cache
    extension — the serving chunked-prefill continuation (docs/DESIGN.md
    §Serving): each chunk attends over the cache so far plus itself, then
    its K/V joins the cache.  ``decode_step`` is the C == 1 special case
    (kept separate: decode stays on the length-mask fast path).

    ``return_load=True`` appends the (L_moe, E) routed-load matrix, exactly
    as in ``decode_step``."""
    pos0 = cache["pos"]
    B, C = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.learned_pos:
        idx = jnp.clip(pos0 + jnp.arange(C), 0, cfg.learned_pos - 1)
        x = x + jnp.take(params["pos_embed"], idx, axis=0)[None]
    x = x.astype(params["embed"].dtype)
    pattern = cfg.pattern
    E = cfg.moe.num_experts if cfg.moe is not None else 1
    layer_loads: list = []
    new_cache: dict = {"pos": pos0 + C}

    new_pre = []
    for i, layer_params in enumerate(params.get("pre", [])):
        out = blocks.apply_layer_extend(layer_params, x, cache["pre"][i],
                                        cfg.prefix[i], cfg, ctx, pos0,
                                        return_load=return_load)
        x, c = out[0], out[1]
        new_pre.append(c)
        if return_load and cfg.prefix[i].ffn == "moe":
            layer_loads.append(out[2][None])
    new_cache["pre"] = new_pre

    if params["periods"] is not None:
        def body(x, inp):
            period_params, period_cache = inp
            new_pc = []
            loads_p = []
            for i, spec in enumerate(pattern):
                out = blocks.apply_layer_extend(period_params[i], x,
                                                period_cache[i], spec, cfg,
                                                ctx, pos0,
                                                return_load=return_load)
                x = out[0]
                new_pc.append(out[1])
                if return_load and spec.ffn == "moe":
                    loads_p.append(out[2])
            if not return_load:
                return x, new_pc
            loads_p = (jnp.stack(loads_p) if loads_p
                       else jnp.zeros((0, E), jnp.float32))
            return x, (new_pc, loads_p)

        x, ys = jax.lax.scan(body, x, (params["periods"], cache["periods"]))
        if return_load:
            new_periods, loads_stack = ys
            n_moe_pat = sum(1 for s in pattern if s.ffn == "moe")
            if n_moe_pat:
                layer_loads.append(
                    loads_stack.reshape(cfg.num_periods * n_moe_pat, E))
        else:
            new_periods = ys
        new_cache["periods"] = new_periods
    else:
        new_cache["periods"] = None

    new_rem = []
    for i, layer_params in enumerate(params["rem"]):
        spec = pattern[i % len(pattern)]
        out = blocks.apply_layer_extend(layer_params, x, cache["rem"][i],
                                        spec, cfg, ctx, pos0,
                                        return_load=return_load)
        x, c = out[0], out[1]
        new_rem.append(c)
        if return_load and spec.ffn == "moe":
            layer_loads.append(out[2][None])
    new_cache["rem"] = new_rem

    logits = unembed(params, cfg, x)
    if return_load:
        load_per_layer = (jnp.concatenate(layer_loads, axis=0) if layer_loads
                          else jnp.zeros((0, E), jnp.float32))
        return logits, new_cache, load_per_layer
    return logits, new_cache
