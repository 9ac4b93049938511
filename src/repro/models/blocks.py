"""One transformer layer: mixer (attention | mamba) + FFN (dense | MoE | none).

Remat policy (docs/DESIGN.md §2):
  * "none"    — store everything (m_g copies in the memory model).
  * "full"    — jax.checkpoint around the whole layer = Megatron full
                recomputation (paper Method 1 when moe_chunks=1).
  * "memfine" — same layer checkpoint, but the MoE inside additionally
                chunk-recomputes (Eq. 7); selected via ctx.moe_chunks > 1
                with remat_chunks=True.  Nested checkpoints compose: during
                a layer's backward, only ONE chunk's dispatch buffers live.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import LayerSpec, ModelConfig
from repro.core.chunking import ScheduleSpec
from repro.core.moe import DistContext, init_moe, moe_ffn, moe_ffn_decode
from repro.models import ssm as ssm_mod
from repro.models.attention import attention, decode_attention, extend_attention
from repro.models.layers import (apply_mlp, apply_norm, apply_rope,
                                 init_attention, init_mlp, init_norm)


def zero_stats(cfg: ModelConfig) -> dict:
    E = cfg.moe.num_experts if cfg.moe else 1
    return {"aux_loss": jnp.float32(0), "load": jnp.zeros((E,), jnp.float32),
            "drops": jnp.float32(0)}


def layer_ctx(ctx: DistContext, moe_index: Optional[int]) -> DistContext:
    """The DistContext one MoE layer actually runs under.

    With a heterogeneous schedule vector (``ctx.layer_schedules``, adaptive
    MACT — docs/DESIGN.md §Adaptive) the layer at MoE position ``moe_index``
    gets its own (chunk bin, pipeline depth), and with a placement vector
    (``ctx.placements``, docs/DESIGN.md §Placement) its own expert->peer
    map; otherwise the global knobs apply unchanged.  The returned ctx drops
    the per-layer vectors so the MoE layer below sees exactly the static
    knobs it always did.
    """
    if moe_index is None or (ctx.layer_schedules is None
                             and ctx.placements is None):
        return ctx
    changes: dict = {}
    if ctx.layer_schedules is not None:
        spec = ScheduleSpec(*ctx.layer_schedules[moe_index])
        changes.update(moe_chunks=spec.chunks, pipeline_chunks=spec.depth,
                       layer_schedules=None)
    if ctx.placements is not None:
        changes.update(placement=ctx.placements[moe_index], placements=None)
    return dataclasses.replace(ctx, **changes)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key: jax.Array, spec: LayerSpec, cfg: ModelConfig,
               cross_attention: bool = False, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": init_norm(cfg.d_model, cfg.norm)}
    if spec.mixer == "attn":
        p["mixer"] = init_attention(ks[0], cfg.d_model, cfg.num_heads,
                                    cfg.num_kv_heads, cfg.resolved_head_dim,
                                    qk_norm=spec.attn.qk_norm, dtype=dtype)
    else:
        p["mixer"] = ssm_mod.init_ssm(ks[0], cfg.d_model, spec.ssm, dtype)
    if cross_attention:
        p["norm_x"] = init_norm(cfg.d_model, cfg.norm)
        p["cross"] = init_attention(ks[3], cfg.d_model, cfg.num_heads,
                                    cfg.num_kv_heads, cfg.resolved_head_dim,
                                    dtype=dtype)
    if spec.ffn != "none":
        p["norm2"] = init_norm(cfg.d_model, cfg.norm)
        if spec.ffn == "dense":
            p["ffn"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
        else:
            p["ffn"] = init_moe(ks[1], cfg.d_model, cfg.moe, dtype)
    return p


# ---------------------------------------------------------------------------
# attention mixer (train/prefill and decode)
# ---------------------------------------------------------------------------

def _hconstrain(x: jax.Array, ctx: DistContext) -> jax.Array:
    """Pin (B, S, H, hd) tensors to head sharding — GSPMD cannot derive it
    through the (KH, G) reshape/repeat and otherwise replicates the score
    tensors (observed 34 GB/device in the dry-run).  Uneven H pads."""
    if ctx.heads_pspec is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.heads_pspec)


def _qkv_base(p: dict, x: jax.Array, cfg: ModelConfig, spec: LayerSpec,
              positions: jax.Array):
    """Projections + qk-norm + RoPE, KV still at KH heads (the cache layout)."""
    B, S, _ = x.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KH, hd)
    v = (x @ p["wv"]).reshape(B, S, KH, hd)
    if "q_norm" in p:
        q = apply_norm(p["q_norm"], q)
        k = apply_norm(p["k_norm"], k)
    if spec.attn.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _qkv(p: dict, x: jax.Array, cfg: ModelConfig, spec: LayerSpec,
         positions: jax.Array, ctx: DistContext, return_raw: bool = False):
    from repro.models.attention import repeat_kv
    S = x.shape[1]
    H = cfg.num_heads
    q, k, v = _qkv_base(p, x, cfg, spec, positions)
    raw = (k, v)
    if S > 1:  # train/prefill: repeat KV to H so every score dim shards
        k = repeat_kv(k, H)
        v = repeat_kv(v, H)
        q, k, v = _hconstrain(q, ctx), _hconstrain(k, ctx), _hconstrain(v, ctx)
        # named for the "selective" remat policy: saving these avoids
        # re-running the sequence-parallel all-gathers during recompute
        q = checkpoint_name(q, "qkv")
        k = checkpoint_name(k, "qkv")
        v = checkpoint_name(v, "qkv")
    if return_raw:
        return q, k, v, raw
    return q, k, v


def attn_mixer(p: dict, x: jax.Array, cfg: ModelConfig, spec: LayerSpec,
               positions: jax.Array, ctx: DistContext,
               causal: bool = True, return_kv: bool = False):
    """Train/prefill attention.  ``return_kv`` additionally returns the
    pre-repeat (B, S, KH, hd) K/V — what single-pass prefill writes into the
    decode cache (docs/DESIGN.md §Serving)."""
    B, S = x.shape[:2]
    if return_kv:
        q, k, v, raw = _qkv(p, x, cfg, spec, positions, ctx, return_raw=True)
    else:
        q, k, v = _qkv(p, x, cfg, spec, positions, ctx)
    out = attention(q, k, v, spec.attn, causal=causal)
    y = out.reshape(B, S, -1) @ p["wo"]
    return (y, raw) if return_kv else y


def cache_len(spec: LayerSpec, seq_len: int) -> int:
    if spec.attn.kind in ("window", "chunked") and spec.attn.window:
        return min(spec.attn.window, seq_len)
    return seq_len


def attn_mixer_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                      cfg: ModelConfig, spec: LayerSpec, ctx: DistContext):
    """x: (B, 1, d).  cache: {"k","v"}: (B, Sc, KH, hd).  pos: scalar int."""
    B = x.shape[0]
    q, k, v = _qkv(p, x, cfg, spec, pos[None, None].astype(jnp.int32)
                   * jnp.ones((B, 1), jnp.int32), ctx)
    Sc = cache["k"].shape[1]
    if spec.attn.kind == "window" and spec.attn.window and Sc == spec.attn.window:
        write = pos % Sc
        length = jnp.minimum(pos + 1, Sc) * jnp.ones((B,), jnp.int32)
    elif spec.attn.kind == "chunked" and spec.attn.window and Sc == spec.attn.window:
        write = pos % Sc
        length = (pos % Sc + 1) * jnp.ones((B,), jnp.int32)   # chunk-local context
    else:
        write = pos
        length = (pos + 1) * jnp.ones((B,), jnp.int32)
    # the cache may be wider than the weights (bf16 weights, f32 cache):
    # write in the cache's dtype, return to the residual stream's
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), write, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), write, axis=1)
    out = decode_attention(q, k_cache, v_cache, length, spec.attn)
    y = out.reshape(B, 1, -1).astype(x.dtype) @ p["wo"]
    return y, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# cache layout: single-pass prefill + chunked extension (docs/DESIGN.md §Serving)
# ---------------------------------------------------------------------------

def _is_ring(spec: LayerSpec, num_slots: int) -> bool:
    """The decode path rings exactly when the cache is window-sized."""
    return (spec.attn.kind in ("window", "chunked") and bool(spec.attn.window)
            and num_slots == spec.attn.window)


def slot_positions(spec: LayerSpec, num_slots: int, filled) -> jax.Array:
    """Token position held by each cache slot after ``filled`` writes
    (-1 = never written).  Linear caches hold position i at slot i; ring
    caches hold the newest position p < filled with p % num_slots == i."""
    i = jnp.arange(num_slots)
    if _is_ring(spec, num_slots):
        pos = i + ((filled - 1 - i) // num_slots) * num_slots
    else:
        pos = i
    return jnp.where(i < filled, jnp.maximum(pos, i), -1)


def build_attn_cache(k: jax.Array, v: jax.Array, spec: LayerSpec,
                     total_len: int, dtype) -> dict:
    """Lay a prompt's (B, S, KH, hd) K/V out as the decode cache the replay
    loop would have produced, bit-for-bit: linear caches get the prompt at
    slots 0..S-1, ring caches the last ``window`` tokens at slots p % W."""
    B, S = k.shape[:2]
    Sc = cache_len(spec, total_len)
    ring = _is_ring(spec, Sc)
    if S > Sc and not ring:
        raise ValueError(f"prompt length {S} exceeds the {Sc}-slot linear "
                         f"cache (cache_len={total_len})")

    def lay(t):
        t = t.astype(dtype)
        if ring and S >= Sc:
            return jnp.roll(t[:, S - Sc:], (S - Sc) % Sc, axis=1)
        buf = jnp.zeros((B, Sc) + t.shape[2:], dtype)
        return jax.lax.dynamic_update_slice_in_dim(buf, t, 0, axis=1)

    return {"k": lay(k), "v": lay(v)}


def write_attn_cache(cache: dict, k: jax.Array, v: jax.Array, pos0,
                     spec: LayerSpec) -> dict:
    """Write a C-token chunk starting at position ``pos0`` into the cache,
    ring or linear — the multi-token generalisation of the decode write."""
    Sc = cache["k"].shape[1]
    C = k.shape[1]
    if _is_ring(spec, Sc):
        if C >= Sc:           # only the last Sc tokens survive a full wrap
            k, v, pos0, C = k[:, C - Sc:], v[:, C - Sc:], pos0 + C - Sc, Sc
        idx = (pos0 + jnp.arange(C)) % Sc
        return {"k": cache["k"].at[:, idx].set(k.astype(cache["k"].dtype)),
                "v": cache["v"].at[:, idx].set(v.astype(cache["v"].dtype))}
    return {"k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), pos0, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), pos0, axis=1)}


def gather_paged_tokens(pool: jax.Array, table: jax.Array, token_axis: int,
                        length: int) -> jax.Array:
    """Assemble a dense token cache from page-pool rows.

    ``pool``: (P, *page_shape) where ``page_shape[token_axis]`` is the page
    size; ``table``: (..., n_blocks) int32 page ids (page 0 is the shared
    zero page, so never-filled blocks read as the zero-initialised cache —
    docs/DESIGN.md §Paging).  Returns (..., *dense_shape) with the token
    axis merged to ``n_blocks * page`` and sliced to ``length`` (ragged
    layouts pad the last page).
    """
    lead = table.ndim - 1
    x = pool[table]                       # (..., n_blocks, *page_shape)
    a = lead + token_axis
    x = jnp.moveaxis(x, lead, a)          # block axis next to its page axis
    sh = x.shape
    x = x.reshape(sh[:a] + (sh[a] * sh[a + 1],) + sh[a + 2:])
    return jax.lax.slice_in_dim(x, 0, length, axis=a)


def scatter_paged_tokens(pool: jax.Array, table: jax.Array, dense: jax.Array,
                         token_axis: int, page: int) -> jax.Array:
    """Inverse of ``gather_paged_tokens``: split a dense token cache into
    page rows and scatter them at ``table``'s ids.  Ragged token axes are
    zero-padded into the last page's tail (never gathered back).  Duplicate
    ids (CoW-shared pages gathered by several slots) carry bit-identical
    rows, so scatter order cannot matter; scratch-page ids (1) absorb
    writes from unallocated blocks and inactive slots."""
    lead = table.ndim - 1
    a = lead + token_axis
    nb = table.shape[-1]
    pad = nb * page - dense.shape[a]
    if pad:
        width = [(0, 0)] * dense.ndim
        width[a] = (0, pad)
        dense = jnp.pad(dense, width)
    sh = dense.shape
    dense = dense.reshape(sh[:a] + (nb, page) + sh[a + 1:])
    dense = jnp.moveaxis(dense, a, lead)  # (..., n_blocks, *page_shape)
    return pool.at[table].set(dense)


def _extend_mask(spec: LayerSpec, key_pos: jax.Array,
                 q_pos: jax.Array) -> jax.Array:
    """(C, Skv) visibility: causal over key *positions* (-1 = empty slot),
    window-banded / chunk-local per the attention kind."""
    m = (key_pos[None, :] >= 0) & (key_pos[None, :] <= q_pos[:, None])
    if spec.attn.kind == "window" and spec.attn.window:
        m &= key_pos[None, :] > q_pos[:, None] - spec.attn.window
    elif spec.attn.kind == "chunked" and spec.attn.window:
        m &= (key_pos[None, :] // spec.attn.window
              == q_pos[:, None] // spec.attn.window)
    return m


def attn_mixer_extend(p: dict, x: jax.Array, cache: dict, pos0,
                      cfg: ModelConfig, spec: LayerSpec, ctx: DistContext):
    """x: (B, C, d) chunk at positions pos0..pos0+C-1.  Attends over the
    cache-before-this-chunk plus the chunk's own K/V (so ring overwrites
    within the chunk cannot clobber still-visible keys), then writes the
    chunk into the cache.  Returns (y, new {"k","v"})."""
    B, C, _ = x.shape
    positions = pos0 + jnp.arange(C)
    q, k, v = _qkv_base(p, x, cfg, spec,
                        jnp.broadcast_to(positions, (B, C)))
    Sc = cache["k"].shape[1]
    key_pos = jnp.concatenate([slot_positions(spec, Sc, pos0), positions])
    mask = _extend_mask(spec, key_pos, positions)
    k_cat = jnp.concatenate([cache["k"], k.astype(cache["k"].dtype)], axis=1)
    v_cat = jnp.concatenate([cache["v"], v.astype(cache["v"].dtype)], axis=1)
    out = extend_attention(q, k_cat, v_cat, mask)
    y = out.reshape(B, C, -1).astype(x.dtype) @ p["wo"]
    return y, write_attn_cache(cache, k, v, pos0, spec)


# ---------------------------------------------------------------------------
# whole layer
# ---------------------------------------------------------------------------

def apply_layer(params: dict, x: jax.Array, spec: LayerSpec, cfg: ModelConfig,
                ctx: DistContext, positions: jax.Array, *,
                causal: bool = True, enc_out: Optional[jax.Array] = None,
                cache_len: Optional[int] = None, cache_dtype=None):
    """Train/prefill.  Returns (x, stats), or (x, stats, cache) when
    ``cache_len`` is given — the single-pass-prefill path (docs/DESIGN.md
    §Serving): the layer's decode cache is built from the same forward pass
    (K/V as computed, ring-laid for window/chunked layers; SSD final state +
    conv tail for mamba; precomputed cross K/V for enc-dec).  Prefill is
    never differentiated, so the cache path skips the remat wrapper."""
    build_cache = cache_len is not None
    if cache_dtype is None:
        cache_dtype = x.dtype

    def layer_fn(x):
        cache: dict = {}
        h = apply_norm(params["norm1"], x, cfg.norm)
        if spec.mixer == "attn":
            with jax.named_scope("attention"):
                if build_cache:
                    h, (k_raw, v_raw) = attn_mixer(
                        params["mixer"], h, cfg, spec, positions, ctx, causal,
                        return_kv=True)
                    cache["attn"] = build_attn_cache(k_raw, v_raw, spec,
                                                     cache_len, cache_dtype)
                else:
                    h = attn_mixer(params["mixer"], h, cfg, spec, positions,
                                   ctx, causal)
        else:
            if build_cache:
                h, state = ssm_mod.apply_ssm(params["mixer"], h, spec.ssm,
                                             return_state=True)
                cache["ssm"] = jax.tree.map(lambda a: a.astype(cache_dtype),
                                            state._asdict())
            else:
                h = ssm_mod.apply_ssm(params["mixer"], h, spec.ssm)
        x = x + h
        if "cross" in params and enc_out is not None:
            h = apply_norm(params["norm_x"], x, cfg.norm)
            q, k, v = _cross_qkv(params["cross"], h, enc_out, cfg)
            o = attention(q, k, v, spec.attn, causal=False)
            x = x + o.reshape(*x.shape[:2], -1) @ params["cross"]["wo"]
            if build_cache:
                cache["cross_k"] = k.astype(cache_dtype)
                cache["cross_v"] = v.astype(cache_dtype)
        stats = zero_stats(cfg)
        if spec.ffn != "none":
            h = apply_norm(params["norm2"], x, cfg.norm)
            if spec.ffn == "dense":
                h = apply_mlp(params["ffn"], h)
            else:
                with jax.named_scope("moe"):
                    h, stats = moe_ffn(params["ffn"], h, cfg.moe, ctx)
            x = x + h
        if build_cache:
            return x, stats, cache
        return x, stats

    if build_cache:
        return layer_fn(x)
    if cfg.remat_policy in ("full", "memfine"):
        layer_fn = jax.checkpoint(layer_fn)
    elif cfg.remat_policy == "selective":
        # keep the all-gathered qkv tensors resident: recompute skips the
        # sequence-parallel gathers (collective term down, memory term up)
        layer_fn = jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.save_only_these_names("qkv"))
    return layer_fn(x)


def _cross_qkv(p: dict, x: jax.Array, enc_out: jax.Array, cfg: ModelConfig):
    B, S, _ = x.shape
    Se = enc_out.shape[1]
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (enc_out @ p["wk"]).reshape(B, Se, KH, hd)
    v = (enc_out @ p["wv"]).reshape(B, Se, KH, hd)
    return q, k, v


def apply_layer_decode(params: dict, x: jax.Array, cache, spec: LayerSpec,
                       cfg: ModelConfig, ctx: DistContext, pos: jax.Array, *,
                       live: Optional[jax.Array] = None, layer=None):
    """Single-token decode.  cache: layer cache pytree.  Returns (x, cache,
    load, reads): this layer's (E,) routed-load histogram, the per-step
    telemetry the expert-aware serving path consumes (docs/DESIGN.md
    §Residency), and the number of experts its MoE read (both zero for
    dense/none FFNs).  ``live`` (B,) marks the rows holding a request and
    ``layer`` says the expert weights are stacked over scanned layers
    (``core.moe.moe_ffn_decode``, docs/DESIGN.md §Decode MoE)."""
    h = apply_norm(params["norm1"], x, cfg.norm)
    if spec.mixer == "attn":
        with jax.named_scope("attention"):
            h, new_attn = attn_mixer_decode(params["mixer"], h, cache["attn"],
                                            pos, cfg, spec, ctx)
        cache = {**cache, "attn": new_attn}
    else:
        h, new_state = ssm_mod.decode_ssm(params["mixer"], h,
                                          ssm_mod.SSMState(**cache["ssm"]),
                                          spec.ssm)
        cache = {**cache, "ssm": new_state._asdict()}
    x = x + h
    if "cross" in params and "cross_k" in cache:
        h = apply_norm(params["norm_x"], x, cfg.norm)
        B = x.shape[0]
        H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
        q = (h @ params["cross"]["wq"]).reshape(B, 1, H, hd)
        Se = cache["cross_k"].shape[1]
        o = decode_attention(q, cache["cross_k"], cache["cross_v"],
                             Se * jnp.ones((B,), jnp.int32), spec.attn)
        x = x + o.reshape(B, 1, -1) @ params["cross"]["wo"]
    load = zero_stats(cfg)["load"]
    reads = jnp.int32(0)
    if spec.ffn != "none":
        h = apply_norm(params["norm2"], x, cfg.norm)
        if spec.ffn == "dense":
            h = apply_mlp(params["ffn"], h)
        else:
            with jax.named_scope("moe"):
                h, st, reads = moe_ffn_decode(params["ffn"], h, cfg.moe, ctx,
                                              live, layer)
            load = st["load"]
        x = x + h
    return x, cache, load, reads


def apply_layer_extend(params: dict, x: jax.Array, cache, spec: LayerSpec,
                       cfg: ModelConfig, ctx: DistContext, pos0, *,
                       return_load: bool = False):
    """C-token cache extension (serving chunked prefill, docs/DESIGN.md
    §Serving).  x: (B, C, d) at positions pos0..pos0+C-1.  Returns
    (x, cache) — the multi-token generalisation of ``apply_layer_decode``,
    with the same optional (E,) load output under ``return_load``."""
    B, C, _ = x.shape
    h = apply_norm(params["norm1"], x, cfg.norm)
    if spec.mixer == "attn":
        with jax.named_scope("attention"):
            h, new_attn = attn_mixer_extend(params["mixer"], h, cache["attn"],
                                            pos0, cfg, spec, ctx)
        cache = {**cache, "attn": new_attn}
    else:
        h, new_state = ssm_mod.apply_ssm(
            params["mixer"], h, spec.ssm, return_state=True,
            initial_state=ssm_mod.SSMState(**cache["ssm"]))
        cache = {**cache,
                 "ssm": jax.tree.map(lambda a, o: a.astype(o.dtype),
                                     new_state._asdict(), cache["ssm"])}
    x = x + h
    if "cross" in params and "cross_k" in cache:
        h = apply_norm(params["norm_x"], x, cfg.norm)
        H, hd = cfg.num_heads, cfg.resolved_head_dim
        q = (h @ params["cross"]["wq"]).reshape(B, C, H, hd)
        Se = cache["cross_k"].shape[1]
        mask = jnp.ones((C, Se), bool)          # cross attention: non-causal
        o = extend_attention(q, cache["cross_k"], cache["cross_v"], mask)
        x = x + o.reshape(B, C, -1) @ params["cross"]["wo"]
    load = None
    if spec.ffn != "none":
        h = apply_norm(params["norm2"], x, cfg.norm)
        if spec.ffn == "dense":
            h = apply_mlp(params["ffn"], h)
        else:
            with jax.named_scope("moe"):
                h, st = moe_ffn(params["ffn"], h, cfg.moe, ctx)
            load = st["load"].astype(jnp.float32)
        x = x + h
    if return_load:
        E = cfg.moe.num_experts if cfg.moe is not None else 1
        if load is None:
            load = jnp.zeros((E,), jnp.float32)
        return x, cache, load
    return x, cache


def init_layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                     seq_len: int, dtype, enc_out: Optional[jax.Array] = None,
                     cross_params: Optional[dict] = None) -> dict:
    """Decode cache for one layer (static shapes; window layers ring-bounded)."""
    cache: dict = {}
    if spec.mixer == "attn":
        Sc = cache_len(spec, seq_len)
        KH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cache["attn"] = {"k": jnp.zeros((batch, Sc, KH, hd), dtype),
                         "v": jnp.zeros((batch, Sc, KH, hd), dtype)}
    else:
        cache["ssm"] = ssm_mod.init_state(batch, cfg.d_model, spec.ssm,
                                          dtype)._asdict()
    if cross_params is not None and enc_out is not None:
        KH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        Se = enc_out.shape[1]
        cache["cross_k"] = (enc_out @ cross_params["wk"]).reshape(batch, Se, KH, hd)
        cache["cross_v"] = (enc_out @ cross_params["wv"]).reshape(batch, Se, KH, hd)
    return cache
