"""Serving engine: single-pass batched prefill + compiled decode/extend steps.

``serve_step`` is what the decode_32k / long_500k dry-run shapes lower: ONE
new token against a cache of ``seq_len`` entries.  Window/chunked-attention
layers keep ring caches bounded by their window (how long_500k decode stays
affordable for mixtral/gemma3/llama4); SSM layers carry constant-size state.

Prefill is a SINGLE ``transformer.forward`` pass that writes every layer's
decode cache as it goes (``return_cache=True``, docs/DESIGN.md §Serving) —
replacing the old token-by-token replay loop, which dispatched O(S) compiled
decode steps per prompt.  The replay survives as ``prefill_replay``, the
reference oracle the cache-layout parity tests compare against.

Compiled steps are hoisted into a per-(cfg, ctx) cache: the old code wrapped
``jax.jit(functools.partial(...))`` inside every ``prefill``/``generate``
call, so each invocation re-traced the decode step from scratch.  On
non-CPU backends the decode step donates its cache argument, updating K/V
rings in place.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.chunking import chunk_spans
from repro.core.moe import DistContext
from repro.models import transformer


def init_serve_cache(params: dict, cfg: ModelConfig, batch: int, seq_len: int,
                     dtype=jnp.float32, enc_out: Optional[jax.Array] = None):
    return transformer.init_cache(params, cfg, batch, seq_len, dtype,
                                  enc_out=enc_out)


def build_placements(cfg: ModelConfig, ctx: DistContext, num_peers: int, *,
                     loads=None, replicas: int = 0):
    """Static expert placement chosen at engine build (docs/DESIGN.md
    §Placement).

    Serving never replans — weights are loaded once, so the placement is
    resolved here from an offline/warmup load profile (``loads``: a
    ``(L_moe, E)`` matrix, e.g. a training run's telemetry EMA; None means
    identity) and baked into the ctx every compiled step is traced under.
    Returns ``(ctx with per-layer placements, replica_weight_bytes)`` — the
    second element is what ``ServeConfig.replica_weight_bytes`` should carry
    so admission control prices the replica slots
    (core/memory_model.py::serving_peak_bytes).
    """
    import dataclasses

    from repro.core import memory_model as mm
    from repro.core import placement as plc

    n_moe = transformer.num_moe_layers(cfg)
    if cfg.moe is None or n_moe == 0 or num_peers <= 1 \
            or cfg.moe.num_experts % num_peers:
        return ctx, 0.0
    placements = plc.choose_placements(
        loads, n_moe, num_peers, num_experts=cfg.moe.num_experts,
        replicas=replicas, hysteresis=0.0)
    extra_slots = max(p.replica_slots for p in placements)
    replica_bytes = mm.replica_weight_bytes(
        cfg, extra_slots, mm.Parallelism(e=num_peers))
    if all(p.is_identity for p in placements):
        return ctx, 0.0
    return dataclasses.replace(ctx, placements=placements), replica_bytes


def make_serve_step(cfg: ModelConfig, ctx: DistContext):
    """Returns step(params, cache, tokens (B,1)) -> (logits, new_cache)."""

    def serve_step(params, cache, tokens):
        return transformer.decode_step(params, cfg, ctx, cache, tokens)

    return serve_step


# ---------------------------------------------------------------------------
# compiled-step cache: one trace per (cfg, ctx), not one per call
# ---------------------------------------------------------------------------

_STEP_CACHE: dict = {}


def step_cache_info() -> dict:
    """Snapshot of the compiled-step cache keys (tests/observability)."""
    return {"entries": len(_STEP_CACHE)}


def clear_step_cache() -> None:
    _STEP_CACHE.clear()


def _cached(key, build):
    """Memoise ``build()`` under ``key``; unhashable keys (exotic mesh
    objects in a ctx) simply skip the cache rather than fail."""
    try:
        fn = _STEP_CACHE.get(key)
    except TypeError:
        return build()
    if fn is None:
        fn = build()
        _STEP_CACHE[key] = fn
    return fn


def _jit(fn, donate_cache_arg: Optional[int] = None):
    if donate_cache_arg is not None and jax.default_backend() != "cpu":
        # steady-state decode rewrites the whole cache every step: donating
        # it lets XLA update the K/V rings in place instead of allocating a
        # second full-size cache per step
        return jax.jit(fn, donate_argnums=(donate_cache_arg,))
    return jax.jit(fn)


def get_decode_step(cfg: ModelConfig, ctx: DistContext):
    """The compiled single-token step(params, cache, tokens (B,1))."""
    def build():
        def serve_decode_step(params, cache, tokens):
            return transformer.decode_step(params, cfg, ctx, cache, tokens)
        return _jit(serve_decode_step, donate_cache_arg=1)
    return _cached(("decode", cfg, ctx), build)


def get_extend_step(cfg: ModelConfig, ctx: DistContext):
    """The compiled chunk step(params, cache, tokens (B,C)) — chunked
    prefill continuation."""
    def build():
        def serve_extend_chunk(params, cache, tokens):
            return transformer.extend_step(params, cfg, ctx, cache, tokens)
        return _jit(serve_extend_chunk, donate_cache_arg=1)
    return _cached(("extend", cfg, ctx), build)


def get_prefill_fn(cfg: ModelConfig, ctx: DistContext, cache_len: int,
                   dtype=jnp.float32):
    """The compiled single-pass prefill(params, batch) -> (logits, cache)."""
    dtype = jnp.dtype(dtype)

    def build():
        def serve_prefill_chunk(params, batch):
            logits, _stats, cache = transformer.forward(
                params, cfg, ctx, batch, return_cache=True,
                cache_len=cache_len, cache_dtype=dtype)
            return logits[:, -1:], cache
        return _jit(serve_prefill_chunk)
    return _cached(("prefill", cfg, ctx, cache_len, dtype.name), build)


# ---------------------------------------------------------------------------
# expert-aware steps (docs/DESIGN.md §Residency)
#
# The loads/masked variants are deliberately NOT cache-donating: the
# residency demand loop may discard a wave that activated an offloaded
# expert and re-run it from the SAME pre-wave cache after restoring the
# missing weights, so the input cache must survive the call.
# ---------------------------------------------------------------------------

def get_decode_step_masked(cfg: ModelConfig, ctx: DistContext):
    """Compiled subset-wave decode over the slot-stacked cache:
    step(params, cache, tokens (S,1), mask (S,) bool)
    -> (logits (S,1,V), cache', load (S, L_moe, E)).

    Every slot runs the vmapped per-slot step (slot math is independent, so
    member outputs are bitwise those of the full-batch step regardless of
    which other slots share the wave); the mask then tree-selects which
    slots' cache entries advance — non-members keep their old cache bits
    exactly, which is what makes grouped waves equivalent to FIFO waves.
    Non-member load rows are zeroed so layer unions only see members."""
    def build():
        def serve_decode_masked(params, cache, tokens, mask):
            logits, new_cache, load = jax.vmap(
                lambda c, t: transformer.decode_step(
                    params, cfg, ctx, c, t, return_load=True),
                in_axes=(0, 0))(cache, tokens)

            def keep(n, o):
                m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
                return jnp.where(m, n, o)
            out_cache = jax.tree_util.tree_map(keep, new_cache, cache)
            load = load * mask.astype(load.dtype)[:, None, None]
            return logits, out_cache, load
        return _jit(serve_decode_masked)
    return _cached(("decode_masked", cfg, ctx), build)


def get_extend_step_loads(cfg: ModelConfig, ctx: DistContext):
    """Compiled chunk step that also reports the (L_moe, E) routed load —
    step(params, cache, tokens (B,C)) -> (logits (B,C,V), cache, load)."""
    def build():
        def serve_extend_chunk_loads(params, cache, tokens):
            return transformer.extend_step(params, cfg, ctx, cache, tokens,
                                           return_load=True)
        return _jit(serve_extend_chunk_loads)
    return _cached(("extend_loads", cfg, ctx), build)


def get_prefill_fn_loads(cfg: ModelConfig, ctx: DistContext, cache_len: int,
                         dtype=jnp.float32):
    """Single-pass prefill that also reports the (L_moe, E) routed load."""
    dtype = jnp.dtype(dtype)

    def build():
        def serve_prefill_chunk_loads(params, batch):
            logits, stats, cache = transformer.forward(
                params, cfg, ctx, batch, return_cache=True,
                cache_len=cache_len, cache_dtype=dtype)
            if cfg.moe is not None:
                load = stats["load_per_layer"]
            else:
                load = jnp.zeros((0, 1), jnp.float32)
            return logits[:, -1:], cache, load
        return _jit(serve_prefill_chunk_loads)
    return _cached(("prefill_loads", cfg, ctx, cache_len, dtype.name), build)


def get_router_probe(cfg: ModelConfig, ctx: DistContext):
    """Compiled router-only probe: probe(params, tokens (N,)) -> (N, L_moe, E)
    activation counts.

    Runs every MoE layer's router directly on the token EMBEDDINGS — no
    attention, no FFN — as a cheap approximation of where those tokens
    would route (the §Residency prefetch hint for requests with no
    telemetry yet).  Approximate by construction: real routing sees the
    residual stream, the probe sees layer-0 input; it is a prediction
    seed, never a correctness input (demand restore covers its misses).
    """
    from repro.core.router import route
    from repro.serving.residency import moe_layer_refs

    refs = moe_layer_refs(cfg)

    def build():
        def serve_router_probe(params, tokens):
            x = jnp.take(params["embed"], tokens, axis=0)
            x = x.astype(params["embed"].dtype)
            E = cfg.moe.num_experts
            per_layer = []
            for head, i, p in refs:
                router = params[head][i]["ffn"]["router"]
                if p is not None:
                    router = jax.tree_util.tree_map(lambda a: a[p], router)
                r = route(router, x, cfg.moe)
                per_layer.append(
                    jax.nn.one_hot(r.expert_idx, E, dtype=jnp.float32)
                    .sum(axis=1))                              # (N, E)
            if not per_layer:
                return jnp.zeros((tokens.shape[0], 0, 1), jnp.float32)
            return jnp.stack(per_layer, axis=1)                # (N, L, E)
        return _jit(serve_router_probe)
    return _cached(("router_probe", cfg, ctx), build)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill(params: dict, cfg: ModelConfig, ctx: DistContext, batch: dict,
            cache_len: int, dtype=jnp.float32):
    """Single-pass batched prefill: ONE forward pass writes K/V rings, SSM
    state and cross K/V for every layer (docs/DESIGN.md §Serving).

    Returns (next_token_logits (B, 1, V), cache) — the same contract as the
    replay it replaces.  Cache contents are bit-identical to the replay's
    given the same layer inputs (the layout math is identical; deep layers
    agree to float tolerance because replay's decode-attention and
    forward's blocked attention round the residual stream differently —
    tests/test_serving.py pins both properties).
    """
    return get_prefill_fn(cfg, ctx, cache_len, dtype)(params, batch)


def prefill_replay(params: dict, cfg: ModelConfig, ctx: DistContext,
                   batch: dict, cache_len: int, dtype=jnp.float32):
    """Token-by-token replay prefill — O(S) compiled-step dispatches.  Kept
    as the reference oracle for cache-layout parity tests; production
    callers use ``prefill``."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    enc_out = None
    if cfg.encoder_layers:
        enc_out = transformer.encode(params, cfg, batch["frames"], ctx)
    cache = init_serve_cache(params, cfg, B, cache_len, dtype, enc_out=enc_out)
    step = get_decode_step(cfg, ctx)
    logits = None
    for i in range(S):
        logits, cache = step(params, cache, tokens[:, i:i + 1])
    return logits, cache


def prefill_chunk(params: dict, cfg: ModelConfig, ctx: DistContext,
                  cache, seg: jax.Array, cache_len: int, dtype=jnp.float32,
                  *, return_load: bool = False):
    """One chunked-prefill span: the first (``cache is None``) runs the
    single-pass prefill, later spans the compiled extend step.  The single
    dispatch point shared by ``prefill_chunked`` and the scheduler's
    interleave.  Returns (next_token_logits (B, 1, V), cache), plus the
    span's (L_moe, E) routed load when ``return_load`` (the expert-aware
    scheduler's telemetry feed; these variants do not donate the cache, so
    the span can re-run after a residency demand restore)."""
    if return_load:
        if cache is None:
            return get_prefill_fn_loads(cfg, ctx, cache_len, dtype)(
                params, {"tokens": seg})
        full, cache, load = get_extend_step_loads(cfg, ctx)(params, cache, seg)
        return full[:, -1:], cache, load
    if cache is None:
        return prefill(params, cfg, ctx, {"tokens": seg}, cache_len, dtype)
    full, cache = get_extend_step(cfg, ctx)(params, cache, seg)
    return full[:, -1:], cache


def prefill_chunked(params: dict, cfg: ModelConfig, ctx: DistContext,
                    tokens: jax.Array, cache_len: int, chunk: int,
                    dtype=jnp.float32):
    """Prefill a (B, S) prompt in <= ``chunk``-token pieces: the first span
    through the single-pass prefill, the rest through compiled extend
    steps.  What the scheduler interleaves between decode waves; also
    usable standalone to bound prefill activation memory for long prompts.
    Returns (next_token_logits (B, 1, V), cache)."""
    S = tokens.shape[1]
    if S > cache_len:
        # the extend path cannot check this itself: chunk write positions
        # are traced, and dynamic_update_slice would silently clamp a
        # linear-cache overflow instead of raising
        raise ValueError(f"prompt length {S} exceeds cache_len {cache_len}")
    logits = cache = None
    for start, stop in chunk_spans(S, chunk):
        logits, cache = prefill_chunk(params, cfg, ctx, cache,
                                      tokens[:, start:stop], cache_len, dtype)
    return logits, cache


# ---------------------------------------------------------------------------
# generation
# ---------------------------------------------------------------------------

def generate(params: dict, cfg: ModelConfig, ctx: DistContext, batch: dict,
             steps: int, cache_len: int, temperature: float = 0.0,
             key: Optional[jax.Array] = None):
    """Greedy/temperature batched generation (example + test driver)."""
    logits, cache = prefill(params, cfg, ctx, batch, cache_len)
    step = get_decode_step(cfg, ctx)
    if temperature > 0 and key is None:
        key = jax.random.PRNGKey(0)   # seeded default; split(None) crashed
    out = []
    for i in range(steps):
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits[:, -1] / temperature)
        else:
            nxt = jnp.argmax(logits[:, -1], axis=-1)
        out.append(nxt)
        logits, cache = step(params, cache, nxt[:, None].astype(jnp.int32))
    return jnp.stack(out, axis=1)
