"""Paged-serving parity matrix + paged memory model (docs/DESIGN.md §Paging).

The contract under test: the paged cache path is *bit-identical* to the
monolithic slot map — same greedy tokens for every request across every
cache layout (linear K/V, window ring wrapping at a page boundary, SSM
state + conv tail, hybrid, enc-dec cross attention), with prefix hits,
preemption spill/restore and decode-time CoW in the loop.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import GPU_64G, HardwareProfile
from repro.core import memory_model as mm
from repro.core import moe
from repro.core.moe import DistContext
from repro.models import blocks, transformer
from repro.serving import engine
from repro.serving.paged_cache import PagedCachePool
from repro.serving.paged_scheduler import PagedScheduler
from repro.serving.paging import SCRATCH_PAGE, ZERO_PAGE
from repro.serving.scheduler import (ContinuousBatchingScheduler, Request,
                                     ServeConfig)

CTX = DistContext()


def _model(arch, seed=0):
    cfg = registry()[arch].reduced()
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def _trace(cfg, shapes, seed=0, prefix=None):
    """shapes: list of (prompt_len, gen[, priority]); ``prefix`` prepends a
    shared stem to every prompt (prefix-cache scenarios)."""
    rng = np.random.default_rng(seed)
    out = []
    for i, sh in enumerate(shapes):
        S, g, prio = (sh + (0,))[:3]
        toks = rng.integers(0, cfg.vocab_size, S).astype(np.int32)
        if prefix is not None:
            toks = np.concatenate([prefix, toks])
        out.append(Request(rid=i, tokens=toks, max_new_tokens=g,
                           arrival=0.0, priority=prio))
    return out


def _run_pair(arch, cache_len, shapes, *, page=8, chunk=8, slots=3,
              prefix_stem=0, prefix_cache=False, seed=0):
    """Run the same trace through the monolithic and the paged scheduler;
    return both schedulers (outputs compared by the caller)."""
    cfg, params = _model(arch)
    rng = np.random.default_rng(seed + 100)
    stem = (rng.integers(0, cfg.vocab_size, prefix_stem).astype(np.int32)
            if prefix_stem else None)
    mono = ContinuousBatchingScheduler(
        params, cfg, CTX,
        ServeConfig(max_slots=slots, cache_len=cache_len,
                    prefill_chunk=chunk), key=jax.random.PRNGKey(1))
    mono.run(_trace(cfg, shapes, seed, stem))
    paged = PagedScheduler(
        params, cfg, CTX,
        ServeConfig(max_slots=slots, cache_len=cache_len,
                    prefill_chunk=chunk, page_size=page,
                    prefix_cache=prefix_cache), key=jax.random.PRNGKey(1))
    paged.run(_trace(cfg, shapes, seed, stem))
    return mono, paged


def _assert_parity(mono, paged):
    a = {r.rid: list(r.out) for r in mono.finished}
    b = {r.rid: list(r.out) for r in paged.finished}
    assert a == b, f"paged outputs diverge: {a} vs {b}"
    paged.pool.alloc.audit()
    if paged.trie is not None:
        paged.trie.clear()
    for key in paged.pool.alloc.spaces:
        assert paged.pool.alloc.allocated(key) == 0, (
            f"space {key} leaked after drain")


# ---------------------------------------------------------------------------
# parity matrix: every cache layout, paged == monolithic bit for bit
# ---------------------------------------------------------------------------

MATRIX = [
    # linear full-attention caches
    ("llama3.2-3b", 48, [(16, 6), (24, 4), (8, 5)]),
    # window-64 ring wrapping exactly at a page boundary (64 = 8 pages):
    # prompt 72 wraps during prefill, decode keeps wrapping
    ("mixtral-8x7b", 96, [(72, 10), (16, 6)]),
    # window + full attention mix, both group kinds live at once
    ("gemma3-27b", 96, [(40, 6), (24, 4)]),
    # no token caches at all: pure SSM state + conv tail blocks
    ("mamba2-130m", 48, [(16, 6), (24, 4)]),
    # hybrid mamba/attention: state blocks and K/V pages together
    ("jamba-1.5-large-398b", 48, [(16, 5), (24, 4)]),
]


@pytest.mark.parametrize("arch,cache_len,shapes", MATRIX,
                         ids=[m[0] for m in MATRIX])
def test_paged_decode_matches_monolithic(arch, cache_len, shapes):
    mono, paged = _run_pair(arch, cache_len, shapes)
    _assert_parity(mono, paged)
    assert paged.pool.alloc.hwm_bytes() > 0 or not paged.pool.groups


def test_paged_page_size_not_dividing_cache_len():
    """A trailing partial page (cache_len % page != 0) pads, never leaks
    into the dense gather."""
    mono, paged = _run_pair("llama3.2-3b", 44, [(16, 5), (20, 4)], page=8)
    _assert_parity(mono, paged)


# ---------------------------------------------------------------------------
# prefix cache: hit-path prefill is bit-identical to the cold path
# ---------------------------------------------------------------------------

def test_prefix_hit_bit_identical_to_cold():
    """Requests sharing a 16-token system prompt: the trie skips the shared
    chunks on later admissions, yet every output token matches the
    monolithic scheduler (which always prefills cold)."""
    mono, paged = _run_pair("llama3.2-3b", 48, [(8, 5)] * 4,
                            prefix_stem=16, prefix_cache=True)
    _assert_parity(mono, paged)
    st = paged.trie.stats()
    assert st["tokens_reused"] > 0 and st["hits"] >= 3
    m = paged.metrics(1.0)
    assert m["prefix_hit_rate"] > 0.5


def test_prefix_hit_ring_wrap_cow():
    """Decode-time CoW: a prefix-adopted ring page is re-entered when the
    write cursor wraps (mixtral window 64) — the request forks a private
    copy mid-decode and still matches the monolithic tokens."""
    # rid 0 registers its 32-token prompt; rid 1 shares it and generates
    # past the ring (32 + 40 = 72 > 64), wrapping into adopted pages
    mono, paged = _run_pair("mixtral-8x7b", 96, [(0, 4), (0, 40)],
                            prefix_stem=32, prefix_cache=True, slots=2)
    _assert_parity(mono, paged)
    assert paged.trie.stats()["tokens_reused"] > 0


def test_prefix_adopted_cache_equals_cold_prefill_cache():
    """Unit-level: gather_dense over trie-adopted pages + the node's state
    snapshot reproduces the cold chunked-prefill cache bit for bit at the
    matched boundary."""
    cfg, params = _model("llama3.2-3b")
    toks = np.arange(24, dtype=np.int32) % cfg.vocab_size
    scfg = ServeConfig(max_slots=2, cache_len=32, prefill_chunk=8,
                       page_size=8, prefix_cache=True)
    sched = PagedScheduler(params, cfg, CTX, scfg, key=jax.random.PRNGKey(1))
    sched.run([Request(rid=0, tokens=toks, max_new_tokens=2, arrival=0.0)])
    matched, nodes = sched.trie.lookup(toks)
    assert matched == 24                  # raw lookup: every whole block
                                          # (the scheduler caps it < prompt)
    rp = sched.pool.ops.new_request()
    sched.trie.adopt(rp, nodes)
    got = sched.pool.gather_dense(rp.tables, nodes[-1].snapshot, matched)
    _, cold = engine.prefill_chunked(params, cfg, CTX, toks[None, :matched],
                                     32, 8)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(cold)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sched.pool.release(rp)


# ---------------------------------------------------------------------------
# preemption: spill / restore round-trips bit-exactly
# ---------------------------------------------------------------------------

def _tight_budget(cfg, sched, shapes, chunk):
    """A budget admitting ~2 worst-case requests of the largest shape."""
    per_req = max(sched.pool.ops.worst_case_bytes(S + g) for S, g in shapes)
    base = mm.serving_paged_peak_bytes(
        cfg, page_bytes=0, decode_tokens=4, prefill_tokens=chunk)
    return dataclasses.replace(GPU_64G, hbm_bytes=base + 2.2 * per_req,
                               alpha=1.0)


def test_preemption_spill_restore_bit_exact():
    """Under a 2-request budget a low-priority resident is spilled for
    high-priority arrivals and later restored — its final output matches a
    run that was never preempted, and nothing accepted is lost."""
    cfg, params = _model("mixtral-8x7b")
    shapes = [(16, 12, 0), (16, 4, 1), (16, 4, 1), (16, 4, 1)]
    scfg0 = ServeConfig(max_slots=4, cache_len=32, prefill_chunk=8,
                        page_size=8, preemption=True)
    probe = PagedScheduler(params, cfg, CTX, scfg0, key=jax.random.PRNGKey(1))
    hw = _tight_budget(cfg, probe, [(16, 12), (16, 4)], 8)
    scfg = dataclasses.replace(scfg0, hw=hw)
    paged = PagedScheduler(params, cfg, CTX, scfg, key=jax.random.PRNGKey(1))
    m = paged.run(_trace(cfg, shapes))
    assert m["preemptions"] >= 1 and m["shed"] == 0
    assert m["requests"] == len(shapes)
    assert m["modeled_peak_bytes"] <= m["budget_bytes"]
    mono = ContinuousBatchingScheduler(
        params, cfg, CTX, ServeConfig(max_slots=4, cache_len=32,
                                      prefill_chunk=8),
        key=jax.random.PRNGKey(1))
    mono.run(_trace(cfg, shapes))
    _assert_parity(mono, paged)
    low = next(r for r in paged.finished if r.rid == 0)
    assert low.preemptions >= 1


def test_pool_spill_restore_roundtrip():
    """Pool-level: spill -> restore returns fresh private pages whose dense
    gather is bit-identical to the pre-spill cache."""
    cfg, params = _model("jamba-1.5-large-398b")
    toks = jnp.arange(16, dtype=jnp.int32)[None] % cfg.vocab_size
    _, cache = engine.prefill_chunked(params, cfg, CTX, toks, 32, 8)
    pool = PagedCachePool(params, cfg, CTX, 2, 32, 8)
    rp = pool.ops.new_request()
    pool.install(rp, cache, 16)
    before = pool.gather_dense(rp.tables, pool.state_snapshot(cache), 16)
    saved = pool.spill(rp)
    for key in pool.alloc.spaces:         # spill dropped every reference
        assert pool.alloc.allocated(key) == 0
    rp2 = pool.restore(saved)
    after = pool.gather_dense(rp2.tables, pool.state_snapshot(cache), 16)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pool.release(rp2)
    pool.alloc.audit()


# ---------------------------------------------------------------------------
# enc-dec: cross-attention state blocks (scheduler rejects encoder archs,
# so parity is pinned at the pool level)
# ---------------------------------------------------------------------------

def test_paged_decode_enc_dec_cross_attention():
    cfg, params = _model("whisper-small")
    batch = {"tokens": jnp.arange(16, dtype=jnp.int32)[None] % cfg.vocab_size,
             "frames": jax.random.normal(jax.random.PRNGKey(2),
                                         (1, cfg.encoder_seq, cfg.d_model))}
    _, cache = engine.prefill(params, cfg, CTX, batch, 24)
    enc_out = jnp.zeros((1, cfg.encoder_seq, cfg.d_model))
    pool = PagedCachePool(params, cfg, CTX, 2, 24, 8, enc_out=enc_out)
    rp = pool.ops.new_request()
    pool.install(rp, cache, 16)
    # reference = the monolithic slot map: vmapped decode_step over a
    # 2-slot pool (slot 1 empty), exactly what the scheduler compiles
    empty = transformer.init_cache(params, cfg, 1, 24, jnp.float32,
                                   enc_out=enc_out)
    refc = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b[None]]),
                        cache, empty)
    step_fn = jax.jit(jax.vmap(
        lambda p, c, t: transformer.decode_step(p, cfg, CTX, c, t),
        in_axes=(None, 0, 0)))
    tok = 7
    for step in range(3):
        toks = np.asarray([[[tok]], [[0]]], np.int32)
        ref_logits, refc = step_fn(params, refc, jnp.asarray(toks))
        pool.prepare_decode_write(rp, 16 + step)
        got, _ = pool.decode_wave(params, [rp, None],
                                  np.asarray([16 + step, 0], np.int32), toks)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(ref_logits)[0])
        tok = int(np.argmax(np.asarray(ref_logits)[0, 0, -1]))
    pool.release(rp)
    pool.alloc.audit()


def _rows_decode(params, x, cfg, ctx, live=None, layer=None):
    """The decode MoE as the row path runs it: every expert, every slot."""
    if layer is not None:
        params = {**params,
                  **{k: params[k][layer] for k in moe.EXPERT_WEIGHTS}}
    y, stats = moe._moe_ffn_rows(params, x, cfg, ctx)
    return y, stats, jnp.int32(cfg.num_experts)


def test_paged_wave_with_empty_slot_matches_row_path(monkeypatch):
    """Two requests in three slots, so every wave has an empty slot: the
    live slots' logits match the row path's (docs/DESIGN.md §Decode MoE),
    the empty slot adds no expert, and the scheduler's read share is the
    experts read over waves x MoE layers x E."""
    cfg, params = _model("mixtral-8x7b")
    E, n_moe = cfg.moe.num_experts, transformer.num_moe_layers(cfg)

    def run():
        sched = PagedScheduler(
            params, cfg, CTX,
            ServeConfig(max_slots=3, cache_len=32, prefill_chunk=8,
                        page_size=8), key=jax.random.PRNGKey(1))
        seen, wave = [], sched.pool.decode_wave

        def recorded(p, slot_rps, pos, toks):
            logits, reads = wave(p, slot_rps, pos, toks)
            live = [i for i, rp in enumerate(slot_rps) if rp is not None]
            assert len(live) < len(slot_rps)
            seen.append((np.asarray(logits)[live], np.asarray(reads)))
            return logits, reads

        sched.pool.decode_wave = recorded
        sched.run(_trace(cfg, [(8, 6), (12, 4)], seed=5))
        return sched, seen

    new, seen_new = run()
    monkeypatch.setattr(blocks, "moe_ffn_decode", _rows_decode)
    old, seen_old = run()
    _assert_parity(old, new)
    assert len(seen_new) == len(seen_old) == new.decode_waves > 0
    for (a, reads), (b, all_read) in zip(seen_new, seen_old):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        assert reads.shape == (n_moe,) and (reads <= E).all()
        np.testing.assert_array_equal(all_read, E)
    assert new.decode_experts_total == new.decode_waves * n_moe * E
    assert new.decode_experts_read == sum(r.sum() for _, r in seen_new)
    assert 0 < new.decode_expert_read_share <= 1
    assert old.decode_expert_read_share == 1.0
    m = new.metrics(1.0)
    assert m["decode_expert_read_share"] == new.decode_expert_read_share


# ---------------------------------------------------------------------------
# gather/scatter paged-token primitives (blocks.py)
# ---------------------------------------------------------------------------

def test_gather_scatter_paged_tokens_roundtrip():
    page, nb, KH, hd, Sc = 4, 3, 2, 5, 10          # trailing partial page
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(8, page, KH, hd)).astype(np.float32))
    pool = pool.at[ZERO_PAGE].set(0.0)
    table = jnp.asarray([5, 3, 7], jnp.int32)
    dense = blocks.gather_paged_tokens(pool, table, 0, Sc)
    assert dense.shape == (Sc, KH, hd)
    want = np.concatenate([np.asarray(pool)[5], np.asarray(pool)[3],
                           np.asarray(pool)[7]])[:Sc]
    np.testing.assert_array_equal(np.asarray(dense), want)
    # a never-filled block gathers the zero page: dense shows zeros
    holey = blocks.gather_paged_tokens(
        pool, jnp.asarray([5, ZERO_PAGE, 7], jnp.int32), 0, Sc)
    np.testing.assert_array_equal(np.asarray(holey)[page:2 * page], 0.0)
    # scatter writes each block's rows to its page; the padded tail of the
    # last page and scratch-targeted blocks never corrupt live pages
    newd = jnp.asarray(rng.normal(size=(Sc, KH, hd)).astype(np.float32))
    out = blocks.scatter_paged_tokens(
        pool, jnp.asarray([5, SCRATCH_PAGE, 7], jnp.int32), newd, 0, page)
    np.testing.assert_array_equal(np.asarray(out)[5], np.asarray(newd)[:page])
    np.testing.assert_array_equal(np.asarray(out)[7][:Sc - 2 * page],
                                  np.asarray(newd)[2 * page:])
    np.testing.assert_array_equal(np.asarray(out)[3], np.asarray(pool)[3])


def test_gather_paged_tokens_batched_tables():
    """The decode wave gathers (n_slots, nb) tables in one shot."""
    page, Sc = 4, 8
    pool = jnp.arange(6 * page, dtype=jnp.float32).reshape(6, page, 1, 1)
    pool = pool.at[ZERO_PAGE].set(0.0)    # invariant: zero page stays zero
    tables = jnp.asarray([[2, 3], [4, ZERO_PAGE]], jnp.int32)
    dense = blocks.gather_paged_tokens(pool, tables, 0, Sc)
    assert dense.shape == (2, Sc, 1, 1)
    np.testing.assert_array_equal(np.asarray(dense)[0, :, 0, 0],
                                  np.arange(8, 16, dtype=np.float32))
    np.testing.assert_array_equal(np.asarray(dense)[1, 4:, 0, 0], 0.0)


# ---------------------------------------------------------------------------
# memory model: decode-act regression + paged variant
# ---------------------------------------------------------------------------

def test_serving_peak_decode_act_clamped_to_residents():
    """Regression: the decode-wave activation term is sized by the tokens a
    wave can actually carry — min(decode_tokens, requests) — so one
    resident request costs one token's activations even on a wide slot
    map, not ``max_slots`` tokens' worth."""
    cfg = registry()["mixtral-8x7b"].reduced()
    kw = dict(cache_len=64, prefill_tokens=0)
    one_wide = mm.serving_peak_bytes(cfg, requests=1, decode_tokens=64, **kw)
    one_narrow = mm.serving_peak_bytes(cfg, requests=1, decode_tokens=1, **kw)
    assert one_wide == one_narrow
    # with enough residents the wave width matters again
    assert (mm.serving_peak_bytes(cfg, requests=64, decode_tokens=64, **kw)
            > mm.serving_peak_bytes(cfg, requests=64, decode_tokens=1, **kw))


def test_serving_paged_peak_and_fits():
    cfg = registry()["mixtral-8x7b"].reduced()
    kw = dict(decode_tokens=4, prefill_tokens=16)
    b0 = mm.serving_paged_peak_bytes(cfg, page_bytes=0, **kw)
    b1 = mm.serving_paged_peak_bytes(cfg, page_bytes=1e6, **kw)
    assert b1 == b0 + 1e6                 # pages are charged verbatim
    hw = HardwareProfile("t", hbm_bytes=b0 + 5e5, peak_flops=1, hbm_bw=1,
                         ici_bw=1, alpha=1.0)
    assert mm.serving_paged_fits(cfg, hw, page_bytes=4e5, **kw)
    assert not mm.serving_paged_fits(cfg, hw, page_bytes=6e5, **kw)


def test_paged_model_beats_monolithic_reservation():
    """The headline: short requests on a long cache_len cost pages for what
    they fill, far below the monolithic full-length reservation."""
    cfg, params = _model("llama3.2-3b")
    scfg = ServeConfig(max_slots=4, cache_len=256, prefill_chunk=8,
                       page_size=8)
    sched = PagedScheduler(params, cfg, CTX, scfg, key=jax.random.PRNGKey(1))
    sched.run(_trace(cfg, [(16, 4)] * 4))
    mono_cache = 4 * mm.decode_cache_bytes(cfg, 256, dtype_bytes=2)
    assert sched.pool.alloc.hwm_bytes() < 0.25 * mono_cache


def test_paged_scheduler_requires_page_size():
    cfg, params = _model("llama3.2-3b")
    with pytest.raises(ValueError, match="page_size"):
        PagedScheduler(params, cfg, CTX, ServeConfig())


# ---------------------------------------------------------------------------
# tracing (docs/DESIGN.md §Tracing): host spans, step names, device scopes
# ---------------------------------------------------------------------------

def _recorded_events(trace_dir):
    """(name, start, end, stats) of every host event of the newest trace."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def test_paged_scheduler_records_serve_spans(tmp_path):
    """A profiled run records the scheduler's phases as ``serve.*`` spans
    (fetches nested in their step, one request's spans sharing its rid),
    its steps as modules named ``jit_serve_*``, and the admission stamp on
    the caller's clock."""
    cfg, params = _model("mixtral-8x7b")
    rng = np.random.default_rng(3)
    stem = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    reqs = _trace(cfg, [(8, 4)] * 3, seed=3, prefix=stem)
    sched = PagedScheduler(
        params, cfg, CTX,
        ServeConfig(max_slots=2, cache_len=48, prefill_chunk=8, page_size=8,
                    prefix_cache=True), key=jax.random.PRNGKey(1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in reqs:
            sched.submit(r, 0.0)
        t = 0.0
        while len(sched.finished) < len(reqs):
            t += 1.0
            sched.step(t)
    finally:
        jax.profiler.stop_trace()
    evs = _recorded_events(str(tmp_path))
    spans = [e for e in evs if e[0].startswith("serve.")]
    by = {}
    for e in spans:
        by.setdefault(e[0], []).append(e)
    steps = by["serve.step"]
    assert [st["step"] for _, _, _, st in steps] == list(range(len(steps)))
    assert all(any(s <= a and b <= e for _, s, e, _ in steps)
               for _, a, b, _ in by["serve.fetch"])
    assert sum(st["admitted"] for *_, st in by["serve.admit"]) == len(reqs)
    admitted = {st["rid"]: st["matched"] for *_, st in
                by["serve.admit_request"]}
    assert set(admitted) == {r.rid for r in reqs}
    assert max(admitted.values()) >= 16        # later requests hit the stem
    for *_, st in by["serve.prefill_chunk"]:
        assert st["rid"] in admitted
        assert 0 <= st["start"] < st["stop"] <= st["length"] == 24
    assert {st["rid"] for *_, st in by["serve.install"]} == set(admitted)
    for name in ("serve.decode_prepare", "serve.page_tables",
                 "serve.decode_dispatch", "serve.sample"):
        assert by[name]
    # the pool's page tables are their own span, beside the scheduler's
    # preparation and not inside it
    assert not any(s <= a and b <= e for _, s, e, _ in
                   by["serve.decode_prepare"]
                   for _, a, b, _ in by["serve.page_tables"])
    modules = {st["hlo_module"] for *_, st in evs if "hlo_module" in st}
    assert {"jit_serve_decode_wave", "jit_serve_prefill_chunk",
            "jit_serve_extend_chunk", "jit_serve_install",
            "jit_serve_gather"} <= modules
    assert "jit_fn" not in modules
    for r in reqs:
        assert r.t_admit is not None and r.t_admit <= r.t_first


def test_pool_host_reads_are_fetch_spans(tmp_path):
    """The pool's blocking device-to-host reads (a state snapshot, a spill)
    each record one ``serve.fetch`` span."""
    cfg, params = _model("jamba-1.5-large-398b")
    toks = jnp.arange(16, dtype=jnp.int32)[None] % cfg.vocab_size
    _, cache = engine.prefill_chunked(params, cfg, CTX, toks, 32, 8)
    pool = PagedCachePool(params, cfg, CTX, 2, 32, 8)
    rp = pool.ops.new_request()
    pool.install(rp, cache, 16)
    jax.block_until_ready(pool.pools)
    jax.profiler.start_trace(str(tmp_path))
    try:
        state = pool.state_snapshot(cache)
        pool.spill(rp)
    finally:
        jax.profiler.stop_trace()
    assert any(v is not None for v in state)
    names = [e[0] for e in _recorded_events(str(tmp_path))
             if e[0].startswith("serve.")]
    assert names == ["serve.fetch", "serve.fetch"]


def test_serving_steps_are_named_and_scoped():
    """Every serving jit compiles to a module named after its function; the
    paged decode wave's HLO carries the device scopes."""
    cfg, params = _model("mixtral-8x7b")
    scfg = ServeConfig(max_slots=2, cache_len=32, prefill_chunk=8,
                       page_size=8)
    sched = PagedScheduler(params, cfg, CTX, scfg)
    pool = sched.pool
    n = scfg.max_slots
    inputs = pool._wave_inputs([None] * n, np.zeros((n,), np.int32),
                               np.zeros((n, 1, 1), np.int32))
    lowered = pool._decode.lower(params, pool.pools, *inputs)
    hlo = lowered.compile().as_text()
    assert "jit_serve_decode_wave" in lowered.as_text()
    for scope in ("attention", "moe", "router", "experts", "paged_gather",
                  "paged_scatter", "lm_head"):
        # a scope under vmap reads ``vmap(<scope>)`` in an op_name
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', hlo), scope
    mono = ContinuousBatchingScheduler(params, cfg, CTX, scfg)
    toks = jnp.zeros((n, 1, 1), jnp.int32)
    assert "jit_serve_decode_slots" in mono._decode.lower(
        params, mono.cache, toks).as_text()
    names = {f.__name__ for f in (
        engine.get_decode_step(cfg, CTX), engine.get_extend_step(cfg, CTX),
        engine.get_prefill_fn(cfg, CTX, 32),
        engine.get_decode_step_masked(cfg, CTX),
        engine.get_extend_step_loads(cfg, CTX),
        engine.get_prefill_fn_loads(cfg, CTX, 32),
        engine.get_router_probe(cfg, CTX),
        pool._decode, pool._build_decode_loads(), pool._install,
        pool._gather, pool._restore, *pool._copy.values())}
    assert names == {
        "serve_decode_step", "serve_extend_chunk", "serve_prefill_chunk",
        "serve_decode_masked", "serve_extend_chunk_loads",
        "serve_prefill_chunk_loads", "serve_router_probe",
        "serve_decode_wave", "serve_decode_wave_loads", "serve_install",
        "serve_gather", "serve_restore", "serve_copy_page"}
