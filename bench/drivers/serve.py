"""Serving driver: drives the program's paged continuous-batching scheduler
(``repro.serving.paged_scheduler.PagedScheduler``: ``submit`` / ``step``)
with an open-loop schedule from ``bench/traffic.py``, stamps every output
token on the host clock when the step that produced it returns (the host
fetch of the logits inside ``step`` is the device sync), and then checks
what the timed path served against the plain float32 reference.

Set-up: weights from the seed (one jitted call, bfloat16), the scheduler
and its compiled steps, then the mix's warm-up requests, which compile
every shape the window uses (first prefill chunk, extend chunk, decode wave,
page install, and with shared prefixes the prefix-hit gather) and warm the
prefix trie.  The lead-in's requests then fill the server, and the window
opens.  After it closes the loop keeps stepping, without new arrivals,
until every request due in the window has its first token, for at most the
mix's ``drain_s``.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from dataclasses import dataclass, field

import numpy as np

from bench import traffic as traffic_mod
from bench import weights as weights_mod
from bench.models import mixtral

TRACE_SECONDS = 10.0          # length of the traced part of a --trace 1 run
#: the number ``correct`` compares (its limit and tolerance are in
#: ``bench/limits/<cell>.json``)
SHARE = "tokens_off_best_share"
IDLE_SLEEP_S = 0.002


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``: the
    program's own architecture entry with every size taken from the file."""
    from repro.configs import get_config
    base = get_config(c["bench"]["program_arch"])
    if base.norm != "rmsnorm" or base.tie_embeddings != c[
            "tie_word_embeddings"]:
        raise ValueError(f"{base.name}: norm or embedding tie differs from "
                         "the configuration file")
    (spec,) = base.pattern
    attn = dataclasses.replace(spec.attn, window=c.get("sliding_window") or 0,
                               kind="window" if c.get("sliding_window")
                               else "full")
    moe = dataclasses.replace(base.moe, num_experts=c["num_local_experts"],
                              top_k=c["num_experts_per_tok"],
                              d_ff_expert=c["intermediate_size"])
    return dataclasses.replace(
        base, num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or 0, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        pattern=(dataclasses.replace(spec, attn=attn),), moe=moe,
        dtype=c["torch_dtype"])


def reference_weights(params: dict) -> mixtral.Weights:
    """The reference's view of the benchmark-made weights (the program's
    tree layout: scanned periods stacked on a leading axis, then the
    unrolled remainder)."""
    layers = []

    def add(p, at):
        pick = (lambda a: a[at]) if at is not None else (lambda a: a)
        layers.append(mixtral.Layer(
            norm1=pick(p["norm1"]["scale"]), wq=pick(p["mixer"]["wq"]),
            wk=pick(p["mixer"]["wk"]), wv=pick(p["mixer"]["wv"]),
            wo=pick(p["mixer"]["wo"]), norm2=pick(p["norm2"]["scale"]),
            router=pick(p["ffn"]["router"]["w"]), w1=p["ffn"]["w1"],
            w3=p["ffn"]["w3"], w2=p["ffn"]["w2"],
            at=() if at is None else (at,)))

    if params["periods"] is not None:
        (stack,) = params["periods"]
        for i in range(stack["norm1"]["scale"].shape[0]):
            add(stack, i)
    for p in params["rem"]:
        add(p, None)
    return mixtral.Weights(embed=params["embed"], head=params["head"],
                           final_norm=params["final_norm"]["scale"],
                           layers=layers)


@dataclass
class ServeRun:
    """What one run recorded; the metric readers read it."""
    kind: str = "serve"
    config: dict = field(default_factory=dict)
    device_kind: str = ""
    chips: int = 1
    seconds: float = 0.0
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)             # absolute host-clock times
    drain_end: float = 0.0
    due: dict = field(default_factory=dict)        # rid -> absolute due
    submitted: dict = field(default_factory=dict)  # rid -> absolute submit
    stamps: dict = field(default_factory=dict)     # rid -> [token times]
    prompt_len: dict = field(default_factory=dict)
    adopted: dict = field(default_factory=dict)    # rid -> prefix tokens
    in_window: set = field(default_factory=set)    # rids due in the window
    steps: list = field(default_factory=list)      # (t0, t1, chunk, decoded)
    compiles_in_window: int = 0
    faults: int = 0
    requeues: int = 0
    memory_peak_bytes: int = 0
    trace: object = None                   # bench.trace.Summary
    attempted: int = 0
    failed: int = 0
    checks: dict = field(default_factory=dict)     # name -> (value, limit)
    reference_s: float = 0.0               # the comparison's own time
    correct: bool = False


class _CompileCounter:
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event in self.EVENTS:
            self.count += 1


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Server:
    """The program under test, built once from the configuration and the
    seed: weights, the paged scheduler and its compiled steps."""

    def __init__(self, c: dict, seed: int):
        import jax
        from repro.core.moe import DistContext
        from repro.models import transformer
        from repro.serving.paged_scheduler import PagedScheduler
        from repro.serving.scheduler import ServeConfig
        self.config = c
        self.sv = c["bench"]["serving"]
        cfg = program_config(c)
        shapes = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                                jax.random.PRNGKey(0))
        self.params = weights_mod.make(shapes, seed,
                                       float(c["bench"]["embed_std"]))
        sv = self.sv
        self.sched = PagedScheduler(self.params, cfg, DistContext(),
                                    ServeConfig(
            max_slots=sv["max_slots"], cache_len=sv["cache_len"],
            prefill_chunk=sv["prefill_chunk"], page_size=sv["page_size"],
            prefix_cache=sv["prefix_cache"], temperature=0.0),
            token_pages=sv.get("token_pages"))
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def request(self, r):
        from repro.serving.scheduler import Request
        return Request(rid=r.rid, tokens=r.tokens,
                       max_new_tokens=r.max_new_tokens, arrival=self.now())

    def warm(self, reqs: list) -> None:
        """Serve ``reqs`` one at a time to completion."""
        from repro.serving.scheduler import FINISHED
        for r in reqs:
            req = self.request(r)
            self.sched.submit(req, self.now())
            while req.state != FINISHED:
                self.sched.step(self.now())

    def serve(self, gen, rec: ServeRun, lead: float, drain: float,
              trace_dir=None, counter=None) -> dict:
        """Open-loop: submit each request when due, step, stamp tokens;
        after the window, step on until every request due in it has its
        first token or ``drain`` seconds have passed.  Returns the program's
        request objects by rid."""
        import jax
        from repro.serving.scheduler import FINISHED
        sched, chunk_len = self.sched, self.sv["prefill_chunk"]
        seconds = rec.seconds
        w0 = time.perf_counter() + lead
        w1 = w0 + seconds
        rec.window = (w0, w1)
        pending = sorted(gen.requests, key=lambda r: r.due)
        for r in pending:
            rec.due[r.rid] = w0 + r.due
            rec.prompt_len[r.rid] = len(r.tokens)
            rec.stamps[r.rid] = []
            if r.due >= 0:
                rec.in_window.add(r.rid)
        reqs, inflight, seen_out, seen_chunks = {}, {}, {}, {}
        # the trace covers the window's last TRACE_SECONDS and is written
        # out after the loop: stopping the profiler blocks for seconds
        trace_at = (w1 - min(TRACE_SECONDS, seconds) if trace_dir else None)
        window_ann = None
        faults0, requeues0 = sched.faults, sched.requeued
        i = 0
        opened = False
        while True:
            now = time.perf_counter()
            if not opened and now >= w0:
                opened = True
                if counter is not None:
                    counter.on = True
            if trace_at is not None and now >= trace_at:
                jax.profiler.start_trace(trace_dir)
                window_ann = jax.profiler.TraceAnnotation("bench.window")
                window_ann.__enter__()
                trace_at = None
            if now >= w1:
                if counter is not None:
                    counter.on = False
                if window_ann is not None:
                    window_ann.__exit__(None, None, None)
                    window_ann = None
                waiting = [rid for rid in rec.in_window
                           if not rec.stamps[rid]]
                if not waiting or now >= w1 + drain:
                    break
            with jax.profiler.TraceAnnotation("bench.submit"):
                while i < len(pending) and rec.due[pending[i].rid] <= now:
                    r = pending[i]
                    req = self.request(r)
                    sched.submit(req, self.now())
                    rec.submitted[r.rid] = time.perf_counter()
                    reqs[r.rid] = inflight[r.rid] = req
                    seen_out[r.rid] = seen_chunks[r.rid] = 0
                    i += 1
            if not inflight:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    nxt = rec.due[pending[i].rid] if i < len(pending) else now
                    time.sleep(min(max(nxt - now, 0.0), IDLE_SLEEP_S))
                continue
            p0, d0 = sched.prefill_chunks, sched.decode_waves
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                sched.step(self.now())
            t1 = time.perf_counter()
            chunk = None
            for rid, req in list(inflight.items()):
                n = len(req.out)
                if n > seen_out[rid]:
                    rec.stamps[rid] += [t1] * (n - seen_out[rid])
                    seen_out[rid] = n
                if req.chunks_done > seen_chunks[rid]:
                    # the scheduler resumes a prefix hit at the matched
                    # chunk, so the first chunk seen marks the adopted part
                    idx = req.chunks_done - 1
                    rec.adopted.setdefault(rid, idx * chunk_len)
                    chunk = (idx * chunk_len,
                             min((idx + 1) * chunk_len, len(req.tokens)),
                             len(req.tokens))
                seen_chunks[rid] = req.chunks_done
                if req.state == FINISHED:
                    del inflight[rid]
            if sched.prefill_chunks == p0:
                chunk = None
            rec.steps.append((t0, t1, chunk, sched.decode_waves > d0))
        rec.drain_end = time.perf_counter()
        if trace_dir:
            jax.profiler.stop_trace()
        rec.faults = sched.faults - faults0
        rec.requeues = sched.requeued - requeues0
        rec.attempted = len(rec.in_window)
        rec.failed = sum(1 for rid in rec.in_window if not rec.stamps[rid])
        return reqs


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        out_dir: str, devices, inspect=None) -> ServeRun:
    """One run of ``cell``.  ``inspect``, when given, is called after the
    comparison as ``inspect(weights, dims, prompts, outs)`` with the
    reference's weights and the sampled requests (``bench/control.py``
    reads the control there)."""
    from repro.serving.scheduler import FINISHED
    c, mix = cell.config, cell.traffic
    traffic_mod.check_fits(mix, c["bench"]["serving"]["cache_len"])
    rec = ServeRun(config=c, seconds=float(seconds),
                   device_kind=devices[0].device_kind, chips=len(devices))
    counter = _CompileCounter()
    server = Server(c, seed)
    gen = traffic_mod.Traffic(mix, seed, seconds, c["vocab_size"],
                              server.sv["prefill_chunk"])
    server.warm(gen.warmup)
    lead = float(mix.get("lead_in_s", 0.0))
    rec.setup_s = time.perf_counter() + lead - t_start
    reqs = server.serve(gen, rec, lead, float(mix["drain_s"]),
                        trace_dir=out_dir if trace else None,
                        counter=counter)
    rec.compiles_in_window = counter.count
    rec.memory_peak_bytes = _memory_peak(devices)

    # -- correctness: free the program's state, then the reference ----------
    finished = [req for req in reqs.values() if req.state == FINISHED]
    params = server.params
    del server, reqs
    gc.collect()
    t_ref = time.perf_counter()
    sample = _sample(finished, c["bench"]["check"]["requests"], seed)
    prompts = [np.asarray(r.prompt) for r in sample]
    outs = [list(r.out) for r in sample]
    w, dm = reference_weights(params), mixtral.Dims.from_config(c)
    rec.checks, rec.correct = _check(c, cell.limits, w, dm, prompts, outs)
    rec.reference_s = time.perf_counter() - t_ref
    if inspect is not None:
        inspect(w, dm, prompts, outs)
    del params, w
    gc.collect()
    return rec


def _sample(finished: list, n: int, seed: int) -> list:
    """The finished request with the most served tokens, and ``n - 1``
    others drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.out), r.rid))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(traffic_mod.seed_sequence(seed).spawn(1)[0])
    k = min(n - 1, len(rest))
    picked = [rest[j] for j in sorted(rng.choice(len(rest), k,
                                                 replace=False))] if k else []
    return [longest] + picked


def _check(c: dict, limits: dict, w, dm, prompts: list, outs: list):
    """Over the sampled requests: the share of served tokens whose logit in
    the reference lies more than the limits file's ``tolerance`` below the
    reference's best at that position, against its limit; and that the
    sample holds enough served tokens.

    Not the widest gap, the mean or a percentile: random weights give flat
    logits, so bfloat16 rounding decides some near-ties the other way (a
    gap of a few thousandths), and at a few positions a near-tie between
    two experts in the router goes the other way, which moves that
    position's logits by up to a unit or two.  Such a flip is as large in
    a sound run as in a run of a lower precision, so the widest gap does
    not separate the two, and the mean and the percentiles jump with the
    count of flips that a sample happens to hold.  A lower precision moves
    the logits by several times more, so it puts several times as many
    served tokens beyond the tolerance: the share reads that, one token at
    a time, and a single flip moves it by one token's worth."""
    lim = limits[SHARE]
    limit, tol = float(lim["limit"]), float(lim["tolerance"])
    if not prompts:
        return {SHARE: (float("nan"), limit)}, False
    need = int(c["bench"]["check"]["min_tokens"])
    gaps = np.concatenate(mixtral.served_gaps(w, dm, prompts, outs))
    share = float(np.mean(gaps > tol)) if gaps.size else float("nan")
    checks = {SHARE: (share, limit),
              "served_tokens_compared": (int(gaps.size), need)}
    ok = np.isfinite(share) and share <= limit and gaps.size >= need
    return checks, bool(ok)
