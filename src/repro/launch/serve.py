"""Serving launcher: continuous batching over a synthetic Poisson trace.

Requests arrive as a Poisson process with per-request prompt/generation
lengths; the continuous-batching scheduler (docs/DESIGN.md §Serving) admits
them against the serving memory model, interleaves chunked prefill with
decode waves, and the run reports aggregate tok/s, p50/p99 request latency
and the modeled-peak-vs-budget memory headroom.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-27b --smoke \
      --requests 16 --arrival-rate 4 --max-slots 4 --budget-gb 32

Weights are built in the config's dtype (bf16 for the published configs),
and admission plans against the profile of the device JAX reports
(``configs.base.device_profile``).  On a TPU, ``--layers N`` serves the
published widths at a cut depth; ``python chip_smoke.py`` drives this path
at mixtral-8x7b widths on one v5e.
"""

from __future__ import annotations

import argparse


def make_trace(rng, n: int, rate_hz: float, prompt_lens, gen_range,
               vocab: int, chunk: int):
    """n Poisson arrivals; prompt lengths are drawn from ``prompt_lens``
    (multiples of the prefill chunk, so every chunk shape compiles once)."""
    import numpy as np
    from repro.serving.scheduler import Request

    for S in prompt_lens:
        if S % chunk and S > chunk:
            raise ValueError(
                f"--prompt-lens entry {S} is not a multiple of "
                f"--prefill-chunk {chunk}; chunk shapes would re-trace")
    t = 0.0
    out = []
    for i in range(n):
        t += rng.exponential(1.0 / rate_hz) if rate_hz > 0 else 0.0
        S = int(rng.choice(prompt_lens))
        out.append(Request(
            rid=i,
            tokens=rng.integers(0, vocab, S).astype(np.int32),
            max_new_tokens=int(rng.integers(gen_range[0], gen_range[1] + 1)),
            arrival=t))
    return out


def main(argv=None):
    """Parse ``argv`` (default: the command line), serve the trace, print
    the summary; returns the scheduler and its metrics dict."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (2 layers, small dims)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers, keeping the "
                         "published widths (0 = the config's depth)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="Poisson arrival rate (requests/s); 0 = all at t=0")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=0,
                    help="per-request cache length (0 = max prompt + gen)")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--prompt-lens", default="16,32,48,64",
                    help="comma list of prompt lengths to draw from")
    ap.add_argument("--gen", default="4,24", help="min,max generated tokens")
    ap.add_argument("--budget-gb", type=float, default=0.0,
                    help="override the hardware memory budget (GB)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="admission deadline: a request not admitted within "
                         "this many seconds of arrival is shed with a "
                         "retry-after quote (docs/DESIGN.md §Resilience)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="overload bound on the WAITING queue (0 = off)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged cache: tokens per page (0 = monolithic "
                         "slot map; docs/DESIGN.md §Paging)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share common prompt prefixes through the "
                         "page-level trie (requires --page-size)")
    ap.add_argument("--preemption", action="store_true",
                    help="spill low-priority residents to host when "
                         "admission is refused (requires --page-size)")
    ap.add_argument("--placement-peers", type=int, default=0,
                    help="choose a static expert placement over this many EP "
                         "peers at engine build, from --placement-loads "
                         "(docs/DESIGN.md §Placement); 0 = identity")
    ap.add_argument("--placement-loads", default=None,
                    help="JSON file with a (L_moe, E) load matrix (e.g. a "
                         "training run's telemetry EMA) the placement is "
                         "solved from; omitted = identity")
    ap.add_argument("--placement-replicas", type=int, default=0,
                    help="extra hot-expert weight slots per peer; their "
                         "weight bytes are priced by admission control")
    ap.add_argument("--expert-batching", action="store_true",
                    help="group decode waves by predicted expert overlap "
                         "instead of FIFO age order (MoE archs only; "
                         "docs/DESIGN.md §Residency)")
    ap.add_argument("--wave-size", type=int, default=0,
                    help="max members per decode wave (0 = every resident); "
                         ">0 engages the masked subset step")
    ap.add_argument("--max-wave-wait", type=int, default=4,
                    help="starvation guard: a resident that skipped this "
                         "many waves is force-included in the next one")
    ap.add_argument("--resident-experts", type=int, default=0,
                    help="per-MoE-layer resident expert capacity; cold "
                         "experts are host-offloaded and prefetched ahead "
                         "of the wave (0 = all resident)")
    ap.add_argument("--probe-router", action="store_true",
                    help="router-only probe on prompt tokens seeds the "
                         "prefetch prediction before telemetry exists")
    ap.add_argument("--inject", default=None,
                    help="chaos faults on scheduler steps, e.g. 'oom@20' "
                         "(faulted decode waves requeue accepted requests)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import dataclasses

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import device_profile
    from repro.core.moe import DistContext
    from repro.models import transformer
    from repro.serving.scheduler import (ContinuousBatchingScheduler,
                                         ServeConfig)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    ctx = DistContext()
    replica_bytes = 0.0
    if args.placement_peers:
        import json as _json

        from repro.serving.engine import build_placements
        loads = None
        if args.placement_loads:
            with open(args.placement_loads) as f:
                loads = np.asarray(_json.load(f), dtype=np.float64)
        ctx, replica_bytes = build_placements(
            cfg, ctx, args.placement_peers, loads=loads,
            replicas=args.placement_replicas)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(args.seed)
    prompt_lens = [int(s) for s in args.prompt_lens.split(",")]
    gen_lo, gen_hi = (int(s) for s in args.gen.split(","))
    trace = make_trace(rng, args.requests, args.arrival_rate, prompt_lens,
                       (gen_lo, gen_hi), cfg.vocab_size, args.prefill_chunk)

    cache_len = args.cache_len or max(prompt_lens) + gen_hi
    hw = device_profile()
    if args.budget_gb:
        # the flag names the admission budget itself, so alpha must not
        # discount it a second time
        hw = dataclasses.replace(hw, hbm_bytes=args.budget_gb * 1e9,
                                 alpha=1.0)
    if (args.prefix_cache or args.preemption) and not args.page_size:
        raise SystemExit("--prefix-cache/--preemption require --page-size")
    scfg = ServeConfig(max_slots=args.max_slots, cache_len=cache_len,
                       prefill_chunk=args.prefill_chunk, hw=hw,
                       temperature=args.temperature,
                       deadline_s=args.deadline_s,
                       max_waiting=args.max_waiting,
                       page_size=args.page_size,
                       prefix_cache=args.prefix_cache,
                       preemption=args.preemption,
                       replica_weight_bytes=replica_bytes,
                       expert_batching=args.expert_batching,
                       wave_size=args.wave_size,
                       max_wave_wait=args.max_wave_wait,
                       resident_experts=args.resident_experts,
                       probe_router=args.probe_router)

    injector = None
    if args.inject:
        from repro.runtime.faults import FaultInjector
        injector = FaultInjector.from_string(args.inject)
    if args.page_size:
        from repro.serving.paged_scheduler import PagedScheduler
        sched = PagedScheduler(params, cfg, ctx, scfg,
                               key=jax.random.PRNGKey(args.seed),
                               injector=injector)
    else:
        sched = ContinuousBatchingScheduler(params, cfg, ctx, scfg,
                                            key=jax.random.PRNGKey(args.seed),
                                            injector=injector)
    mode = (f"paged(page={args.page_size}, prefix={args.prefix_cache}, "
            f"preempt={args.preemption})" if args.page_size else "slot-map")
    if args.placement_peers and ctx.placements is not None:
        placed = sum(1 for p in ctx.placements if not p.is_identity)
        print(f"placement: {placed}/{len(ctx.placements)} layers re-homed "
              f"over {args.placement_peers} peers, replica weights "
              f"{replica_bytes / 1e9:.3f} GB priced by admission")
    print(f"serving {cfg.name}: {args.requests} requests, "
          f"rate={args.arrival_rate}/s, slots={args.max_slots}, "
          f"cache_len={cache_len}, prefill_chunk={args.prefill_chunk}, "
          f"{mode}")
    m = sched.run(trace)

    budget_gb = m["budget_bytes"] / 1e9
    peak_gb = m["modeled_peak_bytes"] / 1e9
    print(f"served {m['requests']} requests, {m['generated_tokens']} tokens "
          f"in {m['elapsed_s']:.2f}s -> {m['tok_per_s']:.1f} tok/s")
    print(f"latency p50={m['latency_p50_s']:.2f}s p99={m['latency_p99_s']:.2f}s "
          f"(gen {gen_lo}-{gen_hi} tokens/request)")
    print(f"memory: modeled peak {peak_gb:.2f} GB <= budget {budget_gb:.2f} GB "
          f"(headroom {budget_gb - peak_gb:.2f} GB), "
          f"max occupancy {m['max_occupancy']}/{args.max_slots} slots")
    print(f"schedule: {m['decode_waves']} decode waves, "
          f"{m['prefill_chunks']} interleaved prefill chunks")
    if m["expert_waves"]:
        print(f"expert waves: {m['expert_waves']} waves, mean "
              f"{m['mean_distinct_experts']:.2f} distinct experts / "
              f"{m['mean_wave_occupancy']:.2f} members per wave, "
              f"{m['forced_includes']} starvation force-includes")
    if "residency" in m:
        r = m["residency"]
        print(f"residency: {args.resident_experts} resident experts/layer "
              f"(hwm {r['resident_experts_hwm']}), prefetch "
              f"{m['prefetch_hits']} hits / {m['prefetch_misses']} misses, "
              f"{r['restores']} restores ({r['demand_restores']} on demand, "
              f"{m['demand_reruns']} re-runs), {r['offloads']} offloads")
    if args.page_size:
        extra = ""
        if args.prefix_cache:
            extra = (f", prefix hit rate {m['prefix_hit_rate']:.2f} "
                     f"({m['prefix_tokens_reused']} tokens reused)")
        print(f"paging: page high-watermark {m['page_hwm_bytes'] / 1e9:.3f} GB"
              f", {m['preemptions']} preemptions{extra}")
    if m["shed"] or m["faults"]:
        print(f"resilience: {m['shed']} shed "
              f"(retry-after p50 {m['retry_after_p50_s']:.1f}s), "
              f"{m['faults']} faulted waves, {m['requeues']} requeues, "
              f"0 accepted requests lost")
    if sched.finished:
        sample = sched.finished[0]
        print(f"sample (rid {sample.rid}): {sample.out[:12]}")
    return sched, m


if __name__ == "__main__":
    main()
