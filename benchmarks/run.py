"""Benchmark driver — one module per paper table/figure + the dry-run-derived
extensions.  Prints ``name,...`` CSV lines per the repo convention.

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run table4 fig5  # a subset
"""

from __future__ import annotations

import sys
import time

from benchmarks import (ablation_capacity, adaptive_microbench,
                        chaos_harness, compiled_memory, dispatch_microbench,
                        fig2_distribution, fig4_throughput, fig5_mact,
                        fused_microbench, paging_microbench,
                        pipeline_microbench, placement_microbench,
                        residency_microbench, roofline, serving_microbench,
                        table4_memory)

SUITES = {
    "dispatch": dispatch_microbench.run,  # single-sort planner vs old path
    "fused": fused_microbench.run,        # 1-launch fused leg + autotuner
    "pipeline": pipeline_microbench.run,  # sequential vs pipelined FCDA
    "placement": placement_microbench.run,  # expert placement vs identity
    "adaptive": adaptive_microbench.run,  # per-layer MACT vs static global
    "serving": serving_microbench.run,    # continuous vs static batching
    "paging": paging_microbench.run,      # paged vs monolithic KV cache
    "residency": residency_microbench.run,  # expert waves + weight residency
    "chaos": chaos_harness.run,           # injected faults: ladder/resume/shed
    "table4": table4_memory.run,       # Table 4 (memory model, Methods 1/2/3)
    "fig2": fig2_distribution.run,     # Fig. 2 (token distribution)
    "fig4": fig4_throughput.run,       # Fig. 4 (TGS Methods 1/2/3)
    "fig5": fig5_mact.run,             # Fig. 5 (MACT chunk trace)
    "ablation": ablation_capacity.run, # §2.2: capacity baseline drops tokens
    "compiled": compiled_memory.run,   # beyond-paper: XLA-measured Table 4
    "roofline": roofline.run,          # deliverable (g)
}


def main() -> int:
    """Run the named suites (default: all); returns 1 if any suite raised.
    A failing suite is reported and the rest still run."""
    names = sys.argv[1:] or list(SUITES)
    failed = []
    for name in names:
        fn = SUITES[name]
        t0 = time.perf_counter()
        try:
            lines = fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            lines = [f"{name},ERROR,{type(e).__name__}: {e}"]
            failed.append(name)
        dt = time.perf_counter() - t0
        for line in lines:
            print(line, flush=True)
        print(f"{name},elapsed_s={dt:.1f}", flush=True)
    if failed:
        print(f"FAILED suites: {','.join(failed)}", file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
