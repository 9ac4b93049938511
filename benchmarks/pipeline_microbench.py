"""Pipelined vs sequential FCDA schedule on a multi-device CPU mesh.

The EP MoE layer (core/ep.py) across chunk counts c ∈ {2, 4, 8}: the
sequential chunk loop (``pipeline_chunks=1``, ``lax.map``) against the wave
pipeline (``pipeline_chunks`` ∈ {2, c}, docs/DESIGN.md §Pipeline).  The
timing subprocess forces an 8-device host platform so the all-to-alls are
real collectives between device threads (the main process must keep the
single real device per the dry-run isolation rule — tests/test_distributed.py
uses the same pattern), pins XLA's CPU ops single-threaded and enables the
concurrency-optimized scheduler so the thunk runtime may actually execute
the schedule's independent work concurrently.

Methodology: variants are timed interleaved in blocks (min over repeats per
block), and the reported speedup is the MEDIAN of per-block paired ratios —
robust to the common-mode load drift of a shared CPU box.  CPU caveat: the
host backend's collectives are synchronous rendezvous, so the win here comes
from filling rendezvous/scheduling idle with the adjacent chunk's
independent work; on TPU the same schedule additionally hides dispatch/
combine ICI latency under the expert GEMMs.  Trajectory anchor, not the TPU
speedup.

Emits CSV lines per repo convention and writes ``BENCH_pipeline.json`` so
later PRs can track the trajectory.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

DEVICES = 8
CHUNKS = (2, 4, 8)
BLOCKS = 6
REPEATS = 8
B, S, D = 4, 1024, 128          # per-device tokens: B * S/DEVICES = 512
EXPERTS, TOP_K, D_FF = 8, 2, 256

_INNER = f"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count={DEVICES} "
    "--xla_cpu_multi_thread_eigen=false "
    "--xla_cpu_enable_concurrency_optimized_scheduler=true")
import json, statistics, time
import jax, jax.numpy as jnp
from repro.core import moe as M
from repro.configs.base import MoEConfig

cfg = MoEConfig(num_experts={EXPERTS}, top_k={TOP_K}, d_ff_expert={D_FF})
from repro.launch.mesh import make_mesh
mesh = make_mesh((1, {DEVICES}), ("data", "model"))
params = M.init_moe(jax.random.PRNGKey(0), {D}, cfg)
x = jax.random.normal(jax.random.PRNGKey(1), ({B}, {S}, {D}))

rows = []
with jax.set_mesh(mesh):
    for chunks in {CHUNKS}:
        depths = sorted({{2, chunks}})
        ctxs = {{"seq": M.DistContext(mesh=mesh, moe_chunks=chunks,
                                      moe_strategy="ep_shardmap")}}
        for d in depths:
            ctxs[f"depth{{d}}"] = M.DistContext(
                mesh=mesh, moe_chunks=chunks, pipeline_chunks=d,
                moe_strategy="ep_shardmap")
        fns = {{k: jax.jit(lambda p, x, ctx=v: M.moe_ffn(p, x, cfg, ctx)[0])
               for k, v in ctxs.items()}}
        for f in fns.values():
            f(params, x).block_until_ready()                # compile
        blocks = {{k: [] for k in fns}}
        for _ in range({BLOCKS}):
            best = {{k: float("inf") for k in fns}}
            for _ in range({REPEATS}):                      # interleaved
                for k, f in fns.items():
                    t0 = time.perf_counter()
                    f(params, x).block_until_ready()
                    best[k] = min(best[k], time.perf_counter() - t0)
            for k in fns:
                blocks[k].append(best[k])
        row = {{"chunks": chunks,
               "sequential_ms": round(statistics.median(blocks["seq"]) * 1e3, 3)}}
        for d in depths:
            k = f"depth{{d}}"
            # paired per-block ratios: machine drift hits both variants alike
            sp = statistics.median(s / p for s, p in zip(blocks["seq"], blocks[k]))
            row[f"{{k}}_ms"] = round(statistics.median(blocks[k]) * 1e3, 3)
            row[f"{{k}}_speedup"] = round(sp, 3)
        best_d = max(depths, key=lambda d: row[f"depth{{d}}_speedup"])
        row["pipelined_ms"] = row[f"depth{{best_d}}_ms"]
        row["speedup"] = row[f"depth{{best_d}}_speedup"]
        row["pipeline_depth"] = best_d
        rows.append(row)
print(json.dumps(rows))
"""


def run() -> list[str]:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "src")
    if os.environ.get("PYTHONPATH"):
        path = path + os.pathsep + os.environ["PYTHONPATH"]
    # virtual CPU devices: the child never reaches for an accelerator the
    # parent process may hold
    out = subprocess.run([sys.executable, "-c", _INNER], capture_output=True,
                         text=True, timeout=1800,
                         env={**os.environ, "PYTHONPATH": path,
                              "JAX_PLATFORMS": "cpu"})
    if out.returncode != 0:
        raise RuntimeError(f"pipeline microbench subprocess failed:\n"
                           f"{out.stdout}\n{out.stderr}")
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    lines = []
    for row in rows:
        lines.append(f"pipeline,chunks={row['chunks']},"
                     f"sequential_ms={row['sequential_ms']:.3f},"
                     f"pipelined_ms={row['pipelined_ms']:.3f},"
                     f"depth={row['pipeline_depth']},"
                     f"speedup={row['speedup']:.3f}")
    with open("BENCH_pipeline.json", "w") as f:
        json.dump({"devices": DEVICES, "tokens_per_device": B * S // DEVICES,
                   "experts": EXPERTS, "top_k": TOP_K, "d": D, "d_ff": D_FF,
                   "blocks": BLOCKS, "repeats": REPEATS, "rows": rows}, f,
                  indent=2)
    lines.append("pipeline,written=BENCH_pipeline.json")
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
