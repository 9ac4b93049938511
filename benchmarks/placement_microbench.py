"""Telemetry-driven expert placement vs identity layout under routing skew.

Three variants of the same EP MoE step (docs/DESIGN.md §Placement):

* ``balanced``  — round-robin routing (token t -> experts (t%E, t%E+1)): the
  no-skew reference where every peer does equal work.
* ``identity``  — worst-case skew: EVERY token routes to experts {0, 1},
  which the identity layout co-locates on peer 0, so that peer receives the
  whole step's routed tokens and the step runs at its pace.
* ``placed``    — the same skewed trace under a placement solved from the
  observed load (LPT + one replica slot per peer): experts 0 and 1 are
  re-homed and each replicated across two peers, restoring the balanced
  per-peer load exactly.

Part 1 (correctness, real 4-peer mesh): the skewed trace is run through the
actual ``moe_ffn`` EP path with and without the placement — the placed
output must be BITWISE-identical with zero drops, and the observed load
histogram feeds ``plan_placement`` exactly like the trainer's telemetry
does at a replan boundary.

Part 2 (timing): the dropless EP path computes over static capacity-padded
buffers, so on this CPU backend the full step's wall time cannot express a
load imbalance (every peer's buffer is the same shape regardless of
routing).  What DOES track the imbalance — and what sets the step time on
real hardware — is the hottest peer's expert-FFN leg, so that is what gets
measured: a single-device gated-FFN over each variant's modeled
bottleneck-peer token count (identity: 4x the balanced tokens; placed: 1x).
Variants are timed interleaved in blocks (min over repeats) and ratios are
medians of per-block PAIRED ratios, per the repo's benchmark methodology.

Emits CSV lines per repo convention and writes ``BENCH_placement.json``.
``PLACEMENT_BENCH_TINY=1`` shrinks shapes/repeats for CI smoke.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

TINY = bool(int(os.environ.get("PLACEMENT_BENCH_TINY", "0")))
DEVICES = 4
BLOCKS = 2 if TINY else 6
REPEATS = 2 if TINY else 8
B, S, D = (2, 128, 64) if TINY else (4, 1024, 128)
EXPERTS, TOP_K, D_FF = 8, 2, (128 if TINY else 256)

_INNER = f"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count={DEVICES} "
    "--xla_cpu_multi_thread_eigen=false "
    "--xla_cpu_enable_concurrency_optimized_scheduler=true")
import json, math, statistics, time
import numpy as np
import jax, jax.numpy as jnp
from repro.core import moe as M
from repro.core import placement as plc
from repro.configs.base import MoEConfig

E, K, B, S, D = {EXPERTS}, {TOP_K}, {B}, {S}, {D}
cfg = MoEConfig(num_experts=E, top_k=K, d_ff_expert={D_FF})
from repro.launch.mesh import make_mesh
mesh = make_mesh((1, {DEVICES}), ("data", "model"))
params = M.init_moe(jax.random.PRNGKey(0), D, cfg)
# router reads the first E features verbatim: a two-hot spike per token
# forces its (top1, top2) pair exactly
params["router"]["w"] = jnp.concatenate(
    [jnp.eye(E, dtype=jnp.float32),
     jnp.zeros((D - E, E), jnp.float32)], axis=0)

def trace(e1, e2):
    rng = np.random.default_rng(0)
    T = B * S
    x = (rng.standard_normal((T, D)) * 0.1).astype(np.float32)
    x[:, :E] = 0.0
    x[np.arange(T), e1] = 5.0
    x[np.arange(T), e2] = 4.0
    return jnp.asarray(x.reshape(B, S, D))

t = np.arange(B * S)
x_bal = trace(t % E, (t + 1) % E)            # round-robin: even per-peer load
x_skew = trace(np.zeros_like(t), np.ones_like(t))   # all tokens -> {{0, 1}}

def ctx_for(placement=None):
    return M.DistContext(mesh=mesh, moe_chunks=2, moe_strategy="ep_shardmap",
                         placement=placement)

# -- part 1: real EP step on the mesh — parity + the observed load ----------
with jax.set_mesh(mesh):
    step = jax.jit(lambda p, x, c=ctx_for(): M.moe_ffn(p, x, cfg, c))
    y_skew, s_skew = step(params, x_skew)
    _, s_bal = step(params, x_bal)
load = np.asarray(s_skew["load"], np.float64)
assert load[0] == B * S and load[1] == B * S, load   # the forcing worked
spec = plc.plan_placement(load, {DEVICES}, replicas=1)
ident = plc.PlacementSpec.identity(E, {DEVICES})
with jax.set_mesh(mesh):
    y_placed, s_placed = jax.jit(
        lambda p, x, c=ctx_for(spec): M.moe_ffn(p, x, cfg, c))(params, x_skew)
np.testing.assert_array_equal(np.asarray(y_skew), np.asarray(y_placed))
assert float(s_placed["drops"]) == 0.0 and float(s_skew["drops"]) == 0.0

# -- part 2: bottleneck-peer expert-FFN leg, sized by the modeled map -------
bottleneck = {{
    "balanced": plc.bottleneck(ident, np.asarray(s_bal["load"], np.float64)),
    "identity": plc.bottleneck(ident, load),
    "placed": plc.bottleneck(spec, load),
}}
w1 = jax.random.normal(jax.random.PRNGKey(2), (D, {D_FF})) * D ** -0.5
w3 = jax.random.normal(jax.random.PRNGKey(3), (D, {D_FF})) * D ** -0.5
w2 = jax.random.normal(jax.random.PRNGKey(4), ({D_FF}, D)) * {D_FF} ** -0.5

def leg(x):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

fns, xs = {{}}, {{}}
for name, n in bottleneck.items():
    n = int(math.ceil(n))
    xs[name] = jax.random.normal(jax.random.PRNGKey(5), (n, D))
    fns[name] = jax.jit(leg)
    fns[name](xs[name]).block_until_ready()          # compile
blocks = {{k: [] for k in fns}}
for _ in range({BLOCKS}):
    best = {{k: float("inf") for k in fns}}
    for _ in range({REPEATS}):                       # interleaved
        for k, f in fns.items():
            t0 = time.perf_counter()
            f(xs[k]).block_until_ready()
            best[k] = min(best[k], time.perf_counter() - t0)
    for k in fns:
        blocks[k].append(best[k])

out = {{
    "balanced_ms": round(statistics.median(blocks["balanced"]) * 1e3, 3),
    "identity_ms": round(statistics.median(blocks["identity"]) * 1e3, 3),
    "placed_ms": round(statistics.median(blocks["placed"]) * 1e3, 3),
    # paired per-block ratios: machine drift hits both variants alike
    "identity_over_balanced": round(statistics.median(
        i / b for i, b in zip(blocks["identity"], blocks["balanced"])), 3),
    "placed_over_balanced": round(statistics.median(
        p / b for p, b in zip(blocks["placed"], blocks["balanced"])), 3),
    "bottleneck_tokens": {{k: float(v) for k, v in bottleneck.items()}},
    "placement": [spec.num_experts, spec.num_peers, list(spec.slot_to_expert)],
    "parity": "bitwise",
    "drops": 0.0,
}}
print(json.dumps(out))
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
        raise RuntimeError(f"placement microbench subprocess failed:\n"
                           f"{out.stdout}\n{out.stderr}")
    row = json.loads(out.stdout.strip().splitlines()[-1])
    with open("BENCH_placement.json", "w") as f:
        json.dump({"devices": DEVICES, "tokens": B * S, "experts": EXPERTS,
                   "top_k": TOP_K, "d": D, "d_ff": D_FF, "tiny": TINY,
                   "blocks": BLOCKS, "repeats": REPEATS, "row": row}, f,
                  indent=2)
    return [
        f"placement,balanced_ms={row['balanced_ms']:.3f},"
        f"identity_ms={row['identity_ms']:.3f},"
        f"placed_ms={row['placed_ms']:.3f},"
        f"identity_over_balanced={row['identity_over_balanced']:.3f},"
        f"placed_over_balanced={row['placed_over_balanced']:.3f},"
        f"parity={row['parity']}",
        "placement,written=BENCH_placement.json",
    ]


if __name__ == "__main__":
    print("\n".join(run()))
