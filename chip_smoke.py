"""Smoke test of the MoE main path on a TPU, through the entry points a user
calls.  Run it from the root of a checkout:

  python chip_smoke.py            # one v5e chip
  python chip_smoke.py --chips 4  # the 4-chip expert-parallel train step only

One chip, in order (weights random, from fixed seeds):

  serve-slotmap  ``repro.launch.serve`` at mixtral-8x7b's published widths
                 (bf16 weights, depth cut to 4 layers): 8 requests at t=0,
                 4 slots, prompts of 256/512 tokens, 16-32 generated tokens.
                 Every request served, no fault, no shed.  One prompt's
                 next-token logits from ``engine.prefill`` are checked against
                 ``transformer.forward`` on the same weights.
  serve-paged    the same trace on the paged cache (page 16, prefix cache),
                 whose steps donate their page pools.
  expert-ffn     the Pallas grouped SwiGLU expert FFN, compiled for the chip,
                 at (E=8, 128 rows, 4096 -> 14336 -> 4096) bf16, against the
                 jnp reference ``kernels/ref.py::expert_ffn_ref``.
  trainer        ``repro.launch.train --smoke`` (reduced widths, MACT on) for
                 3 steps: checks the trainer path only.

Four chips (``--chips 4``): ``repro.launch.train --mesh host`` trains
mixtral-8x7b at published widths (1 layer, f32 master weights, seq 4096,
global batch 8) for 3 steps with the 8 experts sharded 2 per chip
(``ep_shardmap``).  Then one forward loss at batch 1 is compared with the
``tp_gspmd`` strategy on the same mesh, weights and batch: equal per-expert
loads summing to B*S*k, no drops.

Every phase prints its wall seconds, its backend-compile seconds and the
devices' ``peak_bytes_in_use`` (a process-wide high-water mark).  Any failed
check, any OOM-ladder escalation and any non-finite loss or logit fails the
run.  The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or outside a checkout, the script exits non-zero and prints
no result.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the serving trace: ``repro.launch.serve`` flags at mixtral-8x7b widths
SERVE_ARGS = ["--arch", "mixtral-8x7b", "--layers", "4", "--requests", "8",
              "--arrival-rate", "0", "--max-slots", "4",
              "--prompt-lens", "256,512", "--gen", "16,32",
              "--prefill-chunk", "256"]
PAGED_ARGS = ["--page-size", "16", "--prefix-cache"]
TRAIN_SMOKE_ARGS = ["--arch", "mixtral-8x7b", "--smoke", "--steps", "3"]
#: the 4-chip EP train step; batch 8 is the largest that the ahead-of-time
#: v5e:2x2 compile fits (14.4 GB/chip at batch 8, 20.2 GB at 16)
EP_TRAIN_ARGS = ["--arch", "mixtral-8x7b", "--layers", "1", "--mesh", "host",
                 "--seq-len", "4096", "--global-batch", "8", "--steps", "3"]
#: expert-FFN kernel shape: E, rows per expert, d_model, d_ff_expert
KERNEL_SHAPE = (8, 128, 4096, 14336)

#: tolerances, as max |got - want| / max |want|
LOGIT_RTOL = 2e-2    # bf16 weights: two XLA programs over the same math
KERNEL_RTOL = 2e-2   # bf16 in/out, f32 accumulation in a different order
LOSS_RTOL = 1e-4     # f32 forward at matmul precision "highest"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


class Phases:
    """Per-phase wall seconds, backend-compile seconds and peak device
    bytes, printed as each phase ends."""

    def __init__(self, devices):
        import jax
        self.devices = devices
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.compile_s += duration

    def memory(self, key: str) -> int:
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(s.get(key, 0) for s in stats)

    @contextlib.contextmanager
    def __call__(self, name: str):
        print(f"[{name}] start", flush=True)
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        peak = self.memory("peak_bytes_in_use")
        limit = self.memory("bytes_limit")
        print(f"[{name}] done: wall {time.perf_counter() - t0:.1f} s, "
              f"compile {self.compile_s - c0:.1f} s, "
              f"peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB) of "
              f"bytes_limit {limit} ({limit / 2**30:.2f} GiB)", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def check_served(sched, m: dict, n_requests: int, label: str) -> None:
    """Every request of the trace finished with all its tokens; nothing was
    shed, faulted or lost."""
    lost = n_requests - m["requests"] - m["shed"]
    print(f"{label}: served {m['requests']}/{n_requests}, shed {m['shed']}, "
          f"faults {m['faults']}, lost {lost}, "
          f"{m['generated_tokens']} tokens", flush=True)
    check(m["requests"] == n_requests, f"{label}: {m['requests']} of "
          f"{n_requests} requests served")
    check(m["shed"] == 0 and m["faults"] == 0 and lost == 0,
          f"{label}: shed {m['shed']}, faults {m['faults']}, lost {lost}")
    short = [r.rid for r in sched.finished if len(r.out) != r.max_new_tokens]
    check(not short, f"{label}: requests {short} stopped short")


def check_prefill_logits(sched) -> None:
    """``engine.prefill`` next-token logits of one served prompt against
    ``transformer.forward`` on the same weights."""
    import jax
    import numpy as np
    from repro.models import transformer
    from repro.serving import engine

    req = sched.finished[0]
    cfg, ctx = sched.cfg, sched.ctx   # the jitted closure must not hold sched
    tokens = jax.numpy.asarray(np.asarray(req.prompt)[None], jax.numpy.int32)
    got, _ = engine.prefill(sched.params, cfg, ctx, {"tokens": tokens},
                            sched.scfg.cache_len)
    fwd = jax.jit(lambda p, b: transformer.forward(p, cfg, ctx, b)[0][:, -1:])
    want = fwd(sched.params, {"tokens": tokens})
    got, want = np.asarray(got), np.asarray(want)
    err = rel_err(got, want)
    print(f"prefill logits vs forward (rid {req.rid}, {tokens.shape[1]} "
          f"tokens): rel err {err:.3e} (tolerance {LOGIT_RTOL:.0e})",
          flush=True)
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite logits")
    check(err <= LOGIT_RTOL, f"prefill logits off by {err:.3e}")


def serve_phase(args: list, *, check_logits: bool) -> None:
    from repro.launch import serve
    n = int(args[args.index("--requests") + 1])
    sched, m = serve.main(args)
    check_served(sched, m, n, "serve")
    if check_logits:
        check_prefill_logits(sched)


def kernel_phase(E: int, M: int, d: int, f: int) -> None:
    """The grouped SwiGLU expert FFN compiled for the chip vs the jnp
    reference."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ops import expert_ffn
    from repro.kernels.ref import expert_ffn_ref

    k = jax.random.split(jax.random.PRNGKey(1), 4)
    bf = jnp.bfloat16
    x = jax.random.normal(k[0], (E, M, d), bf)
    w1 = jax.random.normal(k[1], (E, d, f), bf) * d ** -0.5
    w3 = jax.random.normal(k[2], (E, d, f), bf) * d ** -0.5
    w2 = jax.random.normal(k[3], (E, f, d), bf) * f ** -0.5
    kern = jax.jit(functools.partial(expert_ffn, use_pallas=True)).lower(
        x, w1, w3, w2).compile()
    check("tpu_custom_call" in kern.as_text(),
          "expert FFN did not compile to a TPU kernel")
    got = np.asarray(kern(x, w1, w3, w2).astype(jnp.float32))
    want = np.asarray(jax.jit(expert_ffn_ref)(x, w1, w3, w2)
                      .astype(jnp.float32))
    err = rel_err(got, want)
    print(f"grouped expert FFN (E={E}, {M} rows, {d}->{f}->{d} bf16): "
          f"rel err {err:.3e} vs expert_ffn_ref (tolerance "
          f"{KERNEL_RTOL:.0e})", flush=True)
    check(np.isfinite(got).all(), "non-finite kernel output")
    check(err <= KERNEL_RTOL, f"expert FFN kernel off by {err:.3e}")


def check_trained(trainer, steps: int, label: str) -> None:
    losses = [r["loss"] for r in trainer.log]
    print(f"{label}: losses {losses}, OOM escalations "
          f"{len(trainer.guard.escalations)}, schedules "
          f"{list(zip(trainer.chunk_trace, trainer.pipeline_trace))}",
          flush=True)
    check(len(losses) == steps, f"{label}: {len(losses)} of {steps} steps")
    check(all(math.isfinite(v) for v in losses),
          f"{label}: non-finite loss {losses}")
    check(not trainer.guard.escalations,
          f"{label}: OOM ladder escalated: {trainer.guard.escalations}")
    drops = [r["drops"] for r in trainer.log]
    check(all(v == 0 for v in drops), f"{label}: dropped tokens {drops}")


def trainer_phase(args: list) -> None:
    from repro.launch import train
    trainer, _ = train.main(args)
    check_trained(trainer, int(args[args.index("--steps") + 1]),
                  "trainer path (reduced widths: checks the path only)")


def ep_phase(args: list) -> None:
    """The EP train steps through the launcher, then one batch-1 forward
    loss under ``ep_shardmap`` against ``tp_gspmd`` on the same mesh."""
    import dataclasses

    import jax
    import numpy as np
    from repro.core.moe import resolve_strategy
    from repro.launch import train
    from repro.models.transformer import num_moe_layers
    from repro.training.step import loss_fn

    trainer, state = train.main(args)
    check_trained(trainer, int(args[args.index("--steps") + 1]),
                  "EP train steps")
    params = state.params
    del state
    gc.collect()
    cfg, mesh = trainer.cfg, trainer.ctx.mesh
    batch = {k: v[:1] for k, v in trainer.data.batch_at(0).items()}
    B, S = batch["tokens"].shape
    chunks = trainer.chunk_trace[-1]
    out = {}
    with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
        placed = trainer._place_batch(batch)
        for strategy in ("ep_shardmap", "tp_gspmd"):
            ctx = dataclasses.replace(trainer.ctx, moe_strategy=strategy,
                                      moe_chunks=chunks, pipeline_chunks=1,
                                      layer_schedules=None, placements=None)
            check(resolve_strategy(cfg.moe, (B, S), ctx) == strategy,
                  f"{strategy} does not resolve at batch {B}")
            _, m = jax.jit(lambda p, b, c=ctx: loss_fn(p, cfg, c, b))(
                params, placed)
            # cross-entropy: the aux balance term is nonlinear in how each
            # strategy groups tokens, so it differs by design (core/moe.py)
            out[strategy] = (float(m["ce"]), np.asarray(m["load"]),
                             float(m["drops"]))
    (l_ep, load_ep, d_ep), (l_tp, load_tp, d_tp) = (out["ep_shardmap"],
                                                    out["tp_gspmd"])
    want = B * S * cfg.moe.top_k * num_moe_layers(cfg)
    err = abs(l_ep - l_tp) / abs(l_tp)
    print(f"batch-1 cross-entropy: ep_shardmap {l_ep!r}, tp_gspmd {l_tp!r}, "
          f"rel err {err:.3e} (tolerance {LOSS_RTOL:.0e}); loads ep "
          f"{load_ep.tolist()} tp {load_tp.tolist()} (sum {load_ep.sum()}, "
          f"want {want}); drops ep {d_ep} tp {d_tp}", flush=True)
    check(math.isfinite(l_ep) and math.isfinite(l_tp), "non-finite loss")
    check(err <= LOSS_RTOL, f"EP loss off by {err:.3e}")
    check(np.array_equal(load_ep, load_tp), "per-expert loads differ")
    check(load_ep.sum() == want, f"loads sum to {load_ep.sum()}, not {want}")
    check(d_ep == 0 and d_tp == 0, f"drops: ep {d_ep}, tp {d_tp}")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: the 4-chip EP train step")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run from the root "
              f"of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    devices = jax.devices()
    dev = devices[0]
    found = (f"platform {dev.platform!r} ({dev.device_kind}, "
             f"{len(devices)} devices)")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {found}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips; "
              f"JAX found {found}", file=sys.stderr)
        return 1
    print(f"chip_smoke: {found}", flush=True)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    used = devices[:args.chips]
    phase = Phases(used)
    try:
        if args.chips == 4:
            with phase("ep-train-4chip"):
                ep_phase(EP_TRAIN_ARGS)
        else:
            with phase("serve-slotmap"):
                serve_phase(SERVE_ARGS, check_logits=True)
            gc.collect()
            with phase("serve-paged"):
                serve_phase(SERVE_ARGS + PAGED_ARGS, check_logits=False)
            gc.collect()
            with phase("expert-ffn"):
                kernel_phase(*KERNEL_SHAPE)
            gc.collect()
            with phase("trainer"):
                trainer_phase(TRAIN_SMOKE_ARGS)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
