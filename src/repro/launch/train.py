"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --smoke \
      --steps 50 --seq-len 128 --global-batch 8 [--no-mact] [--chunks 4]

On the CPU (``JAX_PLATFORMS=cpu``, what the tests run) train the ``--smoke``
reduced variants.  On a TPU the same launcher trains published widths:
``--layers N`` cuts only the depth, and ``--mesh host`` spreads the expert
group over every chip of the host, e.g. mixtral-8x7b on a 4-chip v5e:

  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
      --layers 1 --mesh host --seq-len 4096 --global-batch 4 --steps 3

``--mesh prod`` / ``prod-mp`` are the 256/512-chip pod meshes of the dry-run.
``python chip_smoke.py --chips 4`` drives the host-mesh path end to end.
"""

from __future__ import annotations

import argparse
import dataclasses
import json


def main(argv=None):
    """Parse ``argv`` (default: the command line), train, print the summary;
    returns the ``Trainer`` and its final state."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="train the reduced config (CPU-feasible)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers, keeping the "
                         "published widths (0 = the config's depth)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--chunks", type=int, default=1)
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="max FCDA schedule depth MACT may pick (>=2 overlaps "
                         "chunk all-to-alls with expert compute on the EP "
                         "path); with --no-mact, the fixed depth to run")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="force the sequential FCDA chunk loop")
    ap.add_argument("--no-mact", action="store_true")
    ap.add_argument("--adaptive-mact", action="store_true",
                    help="per-layer (bin, depth) schedules from the online "
                         "expert-load telemetry EMA (docs/DESIGN.md §Adaptive)")
    ap.add_argument("--replan-interval", type=int, default=1,
                    help="steps between adaptive MACT re-plans")
    ap.add_argument("--mact-hysteresis", type=float, default=0.1,
                    help="load-margin hysteresis band; a layer's schedule "
                         "only moves when the re-plan survives (1+h)x load "
                         "noise or memory safety forces it")
    ap.add_argument("--mact-headroom", type=float, default=0.2,
                    help="plan each layer for (1+this)*EMA load — the margin "
                         "that keeps a drifting layer's schedule ahead of "
                         "its load between re-plans")
    ap.add_argument("--placement", action="store_true",
                    help="telemetry-driven expert placement: re-home (and "
                         "with --placement-replicas, replicate) experts "
                         "across EP peers at replan boundaries "
                         "(docs/DESIGN.md §Placement)")
    ap.add_argument("--placement-replicas", type=int, default=0,
                    help="extra hot-expert weight slots per EP peer")
    ap.add_argument("--placement-hysteresis", type=float, default=0.1,
                    help="min fractional bottleneck improvement before a "
                         "layer's placement moves (anti-flapping)")
    ap.add_argument("--remat", default=None, choices=["none", "full", "memfine"])
    ap.add_argument("--mesh", default="local",
                    choices=["local", "host", "prod", "prod-mp"],
                    help="local: one device; host: (1, n) (data, model) over "
                         "the n devices present (experts sharded over all); "
                         "prod/prod-mp: the 256/512-chip pod meshes")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--fused", action="store_true",
                    help="single-launch fused MoE expert leg over the ragged "
                         "layout (kernels/fused_moe.py); MACT plans with the "
                         "reduced Eq. 2 term")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="self-healing restart: restore the newest VALID "
                         "checkpoint in --checkpoint-dir (corrupt saves are "
                         "skipped) and train until --steps total steps")
    ap.add_argument("--max-oom-retries", type=int, default=4,
                    help="degradation-ladder bound per step (docs/DESIGN.md "
                         "§Resilience)")
    ap.add_argument("--inject", default=None,
                    help="chaos faults, e.g. 'oom@3,burst@2x1.5,"
                         "ckpt_truncate@4' (kind@step[xMAG][*TIMES])")
    ap.add_argument("--log-json", default=None)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    from repro.configs import get_config
    from repro.core.moe import DistContext
    from repro.launch import mesh as meshes
    from repro.runtime.faults import FaultInjector
    from repro.training.trainer import Trainer

    if args.fused and jax.devices()[0].platform == "tpu":
        raise SystemExit("--fused: the fused MoE kernel does not compile for "
                         "the TPU (tests/test_tpu_compile.py)")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    if args.remat:
        cfg = dataclasses.replace(cfg, remat_policy=args.remat)

    mesh = None
    if args.mesh == "host":
        mesh = meshes.make_host_mesh()
    elif args.mesh != "local":
        mesh = meshes.make_production_mesh(multi_pod=args.mesh == "prod-mp")
    depth = 1 if args.no_pipeline else args.pipeline_depth
    ctx = DistContext(mesh=mesh, moe_chunks=args.chunks,
                      pipeline_chunks=depth if args.no_mact else 1,
                      use_pallas=args.use_pallas, moe_fused=args.fused)
    trainer = Trainer(cfg, ctx, seq_len=args.seq_len,
                      global_batch=args.global_batch, lr=args.lr,
                      use_mact=not args.no_mact,
                      max_pipeline_depth=depth,
                      adaptive_mact=args.adaptive_mact,
                      replan_interval=args.replan_interval,
                      mact_hysteresis=args.mact_hysteresis,
                      mact_headroom=args.mact_headroom,
                      use_placement=args.placement,
                      placement_replicas=args.placement_replicas,
                      placement_hysteresis=args.placement_hysteresis,
                      checkpoint_dir=args.checkpoint_dir,
                      checkpoint_every=args.checkpoint_every,
                      resume=args.resume,
                      max_oom_retries=args.max_oom_retries,
                      injector=(FaultInjector.from_string(args.inject)
                                if args.inject else None))
    state = trainer.fit(args.steps, verbose=True)
    if trainer.resumed_from is not None:
        print(f"resumed from checkpoint step {trainer.resumed_from}")
    if trainer.guard.escalations:
        print(f"OOM ladder: {len(trainer.guard.escalations)} escalation(s), "
              f"headroom now {trainer.mact_headroom:.2f}")
    if trainer.log:
        print(f"final loss {trainer.log[-1]['loss']:.4f} at step "
              f"{int(state.step)}; "
              f"chunk trace tail {trainer.chunk_trace[-8:]}; "
              f"pipeline trace tail {trainer.pipeline_trace[-8:]}")
    else:
        print(f"nothing to do: checkpoint already at step {int(state.step)} "
              f">= target {args.steps}")
    if args.placement and trainer.placement_trace:
        last = trainer.placement_trace[-1]
        imb = last["imbalance"]
        print(f"placement: {len(trainer.placement_trace)} replan(s), last "
              f"moved {last['migrated_slots']} slots "
              f"({last['migrated_bytes'] / 2**20:.1f} MiB), imbalance "
              f"{'n/a' if imb is None else f'{max(imb):.2f}'}")
    if args.adaptive_mact and trainer.schedule_trace:
        last = trainer.schedule_trace[-1]
        print(f"adaptive layer schedules (last plan): "
              f"{[tuple(s) for s in last]}; "
              f"compiles {trainer.compile_count}")
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump(trainer.log, f, indent=1)
    return trainer, state


if __name__ == "__main__":
    main()
