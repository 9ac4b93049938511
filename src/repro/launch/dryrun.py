import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) combo.

The three lines above MUST stay first — jax locks the platform and device
count at first init, and the production meshes need 512 placeholder CPU
devices (pinned to the CPU: on a TPU host the dry-run must not take the
chips).  Smoke tests and benches do NOT import this module (they see 1
device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape train_4k [--multi-pod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Per combo it writes JSON with memory_analysis, cost_analysis, the collective
schedule and the roofline terms (docs/DESIGN.md §Dry-run / §Roofline read
these).
"""

import argparse
import json
import time
import traceback


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            chunks=None, strategy="auto", tag="", flags=None) -> dict:
    import jax
    from repro.configs.base import TPU_V5E
    from repro.launch import dryrun_lib as lib
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "status": "ok",
           "tag": tag}
    try:
        lowered, meta = lib.lower_combo(arch, shape_name, mesh, chunks=chunks,
                                        strategy=strategy, flags=flags)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        rec.update(meta)
        rec.update(lib.analyse(lowered, compiled, TPU_V5E, chips))
        rec["t_lower_s"] = round(t_lower, 1)
        rec["t_compile_s"] = round(t_compile, 1)
        mem = rec["memory"]
        print(f"[ok] {arch} x {shape_name} x {rec['mesh']}: "
              f"peak/device {mem['peak_device_gb']:.2f} GB, "
              f"dominant={rec['roofline']['dominant']}, "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s", flush=True)
        print(f"     memory_analysis: args {mem['argument_bytes']/1e9:.2f} GB + "
              f"temp {mem['temp_bytes']/1e9:.2f} GB", flush=True)
        print(f"     cost_analysis: {rec['cost']['flops_per_device']:.3e} "
              f"FLOPs/dev, {rec['cost']['bytes_per_device']:.3e} B/dev, "
              f"coll {rec['collectives']['total_bytes']/1e9:.3f} GB/dev", flush=True)
    except lib.SkipCombo as e:
        rec.update(status="skipped", reason=str(e))
        print(f"[skip] {arch} x {shape_name}: {e}", flush=True)
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[ERR] {arch} x {shape_name} x {rec['mesh']}: {e}", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "_mp" if multi_pod else ""
        suffix += f"_{tag}" if tag else ""
        path = os.path.join(out_dir, f"{arch}__{shape_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> None:
    from repro.configs.base import SHAPES, registry

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--chunks", type=int, default=None)
    ap.add_argument("--strategy", default="auto")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--flag", action="append", default=[],
                    help="optimization knob, e.g. --flag seq_shard_acts=1 "
                         "--flag prefill_chunks=8 --flag opt_shard_data=1")
    args = ap.parse_args()
    flags = {}
    for kv in args.flag:
        k, _, v = kv.partition("=")
        flags[k] = int(v) if v.lstrip("-").isdigit() else v

    if args.all:
        combos = [(a, s) for a in sorted(registry()) for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]
    n_ok = n_fail = 0
    for arch, shape in combos:
        rec = run_one(arch, shape, args.multi_pod, args.out,
                      chunks=args.chunks, strategy=args.strategy, tag=args.tag,
                      flags=flags)
        n_ok += rec["status"] in ("ok", "skipped")
        n_fail += rec["status"] == "error"
    print(f"done: {n_ok} ok/skipped, {n_fail} failed", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
