#!/usr/bin/env python3
"""Benchmark entry point.  From the root of a checkout:

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix; the configuration file
(``bench/configs/<config>.json``) names the driver that runs it
(``bench/drivers/<driver>.py``) and, by its ``model_type``, the adapter
that holds what is its architecture's (``bench/arch/<model_type>.py``:
the program's model, the plain reference, the counts of operations and
bytes); the mix is ``bench/traffic/<mix>.json``;
the limits of the correctness comparison are ``bench/limits/<cell>.json``;
and each metric is computed by its own reader, ``bench/metrics/<metric>.py``.
A reader that finds nothing to read returns None and the metric is left out.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read partly from a profiler trace of
the end of the window (``bench/trace.py``: device time by module and
program scope, module executions, the program's spans) and from the
program's own counters.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``; ``checks`` comes last); the last lines of standard
error repeat each compared number beside its limit.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: JAX's persistent compile cache: a fixed directory inside the checkout,
#: unless the environment names one (JAX_COMPILATION_CACHE_DIR)
CACHE_DIR = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(ROOT, ".jax_cache"))
OUT_DIR = os.path.join(ROOT, "bench_out")


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    manifest: dict


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    manifest = _json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in manifest["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    (conf,) = [c for c in manifest["configs"] if c["name"] == w["config"]]
    bench = os.path.join(root, "bench")
    return Cell(name=name, workload=w,
                config=_json(os.path.join(root, conf["file"])),
                traffic=_json(os.path.join(bench, "traffic",
                                           w["traffic"] + ".json")),
                limits=_json(os.path.join(bench, "limits", name + ".json")),
                manifest=manifest)


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(cell: Cell, trace: bool) -> list:
    """The manifest's metric entries this cell reports in this mode."""
    e2e = [m for m in cell.manifest["end_to_end"]
           if cell.name in m.get("workloads", [cell.name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in cell.manifest["per_layer"]
            if m["moves"] in moved
            and cell.name in m.get("workloads", [cell.name])]


def read_metric(name: str, run, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    return _load(path, "bench_metric_" + name.replace(".", "_")
                 .replace("-", "_")).read(run)


def _devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    found = (f"platform {devs[0].platform!r} ({devs[0].device_kind}, "
             f"{len(devs)} devices)")
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {found}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found "
                         f"{found}")
    return devs[:chips]


def prepare(cell: Cell, require_chip: bool = True):
    """Check the chips, point JAX's compile cache into the checkout, and
    return (the devices the cell uses, its driver module)."""
    import jax
    from bench import peaks
    devices = _devices(int(cell.workload["chips"]), require_chip)
    if require_chip:
        peaks.peaks_for(devices[0].device_kind)   # unknown kind: an error
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = importlib.import_module(
        "bench.drivers." + cell.config["bench"]["driver"])
    return devices, driver


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_start: float = T_START,
             root: str = ROOT) -> dict:
    """Run ``cell`` once and return the result object (not printed)."""
    from bench import trace as trace_mod
    devices, driver = prepare(cell, require_chip)
    out_dir = os.path.join(OUT_DIR, "trace")
    shutil.rmtree(out_dir, ignore_errors=True)
    run = driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start, out_dir=out_dir, devices=devices)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed}
    if trace:
        prof = trace_mod.read_profile(
            out_dir, host_ops=not require_chip,
            scope_maps=getattr(run, "scope_maps", None))
        span = [(s, e) for n, s, e in prof.spans if n == "bench.window"]
        ops = [t for v in prof.device_ops.values() for _, a, b in v
               for t in (a, b)]
        print(f"trace: device lines {prof.device_lines}; window {span}; "
              f"device ops from {min(ops, default=None)} to "
              f"{max(ops, default=None)}", file=sys.stderr, flush=True)
        run.trace = trace_mod.summarize(prof)
        shutil.rmtree(out_dir, ignore_errors=True)
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
    metrics = {}
    for m in cell_metrics(cell, trace):
        v = read_metric(m["name"], run, root)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = dev
    if trace:
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["window"] = {"compiles": run.compiles_in_window,
                        "faults": run.faults, "requeues": run.requeues,
                        "reference_s": run.reference_s}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in run.checks.items()}
    return result


def add_paths() -> None:
    """The checkout's ``bench`` package and the program under ``src``."""
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    add_paths()
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (ImportError, OSError, KeyError, ValueError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    w = result["window"]
    print(f"in the window: {w['compiles']} compiles, {w['faults']} scheduler "
          f"faults, {w['requeues']} requeues; the comparison took "
          f"{w['reference_s']:.1f} s", flush=True)
    print(json.dumps(result), flush=True)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
