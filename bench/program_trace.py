#!/usr/bin/env python3
"""The program's own instrumentation in a profiler trace, and the per-layer
numbers the harness does not report yet.  A tool for runs on the TPU, like
``bench/control.py``:

  python3 bench/program_trace.py --workload <cell> --seeds 1,2,3 --trace 1

For each seed it makes a whole run of the cell through the cell's driver
(``bench/drivers/<driver>.py``) and prints one JSON line: the cell's
end-to-end and per-layer metrics as the harness reads them (``metrics``),
and with ``--trace 1`` the numbers below (``program``).  It reads the trace
as the harness does (``bench/trace.py``: device time by module and program
scope, module executions, the ``serve.*`` spans; the scope maps the serving
driver takes after the window), with the top 40 device ops.

The numbers (``program``):

* ``decode_wave_device_ms``: median device duration of a decode-wave
  execution (modules ``jit_serve_decode_wave*``);
* ``prefill_device_mfu``: model FLOPs of the prefill chunks (the
  ``serve.prefill_chunk`` spans' ``start``/``stop``/``length``; the
  configuration's adapter counts them) over the device time of the prefill
  and extend executions, over the chip's bf16 peak, in %;
* ``step_host_ms``: median over ``serve.step`` spans of the span minus the
  part its ``serve.fetch`` children cover (the scheduler's self time);
* ``queue_wait_p90_ms``: 90th percentile over the requests due in the
  window of admission (``Request.t_admit``) minus submission; one never
  admitted counts until the end of the drain.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import flops, peaks, trace  # noqa: E402
from bench.metrics import _serve  # noqa: E402

PREFILL_MODULES = ("jit_serve_prefill_chunk", "jit_serve_extend_chunk")


def _waves(s: trace.Summary) -> list:
    return [d for m, v in s.runs.items() if m.startswith(_serve.DECODE_WAVE)
            for d in v]


def decode_wave_device_ms(s: trace.Summary):
    w = _waves(s)
    return 1e3 * float(np.median(w)) if w else None


def prefill_device_mfu(s: trace.Summary, config: dict, device_kind: str,
                       chips: int = 1):
    """Model FLOPs of the window's prefill chunks over the device time of
    the prefill and extend executions, over the bf16 peak, in %; None where
    the configuration's adapter counts no FLOPs."""
    work = 0.0
    for name, _, _, st in s.spans:
        if name == "serve.prefill_chunk":
            start, stop, length = st["start"], st["stop"], st["length"]
            count = flops.prefill_flops(config, start, stop,
                                        logits=int(stop == length))
            if count is None:
                return None
            work += count
    dev_s = sum(d for m, v in s.runs.items()
                if m.startswith(PREFILL_MODULES) for d in v)
    dev_s /= max(s.devices, 1)
    if dev_s <= 0 or work <= 0:
        return None
    peak = peaks.peaks_for(device_kind).flops_bf16 * chips
    return 100.0 * work / dev_s / peak


def step_host_ms(s: trace.Summary):
    """Median over ``serve.step`` spans of the span less the union of its
    ``serve.fetch`` children."""
    fetches = sorted((a, b) for n, a, b, _ in s.spans if n == "serve.fetch")
    out = []
    for n, a, b, _ in s.spans:
        if n != "serve.step" or b > s.window[1]:
            continue
        inside = [(x, y) for x, y in fetches if x >= a and y <= b]
        out.append((b - a) - trace.total(trace.merge(inside)))
    return 1e-6 * float(np.median(out)) if out else None


def queue_wait_p90_ms(run):
    """90th percentile over the requests due in ``run``'s window of the
    admission time (``run.admitted``: rid -> absolute host-clock time) less
    the submission time; one never admitted counts until the end of the
    drain.  None when the program stamps no admission at all."""
    if not run.admitted:
        return None
    waits = [run.admitted.get(rid, run.drain_end) - run.submitted[rid]
             for rid in run.in_window if rid in run.submitted]
    return 1e3 * float(np.percentile(waits, 90)) if waits else None


def metrics(run) -> dict:
    """Each of the numbers above that the run and its trace hold."""
    s = run.trace
    out = {
        "decode_wave_device_ms": decode_wave_device_ms(s),
        "prefill_device_mfu": prefill_device_mfu(
            s, run.config, run.device_kind, run.chips),
        "step_host_ms": step_host_ms(s),
        "queue_wait_p90_ms": queue_wait_p90_ms(run),
    }
    return {k: v for k, v in out.items() if v is not None}


# -- the command line ---------------------------------------------------------

def main(argv=None) -> int:
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: the manifest's)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    run.add_paths()
    cell = run.load_cell(args.workload)
    seconds = args.seconds or float(cell.manifest["run_seconds"])
    devices, driver = run.prepare(cell)
    traced = bool(args.trace)
    out_dir = os.path.join(run.OUT_DIR, "program_trace")
    names = [m["name"] for m in cell.manifest["end_to_end"]
             + cell.manifest["per_layer"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        shutil.rmtree(out_dir, ignore_errors=True)
        t = time.perf_counter()
        rec = driver.run(cell, seed=seed, seconds=seconds, trace=traced,
                         t_start=t, out_dir=out_dir, devices=devices)
        line = {"workload": cell.name, "seed": seed, "trace": args.trace,
                "correct": rec.correct, "attempted": rec.attempted,
                "failed": rec.failed,
                "checks": {k: v for k, (v, _) in rec.checks.items()},
                "window": {"compiles": rec.compiles_in_window,
                           "faults": rec.faults, "requeues": rec.requeues},
                "memory_peak_bytes": rec.memory_peak_bytes}
        if traced:
            t_read = time.perf_counter()
            prof = trace.read_profile(out_dir,
                                      scope_maps=rec.scope_maps)
            rec.trace = s = trace.summarize(prof, top=40)
            line["program"] = metrics(rec)
            line["device"] = {
                "busy_s": s.busy_s, "window_s": s.window_s,
                "waves": len(_waves(s)), "lines": prof.device_lines,
                "modules": sorted(s.runs),
                "scope_maps": {m: len(v) for m, v in rec.scope_maps.items()},
                "read_s": time.perf_counter() - t_read}
            line["breakdown"] = {"device_ops": s.device_ops[:15],
                                 "idle_gaps": s.idle_gaps}
        else:
            line["program"] = {"queue_wait_p90_ms": queue_wait_p90_ms(rec)}
        line["metrics"] = {n: run.read_metric(n, rec) for n in names}
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    shutil.rmtree(out_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
