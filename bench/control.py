#!/usr/bin/env python3
"""The control study of a serving cell, on the chip, in one process:

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed it makes a whole run of the cell at the cell's own load (the
window as given), and on the requests that the run's comparison sampled
reads how the served tokens' gaps below the float32 reference's best are
spread, and the cell's end-to-end metrics.  Beside them it reads the
control: at each position of the same prompts and served tokens, the gap
of the token that the reference puts first when computed in a lower
precision: int8 or fp8 weights alone (``int8-weights``, ``fp8-weights``),
or every operand of every projection, weights and activations (``int8``,
``fp8``), always with float32 accumulation.  With ``--witness-eps`` it
also reads the program's gaps against the reference at that RMSNorm
epsilon.  One JSON line per seed.  The limits in
``bench/limits/<cell>.json`` are set from these readings: above the
program's largest, below the control's smallest.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402

#: gap thresholds of the shares read (the share of served positions whose
#: gap is above each)
THRESHOLDS = (0.0, 0.01, 0.02, 0.05, 0.1, 0.25, 0.5)


def spread(g) -> dict:
    """How a set of gaps is spread."""
    import numpy as np
    g = np.asarray(g, np.float64)
    out = {"n": int(g.size), "mean": float(g.mean()),
           "mean_clip1": float(np.minimum(g, 1.0).mean()),
           "p90": float(np.percentile(g, 90)),
           "p99": float(np.percentile(g, 99)), "max": float(g.max())}
    for t in THRESHOLDS:
        out[f"share_gt_{t:g}"] = float((g > t + 1e-6).mean())
    return out


def study(quants: tuple, witness_eps):
    """An ``inspect`` hook for the serving driver; it fills ``readings``
    with the spread of each set of gaps and ``raw`` with the gaps, read from
    the reference module the driver hands it (the cell's adapter's)."""
    import numpy as np
    readings, raw = {}, {}

    def read(name, parts):
        raw[name] = np.concatenate(parts)
        readings[name] = spread(raw[name])

    def inspect(model, w, dm, prompts, outs):
        ref = model.served_logits(w, dm, prompts, outs)
        read("program", model.served_gaps(w, dm, prompts, outs, ref=ref))
        if witness_eps is not None:
            dw = dataclasses.replace(dm, eps=witness_eps)
            read(f"program_eps_{witness_eps:g}",
                 model.served_gaps(w, dw, prompts, outs))
        for q in quants:
            read(f"control_{q}", model.served_gaps(
                w, dm, prompts, outs, quant=q, ref=ref))

    return inspect, readings, raw


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--quants", default="int8-weights,fp8-weights,int8,fp8")
    ap.add_argument("--witness-eps", type=float, default=None)
    ap.add_argument("--embed-std", type=float, default=None,
                    help="draw the embedding at this scale instead of the "
                         "configuration's")
    ap.add_argument("--dump", default=None,
                    help="directory for each seed's gaps (<seed>.npz)")
    args = ap.parse_args(argv)
    run.add_paths()
    cell = run.load_cell(args.workload)
    if args.embed_std is not None:
        cell.config["bench"]["embed_std"] = args.embed_std
    devices, driver = run.prepare(cell)
    quants = tuple(q for q in args.quants.split(",") if q)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        inspect, readings, raw = study(quants, args.witness_eps)
        rec = driver.run(cell, seed=seed, seconds=args.seconds, trace=False,
                         t_start=t, out_dir=run.OUT_DIR, devices=devices,
                         inspect=inspect)
        print(json.dumps({"seed": seed, "correct": rec.correct,
                          "failed": rec.failed, "attempted": rec.attempted,
                          "faults": rec.faults,
                          "memory_peak_bytes": rec.memory_peak_bytes,
                          "checks": {k: v for k, (v, _) in
                                     rec.checks.items()},
                          "readings": readings,
                          "metrics": {m["name"]: run.read_metric(m["name"],
                                                                 rec)
                                      for m in run.cell_metrics(cell,
                                                                False)},
                          "reference_s": rec.reference_s,
                          "seconds": time.perf_counter() - t}), flush=True)
        if args.dump:
            import numpy as np
            os.makedirs(args.dump, exist_ok=True)
            np.savez(os.path.join(args.dump, f"{seed}.npz"), **raw)
    return 0


if __name__ == "__main__":
    sys.exit(main())
