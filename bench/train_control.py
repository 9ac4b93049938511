#!/usr/bin/env python3
"""The control study of a training cell, on the chip, in one process:

  python3 bench/train_control.py --workload <cell> --seeds 1,2,3 \
      [--variants int8,fp8,half_batch,no_exchange] [--seconds 0] \
      [--first-step] [--skew]

For each seed it makes a whole run of the cell (the window as given; the
readings need none, so 0 by default) and prints the program's numbers
beside their limits.  Then it puts each variant of the reference in the
program's place, from the same weights and batches, and reads the same
numbers against the float32 reference:

* ``int8``, ``fp8``: the reference computed with every operand of every
  projection rounded to that format (W8A8), the control;
* ``half_batch``: half of the batch left out, the mean taken over the rest;
* ``no_exchange``: the exchange between chips left out: a token routed to
  an expert on another chip gets nothing from it.

``--first-step`` runs each variant for the first step alone and reads only
the numbers of the first gradient (``grad_diff``, ``grad_norm_gap``) and
the first loss, by leaf: a third of the time.  ``--skew`` also reads how
the float32 router spreads batch 0's tokens over the experts and over the
chips that hold them, beside a batch of uniform ids of the same shape.

One JSON line per seed.  The limits in ``bench/limits/<cell>.json`` are
set from these readings: above the program's largest, below the smallest
of the control and of each fault that reads ten times the program's or
more.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402

VARIANTS = ("int8", "fp8", "half_batch", "no_exchange")


def as_program(variant: dict, refs: dict) -> dict:
    """A reference variant's readings under the program's leaf names."""
    def by_leaf(d):
        return {k: math.sqrt(sum(d[n] ** 2 for n in names))
                for k, names in refs.items()}
    return {"losses": variant["losses"], "grad1": by_leaf(variant["grad1"]),
            "change": by_leaf(variant["change"]), "refs": refs,
            "grad1_full": variant["grad1_full"]}


FIRST_STEP = ("grad_diff", "grad_norm_gap")


def study(variants: tuple, limits: dict, first_step: bool = False,
          skew_seed=None):
    """An ``inspect`` hook for the training driver; it fills ``readings``
    with each variant's numbers, and with the leaves that set the
    program's, read from the reference module the driver hands it (the
    cell's adapter's)."""
    import numpy as np
    from bench.drivers import train
    readings = {}

    def inspect(model, dm, weights, batches, args, prog, ref):
        readings["program"] = {"worst": worst(train.leaf_gaps(prog, ref,
                                                              limits))}
        if skew_seed is not None:
            readings["skew"] = skew(model, weights, batches[0][0], dm, args,
                                    skew_seed)
        if first_step:
            batches = batches[:1]
            args = dict(args, chunks=args["chunks"][:1])
        for v in variants:
            kw = {}
            b = batches
            if v in ("int8", "fp8"):
                kw["quant"] = v
            elif v == "half_batch":
                b = [(t[:len(t) // 2], lab[:len(lab) // 2])
                     for t, lab in batches]
            elif v == "no_exchange":
                kw["local_only"] = True
            else:
                raise ValueError(f"unknown variant {v!r}")
            out = model.train_steps(weights, b, dm, **args, **kw,
                                    keep_grad1=True)
            mine = as_program(out, prog["refs"])
            if first_step:
                gaps = train.leaf_gaps(mine, ref, limits)
                by_leaf = {k: worst(gaps)[k] for k in FIRST_STEP}
                readings[v] = {k: by_leaf[k][0][1] for k in FIRST_STEP}
                readings[v].update(
                    loss0=gaps["loss_rel_err"]["0"], worst=by_leaf,
                    grad_diff_median=float(np.median(
                        list(gaps["grad_diff"].values()))))
                continue
            checks, ok = train.compare(mine, ref, limits)
            readings[v] = {k: c[0] for k, c in checks.items()}
            readings[v]["passes"] = ok
            readings[v]["worst"] = worst(train.leaf_gaps(mine, ref, limits))

    return inspect, readings


def skew(model, weights, tokens, dm, args, seed: int) -> dict:
    """The float32 router's top-k shares of ``tokens`` (one batch) over the
    experts and over the chips that hold them (experts split evenly), and
    the same for uniform ids of that shape drawn from ``seed``: max / mean
    of each, and the experts' shares."""
    import numpy as np
    lp = weights["layers"][0]
    uniform = np.random.default_rng(seed).integers(
        0, dm.v, tokens.shape).astype(np.int32)
    out = {}
    for name, rows in (("batch0", tokens), ("uniform", uniform)):
        count = np.zeros(dm.e)
        for t in rows:
            x = model._embed(weights["embed"], t, dm, None)
            topk = np.asarray(model._attn_route(lp, x, dm, None)[3])
            count += np.bincount(topk.reshape(-1), minlength=dm.e)
        share = count / count.sum()
        chips = share.reshape(args["devices"], -1).sum(1)
        out[name] = {"expert_max_over_mean": float(share.max() * dm.e),
                     "chip_max_over_mean": float(chips.max() * len(chips)),
                     "shares": [float(v) for v in share]}
    return out


def worst(gaps: dict) -> dict:
    """Each number's per-leaf (per-step) gaps, largest first."""
    return {k: sorted(d.items(), key=lambda kv: -kv[1])
            for k, d in gaps.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--first-step", action="store_true")
    ap.add_argument("--skew", action="store_true")
    args = ap.parse_args(argv)
    run.add_paths()
    cell = run.load_cell(args.workload)
    devices, driver = run.prepare(cell)
    variants = tuple(v for v in args.variants.split(",") if v)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        inspect, readings = study(variants, cell.limits, args.first_step,
                                  seed if args.skew else None)
        rec = driver.run(cell, seed=seed, seconds=args.seconds, trace=False,
                         t_start=t, out_dir=run.OUT_DIR, devices=devices,
                         inspect=inspect)
        print(json.dumps({
            "seed": seed, "correct": rec.correct, "failed": rec.failed,
            "attempted": rec.attempted, "faults": rec.faults,
            "lead_in_steps": rec.lead_in_steps,
            "memory_peak_bytes": rec.memory_peak_bytes,
            "setup_s": rec.setup_s, "setup_parts": rec.setup_parts,
            "reference_s": rec.reference_s,
            "checks": {k: v for k, (v, _) in rec.checks.items()},
            "readings": readings,
            "metrics": {m["name"]: run.read_metric(m["name"], rec)
                        for m in run.cell_metrics(cell, False)},
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
