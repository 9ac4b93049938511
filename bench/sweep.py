#!/usr/bin/env python3
"""Knee sweep of a serving cell, on the chip, in one process:

  python3 bench/sweep.py --workload <cell> --rates 0.8,1.0,1.2 \\
      --seconds 40 --seed <n>

The server is built once; for each offered rate the scheduler is reset,
the mix's warm-up requests are served again, and the mix runs open-loop at
that rate (its lead-in, window and drain).  One JSON line per rate: time to
first token over the first and the last third of the window, the requests
still without a first token when the window closed (the backlog), and the
output tokens per second.  A rate is sustained when the backlog stays near
zero and the last third's TTFT does not run away from the first third's.
The cell's rate is then fixed in its traffic file at about 0.8 of the
highest sustained rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def _p(values, q):
    import numpy as np
    return float(np.percentile(values, q)) if values else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run.add_paths()
    cell = run.load_cell(args.workload)
    devices, driver = run.prepare(cell)
    from bench import traffic
    c = cell.config
    server = driver.Server(c, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        gen = traffic.Traffic(mix, args.seed, args.seconds, c["vocab_size"],
                              server.sv["prefill_chunk"])
        server.sched.reset()
        server.warm(gen.warmup)
        rec = driver.ServeRun(config=c, seconds=args.seconds,
                              device_kind=devices[0].device_kind)
        server.serve(gen, rec, float(mix.get("lead_in_s", 0.0)),
                     float(mix["drain_s"]))
        w0, w1 = rec.window
        third = args.seconds / 3
        ttft = {rid: (st[0] if st else rec.drain_end) - rec.due[rid]
                for rid, st in rec.stamps.items() if rid in rec.in_window}
        early = [v for r, v in ttft.items() if rec.due[r] < w0 + third]
        late = [v for r, v in ttft.items() if rec.due[r] >= w1 - third]
        backlog = sum(1 for rid in rec.in_window
                      if not rec.stamps[rid] or rec.stamps[rid][0] >= w1)
        toks = sum(1 for st in rec.stamps.values() for t in st
                   if w0 <= t < w1)
        print(json.dumps({
            "rate": rate, "attempted": rec.attempted, "failed": rec.failed,
            "backlog_at_close": backlog, "faults": rec.faults,
            "ttft_p90_first_third_s": _p(early, 90),
            "ttft_p90_last_third_s": _p(late, 90),
            "ttft_p50_s": _p(list(ttft.values()), 50),
            "ttft_p90_s": _p(list(ttft.values()), 90),
            "tokens_per_s": toks / args.seconds,
            "steps": len(rec.steps),
            "memory_peak_bytes": driver._memory_peak(devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
