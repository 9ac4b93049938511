"""Beyond the paper's Table 4: the SAME comparison measured from the XLA
buffer assignment of the production-mesh dry-run (paper Model II, train_4k,
256 chips), not just the theoretical model.

Reads the cached sweep results when present; otherwise launches the dry-run
subprocess per chunk setting (c=1 Method 1 analogue, c=2, c=8).  Note the
CPU-backend bf16 legalization inflates absolute bytes ~2x vs TPU (docs/DESIGN.md);
the RATIOS are the result.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ARCH = "deepseek-mini-8l"
SHAPE = "train_4k"
OUT = "results/dryrun"


def _path(tag: str) -> str:
    suffix = f"_{tag}" if tag else ""
    return os.path.join(OUT, f"{ARCH}__{SHAPE}{suffix}.json")


def _ensure(chunks: int, tag: str) -> dict:
    p = _path(tag)
    if not os.path.exists(p):
        # the dry-run compiles on virtual CPU devices, never the accelerator
        env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
        subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", ARCH,
             "--shape", SHAPE, "--chunks", str(chunks), "--tag", tag,
             "--out", OUT],
            env=env, check=False, capture_output=True, timeout=900)
    with open(p) as f:
        return json.load(f)


def run() -> list[str]:
    recs = {}
    for chunks, tag in ((1, "c1"), (2, "c2"), (8, "c8")):
        try:
            recs[chunks] = _ensure(chunks, tag)
        except FileNotFoundError:
            return [f"compiled_memory,SKIPPED (dry-run unavailable for c={chunks})"]
    base = recs[1]["memory"]["temp_bytes"]
    lines = []
    for c, rec in sorted(recs.items()):
        t = rec["memory"]["temp_bytes"]
        lines.append(
            f"compiled_memory,{ARCH},{SHAPE},c={c},"
            f"temp_gb={t / 1e9:.1f},reduction_vs_c1={(1 - t / base) * 100:.1f}%")
    lines.append("compiled_memory,note=absolute_bytes_inflated_~2x_by_cpu_"
                 "bf16_legalization;ratios_are_the_result")
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
