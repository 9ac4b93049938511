"""Measured tile autotuner for the Pallas kernels (docs/DESIGN.md §Autotune).

The tuning pass is separate from the kernels themselves (the
transformation-pass shape of DaCe's optimization layer): kernels declare
*which* tile names they consume and a heuristic default, and this module
owns *how* winners are found and remembered.

* **Search** — ``autotune`` times a caller-built kernel closure over a
  candidate tile grid with the paired-block methodology of
  ``benchmarks/pipeline_microbench.py``: candidates are timed interleaved
  in blocks (min over repeats within a block, median across blocks per
  candidate), so common-mode machine drift hits every candidate alike.
  Candidates that fail to compile/execute (e.g. VMEM overflow on a real
  TPU) are skipped, not fatal.  Because the kernels pad to any block size
  (kernels/tiling.py::choose_block), the space is a free grid — not just
  divisors.
* **Persistence** — winners are stored per ``(op, shape, dtype,
  device_kind)`` in ``tiles.json`` next to this module, a file the
  repository commits (``set_cache_path`` points a process elsewhere, as the
  tests do).  What a kernel compiles to therefore depends only on the
  checkout.  Every kernel in the package consults it through
  ``tiling.resolve_tiles`` at trace time; a missing or corrupt file falls
  back to the heuristic defaults — tuning is an optimization, never a
  correctness dependency.
"""

from __future__ import annotations

import json
import os
import statistics
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

#: the committed tile winners (absent until a chip run records some)
TILES_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tiles.json")

#: set_cache_path's override of TILES_FILE
_path: Optional[str] = None
#: lazily-loaded in-process view of the on-disk cache; reset by set_cache_path
_cache: Optional[dict] = None
_cache_from: Optional[str] = None


def cache_path() -> str:
    return _path or TILES_FILE


def set_cache_path(path: Optional[str]) -> None:
    """Point the process at a different cache file (tests, benchmarks).
    ``None`` restores the committed ``TILES_FILE``."""
    global _path, _cache, _cache_from
    _path = path
    _cache, _cache_from = None, None


def load_cache(path: Optional[str] = None) -> dict:
    """Read the JSON cache; a missing, unreadable or corrupt file is an
    empty cache (heuristic fallback), never an error."""
    path = path or cache_path()
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def save_cache(cache: dict, path: Optional[str] = None) -> None:
    path = path or cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind.replace(" ", "_")


def cache_key(op: str, shape: Sequence[int], dtype, kind: str | None = None) -> str:
    dname = getattr(dtype, "__name__", None) or getattr(dtype, "name", str(dtype))
    return "|".join([op, "x".join(str(int(s)) for s in shape), str(dname),
                     kind or device_kind()])


def _loaded() -> dict:
    global _cache, _cache_from
    path = cache_path()
    if _cache is None or _cache_from != path:
        _cache = load_cache(path)
        _cache_from = path
    return _cache


def lookup(op: str, shape: Sequence[int], dtype) -> Optional[dict]:
    """Cached winner tiles for this exact (op, shape, dtype, device), or
    None — the trace-time hook ``tiling.resolve_tiles`` calls."""
    entry = _loaded().get(cache_key(op, shape, dtype))
    return dict(entry["tiles"]) if isinstance(entry, dict) and "tiles" in entry \
        else None


def record(op: str, shape: Sequence[int], dtype, tiles: dict, *,
           time_ms: Optional[float] = None,
           baseline_ms: Optional[float] = None) -> None:
    """Persist a winner (and refresh the in-process view)."""
    cache = _loaded()
    cache[cache_key(op, shape, dtype)] = {
        "tiles": {k: int(v) for k, v in tiles.items()},
        "time_ms": time_ms, "baseline_ms": baseline_ms,
    }
    save_cache(cache)


# ---------------------------------------------------------------------------
# measured search
# ---------------------------------------------------------------------------

@dataclass
class AutotuneResult:
    op: str
    winner: dict                      # winning tile dict
    winner_ms: float
    baseline: Optional[dict]          # the heuristic candidate, if supplied
    baseline_ms: Optional[float]
    table: list = field(default_factory=list)   # [(tiles, median_ms)]
    skipped: list = field(default_factory=list)

    @property
    def speedup_vs_baseline(self) -> Optional[float]:
        if self.baseline_ms is None:
            return None
        return self.baseline_ms / self.winner_ms


def _min_time(fn: Callable, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(op: str, shape: Sequence[int], dtype,
             make_fn: Callable[..., Callable[[], object]],
             candidates: Sequence[dict], *, baseline: Optional[dict] = None,
             blocks: int = 3, repeats: int = 3,
             persist: bool = True) -> AutotuneResult:
    """Measure ``candidates`` and persist the winner for ``(op, shape,
    dtype, device)``.

    ``make_fn(**tiles)`` must return a zero-arg callable that runs the
    kernel to completion (compile + block_until_ready inside the callable's
    first invocation is fine — every candidate is warmed once before
    timing).  ``baseline`` (the heuristic tiling) is prepended to the
    candidate list when given, so the winner is *never slower than the
    heuristic on the measurements that chose it* — the autotuned >=
    heuristic guarantee the microbench asserts.
    """
    cands = list(candidates)
    if baseline is not None and baseline not in cands:
        cands.insert(0, dict(baseline))

    runnable: list[tuple[dict, Callable]] = []
    skipped: list[dict] = []
    for c in cands:
        try:
            fn = make_fn(**c)
            fn()                                   # compile + warm
            runnable.append((c, fn))
        except Exception:
            skipped.append(dict(c))
    if not runnable:
        raise RuntimeError(f"autotune({op}): no candidate ran")

    times: dict[int, list[float]] = {i: [] for i in range(len(runnable))}
    for _ in range(blocks):                        # interleaved: paired blocks
        for i, (_, fn) in enumerate(runnable):
            times[i].append(_min_time(fn, repeats))
    medians = [statistics.median(times[i]) for i in range(len(runnable))]
    win = min(range(len(runnable)), key=medians.__getitem__)

    base_ms = None
    if baseline is not None:
        for i, (c, _) in enumerate(runnable):
            if c == baseline:
                base_ms = medians[i] * 1e3
                break
    result = AutotuneResult(
        op=op, winner=dict(runnable[win][0]), winner_ms=medians[win] * 1e3,
        baseline=baseline, baseline_ms=base_ms,
        table=[(dict(c), m * 1e3) for (c, _), m in zip(runnable, medians)],
        skipped=skipped)
    if persist:
        record(op, shape, dtype, result.winner, time_ms=result.winner_ms,
               baseline_ms=base_ms)
    return result


def matmul_candidates(M: int, N: int, K: int, *,
                      sizes: Sequence[int] = (32, 64, 128, 256, 512),
                      cap: int = 24) -> list[dict]:
    """A bounded (bm, bn, bk) grid for matmul-shaped ops: every size <= the
    padded dim's next multiple, deduped, largest-first truncated to ``cap``
    (the search must stay cheap enough to run inside a microbench)."""
    def opts(dim):
        out = [s for s in sizes if s <= 2 * dim]
        return out or [min(sizes)]
    cands, seen = [], set()
    for bm in opts(M):
        for bn in opts(N):
            for bk in opts(K):
                key = (min(bm, 2 * M), min(bn, 2 * N), min(bk, 2 * K))
                if key in seen:
                    continue
                seen.add(key)
                cands.append({"bm": bm, "bn": bn, "bk": bk})
    cands.sort(key=lambda c: -(c["bm"] * c["bn"] * c["bk"]))
    return cands[:cap]
