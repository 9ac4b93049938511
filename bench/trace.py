"""Reduction of a profiler trace to the benchmark's device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but JAX
(``jax.profiler.ProfileData``).  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane; a trace without them
is an error.  Only the tests, which record a trace on the CPU, ask for the
host events that carry an ``hlo_op`` stat instead.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, whose names start with ``bench.``;
the span named ``bench.window`` marks the traced window.

* busy: the union of a device's operation intervals inside the window,
  averaged over the devices;
* idle share: 1 - busy / window;
* ``device_ops``: the operations that took most device time (seconds summed
  over the window, averaged over the devices), by the HLO name the trace
  gives (a ``while`` op's time includes the ops of its body);
* ``idle_gaps``: device idle time inside the window, summed by the innermost
  benchmark span that covered the middle of each gap (what the host was
  doing), averaged over the devices.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "outside any bench span"


def merge(intervals) -> list:
    """Sorted, non-overlapping union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(merged, lo, hi) -> list:
    """The parts of [lo, hi] not covered by ``merged`` (sorted, disjoint)."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class Profile:
    """Events of one trace, times in nanoseconds on the trace's clock."""
    device_ops: dict = field(default_factory=dict)   # device -> [(name, s, e)]
    spans: list = field(default_factory=list)        # [(name, s, e)]
    device_lines: list = field(default_factory=list)  # [(plane, line, n)]


def _op_name(name: str) -> str:
    """``%fusion.10 = f32[...] fusion(...)`` -> ``fusion.10``: the TPU trace
    names an op by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def read_profile(trace_dir: str, host_ops: bool = False) -> Profile:
    """The newest ``.xplane.pb`` under ``trace_dir``, as a ``Profile``.
    Raises when it holds no TPU operation, unless ``host_ops`` (the CPU
    tests) takes the host's operations in their place."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    prof = Profile()
    on_host = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                evs = list(line.events)
                prof.device_lines.append((plane.name, line.name, len(evs)))
                if line.name == "XLA Ops":
                    ops += [(_op_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns) for ev in evs]
            if ops:
                prof.device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name.startswith(SPAN_PREFIX):
                        prof.spans.append((ev.name, s, e))
                    elif "hlo_op" in _stats(ev):
                        on_host.append((ev.name, s, e))
    if host_ops and not prof.device_ops and on_host:
        prof.device_ops["/host:CPU"] = on_host
    if not prof.device_ops:
        raise ValueError("trace holds no TPU 'XLA Ops' events; device "
                         f"lines: {prof.device_lines}")
    return prof


@dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over devices
    devices: int
    device_ops: list              # [[name, seconds]], at most 10
    idle_gaps: list               # [[host span, seconds]], at most 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _innermost(spans, t) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and name != WINDOW_SPAN:
            if best is None or (e - s) < (best[2] - best[1]):
                best = (name, s, e)
    return best[0] if best else NO_SPAN


def summarize(prof: Profile, top: int = 10) -> Summary:
    """Reduce ``prof`` over its ``bench.window`` span (the whole trace when
    there is none)."""
    win = [(s, e) for n, s, e in prof.spans if n == WINDOW_SPAN]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        every = [t for ops in prof.device_ops.values() for _, s, e in ops
                 for t in (s, e)]
        if not every:
            raise ValueError("trace holds no device operation")
        lo, hi = min(every), max(every)
    n_dev = max(len(prof.device_ops), 1)
    busy = 0.0
    op_time: dict = defaultdict(float)
    gap_time: dict = defaultdict(float)
    spans = sorted(prof.spans, key=lambda x: x[1])
    for ops in prof.device_ops.values():
        merged = clip(merge((s, e) for _, s, e in ops), lo, hi)
        busy += total(merged)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] += d
        for s, e in gaps(merged, lo, hi):
            gap_time[_innermost(spans, (s + e) / 2)] += e - s
    ns = 1e-9
    rank = lambda d: [[k, v * ns / n_dev] for k, v in   # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Summary(window_s=(hi - lo) * ns, busy_s=busy * ns / n_dev,
                   devices=len(prof.device_ops), device_ops=rank(op_time),
                   idle_gaps=rank(gap_time))
