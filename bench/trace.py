"""Reduction of a profiler trace to the benchmark's device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but JAX
(``jax.profiler.ProfileData``), once.  Device operations are the events of
the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; a trace without them
is an error.  Only the tests, which record a trace on the CPU, ask for the
host events that carry an ``hlo_op`` stat instead.  Host spans are the
benchmark's own ``jax.profiler.TraceAnnotation`` events, whose names start
with ``bench.``, and the program's, whose names start with ``serve.`` (the
scheduler's phases, with their arguments); the span named ``bench.window``
marks the traced window.

Each device op also carries the module it ran in (``jit_<function>``: the
TPU's ``XLA Modules`` line, the ``hlo_module`` stat on the CPU) and its
program scopes (``jax.named_scope`` names in its ``op_name``).  Neither
backend's op events carry the ``op_name``, so the scopes come from the
compiled HLO text of the program's steps (``hlo_scopes``), which the driver
hands over as ``scope_maps`` ({module: {instruction: scopes}}).  An op event
that straddles two scopes (a fusion) carries the scope of its root op.

Over the window:

* busy: the union of a device's operation intervals inside the window,
  averaged over the devices;
* idle share: 1 - busy / window;
* ``device_ops``: the operations that took most device time (seconds summed
  over the window, averaged over the devices), named
  ``<module>/<innermost scope>/<op>`` as far as these are known (a ``while``
  op's time includes the ops of its body);
* ``idle_gaps``: device idle time inside the window, summed by the innermost
  ``bench.*`` or ``serve.*`` span that covered the middle of each gap (what
  the host was doing), averaged over the devices;
* ``scoped``: device seconds per (module, scope), the union of the
  intervals of that module's ops under that scope, averaged over the
  devices;
* ``runs``: each module's executions begun in the window (their seconds,
  every device);
* ``spans``: the ``serve.*`` spans begun in the window.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import NamedTuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "serve."
NO_SPAN = "outside any bench span"
#: the program's ``jax.named_scope`` names, outermost first where they nest
#: (an architecture's adapter may name more: ``bench/arch``'s ``SCOPES``)
SCOPES = ("attention", "moe", "router", "dispatch", "experts", "combine",
          "paged_gather", "paged_scatter", "lm_head")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=")
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_MODULE_RUN = re.compile(r"^(.*?)(?:\(\d+\))?$")


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


# -- program scopes from the compiled HLO -------------------------------------

def scope_path(op_name: str, scopes: tuple = SCOPES) -> tuple:
    """The program scopes in ``op_name``, outermost first:
    ``jit(serve_decode_wave)/vmap()/while/body/moe/experts/dot_general``
    -> ``("moe", "experts")``.  Transform wrappers (``vmap(moe)``) are
    looked through; of a fused op's ``a;b`` names the first is read."""
    out = []
    for part in op_name.split(";")[0].split("/"):
        while True:
            m = re.fullmatch(r"\w+\((.*)\)", part)
            if not m:
                break
            part = m.group(1)
        if part in scopes:
            out.append(part)
    return tuple(out)


def hlo_scopes(hlo_text: str, scopes: tuple = SCOPES) -> dict:
    """{instruction name: scope path} of a compiled module's HLO text.  A
    fusion whose line names none takes the scope of its fused computation's
    root."""
    out, roots, calls = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        if op:
            out[name] = scope_path(op.group(1), scopes)
            if m.group(1) and comp is not None:
                roots[comp] = out[name]
        else:
            called = _HLO_CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    for name, comp in calls.items():
        if comp in roots:
            out[name] = roots[comp]
    return out


# -- reading a trace ----------------------------------------------------------

class Op(NamedTuple):
    name: str            # HLO instruction name, e.g. ``fusion.10``
    start: int           # ns, the trace's clock
    end: int
    module: str = ""     # ``jit_<function>``, "" when unknown
    scopes: tuple = ()   # the program scopes in its op_name, outer first


@dataclass
class Profile:
    """Events of one trace, times in nanoseconds on the trace's clock."""
    device_ops: dict = field(default_factory=dict)   # device -> [(name, s, e)]
    spans: list = field(default_factory=list)        # [(name, s, e)]
    device_lines: list = field(default_factory=list)  # [(plane, line, n)]
    ops: dict = field(default_factory=dict)          # device -> [Op]
    runs: dict = field(default_factory=dict)    # device -> [(module, s, e)]
    program_spans: list = field(default_factory=list)  # [(name, s, e, stats)]


def _op_name(name: str) -> str:
    """``%fusion.10 = f32[...] fusion(...)`` -> ``fusion.10``: the TPU trace
    names an op by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _module_at(runs: list, starts: list, t: int) -> str:
    """The module whose execution covers time ``t`` (``runs`` sorted by
    start, ``starts`` their starts; one device runs one module at a
    time)."""
    i = bisect.bisect_right(starts, t) - 1
    return runs[i][0] if i >= 0 and t < runs[i][2] else ""


def read_profile(trace_dir: str, host_ops: bool = False,
                 scope_maps: dict | None = None) -> Profile:
    """The newest ``.xplane.pb`` under ``trace_dir``, as a ``Profile``.
    Raises when it holds no TPU operation, unless ``host_ops`` (the CPU
    tests) takes the host's operations in their place.  ``scope_maps``
    ({module: ``hlo_scopes(...)``}) names the ops' scopes."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    prof = Profile()
    scope_maps = scope_maps or {}
    raw: dict = defaultdict(list)       # device -> [(name, s, e, module)]
    on_host = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = list(line.events)
                prof.device_lines.append((plane.name, line.name, len(evs)))
                if line.name == "XLA Ops":
                    raw[plane.name] += [
                        (_op_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns, "") for ev in evs]
                elif line.name == "XLA Modules":
                    prof.runs.setdefault(plane.name, []).extend(
                        (_MODULE_RUN.match(ev.name).group(1), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name.startswith(SPAN_PREFIX):
                        prof.spans.append((ev.name, s, e))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        prof.program_spans.append((ev.name, s, e,
                                                   _stats(ev)))
                    elif host_ops:
                        st = _stats(ev)
                        if "hlo_op" in st:
                            on_host.append((ev.name, s, e, st))
    raw = {dev: evs for dev, evs in raw.items() if evs}
    if host_ops and not raw and on_host:
        raw["/host:CPU"] = [(_op_name(n), s, e, st.get("hlo_module", ""))
                            for n, s, e, st in on_host]
        execs: dict = {}
        for _, s, e, st in on_host:
            key = (st.get("hlo_module", ""), st.get("run_id"))
            a, b = execs.get(key, (s, e))
            execs[key] = (min(a, s), max(b, e))
        prof.runs["/host:CPU"] = [(m, s, e)
                                  for (m, _), (s, e) in execs.items()]
    if not raw:
        raise ValueError("trace holds no TPU 'XLA Ops' events; device "
                         f"lines: {prof.device_lines}")
    for dev in prof.runs:
        prof.runs[dev].sort(key=lambda r: r[1])
    for dev, evs in raw.items():
        runs = prof.runs.get(dev, [])
        starts = [r[1] for r in runs]
        ops = []
        for name, s, e, module in evs:
            module = module or _module_at(runs, starts, s)
            ops.append(Op(name, s, e, module,
                          scope_maps.get(module, {}).get(name, ())))
        prof.ops[dev] = ops
        prof.device_ops[dev] = [(o.name, o.start, o.end) for o in ops]
    return prof


# -- the reduction ------------------------------------------------------------

@dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over devices
    devices: int
    device_ops: list              # [[name, seconds]], at most ``top``
    idle_gaps: list               # [[host span, seconds]], at most ``top``
    window: tuple = (0, 0)        # (start, end), ns on the trace's clock
    scoped: dict = field(default_factory=dict)  # (module, scope) -> s
    runs: dict = field(default_factory=dict)    # module -> [s of each run]
    spans: list = field(default_factory=list)   # [(name, s, e, stats)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def scope_s(self, module_prefix: str, scopes: tuple) -> float:
        """Device seconds, mean over devices, of the ops of modules
        ``module_prefix*`` under each of ``scopes``, summed."""
        return sum(v for (mod, sc), v in self.scoped.items()
                   if mod.startswith(module_prefix) and sc in scopes)

    def executions(self, module_prefix: str) -> float:
        """Executions of modules ``module_prefix*`` begun in the window,
        mean over devices."""
        return sum(len(v) for m, v in self.runs.items()
                   if m.startswith(module_prefix)) / max(self.devices, 1)


def op_label(op: Op) -> str:
    """``<module>/<innermost scope>/<op>``, leaving out what is unknown."""
    return "/".join(p for p in (op.module, (op.scopes or ("",))[-1], op.name)
                    if p)


def _innermost(spans, times) -> list:
    """For each of ``times`` (ascending): the name of the shortest span of
    ``spans`` (sorted by start) that covers it, other than the window."""
    out, open_, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            name, s, e = spans[i]
            if name != WINDOW_SPAN:
                heapq.heappush(open_, (e - s, s, e, name))
            i += 1
        while open_ and open_[0][2] <= t:
            heapq.heappop(open_)
        out.append(open_[0][3] if open_ else NO_SPAN)
    return out


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
    ns = 1e-9
    busy = 0.0
    op_time: dict = defaultdict(float)
    gap_time: dict = defaultdict(float)
    scoped: dict = defaultdict(float)
    spans = sorted(prof.spans + [x[:3] for x in prof.program_spans],
                   key=lambda x: x[1])
    for dev, raw in prof.device_ops.items():
        ops = prof.ops.get(dev) or [Op(*o) for o in raw]
        merged = clip(merge((o.start, o.end) for o in ops), lo, hi)
        busy += total(merged)
        by_scope = defaultdict(list)
        for op in ops:
            d = min(op.end, hi) - max(op.start, lo)
            if d > 0:
                op_time[op_label(op)] += d
            for sc in set(op.scopes):
                by_scope[(op.module, sc)].append((op.start, op.end))
        for k, iv in by_scope.items():
            scoped[k] += total(clip(merge(iv), lo, hi)) * ns / n_dev
        idle = gaps(merged, lo, hi)
        for (s, e), name in zip(idle, _innermost(
                spans, [(s + e) / 2 for s, e in idle])):
            gap_time[name] += e - s
    runs: dict = defaultdict(list)
    for dev_runs in prof.runs.values():
        for mod, s, e in dev_runs:
            if lo <= s < hi:
                runs[mod].append((min(e, hi) - s) * ns)
    rank = lambda d: [[k, v * ns / n_dev] for k, v in   # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Summary(window_s=(hi - lo) * ns, busy_s=busy * ns / n_dev,
                   devices=len(prof.device_ops), device_ops=rank(op_time),
                   idle_gaps=rank(gap_time), window=(lo, hi),
                   scoped=dict(scoped), runs=dict(runs),
                   spans=[x for x in prof.program_spans if lo <= x[1] < hi])
