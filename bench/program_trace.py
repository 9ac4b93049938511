#!/usr/bin/env python3
"""The program's own instrumentation in a profiler trace, and the per-layer
numbers it gives.  A tool for runs on the TPU, like ``bench/control.py``:

  python3 bench/program_trace.py --workload <cell> --seeds 1,2,3 --trace 1

For each seed it makes a whole run of the cell through the cell's driver
(``bench/drivers/<driver>.py``) and prints one JSON line: the cell's
end-to-end and per-layer metrics as the harness reads them, and with
``--trace 1`` the numbers below.  It reads the trace once: what
``bench/trace.py`` reads (the benchmark's own spans, the device ops by HLO
name) and, beside that:

* host spans whose names start with ``serve.`` (the scheduler's phases,
  ``repro/serving/scheduler.py``), with their arguments;
* for each device op, the module it ran in (``jit_<function>``: the TPU's
  ``XLA Modules`` line, the ``hlo_module`` stat on the CPU) and its program
  scopes (``jax.named_scope`` names in its ``op_name``).  Neither backend's
  op events carry the ``op_name`` (the line's ``device.op_stat_keys`` lists
  what they carry), so it is read from the compiled HLO text of the serving
  steps (``hlo_scopes``, ``serving_scope_maps``), compiled again after the
  window;
* each module's executions.

The numbers (``metrics``):

* ``decode_wave_device_ms``: median device duration of a decode-wave
  execution (modules ``jit_serve_decode_wave*``);
* ``decode_moe_device_ms``, ``decode_attn_device_ms``,
  ``decode_cache_device_ms``: the union of the device intervals of the
  decode-wave ops under ``moe``, under ``attention``, and under
  ``paged_gather`` or ``paged_scatter``, over the number of waves;
* ``prefill_device_mfu``: model FLOPs of the prefill chunks (the
  ``serve.prefill_chunk`` spans' ``start``/``stop``/``length``) over the
  device time of the prefill and extend executions, over the chip's bf16
  peak, in %;
* ``step_host_ms``: median over ``serve.step`` spans of the span minus the
  part its ``serve.fetch`` children cover (the scheduler's self time);
* ``queue_wait_p90_ms``: 90th percentile over the requests due in the
  window of admission (``Request.t_admit``) minus submission; one never
  admitted counts until the end of the drain.

Everything is read over the ``bench.window`` span, as ``bench/trace.py``
does.  An op event that straddles two scopes (a fusion) carries the scope
of its root op.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import math
import os
import re
import shutil
import sys
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import flops, peaks, trace  # noqa: E402

PROGRAM_PREFIX = "serve."
#: the program's ``jax.named_scope`` names, outermost first where they nest
SCOPES = ("attention", "moe", "router", "dispatch", "experts", "combine",
          "paged_gather", "paged_scatter", "lm_head")
DECODE_WAVE = "jit_serve_decode_wave"
PREFILL_MODULES = ("jit_serve_prefill_chunk", "jit_serve_extend_chunk")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=")
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_MODULE_RUN = re.compile(r"^(.*?)(?:\(\d+\))?$")


class Op(NamedTuple):
    name: str            # HLO instruction name, e.g. ``fusion.10``
    start: int           # ns, the trace's clock
    end: int
    module: str          # ``jit_<function>``, "" when unknown
    scopes: tuple        # the program scopes in its op_name, outer first


@dataclass
class ProgramProfile:
    """What one trace holds of the program, times in ns."""
    base: trace.Profile     # what ``bench.trace.read_profile`` reads of it
    spans: list = field(default_factory=list)  # [(name, s, e, stats)]
    ops: dict = field(default_factory=dict)    # device -> [Op]
    runs: dict = field(default_factory=dict)   # device -> [(module, s, e)]
    op_stat_keys: list = field(default_factory=list)  # on the op events


def scope_path(op_name: str) -> tuple:
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
        if part in SCOPES:
            out.append(part)
    return tuple(out)


def hlo_scopes(hlo_text: str) -> dict:
    """{instruction name: scope path} of a compiled module's HLO text, for
    traces whose op events carry no ``op_name``.  A fusion whose line names
    none takes the scope of its fused computation's root."""
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
            out[name] = scope_path(op.group(1))
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


def _module_at(runs: list, starts: list, t: int) -> str:
    """The module whose execution covers time ``t`` (``runs`` sorted by
    start, ``starts`` their starts; one device runs one module at a
    time)."""
    i = bisect.bisect_right(starts, t) - 1
    return runs[i][0] if i >= 0 and t < runs[i][2] else ""


def read_program(trace_dir: str, host_ops: bool = False,
                 scope_maps: dict | None = None) -> ProgramProfile:
    """The newest ``.xplane.pb`` under ``trace_dir``, read once: ``base``
    holds what ``bench.trace.read_profile`` would (and raises where it
    would), the rest the program's spans, modules and scopes.
    ``scope_maps`` ({module: ``hlo_scopes(...)``}) names the ops' scopes;
    ``host_ops`` takes the host's op events (the CPU tests)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    pp = ProgramProfile(base=trace.Profile())
    scope_maps = scope_maps or {}
    raw: dict = defaultdict(list)       # device -> [(name, s, e, stats)]
    on_host = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = list(line.events)
                pp.base.device_lines.append((plane.name, line.name, len(evs)))
                if line.name == "XLA Ops":
                    raw[plane.name] += [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         trace._stats(ev)) for ev in evs]
                elif line.name == "XLA Modules":
                    pp.runs.setdefault(plane.name, []).extend(
                        (_MODULE_RUN.match(ev.name).group(1), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name.startswith(trace.SPAN_PREFIX):
                        pp.base.spans.append((ev.name, s, e))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        pp.spans.append((ev.name, s, e, trace._stats(ev)))
                    elif host_ops:
                        st = trace._stats(ev)
                        if "hlo_op" in st:
                            on_host.append((ev.name, s, e, st))
    raw = {dev: evs for dev, evs in raw.items() if evs}
    if host_ops and not raw and on_host:
        raw["/host:CPU"] = on_host
        execs: dict = {}
        for _, s, e, st in on_host:
            key = (st.get("hlo_module", ""), st.get("run_id"))
            a, b = execs.get(key, (s, e))
            execs[key] = (min(a, s), max(b, e))
        pp.runs["/host:CPU"] = [(m, s, e) for (m, _), (s, e) in execs.items()]
    if not raw:
        raise ValueError("trace holds no TPU 'XLA Ops' events; device "
                         f"lines: {pp.base.device_lines}")
    for dev in pp.runs:
        pp.runs[dev].sort(key=lambda r: r[1])
    keys = set()
    for dev, evs in raw.items():
        runs = pp.runs.get(dev, [])
        starts = [r[1] for r in runs]
        ops = []
        for name, s, e, st in evs:
            keys.update(st)
            op = trace._op_name(name)
            module = st.get("hlo_module") or _module_at(runs, starts, s)
            ops.append(Op(op, s, e, module,
                          scope_maps.get(module, {}).get(op, ())))
        pp.ops[dev] = ops
        pp.base.device_ops[dev] = [(o.name, o.start, o.end) for o in ops]
    pp.op_stat_keys = sorted(keys)
    return pp


@dataclass
class ProgramSummary(trace.Summary):
    """``bench.trace.summarize`` over the program's names (device ops as
    ``<module>/<scope>/<op>``, idle gaps over the ``bench.*`` and
    ``serve.*`` spans), and what it lacks: the window in ns (the whole
    trace when there is no ``bench.window``), ``runs`` (module -> seconds
    of each execution begun in the window, every device), ``scoped``
    ((module, scope) -> {device: merged intervals in the window, ns}) and
    the ``serve.*`` ``spans`` begun in the window."""
    window: tuple = (-math.inf, math.inf)
    runs: dict = field(default_factory=dict)
    scoped: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)


def op_label(op: Op) -> str:
    """``<module>/<innermost scope>/<op>``, leaving out what is unknown."""
    return "/".join(p for p in (op.module, (op.scopes or ("",))[-1], op.name)
                    if p)


def reduce(pp: ProgramProfile, top: int = 10) -> ProgramSummary:
    """``bench.trace.summarize`` of ``pp`` with the ops named by
    ``op_label`` and the ``serve.*`` spans beside the ``bench.*`` ones,
    plus the module runs, scoped intervals and ``serve.*`` spans of its
    window."""
    labelled = trace.Profile(
        device_ops={dev: [(op_label(o), o.start, o.end) for o in ops]
                    for dev, ops in pp.ops.items()},
        spans=pp.base.spans + [(n, s, e) for n, s, e, _ in pp.spans])
    win = [(s, e) for n, s, e in pp.base.spans if n == trace.WINDOW_SPAN]
    lo, hi = ((min(s for s, _ in win), max(e for _, e in win)) if win
              else (-math.inf, math.inf))
    runs: dict = defaultdict(list)
    for dev_runs in pp.runs.values():
        for mod, s, e in dev_runs:
            if lo <= s < hi:
                runs[mod].append((min(e, hi) - s) * 1e-9)
    scoped: dict = defaultdict(dict)
    for dev, ops in pp.ops.items():
        by_scope = defaultdict(list)
        for op in ops:
            for sc in set(op.scopes):
                by_scope[(op.module, sc)].append((op.start, op.end))
        for k, iv in by_scope.items():
            scoped[k][dev] = trace.clip(trace.merge(iv), lo, hi)
    return ProgramSummary(
        **vars(trace.summarize(labelled, top)), window=(lo, hi),
        runs=dict(runs), scoped=dict(scoped),
        spans=[x for x in pp.spans if lo <= x[1] < hi])


# -- the numbers ------------------------------------------------------------

def _waves(ps: ProgramSummary) -> list:
    return [d for m, v in ps.runs.items() if m.startswith(DECODE_WAVE)
            for d in v]


def decode_wave_device_ms(ps: ProgramSummary):
    w = _waves(ps)
    return 1e3 * float(np.median(w)) if w else None


def scope_s(ps: ProgramSummary, module_prefix: str, scopes: tuple) -> float:
    """Seconds, mean over devices, of the union of the device intervals of
    the ops of modules ``module_prefix*`` under any of ``scopes`` (an op
    under two of them counts once)."""
    per_dev: dict = defaultdict(list)
    for (mod, sc), by_dev in ps.scoped.items():
        if mod.startswith(module_prefix) and sc in scopes:
            for dev, iv in by_dev.items():
                per_dev[dev] += iv
    return sum(trace.total(trace.merge(iv)) for iv in per_dev.values()) \
        * 1e-9 / max(ps.devices, 1)


def decode_scope_ms(ps: ProgramSummary, scopes: tuple):
    """Device time of the decode waves' ops under ``scopes`` per wave, in
    ms."""
    n = len(_waves(ps)) / max(ps.devices, 1)
    return 1e3 * scope_s(ps, DECODE_WAVE, scopes) / n if n else None


def prefill_device_mfu(ps: ProgramSummary, config: dict, device_kind: str,
                       chips: int = 1):
    """Model FLOPs of the window's prefill chunks over the device time of
    the prefill and extend executions, over the bf16 peak, in %."""
    work = 0.0
    for name, _, _, st in ps.spans:
        if name == "serve.prefill_chunk":
            start, stop, length = st["start"], st["stop"], st["length"]
            work += flops.prefill_flops(config, start, stop,
                                        logits=int(stop == length))
    dev_s = sum(d for m, v in ps.runs.items()
                if m.startswith(PREFILL_MODULES) for d in v)
    dev_s /= max(ps.devices, 1)
    if dev_s <= 0 or work <= 0:
        return None
    peak = peaks.peaks_for(device_kind).flops_bf16 * chips
    return 100.0 * work / dev_s / peak


def step_host_ms(ps: ProgramSummary):
    """Median over ``serve.step`` spans of the span less the union of its
    ``serve.fetch`` children."""
    fetches = sorted((s, e) for n, s, e, _ in ps.spans if n == "serve.fetch")
    out = []
    for n, s, e, _ in ps.spans:
        if n != "serve.step" or e > ps.window[1]:
            continue
        inside = [(a, b) for a, b in fetches if a >= s and b <= e]
        out.append((e - s) - trace.total(trace.merge(inside)))
    return 1e-6 * float(np.median(out)) if out else None


def queue_wait_p90_ms(run, admitted: dict):
    """90th percentile over the requests due in ``run``'s window of the
    admission time (``admitted``: rid -> absolute host-clock time) less the
    submission time; one never admitted counts until the end of the
    drain.  None when the program stamps no admission at all."""
    if not admitted:
        return None
    waits = [admitted.get(rid, run.drain_end) - run.submitted[rid]
             for rid in run.in_window if rid in run.submitted]
    return 1e3 * float(np.percentile(waits, 90)) if waits else None


def metrics(ps: ProgramSummary, run, admitted: dict) -> dict:
    """Each of the numbers above that the trace and the run hold."""
    out = {
        "decode_wave_device_ms": decode_wave_device_ms(ps),
        "decode_moe_device_ms": decode_scope_ms(ps, ("moe",)),
        "decode_attn_device_ms": decode_scope_ms(ps, ("attention",)),
        "decode_cache_device_ms": decode_scope_ms(
            ps, ("paged_gather", "paged_scatter")),
        "prefill_device_mfu": prefill_device_mfu(
            ps, run.config, run.device_kind, run.chips),
        "step_host_ms": step_host_ms(ps),
        "queue_wait_p90_ms": queue_wait_p90_ms(run, admitted),
    }
    return {k: v for k, v in out.items() if v is not None}


# -- the command line ---------------------------------------------------------

def serving_scope_maps(server) -> dict:
    """{module: ``hlo_scopes``} of the decode wave and the prefill and
    extend chunks of a built serving driver's scheduler, compiled again at
    the shapes the cell runs (the persistent compile cache answers)."""
    import jax
    import jax.numpy as jnp
    from repro.serving import engine
    sched, sv = server.sched, server.sv
    pool, n = sched.pool, sv["max_slots"]
    inputs = pool._wave_inputs([None] * n, np.zeros((n,), np.int32),
                               np.zeros((n, 1, 1), np.int32))
    tok = jax.ShapeDtypeStruct((1, sv["prefill_chunk"]), jnp.int32)
    prefill = engine.get_prefill_fn(sched.cfg, sched.ctx, sv["cache_len"])
    extend = engine.get_extend_step(sched.cfg, sched.ctx)
    cache = jax.eval_shape(prefill, sched.params, {"tokens": tok})[1]
    lowered = {
        "jit_serve_decode_wave": pool._decode.lower(
            sched.params, pool.pools, *inputs),
        "jit_serve_prefill_chunk": prefill.lower(sched.params,
                                                 {"tokens": tok}),
        "jit_serve_extend_chunk": extend.lower(sched.params, cache, tok),
    }
    return {m: hlo_scopes(low.compile().as_text())
            for m, low in lowered.items()}


def _capture(driver, scope_maps: bool) -> dict:
    """Wrap the driver's ``Server.serve`` so that, when the window's loop
    returns, the program's admission stamps (on the driver's clock) and,
    once, the serving steps' scope maps are kept for the reading."""
    kept = {"admitted": {}, "scope_maps": {}, "maps_error": None}
    real = driver.Server.serve

    def serve(self, gen, rec, *a, **kw):
        reqs = real(self, gen, rec, *a, **kw)
        kept["admitted"] = {rid: self.t0 + r.t_admit
                            for rid, r in reqs.items()
                            if getattr(r, "t_admit", None) is not None}
        if scope_maps and not kept["scope_maps"] \
                and kept["maps_error"] is None:
            try:
                kept["scope_maps"] = serving_scope_maps(self)
            except Exception as e:      # the spans' numbers still read
                kept["maps_error"] = f"{type(e).__name__}: {e}"
        return reqs

    driver.Server.serve = serve
    return kept


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
    kept = _capture(driver, traced)
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
            pp = read_program(out_dir, scope_maps=kept["scope_maps"])
            rec.trace = trace.summarize(pp.base)
            try:
                ps = reduce(pp, top=40)
                line["program"] = metrics(ps, rec, kept["admitted"])
                line["device"] = {
                    "busy_s": ps.busy_s, "window_s": ps.window_s,
                    "waves": len(_waves(ps)),
                    "lines": pp.base.device_lines,
                    "op_stat_keys": pp.op_stat_keys,
                    "modules": sorted({m for v in pp.runs.values()
                                       for m, _, _ in v}),
                    "scope_maps": {m: len(v) for m, v in
                                   kept["scope_maps"].items()},
                    "scope_maps_error": kept["maps_error"]}
                line["breakdown"] = {"device_ops": ps.device_ops[:15],
                                     "idle_gaps": ps.idle_gaps,
                                     "bench_idle_gaps": rec.trace.idle_gaps}
            except Exception as e:          # keep the harness's numbers
                line["program_error"] = f"{type(e).__name__}: {e}"
        else:
            line["program"] = {"queue_wait_p90_ms": queue_wait_p90_ms(
                rec, kept["admitted"])}
        line["metrics"] = {n: run.read_metric(n, rec) for n in names}
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    shutil.rmtree(out_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
