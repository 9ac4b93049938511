"""The reduction of the program's own spans, modules and scopes
(``bench/trace.py``) and the numbers read from it (the harness's readers,
``bench/program_trace.py``): each number on a profile made by hand, the
benchmark's own numbers unchanged by it, and a whole serving run at toy
widths traced on the CPU, reduced as the earlier per-scope reduction did
before the harness took the reduction over."""

import json
import math
import os
from collections import defaultdict

import numpy as np
import pytest

from bench import program_trace as pt
from bench import trace
from bench.drivers.serve import ServeRun
from bench.metrics import _serve

MS = 1e6            # ns per ms
DEV = "/device:TPU:0"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DECODE_READERS = ("decode_moe_device_ms", "decode_attn_device_ms",
                  "decode_cache_device_ms", "decode_expert_read_share")


def _tiny_cell():
    """The chat cell at toy widths (the files ``test_bench_cell.py`` runs)."""
    from bench import run

    def load(*parts):
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    manifest = load(ROOT, "BENCHMARK.json")
    name = "mixtral-l4.serve-chat"
    (work,) = [w for w in manifest["workloads"] if w["name"] == name]
    return run.Cell(name, work, load(HERE, "tiny-serve.json"),
                    load(HERE, "tiny-chat.json"),
                    load(HERE, "tiny-limits.json"), manifest)


def _old_profile():
    """The hand-made profile of ``test_bench_trace.py``."""
    return trace.Profile(
        device_ops={"/device:TPU:0": [("dot", 0, 40), ("add", 30, 50),
                                      ("dot", 70, 90)],
                    "/device:TPU:1": [("dot", 0, 100)]},
        spans=[("bench.window", 0, 100), ("bench.step", 0, 60),
               ("bench.wait", 60, 100)])


def _program(base, ops=None, spans=(), runs=None):
    """``base`` with the program's ops (default: ``base``'s, with no module
    or scope), spans and module runs."""
    ops = ops if ops is not None else {
        dev: [trace.Op(n, s, e) for n, s, e in v]
        for dev, v in base.device_ops.items()}
    return trace.Profile(
        device_ops={d: [(o.name, o.start, o.end) for o in v]
                    for d, v in ops.items()},
        spans=base.spans, ops=ops, runs=runs or {},
        program_spans=list(spans))


def _served(summary, **kw):
    return ServeRun(trace=summary, **kw)


def test_benchmark_numbers_unchanged_on_the_old_profile():
    base = _old_profile()
    old = trace.summarize(base)
    assert old.window_s == pytest.approx(100e-9)
    assert old.busy_s == pytest.approx((70 + 100) / 2 * 1e-9)
    assert old.idle_share == pytest.approx(0.15)
    assert old.idle_gaps == [["bench.wait", pytest.approx(15e-9)]]
    new = trace.summarize(_program(base))
    assert (new.window_s, new.busy_s, new.idle_share) == \
        (old.window_s, old.busy_s, old.idle_share)
    assert new.device_ops == old.device_ops
    assert new.idle_gaps == old.idle_gaps


def test_gap_under_a_program_span_in_a_bench_step():
    base = trace.Profile(
        device_ops={DEV: [("dot", 0, 20), ("dot", 50, 55), ("dot", 80, 100)]},
        spans=[("bench.window", 0, 100), ("bench.step", 0, 100)])
    spans = [("serve.step", 0, 100, {"step": 3}),
             ("serve.sample", 20, 50, {}), ("serve.admit", 55, 80, {})]
    s = trace.summarize(_program(base, spans=spans))
    # each gap goes to the innermost span at its middle
    assert dict(s.idle_gaps) == {"serve.sample": pytest.approx(30e-9),
                                 "serve.admit": pytest.approx(25e-9)}
    # without the program's spans both gaps fall under bench.step
    assert trace.summarize(base).idle_gaps == \
        [["bench.step", pytest.approx(55e-9)]]


def test_same_named_ops_of_two_modules_do_not_merge():
    base = trace.Profile(device_ops={DEV: [("fusion.10", 0, 30),
                                           ("fusion.10", 40, 50)]},
                         spans=[("bench.window", 0, 100)])
    ops = {DEV: [trace.Op("fusion.10", 0, 30, "jit_serve_decode_wave",
                          ("moe", "experts")),
                 trace.Op("fusion.10", 40, 50, "jit_serve_install", ())]}
    s = trace.summarize(_program(base, ops=ops))
    assert s.device_ops == [
        ["jit_serve_decode_wave/experts/fusion.10", pytest.approx(30e-9)],
        ["jit_serve_install/fusion.10", pytest.approx(10e-9)]]
    assert trace.summarize(base).device_ops == \
        [["fusion.10", pytest.approx(40e-9)]]


def test_step_self_time_subtracts_overlapping_fetches_once():
    base = trace.Profile(device_ops={DEV: [("dot", 0, 1)]},
                         spans=[("bench.window", 0, 100 * MS)])
    spans = [("serve.step", 0, 10 * MS, {}),
             ("serve.fetch", 2 * MS, 6 * MS, {}),
             ("serve.fetch", 4 * MS, 8 * MS, {}),     # overlaps the first
             ("serve.step", 20 * MS, 24 * MS, {}),
             ("serve.fetch", 21 * MS, 22 * MS, {}),
             ("serve.step", 30 * MS, 33 * MS, {}),
             ("serve.fetch", 40 * MS, 45 * MS, {})]   # outside every step
    s = trace.summarize(_program(base, spans=spans))
    # self times 10 - 6, 4 - 1 and 3: the median is 3 ms
    assert pt.step_host_ms(s) == pytest.approx(3.0)


def test_scope_union_over_waves():
    base = trace.Profile(device_ops={DEV: [("x", 0, 1)]},
                         spans=[("bench.window", 0, 100 * MS)])
    wave = "jit_serve_decode_wave"
    ops = {DEV: [
        # wave 1, 0-10 ms: a while op holding its body's ops
        trace.Op("while.14", 0, 9 * MS, wave, ()),
        trace.Op("fusion.1", 0, 2 * MS, wave, ("attention",)),
        trace.Op("fusion.2", 2 * MS, 7 * MS, wave, ("moe", "experts")),
        trace.Op("fusion.3", 6 * MS, 8 * MS, wave, ("moe", "combine")),
        trace.Op("fusion.4", 9 * MS, 10 * MS, wave, ("paged_scatter",)),
        # wave 2, 20-32 ms
        trace.Op("fusion.5", 20 * MS, 21 * MS, wave, ("paged_gather",)),
        trace.Op("fusion.1", 21 * MS, 23 * MS, wave, ("attention",)),
        trace.Op("fusion.2", 23 * MS, 29 * MS, wave, ("moe", "experts")),
        trace.Op("fusion.4", 29 * MS, 32 * MS, wave, ("paged_scatter",)),
        # a prefill chunk's moe is not a decode wave's
        trace.Op("fusion.9", 40 * MS, 60 * MS, "jit_serve_prefill_chunk",
                 ("moe",))]}
    runs = {DEV: [(wave, 0, 10 * MS), (wave, 20 * MS, 32 * MS),
                  ("jit_serve_prefill_chunk", 40 * MS, 60 * MS)]}
    s = trace.summarize(_program(base, ops=ops, runs=runs))
    assert s.executions(wave) == 2
    assert s.scoped[(wave, "moe")] == pytest.approx((6 + 6) * 1e-3)
    assert pt.decode_wave_device_ms(s) == pytest.approx(11.0)
    run = _served(s)
    assert _serve.decode_scope_ms(run, ("moe",)) == pytest.approx(
        (6 + 6) / 2)
    assert _serve.decode_scope_ms(run, ("attention",)) == pytest.approx(2.0)
    assert _serve.decode_scope_ms(run, ("paged_gather", "paged_scatter")) \
        == pytest.approx((1 + 1 + 3) / 2)
    # a scope no op carries gives no number, never 0
    assert _serve.decode_scope_ms(run, ("lm_head",)) is None


def test_prefill_flops_come_from_the_span_arguments(monkeypatch):
    from bench import flops, peaks
    monkeypatch.setitem(peaks.PEAKS, "toy", peaks.Peaks(1e12, 1e11, "toy"))
    cfg = {"model_type": "mixtral", "hidden_size": 64,
           "intermediate_size": 128, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_local_experts": 4, "num_experts_per_tok": 2,
           "vocab_size": 256}
    base = trace.Profile(device_ops={DEV: [("x", 0, 1)]},
                         spans=[("bench.window", 0, 100 * MS)])
    spans = [("serve.prefill_chunk", 1 * MS, 2 * MS,
              {"rid": 4, "start": 0, "stop": 32, "length": 64}),
             ("serve.prefill_chunk", 5 * MS, 6 * MS,
              {"rid": 4, "start": 32, "stop": 64, "length": 64}),
             # begun before the window: not counted
             ("serve.prefill_chunk", -3 * MS, 1, {"rid": 3, "start": 0,
                                                  "stop": 32, "length": 32})]
    runs = {DEV: [("jit_serve_prefill_chunk", 1 * MS, 2 * MS),
                  ("jit_serve_extend_chunk", 5 * MS, 8 * MS),
                  ("jit_serve_decode_wave", 9 * MS, 19 * MS)]}
    s = trace.summarize(_program(base, spans=spans, runs=runs))
    work = (flops.prefill_flops(cfg, 0, 32)
            + flops.prefill_flops(cfg, 32, 64, logits=1))
    assert pt.prefill_device_mfu(s, cfg, "toy") == \
        pytest.approx(100 * work / 4e-3 / 1e12)


def test_queue_wait_counts_an_unadmitted_request_to_the_drain():
    admitted = {0: 9.5, 1: 10.5, 2: 13.0, 3: 12.0}   # 4 never admitted
    run = ServeRun(window=(10.0, 20.0), drain_end=25.0,
                   in_window={1, 2, 3, 4}, admitted=admitted,
                   submitted={0: 9.0, 1: 10.0, 2: 11.0, 3: 12.0, 4: 13.0})
    waits_ms = [500.0, 2000.0, 0.0, 12000.0]
    assert pt.queue_wait_p90_ms(run) == \
        pytest.approx(float(np.percentile(waits_ms, 90)))
    # a program that stamps no admission gives no number
    run.admitted = {}
    assert pt.queue_wait_p90_ms(run) is None


def test_an_op_takes_the_module_running_around_it():
    runs = [("jit_a", 0, 10), ("jit_b", 10, 20), ("jit_c", 30, 40)]
    starts = [r[1] for r in runs]
    assert [trace._module_at(runs, starts, t)
            for t in (-1, 0, 9, 10, 25, 30, 39, 40)] == \
        ["", "jit_a", "jit_a", "jit_b", "", "jit_c", "jit_c", ""]


def test_scope_path_reads_through_transforms():
    assert trace.scope_path("jit(serve_decode_wave)/vmap()/while/body/"
                            "closed_call/moe/experts/jit(silu)/neg") == \
        ("moe", "experts")
    assert trace.scope_path("jit(f)/vmap(attention)/dot_general") == \
        ("attention",)
    assert trace.scope_path("jit(f)/add") == ()
    # an adapter's own scope names are read where it names them
    assert trace.scope_path("jit(f)/mla/dot_general") == ()
    assert trace.scope_path("jit(f)/mla/dot_general",
                            trace.SCOPES + ("mla",)) == ("mla",)
    text = ('%fused_computation.7 (param_0: f32[4]) -> f32[4] {\n'
            '  %param_0 = f32[4]{0} parameter(0)\n'
            '  ROOT %neg.2 = f32[4]{0} negate(%param_0), '
            'metadata={op_name="jit(f)/moe/experts/neg"}\n'
            '}\n\n'
            'ENTRY %main.9 (p: f32[4]) -> (f32[4]) {\n'
            '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(f)/lm_head/dot_general"}\n'
            '  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, '
            'calls=%fused_computation.7\n'
            '  ROOT %t = (f32[4]{0}) tuple(%fusion.3)\n'
            '}\n')
    assert trace.hlo_scopes(text) == {"fusion.3": ("lm_head",),
                                      "neg.2": ("moe", "experts"),
                                      "fusion.7": ("moe", "experts")}


# -- a traced serving run on the CPU ------------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The tiny cell through the serving driver with the profiler on; the
    trace stays on disk."""
    from bench import run as run_mod
    out = str(tmp_path_factory.mktemp("program_trace"))
    cell = _tiny_cell()
    run_mod.add_paths()
    devices, driver = run_mod.prepare(cell, require_chip=False)
    rec = driver.run(cell, seed=11, seconds=2.0, trace=True, t_start=0.0,
                     out_dir=out, devices=devices)
    return cell, rec, out


def _read(traced_run, top=10):
    _, rec, out = traced_run
    prof = trace.read_profile(out, host_ops=True, scope_maps=rec.scope_maps)
    return prof, trace.summarize(prof, top)


def test_a_traced_serving_run_on_the_cpu(traced, monkeypatch):
    """Every number reads, and the decode wave's scopes fit inside it."""
    import jax
    from bench import peaks
    _, rec, out = traced
    assert rec.correct, rec.checks
    assert set(rec.scope_maps) == {"jit_serve_decode_wave",
                                   "jit_serve_prefill_chunk",
                                   "jit_serve_extend_chunk"}
    prof, s = _read(traced)
    names = {n for n, _, _, _ in prof.program_spans}
    assert {"serve.step", "serve.admit", "serve.prefill_chunk",
            "serve.fetch", "serve.sample", "serve.decode_dispatch"} <= names
    mods = {o.module for ops in prof.ops.values() for o in ops}
    assert {"jit_serve_decode_wave", "jit_serve_prefill_chunk",
            "jit_serve_install"} <= mods
    assert "jit_fn" not in mods
    rec.trace = s
    wave = pt.decode_wave_device_ms(s)
    parts = [_serve.decode_scope_ms(rec, sc) for sc in
             (("moe",), ("attention",), ("paged_gather", "paged_scatter"))]
    assert wave > 0 and all(p > 0 for p in parts)
    assert sum(parts) <= wave * 1.001
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.Peaks(1e12, 1e11, "toy"))
    got = pt.metrics(rec)
    assert set(got) == {"decode_wave_device_ms", "prefill_device_mfu",
                        "step_host_ms", "queue_wait_p90_ms"}
    assert 0 < got["step_host_ms"] and got["prefill_device_mfu"] > 0
    assert 0 <= got["queue_wait_p90_ms"]
    # idle time inside the benchmark's step spans is named by the program's
    # spans
    under_step = dict(s.idle_gaps).get("bench.step", 0.0)
    program = sum(v for k, v in s.idle_gaps if k.startswith("serve."))
    assert program > under_step


def _old_scoped(trace_dir, scope_maps):
    """Seconds per (module, scope), mean over devices, as the earlier
    per-scope reduction read them before ``bench/trace.py`` took it over: the
    host's op events with their ``hlo_module`` stat, each op's scopes from
    the maps, the union of each (module, scope)'s intervals per device
    clipped to ``bench.window``."""
    import glob
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    win, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == "bench.window":
                    win.append((s, e))
                elif ev.name.startswith(("bench.", "serve.")):
                    continue
                else:
                    st = dict(ev.stats)
                    if "hlo_op" in st:
                        ops.append((ev.name.split(" = ", 1)[0].lstrip("%"),
                                    s, e, st.get("hlo_module", "")))
    lo, hi = ((min(s for s, _ in win), max(e for _, e in win)) if win
              else (-math.inf, math.inf))
    by_scope = defaultdict(list)
    for name, s, e, module in ops:
        for sc in set(scope_maps.get(module, {}).get(name, ())):
            by_scope[(module, sc)].append((s, e))
    return {k: trace.total(trace.clip(trace.merge(iv), lo, hi)) * 1e-9
            for k, iv in by_scope.items()}


def test_harness_scopes_equal_the_old_reduction(traced):
    _, rec, out = traced
    _, s = _read(traced)
    old = _old_scoped(out, rec.scope_maps)
    assert {k for k, v in old.items() if v > 0} == \
        {k for k, v in s.scoped.items() if v > 0}
    assert any(m == "jit_serve_decode_wave" for m, _ in s.scoped)
    for k, v in old.items():
        assert s.scoped.get(k, 0.0) == pytest.approx(v, rel=1e-12, abs=0)


def test_the_four_decode_readers_read_a_recorded_run(traced):
    from bench import run as run_mod
    cell, rec, _ = traced
    _, rec.trace = _read(traced)
    got = {n: run_mod.read_metric(n, rec) for n in DECODE_READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["decode_expert_read_share"] <= 100
    assert set(rec.counters) == {"open", "close"}
    # the cell reports each of them
    names = [m["name"] for m in run_mod.cell_metrics(cell, True)]
    assert set(DECODE_READERS) <= set(names)
