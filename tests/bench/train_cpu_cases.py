"""Whole runs of the training cell at toy widths on four virtual CPU
devices, for ``test_bench_train.py``, which starts this file in a process
of its own (JAX must see four devices from the start):

  python tests/bench/train_cpu_cases.py <out.json> <case> [<case> ...]

Each case runs the harness with the look for a chip skipped, and with the
timed path broken underneath where the case names a fault.  It writes one
JSON object, case -> what the run gave (or the error it raised).
"""

import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "mixtral-l1-ep4.train-zipf"


def _json(path):
    with open(path) as f:
        return json.load(f)


def cell():
    """The training cell as its entries in ``train-cell.json`` would stand
    in ``BENCHMARK.json``, which does not hold it yet (PERF.md §7)."""
    from bench import run
    manifest = _json(os.path.join(ROOT, "BENCHMARK.json"))
    for key, entries in _json(os.path.join(HERE, "train-cell.json")).items():
        manifest[key] = manifest[key] + entries
    (work,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    return run.Cell(CELL, work, _json(os.path.join(HERE, "tiny-train.json")),
                    _json(os.path.join(HERE, "tiny-zipf-train.json")),
                    _json(os.path.join(HERE, "tiny-train-limits.json")),
                    manifest)


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


# -- faults planted in the timed path ----------------------------------------

def stale_state():
    """A step that returns its state unchanged."""
    from repro.training import trainer
    real = trainer.make_train_step

    def make(cfg, ctx, **kw):
        step = real(cfg, ctx, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    return patched(trainer, "make_train_step", make)


def half_batch():
    """Half of the batch left out, the mean taken over the rest."""
    from repro.training import step
    real = step.loss_fn

    def half(params, cfg, ctx, batch):
        n = batch["tokens"].shape[0] // 2
        return real(params, cfg, ctx, {k: v[:n] for k, v in batch.items()})
    return patched(step, "loss_fn", half)


def no_exchange():
    """The all-to-all between chips left out: every chip keeps its rows."""
    import jax
    from repro.core import ep

    class Lax:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def all_to_all(x, *args, **kw):
            return x
    return patched(ep, "lax", Lax())


def router_grad_stopped():
    """The router's gradient stopped."""
    import jax
    from repro.core import ep
    real = ep.route

    def route(params, x, cfg):
        return real(jax.tree.map(jax.lax.stop_gradient, params), x, cfg)
    return patched(ep, "route", route)


def peer_expert_grads_zeroed():
    """The expert gradients of one EP peer (the second of four) zeroed."""
    import jax
    from repro.training import step
    real = step.adamw_update

    def zero(path, g):
        name = getattr(path[-1], "key", None)
        if name in ("w1", "w2", "w3") and g.ndim == 3:
            per = g.shape[0] // 4
            return g.at[per:2 * per].set(0)
        return g

    def update(grads, state, params, **kw):
        return real(jax.tree_util.tree_map_with_path(zero, grads), state,
                    params, **kw)
    return patched(step, "adamw_update", update)


def token_drops():
    """Tokens dropped: capacity factor 1.0 in place of dropless routing."""
    from bench import arch
    adapter = arch.adapter(cell().config)
    real = adapter.train_program_config

    def config(c):
        cfg = real(c)
        return dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_mode="capacity", capacity_factor=1.0))
    return patched(adapter, "train_program_config", config)


def neighbour_recompute():
    """Each chunk's backward recomputes from the next chunk's tokens (the
    forward is unchanged)."""
    import jax
    import jax.numpy as jnp
    from repro.core import ep
    real = ep.chunked_pipeline

    def pipeline(stages, x, num_chunks, *, depth, remat):
        y, stats = real(stages, x, num_chunks, depth=depth, remat=remat)
        xr = jnp.roll(x, -(x.shape[0] // num_chunks), axis=0)
        yr, _ = real(stages, xr, num_chunks, depth=depth, remat=remat)
        sg = jax.lax.stop_gradient
        return sg(y) + yr - sg(yr), stats
    return patched(ep, "chunked_pipeline", pipeline)


FAULTS = {f.__name__: f for f in (
    stale_state, half_batch, no_exchange, router_grad_stopped,
    peer_expert_grads_zeroed, token_drops, neighbour_recompute)}


# -- cases -------------------------------------------------------------------

def _result(res: dict) -> dict:
    return {k: res[k] for k in ("correct", "attempted", "failed", "metrics",
                                "window", "checks")} | {"keys": list(res)}


def case_program():
    """The program, and the control (int8 W8A8) in its place."""
    from bench import run, train_control
    from bench.drivers import train
    c = cell()
    inspect, readings = train_control.study(("int8",), c.limits)
    real = train.run

    def with_control(*a, **kw):
        return real(*a, inspect=inspect, **kw)
    with patched(train, "run", with_control):
        res = run.run_cell(c, 2**33 + 3, 2.0, False, require_chip=False)
    out = _result(res)
    out["control"] = readings["int8"]
    return out


def case_fault(name):
    from bench import run
    with FAULTS[name]():
        res = run.run_cell(cell(), 5, 0.5, False, require_chip=False)
    return _result(res)


def case_window():
    """A simulated out-of-memory failure at the window's first step (a
    fixed step number may fall after a window that a loaded host made
    short)."""
    from bench import run
    from bench.drivers import train
    from repro.runtime.faults import FaultInjector
    real = train.Job.window

    def window(self, *a, **kw):
        self.trainer.injector = FaultInjector.from_string(
            f"oom@{int(self.state.step)}")
        return real(self, *a, **kw)
    c = cell()
    devices, driver = run.prepare(c, require_chip=False)
    with patched(train.Job, "window", window):
        rec = driver.run(c, seed=7, seconds=1.5, trace=False,
                         t_start=time.perf_counter(), out_dir="",
                         devices=devices)
    return {"seconds": rec.seconds, "window": list(rec.window),
            "steps": [list(s) for s in rec.steps],
            "attempted": rec.attempted, "failed": rec.failed,
            "faults": rec.faults, "lead_in_steps": rec.lead_in_steps,
            "correct": rec.correct}


def case_traced(out_dir):
    """A traced run: the per-layer metrics of the cell."""
    import jax
    from bench import peaks, run, trace
    c = cell()
    devices, driver = run.prepare(c, require_chip=False)
    rec = driver.run(c, seed=9, seconds=1.5, trace=True,
                     t_start=time.perf_counter(), out_dir=out_dir,
                     devices=devices)
    rec.trace = trace.summarize(trace.read_profile(out_dir, host_ops=True))
    peaks.PEAKS[jax.devices()[0].device_kind] = peaks.Peaks(1e12, 1e11,
                                                            "toy")
    return {"correct": rec.correct, "metrics": {
        m["name"]: run.read_metric(m["name"], rec)
        for m in run.cell_metrics(c, True)}}


def case_reference_layout():
    """The reference's gradient accumulator and gradients lie where their
    parameters do (an accumulator made under ``jit`` landed whole on one
    device, which a chip cannot hold at Mixtral widths)."""
    import jax
    import numpy as np
    from bench.models import mixtral, mixtral_train
    c = cell()
    dm = mixtral.Dims.from_config(c.config)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("x",))
    rng = np.random.default_rng(0)
    shapes = {"norm1": (dm.d,), "wq": (dm.d, dm.h * dm.hd),
              "wk": (dm.d, dm.kv * dm.hd), "wv": (dm.d, dm.kv * dm.hd),
              "wo": (dm.h * dm.hd, dm.d), "norm2": (dm.d,),
              "router": (dm.d, dm.e), "w1": (dm.e, dm.d, dm.f),
              "w3": (dm.e, dm.d, dm.f), "w2": (dm.e, dm.f, dm.d)}
    draw = lambda s: 0.1 * rng.standard_normal(s)   # noqa: E731
    params = mixtral_train.place(
        {"embed": draw((dm.v, dm.d)), "head": draw((dm.d, dm.v)),
         "final_norm": 1 + draw((dm.d,)),
         "layers": [{k: draw(v) for k, v in shapes.items()}]}, mesh)
    tokens = rng.integers(0, dm.v, (2, 32)).astype(np.int32)
    _, zeros = mixtral_train._zeros(params)
    _, grads = mixtral_train.batch_loss_grads(
        params, tokens, tokens, dm, devices=4, chunks=1, coef=0.02,
        cap_multiple=8)
    want = {k: v.sharding for k, v in mixtral_train.flat(params).items()}
    return {"zeros": [k for k, v in mixtral_train.flat(zeros).items()
                      if not v.sharding.is_equivalent_to(want[k], v.ndim)],
            "grads": [k for k, v in mixtral_train.flat(grads).items()
                      if not v.sharding.is_equivalent_to(want[k], v.ndim)]}


def main(argv):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    for p in (ROOT, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    out_path, cases = argv[0], argv[1:]
    out = {}
    for case in cases:
        try:
            if case == "program":
                out[case] = case_program()
            elif case == "window":
                out[case] = case_window()
            elif case == "reference_layout":
                out[case] = case_reference_layout()
            elif case == "traced":
                out[case] = case_traced(os.path.join(
                    os.path.dirname(out_path), "trace"))
            else:
                out[case] = case_fault(case)
        except Exception:          # noqa: BLE001 - reported to the test
            out[case] = {"error": traceback.format_exc()}
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
