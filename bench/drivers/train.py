"""Training driver: drives the program's ``Trainer`` (``repro.training.
trainer``: ``fit``) step by step on a ``(1, chips)`` ("data", "model") mesh,
a closed loop whose next step starts when the last one returns, and then
checks the first steps it took against the plain float32 reference.

Set-up: the weights from the seed (one jitted call, float32 masters, each
leaf born in its ``param_shardings`` layout) with zeroed AdamW moments as
the program's ``TrainState``; the trainer, its MACT planner and its
compiled steps; every batch of the mix (``bench/train_data.py``) behind the
trainer's ``data``.  The lead-in then runs the job's first steps through
``Trainer.fit``, the window's own call: the first three are the ones the
check compares, and it goes on until no new compiled step has appeared for
``stable_steps`` steps, so that MACT's schedule keys are compiled.

The window opens at a step boundary, holds every step that starts before
``seconds`` have passed and closes when the last of them returns.  With the
profiler on, the trace covers the steps that start in the window's last
``TRACE_SECONDS``.

What is the architecture's (the program's model for the configuration
file, the plain reference, its view and placement of the weights and its
arguments) comes from the configuration's adapter,
``bench/arch/<model_type>.py``.

The check (limits in ``bench/limits/<cell>.json``) compares, after the
program's state is freed, the first three steps with the reference's (the
adapter's ``train_reference``) on the same bench-made weights and batches,
at the chunk count MACT used in each step:

* ``loss_rel_err``: the largest relative gap of the logged loss of steps 1-3;
* ``grad_norm_gap``: the first gradient as the optimizer took it (clipped),
  worked out from the AdamW state after one step (mu / (1 - b1)): over the
  leaves, the largest gap between the program's norm and the reference's,
  over the larger of that leaf's reference norm and the median leaf's;
* ``change_norm_gap``: the same for the parameters' change over the three
  steps, over the leaves whose reference gradient is at least
  ``exclude_below`` times the median leaf's (a leaf the loss does not reach,
  such as an unused router bias, moves by round-off alone);
* ``grad_diff``: the first gradient again, by the norm of its difference
  from the reference's, over the larger of the leaf's reference norm and the
  median leaf's, largest over the reference's leaves.  The norms above
  average a gradient's rounding away; its difference does not;
* ``grad_diff_median``: the same gaps' median over the leaves.  The largest
  leaf is the router's, whose top-k flips under bfloat16 passes set the
  program's floor; the median leaf's gap follows the rounding itself, and
  tells the program's bfloat16 passes from int8 ones (``PERF.md`` §2).
"""

from __future__ import annotations

import gc
import math
import time
import weakref
from dataclasses import dataclass, field

import numpy as np

from bench import arch as arch_mod
from bench import train_data
from bench import weights as weights_mod
from bench.drivers import serve

TRACE_SECONDS = 8.0
#: each number compared -> the per-leaf (per-step) gaps it reduces, and how
CHECKS = {"loss_rel_err": ("loss_rel_err", np.max),
          "grad_norm_gap": ("grad_norm_gap", np.max),
          "change_norm_gap": ("change_norm_gap", np.max),
          "grad_diff": ("grad_diff", np.max),
          "grad_diff_median": ("grad_diff", np.median)}
@dataclass
class TrainRun:
    """What one run recorded; the metric readers read it."""
    kind: str = "train"
    config: dict = field(default_factory=dict)
    device_kind: str = ""
    chips: int = 1
    seconds: float = 0.0
    setup_s: float = 0.0
    global_batch: int = 0
    seq_len: int = 0
    window: tuple = (0.0, 0.0)            # absolute host-clock times
    steps: list = field(default_factory=list)   # (t0, t1, chunks, depth)
    lead_in_steps: int = 0
    setup_parts: dict = field(default_factory=dict)   # phase -> seconds
    compiles_in_window: int = 0
    faults: int = 0                       # OOM-ladder escalations, window
    requeues: int = 0
    memory_peak_bytes: int = 0
    trace: object = None                  # bench.trace.Summary
    a2a_exposed_share: object = None      # bench.collectives, traced runs
    attempted: int = 0
    failed: int = 0
    checks: dict = field(default_factory=dict)    # name -> (value, limit)
    reference_s: float = 0.0
    correct: bool = False


def _path(path) -> str:
    return weights_mod._path_str(path)


class Job:
    """The program under test, built once from the configuration, the mix
    and the seed: the trainer with its compiled steps, its state and the
    mix's batches."""

    def __init__(self, c: dict, mix: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from repro.core.moe import DistContext
        from repro.launch.mesh import make_mesh
        from repro.models import transformer
        from repro.optim.adamw import adamw_init
        from repro.training.step import TrainState, train_state_shardings
        from repro.training.trainer import Trainer
        self.c, self.seed = c, seed
        self.arch = arch_mod.adapter(c)
        tr = c["bench"]["training"]
        self.cfg = cfg = self.arch.train_program_config(c)
        self.mesh = make_mesh((1, len(devices)), ("data", "model"))
        self.data = train_data.TrainBatches(mix, seed, c["vocab_size"])
        self.trainer = Trainer(
            cfg, DistContext(mesh=self.mesh, moe_strategy=tr["moe_strategy"]),
            seq_len=self.data.seq_len, global_batch=self.data.global_batch,
            **tr["trainer"])
        self.trainer.data = self.data
        self.shapes = shapes = jax.eval_shape(
            lambda k: transformer.init_params(k, cfg, jnp.float32),
            jax.random.PRNGKey(0))
        std = float(c["bench"]["embed_std"])

        def make_params():
            return weights_mod.make(shapes, seed, std)

        def init():
            params = make_params()
            return TrainState(params, adamw_init(params), jnp.int32(0))

        # closes over no part of the job, so that the reference, which makes
        # the weights again once the job is gone, finds its memory free
        self.make_params = make_params
        self.state = jax.jit(init, out_shardings=train_state_shardings(
            jax.eval_shape(init), self.mesh, cfg))()

    def step(self) -> dict:
        """One step through ``Trainer.fit``; returns its log record."""
        self.state = self.trainer.fit(1, self.state)
        return self.trainer.log[-1]

    def leaf_norms(self, tree) -> dict:
        import jax
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self.arch.train_reference.leaf_norms(tree))
        return {_path(p): float(v) for p, v in flat}

    def change_norms(self) -> dict:
        """Leaf norms of the parameters' change since the seed's weights
        (made again from the seed, in the same layout, inside one call)."""
        import jax
        import jax.numpy as jnp
        with jax.set_mesh(self.mesh):
            tree = jax.jit(lambda p: jax.tree.map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p,
                self.make_params()))(self.state.params)
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {_path(p): float(v) for p, v in flat}

    def lead_in(self, check_steps: int, stable: int, most: int) -> dict:
        """Run the first steps; returns the program's readings of the first
        ``check_steps``."""
        import jax
        b1 = float(self.c["bench"]["training"]["adamw"]["b1"])
        out = {"losses": [], "chunks": []}
        calm = 0
        for i in range(most):
            before = self.trainer.compile_count
            rec = self.step()
            calm = calm + 1 if self.trainer.compile_count == before else 0
            if i < check_steps:
                out["losses"].append(float(rec["loss"]))
                out["chunks"].append(int(rec["chunks"]))
            if i == 0:
                t = time.perf_counter()
                out["grad1"] = {k: v / (1.0 - b1) for k, v in
                                self.leaf_norms(self.state.opt.mu).items()}
                out["grad1_full"] = {
                    k: np.asarray(v) / (1.0 - b1) for k, v in
                    self.arch.train_reference.flat(self.arch.reference_tree(
                        jax.device_get(self.state.opt.mu))).items()}
                out["copy_s"] = time.perf_counter() - t
            if i + 1 == check_steps:
                out["change"] = self.change_norms()
            if i + 1 >= check_steps and calm >= stable:
                break
        out["steps"] = i + 1
        return out

    def window(self, rec: TrainRun, trace_dir, counter) -> None:
        import jax
        esc = self.trainer.guard.escalations
        trace_at = None
        ann = None
        w0 = time.perf_counter()
        end = w0 + rec.seconds
        if trace_dir:
            trace_at = end - min(TRACE_SECONDS, rec.seconds)
        counter.on = True
        now = w0
        while now < end:
            if trace_at is not None and now >= trace_at:
                jax.profiler.start_trace(trace_dir)
                ann = jax.profiler.TraceAnnotation("bench.window")
                ann.__enter__()
                trace_at = None
            n_esc = len(esc)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                r = self.step()
            now = t1 = time.perf_counter()
            rec.steps.append((t0, t1, int(r["chunks"]), int(r["pipeline"])))
            bad = len(esc) > n_esc or not math.isfinite(r["loss"])
            rec.failed += int(bad)
            rec.faults += len(esc) - n_esc
        counter.on = False
        rec.window = (w0, rec.steps[-1][1] if rec.steps else w0)
        if ann is not None:
            ann.__exit__(None, None, None)
        if trace_dir:
            jax.profiler.stop_trace()
        rec.attempted = len(rec.steps)


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        out_dir: str, devices, inspect=None) -> TrainRun:
    """One run of ``cell``.  ``inspect``, when given, is called after the
    comparison as ``inspect(model, dims, weights, batches, args, program,
    reference)``: the reference module (the adapter's ``train_reference``)
    and its sizes, its placed weights, the checked batches, the keyword
    arguments of its ``train_steps``, the program's readings and the
    reference's (``bench/train_control.py`` reads the control there)."""
    c, mix = cell.config, cell.traffic
    tr = c["bench"]["training"]
    rec = TrainRun(config=c, seconds=float(seconds), chips=len(devices),
                   device_kind=devices[0].device_kind)
    counter = serve._CompileCounter()
    t_job = time.perf_counter()
    job = Job(c, mix, seed, devices)
    rec.global_batch, rec.seq_len = job.data.global_batch, job.data.seq_len
    lead = tr["lead_in"]
    t_lead = time.perf_counter()
    prog = job.lead_in(c["bench"]["check"]["steps"], lead["stable_steps"],
                       lead["max_steps"])
    rec.lead_in_steps = prog["steps"]
    rec.setup_s = time.perf_counter() - t_start
    rec.setup_parts = {
        "start": t_job - t_start, "job": t_lead - t_job,
        "lead_in": rec.setup_s - (t_lead - t_start),
        "copy": prog["copy_s"],
        "steps": [r["time_s"] for r in job.trainer.log]}
    job.window(rec, out_dir if trace else None, counter)
    rec.compiles_in_window = counter.count
    rec.memory_peak_bytes = serve._memory_peak(devices)
    if trace:
        _read_collectives(rec, out_dir, devices)

    # -- correctness: free the program's state, then the reference ----------
    n_periods = (0 if job.shapes["periods"] is None else
                 job.shapes["periods"][0]["norm1"]["scale"].shape[0])
    arch = job.arch
    prog["refs"] = {k: arch.reference_leaves(k, n_periods)
                    for k in prog["grad1"]}
    data, make_params = job.data, job.make_params
    alive = weakref.ref(job)
    del job
    gc.collect()
    if alive() is not None:
        raise RuntimeError("the program's job outlives the window: its state "
                           "would hold the chips' memory the reference needs")
    t_ref = time.perf_counter()
    weights = arch.train_reference_weights(make_params, devices)
    steps = len(prog["losses"])
    batches = [(b["tokens"], b["labels"]) for b in
               (data.batch_at(i) for i in range(steps))]
    args = arch.reference_args(c, len(devices), prog["chunks"])
    model, dm = arch.train_reference, arch.dims(c)
    ref = model.train_steps(weights, batches, dm, **args, keep_grad1=True)
    rec.checks, rec.correct = compare(prog, ref, cell.limits)
    rec.reference_s = time.perf_counter() - t_ref
    if inspect is not None:
        inspect(model, dm, weights, batches, args, prog, ref)
    del weights
    gc.collect()
    return rec


def _read_collectives(rec: TrainRun, out_dir: str, devices) -> None:
    from bench import collectives, trace as trace_mod
    prof = trace_mod.read_profile(out_dir,
                                  host_ops=devices[0].platform != "tpu")
    rec.a2a_exposed_share = collectives.exposed_share(prof)


def _gap(a: float, b: float, floor: float) -> float:
    return abs(a - b) / max(b, floor)


def leaf_gaps(prog: dict, ref: dict, limits: dict) -> dict:
    """Per step and per leaf, what the check's numbers take the largest of:
    ``loss_rel_err`` by step, ``grad_norm_gap`` by leaf and
    ``change_norm_gap`` by the leaves the reference moves."""
    def ref_norm(d, names):
        return math.sqrt(sum(d[n] ** 2 for n in names))

    refs = prog["refs"]
    g0 = {k: ref_norm(ref["grad0"], refs[k]) for k in refs}
    g1 = {k: ref_norm(ref["grad1"], refs[k]) for k in refs}
    ch = {k: ref_norm(ref["change"], refs[k]) for k in refs}
    cut = float(limits["exclude_below"]) * float(np.median(list(g0.values())))
    moved = [k for k in refs if g0[k] >= cut]
    g_med = float(np.median([g1[k] for k in moved]))
    c_med = float(np.median([ch[k] for k in moved]))
    want = ref["grad1_full"]
    d_norm = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    d_med = float(np.median(list(d_norm.values())))
    return {
        "loss_rel_err": {str(i): abs(a - b) / abs(b) for i, (a, b) in
                         enumerate(zip(prog["losses"], ref["losses"]))},
        "grad_diff": {k: float(np.linalg.norm(prog["grad1_full"][k] - v))
                      / max(d_norm[k], d_med) for k, v in want.items()},
        "grad_norm_gap": {k: _gap(prog["grad1"][k], g1[k], g_med)
                          for k in refs},
        "change_norm_gap": {k: _gap(prog["change"][k], ch[k], c_med)
                            for k in moved},
    }


def compare(prog: dict, ref: dict, limits: dict):
    """The check's numbers, each beside its limit, and whether all hold."""
    gaps = leaf_gaps(prog, ref, limits)
    checks = {k: (float(how(list(gaps[g].values()))),     # NaN stays NaN
                  float(limits[k]["limit"])) for k, (g, how) in CHECKS.items()}
    ok = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    ok = ok and len(prog["losses"]) == len(ref["losses"])
    return checks, bool(ok)
