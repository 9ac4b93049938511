"""Whole runs of the training cell at toy widths on four virtual CPU
devices (``train_cpu_cases.py``, in a process of its own): the look for a
chip is skipped, everything else is what a chip run does.

* the program passes the comparison with the reference, and the control
  (the reference in int8 W8A8, put in the program's place) fails it;
* each fault planted in the timed path fails it;
* the window holds whole steps, and ``attempted`` and ``failed`` count them;
* a traced run reads every per-layer metric of the cell;
* the reference keeps each gradient where its parameter lives.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import train_cpu_cases as cases  # noqa: E402

FAULTS = sorted(cases.FAULTS)


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    out = tmp_path_factory.mktemp("train") / "cases.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "train_cpu_cases.py"), str(out),
         "program", "window", "traced", "reference_layout", *FAULTS],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    with open(out) as f:
        return json.load(f)


def _case(results, name):
    res = results[name]
    assert "error" not in res, res.get("error")
    return res


def test_program_passes_and_the_control_fails(results):
    res = _case(results, "program")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["window"]["compiles"] == 0
    assert res["keys"][-1] == "checks"
    ctl = res["control"]
    assert not ctl["passes"]
    assert any(ctl[k] > c["limit"] for k, c in res["checks"].items())


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_fails(results, fault):
    res = _case(results, fault)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_window_holds_whole_steps_and_counts_failures(results):
    res = _case(results, "window")
    w0, w1 = res["window"]
    steps = res["steps"]
    assert steps and steps[0][0] >= w0
    # every step that starts before the window's seconds, each whole
    assert all(t0 < w0 + res["seconds"] for t0, _, _, _ in steps)
    assert all(a[1] <= b[0] for a, b in zip(steps, steps[1:]))
    assert w1 == steps[-1][1] >= w0 + res["seconds"]
    assert res["attempted"] == len(steps)
    # the simulated out-of-memory failure: one step escalated
    assert res["failed"] == 1 and res["faults"] >= 1
    assert res["correct"]


def test_traced_run_reads_every_per_layer_metric(results):
    res = _case(results, "traced")
    m = res["metrics"]
    assert res["correct"]
    # the CPU reports no memory peak; every other metric reads
    assert {k for k, v in m.items() if v is not None} == {
        "train_mfu", "train_a2a_exposed_share", "train_mact_chunks",
        "train_idle_share"}
    assert 0 < m["train_mfu"] < 100
    assert 0 < m["train_a2a_exposed_share"] < 100
    assert 0 <= m["train_idle_share"] < 100
    assert m["train_mact_chunks"] == 4


def test_reference_keeps_gradients_where_their_parameters_live(results):
    res = _case(results, "reference_layout")
    assert res == {"zeros": [], "grads": []}
