"""A whole serving run of the harness at toy widths on the CPU: the look for
a chip is skipped, everything else is what a chip run does.

* the program's served tokens pass the comparison with the reference;
* the control (the reference in fp8, put in the program's place) fails it;
* an answer altered where the scheduler produces it fails it, and so does
  a decode wave that leaves the cache as it was;
* off a TPU the command exits non-zero and prints no result.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "mixtral-l4.serve-chat"


def _json(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def _cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (work,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    return run.Cell(CELL, work, _json("tiny-serve.json"),
                    _json("tiny-chat.json"), _json("tiny-limits.json"),
                    manifest)


def _run(seed, **kw):
    return run.run_cell(_cell(), seed, 2.0, False, require_chip=False, **kw)


def test_program_passes_and_the_control_fails(monkeypatch):
    from bench import control
    from bench.drivers import serve
    inspect, readings, _ = control.study(("fp8",), None)
    real = serve.run
    monkeypatch.setattr(serve, "run", lambda *a, **kw: real(
        *a, inspect=inspect, **kw))
    res = _run(2**33 + 3)
    share = res["checks"]["tokens_off_best_share"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["served_tokens_compared"]["value"] >= 20
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms",
                                   "itl_p99_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {k: res["window"][k] for k in ("compiles", "faults",
                                          "requeues")} == \
        {"compiles": 0, "faults": 0, "requeues": 0}
    assert readings["program"]["share_gt_0.02"] == \
        pytest.approx(share["value"])
    assert readings["control_fp8"]["share_gt_0.02"] > share["limit"] >= \
        share["value"]
    assert list(res)[-1] == "checks"


def _alter_token(monkeypatch):
    """An answer altered where the scheduler samples it: every token from
    the third on.  (One altered token among hundreds is not seen: its gap
    is no wider than that of a sound run's router flip.)"""
    from repro.serving.scheduler import ContinuousBatchingScheduler
    sample = ContinuousBatchingScheduler._sample

    def altered(self, req, logits):
        tok = sample(self, req, logits)
        return (tok + 1) % len(logits) if len(req.out) >= 2 else tok

    monkeypatch.setattr(ContinuousBatchingScheduler, "_sample", altered)


def _stale_cache(monkeypatch):
    """A decode wave that returns the page pools unchanged: the K/V of the
    tokens it decoded never reach the cache."""
    from repro.serving.paged_cache import PagedCachePool
    wave = PagedCachePool.decode_wave

    def stale(self, *args):
        before = self.pools
        logits = wave(self, *args)
        self.pools = before
        return logits

    monkeypatch.setattr(PagedCachePool, "decode_wave", stale)


@pytest.mark.parametrize("fault", [_alter_token, _stale_cache])
def test_planted_fault_fails(monkeypatch, fault):
    fault(monkeypatch)
    res = _run(5)
    assert not res["correct"]
    assert res["checks"]["tokens_off_best_share"]["value"] > \
        res["checks"]["tokens_off_best_share"]["limit"]


@pytest.mark.parametrize("bare", [False, True])
def test_no_result_off_a_tpu_or_outside_a_checkout(tmp_path, bare):
    root = ROOT
    if bare:
        root = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        shutil.copytree(os.path.join(ROOT, "bench"),
                        os.path.join(root, "bench"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
