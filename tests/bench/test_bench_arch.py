"""A configuration of a second architecture runs through the harness with
new files only: its configuration, its adapter (here the test-only
``tests/bench/arch/toy_moe.py``, found by pointing the adapter lookup at
that directory), and its entries in the manifest.  Its adapter counts no
operations, so the metric built on them is left out, and nothing raises.
A ``model_type`` with no adapter fails at the driver's start, naming the
missing file."""

import json
import os

import pytest

from bench import arch, flops, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "toy-moe.serve-chat"
#: the entries a configuration of a new architecture adds to the manifest
ENTRIES = {
    "configs": [{"name": "toy-moe-serve", "source": "test-only",
                 "file": "tests/bench/toy-moe-serve.json", "reduced": [],
                 "why": "a second architecture at toy widths"}],
    "workloads": [{"name": CELL, "config": "toy-moe-serve",
                   "traffic": "chat", "chips": 1,
                   "why": "the chat mix at toy sizes"}]}
DECODE_READERS = {"decode_moe_device_ms", "decode_attn_device_ms",
                  "decode_cache_device_ms", "decode_expert_read_share"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture
def cell(monkeypatch, tmp_path):
    monkeypatch.setattr(arch, "ARCH_DIR", os.path.join(HERE, "arch"))
    # a trace of its own: other test files' runs clear the checkout's
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    manifest = _json(ROOT, "BENCHMARK.json")
    for key, entries in ENTRIES.items():
        manifest[key] = manifest[key] + entries
    (work,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    return run.Cell(CELL, work, _json(HERE, "toy-moe-serve.json"),
                    _json(HERE, "tiny-chat.json"),
                    _json(HERE, "tiny-limits.json"), manifest)


@pytest.mark.parametrize("traced", [False, True])
def test_a_second_architecture_runs_from_new_files(cell, monkeypatch,
                                                   traced):
    import jax
    from bench import peaks
    # the chip's peak is known, so only the missing count leaves a
    # share of it out
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.Peaks(1e12, 1e11, "toy"))
    res = run.run_cell(cell, 2**33 + 5, 2.0, traced, require_chip=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    if not traced:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p90_ms",
                                       "itl_p99_ms", "setup_s"}
        return
    assert "prefill_mfu" not in res["metrics"]
    assert DECODE_READERS <= set(res["metrics"])
    assert {"decode_step_ms", "serve_idle_share"} <= set(res["metrics"])
    assert res["device"]["busy_s"] > 0


def test_counts_the_adapter_does_not_give_read_none(cell):
    assert flops.prefill_flops(cell.config, 0, 32, logits=1) is None
    assert flops.weight_bytes(cell.config) is None


def test_a_model_type_without_an_adapter_fails_at_the_driver_start(cell):
    cell.config["model_type"] = "no_such_model"
    with pytest.raises(FileNotFoundError, match="no_such_model.py"):
        run.run_cell(cell, 1, 1.0, False, require_chip=False)
