"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name in
it resolves to a file of its own."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    assert manifest["command"][1] == "bench/run.py"


def test_configs_and_cells(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        for key in c["reduced"]:
            assert key in conf["published"]
            assert conf[key] != conf["published"][key]
        drv = conf["bench"]["driver"]
        assert os.path.isfile(os.path.join(ROOT, "bench", "drivers",
                                           drv + ".py"))
    used = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["config"] in configs and len(w["why"]) <= 200
        used.add(w["config"])
        for sub in ("traffic", "limits"):
            name = w["traffic"] if sub == "traffic" else w["name"]
            assert os.path.isfile(os.path.join(ROOT, "bench", sub,
                                               name + ".json"))
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_have_readers_and_move_reported_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved


#: what each driver takes from a configuration's adapter
NEEDS = {"serve": ("program_config", "dims", "serve_reference",
                   "reference_weights"),
         "train": ("train_program_config", "dims", "train_reference",
                   "reference_tree", "reference_leaves",
                   "train_reference_weights", "reference_args")}


def _configs(manifest):
    """Every configuration of the manifest and of the held-out training
    cell's entries."""
    with open(os.path.join(ROOT, "tests", "bench", "train-cell.json")) as f:
        held = json.load(f)
    return manifest["configs"] + held["configs"]


def test_every_configuration_has_an_architecture_adapter(manifest):
    from bench import arch
    for c in _configs(manifest):
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        path = os.path.join(ROOT, "bench", "arch",
                            conf["model_type"] + ".py")
        assert os.path.isfile(path), path
        adapter = arch.adapter(conf)
        assert os.path.samefile(adapter.__file__, path)
        for name in NEEDS[conf["bench"]["driver"]]:
            assert callable(getattr(adapter, name, None)) or \
                hasattr(getattr(adapter, name, None), "__file__"), \
                (conf["model_type"], name)
