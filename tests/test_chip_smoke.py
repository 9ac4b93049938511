"""``chip_smoke.py`` and the compile cache, off the chip.

The smoke refuses to run without a TPU or outside a checkout, and never
prints a result there; its phases pass at reduced widths on the CPU (the
4-chip EP phase on 4 virtual CPU devices).  The launchers' persistent
compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to the
one fixed directory in the checkout, and importing the package never turns
it on.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

SERVE_SMOKE = ["--arch", "mixtral-8x7b", "--smoke", "--requests", "6",
               "--arrival-rate", "0", "--max-slots", "4",
               "--prompt-lens", "16,32", "--gen", "2,4",
               "--prefill-chunk", "16"]


def _run(args, cwd, env=None, timeout=600):
    """Run python with ``args`` on the CPU; ``env`` entries override the
    environment (None removes the variable)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": SRC,
           **(env or {})}
    env = {k: v for k, v in env.items() if v is not None}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu():
    out = _run(["chip_smoke.py"], REPO, env={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert "'cpu'" in out.stderr              # names the platform it found
    assert '"ok"' not in out.stdout


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], tmp_path, env={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert "no repro package" in out.stderr
    assert '"ok"' not in out.stdout


def test_serving_phases_at_reduced_widths(smoke):
    smoke.serve_phase(SERVE_SMOKE, check_logits=True)
    smoke.serve_phase(SERVE_SMOKE + smoke.PAGED_ARGS, check_logits=False)


def test_trainer_phase(smoke):
    smoke.trainer_phase(smoke.TRAIN_SMOKE_ARGS)


def test_check_served_rejects_a_shed_request(smoke):
    class Sched:
        finished = []
    m = {"requests": 5, "shed": 1, "faults": 0, "generated_tokens": 10}
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_served(Sched(), m, 6, "serve")


def test_ep_phase_on_four_cpu_devices():
    """The --chips 4 path (EP train steps via ``launch.train --mesh host``,
    then ep_shardmap vs tp_gspmd at batch 1) at reduced widths."""
    body = f"""
        import sys
        sys.path[:0] = [{REPO!r}, {SRC!r}]
        import chip_smoke
        chip_smoke.ep_phase(["--arch", "mixtral-8x7b", "--smoke", "--mesh",
                             "host", "--seq-len", "64", "--global-batch", "4",
                             "--steps", "3"])
        print("EP PHASE OK")
    """
    out = _run(["-c", textwrap.dedent(body)], REPO, env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stdout + out.stderr
    assert "EP PHASE OK" in out.stdout
    assert "OOM escalations 0" in out.stdout


def _cache_probe(body: str, env: dict):
    code = textwrap.dedent("""
        import jax
        from repro.launch.compile_cache import enable_compile_cache
    """) + textwrap.dedent(body)
    out = _run(["-c", code], REPO, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


def test_compile_cache_uses_the_env_dir(tmp_path):
    cache = str(tmp_path / "cc")
    out = _cache_probe("""
        print("DIR", enable_compile_cache())
        jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()
    """, {"JAX_COMPILATION_CACHE_DIR": cache,
          "JAX_ENABLE_COMPILATION_CACHE": "true",
          "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
          "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert f"DIR {cache}" in out
    assert os.listdir(cache)                   # written there


def test_compile_cache_default_is_the_checkout_dir():
    out = _cache_probe("""
        print("BEFORE", jax.config.jax_compilation_cache_dir)
        import repro.launch.serve, repro.launch.train   # importing: still off
        print("IMPORTED", jax.config.jax_compilation_cache_dir)
        print("DIR", enable_compile_cache())
        print("CONFIG", jax.config.jax_compilation_cache_dir)
    """, {"JAX_COMPILATION_CACHE_DIR": None})
    want = os.path.join(REPO, ".jax_cache")
    assert "BEFORE None" in out and "IMPORTED None" in out
    assert f"DIR {want}" in out and f"CONFIG {want}" in out
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
