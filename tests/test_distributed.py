"""Multi-device tests.  Each test runs in a SUBPROCESS that sets
--xla_force_host_platform_device_count (the main pytest process must keep the
single real device per the dry-run isolation rule)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout: int = 600) -> str:
    src = (f"import os\n"
           f"os.environ['XLA_FLAGS'] = "
           f"'--xla_force_host_platform_device_count={devices}'\n"
           + textwrap.dedent(body))
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_ep_shardmap_equals_tp_path():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import moe as M
        from repro.configs.base import MoEConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=64)
        params = M.init_moe(jax.random.PRNGKey(0), 32, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        ctx_ep = M.DistContext(mesh=mesh, moe_chunks=2, moe_strategy="ep_shardmap")
        with jax.set_mesh(mesh):
            y_ep, s_ep = jax.jit(lambda p, x: M.moe_ffn(p, x, cfg, ctx_ep))(params, x)
            g_ep = jax.jit(jax.grad(lambda p: M.moe_ffn(p, x, cfg, ctx_ep)[0].sum()))(params)
        y_tp, s_tp = M.moe_ffn(params, x, cfg, M.DistContext(moe_chunks=2))
        g_tp = jax.grad(lambda p: M.moe_ffn(p, x, cfg, M.DistContext(moe_chunks=2))[0].sum())(params)
        assert np.abs(np.asarray(y_ep) - np.asarray(y_tp)).max() < 1e-5
        assert float(s_ep["drops"]) == 0.0
        np.testing.assert_array_equal(np.asarray(s_ep["load"]), np.asarray(s_tp["load"]))
        errs = [np.abs(np.asarray(a) - np.asarray(b)).max()
                for a, b in zip(jax.tree.leaves(g_ep), jax.tree.leaves(g_tp))]
        assert max(errs) < 1e-4, errs
        print("EP==TP OK")
    """, devices=4)
    assert "EP==TP OK" in out


def test_ep_chunk_invariance_on_mesh():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import moe as M
        from repro.configs.base import MoEConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=32)
        params = M.init_moe(jax.random.PRNGKey(0), 16, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))
        with jax.set_mesh(mesh):
            outs = []
            for c in (1, 2, 4):
                ctx = M.DistContext(mesh=mesh, moe_chunks=c, moe_strategy="ep_shardmap")
                y, _ = jax.jit(lambda p, x: M.moe_ffn(p, x, cfg, ctx))(params, x)
                outs.append(np.asarray(y))
        assert np.abs(outs[0] - outs[1]).max() < 1e-5
        assert np.abs(outs[0] - outs[2]).max() < 1e-5
        print("CHUNK-INVARIANT OK")
    """, devices=8)
    assert "CHUNK-INVARIANT OK" in out


def test_ep_pipelined_schedule_on_mesh():
    """The wave-pipelined FCDA schedule (pipeline_chunks=2) matches the
    sequential loop bit-for-bit on a real multi-device mesh — values, stats
    and gradients — with remat on and off."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import moe as M
        from repro.configs.base import MoEConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=32)
        params = M.init_moe(jax.random.PRNGKey(0), 16, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))
        with jax.set_mesh(mesh):
            for remat in (True, False):
                ctx0 = M.DistContext(mesh=mesh, moe_chunks=4, remat_chunks=remat,
                                     moe_strategy="ep_shardmap")
                ctx1 = M.DistContext(mesh=mesh, moe_chunks=4, remat_chunks=remat,
                                     pipeline_chunks=2, moe_strategy="ep_shardmap")
                y0, s0 = jax.jit(lambda p, x: M.moe_ffn(p, x, cfg, ctx0))(params, x)
                y1, s1 = jax.jit(lambda p, x: M.moe_ffn(p, x, cfg, ctx1))(params, x)
                assert np.abs(np.asarray(y0) - np.asarray(y1)).max() < 1e-6
                np.testing.assert_array_equal(np.asarray(s0["load"]), np.asarray(s1["load"]))
                assert float(s1["drops"]) == 0.0
                g0 = jax.jit(jax.grad(lambda p: M.moe_ffn(p, x, cfg, ctx0)[0].sum()))(params)
                g1 = jax.jit(jax.grad(lambda p: M.moe_ffn(p, x, cfg, ctx1)[0].sum()))(params)
                errs = [np.abs(np.asarray(a) - np.asarray(b)).max()
                        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1))]
                assert max(errs) < 1e-5, (remat, errs)
        print("PIPELINE-EP OK")
    """, devices=8)
    assert "PIPELINE-EP OK" in out


def test_full_train_step_on_mesh():
    """A whole MoE train step (MoE EP + TP attention + sharded batch) runs
    and produces finite loss on a 2x4 mesh."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from dataclasses import replace
        from repro.configs import get_config
        from repro.launch import dryrun_lib as lib
        from repro.configs.base import InputShape
        from repro.training.step import init_train_state, make_train_step
        from repro.data.pipeline import SyntheticLMData
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = replace(get_config("mixtral-8x7b").reduced(),
                      moe=replace(get_config("mixtral-8x7b").reduced().moe,
                                  num_experts=4))
        shape = InputShape("t", 32, 4, "train")
        cfg, ctx = lib.build_context(cfg, shape, mesh, chunks=2)
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        data = SyntheticLMData(cfg, 32, 4)
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
        with jax.set_mesh(mesh):
            step = jax.jit(make_train_step(cfg, ctx, lr=1e-3))
            state, m = step(state, batch)
            state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))
        print("MESH TRAIN OK", float(m["loss"]))
    """, devices=8)
    assert "MESH TRAIN OK" in out


def test_dryrun_small_mesh_lowers_and_compiles():
    """The dry-run machinery end-to-end on a small mesh for one arch/shape
    per mode (train/prefill/decode)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch import dryrun_lib as lib
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        for arch, shape in [("mixtral-8x7b", "train_4k"),
                            ("gemma3-27b", "prefill_32k"),
                            ("mamba2-130m", "decode_32k")]:
            # full configs on 4 devices: lower only (compiling is the sweep's job)
            lowered, meta = lib.lower_combo(arch, shape, mesh)
            txt = lowered.as_text()
            assert "main" in txt
            print("LOWERED", arch, shape)
        print("DRYRUN-SMALL OK")
    """, devices=4, timeout=900)
    assert "DRYRUN-SMALL OK" in out


def test_multipod_mesh_axes():
    out = run_py("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
        assert m2.devices.shape == (2, 16, 16) and m2.axis_names == ("pod", "data", "model")
        print("MESH OK")
    """, devices=512)
    assert "MESH OK" in out


def test_ragged_ep_equals_per_expert_ep():
    """The MegaBlocks-style ragged buffers (+ Pallas interpret kernels) give
    identical outputs/grads to the per-expert buffer EP path on a mesh."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import moe as M
        from repro.configs.base import MoEConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=64)
        params = M.init_moe(jax.random.PRNGKey(0), 32, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        ctxs = {
          "ep": M.DistContext(mesh=mesh, moe_chunks=2, moe_strategy="ep_shardmap"),
          "ragged": M.DistContext(mesh=mesh, moe_chunks=2,
                                  moe_strategy="ep_shardmap", moe_ragged=True),
          "ragged_pallas": M.DistContext(mesh=mesh, moe_chunks=2,
                                         moe_strategy="ep_shardmap",
                                         moe_ragged=True, use_pallas=True,
                                         pallas_interpret=True),
        }
        ys = {}
        with jax.set_mesh(mesh):
            for name, ctx in ctxs.items():
                y, s = jax.jit(lambda p, x, c=ctx: M.moe_ffn(p, x, cfg, c))(params, x)
                ys[name] = np.asarray(y)
                assert float(s["drops"]) == 0.0, name
            g1 = jax.jit(jax.grad(lambda p: M.moe_ffn(p, x, cfg, ctxs["ragged_pallas"])[0].sum()))(params)
        g2 = jax.grad(lambda p: M.moe_ffn(p, x, cfg, M.DistContext(moe_chunks=2))[0].sum())(params)
        assert np.abs(ys["ragged"] - ys["ep"]).max() < 1e-5
        assert np.abs(ys["ragged_pallas"] - ys["ep"]).max() < 1e-5
        errs = [np.abs(np.asarray(a) - np.asarray(b)).max()
                for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2))]
        assert max(errs) < 1e-4, errs
        print("RAGGED-EP OK")
    """, devices=4)
    assert "RAGGED-EP OK" in out
