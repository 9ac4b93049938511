"""Divisibility-guarded sharding rules (subprocess: needs a real mesh)."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_param_shardings_guarded():
    src = textwrap.dedent("""
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=16'
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.models import transformer
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 8), ("data", "model"))
        for arch in ("mixtral-8x7b", "whisper-small", "jamba-1.5-large-398b"):
            cfg = get_config(arch).reduced()
            params = jax.eval_shape(
                lambda k: transformer.init_params(k, cfg),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            sh = shd.param_shardings(params, mesh, cfg)
            # every sharded dim divides its axis product
            for leaf, s in zip(jax.tree.leaves(params), jax.tree.leaves(sh)):
                spec = list(s.spec) + [None] * (len(leaf.shape) - len(s.spec))
                for dim, ax in zip(leaf.shape, spec):
                    if ax is not None:
                        n = shd.axis_size(mesh, ax)
                        assert dim % n == 0, (arch, leaf.shape, s.spec)
            print("OK", arch)
        # cache pspec: batch-shardable, stacked, and long-context cases
        # (PartitionSpec normalises 1-tuples to bare names)
        assert shd.cache_pspec(mesh, (8, 128, 4, 16), 8)[0] == "data"
        assert shd.cache_pspec(mesh, (3, 8, 128, 4, 16), 8)[1] == "data"
        assert shd.cache_pspec(mesh, (1, 1024, 4, 16), 1)[1] == "data"
        print("CACHE OK")
    """)
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr
    assert "CACHE OK" in out.stdout
