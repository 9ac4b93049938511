"""Unit tests for the scan-aware HLO analyzer (launch/hlo_analysis.py)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H


def _analyse(f, *args):
    txt = jax.jit(f).lower(*args).compile().as_text()
    return H.analyse_module(txt)


def test_flops_single_matmul():
    x = jnp.ones((64, 128))
    w = jnp.ones((128, 32))
    r = _analyse(lambda a, b: a @ b, x, w)
    assert r["flops"] == 2 * 64 * 128 * 32


def test_flops_scan_weighted_by_trip_count():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y @ w
    x = jnp.ones((32, 32))
    r = _analyse(f, x, jnp.ones((32, 32)))
    assert r["flops"] == 2 * 32 ** 3 * 8        # 7 in-loop + 1 outside


def test_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=2)
        return y
    r = _analyse(f, jnp.ones((16, 16)), jnp.ones((16, 16)))
    assert r["flops"] == 2 * 16 ** 3 * 6        # 2 x 3 matmuls


def test_shape_bytes():
    assert H.shape_bytes("bf16[8,128]") == 8 * 128 * 2
    assert H.shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert H.shape_bytes("pred[3]") == 3


def test_collectives_counted_with_trips():
    import os
    import subprocess
    import sys
    import textwrap
    # needs >1 device -> subprocess
    src = textwrap.dedent("""
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo_analysis as H
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ('m',))
        def f(x):
            def body(c, _):
                s = jax.lax.with_sharding_constraint(c.sum(0, keepdims=True),
                                                     NamedSharding(mesh, P()))
                return c + s, None
            y, _ = jax.lax.scan(body, x, None, length=5)
            return y.sum()
        xs = jax.ShapeDtypeStruct((16, 64), jnp.float32,
                                  sharding=NamedSharding(mesh, P('m', None)))
        with jax.set_mesh(mesh):
            txt = jax.jit(f).lower(xs).compile().as_text()
        r = H.analyse_module(txt)
        print('COLL', r['collective_total'])
        assert r['collective_total'] > 0
    """)
    env = {**os.environ, "PYTHONPATH": "src"}
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=300, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr
    assert "COLL" in out.stdout
