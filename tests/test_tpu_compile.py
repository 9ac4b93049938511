"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e, at
mixtral-8x7b widths (8 experts, d_model 4096, d_ff_expert 14336).

Nothing runs: the TPU compiler installed with jax compiles for a described
``v5e:2x2`` topology whose chips are not attached, so a kernel the chip's
compiler refuses (tiling, VMEM, lowering) fails here and costs no chip
time.  Interpret mode, which the other kernel tests use, checks none of
that.  A kernel the compiler refuses is a strict xfail quoting the
compiler's reason, and stays off every chip path.

The topology is described inside a fixture, never while a module is being
imported: only one process may load the TPU library at a time, and test
collection happens in every worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest

E, D, F = 8, 4096, 14336        # mixtral-8x7b experts, d_model, d_ff_expert
ROWS = 128                      # rows per expert (grouped) / row block
R = 1024                        # ragged / dispatch rows: 8 blocks of 128
T = 512                         # tokens of one dispatch chunk (top-2 -> R)
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler: skip the file
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name: str, dtype):
    """(fn, [(shape, dtype)]) of one kernel call at mixtral widths."""
    from repro.kernels import dispatch_pallas as dp
    from repro.kernels.fused_moe import fused_moe
    from repro.kernels.grouped_mlp import grouped_matmul, grouped_swiglu
    from repro.kernels.ragged_mlp import ragged_matmul, ragged_swiglu

    i32 = jnp.int32
    nb = R // ROWS
    cases = {
        "grouped_swiglu": (grouped_swiglu,
                           [((E, ROWS, D), dtype), ((E, D, F), dtype),
                            ((E, D, F), dtype)]),
        "grouped_matmul": (grouped_matmul,
                           [((E, ROWS, F), dtype), ((E, F, D), dtype)]),
        "ragged_swiglu": (
            lambda x, w1, w3, b, r: ragged_swiglu(x, w1, w3, b, r,
                                                  block_m=ROWS),
            [((R, D), dtype), ((E, D, F), dtype), ((E, D, F), dtype),
             ((nb,), i32), ((), i32)]),
        "ragged_matmul": (
            lambda x, w, b, r: ragged_matmul(x, w, b, r, block_m=ROWS),
            [((R, F), dtype), ((E, F, D), dtype), ((nb,), i32), ((), i32)]),
        "scatter_rows": (dp.scatter_rows,
                         [((T, D), dtype), ((R,), i32), ((), i32)]),
        "gather_combine": (dp.gather_combine,
                           [((R, D), dtype), ((T, 2), i32), ((T, 2), dtype)]),
        "fused_moe": (fused_moe,
                      [((T, D), dtype), ((E, D, F), dtype), ((E, D, F), dtype),
                       ((E, F, D), dtype), ((R,), i32), ((R,), dtype),
                       ((), i32), ((nb,), i32)]),
    }
    return cases[name]


_ROW_LOAD = ("Mosaic: 'cannot statically prove that index in dimension 0 is "
             "a multiple of 8' — the one-row dynamic VMEM load of a packed "
             "bf16 row")
_WHOLE_BUF = ("RESOURCE_EXHAUSTED: 'Ran out of memory in memory space vmem' "
              "— the whole (R, d) f32 source buffer is one VMEM block "
              "(16.25M of a 16.00M scoped limit at R=1024)")
_FUSED = ("Pallas TPU lowering: 'Unimplemented primitive ... dynamic_slice' "
          "(ROADMAP S4: fused_moe also blocks the full f axis in VMEM)")


@pytest.mark.parametrize("name,dtype", [
    ("grouped_swiglu", BF16),
    ("grouped_matmul", BF16),
    ("ragged_swiglu", BF16),
    ("ragged_matmul", BF16),
    ("ragged_swiglu", jnp.float32),          # the f32 training path
    ("ragged_matmul", jnp.float32),
    pytest.param("scatter_rows", BF16,
                 marks=pytest.mark.xfail(strict=True, reason=_ROW_LOAD)),
    pytest.param("gather_combine", BF16,
                 marks=pytest.mark.xfail(strict=True, reason=_ROW_LOAD)),
    ("scatter_rows", jnp.float32),
    pytest.param("gather_combine", jnp.float32,
                 marks=pytest.mark.xfail(strict=True, reason=_WHOLE_BUF)),
    pytest.param("fused_moe", BF16,
                 marks=pytest.mark.xfail(strict=True, reason=_FUSED)),
])
def test_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, specs = _kernel_case(name, dtype)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # a kernel, not XLA
