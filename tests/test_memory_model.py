"""Validate the theoretical memory model against the paper's Table 4 and the
MACT equations (Eq. 8-9), plus hypothesis property checks."""

import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import GPU_64G, TPU_V5E, get_config
from repro.core import memory_model as mm
from repro.core.mact import MACTController

# Paper §5 experimental setup: t=1, p=4, e=32, d=1, c=1, b=1, s=4096, bf16.
PAPER_PAR = mm.Parallelism(t=1, p=4, c=1, e=32, d=1, b=1)
# docs/DESIGN.md calibration: the s'' behind the paper's 22.9 GB activation figure.
CALIBRATED_S_PP = 5.97e5


@pytest.fixture(scope="module")
def model_i():
    return get_config("deepseek-mini-16l")


def test_paper_reduction_ratios(model_i):
    """Table 4: c=8 -> -83.84%, MACT c=2 -> -48.03% activation memory.
    Our model reproduces both within 2.5 points (paper omits h_d/k_a/e_n)."""
    dims = mm.LayerDims.from_config(model_i)
    base = mm.activation_bytes(dims, 4096, CALIBRATED_S_PP, PAPER_PAR, chunks=1)
    red2 = 1 - mm.activation_bytes(dims, 4096, CALIBRATED_S_PP, PAPER_PAR,
                                   chunks=2) / base
    red8 = 1 - mm.activation_bytes(dims, 4096, CALIBRATED_S_PP, PAPER_PAR,
                                   chunks=8) / base
    assert abs(red2 - 0.4803) < 0.025, red2
    assert abs(red8 - 0.8384) < 0.025, red8


def test_paper_activation_magnitude(model_i):
    """Method 1 activation ~22.9 GB (we land within 15% with MHA-for-MLA)."""
    dims = mm.LayerDims.from_config(model_i)
    act = mm.activation_bytes(dims, 4096, CALIBRATED_S_PP, PAPER_PAR, chunks=1)
    assert 19e9 < act < 26e9, act / 1e9


def test_mact_reproduces_paper_chunk_choice(model_i):
    """With the paper's measured static memory (43 GB) on 64 GB GPUs, MACT
    derives c*=2 for the observed distribution — exactly Table 4 Method 3."""
    mact = MACTController(model_i, PAPER_PAR, GPU_64G, seq_len=4096,
                          static_override=43e9)
    c = mact.optimal_c(CALIBRATED_S_PP)
    assert c == 2
    assert mact.snap(c) == 2


def test_mact_cold_start_is_conservative(model_i):
    mact = MACTController(model_i, PAPER_PAR, GPU_64G, seq_len=4096,
                          static_override=43e9)
    cold = mact.choose()            # worst case s' -> e*s*k
    informed = mact.snap(mact.optimal_c(CALIBRATED_S_PP))
    assert cold >= informed


def test_eq8_inverts_eq2(model_i):
    """s'_max is exactly the s' at which Eq. 2 meets the budget (Eq. 3)."""
    dims = mm.LayerDims.from_config(model_i)
    static = 43e9
    smax = mm.s_prime_max(dims, 4096, PAPER_PAR, GPU_64G, static)
    act = mm.activation_bytes(dims, 4096, smax, PAPER_PAR, chunks=1)
    assert math.isclose(static + act, GPU_64G.alpha * GPU_64G.hbm_bytes,
                        rel_tol=1e-6)


def test_worst_case_s_prime(model_i):
    wc = mm.worst_case_s_prime(4096, PAPER_PAR, topk=8)
    assert wc == 32 * 4096 * 8      # e * s * k (b=1)


def test_static_memory_model_vs_paper(model_i):
    """Eq. 1 static memory: our param-count model lands in the right decade
    and Model I > Model II (can't invert exactly — MLA internals unknown)."""
    s16 = mm.static_bytes(model_i, PAPER_PAR)
    s8 = mm.static_bytes(get_config("deepseek-mini-8l"), PAPER_PAR)
    assert 30e9 < s16 < 90e9
    assert s8 < s16


def test_snap_picks_covering_bin(model_i):
    mact = MACTController(model_i, PAPER_PAR, GPU_64G, seq_len=4096,
                          static_override=43e9)
    assert mact.snap(1) == 1
    assert mact.snap(3) == 4
    assert mact.snap(8) == 8
    assert mact.snap(100) == 8       # none covers -> largest bin


@given(s_pp=st.floats(1, 1e7), chunks=st.integers(1, 64))
@settings(max_examples=40, deadline=None)
def test_activation_monotonicity(s_pp, chunks):
    """More chunks never increases the modeled activation; more received
    tokens never decreases it."""
    cfg = get_config("deepseek-mini-16l")
    dims = mm.LayerDims.from_config(cfg)
    a1 = mm.activation_bytes(dims, 4096, s_pp, PAPER_PAR, chunks=chunks)
    a2 = mm.activation_bytes(dims, 4096, s_pp, PAPER_PAR, chunks=chunks + 1)
    a3 = mm.activation_bytes(dims, 4096, s_pp * 2, PAPER_PAR, chunks=chunks)
    assert a2 <= a1 + 1e-6
    assert a3 >= a1 - 1e-6


@given(s_pp=st.floats(1e3, 1e7))
@settings(max_examples=30, deadline=None)
def test_eq9_chunk_count_sufficient(s_pp):
    """The chunk count from Eq. 9 always brings the per-chunk token count
    under s'_max (the defining property of MACT)."""
    cfg = get_config("deepseek-mini-16l")
    mact = MACTController(cfg, PAPER_PAR, GPU_64G, seq_len=4096,
                          static_override=43e9)
    smax = mact.s_prime_max()
    c = mm.optimal_chunks(s_pp, smax)
    if c < (1 << 30):
        assert s_pp / c <= smax + 1e-6
        if c > 1:                    # and c is minimal
            assert s_pp / (c - 1) > smax


def test_params_active_vs_total():
    cfg = get_config("mixtral-8x7b")
    total = mm.total_params(cfg)
    active = mm.active_params(cfg)
    assert 40e9 < total < 52e9       # Mixtral ~47B
    assert 10e9 < active < 16e9      # ~13B active


@pytest.mark.parametrize("kind,want", [("TPU v5 lite", TPU_V5E),
                                       ("cpu", TPU_V5E), ("TPU v4", None)])
def test_device_profile_by_device_kind(kind, want):
    """The memory model plans against the device JAX reports; a kind with
    no profile is an error, never a default."""
    from repro.configs import device_profile
    if want is None:
        with pytest.raises(ValueError, match="no hardware profile"):
            device_profile(kind)
    else:
        assert device_profile(kind) is want
