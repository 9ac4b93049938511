"""FLOP and byte counts, through the Mixtral adapter (``bench/arch/
mixtral.py``) and the lookup in ``bench/flops.py``, against hand counts at
Mixtral-8x7B widths and against the formulas the benchmark used before the
counts moved into the adapter; and the peaks table."""

import json
import os

import pytest

from bench import arch, flops, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MIXTRAL = {"model_type": "mixtral",
           "hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 4, "num_attention_heads": 32,
           "num_key_value_heads": 8, "num_local_experts": 8,
           "num_experts_per_tok": 2, "vocab_size": 32000,
           "sliding_window": 4096}
CONFIG_FILES = ("mixtral-8x7b-l4-serve.json", "mixtral-8x7b-l1-ep4-train.json")


def _adapter():
    return arch.adapter(MIXTRAL)


def test_layer_flops_count_routed_experts_only():
    # q, o: 4096x4096 each; k, v: 4096x1024 each; 2 FLOPs per MAC
    proj = 2 * 4096 * (4096 + 1024 + 1024 + 4096)
    scores = 4 * 32 * 128 * 10           # QK^T and PV over 10 keys
    router = 2 * 4096 * 8
    experts = 2 * (3 * 2 * 4096 * 14336)  # top-2, SwiGLU's three matmuls
    assert _adapter().layer_flops_per_token(MIXTRAL, 10) == pytest.approx(
        proj + scores + router + experts, rel=0, abs=0)


def test_window_caps_the_context():
    m = _adapter()
    short = m.layer_flops_per_token(MIXTRAL, 4096)
    assert m.layer_flops_per_token(MIXTRAL, 10_000) == short


def test_prefill_flops_sum_per_position_and_head_once():
    m = _adapter()
    want = sum(m.layer_flops_per_token(MIXTRAL, p + 1)
               for p in range(256, 512)) * 4 + 2 * 4096 * 32000
    assert flops.prefill_flops(MIXTRAL, 256, 512, logits=1) == \
        pytest.approx(want, rel=1e-12)
    # past the window every position sees exactly 4096 keys
    win = flops.prefill_flops(MIXTRAL, 4096, 4100)
    assert win == pytest.approx(
        4 * 4 * m.layer_flops_per_token(MIXTRAL, 4096), rel=1e-12)


def test_weight_bytes_at_mixtral_widths():
    per_layer = (4096 * (4096 + 1024 + 1024 + 4096)   # attention
                 + 8 * 3 * 4096 * 14336                 # experts
                 + 4096 * 8 + 2 * 4096)                 # router, norms
    want = 2 * (4 * per_layer + 4096 * 32000 + 4096)    # + head, final norm
    assert flops.weight_bytes(MIXTRAL) == want
    # the decode floor of the 4-layer cut: about 11.9 GB at 819 GB/s
    assert 14.0e-3 < want / peaks.peaks_for("TPU v5 lite").hbm_bytes_per_s \
        < 15.0e-3
    assert flops.weight_bytes(MIXTRAL, experts_read=2) < want


# -- the formulas as they stood in bench/flops.py before the adapters ---------

def _dims_before(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "e": cfg["num_local_experts"],
            "k": cfg["num_experts_per_tok"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"],
            "window": cfg.get("sliding_window") or 0}


def _layer_before(cfg, context):
    m = _dims_before(cfg)
    if m["window"]:
        context = min(context, m["window"])
    proj = 2 * m["d"] * (2 * m["h"] * m["hd"] + 2 * m["kv"] * m["hd"])
    scores = 2 * 2 * m["h"] * m["hd"] * context
    router = 2 * m["d"] * m["e"]
    experts = m["k"] * 3 * 2 * m["d"] * m["f"]
    return float(proj + scores + router + experts)


def _context_sum_before(start, stop, window):
    def upto(n):
        if not window or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(stop) - upto(start)


def _prefill_before(cfg, start, stop, logits=0):
    m = _dims_before(cfg)
    per_layer = (_layer_before(cfg, 0) * (stop - start)
                 + 2 * 2 * m["h"] * m["hd"]
                 * _context_sum_before(start, stop, m["window"]))
    return float(m["layers"] * per_layer
                 + logits * float(2 * m["d"] * m["v"]))


def _weight_bytes_before(cfg, bytes_per_param=2.0, experts_read=None):
    m = _dims_before(cfg)
    e = m["e"] if experts_read is None else experts_read
    attn = m["d"] * (2 * m["h"] * m["hd"] + 2 * m["kv"] * m["hd"])
    experts = e * 3 * m["d"] * m["f"]
    return float(bytes_per_param * (m["layers"] * (
        attn + experts + m["d"] * m["e"] + 2 * m["d"])
        + m["d"] * m["v"] + m["d"]))


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIG_FILES + ("window",))
@pytest.mark.parametrize("start,stop,logits", [
    (0, 256, 0), (256, 512, 1), (1000, 1256, 0), (0, 4096, 4096),
    (3840, 4096, 1), (4096, 4352, 1)])
def test_counts_equal_the_formulas_before_the_adapters(name, start, stop,
                                                       logits):
    cfg = MIXTRAL if name == "window" else _config(name)
    assert flops.prefill_flops(cfg, start, stop, logits=logits) == \
        _prefill_before(cfg, start, stop, logits)
    assert flops.weight_bytes(cfg) == _weight_bytes_before(cfg)
    assert flops.weight_bytes(cfg, 1.0, experts_read=3) == \
        _weight_bytes_before(cfg, 1.0, experts_read=3)


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
