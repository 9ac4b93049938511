"""Operations and bytes the published model needs, from a configuration file's
sizes (Hugging Face key names).

Only the work the model itself requires is counted: the two routed experts of
each token (top-k, not every expert and not a padded capacity), the router,
the attention projections, causal attention scores over the context each token
actually sees (capped by the sliding window), and the LM head only where a
logit is needed.  Recomputation, capacity padding and the dense copies a
program may make are not model work, so a program that does less of them can
only read higher, and a share of a peak built on these counts cannot pass 100%
unless the timing leaves out work.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": kv, "hd": hd,
            "f": cfg["intermediate_size"], "e": cfg["num_local_experts"],
            "k": cfg["num_experts_per_tok"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"],
            "window": cfg.get("sliding_window") or 0}


def layer_flops_per_token(cfg: dict, context: int) -> float:
    """Forward FLOPs of one decoder layer for one token that attends to
    ``context`` positions (itself included; the window caps it)."""
    m = _dims(cfg)
    if m["window"]:
        context = min(context, m["window"])
    proj = 2 * m["d"] * (2 * m["h"] * m["hd"] + 2 * m["kv"] * m["hd"])
    scores = 2 * 2 * m["h"] * m["hd"] * context        # QK^T and PV
    router = 2 * m["d"] * m["e"]
    experts = m["k"] * 3 * 2 * m["d"] * m["f"]          # SwiGLU: w1, w3, w2
    return float(proj + scores + router + experts)


def head_flops(cfg: dict) -> float:
    """LM-head FLOPs for one token whose logits are needed."""
    m = _dims(cfg)
    return float(2 * m["d"] * m["v"])


def _context_sum(start: int, stop: int, window: int) -> int:
    """Sum over positions p in [start, stop) of the keys p sees: p + 1,
    capped at ``window`` (0 = no window)."""
    def upto(n):                       # sum_{p<n} min(p + 1, window)
        if not window or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(stop) - upto(start)


def prefill_flops(cfg: dict, start: int, stop: int, logits: int = 0) -> float:
    """Forward FLOPs of prompt positions ``[start, stop)`` (position p sees
    p + 1 keys) through every layer, plus the head for ``logits`` tokens."""
    m = _dims(cfg)
    n = stop - start
    per_layer = (layer_flops_per_token(cfg, 0) * n
                 + 2 * 2 * m["h"] * m["hd"]
                 * _context_sum(start, stop, m["window"]))
    return float(m["layers"] * per_layer + logits * head_flops(cfg))


def weight_bytes(cfg: dict, bytes_per_param: float = 2.0,
                 experts_read: int | None = None) -> float:
    """Bytes of the decoder's weights (attention, router, experts, norms) plus
    the LM head; ``experts_read`` experts per layer (default all of them).
    The embedding table is excluded: a step reads only its rows."""
    m = _dims(cfg)
    e = m["e"] if experts_read is None else experts_read
    attn = m["d"] * (2 * m["h"] * m["hd"] + 2 * m["kv"] * m["hd"])
    experts = e * 3 * m["d"] * m["f"]
    router = m["d"] * m["e"]
    norms = 2 * m["d"]
    head = m["d"] * m["v"]
    return float(bytes_per_param * (m["layers"] * (attn + experts + router
                                                   + norms) + head + m["d"]))
