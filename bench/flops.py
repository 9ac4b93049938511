"""Operations and bytes the published model needs, from a configuration
file's sizes.

The counts are the architecture's, so they live in its adapter
(``bench/arch/<model_type>.py``: ``prefill_flops``, ``weight_bytes``); this
module looks them up for a configuration and keeps the helpers that hold for
every architecture.  Where the adapter gives no count, the lookup returns
None, and a metric built on it is left out of the result, never read as 0.
"""

from __future__ import annotations

from bench import arch


def context_sum(start: int, stop: int, window: int) -> int:
    """Sum over positions p in [start, stop) of the keys p sees: p + 1,
    capped at ``window`` (0 = no window)."""
    def upto(n):                       # sum_{p<n} min(p + 1, window)
        if not window or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(stop) - upto(start)


def _count(cfg: dict, name: str):
    return getattr(arch.adapter(cfg), name, None)


def prefill_flops(cfg: dict, start: int, stop: int, logits: int = 0):
    """Forward FLOPs of prompt positions ``[start, stop)`` through every
    layer, plus the head for ``logits`` tokens; None where the adapter
    counts none."""
    f = _count(cfg, "prefill_flops")
    return None if f is None else f(cfg, start, stop, logits=logits)


def weight_bytes(cfg: dict, bytes_per_param: float = 2.0,
                 experts_read: int | None = None):
    """Bytes of the weights a decode step reads (``experts_read`` experts a
    layer, default all); None where the adapter counts none."""
    f = _count(cfg, "weight_bytes")
    return None if f is None else f(cfg, bytes_per_param, experts_read)
