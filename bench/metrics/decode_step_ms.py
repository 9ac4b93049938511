"""Median host span of the window's scheduler steps that ran a decode wave
and no prefill chunk."""
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run):
        return None
    v = _serve.pct([t1 - t0 for t0, t1, chunk, dec in _serve.window_steps(run)
                    if dec and chunk is None], 50)
    return None if v is None else 1e3 * v
