"""90th percentile of time to first token over every request due in the
window, timed from when it was due (linear interpolation)."""
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run):
        return None
    v = _serve.pct(_serve.ttft_s(run), 90)
    return None if v is None else 1e3 * v
