"""99th percentile of every gap between consecutive output tokens of a
request, over the gaps that closed in the window."""
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run):
        return None
    v = _serve.pct(_serve.gaps_s(run), 99)
    return None if v is None else 1e3 * v
