"""99th percentile of how late the load generator submitted a request
after it was due: the single-threaded loop submits only between steps."""
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run):
        return None
    v = _serve.pct([run.submitted[r] - run.due[r] for r in run.in_window
                    if r in run.submitted], 99)
    return None if v is None else 1e3 * v
