"""Output tokens delivered in the window over the window's seconds."""
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run):
        return None
    return len(_serve.in_window_token_times(run)) / run.seconds
