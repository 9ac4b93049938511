"""Highest ``memory_stats()["peak_bytes_in_use"]`` of the chip over the run
(set-up included), read before the reference runs, in GiB."""
from bench.metrics import _serve


def read(run):
    if not _serve.is_serve(run) or not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2**30
