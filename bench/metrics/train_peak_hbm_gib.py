"""Highest ``memory_stats()["peak_bytes_in_use"]`` of the cell's chips over
the run (set-up included), read before the reference runs, in GiB."""
from bench.metrics import _train


def read(run):
    if not _train.is_train(run) or not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2**30
