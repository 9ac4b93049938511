"""Share of the traced part of the window in which no operation ran on a
chip, in %: 1 - (union of device op intervals / traced window), averaged
over the chips."""
from bench.metrics import _train


def read(run):
    if not _train.is_train(run) or run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
