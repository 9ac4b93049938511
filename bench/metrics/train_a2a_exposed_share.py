"""Share of the traced part of the window in which a chip ran an
all-to-all operation and no other operation, in %, averaged over the chips
(``bench/collectives.py``).  A trace with no all-to-all gives nothing."""
from bench.metrics import _train


def read(run):
    if not _train.is_train(run) or run.a2a_exposed_share is None:
        return None
    return 100.0 * run.a2a_exposed_share
