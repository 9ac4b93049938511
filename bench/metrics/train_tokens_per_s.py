"""Tokens trained in the window (global batch x sequence length x the
window's steps) over the window's seconds on the host clock, from its
opening at a step boundary to the return of its last step."""
from bench.metrics import _train


def read(run):
    if not _train.is_train(run) or not run.steps:
        return None
    return (run.global_batch * run.seq_len * len(run.steps)
            / _train.window_s(run))
