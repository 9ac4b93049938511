"""Mean FCDA chunk count MACT chose over the window's steps (the trainer's
``chunk_trace``)."""
from bench.metrics import _train


def read(run):
    if not _train.is_train(run) or not run.steps:
        return None
    return sum(s[2] for s in run.steps) / len(run.steps)
