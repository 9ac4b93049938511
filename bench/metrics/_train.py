"""Shared arithmetic of the training metric readers (not a metric itself)."""

from __future__ import annotations


def is_train(run) -> bool:
    return getattr(run, "kind", None) == "train"


def window_s(run) -> float:
    w0, w1 = run.window
    return w1 - w0
