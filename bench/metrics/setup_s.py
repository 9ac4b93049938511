"""Seconds from process start to the opening of the measured window:
imports, weights, compilation (or the compile-cache reads), warm-up
requests and the lead-in traffic."""


def read(run):
    return run.setup_s or None
