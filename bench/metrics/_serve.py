"""Shared arithmetic of the serving metric readers (not a metric itself)."""

from __future__ import annotations

import numpy as np


def is_serve(run) -> bool:
    return getattr(run, "kind", None) == "serve"


def ttft_s(run) -> list:
    """Time to first token of every request due in the window, from when
    it was due.  One with no first token counts at least until the end of
    the drain."""
    out = []
    for rid in run.in_window:
        st = run.stamps[rid]
        out.append((st[0] if st else run.drain_end) - run.due[rid])
    return out


def in_window_token_times(run) -> list:
    w0, w1 = run.window
    return [t for st in run.stamps.values() for t in st if w0 <= t < w1]


def gaps_s(run) -> list:
    """Every gap between consecutive output tokens of one request whose
    later token came in the window."""
    w0, w1 = run.window
    out = []
    for st in run.stamps.values():
        for a, b in zip(st, st[1:]):
            if w0 <= b < w1:
                out.append(b - a)
    return out


def window_steps(run) -> list:
    w0, w1 = run.window
    return [s for s in run.steps if w0 <= s[0] < w1]


def pct(values, q: float):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


#: the module of the paged decode wave (and its ``_loads`` twin)
DECODE_WAVE = "jit_serve_decode_wave"


def decode_scope_ms(run, scopes: tuple):
    """Device time of the traced decode waves' ops under any of the program
    scopes ``scopes`` (``bench/trace.py``'s ``scoped``), per wave, in ms;
    None where the trace holds no wave or no op under them."""
    if not is_serve(run) or run.trace is None:
        return None
    waves = run.trace.executions(DECODE_WAVE)
    spent = run.trace.scope_s(DECODE_WAVE, scopes)
    if not waves or spent <= 0:
        return None
    return 1e3 * spent / waves
