"""The exposed share of a collective in a profiler trace: the part of the
traced window in which a chip ran the collective's operations and no other
operation, averaged over the chips (``bench/trace.py``'s ``Profile``, the
same ``bench.window`` span).  A trace with no such operation gives None.

A device's ``XLA Ops`` line nests events: a ``while`` or ``conditional``
op spans the ops of its body.  Only the innermost events (those that hold
no other event) count, so that the loop around a collective is not read as
work that hides it."""

from __future__ import annotations

from bench import trace

ALL_TO_ALL = "all-to-all"


def innermost(ops) -> list:
    """The events of ``ops`` ([(name, start, end)]) that hold no other."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    holds = [False] * len(ops)
    open_ = []                   # indices of events that may hold the next
    for i in order:
        _, s, e = ops[i]
        while open_ and ops[open_[-1]][2] <= s:
            open_.pop()
        if open_ and ops[open_[-1]][2] >= e:
            holds[open_[-1]] = True
        open_.append(i)
    return [op for op, h in zip(ops, holds) if not h]


def exposed_share(prof: trace.Profile, name: str = ALL_TO_ALL):
    """Share of the window, in [0, 1], in which an innermost operation whose
    name holds ``name`` ran on a chip and no other innermost operation did,
    mean over chips."""
    win = [(s, e) for n, s, e in prof.spans if n == trace.WINDOW_SPAN]
    if not win:
        return None
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    found, shares = False, []
    for ops in prof.device_ops.values():
        leaves = innermost(ops)
        coll = [(s, e) for n, s, e in leaves if name in n]
        rest = [(s, e) for n, s, e in leaves if name not in n]
        found = found or bool(coll)
        other = trace.total(trace.clip(trace.merge(rest), lo, hi))
        both = trace.total(trace.clip(trace.merge(coll + rest), lo, hi))
        shares.append((both - other) / (hi - lo))
    if not found:
        return None
    return sum(shares) / len(shares)
