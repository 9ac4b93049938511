"""The one request generator: it reads a traffic mix's parameters from
``bench/traffic/<mix>.json`` and turns them, with the run's seed, into an
open-loop schedule of requests.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (stratified quantiles of the stated distributions), in
the one order that the mix's ``schedule_seed`` draws (with shared prefixes,
each request keeps its prefix too); the run's seed draws only the token ids.
A window of a few dozen requests then does the same work on every seed.

Arrivals (Poisson in shape, after ``launch/serve.py::make_trace``) are
placed in ``[-lead_in_s, seconds)``; the measured window is ``[0, seconds)``
and the lead-in fills the server before it opens.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
import numpy as np


@dataclass
class Req:
    rid: int
    due: float                 # seconds after the window opens (< 0: lead-in)
    tokens: np.ndarray         # (S,) int32 prompt
    max_new_tokens: int
    prefix: int = -1           # shared-prefix index, -1 = none


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """Any non-negative whole number, however large, keys its own stream."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.SeedSequence(seed)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(spec: dict, n: int) -> np.ndarray:
    """n stratified quantiles of a lognormal (median, sigma), clipped to
    [min, max] and rounded up to ``multiple_of``."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = spec["lognormal_median"] * np.exp(spec["lognormal_sigma"] * z)
    m = spec.get("multiple_of", 1)
    x = np.ceil(x / m) * m
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _arrivals(rate: float, n: int, span: float, rng) -> np.ndarray:
    """n arrival offsets in [0, span): stratified exponential gaps, shuffled,
    scaled so that the mean rate is exactly n / span."""
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps = rng.permutation(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * span / gaps.sum()


def _zipf_counts(k: int, s: float, n: int) -> np.ndarray:
    """Counts of n draws over k items with Zipf(s) popularity, apportioned
    by largest remainder so that they sum to n."""
    p = 1.0 / np.arange(1, k + 1) ** s
    p /= p.sum()
    raw = p * n
    c = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - c))[: n - c.sum()]:
        c[i] += 1
    return c


def _split_counts(options: list, n: int) -> list:
    base = [n // len(options)] * len(options)
    for i in range(n - sum(base)):
        base[i] += 1
    return [o for o, c in zip(options, base) for _ in range(c)]


class Traffic:
    """A generated schedule: ``warmup`` requests served in set-up, then
    ``requests`` due at their times (lead-in first, window after)."""

    def __init__(self, spec: dict, seed: int, seconds: float, vocab: int,
                 chunk: int):
        self.spec = spec
        self.seconds = float(seconds)
        rng = np.random.default_rng(seed_sequence(seed))
        order = np.random.default_rng(seed_sequence(spec["schedule_seed"]))
        self.prefixes: list = []
        sp = spec.get("shared_prefixes")
        if sp:
            if sp["tokens"] % chunk:
                raise ValueError(f"prefix of {sp['tokens']} tokens is not a "
                                 f"multiple of the prefill chunk {chunk}")
            self.prefixes = [rng.integers(0, vocab, sp["tokens"]).astype(
                np.int32) for _ in range(sp["count"])]
        rate = float(spec["rate_per_s"])
        lead = float(spec.get("lead_in_s", 0.0))
        n_lead = int(round(rate * lead))
        n_win = max(1, int(round(rate * self.seconds)))
        reqs = []
        for span, n, off in ((lead, n_lead, -lead), (self.seconds, n_win, 0.0)):
            due = _arrivals(rate, n, span, order) + off
            reqs += self._make(n, due, rng, order, vocab, chunk)
        for i, r in enumerate(reqs):
            r.rid = i
        self.requests = reqs
        self.warmup = self._warmup(spec.get("warmup", {}), rng, vocab,
                                   len(reqs))

    def _make(self, n: int, due, rng, order, vocab: int, chunk: int) -> list:
        """``order`` draws the order of the sizes, ``rng`` the token ids."""
        spec = self.spec
        outs = order.permutation(lognormal_sizes(spec["output_tokens"], n))
        sp = spec.get("shared_prefixes")
        out = []
        if sp:
            which = order.permutation(np.repeat(
                np.arange(sp["count"]),
                _zipf_counts(sp["count"], sp["zipf_exponent"], n)))
            suffix = order.permutation(_split_counts(sp["suffix_tokens"], n))
            for i in range(n):
                if suffix[i] % chunk:
                    raise ValueError(f"suffix {suffix[i]} is not a multiple "
                                     f"of the prefill chunk {chunk}")
                toks = np.concatenate([
                    self.prefixes[which[i]],
                    rng.integers(0, vocab, suffix[i]).astype(np.int32)])
                out.append(Req(0, float(due[i]), toks, int(outs[i]),
                               int(which[i])))
        else:
            if spec["prompt_tokens"].get("multiple_of", 1) % chunk:
                raise ValueError("prompt lengths must be multiples of the "
                                 f"prefill chunk {chunk}")
            lens = order.permutation(lognormal_sizes(spec["prompt_tokens"],
                                                     n))
            for i in range(n):
                out.append(Req(0, float(due[i]),
                               rng.integers(0, vocab, lens[i]).astype(
                                   np.int32), int(outs[i])))
        return out

    def _warmup(self, spec: dict, rng, vocab: int, rid0: int) -> list:
        """Set-up requests: one per shared prefix (so the trie starts warm)
        followed by ``extra`` plain ones of ``prompt_tokens`` tokens."""
        out = []
        suffix = spec.get("suffix_tokens", 0)
        gen = spec.get("output_tokens", 2)
        for j, p in enumerate(self.prefixes):
            toks = np.concatenate([p, rng.integers(0, vocab, suffix).astype(
                np.int32)])
            out.append(Req(rid0 + len(out), 0.0, toks, gen, j))
        for _ in range(spec.get("extra", 0)):
            toks = rng.integers(0, vocab, spec["prompt_tokens"]).astype(
                np.int32)
            out.append(Req(rid0 + len(out), 0.0, toks, gen))
        if self.prefixes and spec.get("hit", False):
            # one more request on prefix 0: compiles the prefix-hit path
            toks = np.concatenate([self.prefixes[0], rng.integers(
                0, vocab, suffix).astype(np.int32)])
            out.append(Req(rid0 + len(out), 0.0, toks, gen, 0))
        return out

    def window(self) -> list:
        return [r for r in self.requests if r.due >= 0.0]


def check_fits(spec: dict, cache_len: int) -> None:
    """Every request of the mix must fit the configured cache length."""
    sp = spec.get("shared_prefixes")
    if sp:
        longest = sp["tokens"] + max(sp["suffix_tokens"])
    else:
        longest = spec["prompt_tokens"]["max"]
    longest += spec["output_tokens"]["max"]
    if longest > cache_len:
        raise ValueError(f"traffic needs {longest} tokens per request, the "
                         f"configuration holds {cache_len}")
