"""The seeded traffic generator: determinism, sizes, clipping, alignment."""

import json
import math
import os

import numpy as np
import pytest

from bench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(os.path.dirname(HERE)), "bench",
                     "traffic")
CHUNK, PAGE, VOCAB = 256, 16, 32000


def _mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def _gen(name, seed, seconds=45.0):
    return traffic.Traffic(_mix(name), seed, seconds, VOCAB, CHUNK)


@pytest.mark.parametrize("name", ["chat", "shared-prefix"])
def test_same_seed_same_traffic(name):
    a, b = _gen(name, 2**33 + 1), _gen(name, 2**33 + 1)
    for x, y in zip(a.requests + a.warmup, b.requests + b.warmup):
        assert x.due == y.due and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.tokens, y.tokens)


@pytest.mark.parametrize("name", ["chat", "shared-prefix"])
def test_seeds_reorder_one_set_of_sizes(name):
    """Every seed gets the schedule that the mix's ``schedule_seed`` draws,
    with its own token ids; another schedule seed reorders the sizes."""
    mix = _mix(name)
    sched = lambda g: [(r.due, len(r.tokens), r.max_new_tokens, r.prefix)
                       for r in g.requests]  # noqa: E731
    a, b = _gen(name, 1), _gen(name, 2**31 + 5)
    assert sched(a) == sched(b)
    assert not np.array_equal(a.requests[0].tokens, b.requests[0].tokens)
    other = traffic.Traffic(dict(mix, schedule_seed=mix["schedule_seed"] + 1),
                            1, 45.0, VOCAB, CHUNK)
    for size in (lambda r: len(r.tokens), lambda r: r.max_new_tokens):
        assert sorted(map(size, a.requests)) == \
            sorted(map(size, other.requests))
    assert sched(a) != sched(other)


def test_chat_lengths_clipped_and_chunk_aligned():
    g = _gen("chat", 7)
    spec = _mix("chat")
    p, o = spec["prompt_tokens"], spec["output_tokens"]
    for r in g.requests:
        assert len(r.tokens) % CHUNK == 0
        assert p["min"] <= len(r.tokens) <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert r.tokens.min() >= 0 and r.tokens.max() < VOCAB
    window = g.window()
    assert len(window) == round(spec["rate_per_s"] * 45.0)
    assert all(0.0 <= r.due < 45.0 for r in window)
    lead = [r for r in g.requests if r.due < 0]
    assert all(-spec["lead_in_s"] <= r.due for r in lead)


def test_shared_prefixes_aligned_to_pages_and_chunks():
    g = _gen("shared-prefix", 3)
    spec = _mix("shared-prefix")["shared_prefixes"]
    align = PAGE * CHUNK // math.gcd(PAGE, CHUNK)
    assert spec["tokens"] % align == 0
    counts = np.bincount([r.prefix for r in g.requests],
                         minlength=spec["count"])
    assert counts[0] == counts.max() and counts[-1] == counts.min()
    for r in g.requests:
        assert np.array_equal(r.tokens[:spec["tokens"]],
                              g.prefixes[r.prefix])
        assert len(r.tokens) - spec["tokens"] in spec["suffix_tokens"]
        assert len(r.tokens) % CHUNK == 0
    # set-up serves every prefix once, plus one prefix hit
    assert sorted({w.prefix for w in g.warmup}) == list(range(spec["count"]))


def test_every_request_fits_the_cache():
    traffic.check_fits(_mix("chat"), 4096)
    traffic.check_fits(_mix("shared-prefix"), 4096)
    with pytest.raises(ValueError, match="needs"):
        traffic.check_fits(_mix("chat"), 2048)


def test_negative_seed_refused():
    with pytest.raises(ValueError):
        traffic.seed_sequence(-1)
