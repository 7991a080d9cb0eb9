"""The traffic generator: seeded, stratified, within the cache."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"


def mix(name, **arrivals):
    m = json.loads((MIXES / f"{name}.json").read_text())
    if arrivals and "arrivals" in m:
        m["arrivals"] = dict(m["arrivals"], **arrivals)
    return m


def sizes(t):
    return sorted((len(r.prompt), r.max_new) for r in t.requests)


def flat(t):
    return [(len(r.prompt), r.max_new, r.due, r.prompt.tolist()) for r in t.requests]


@pytest.mark.parametrize("name", ["agent-decode", "chat-burst"])
def test_same_seed_same_traffic(name):
    m = mix(name, knee_req_per_s=1.0)
    a = traffic.build(m, 2**31 + 17, 51, 64000, 2048)
    b = traffic.build(m, 2**31 + 17, 51, 64000, 2048)
    assert flat(a) == flat(b)


@pytest.mark.parametrize("name", ["agent-decode", "chat-burst"])
def test_other_seed_other_tokens_same_work(name):
    m = mix(name, knee_req_per_s=1.0)
    a = traffic.build(m, 1, 51, 64000, 2048)
    b = traffic.build(m, 2, 51, 64000, 2048)
    assert [r.prompt.tolist() for r in a.requests] != [r.prompt.tolist() for r in b.requests]
    assert sizes(a) == sizes(b)      # the same set of sizes, in another order


def test_closed_loop_seed_reorders_clients():
    m = mix("agent-decode")
    orders = {tuple(tuple((len(r.prompt), r.max_new) for r in c) for c in
                    traffic.build(m, s, 51, 64000, 2048).clients) for s in range(6)}
    assert len(orders) > 1


@pytest.mark.parametrize("name", ["agent-decode", "chat-burst"])
def test_clips_keep_requests_inside_the_cache(name):
    m = mix(name, knee_req_per_s=1.0)
    t = traffic.build(m, 3, 51, 64000, 2048)
    for r in t.requests:
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
        assert len(r.prompt) + r.max_new <= 2048
        assert r.prompt.min() >= 1 and r.prompt.max() < 64000
    with pytest.raises(ValueError):
        traffic.build(m, 3, 51, 64000, traffic.max_total(m) - 1)


def test_stratified_lengths_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 32, "sigma": 0.6, "min": 1, "max": 10**6}
    x = traffic.lengths(spec, 1001, np.random.default_rng(0))
    assert np.median(x) == 32


def test_open_loop_offers_the_mix_rate():
    m = mix("chat-burst", knee_req_per_s=2.0)
    t = traffic.build(m, 5, 51, 64000, 2048)
    due = np.array([r.due for r in t.requests])
    assert np.all(np.diff(due) >= 0)
    horizon = t.preroll_s + 51
    assert abs(len(due) / horizon - traffic.rate(m)) < 0.1
    gaps = np.diff(due)
    assert gaps.std() / gaps.mean() > 1.2       # bursty, not Poisson-regular


def test_open_loop_needs_a_rate():
    with pytest.raises(ValueError):
        traffic.build(mix("chat-burst", knee_req_per_s=None), 1, 51, 64000, 2048)
