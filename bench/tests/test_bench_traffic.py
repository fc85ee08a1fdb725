"""The traffic generator: deterministic per seed, and drawing the
distributions that the mix states."""
import math

import numpy as np
import pytest

from harness import common, traffic

MIX = common.load_json(common.BENCH / "mixes" / "chat-steady.json")
SEED = 2 ** 33 + 12345          # beyond 32 bits, as a check's seeds are


def test_same_seed_same_schedule_other_seed_same_work_reordered():
    a = traffic.serve_schedule(MIX, SEED, 30, 49155)
    b = traffic.serve_schedule(MIX, SEED, 30, 49155)
    c = traffic.serve_schedule(MIX, SEED + 1, 30, 49155)
    key = lambda s: [(r.due, r.max_new_tokens, r.prompt.tolist()) for r in s]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # the work is laid out alike for every seed: the same sizes and due
    # times in the same order; the seed draws the token ids
    size = lambda s: [(r.due, len(r.prompt), r.max_new_tokens) for r in s]
    assert size(a) == size(c)
    assert all(x.prompt.tolist() != y.prompt.tolist() for x, y in zip(a, c))
    assert len({len(r.prompt) for r in a}) > len(a) // 2


def test_schedule_spans_the_window_at_the_rate():
    s = traffic.serve_schedule(MIX, SEED, 30, 49155)
    rate = MIX["arrivals"]["rate_per_s"]
    assert len(s) == round(rate * 30)
    assert s[0].due == 0.0
    assert all(x.due < y.due for x, y in zip(s, s[1:]))
    assert s[-1].due < 30
    assert all(0 <= t < 49155 for r in s for t in r.prompt)


@pytest.mark.parametrize("which", ["prompt_tokens", "output_tokens"])
def test_lengths_follow_the_clipped_lognormal(which):
    spec = MIX[which]
    n = 4001
    x = traffic.lognormal_lengths(spec, n)
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    assert np.median(x) == pytest.approx(spec["median"], rel=0.01)
    # inside the clip the log-lengths' quartiles are median * e^(+-0.674 s)
    for q, z in ((25, -0.6745), (75, 0.6745)):
        want = spec["median"] * math.exp(spec["sigma"] * z)
        if spec["min"] < want < spec["max"]:
            assert np.percentile(x, q) == pytest.approx(want, rel=0.02)


def test_every_stretch_of_requests_spans_the_length_distribution():
    s = traffic.serve_schedule(MIX, SEED, 60, 49155)
    out = np.asarray([r.max_new_tokens for r in s])
    half = len(out) // 2
    # each half of the window offers about half of the output tokens
    assert out[:half].sum() / out.sum() == pytest.approx(0.5, abs=0.1)


def test_requests_in_flight_at_the_opening_fit_and_are_length_biased():
    reqs = traffic.in_flight(MIX, SEED, 49155)
    assert len(reqs) == MIX["in_flight_at_open"]
    assert all(len(r.prompt) + r.max_new_tokens <= 4096 for r in reqs)
    assert all(r.max_new_tokens >= 1 and r.due < 0 for r in reqs)
    again = traffic.in_flight(MIX, SEED, 49155)
    assert [(r.prompt.tolist(), r.max_new_tokens) for r in reqs] == \
        [(r.prompt.tolist(), r.max_new_tokens) for r in again]
    other = traffic.in_flight(MIX, SEED + 1, 49155)
    assert [(len(r.prompt), r.max_new_tokens) for r in reqs] == \
        [(len(r.prompt), r.max_new_tokens) for r in other]
    # length-biased totals: a request in flight is longer than a fresh
    # one on the mean (what it has produced is in its prompt)
    produced = [len(r.prompt) for r in reqs]
    fresh = traffic.lognormal_lengths(MIX["prompt_tokens"], len(reqs))
    assert np.mean(produced) > np.mean(fresh)


def test_gaps_are_exponential_quantiles_summing_to_the_span():
    g = traffic.exponential_gaps(1000, 100.0)
    assert g.sum() == pytest.approx(100.0)
    # exponential: the median gap is ln 2 times the mean
    assert np.median(g) / g.mean() == pytest.approx(math.log(2), rel=0.02)


def test_an_arrival_process_other_than_poisson_is_refused():
    mix = {**MIX, "arrivals": {"process": "burst", "rate_per_s": 2.0}}
    with pytest.raises(ValueError, match="burst"):
        traffic.serve_schedule(mix, SEED, 30, 49155)
