"""The general traffic generator: a mix file's parameters in, a request
schedule or a batch feed out, every draw from the seed.

``serve_schedule`` draws a Poisson mix's requests *stratified*:
the n inter-arrival gaps are the n quantiles of the exponential at
(i + 1/2)/n, rescaled to span the window exactly, and the lengths are
the n quantiles of their clipped lognormals.  They are dealt out in a
bit-reversed order, so that every stretch of consecutive requests spans
the whole distribution, shuffled within blocks of four by a generator of
fixed seed (``LAYOUT``).  So every seed offers the same sequence of sizes
and gaps, and draws only the token ids: a tail over a few dozen requests
depends on which sizes arrive close together, and would otherwise change
with the seed far more than between two runs of one seed.  ``in_flight``
puts the slots at steady occupancy when the window opens: requests caught
part-way through their output, with totals drawn in proportion to how
long each stays, laid out alike for every seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np

SEED_WORDS = 2 ** 32
#: the seed of the layout of sizes and gaps, the same for every run
LAYOUT = 20_251_017


def seed_words(seed: int, *stream: int):
    """A numpy seed sequence from any whole number (beyond 32 bits too)
    and a stream tag."""
    seed = int(seed)
    return [seed % SEED_WORDS, (seed // SEED_WORDS) % SEED_WORDS, *stream]


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a clipped lognormal, as whole
    tokens (ascending)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf(u) for u in quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, span: float) -> np.ndarray:
    """n stratified exponential gaps summing to ``span`` (ascending)."""
    g = -np.log1p(-quantiles(n))
    return g * (span / g.sum())


@dataclasses.dataclass
class Planned:
    rid: int
    due: float                  # seconds from the window's opening
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int


def spread_order(n: int, rng, block: int = 4) -> np.ndarray:
    """A permutation of range(n) in bit-reversed order (consecutive
    entries fall far apart in the range), shuffled by ``rng`` within
    blocks of ``block``."""
    bits = max(1, (n - 1).bit_length())
    rev = sorted(range(n), key=lambda i: int(f"{i:0{bits}b}"[::-1], 2))
    return np.concatenate([rng.permutation(rev[b:b + block])
                           for b in range(0, n, block)]).astype(np.int64)


def serve_schedule(mix: dict, seed: int, seconds: float,
                   vocab: int) -> List[Planned]:
    """Requests due from 0 to ``seconds`` at the mix's mean rate, lengths
    from its distributions, ordered by due time and drawn as the module
    says.  Only Poisson arrivals are drawn; another process is an
    error."""
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = max(1, int(round(float(arr["rate_per_s"]) * float(seconds))))
    rng = np.random.default_rng(seed_words(seed, 7))
    order = spread_order(n, np.random.default_rng(seed_words(LAYOUT, 7)))
    gaps = exponential_gaps(n, float(seconds))[np.roll(order, n // 3)]
    due = np.cumsum(gaps) - gaps[0]           # the first opens the window
    plen = lognormal_lengths(mix["prompt_tokens"], n)[order]
    olen = lognormal_lengths(mix["output_tokens"], n)[
        np.roll(order, 2 * n // 3)]
    return [Planned(rid=i, due=float(due[i]),
                    prompt=rng.integers(0, vocab, int(plen[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(olen[i]))
            for i in range(n)]


#: request ids of the requests in flight when the window opens
IN_FLIGHT_RID = 1_000_000


def in_flight(mix: dict, seed: int, vocab: int) -> List[Planned]:
    """``in_flight_at_open`` requests caught part-way through their
    output, as a steady stream leaves them: totals drawn from the output
    distribution in proportion to their length (a long request is in
    flight for longer), progress uniform over each.  What a request has
    already produced is part of its prompt; its remaining output is its
    budget.  Due before the window, so not in its time to first token."""
    k = int(mix.get("in_flight_at_open", 0))
    if not k:
        return []
    rng = np.random.default_rng(seed_words(seed, 9))
    layout = np.random.default_rng(seed_words(LAYOUT, 9))
    lengths = lognormal_lengths(mix["output_tokens"], 4096)
    cdf = np.cumsum(lengths) / lengths.sum()
    total = lengths[np.searchsorted(cdf, quantiles(k))][
        spread_order(k, layout)]
    done = np.floor(quantiles(k)[layout.permutation(k)] * total).astype(int)
    plen = lognormal_lengths(mix["prompt_tokens"], k)[
        spread_order(k, layout)]
    return [Planned(rid=IN_FLIGHT_RID + i, due=-1.0,
                    prompt=rng.integers(0, vocab, int(plen[i] + done[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(total[i] - done[i]))
            for i in range(k)]


def length_range(spec: dict):
    return int(spec["min"]), int(spec["max"])
