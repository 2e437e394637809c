"""The one traffic generator.  A traffic file gives parameters; this turns
them and ``--seed`` into requests, batches or a job.

Every seed gets the same multiset of sizes and gaps, in another order: the
lengths are the quantiles of their distribution at evenly spaced
probabilities, and the seed permutes them.  Token ids come from the seed.
So the work is the same from seed to seed and only its order moves, which
keeps the spread between seeds near the spread between two runs of one seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one seed.  Seeds may exceed 32 bits."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the evenly spaced quantiles of a lognormal with the
    given median and sigma, clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps at the evenly spaced quantiles of an exponential with the
    given rate: the gaps of a Poisson process, with its mean exactly 1/rate."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])


def request_set(traffic: dict, seed: int, n: int, vocab: int,
                stream: int = 0) -> list[dict]:
    """``n`` requests: prompt and output lengths from the traffic file's
    distributions, in the seed's order, with prompt ids drawn from the seed
    (ids 1 .. vocab-1; 0 is the engine's filler for empty slots).
    ``stream`` tells apart the sets one seed makes."""
    prompts = lognormal_lengths(traffic["prompt"], n)
    outputs = lognormal_lengths(traffic["output"], n)
    r = rng_for(seed, 1, stream)
    prompts = prompts[r.permutation(n)]
    outputs = outputs[r.permutation(n)]
    ids = rng_for(seed, 2, stream)
    return [{"prompt": ids.integers(1, vocab, size=int(p)).tolist(),
             "max_new_tokens": int(o)} for p, o in zip(prompts, outputs)]


def poisson_arrivals(rate: float, start: float, span: float, seed: int,
                     stream: int) -> np.ndarray:
    """Arrival times in [start, start + span) at ``rate``: the quantile
    gaps of ``exponential_gaps`` in the seed's order, scaled so that their
    count fits the span with one mean gap left after the last."""
    n = max(1, int(round(span * rate)))
    gaps = exponential_gaps(rate, n)[rng_for(seed, 3, stream).permutation(n)]
    return start + np.cumsum(gaps) * (span / (float(np.sum(gaps)) + 1 / rate))


def poisson_schedule(traffic: dict, seed: int, seconds: float,
                     vocab: int) -> list[dict]:
    """An open-loop schedule: requests due from ``-prelude_s`` to
    ``seconds`` at the traffic file's rate.  Each request carries ``due``,
    seconds from the opening of the window (negative in the prelude).

    The prelude and the window are two sets of their own, so every seed
    has the same requests due inside the window, with the same gaps, only
    in another order."""
    rate = float(traffic["rate_per_s"])
    prelude = float(traffic["prelude_s"])
    out = []
    for stream, (start, span) in enumerate(((-prelude, prelude),
                                            (0.0, seconds))):
        due = poisson_arrivals(rate, start, span, seed, stream)
        reqs = request_set(traffic, seed, len(due), vocab, stream)
        for r, t in zip(reqs, due):
            r["due"] = float(t)
        out += reqs
    return out
