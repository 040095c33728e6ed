"""The one general traffic generator for serving cells. It reads a traffic
file's parameters (lengths, rate, arrival law, order) and makes the
requests of one run from the seed.

Every run gets the SAME multiset of prompt lengths, of output lengths and
of inter-arrival gaps -- the quantiles of the laws the file names -- each
shuffled over the WHOLE window, independently of the others. The shuffles
are the traffic file's (``order_seed``, which every serving file carries):
every seed offers the SAME requests at the SAME times, one sample path of
the stream with its bursts and lulls where that constant put them, short
gaps falling together as they do in a Poisson stream; the run's seed makes
the token ids (and the weights). This is not an i.i.d. draw: the empirical
laws are exact in every run. Until PR 33 the run's seed drew the order
too; since PR 29 a step's time follows the occupancy, the order then IS
the work, and a free order moved a tail by more than any bound (PERF.md
section 6, PR 33), so there is one path and no other.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

from benchmark.harness import BenchError


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def _norm_ppf(q):
    """Inverse of the standard normal's distribution function."""
    inv = statistics.NormalDist().inv_cdf
    return np.array([inv(float(x)) for x in q])


def lengths(law, n):
    """``n`` lengths at the quantiles of ``law``: lognormal with the
    given median and sigma, clipped to [min, max]."""
    if law.get("dist") != "lognormal":
        raise BenchError("generate: unknown length law %r" % law.get("dist"))
    x = law["median"] * np.exp(law["sigma"] * _norm_ppf(_quantiles(n)))
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def gaps(traffic, n, seconds):
    """``n`` inter-arrival gaps that fill ``seconds``: the quantiles of the
    exponential law, the gaps of a Poisson stream at ``n / seconds``."""
    law = traffic["arrivals"]
    if law != "exponential_quantiles_shuffled":
        raise BenchError("generate: unknown arrival law %r" % law)
    g = -np.log1p(-_quantiles(n))
    return g * (seconds / g.sum())


def requests(traffic, vocab, seed, seconds):
    """[(due_s, prompt ids int32, max_tokens)] for one open-loop window
    of ``seconds`` at the file's ``rate_per_s``, in order of arrival: the
    lengths and due times are the file's (``order_seed``), the ids the
    seed's."""
    if traffic.get("order_seed") is None:
        raise BenchError("generate: the traffic file names no order_seed")
    n = max(1, int(math.floor(traffic["rate_per_s"] * seconds + 0.5)))
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(traffic["order_seed"]))
    plen = order.permutation(lengths(traffic["prompt"], n))
    olen = order.permutation(lengths(traffic["output"], n))
    due = np.cumsum(order.permutation(gaps(traffic, n, seconds)))
    due -= due[0] / 2.0          # the first a half-gap in, the last inside
    out = []
    for i in range(n):
        ids = rng.integers(0, vocab, int(plen[i])).astype(np.int32)
        out.append((float(due[i]), ids, int(olen[i])))
    return out


def warm_requests(traffic, vocab, seed):
    """One prompt per prefill bucket the traffic can reach, long enough
    to run a decode round: what set-up compiles and no more."""
    rng = np.random.default_rng(int(seed) + 1)
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    out, prev = [], 0
    for b in traffic["prefill_buckets"]:
        if prev < hi and b >= lo:
            n = min(b, hi)
            out.append((rng.integers(0, vocab, n).astype(np.int32),
                        2 * traffic["steps_per_round"]))
        prev = b
    return out
