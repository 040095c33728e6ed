"""The one general traffic generator for serving cells. It reads a traffic
file's parameters (lengths, rate, arrival law) and makes the requests of
one run from the seed.

Every seed gets the SAME multiset of prompt lengths, of output lengths and
of inter-arrival gaps -- the quantiles of the laws the file names -- each
shuffled by the seed over the WHOLE window, independently of the others,
and its own token ids. So the offered work of a window is the same for
every seed (the same requests, the same total of gaps) while the order is
free: short gaps fall together by chance as they do in a Poisson stream,
a long prompt meets a burst or a lull, and what the close of the window
cuts off differs from seed to seed. This is not an i.i.d. draw: the
empirical laws are exact in every run, only the order is random.
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
    of ``seconds`` at the file's ``rate_per_s``, in order of arrival."""
    n = max(1, int(math.floor(traffic["rate_per_s"] * seconds + 0.5)))
    rng = np.random.default_rng(int(seed))
    plen = rng.permutation(lengths(traffic["prompt"], n))
    olen = rng.permutation(lengths(traffic["output"], n))
    due = np.cumsum(rng.permutation(gaps(traffic, n, seconds)))
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
