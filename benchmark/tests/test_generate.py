"""The traffic generator: every seed offers the same requests and gaps in
another order, over the whole window."""
import json
import os

import numpy as np
import pytest

from benchmark import generate as G
from benchmark import harness as H


def mix():
    with open(os.path.join(H.CODE, "traffic", "serve-chat.json")) as f:
        t = json.load(f)
    t["rate_per_s"] = 4.0
    return t


@pytest.mark.parametrize("seed", [1, 3000000007])
def test_every_seed_offers_the_same_work_in_another_order(seed):
    t = mix()
    a = G.requests(t, 50272, 0, 30.0)
    b = G.requests(t, 50272, seed, 30.0)
    assert len(a) == len(b) == 120
    for k in (1, 2):                      # prompt lengths, output lengths
        size = (lambda r: len(r[1])) if k == 1 else (lambda r: r[2])
        assert sorted(map(size, a)) == sorted(map(size, b))
        assert list(map(size, a)) != list(map(size, b))
    law = np.round(G.gaps(t, 120, 30.0), 9)
    for x in (a, b):                      # every gap is one of the law's
        assert np.isin(np.round(np.diff([r[0] for r in x]), 9), law).all()
    assert all(0.0 < r[0] < 30.0 for r in b)
    assert all(x[0] <= y[0] for x, y in zip(b, b[1:]))
    assert all(len(r[1]) + r[2] <= t["max_len"] for r in b)


def test_gaps_are_those_of_a_poisson_stream_in_a_free_order():
    """The gaps are the exponential law's quantiles (their spread is the
    law's: standard deviation about the mean), and the seed shuffles them
    over the whole window: the count of arrivals in a stretch swings as a
    Poisson count does, it is not evened out."""
    t = mix()
    g = G.gaps(t, 120, 30.0)
    assert abs(g.sum() - 30.0) < 1e-9
    assert 0.85 < g.std() / g.mean() < 1.0
    counts = []
    for seed in range(40):
        due = np.array([r[0] for r in G.requests(t, 100, seed, 30.0)])
        counts.append(np.sum((due >= 10.0) & (due < 15.0)))
    # 20 expected in 5 s; a Poisson count's variance is its mean, less
    # the little that the fixed total takes away
    assert 10.0 < np.var(counts) < 30.0


def test_unknown_laws_are_errors():
    t = mix()
    with pytest.raises(H.BenchError, match="arrival law"):
        G.gaps(dict(t, arrivals="poisson"), 10, 1.0)
    with pytest.raises(H.BenchError, match="length law"):
        G.lengths({"dist": "zipf"}, 10)
