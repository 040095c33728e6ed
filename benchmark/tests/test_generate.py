"""The traffic generator: every run offers the same requests and gaps, in
the one order the traffic file fixes; another constant is another order
of the same work, over the whole window."""
import json
import os

import numpy as np
import pytest

from benchmark import generate as G
from benchmark import harness as H


def mix(order_seed=42):
    """serve-chat's lengths and arrival law at 4 requests/s, in the order
    ``order_seed`` fixes."""
    with open(os.path.join(H.CODE, "traffic", "serve-chat.json")) as f:
        t = json.load(f)
    t["rate_per_s"] = 4.0
    t["order_seed"] = order_seed
    return t


@pytest.mark.parametrize("constant", [1, 3000000007])
def test_every_order_offers_the_same_work_in_another_order(constant):
    t = mix(constant)
    a = G.requests(mix(0), 50272, 5, 30.0)
    b = G.requests(t, 50272, 5, 30.0)
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


def test_gaps_are_those_of_a_poisson_stream_in_any_order():
    """The gaps are the exponential law's quantiles (their spread is the
    law's: standard deviation about the mean), and the file's constant
    shuffles them over the whole window: from constant to constant the
    count of arrivals in a stretch swings as a Poisson count does, it is
    not evened out."""
    g = G.gaps(mix(), 120, 30.0)
    assert abs(g.sum() - 30.0) < 1e-9
    assert 0.85 < g.std() / g.mean() < 1.0
    counts = []
    for constant in range(40):
        due = np.array([r[0] for r in
                        G.requests(mix(constant), 100, 0, 30.0)])
        counts.append(np.sum((due >= 10.0) & (due < 15.0)))
    # 20 expected in 5 s; a Poisson count's variance is its mean, less
    # the little that the fixed total takes away
    assert 10.0 < np.var(counts) < 30.0


@pytest.mark.parametrize("seed", [1, 3000000007])
def test_a_fixed_order_offers_every_seed_the_same_sample_path(seed):
    """The lengths and the due times are the file's (``order_seed``), the
    same for every seed and another under another constant; the token ids
    are the seed's. The path is one draw of the same law: every gap is
    the law's, and arrivals bunch as a Poisson stream's do (they are not
    evened out)."""
    t = mix(42)
    a = G.requests(t, 50272, 0, 30.0)
    b = G.requests(t, 50272, seed, 30.0)
    assert [(r[0], len(r[1]), r[2]) for r in a] \
        == [(r[0], len(r[1]), r[2]) for r in b]
    assert any((x[1] != y[1]).any() for x, y in zip(a, b))
    other = G.requests(mix(43), 50272, seed, 30.0)
    assert [r[0] for r in other] != [r[0] for r in b]
    assert sorted((len(r[1]), r[2]) for r in b) != \
        sorted((len(r[1]), r[2]) for r in other)    # paired anew
    assert sorted(len(r[1]) for r in b) == sorted(len(r[1]) for r in other)
    assert sorted(r[2] for r in b) == sorted(r[2] for r in other)
    law = np.round(G.gaps(t, 120, 30.0), 9)
    assert np.isin(np.round(np.diff([r[0] for r in b]), 9), law).all()
    due = np.array([r[0] for r in b])
    counts = np.histogram(due, bins=30, range=(0.0, 30.0))[0]
    assert counts.sum() == 120 and 2.0 < np.var(counts) < 8.0   # mean 4


def test_unknown_laws_are_errors():
    t = mix()
    with pytest.raises(H.BenchError, match="arrival law"):
        G.gaps(dict(t, arrivals="poisson"), 10, 1.0)
    with pytest.raises(H.BenchError, match="length law"):
        G.lengths({"dist": "zipf"}, 10)
    with pytest.raises(H.BenchError, match="order_seed"):
        G.requests(mix(None), 100, 1, 1.0)     # no order left to the seed


def knee_files():
    """Every serving traffic file that records the knee it was set from."""
    out = []
    folder = os.path.join(H.CODE, "traffic")
    for name in sorted(os.listdir(folder)):
        if name.endswith(".json"):
            t = H.load_json(folder, name)
            if t.get("driver") == "serve" and "knee_per_s" in t:
                out.append(pytest.param(t, id=name[:-len(".json")]))
    return out


@pytest.mark.parametrize("t", knee_files())
def test_a_latency_cell_sits_at_four_fifths_of_its_knee(t):
    """``rate_per_s`` is 0.8 of the knee the file records (to 5%), and a
    window of ``run_seconds`` offers at least 100 requests: ten beyond
    the p90, the least a tail can stand on."""
    seconds = float(H.load_json(H.ROOT, "BENCHMARK.json")["run_seconds"])
    assert abs(t["rate_per_s"] / (0.8 * t["knee_per_s"]) - 1.0) <= 0.05
    assert len(G.requests(t, 50272, 1, seconds)) >= 100
