"""The trace reduction: interval arithmetic on hand-made events, and the
small trace recorded on the chip (``data/tiny.xplane.pb``: three calls of
``jit_alpha``, a 20 ms pause on the host, two calls of ``jit_beta``)."""
import os

import pytest

from benchmark import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_drops_empty():
    assert T.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) == \
        [(0, 4), (5, 12)]


def test_busy_and_gaps_inside_a_window():
    busy, gaps = T.busy_and_gaps([(0, 4), (2, 6), (10, 12), (30, 40)],
                                 (1, 20))
    assert busy == 5 + 2              # [1,6) and [10,12); (30,40) is outside
    assert gaps == [(6, 10), (12, 20)]


def test_names():
    assert T.program_name("jit_step(1234567)") == "jit_step"
    assert T.program_name("jit__step_impl(42)") == "jit__step_impl"
    assert T.op_name("%fusion.12 = bf16[8,128] fusion(...)") == "fusion.12"


def test_gaps_are_named_by_the_host_span_that_covers_most():
    host = [("bench.a", 0, 50), ("bench.b", 50, 200)]
    rows = T.name_gaps([(40, 100), (300, 310)], host)
    assert rows == [["bench.b", 60 / 1e9], ["unattributed", 10 / 1e9]]


def test_per_name_sums_clip_to_the_window():
    ev = [("jit_a(1)", 0, 10), ("jit_a(1)", 20, 30), ("jit_b(2)", 28, 50)]
    got = T.by_name(ev, T.program_name, (5, 40))
    assert got["jit_a"] == {"calls": 2, "seconds": 15 / 1e9,
                            "text": "jit_a(1)"}
    assert got["jit_b"] == {"calls": 1, "seconds": 12 / 1e9,
                            "text": "jit_b(2)"}


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA,
                                                    "tiny.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_trace_gives_known_numbers():
    import json
    want = json.load(open(os.path.join(DATA, "tiny.json")))
    got = T.reduce(os.path.join(DATA, "tiny.xplane.pb"))
    assert got["chips"] == want["chips"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for name, row in want["programs"].items():
        assert got["programs"][name]["calls"] == row["calls"]
        assert got["programs"][name]["seconds"] == pytest.approx(
            row["seconds"], rel=1e-9)
    # the pause on the host is the longest idle gap, and is named
    assert got["breakdown"]["idle_gaps"][0][0] == want["longest_gap"]
    assert 0 < got["busy_s"] < got["window_s"]
