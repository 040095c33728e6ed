"""``moe_experts_touched.serve``: the entry resolves in the two cells with
routed experts and names them, the reader gives the program's own
quotient, prints the share of slots that routed nothing where the program
counts it, and finds nothing to read (None, never 0) where no routed layer
ran a decode step."""
import json
import os

import pytest

from benchmark import harness as H

NAME = "moe_experts_touched.serve"
CELLS = ("zaya1-8b.serve-reason", "qwen3-next-80b-a3b.serve-longdoc")


class Fixed:
    """A counter that reads what it was given."""

    def __init__(self, value):
        self.value = value


def counters(monkeypatch, **values):
    import mxnet_tpu as mx
    monkeypatch.setattr(
        mx.telemetry, "counter",
        lambda name: Fixed(values.get(name.split(".", 1)[1], 0)))


def test_the_entry_names_its_cells():
    bench = json.load(open(os.path.join(H.ROOT, "BENCHMARK.json")))
    row = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert row == {
        "name": NAME, "unit": "experts/layer", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p90_ms", "workloads": list(CELLS)}
    assert len(row["unit"]) <= 16
    moved = {e["name"]: e for e in bench["end_to_end"]}[row["moves"]]
    assert set(CELLS) <= set(moved["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_the_entry_resolves_in_its_cell(cell):
    listed = [m["name"] for m in H.load_cell(cell)["per_layer"]]
    assert NAME in listed and "moe_decode_roofline" in listed
    assert callable(H.load_module("metrics", NAME).read)


def test_a_cell_without_routed_experts_does_not_list_it():
    listed = [m["name"]
              for m in H.load_cell("opt-1.3b.serve-chat")["per_layer"]]
    assert NAME not in listed


def test_the_reader_finds_nothing_where_no_routed_layer_stepped(
        monkeypatch):
    counters(monkeypatch)
    ctx = {"trace": None, "traffic": {"slots": 32}, "spans": {}}
    assert H.load_module("metrics", NAME).read(ctx) is None


def test_the_reader_by_hand(monkeypatch, capsys):
    """300 experts touched over 40 layer-steps; 32 slots of which 11 held
    a request after each of 5 rounds, 840 of 1,280 slot-layer-steps
    masked."""
    counters(monkeypatch, moe_experts_touched=300, moe_layer_steps=40,
             moe_rows_masked=840)
    ctx = {"traffic": {"slots": 32},
           "spans": {"rounds": 5, "live_slots": 55}}
    assert H.load_module("metrics", NAME).read(ctx) == 7.5
    out = capsys.readouterr().out
    assert "840 of 1280 slots x layer-steps (65.62%)" in out
    assert "no request after a round = 65.62%" in out
    # the parent of the PR that added the counter: the same number, and
    # no line about rows that nothing counted
    counters(monkeypatch, moe_experts_touched=300, moe_layer_steps=40)
    assert H.load_module("metrics", NAME).read(ctx) == 7.5
    assert capsys.readouterr().out == ""
