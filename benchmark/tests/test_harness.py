"""The harness end to end at a toy size on the CPU, and what it refuses."""
import json

import pytest

from benchmark import harness as H
from benchmark.tests.conftest import last_json

CELLS = ["resnet50.train-b256", "opt-1.3b.serve-chat", "opt-1.3b.train-2k"]


def test_every_name_in_the_benchmark_file_resolves():
    bench = json.load(open(H.ROOT + "/BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = H.load_cell(w["name"])
        assert cell["end_to_end"] and cell["per_layer"]
        H.load_module("drivers", cell["traffic"]["driver"])
        for m in cell["per_layer"]:
            assert callable(H.load_module("metrics", m["name"]).read)
    for m in bench["per_layer"]:
        assert any(e["name"] == m["moves"] for e in bench["end_to_end"])


def test_unknown_device_is_an_error():
    assert H.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    assert H.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(H.BenchError, match="no peaks on record"):
        H.peaks_for("TPU v9 mega")
    with pytest.raises(H.BenchError, match="no peaks on record"):
        H.peaks_for("cpu")


def test_unknown_cell_and_metric_are_errors():
    with pytest.raises(H.BenchError, match="unknown workload"):
        H.load_cell("resnet50.train-b999")
    with pytest.raises(H.BenchError, match="unknown metric"):
        H.load_module("metrics", "no_such_metric")
    with pytest.raises(H.BenchError, match="unknown driver"):
        H.load_module("drivers", "no_such_driver")


def test_refuses_to_measure_off_the_accelerator(capsys):
    from benchmark import run
    with pytest.raises(SystemExit, match="needs a tpu"):
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert "correct" not in capsys.readouterr().out


@pytest.mark.parametrize("cell", CELLS)
def test_toy_cell_runs_end_to_end(toy_harness, capsys, cell):
    from benchmark import run
    assert run.main(["--workload", cell, "--seed", "3000000007",
                     "--seconds", "2", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    line = last_json(out)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {
        m["name"] for m in toy_harness.load_cell(cell)["end_to_end"]}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    # each number compared stands beside its limit, on stderr too
    for name, row in line["checks"].items():
        assert "check %s " % name in err
        assert row["value"] <= row["limit"]
    assert err.strip().splitlines()[-1] == "correct=True"


def test_toy_traced_run_prints_no_device_metric_off_the_chip(toy_harness,
                                                              capsys):
    """--trace 1 needs device operations in the trace; the CPU has no
    device plane, so the run ends without a result line."""
    from benchmark import run
    with pytest.raises(SystemExit, match="no device operation"):
        run.main(["--workload", "opt-1.3b.train-2k", "--seed", "5",
                  "--seconds", "2", "--trace", "1"])
    assert '"correct"' not in capsys.readouterr().out
