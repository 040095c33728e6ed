"""The benchmark's own tests run on the CPU at toy sizes:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402


@pytest.fixture()
def toy_harness(tmp_path, monkeypatch):
    """The harness pointed at a toy copy of the benchmark's data, on the
    CPU; undone after the test."""
    from benchmark import harness as H
    from benchmark.tests import toy
    root, data = toy.build(str(tmp_path))
    monkeypatch.setattr(H, "ROOT", root)
    monkeypatch.setattr(H, "HERE", data)
    monkeypatch.setattr(H, "PLATFORM", "cpu")
    monkeypatch.setattr(H, "peaks_for", lambda kind: {
        "flops_bf16": 1e12, "hbm_bytes_per_s": 1e11})
    return H


def last_json(text):
    import json
    return json.loads(text.strip().splitlines()[-1])
