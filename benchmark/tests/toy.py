"""A toy copy of the benchmark's data in a scratch directory: the same
cells, families, drivers and readers, at sizes the CPU can run. Every
configuration, traffic and limits file carries its own ``toy`` block (the
keys to lay over it at toy size), so a cell that a later PR adds brings
its toy size with it and nothing here names a cell. Used by the tests (and
by hand: ``python3 benchmark/tests/toy.py <dir> <run.py arguments>`` runs
one toy cell on the CPU)."""
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _toy(src, dst):
    """``src`` with its ``toy`` block laid over it, written to ``dst``."""
    with open(src) as f:
        d = json.load(f)
    d.update(d.pop("toy", {}))
    with open(dst, "w") as f:
        json.dump(d, f)


def build(dst):
    """Write the toy tree under ``dst``; returns (root, data) for
    ``harness.ROOT`` and ``harness.HERE``."""
    root, data = os.path.join(dst, "root"), os.path.join(dst, "data")
    for d in (root, os.path.join(data, "traffic"),
              os.path.join(data, "limits")):
        os.makedirs(d, exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"), data)
    for c in bench["configs"]:
        os.makedirs(os.path.dirname(os.path.join(root, c["file"])),
                    exist_ok=True)
        _toy(os.path.join(REPO, c["file"]), os.path.join(root, c["file"]))
    for w in bench["workloads"]:
        for kind, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            rel = os.path.join(kind, name + ".json")
            _toy(os.path.join(REPO, "benchmark", rel),
                 os.path.join(data, rel))
    return root, data


def point_harness_at(dst, platform="cpu"):
    sys.path.insert(0, REPO)
    from benchmark import harness as H
    H.ROOT, H.HERE = build(dst)
    H.PLATFORM = platform
    H.peaks_for = lambda kind: {"flops_bf16": 1e12,
                                "hbm_bytes_per_s": 1e11}
    return H


if __name__ == "__main__":
    point_harness_at(sys.argv[1])
    from benchmark import run
    sys.exit(run.main(sys.argv[2:]))
