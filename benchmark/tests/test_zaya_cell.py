"""``zaya1-8b.serve-reason`` at its toy size on the CPU: the cell end to
end through ``run.py``, its control coming out not correct, the counters
of its family by hand, and the new readers finding nothing to read in a
program that lacks what they read."""
import json
import os

import numpy as np

from benchmark import harness as H
from benchmark.tests.conftest import last_json

CELL = "zaya1-8b.serve-reason"


def test_toy_cell_runs_end_to_end(toy_harness, capsys):
    from benchmark import run
    assert run.main(["--workload", CELL, "--seed", "3000000007",
                     "--seconds", "2", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    line = last_json(out)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"tpot_p90_ms", "setup_s"}
    # held to the widest gap, the mean and the 99th percentile, as the
    # cell is on the chip (limits/<cell>.json)
    assert set(line["checks"]) == {
        "logit_gap", "logit_gap_mean", "logit_gap_p99", "never_answered",
        "compiles_in_window"}
    assert line["checks"]["logit_gap"]["value"] <= 1e-3
    assert line["checks"]["never_answered"]["value"] == 0
    assert line["checks"]["compiles_in_window"]["value"] == 0
    # the engine counted the experts its decode steps touched
    import mxnet_tpu as mx
    steps = mx.telemetry.counter("serving.moe_layer_steps").value
    touched = mx.telemetry.counter("serving.moe_experts_touched").value
    assert steps > 0 and steps <= touched <= 4 * steps


def test_control_is_not_correct(toy_harness):
    """The token the fp8 reference puts first lies further below the
    float32 reference's best than the cell's limit allows."""
    from benchmark.drivers import serve as D
    c = toy_harness.load_cell(CELL)
    fam, cfg = c["family"], c["cfg"]
    seqs = np.random.default_rng(3).integers(
        0, cfg["vocab_size"], (3, 40)).astype(np.int32)
    ref = D.reference_logits(fam, cfg, 77, seqs)
    low = D.reference_logits(fam, cfg, 77, seqs, precision="fp8")
    judged = np.ones(seqs.shape, bool)
    assert D.logit_gap(ref, np.asarray(ref).argmax(-1), judged) == 0.0
    got = D.logit_gaps(ref, np.asarray(low).argmax(-1), judged)
    limits = c["limits"]["limits"]
    assert set(limits) == set(D.GAP_NUMBERS)
    assert all(got[k] > limits[k] for k in limits), got


def test_counters_by_hand():
    fam = H.load_module("families", "zaya")
    c = json.load(open(os.path.join(H.CODE, "configs", "zaya1-8b.json")))
    # CCA: qk 1280x2048, v 256x2048, out 2048x1024, ten heads x two taps
    # of 128x128; router 2048x256, two 256x256, 256x16; ONE expert's
    # three 2048x2048 matrices
    cca = 2048 * 1280 + 2048 * 256 + 1024 * 2048 + 10 * 2 * 128 * 128
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert fam.layer_macs_per_token(c) == cca + router + 3 * 2048 * 2048 \
        == 18_812_928
    # K and V rows of 256 lanes, bf16, 20 layers: 20 KB a row
    assert fam.decode_cache_bytes_per_row(c) == 2 * 2 * 256 * 20 \
        == 1024 * 20
    assert fam.expert_bytes(c) == 2 * 3 * 2048 * 2048 == 25_165_824
    # 14 of 16 experts touched in each of 20 layers: 7.05 GB a step
    assert fam.moe_decode_bytes(c, 14.0) == 20 * 14 * 25_165_824
    # one token over 100 rows: 20 layers + the head, scores and values
    # over the 1024 query lanes
    assert fam.decode_flops(c, 1, 100) == 2.0 * (
        20 * 18_812_928 + 2048 * 262272 + 20 * 2 * 100 * 1024)
    # every weight once, the tied matrix once: 9.38 GB in bf16
    assert 9.3e9 < fam.weight_bytes(c) < 9.5e9


def test_new_readers_find_nothing_without_their_counter_or_trace():
    """On the parent of this PR (no counter, no such scopes) the three
    readers return None and do not raise."""
    ctx = {"trace": None, "traffic": {"programs": {"decode": "jit_step"}},
           "spans": {"steps_per_round": 8}, "cfg": {}, "family": None,
           "peaks": {"hbm_bytes_per_s": 1.0}}
    for name in ("moe_decode_ms", "cca_decode_ms", "moe_decode_roofline"):
        assert H.load_module("metrics", name).read(ctx) is None
