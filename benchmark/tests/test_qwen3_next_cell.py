"""``qwen3-next-80b-a3b.serve-longdoc`` at its toy size on the CPU: the cell
end to end through ``run.py`` (prompts entering in pieces, a recurrent state
and K/V rows side by side), its control coming out not correct, the counters
of its family by hand, and the new readers finding nothing to read in a
program that lacks what they read."""
import json
import os

import numpy as np

from benchmark import harness as H
from benchmark.tests.conftest import last_json

CELL = "qwen3-next-80b-a3b.serve-longdoc"
READERS = ("gdn_decode_ms", "gdn_decode_roofline", "gdn_prefill_ms",
           "gattn_decode_ms", "state_live_share.serve")


def test_toy_cell_runs_end_to_end(toy_harness, capsys):
    from benchmark import run
    import mxnet_tpu as mx
    names = ("serving.state_slots_advanced", "serving.state_slots_pool",
             "serving.moe_pairs_held", "serving.moe_pairs_routed",
             "serving.prefill_chunks_per_request")
    tele = mx.telemetry
    before = {n: tele.counter(n).value for n in names[:4]}
    assert run.main(["--workload", CELL, "--seed", "3000000007",
                     "--seconds", "2", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    line = last_json(out)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert set(line["checks"]) == {
        "logit_gap", "logit_gap_mean", "logit_gap_p99", "never_answered",
        "compiles_in_window"}
    assert line["checks"]["logit_gap"]["value"] <= 1e-3
    assert line["checks"]["never_answered"]["value"] == 0
    assert line["checks"]["compiles_in_window"]["value"] == 0
    # some prompt was longer than a piece: its state was carried over
    assert "prefill': {8: 1, 16: 1}" in out
    got = {n: tele.counter(n).value - before[n] for n in before}
    assert 0 < got["serving.state_slots_advanced"] \
        < got["serving.state_slots_pool"]
    assert 0 < got["serving.moe_pairs_held"] \
        < got["serving.moe_pairs_routed"]


def test_control_is_not_correct(toy_harness):
    """The token the fp8 reference puts first lies further below the
    float32 reference's best than the cell's limits allow."""
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


def test_the_cells_own_limits_name_the_widest_gap():
    from benchmark.drivers import serve as D
    real = H.load_json(H.CODE, "limits", CELL + ".json")
    assert "logit_gap" in real["limits"]
    assert set(real["limits"]) <= set(D.GAP_NUMBERS)
    assert set(real["toy"]["limits"]) == set(real["limits"])


def test_configuration_carries_every_published_width():
    """Every number of the catalog's config under its own key, but the
    three that the cut changes; the cut and the deployment stated."""
    c = json.load(open(os.path.join(H.CODE, "configs",
                                    "qwen3-next-80b-a3b.json")))
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512}
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (8, 128, 37984)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert "four chips share each layer" in c["deployment"]


def test_counters_by_hand():
    fam = H.load_module("families", "qwen3_next")
    c = json.load(open(os.path.join(H.CODE, "configs",
                                    "qwen3-next-80b-a3b.json")))
    assert fam.layer_kinds(c) == (6, 2)
    assert [i for i in range(8) if fam.is_attention(c, i)] == [3, 7]
    # DeltaNet: qkvz 12288x2048, ba 64x2048, conv 8192x4, two vectors of
    # 32, the norm's 128, out 2048x4096: 33.72M
    gdn = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 64 + 128 + 4096 * 2048
    assert fam.gdn_params(c) == gdn == 33_718_464
    # attention: q with its gate 8192x2048, k and v 512x2048 each, two
    # norms of 256, out 2048x4096: 27.26M
    attn = 2048 * (8192 + 1024) + 512 + 4096 * 2048
    assert fam.attn_params(c) == attn == 27_263_488
    # K and V rows of 512 lanes, bf16, in the TWO attention layers: 4 KB
    assert fam.decode_cache_bytes_per_row(c) == 2 * 2 * 2 * 512 == 4096
    # per slot, whatever its length: six float32 states of 32x128x128
    # and six windows of 3x8192 bf16: 12.9 MB
    assert fam.state_bytes_per_slot(c) \
        == 6 * (4 * 32 * 128 * 128 + 2 * 3 * 8192) == 12_877_824
    assert fam.expert_bytes(c) == 2 * 3 * 2048 * 512 == 6_291_456
    # 90 of 128 held experts touched in each of 8 layers: 4.5 GB a step
    assert fam.moe_decode_bytes(c, 90.0) == 8 * 90 * 6_291_456
    # the DeltaNet layers' weights once and 20 slots' states read and
    # written once
    assert fam.gdn_decode_bytes(c, 20.0) \
        == 6 * (2 * gdn + 20 * 2 * 12_877_824 / 6)
    # one token over 100 rows: the router over 512, the shared expert and
    # its gate, a quarter of ten experts; the recurrence; the head's slice
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2.5 * 3 * 2048 * 512
    assert fam.moe_macs_per_token(c) == moe
    assert fam.decode_flops(c, 1, 100) == 2.0 * (
        6 * (gdn + 4 * 32 * 128 * 128) + 2 * attn + 8 * moe
        + 2048 * 37984 + 2 * 2 * 100 * 4096)
    # every weight once: 3.67G parameters, 7.33 GB in bf16
    assert 7.30e9 < fam.weight_bytes(c) < 7.36e9


class Fixed:
    """A counter that reads what it was given."""

    def __init__(self, value):
        self.value = value


def test_new_readers_find_nothing_without_their_counter_or_trace(
        monkeypatch):
    """On the parent of this PR (counters that nothing ever counted, no
    such scopes, a family without ``gdn_decode_bytes``) the five readers
    return None and do not raise."""
    ctx = {"trace": None,
           "traffic": {"programs": {"decode": "jit_step",
                                    "prefill": "jit_prefill"},
                       "slots": 64},
           "spans": {"steps_per_round": 8}, "cfg": {}, "family": None,
           "peaks": {"hbm_bytes_per_s": 1.0}}
    import mxnet_tpu as mx
    monkeypatch.setattr(mx.telemetry, "counter", lambda name: Fixed(0))
    for name in READERS:
        assert H.load_module("metrics", name).read(ctx) is None


def test_state_readers_by_hand(monkeypatch):
    """The two counter readers over counters set by hand: 120 advanced of
    a pool of 64 slots x 6 layers x 5 steps."""
    import mxnet_tpu as mx
    values = {"serving.state_slots_advanced": 120,
              "serving.state_slots_pool": 64 * 6 * 5,
              "serving.moe_pairs_held": 7, "serving.moe_pairs_routed": 28}
    monkeypatch.setattr(mx.telemetry, "counter",
                        lambda name: Fixed(values[name]))
    ctx = {"traffic": {"slots": 64}}
    share = H.load_module("metrics", "state_live_share.serve").read(ctx)
    assert share == 100.0 * 120 / (64 * 6 * 5)
    roof = H.load_module("metrics", "gdn_decode_roofline")
    # 120 advanced over 30 layer-steps: 4 slots a layer and step
    assert roof.advanced_per_layer_step(ctx) == 4.0
