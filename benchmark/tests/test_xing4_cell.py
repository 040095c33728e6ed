"""``xing4.0-29b-a4b.serve-longctx`` at its toy size on the CPU: the cell
end to end through ``run.py`` (prompts entering in pieces whose later ones
read the earlier ones' latent rows, the decode step reading them absorbed
through the bounded read), its control coming out not correct, the counters
of its family by hand, and the new readers finding nothing to read in a
program that lacks what they read."""
import json
import os

import numpy as np

from benchmark import harness as H
from benchmark.tests.conftest import last_json

CELL = "xing4.0-29b-a4b.serve-longctx"
CONFIG = os.path.join(H.CODE, "configs", "xing4.0-29b-a4b.json")
READERS = ("mla_decode_ms", "mla_decode_roofline", "mla_prefill_ms",
           "hc_decode_ms")


def test_toy_cell_runs_end_to_end(toy_harness, capsys):
    from benchmark import run
    import mxnet_tpu as mx
    names = ("serving.latent_rows_live", "serving.attn_rows_read",
             "serving.attn_rows_pool", "serving.moe_pairs_held",
             "serving.moe_pairs_routed", "serving.moe_experts_touched")
    tele = mx.telemetry
    before = {n: tele.counter(n).value for n in names}
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
    # some prompt was longer than a piece: its later pieces read the
    # earlier ones' latent rows
    assert "prefill': {8: 1, 16: 1}" in out
    got = {n: tele.counter(n).value - before[n] for n in before}
    # the live slots' true lengths lie under what whole blocks fetch,
    # which lies under the pool
    assert 0 < got["serving.latent_rows_live"] \
        <= got["serving.attn_rows_read"] < got["serving.attn_rows_pool"]
    assert 0 < got["serving.moe_pairs_held"] \
        < got["serving.moe_pairs_routed"]
    assert got["serving.moe_experts_touched"] > 0


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
    five that the cut changes; the cut and the deployment stated."""
    c = json.load(open(CONFIG))
    published = {
        "hidden_size": 3584, "num_attention_heads": 32,
        "num_key_value_heads": 32, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 9216, "moe_intermediate_size": 1024,
        "num_experts_per_tok": 4, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": 2, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "max_position_embeddings": 262144, "moe_layer_freq": 1,
        "ep_size": 1}
    assert {k: c[k] for k in published} == published
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (c["scoring_func"], c["topk_method"], c["norm_topk_prob"]) \
        == ("sigmoid", "noaux_tc", True)
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "n_routed_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    assert [c[k] for k in c["reduced"]] == [12, 1, 16, 32768, 0]
    assert c["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    assert "four chips share each layer" in c["deployment"]
    assert {"lanes", "hyper_connection", "hc_res_diag", "rotary", "norms",
            "mtp", "weights"} <= set(c["assumed"])


def test_counters_by_hand():
    fam = H.load_module("families", "xing4")
    c = json.load(open(CONFIG))
    assert fam.layer_kinds(c) == (1, 11)
    assert [i for i in range(12) if fam.is_dense(c, i)] == [0]
    # W_dq 3584x768, W_uq 768x6144, W_dkv 3584x576, W_ukv 512x8192,
    # W_o 4096x3584 and the two norms: 28.41M
    attn = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 \
        + 4096 * 3584 + 768 + 512
    assert fam.attn_params(c) == attn == 28_411_136
    # Phi [24, 4 x 3584], three alpha, 24 biases, the sublayer's norm
    hc = 24 * 14336 + 3 + 24 + 3584
    assert fam.hc_params(c) == hc == 347_675
    # a latent row of 576 bf16 numbers in each of twelve layers: 13.8 KB
    assert fam.decode_cache_bytes_per_row(c) == 12 * 576 * 2 == 13_824
    assert fam.expert_bytes(c) == 2 * 3 * 3584 * 1024 == 22_020_096
    # 8.5 of 16 held experts touched in each of 11 routed layers
    assert fam.moe_decode_bytes(c, 8.5) == 11 * 8.5 * 22_020_096
    # each layer's weights once, 30,000 live rows once, 12 rows written
    assert fam.mla_decode_bytes(c, 30000.0, 12.0) \
        == 12 * (2 * attn + (30000 + 12) * 1152)
    # one token over 100 rows: the router over 64, the shared expert, a
    # quarter of four experts; the absorbed read (512 + 64 for a score,
    # 512 for a value, per head)
    moe = 3584 * 64 + 3 * 3584 * 1024 + 1.0 * 3 * 3584 * 1024
    assert fam.moe_macs_per_token(c) == moe
    assert fam.decode_flops(c, 1, 100) == 2.0 * (
        12 * (attn + 2 * hc) + 3 * 3584 * 9216 + 11 * moe + 3584 * 32768
        + 12 * 100 * 32 * (2 * 512 + 64))
    # every weight once: 2.75G parameters, 5.5 GB in bf16
    assert 5.45e9 < fam.weight_bytes(c) < 5.55e9


class Fixed:
    """A counter that reads what it was given."""

    def __init__(self, value):
        self.value = value


def test_new_readers_find_nothing_without_their_counter_or_trace(
        monkeypatch):
    """On the parent of this PR (counters that nothing ever counted, no
    such scopes, a family without ``mla_decode_bytes``) the four readers
    return None and do not raise."""
    ctx = {"trace": None,
           "traffic": {"programs": {"decode": "jit_step",
                                    "prefill": "jit_prefill"},
                       "slots": 48, "max_len": 9216},
           "spans": {"steps_per_round": 8}, "cfg": {}, "family": None,
           "peaks": {"hbm_bytes_per_s": 1.0}}
    import mxnet_tpu as mx
    monkeypatch.setattr(mx.telemetry, "counter", lambda name: Fixed(0))
    for name in READERS:
        assert H.load_module("metrics", name).read(ctx) is None


def test_latent_rows_reader_by_hand(monkeypatch):
    """The counter reader over counters set by hand: 360,000 live rows
    summed over 12 layers x 10 steps of a pool of 48 x 9,216."""
    import mxnet_tpu as mx
    values = {"serving.latent_rows_live": 360_000,
              "serving.attn_rows_pool": 48 * 9216 * 12 * 10}
    monkeypatch.setattr(mx.telemetry, "counter",
                        lambda name: Fixed(values[name]))
    roof = H.load_module("metrics", "mla_decode_roofline")
    ctx = {"traffic": {"slots": 48, "max_len": 9216}}
    assert roof.live_rows_per_layer_step(ctx) == 3000.0


def test_yarn_frequencies_against_the_formula():
    """Pair 0 turns as without scaling, the last pair 64 times slower, the
    blend between pairs 10 and 23 (the pairs that 32 turns and 1 turn over
    4,096 positions give at base 10,000 and 64 rotary dims)."""
    fam = H.load_module("families", "xing4")
    c = json.load(open(CONFIG))
    f = fam.yarn_inv_freq(c)
    theta = 10000.0 ** (-np.arange(32) / 32.0)
    assert f.shape == (32,)
    np.testing.assert_allclose(f[:11], theta[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], theta[23:] / 64, rtol=1e-12)
    ramp = (np.arange(32) - 10) / 13.0
    np.testing.assert_allclose(
        f[11:23], (theta / 64 * ramp + theta * (1 - ramp))[11:23],
        rtol=1e-12)
