"""``nemotron-3-super-120b-a12b.serve-think`` at its toy size on the CPU: the
cell end to end through ``run.py``, the control coming out not correct, the
configuration against the catalog's published numbers, the counts of its
family by hand, and the latent projections' reader on a recorded round and
on a program that lacks what it reads."""
import json
import os

import numpy as np
import pytest

from benchmark import harness as H
from benchmark.tests.conftest import last_json

CELL = "nemotron-3-super-120b-a12b.serve-think"
CONFIG = os.path.join(H.CODE, "configs", "nemotron-3-super-120b-a12b.json")


def test_toy_cell_runs_end_to_end(toy_harness, capsys):
    from benchmark import run
    import mxnet_tpu as mx
    tele = mx.telemetry
    names = ("serving.state_slots_advanced", "serving.state_slots_pool",
             "serving.moe_experts_touched", "serving.moe_layer_steps")
    before = {n: tele.counter(n).value for n in names}
    assert run.main(["--workload", CELL, "--seed", "3000000043",
                     "--seconds", "2", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    line = last_json(out)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"tpot_p90_ms", "setup_s"}
    limits = toy_harness.load_cell(CELL)["limits"]["limits"]
    assert set(line["checks"]) == set(limits) | {"never_answered",
                                                  "compiles_in_window"}
    assert line["checks"]["logit_gap"]["value"] <= 1e-3
    got = {n: tele.counter(n).value - before[n] for n in names}
    # a recurrent state advanced in some slots, never in all of them, and
    # the LatentMoE layers counted beside the attention layer's rows
    assert 0 < got["serving.state_slots_advanced"] \
        < got["serving.state_slots_pool"]
    assert got["serving.moe_layer_steps"] > 0
    assert 0 < got["serving.moe_experts_touched"] \
        <= 4 * got["serving.moe_layer_steps"]


def test_control_is_not_correct(toy_harness):
    """The token the fp8 reference puts first lies further below the
    float32 reference's best than the cell's toy limits allow."""
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
    assert all(got[k] > limits[k] for k in limits), got


def test_the_cells_own_limits_name_the_widest_gap():
    from benchmark.drivers import serve as D
    real = H.load_json(H.CODE, "limits", CELL + ".json")
    assert "logit_gap" in real["limits"]
    assert set(real["limits"]) == set(D.GAP_NUMBERS)
    assert set(real["toy"]["limits"]) == set(real["limits"])


def test_configuration_carries_every_published_width():
    """Every number of the catalog's config under its own key, but the
    five that the cut changes; the cut and the deployment stated."""
    c = json.load(open(CONFIG))
    published = {
        "hidden_size": 4096, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 128,
        "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
        "conv_kernel": 4, "chunk_size": 128, "expand": 2,
        "moe_intermediate_size": 2688, "intermediate_size": 2688,
        "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
        "num_experts_per_tok": 22, "n_shared_experts": 1,
        "routed_scaling_factor": 5, "norm_eps": 1e-05,
        "layer_norm_epsilon": 1e-05, "n_group": 1, "topk_group": 1,
        "max_position_embeddings": 262144}
    assert {k: c[k] for k in published} == published
    assert (c["mlp_hidden_act"], c["mamba_hidden_act"], c["model_type"]) \
        == ("relu2", "silu", "nemotron_h")
    assert not (c["attention_bias"] or c["mlp_bias"] or c["use_bias"]
                or c["mamba_proj_bias"] or c["tie_word_embeddings"])
    assert c["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                            "n_routed_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    assert [c[k] for k in c["reduced"]] == [11, "EMEMEMEMEM*", 128, 32768,
                                            0]
    pat = c["published"]["hybrid_override_pattern"]
    assert (len(pat), pat.count("M"), pat.count("E"), pat.count("*")) \
        == (88, 40, 40, 8)
    assert pat[26:37] == c["hybrid_override_pattern"]
    assert c["published"]["n_routed_experts"] == 512
    assert c["published"]["vocab_size"] == 131072
    assert "four chips share each layer" in c["deployment"]
    assert {"latent_placement", "groups", "recurrent_state", "weights",
            "correction_bias", "mtp"} <= set(c["assumed"])


def test_counters_by_hand():
    fam = H.load_module("families", "nemotron_h")
    c = json.load(open(CONFIG))
    assert fam.layer_kinds(c) == (5, 1, 5)
    # in_proj 4096 x 18560, conv 10240 x 4 with its bias, A_log, D and
    # dt_bias of 128, the grouped norm's 8192, out_proj 8192 x 4096
    mamba = 4096 * 18560 + 10240 * 5 + 3 * 128 + 8192 + 8192 * 4096
    assert fam.mamba_params(c) == mamba == 109_635_968
    # q 4096 x 4096, k and v 256 x 4096 each, o 4096 x 4096
    attn = 4096 * 4608 + 4096 * 4096
    assert fam.attn_params(c) == attn == 35_651_584
    # the router 512 x 4096 and its bias, the latent projections, the
    # shared expert, 128 held experts of 2 x 1024 x 2688
    moe = 512 * 4097 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        + 128 * 2 * 1024 * 2688
    assert fam.moe_params(c) == moe == 759_169_536
    assert fam.expert_bytes(c) == 2 * 2 * 1024 * 2688 == 11_010_048
    assert fam.moe_decode_bytes(c, 97.0) == 5 * 97.0 * 11_010_048
    # K and V rows of 256 lanes, bf16, in the one attention layer: 1 KB
    assert fam.decode_cache_bytes_per_row(c) == 2 * 2 * 256 == 1024
    # per slot and Mamba-2 layer: a float32 state of 128 x 64 x 128 and a
    # window of 3 x 10240 bf16; over 5 layers 21.3 MB
    assert fam.ssm_state_bytes(c) == 4 * 128 * 64 * 128 + 2 * 3 * 10240
    assert fam.state_bytes_per_slot(c) == 5 * 4_255_744 == 21_278_720
    # one token over 100 rows: every sublayer, a quarter of 22 experts,
    # the head over the vocabulary's slice
    per = 5 * (mamba + 2 * 128 * 64 * 128) + attn \
        + 5 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 22 * 128 / 512.0 * 2 * 1024 * 2688) + 4096 * 32768
    assert fam.decode_flops(c, 1, 100) == pytest.approx(
        2.0 * (per + 2 * 100 * 32 * 128))
    # 4,648,163,712 parameters: 9.30 GB in bf16
    assert fam.weight_bytes(c) == 2 * 4_648_163_712


class _Trace:
    """A parsed trace of two decode calls: the routed experts' parts and
    their latent projections."""

    def __init__(self, latent_s):
        path = "jit(step)/MoEFFN/layer0_moe/%s/x"
        self.rows = [{"path": path % "latent", "seconds": latent_s},
                     {"path": path % "experts", "seconds": 1.0},
                     {"path": "jit(step)/Mamba2/layer1_mamba/latent/x",
                      "seconds": 5.0}]

    def calls(self, name):
        return 2 if name == "jit_step" else 0

    def scope_seconds(self, name, predicate):
        return sum(r["seconds"] for r in self.rows
                   if predicate(r["path"]))


def test_latent_reader_reads_a_recorded_round_and_nothing_without_it(
        monkeypatch):
    from benchmark import scopes as S
    reader = H.load_module("metrics", "moe_latent_ms")
    ctx = {"trace": {"window_s": 1.0},
           "traffic": {"programs": {"decode": "jit_step"}}}
    monkeypatch.setattr(S, "of_run", lambda ctx: _Trace(0.0004))
    assert reader.read(ctx) == pytest.approx(0.2)
    monkeypatch.setattr(S, "of_run", lambda ctx: _Trace(0.0))
    assert reader.read(ctx) is None
    monkeypatch.setattr(S, "of_run", lambda ctx: None)
    assert reader.read(ctx) is None
