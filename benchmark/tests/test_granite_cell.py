"""``granite-4.0-h-micro.serve-shortchat`` and ``opt-1.3b.serve-longprompt``
at their toy sizes on the CPU: each cell end to end through ``run.py``, the
Granite control coming out not correct, the counters of its family by hand,
the state roofline's reader on a recorded round, and the new readers finding
nothing to read in a program that lacks what they read."""
import json
import os

import numpy as np
import pytest

from benchmark import harness as H
from benchmark.tests.conftest import last_json

CELL = "granite-4.0-h-micro.serve-shortchat"
LONGPROMPT = "opt-1.3b.serve-longprompt"
READERS = ("ssm_decode_ms", "ssm_prefill_ms", "ssm_state_roofline_traced")


@pytest.mark.parametrize("cell", [CELL, LONGPROMPT])
def test_toy_cell_runs_end_to_end(toy_harness, capsys, cell):
    from benchmark import run
    import mxnet_tpu as mx
    tele = mx.telemetry
    names = ("serving.state_slots_advanced", "serving.state_slots_pool")
    before = {n: tele.counter(n).value for n in names}
    assert run.main(["--workload", cell, "--seed", "3000000007",
                     "--seconds", "2", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    line = last_json(out)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"tpot_p90_ms", "setup_s"}
    limits = toy_harness.load_cell(cell)["limits"]["limits"]
    assert set(line["checks"]) == set(limits) | {"never_answered",
                                                  "compiles_in_window"}
    assert line["checks"]["logit_gap"]["value"] <= 1e-3
    got = {n: tele.counter(n).value - before[n] for n in names}
    if cell == CELL:
        # a recurrent state advanced in some slots, never in all of them
        assert 0 < got["serving.state_slots_advanced"] \
            < got["serving.state_slots_pool"]
    else:
        assert got["serving.state_slots_pool"] == 0


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
    assert all(got[k] > limits[k] for k in limits), got


@pytest.mark.parametrize("cell", [CELL, LONGPROMPT])
def test_the_cells_own_limits_name_the_widest_gap(cell):
    from benchmark.drivers import serve as D
    real = H.load_json(H.CODE, "limits", cell + ".json")
    assert "logit_gap" in real["limits"]
    assert set(real["limits"]) <= set(D.GAP_NUMBERS)
    assert set(real["toy"]["limits"]) == set(real["limits"])


def _config():
    return json.load(open(os.path.join(H.CODE, "configs",
                                       "granite-4.0-h-micro.json")))


def test_configuration_is_the_published_one_uncut():
    """Every number of the published config under its own key, nothing
    reduced: 40 layers, the whole vocabulary, every width."""
    c = _config()
    published = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64,
        "num_attention_heads": 32, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "shared_intermediate_size": 8192,
        "vocab_size": 100352, "position_embedding_type": "nope",
        "tie_word_embeddings": True, "num_local_experts": 0}
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == []
    assert [i for i, t in enumerate(c["layer_types"]) if t == "attention"] \
        == [5, 15, 25, 35]
    assert {"recurrent_state", "weights", "positions"} <= set(c["assumed"])


def test_counters_by_hand():
    fam = H.load_module("families", "granite")
    c = _config()
    assert fam.layer_kinds(c) == (36, 4)
    # in_proj 2048 x 8512, conv 4352 x 4 with its bias, A_log, D and
    # dt_bias of 64, the gated norm's 4096, out_proj 4096 x 2048
    mamba = 2048 * 8512 + 4352 * 5 + 3 * 64 + 4096 + 4096 * 2048
    assert fam.mamba_params(c) == mamba == 25_847_232
    # q 2048 x 2048, k and v 512 x 2048 each, o 2048 x 2048
    attn = 2048 * 3072 + 2048 * 2048
    assert fam.attn_params(c) == attn == 10_485_760
    assert fam.mlp_params(c) == 3 * 2048 * 8192
    # K and V rows of 512 lanes, bf16, in the four attention layers: 8 KB
    assert fam.decode_cache_bytes_per_row(c) == 2 * 2 * 4 * 512 == 8192
    # per slot and Mamba-2 layer: a float32 state of 64 x 64 x 128 and a
    # window of 3 x 4352 bf16; over 36 layers 76.4 MB
    assert fam.ssm_state_bytes(c) == 4 * 64 * 64 * 128 + 2 * 3 * 4352
    assert fam.state_bytes_per_slot(c) == 36 * 2_123_264 == 76_437_504
    # one token over 100 rows: every mixer and MLP, the state's write and
    # read, the tied head over the vocabulary
    assert fam.decode_flops(c, 1, 100) == 2.0 * (
        36 * (mamba + 2 * 64 * 64 * 128) + 4 * attn
        + 40 * 3 * 2048 * 8192 + 2048 * 100352 + 4 * 2 * 100 * 2048)
    # 3,191,396,096 parameters: 6.38 GB in bf16
    assert fam.weight_bytes(c) == 2 * 3_191_396_096


class Fixed:
    """A counter that reads what it was given."""

    def __init__(self, value):
        self.value = value


def test_new_readers_find_nothing_without_their_scopes_or_counts(
        monkeypatch):
    """On a program from before the Mamba-2 op (no such scopes, no
    trace, a family without ``ssm_state_bytes``) the three readers return
    None and do not raise."""
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


class _Trace:
    """A parsed trace of two rounds: the engine's spans and the decode
    program's operations under the Mamba-2 scopes."""

    def __init__(self, state_s):
        self.host = [
            ("serving.decode_round", 0, 1, {"round": 7, "slots_busy": 30}),
            ("serving.round_counts", 2, 3,
             {"round": 7, "state_slots_advanced": 36 * 8 * 30}),
            ("serving.decode_round", 4, 5, {"round": 8, "slots_busy": 34}),
            ("serving.round_counts", 6, 7,
             {"round": 8, "state_slots_advanced": 36 * 8 * 34})]
        path = "jit(step)/Mamba2/layer0_mamba/%s/x"
        self.rows = [{"path": path % "state", "seconds": state_s},
                     {"path": path % "proj", "seconds": 1.0}]

    def spans(self, name):
        return [(s, e, st) for n, s, e, st in self.host if n == name]

    def calls(self, name):
        return 2 if name == "jit_step" else 0

    def scope_seconds(self, name, predicate):
        return sum(r["seconds"] for r in self.rows
                   if predicate(r["path"]))


def test_state_roofline_reads_a_recorded_round(monkeypatch):
    """Two joined rounds of 8 steps with 30 and 34 live slots over 36
    layers: least time = 32 x 8 x 36 slot-layers x the state's bytes read
    and written, over the time under ``Mamba2/*/state`` alone (the
    projections' second is left out)."""
    from benchmark import scopes as S
    import mxnet_tpu as mx
    fam = H.load_module("families", "granite")
    cfg = _config()
    per_round_s = 0.004
    monkeypatch.setattr(S, "of_run",
                        lambda ctx: _Trace(2 * per_round_s))
    monkeypatch.setattr(mx.telemetry, "counter", lambda name: Fixed(1))
    ctx = {"trace": {"window_s": 1.0}, "cfg": cfg, "family": fam,
           "traffic": {"programs": {"decode": "jit_step"}},
           "spans": {"steps_per_round": 8},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    got = H.load_module("metrics", "ssm_state_roofline_traced").read(ctx)
    least = 32 * 8 * 36 * 2 * fam.ssm_state_bytes(cfg) / 819e9
    assert got == pytest.approx(100.0 * least / per_round_s)
    assert H.load_module("metrics", "ssm_decode_ms").value(ctx) \
        == pytest.approx((2 * per_round_s + 1.0) / 2 * 1e3)
