"""``moe_roofline_traced`` and ``mla_roofline_traced`` on hand-made traces:
the engine's ``serving.round_counts`` joined to ``serving.decode_round`` by
``round``, a round missing on either side left out, None without the round
counts, and the run-wide counters, which a traced window need not resemble,
read by nothing."""
import json
import os

import pytest

from benchmark import harness as H
from benchmark import scopes as S

ZAYA = "zaya1-8b.serve-reason"
XING = "xing4.0-29b-a4b.serve-longctx"
ROUTED = (ZAYA, "qwen3-next-80b-a3b.serve-longdoc", XING)
HBM = 819e9


class Fixed:
    def __init__(self, value):
        self.value = value


@pytest.fixture()
def run_wide(monkeypatch):
    """Whole-run counters that say something else than the traced
    rounds: what they read must not reach a reading."""
    import mxnet_tpu as mx
    values = {}
    monkeypatch.setattr(mx.telemetry, "counter", lambda name: Fixed(
        values.get(name.split(".", 1)[1], 0)))
    return values


def trace(monkeypatch, host, decode_ms):
    """The run's trace as ``host`` spans, and ``decode_ms`` as the device
    time both ``*_decode_ms`` readers give."""
    sc = S.Scopes({}, {}, [(n, i * 10, i * 10 + 5, st)
                           for i, (n, st) in enumerate(host)])
    monkeypatch.setattr(S, "of_run", lambda ctx: sc)
    for name in ("moe_decode_ms", "mla_decode_ms"):
        monkeypatch.setattr(H.load_module("metrics", name), "value",
                            lambda ctx, which="decode": decode_ms)


def context(cell):
    c = H.load_cell(cell)
    return {"cfg": c["cfg"], "family": c["family"], "traffic": c["traffic"],
            "peaks": {"hbm_bytes_per_s": HBM}, "trace": {"busy_s": 1.0},
            "spans": {"steps_per_round": 8, "rounds": 100,
                      "live_slots": 490}}


def rounds(dispatched, drained):
    """``serving.decode_round`` for the rounds ``dispatched``
    ({round: slots_busy}) and ``serving.round_counts`` for those
    ``drained`` ({round: counts}), dispatches first as in a trace."""
    return [("serving.decode_round", {"slots_busy": b, "round": n})
            for n, b in dispatched.items()] \
        + [("serving.round_counts", dict(c, round=n))
           for n, c in drained.items()]


def test_the_entries_name_their_cells():
    bench = json.load(open(os.path.join(H.ROOT, "BENCHMARK.json")))
    rows = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] \
        == ["moe_roofline_traced", "mla_roofline_traced"]
    for name, cells in (("moe_roofline_traced", list(ROUTED)),
                        ("mla_roofline_traced", [XING])):
        assert rows[name] == {
            "name": name, "unit": "%", "better": "higher",
            "source": "program_span", "layer": "kernels",
            "moves": "tpot_p90_ms", "workloads": cells}
        for cell in cells:
            assert name in [m["name"] for m in H.load_cell(cell)["per_layer"]]
    listed = [m["name"]
              for m in H.load_cell("opt-1.3b.serve-chat")["per_layer"]]
    assert "moe_roofline_traced" not in listed


def test_moe_joins_by_round_and_leaves_the_unmatched_out(
        monkeypatch, run_wide, capsys):
    """Round 0's counts drained before the trace opened and round 3's
    after it closed; round 4's counts name a round whose dispatch the
    trace missed. Rounds 1 and 2 join: (70 + 90) experts over (20 + 20)
    layer-steps, 4 a layer-step."""
    ctx = context(ZAYA)
    trace(monkeypatch, rounds(
        {0: 9, 1: 10, 2: 12, 3: 11},
        {1: {"moe_experts_touched": 70, "moe_layer_steps": 20},
         2: {"moe_experts_touched": 90, "moe_layer_steps": 20},
         4: {"moe_experts_touched": 400, "moe_layer_steps": 20}}), 50.0)
    run_wide.update(moe_experts_touched=1000, moe_layer_steps=100)
    mod = H.load_module("metrics", "moe_roofline_traced")
    got = mod.read(ctx)
    fam, cfg = ctx["family"], ctx["cfg"]
    want = 100.0 * 8 * fam.moe_decode_bytes(cfg, 4.0) / HBM / 50e-3
    assert got == pytest.approx(want)
    assert 0 < got < 100
    out = capsys.readouterr().out
    assert "rounds joined=2" in out and "= 4.000 of 16 held" in out
    assert "(160 of 40 layer-steps), 10.000 over the run" in out
    # a whole run that says otherwise changes nothing
    run_wide.update(moe_experts_touched=1, moe_layer_steps=1000)
    assert mod.read(ctx) == got


def test_moe_takes_the_held_experts_from_the_family(monkeypatch, run_wide,
                                                    capsys):
    ctx = context(XING)
    trace(monkeypatch, rounds(
        {7: 5}, {7: {"moe_experts_touched": 33, "moe_layer_steps": 11}}),
        20.0)
    assert H.load_module("metrics", "moe_roofline_traced").read(ctx) > 0
    assert "= 3.000 of 16 held" in capsys.readouterr().out


def test_mla_joins_rows_and_slots_of_the_same_rounds(monkeypatch, run_wide,
                                                     capsys):
    """Two rounds join (busy 8 and 9): 12 latent layers x 8 steps each,
    so their pools are 2 x 96 layer-steps of slots x max_len rows."""
    ctx = context(XING)
    t = ctx["traffic"]
    pool = t["slots"] * t["max_len"] * 96
    trace(monkeypatch, rounds(
        {1: 8, 2: 9, 3: 40},
        {0: {"latent_rows_live": 1, "attn_rows_pool": pool},
         1: {"latent_rows_live": 96 * 2000, "attn_rows_pool": pool},
         2: {"latent_rows_live": 96 * 3000, "attn_rows_pool": pool}}),
        15.0)
    run_wide.update(latent_rows_live=10, attn_rows_pool=pool)
    mod = H.load_module("metrics", "mla_roofline_traced")
    got = mod.read(ctx)
    want = 100.0 * 8 * ctx["family"].mla_decode_bytes(
        ctx["cfg"], 2500.0, 8.5) / HBM / 15e-3
    assert got == pytest.approx(want)
    assert 0 < got < 100
    out = capsys.readouterr().out
    assert "rounds joined=2" in out
    assert "= 2500.0 over 8.50 busy slots of them, 0.1 over 4.90" in out
    run_wide.update(latent_rows_live=10 ** 9)
    assert mod.read(ctx) == got


@pytest.mark.parametrize("name", ["moe_roofline_traced",
                                  "mla_roofline_traced"])
def test_nothing_to_read_without_the_round_counts(monkeypatch, run_wide,
                                                  name):
    """The parent's trace has ``serving.decode_round`` spans (without a
    ``round``) and no round counts; the run-wide counters are there."""
    ctx = context(XING)
    run_wide.update(moe_experts_touched=50, moe_layer_steps=10,
                    latent_rows_live=100, attn_rows_pool=10 ** 6)
    mod = H.load_module("metrics", name)
    trace(monkeypatch, [("serving.decode_round", {"slots_busy": 5})] * 3,
          20.0)
    assert mod.read(ctx) is None
    # rounds on both sides that never meet
    trace(monkeypatch, rounds({1: 5}, {2: {"moe_layer_steps": 11,
                                           "attn_rows_pool": 10 ** 6}}),
          20.0)
    assert mod.read(ctx) is None
    # no trace at all
    monkeypatch.setattr(S, "of_run", lambda ctx: None)
    assert mod.read(ctx) is None
