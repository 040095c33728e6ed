"""The reader of the program's own names (``scopes.py``) on the two traces
recorded on the chip, and the five per-layer readers this PR brought:
what each sums, and that a run with nothing to read gives None, never
0."""
import json
import os
import shutil

import pytest

from benchmark import harness as H
from benchmark import scopes as S

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tiny.xplane.pb")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")

NEW = ["fwd_ms.train", "bwd_ms.train", "decode_attn_ms",
       "batch_occupancy.serve", "round_host_ms"]
# scoped.json's name for each part of ``fwd_ms.train.PARTS``
RECORDED = {"fwd": "fwd", "bwd": "bwd", "optimizer_unfused": "optimizer"}


def test_under_matches_whole_segments_inside_transforms():
    grads = S.under("mx.grads")
    assert grads("jit(_step_impl)/mx.grads/jvp(Convolution/c1)/conv")
    assert grads("jit(f)/mx.grads/transpose(mx.grads)/jvp(BatchNorm/b)/mul")
    assert not grads("jit(f)/mx.grads_extra/mul")
    assert not grads("jit(f)/mx.optimizer/mul") and not grads("")
    mha = S.under("MultiHeadAttention/")
    assert mha("jit(step)/while/body/vmap(MultiHeadAttention/l0_attn)/cache/x")
    assert mha("jit(f)/mx.grads/transpose(jvp(MultiHeadAttention/l0))/dot")
    assert not mha("jit(f)/NotMultiHeadAttention/l0/dot")
    assert S.NODE.search("jit(f)/vmap(LayerNorm/lnf)/reduce_sum")
    assert S.NODE.search("jit(f)/mx.grads/jvp(_Plus/plus0)/add")
    assert not S.NODE.search("jit(step)/while/body/dynamic_update_slice")


@pytest.mark.skipif(not os.path.exists(TINY), reason="no recorded trace")
def test_tiny_trace_gives_the_name_stack_of_each_operation():
    want = json.load(open(os.path.join(DATA, "tiny.json")))
    sc = S.read(TINY)
    by_name = {row["name"]: pid for pid, row in sc.programs.items()}
    assert set(by_name) == set(want["programs"])
    for name, row in want["programs"].items():
        got = sc.programs[by_name[name]]
        assert got["calls"] == row["calls"]
        # tiny.json's seconds are whole nanoseconds an event
        assert got["seconds"] == pytest.approx(row["seconds"], rel=1e-4)
        assert sc.calls(name) == row["calls"]
    a, b = by_name["jit_alpha"], by_name["jit_beta"]
    assert a.isdigit() and b.isdigit() and a != b       # program_id
    conv = sc.ops[a]["convolution_tanh_fusion"]
    assert conv["path"] == "jit(alpha)/dot_general"
    assert conv["category"] == "convolution fusion" and conv["calls"] == 3
    red = sc.ops[b]["multiply_reduce_fusion"]
    assert red["path"] == "jit(beta)/reduce_sum" and red["calls"] == 2
    # the operations of a program add up to its module time (to the gaps
    # between them)
    for name, pid in by_name.items():
        ops = sum(r["seconds"] for r in sc.leaves(name))
        assert 0.9 * sc.programs[pid]["seconds"] < ops \
            <= sc.programs[pid]["seconds"]
    assert sc.scope_seconds("jit_alpha", lambda p: "dot_general" in p) \
        == pytest.approx(conv["seconds"])
    assert sc.scope_seconds("jit_alpha", S.under("mx.grads")) == 0
    assert sc.host == []            # its host spans were ``bench.`` ones


@pytest.mark.skipif(not os.path.exists(SCOPED), reason="no recorded trace")
def test_scoped_trace_gives_hand_read_values():
    want = json.load(open(os.path.join(DATA, "scoped.json")))
    sc = S.read(SCOPED)
    name = want["program"]
    assert sc.calls(name) == want["calls"]
    fwd_ms = load("fwd_ms.train")
    for label, pred in fwd_ms.PARTS.items():
        # scoped.json's seconds were read to the nanosecond an operation
        assert sc.scope_seconds(name, pred) == pytest.approx(
            want["seconds"][RECORDED[label]], rel=1e-4), label
    assert sc.scope_seconds(name, S.under("FullyConnected/")) \
        == pytest.approx(want["seconds"]["FullyConnected"], rel=1e-4)
    total = sum(r["seconds"] for r in sc.leaves(name))
    scoped = sum(want["seconds"][k] for k in ("fwd", "bwd", "optimizer"))
    assert total - scoped == pytest.approx(want["unscoped_seconds"],
                                           rel=0.05)
    assert scoped / total == pytest.approx(want["scoped_share"], rel=1e-5)
    dec = sc.spans("serving.decode_round")
    assert [st["slots_busy"] for _, _, st in dec] == want["slots_busy"]
    assert [st["live_rows"] for _, _, st in dec] == want["live_rows"]
    assert len(sc.spans("serving.round")) == want["rounds"]
    assert len(sc.spans("serving.drain")) == len(dec)


@pytest.fixture()
def as_run(tmp_path, monkeypatch):
    """``as_run(path)``: the recorded trace laid where a run's driver
    writes its own, under a scratch ``harness.ROOT``."""
    monkeypatch.setattr(H, "ROOT", str(tmp_path))

    def lay(path):
        d = tmp_path / ".cache" / "trace" / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, d / os.path.basename(path))
    return lay


def load(name):
    return H.load_module("metrics", name)


def ctx_for(step=None, decode=None, slots=8, spans=None):
    programs = {k: v for k, v in (("step", step), ("decode", decode)) if v}
    return {"trace": {"window_s": 1.0}, "spans": spans or {},
            "traffic": {"programs": programs, "slots": slots}}


@pytest.mark.skipif(not os.path.exists(SCOPED), reason="no recorded trace")
def test_readers_on_the_scoped_trace(as_run, capsys):
    want = json.load(open(os.path.join(DATA, "scoped.json")))
    as_run(SCOPED)
    ctx = ctx_for(step=want["program"], decode=want["program"])
    per_call = {k: v / want["calls"] * 1e3
                for k, v in want["seconds"].items()}
    assert load("fwd_ms.train").read(ctx) == pytest.approx(per_call["fwd"],
                                                           rel=1e-4)
    assert load("bwd_ms.train").read(ctx) == pytest.approx(per_call["bwd"],
                                                           rel=1e-4)
    # a FullyConnected step holds no attention; its dense layers are on
    # the hand-read line only
    assert load("decode_attn_ms").read(ctx) is None
    # the window's count is the metric; the trace's spans are printed
    # beside it
    ctx["spans"] = {"live_slots": 30, "rounds": 6}
    assert load("batch_occupancy.serve").read(ctx) == pytest.approx(
        100.0 * 5 / 8)
    out = capsys.readouterr().out
    assert "scopes: %s calls=%d" % (want["program"], want["calls"]) in out
    assert "fwd_ms=" in out and "unscoped_top=[" in out
    assert "fc_dots_ms=%.4f" % per_call["FullyConnected"] in out
    busy = want["slots_busy"]
    assert "live_slots_mean=5.000 over 6 rounds" in out
    assert "spans=%d of the traced seconds slots_busy mean=%.3f" % (
        len(busy), sum(busy) / len(busy)) in out


@pytest.mark.skipif(not os.path.exists(TINY), reason="no recorded trace")
@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_trace_without_scopes(as_run, name):
    """The parent's programs carry no scope and its host no span: every
    new reader returns None (the metric is left out), never 0."""
    as_run(TINY)
    assert load(name).read(ctx_for(step="jit_alpha", decode="jit_beta")) \
        is None
    # and with no trace at all
    assert load(name).read({"trace": None, "spans": {},
                            "traffic": {"programs": {}}}) is None


def test_serving_readers_take_the_whole_window():
    """``round_host_ms`` is every phase of the engine's round but the
    wait for the device, ``batch_occupancy.serve`` the driver's count of
    live requests: both over all rounds of the window, trace or none."""
    spans = {"phase_ms": {"sched": 60.0, "prefix_lookup": 0.0, "h2d": 20.0,
                          "prefill": 100.0, "copy": 0.0, "dispatch": 520.0,
                          "drain": 29000.0},
             "rounds_in_window": 200, "rounds": 210, "live_slots": 1974}
    ctx = dict(ctx_for(slots=16, spans=spans), trace=None)
    assert load("round_host_ms").read(ctx) == pytest.approx(3.5)
    assert load("round_host_ms").read(ctx) \
        >= load("sched_host_ms").read(ctx) == pytest.approx(0.4)
    assert load("batch_occupancy.serve").read(ctx) \
        == pytest.approx(100.0 * 9.4 / 16)


def test_of_run_parses_once_and_follows_the_newest_trace(as_run,
                                                         monkeypatch):
    if not os.path.exists(TINY):
        pytest.skip("no recorded trace")
    as_run(TINY)
    calls = []
    real = S.read
    monkeypatch.setattr(S, "read", lambda p: calls.append(p) or real(p))
    ctx = ctx_for()
    first = S.of_run(ctx)
    assert S.of_run(ctx) is first and len(calls) == 1
    assert S.of_run({"trace": None}) is None


def test_every_new_entry_names_its_cells_and_resolves():
    bench = json.load(open(os.path.join(H.ROOT, "BENCHMARK.json")))
    rows = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(rows)      # by name: later PRs append their own
    for name in NEW:
        assert callable(load(name).read)
        moved = {e["name"]: e for e in bench["end_to_end"]}[
            rows[name]["moves"]]
        assert set(rows[name]["workloads"]) <= set(moved["workloads"])
