"""Serving time machine (ISSUE 13): traffic capture, deterministic
replay, and round-phase attribution.

The acceptance pin: a capture recorded from a SPEC-ON + prefix-cache +
chunked-prefill engine replays with verify passing on fresh engines in
two config flavors (speculation off; a different steps_per_round +
cache off) — byte-identity is the engine's existing contract, so the
capture/replay layer must only carry the request identities
faithfully. Phase-ledger honesty is pinned arithmetically: the phases
of every recorded round sum to its wall time (``sched`` is the exact
remainder). The compile-count contract
({decode, verify<=1, prefill/bucket, copy/bucket}) is re-pinned on
every engine here — capture, replay and attribution add ZERO compiled
programs.

Runtime discipline (test_serving.py precedent): one tiny 1-layer LM,
module-scoped capture fixture (ONE capture-source engine serves the
whole gauntlet, crash-cycle included), replay engines shared between
the tests that only read them, oracle outputs memoized. The
capture-stream unit tests (size bound, torn line) run on fake request
objects — zero compiles.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx

from check_utils import assert_compile_contract
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.serving import InferenceEngine, CaptureStream, \
    load_capture

import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tools import replay_serving  # noqa: E402

VOCAB, LAYERS, EMBED, HEADS = 17, 1, 16, 2
T = 16


def _lm():
    return get_transformer_lm(VOCAB, num_layers=LAYERS,
                              embed_dim=EMBED, num_heads=HEADS,
                              impl="dense")


@pytest.fixture(scope="module")
def lm():
    rng = np.random.RandomState(0)
    sym = _lm()
    shapes = {"data": (2, T), "softmax_label": (2, T)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.3, 0.3, s)
                             .astype(np.float32))
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    return sym, params, Decoder(sym, params, max_len=T)


_ORACLE = {}


def _oracle(dec, prompt, n):
    prompt = np.asarray(prompt)
    n = min(n, T - len(prompt))
    key = (id(dec), prompt.tobytes(), len(prompt), n)
    if key not in _ORACLE:
        _ORACLE[key] = np.asarray(
            dec.generate(prompt[None], num_steps=n))[0, len(prompt):]
    return _ORACLE[key]


def _dec(lm):
    sym, params, _ = lm
    return Decoder(sym, params, max_len=T)


# the capture-source config: speculation ON (n-gram), 1-slot prefix
# pool (eviction churn included), chunked prefill — the full gauntlet
# the acceptance criterion names
_CAP_CFG = dict(slots=2, prefill_buckets=(4, 8), prefix_cache_mb=0.0021,
                prefill_chunk=3, draft="ngram", spec_k=3)


def _workload(rng):
    """(prompt, max_tokens) mix exercising prefix hits, eviction,
    chunk boundaries, beyond-bucket chunked admission, and an
    engineered draft-accepting prompt (test_serving.py's probed
    cases — shapes reuse the oracle compile set)."""
    base = rng.randint(0, VOCAB, (7,))
    return [
        (base, 3),                                       # retained
        (base[:4].copy(), 6),                            # prefix hit
        (np.concatenate([base[:4], rng.randint(0, VOCAB, (3,))]), 3),
        (rng.randint(0, VOCAB, (2,)), 5),                # miss
        (base.copy(), 3),                                # full dup
        (rng.randint(0, VOCAB, (10,)), 3),               # beyond bucket
        (np.array([0, 3, 3]), 13),                       # spec-accepting
    ]


@pytest.fixture(scope="module")
def captured(lm, tmp_path_factory):
    """Record the module's capture: serve the gauntlet on a spec-on +
    prefix-cache + chunked engine with capture armed, then run a
    CRASH CYCLE (snapshot mid-flight -> close -> restore on the
    carried capture_dir) so the directory holds two generations of
    capture file. Returns everything the read-only tests need."""
    sym, params, dec = lm
    cap_dir = str(tmp_path_factory.mktemp("serving_capture"))
    eng = InferenceEngine(_dec(lm), capture_dir=cap_dir, **_CAP_CFG)
    # seed 11: a workload draw that is also argmax-STABLE under int8
    # weight quantization (seed 13's prefix case sits on a near-tie),
    # so the ISSUE 15 quantized-replay acceptance test can ride THIS
    # capture; every other test derives its expectations from the
    # capture + oracle dynamically and is seed-agnostic
    rng = np.random.RandomState(11)
    cases = _workload(rng)
    handles = [eng.submit(p, max_tokens=n) for p, n in cases]
    done = eng.serve_forever()
    assert len(done) == len(cases)
    assert_compile_contract(eng)
    rounds = eng.round_table()

    # crash cycle: two fresh requests, a few rounds in, snapshot,
    # close (the capture file flushes per record, so even a SIGKILL
    # here would have left everything durable), restore — the carried
    # capture_dir opens a SECOND capture file
    p_cut = rng.randint(0, VOCAB, (4,))
    cut = eng.submit(p_cut, max_tokens=6)
    for _ in range(20):
        eng.step()
        if len(cut.tokens) >= 2:       # some, not all, tokens drained
            break
    emitted_at_cut = len(cut.tokens)
    assert 0 < emitted_at_cut < 6
    snap = eng.snapshot()
    assert snap["engine"]["capture_dir"] == cap_dir
    path1 = eng.capture.path
    eng.close()
    eng2, resumed = InferenceEngine.restore(snap, _dec(lm))
    assert eng2.capture.enabled and eng2.capture.path != path1
    eng2.serve_forever()
    np.testing.assert_array_equal(resumed[cut.id].result(),
                                  _oracle(dec, p_cut, 6))
    path2 = eng2.capture.path
    eng2.close()
    return {
        "dir": cap_dir, "path": path1, "path2": path2,
        "cases": cases, "handles": handles, "rounds": rounds,
        "cut": cut, "emitted_at_cut": emitted_at_cut, "p_cut": p_cut,
    }


@pytest.fixture(scope="module")
def replay_spec_off(lm, captured):
    """Replay flavor 1: speculation OFF (the capture was spec-on).
    Module-scoped — the recorded-timing test reuses it with zero new
    compiles."""
    cap = load_capture(captured["path"])
    eng = replay_serving.build_engine(cap, _dec(lm), draft="off")
    report = replay_serving.replay(cap, eng, timing="max", verify=True)
    return eng, report


def test_capture_file_complete_and_replayable_header(lm, captured):
    """The capture is a readable JSONL: header first (geometry +
    max_len — everything build_engine needs), one submit per accepted
    request with ascending arrival times and the full sampling
    identity, one retire per completion with the emitted tokens the
    handles actually got."""
    cap = load_capture(captured["path"])
    geo = cap["engine"]
    assert geo["slots"] == 2 and geo["prefill_chunk"] == 3
    assert geo["draft"] == "ngram" and geo["spec_k"] == 3
    assert geo["max_len"] == T
    # submits: the gauntlet + the crash-cycle request
    assert len(cap["submits"]) == len(captured["cases"]) + 1
    ts = [s["t"] for s in cap["submits"]]
    assert ts == sorted(ts) and all(t >= 0 for t in ts)
    for s in cap["submits"]:
        assert isinstance(s["prompt"], list) and s["max_tokens"] >= 1
        assert "seed" in s and "temperature" in s
    # retires: every gauntlet completion carries its exact tokens
    by_id = {h.id: h for h in captured["handles"]}
    for rid, h in by_id.items():
        rec = cap["retires"][rid]
        assert rec["reason"] == h.retire_reason
        assert rec["tokens"] == [int(t) for t in h.tokens]
        assert rec["ttft_ms"] > 0
    # the crash-cut request retired as "closed" with its partial
    # tokens — the tape records the incident as it happened
    cut = captured["cut"]
    assert cap["retires"][cut.id]["reason"] == "closed"
    assert len(cap["retires"][cut.id]["tokens"]) \
        == captured["emitted_at_cut"]


def test_capture_header_records_migration_provenance(captured):
    """Fleet satellite (ISSUE 16): restore() under an armed
    capture_dir stamps the SOURCE engine's id into the successor's
    capture header (``migrated_from``) — the tape of the
    post-migration generation says where its work came from, and the
    original generation says it came from nowhere."""
    cap1 = load_capture(captured["path"])
    cap2 = load_capture(captured["path2"])
    assert cap1["engine"]["engine_id"]
    assert cap1["engine"]["migrated_from"] is None
    assert cap2["engine"]["migrated_from"] \
        == cap1["engine"]["engine_id"]
    # the successor is a NEW replica identity, not a clone
    assert cap2["engine"]["engine_id"] != cap1["engine"]["engine_id"]


def test_replay_verify_spec_off_byte_identical(lm, captured,
                                               replay_spec_off):
    """Acceptance flavor 1: the spec-on capture replays on a spec-OFF
    engine with every normally-completed request byte-identical and
    the crash-cut request verified as a prefix. Compile contract:
    replay adds nothing (and no verify program compiles — draft is
    off)."""
    eng, report = replay_spec_off
    n_complete = len(captured["cases"])
    assert report["verified"] == n_complete
    assert report["verified_prefix"] == 1          # the crash-cut one
    assert report["mismatches"] == []
    assert report["verify_skipped"] == 0
    assert_compile_contract(eng, verify=0)
    # the report carries the recorded run's latency block to diff
    # against (the capture's own retire timings)
    assert report["recorded"]["ttft_p50_ms"] > 0
    assert report["requests"] == report["replayed"]


def test_replay_header_with_a_stale_attn_impl_key(lm, captured):
    """A capture header written by an older tree names the decode read
    it took (``"attn_impl"``): build_engine reads the key and ignores
    it, and the replay verifies byte for byte."""
    cap = load_capture(captured["path"])
    assert "attn_impl" not in cap["engine"]
    cap["engine"]["attn_impl"] = "paged"
    eng = replay_serving.build_engine(cap, _dec(lm), draft="off")
    report = replay_serving.replay(cap, eng, timing="max", verify=True)
    assert report["verified"] == len(captured["cases"])
    assert report["verified_prefix"] == 1
    assert report["mismatches"] == []


def test_replay_verify_different_round_geometry(lm, captured):
    """Acceptance flavor 2: steps_per_round=2 + prefix cache OFF —
    different scheduling granularity, no copy programs, speculation
    still on from the header. Byte-identity must hold; the compile
    contract shows the geometry change (no copies)."""
    cap = load_capture(captured["path"])
    eng = replay_serving.build_engine(cap, _dec(lm),
                                      steps_per_round=2,
                                      prefix_cache_mb=0)
    assert eng.steps_per_round == 2 and eng._prefix is None
    assert not eng.capture.enabled       # replay does not re-capture
    report = replay_serving.replay(cap, eng, timing="max", verify=True)
    assert report["verified"] == len(captured["cases"])
    assert report["verified_prefix"] == 1
    assert report["mismatches"] == []
    assert_compile_contract(eng, copy={})


def test_replay_verify_tp2(lm, captured):
    """Acceptance flavor 3 (ISSUE 14): the ``--tp`` override axis — a
    single-chip capture validates a TENSOR-PARALLEL config offline.
    The spec-on + prefix-cache + chunked capture replays verify-clean
    on a tp=2 engine (KV cache and every program sharded over a real
    2-device mesh; greedy byte-identity across tp is part of the
    serving contract), crash-cut request prefix-verified, compile
    contract intact."""
    cap = load_capture(captured["path"])
    assert cap["engine"].get("tp", 1) == 1    # captured single-chip
    eng = replay_serving.build_engine(cap, _dec(lm), tp=2)
    assert eng.tp == 2 and eng._mesh is not None
    report = replay_serving.replay(cap, eng, timing="max", verify=True)
    assert report["verified"] == len(captured["cases"])
    assert report["verified_prefix"] == 1
    assert report["mismatches"] == []
    assert_compile_contract(eng)


def test_replay_verify_weight_dtype_int8(lm, captured):
    """Acceptance flavor 4 (ISSUE 15): the ``--weight-dtype`` override
    axis — the spec-on + prefix-cache + chunked capture replays on a
    QUANTIZED-weight engine. The capture header records the float
    dtype, so ``--verify`` switches to the prefix-equality/tolerance
    mode automatically (quantized numerics void the byte-identity
    contract); this workload is argmax-stable under the ~0.5% weight
    rounding, so every request — crash-cut one included — agrees in
    full. An exact-mode fp replay of the same capture is flavor 1."""
    cap = load_capture(captured["path"])
    assert cap["engine"].get("weight_dtype") == "float"
    eng = replay_serving.build_engine(cap, _dec(lm),
                                      weight_dtype="int8")
    assert eng.weight_dtype == "int8"
    report = replay_serving.replay(cap, eng, timing="max",
                                   verify=True)
    assert report["verify_mode"] == "prefix"
    assert report["mismatches"] == []
    # prefix mode verifies EVERY retired request by common prefix
    assert report["verified_prefix"] == len(captured["cases"]) + 1
    assert report["verified"] == 0
    assert_compile_contract(eng)


def test_replay_recorded_timing_paces_arrivals(lm, captured,
                                               replay_spec_off):
    """--timing recorded replays the captured inter-arrival gaps: a
    hand-built two-submit capture 0.25 s apart takes at least that
    long, while the same capture under --timing max does not wait.
    Runs on the module replay engine — ZERO new compiles (pinned)."""
    eng, _ = replay_spec_off
    cap = load_capture(captured["path"])
    rng = np.random.RandomState(3)
    sub = []
    for i, t in enumerate((0.0, 0.25)):
        sub.append({"kind": "submit", "t": t, "id": "pace-%d" % i,
                    "prompt": rng.randint(0, VOCAB, (4,)).tolist(),
                    "max_tokens": 2, "temperature": 0.0, "seed": i})
    cap2 = {"engine": cap["engine"], "version": 1, "submits": sub,
            "retires": {}}
    log_len = len(eng._compile_log)
    rep = replay_serving.replay(cap2, eng, timing="recorded")
    assert rep["wall_s"] >= 0.25 and rep["replayed"] == 2
    rep_max = replay_serving.replay(cap2, eng, timing="max")
    assert rep_max["wall_s"] < rep["wall_s"]
    assert len(eng._compile_log) == log_len
    with pytest.raises(ValueError, match="timing"):
        replay_serving.replay(cap2, eng, timing="bogus")


def test_crash_cycle_second_capture_resumes(lm, captured):
    """snapshot() carried capture_dir across the crash cycle: the
    restored engine wrote a SECOND capture file whose resubmit records
    carry the pre-crash tokens as resume_tokens (replaying THAT
    capture reproduces the continuation, not the whole request), and
    whose retire shows the completed continuation."""
    assert captured["path2"] != captured["path"]
    assert os.path.dirname(captured["path2"]) == captured["dir"]
    cap2 = load_capture(captured["path2"])
    cut = captured["cut"]
    sub = {s["id"]: s for s in cap2["submits"]}[cut.id]
    assert sub["resume_tokens"] == \
        [int(t) for t in cut.tokens[:captured["emitted_at_cut"]]]
    ret = cap2["retires"][cut.id]
    assert ret["reason"] in ("eos", "length")
    np.testing.assert_array_equal(
        np.asarray(ret["tokens"]),
        _oracle(lm[2], captured["p_cut"], 6))


def test_round_phase_ledger_sums_to_wall(lm, captured):
    """Phase-ledger honesty (acceptance criterion): for EVERY recorded
    round the phases sum to the round's wall time within the ledger's
    0.1 us rounding; rows are bounded, ascending, and carry the
    dispatch kind; the serving.round_phase_ms.* histograms are
    populated process-wide. The ledger rows come from the capture
    engine's full gauntlet run."""
    rounds = captured["rounds"]
    assert 0 < len(rounds) <= 256
    assert [r["round"] for r in rounds] == \
        sorted(r["round"] for r in rounds)
    kinds = set()
    for r in rounds:
        total = sum(r["phases_ms"].values())
        assert total == pytest.approx(r["wall_ms"], abs=1e-2), r
        assert r["wall_ms"] > 0 and "sched" in r["phases_ms"]
        assert all(v >= 0 for v in r["phases_ms"].values())
        assert r["dispatched"] in (None, "decode", "verify")
        kinds.add(r["dispatched"])
        assert set(r["phases_ms"]) <= {
            "sched", "prefix_lookup", "h2d", "prefill", "copy",
            "dispatch", "drain"}
    # the gauntlet dispatched real work: decode and/or verify rounds,
    # prefill + copy + drain phases all appeared somewhere
    assert kinds & {"decode", "verify"}
    seen = set()
    for r in rounds:
        seen.update(k for k, v in r["phases_ms"].items() if v > 0)
    assert {"prefill", "copy", "dispatch", "drain"} <= seen
    snap = mx.telemetry.snapshot()["serving"]
    for ph in ("sched", "prefill", "dispatch", "drain"):
        assert snap["round_phase_ms"][ph]["count"] >= 1
    assert snap["round_wall_ms"]["count"] >= len(rounds)


def test_round_table_returns_bounded_copies(lm, captured,
                                            replay_spec_off):
    """round_table(n) truncation + copy semantics on a live engine."""
    eng, _ = replay_spec_off
    rows = eng.round_table()
    assert rows, "replay engine recorded no rounds"
    assert len(eng.round_table(2)) == min(2, len(rows))
    assert eng.round_table(0) == []          # last 0 rows IS no rows
    eng.round_table()[-1]["phases_ms"]["sched"] = 1e9
    assert eng.round_table()[-1]["phases_ms"].get("sched", 0) != 1e9


class _FakeReq:
    """Just the attributes CaptureStream reads — zero-compile unit
    tests for the stream itself."""

    def __init__(self, rid, prompt=(1, 2, 3), tokens=(), resumed=0):
        self.id = rid
        self.prompt = np.asarray(prompt, np.int32)
        self.max_tokens = 4
        self.eos_id = None
        self.temperature = 0.0
        self.seed = 7
        self.deadline_ms = None
        self.ttft_deadline_ms = None
        self.resumed = resumed
        self.tokens = list(tokens)
        self.t_submit = 100.0
        self.t_first = 100.5
        self.t_done = 101.0
        self.retire_reason = "length"


def test_capture_stream_size_bound_and_terminal_retires(tmp_path):
    """MXNET_SERVING_CAPTURE_MB semantics at the stream level: past
    the byte budget NEW submits are skipped (counted), but the retire
    of an ALREADY-captured submit always lands (the log must stay
    verify-replayable); retires of uncaptured submits are dropped."""
    path = str(tmp_path / "cap.jsonl")
    st = CaptureStream(path, max_bytes=400, header={"slots": 1})
    st._t0 = 0.0
    st.submit(_FakeReq("a"))
    for i in range(50):
        st.submit(_FakeReq("fill-%d" % i))
    assert st.skipped > 0
    captured_ids = {json.loads(l)["id"]
                    for l in open(path) if '"submit"' in l}
    assert "a" in captured_ids and len(captured_ids) < 51
    # retire of a captured submit lands even past the budget...
    st.retire(_FakeReq("a", tokens=(5, 6)))
    # ...retire of a skipped submit does not
    st.retire(_FakeReq("fill-49", tokens=(9,)))
    st.close()
    cap = load_capture(path)
    assert cap["retires"]["a"]["tokens"] == [5, 6]
    assert "fill-49" not in cap["retires"]
    assert len(cap["submits"]) == len(captured_ids)


def test_capture_loader_torn_line_and_validation(tmp_path):
    """Crash-safety contract: a torn FINAL line (killed mid-write) is
    tolerated; garbage mid-file, a headerless file, and an empty file
    are loud errors; capture_mb <= 0 is rejected at open."""
    path = str(tmp_path / "cap.jsonl")
    st = CaptureStream(path, max_bytes=1 << 20, header={"slots": 1})
    st._t0 = 0.0
    st.submit(_FakeReq("x"))
    st.retire(_FakeReq("x", tokens=(1,)))
    st.close()
    with open(path, "a") as f:
        f.write('{"kind": "submit", "t": 9, "id": "to')  # torn
    cap = load_capture(path)
    assert len(cap["submits"]) == 1 and "x" in cap["retires"]
    # garbage mid-file: loud
    lines = open(path).read().splitlines()
    bad = str(tmp_path / "bad.jsonl")
    open(bad, "w").write("\n".join([lines[0], "not json", lines[1]]))
    with pytest.raises(MXNetError, match="unparseable"):
        load_capture(bad)
    # headerless / empty: loud
    nohdr = str(tmp_path / "nohdr.jsonl")
    open(nohdr, "w").write(lines[1] + "\n")
    with pytest.raises(MXNetError, match="header"):
        load_capture(nohdr)
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").write("")
    with pytest.raises(MXNetError, match="empty"):
        load_capture(empty)
    with pytest.raises(MXNetError, match="CAPTURE_MB"):
        CaptureStream.open(str(tmp_path), 0, {"slots": 1}, 0.0)
    # capture failures never unwind the caller (review finding — a
    # raise out of submit/retire would corrupt engine state
    # mid-mutation): an unserializable record is skipped + counted,
    # an I/O error disables the stream and later writes no-op
    st2 = CaptureStream(str(tmp_path / "iso.jsonl"), 1 << 20,
                        {"slots": 1})
    st2._t0 = 0.0
    st2.submit(_FakeReq(object()))           # np.int64-style bad id
    assert st2.skipped == 1 and st2.enabled

    class _BoomFile:
        def write(self, s):
            raise OSError("disk full")

        def flush(self):
            pass

        def close(self):
            pass

    st2._f = _BoomFile()
    st2.submit(_FakeReq("ok-id"))            # no raise
    assert not st2.enabled                   # stream self-disabled
    st2.submit(_FakeReq("after"))            # no-op, still no raise
    st2.close()
    # a disabled stream (no dir) is a no-op everywhere
    off = CaptureStream.open(None, None, {"slots": 1}, 0.0)
    assert not off.enabled
    off.submit(_FakeReq("y"))
    off.retire(_FakeReq("y"))
    off.close()
