"""Serving engine under hostile traffic and partial failures
(doc/serving.md "Serving under hostile traffic"): deadlines,
cancellation, load shedding, the round watchdog, poisoned-request
isolation, shutdown, and crash-safe snapshot()/restore() — driven
deterministically by the serving-side FaultInjector hooks
(mxnet_tpu.testing.faults).

The correctness bar is the same as tests/test_serving.py: every
SURVIVING request's greedy output stays byte-identical to offline
``Decoder.generate`` no matter what retired, wedged, or crashed around
it, and the compile-count contract is untouched — every robustness
mechanism is host-side. Every fault path must also drain clean: free
slots and prefix-cache pins return to their pre-test values (a leaked
pin is eventual pool starvation).

Runtime discipline (tier-1 budget): TWO module-scoped engines serve
almost every test — a plain one (lifecycle/overload/watchdog; its
``overload``/``max_queue``/``round_timeout_ms`` knobs are plain
mutable attributes, flipped and restored per test) and a prefix-cache+
chunked-prefill one (poison/crash) — and the close test closes the
plain engine LAST instead of building its own. Oracle calls reuse a
small set of (prompt_len, num_steps) shapes."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.serving import (InferenceEngine, EngineOverloaded,
                               EngineClosed, EngineStuck)
from mxnet_tpu.testing.faults import FaultInjector, InjectedCrash

from check_utils import assert_compile_contract

pytestmark = pytest.mark.faults

VOCAB, T = 17, 16


def _init(rng, sym):
    import jax.numpy as jnp
    shapes = {"data": (2, T), "softmax_label": (2, T)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    return {n: jnp.asarray(rng.uniform(-0.3, 0.3, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}


@pytest.fixture(scope="module")
def lm():
    rng = np.random.RandomState(0)
    sym = get_transformer_lm(VOCAB, num_layers=1, embed_dim=16,
                             num_heads=2, impl="dense")
    params = _init(rng, sym)
    return sym, params, Decoder(sym, params, max_len=T)


def _mkdec(lm):
    sym, params, _ = lm
    return Decoder(sym, params, max_len=T)


@pytest.fixture(scope="module")
def feng(lm):
    """The shared plain engine (cache off). Tests flip its mutable
    policy knobs and MUST restore them and drain it to idle; the close
    test (last in the file) consumes it."""
    return InferenceEngine(_mkdec(lm), slots=2, prefill_buckets=(4, 8),
                           prefix_cache_mb=0)


@pytest.fixture(scope="module")
def ceng(lm):
    """The shared prefix-cache + chunked-prefill engine (1-slot pool —
    2 KiB covers one 1-layer f32 slot). Speculation is ON (n-gram):
    the crash/restore and poison scenarios below therefore pin that
    fault recovery composes with draft-and-verify byte-identically."""
    eng = InferenceEngine(_mkdec(lm), slots=2, prefill_buckets=(4, 8),
                          prefix_cache_mb=0.0021, prefill_chunk=3,
                          spec_k=3, draft="ngram")
    assert eng._prefix is not None and eng._prefix.capacity == 1
    return eng


_ORACLE = {}


def _oracle(lm, prompt, n):
    _, _, dec = lm
    prompt = np.asarray(prompt)
    n = min(n, T - len(prompt))
    key = (prompt.tobytes(), len(prompt), n)
    if key not in _ORACLE:
        _ORACLE[key] = np.asarray(
            dec.generate(prompt[None], num_steps=n))[0, len(prompt):]
    return _ORACLE[key]


def _tm():
    return mx.telemetry.snapshot().get("serving", {})


def test_cancel_queued_and_inflight(lm, feng):
    """cancel() retires an IN-FLIGHT request at the round boundary
    (tokens so far stay readable) and fails a QUEUED one without it
    ever occupying a slot; co-resident survivors stay byte-identical;
    slots drain back."""
    rng = np.random.RandomState(1)
    p1, p2, p3 = (rng.randint(0, VOCAB, (4,)) for _ in range(3))
    t0 = _tm().get("cancelled", 0)
    r1 = feng.submit(p1, max_tokens=6)
    r2 = feng.submit(p2, max_tokens=6)
    r3 = feng.submit(p3, max_tokens=6)      # 2 slots -> r3 queued
    feng.step()
    feng.step()
    assert feng.cancel(r3.id)               # still queued
    assert feng.cancel(r1.id)               # decoding in a slot
    feng.serve_forever()
    assert r1.retire_reason == "cancelled" and r1.done
    assert r3.retire_reason == "cancelled" and r3.t_admit is None
    # cancellation is not an error: result() returns the partial tokens
    got = r1.result()
    np.testing.assert_array_equal(got, _oracle(lm, p1, 6)[:len(got)])
    assert r3.result().size == 0
    np.testing.assert_array_equal(r2.result(), _oracle(lm, p2, 6))
    assert not feng.cancel(r1.id)           # already done
    assert not feng.cancel("nope")          # unknown id
    assert feng.idle and len(feng._free) == feng.slots
    assert _tm()["cancelled"] - t0 == 2
    assert feng.stats["cancelled"] == 2


def test_deadlines_queued_and_inflight(lm, feng):
    """ttft_deadline_ms expires a QUEUED request without a slot;
    deadline_ms retires an in-flight one at the round boundary with
    its partial output (an oracle prefix); survivors unaffected."""
    rng = np.random.RandomState(2)
    p1, p2 = rng.randint(0, VOCAB, (4,)), rng.randint(0, VOCAB, (4,))
    t0 = _tm().get("deadline_missed", 0)
    ra = feng.submit(p1, max_tokens=6)
    rb = feng.submit(p2, max_tokens=6, ttft_deadline_ms=0.0)
    rc = feng.submit(p2, max_tokens=6, deadline_ms=0.0)
    feng.serve_forever()
    assert rb.retire_reason == "deadline" and rb.t_admit is None
    assert rc.retire_reason == "deadline"
    np.testing.assert_array_equal(ra.result(), _oracle(lm, p1, 6))

    # in-flight expiry: run a few rounds, then force the deadline past
    rd = feng.submit(p1, max_tokens=6, deadline_ms=1e9)
    feng.step()
    feng.step()
    feng.step()
    rd._deadline = 0.0
    feng.serve_forever()
    assert rd.retire_reason == "deadline"
    got = rd.result()                        # partial, not an error
    np.testing.assert_array_equal(got, _oracle(lm, p1, 6)[:len(got)])
    assert feng.idle and len(feng._free) == feng.slots
    assert _tm()["deadline_missed"] - t0 == 3
    # restore() carries REMAINING deadline budget; an expired one
    # retires on the first round of the restored engine
    re_ = feng.submit(p1, max_tokens=6, deadline_ms=0.0)
    snap = feng.snapshot()
    assert snap["requests"][0]["deadline_ms"] <= 0
    feng.cancel(re_.id)
    feng.serve_forever()


def test_overload_shed_and_shed_oldest(lm, feng):
    """overload='shed' fails the NEW submit fast with a typed
    EngineOverloaded; 'shed_oldest' evicts the oldest QUEUED request
    (admitted work is never shed) and its handle carries the typed
    error; 'block' keeps the PR 3 generic-MXNetError backpressure."""
    rng = np.random.RandomState(3)
    p = rng.randint(0, VOCAB, (4,))
    t0 = _tm().get("shed", 0)
    feng.overload, feng.max_queue = "shed", 0
    try:
        with pytest.raises(EngineOverloaded, match="overloaded"):
            feng.submit(p, max_tokens=6)
        assert feng.stats["shed"] >= 1

        feng.overload, feng.max_queue = "shed_oldest", 1
        g1 = feng.submit(p, max_tokens=6)           # queued
        g2 = feng.submit(p, max_tokens=6)           # evicts g1
        assert g1.done and g1.retire_reason == "shed"
        with pytest.raises(EngineOverloaded, match="shed_oldest"):
            g1.result()
        assert g1.tokens == []                      # never admitted
        feng.step()                                 # g2 admitted
        g3 = feng.submit(p, max_tokens=6)           # queued behind g2
        # an INADMISSIBLE submit is rejected before the overload
        # branch: it must never shed valid queued work
        with pytest.raises(MXNetError, match="integers"):
            feng.submit(np.asarray([1.5, 2.5]), max_tokens=6)
        assert not g3.done
        g4 = feng.submit(p, max_tokens=6)           # evicts g3, not g2
        assert g3.done and g3.retire_reason == "shed"
        assert not g2.done
    finally:
        feng.overload, feng.max_queue = "block", 256
    with pytest.raises(MXNetError, match="queue is full"):
        feng.max_queue = 0
        try:
            feng.submit(p, max_tokens=6)
        finally:
            feng.max_queue = 256
    feng.serve_forever()
    np.testing.assert_array_equal(g2.result(), _oracle(lm, p, 6))
    np.testing.assert_array_equal(g4.result(), _oracle(lm, p, 6))
    assert feng.idle and len(feng._free) == feng.slots
    # one fast-fail shed + two shed_oldest evictions
    assert _tm()["shed"] - t0 == 3


def test_watchdog_trip_and_recovery(lm, feng):
    """A wedged round trips the round_timeout_ms watchdog with a typed
    EngineStuck instead of hanging serve_forever forever; the undrained
    round stays queued, so a recovered device finishes the request
    byte-identically. A transient stall shorter than the timeout never
    trips."""
    rng = np.random.RandomState(4)
    p = rng.randint(0, VOCAB, (4,))
    t0 = _tm().get("watchdog_trips", 0)
    feng.round_timeout_ms = 60.0
    fi = FaultInjector()
    try:
        w = feng.submit(p, max_tokens=6)
        with fi.serving_round_hang(seconds=60):
            with pytest.raises(EngineStuck, match="round_timeout_ms"):
                feng.serve_forever()
        assert not w.done
        # injector uninstalled at context exit = the device recovered:
        # the SAME engine drains the held round and finishes
        feng.serve_forever()
        np.testing.assert_array_equal(w.result(), _oracle(lm, p, 6))
        assert fi.log and fi.log[0][0] == "hang"

        w2 = feng.submit(p, max_tokens=6)
        with fi.serving_round_hang(seconds=0.01):
            feng.serve_forever()             # transient: no trip
        np.testing.assert_array_equal(w2.result(), _oracle(lm, p, 6))
    finally:
        feng.round_timeout_ms = 0.0
    assert feng.idle and len(feng._free) == feng.slots
    assert _tm()["watchdog_trips"] - t0 == 1
    assert feng.stats["watchdog_trips"] == 1


def test_serve_forever_ingest_error_drains_or_sheds(lm, feng):
    """A requests iterable that raises mid-iteration: under 'block'
    every ingested request FINISHES before the exception propagates
    (traceback intact); under a shedding policy the unadmitted backlog
    is shed first. Either way the engine is reusable afterwards."""
    rng = np.random.RandomState(5)
    ps = [rng.randint(0, VOCAB, (4,)) for _ in range(4)]
    hs = []

    def arrivals():
        hs.append(feng.submit(ps[0], max_tokens=6))
        hs.append(feng.submit(ps[1], max_tokens=6))
        yield None                      # engine steps: both admitted
        hs.append(feng.submit(ps[2], max_tokens=6))   # queued (2 slots)
        hs.append(feng.submit(ps[3], max_tokens=6))
        raise ValueError("ingest boom")
        yield None                      # pragma: no cover

    with pytest.raises(ValueError, match="ingest boom"):
        feng.serve_forever(arrivals())
    for h, p in zip(hs, ps):            # ALL finished first (block)
        np.testing.assert_array_equal(h.result(), _oracle(lm, p, 6))
    assert feng.idle

    # shedding policy: the queued backlog is shed, admitted work runs
    hs2 = []

    def arrivals2():
        hs2.append(feng.submit(ps[0], max_tokens=6))
        yield None                      # admitted
        hs2.append(feng.submit(ps[1], max_tokens=6))
        hs2.append(feng.submit(ps[2], max_tokens=6))
        hs2.append(feng.submit(ps[3], max_tokens=6))
        raise ValueError("boom2")
        yield None                      # pragma: no cover

    feng.overload = "shed"
    try:
        with pytest.raises(ValueError, match="boom2"):
            feng.serve_forever(arrivals2())
    finally:
        feng.overload = "block"
    np.testing.assert_array_equal(hs2[0].result(),
                                  _oracle(lm, ps[0], 6))
    # everything not yet admitted at the raise was shed with the typed
    # error (how many WERE admitted depends on staging depth — at least
    # the last one must have still been queued)
    shed = [h for h in hs2[1:] if h.retire_reason == "shed"]
    assert shed
    for h in shed:
        # the victim's error names the ACTUAL cause (the raising
        # stream), not a shed_oldest displacement that never happened
        with pytest.raises(EngineOverloaded, match="stream raised"):
            h.result()
    for h in hs2[1:]:
        if h.retire_reason != "shed":
            assert h.retire_reason == "length"
    assert feng.idle and len(feng._free) == feng.slots
    # a bad item's submit-validation error propagates the same way
    with pytest.raises(MXNetError, match="max_tokens"):
        feng.serve_forever(iter([dict(prompt=[1, 2], max_tokens=0)]))
    assert feng.idle


def test_submit_validation_rejects_bad_scalars(feng):
    """PR satellite: eos_id / temperature / max_tokens validation at
    submit — not as opaque compiled-program misbehavior later."""
    with pytest.raises(MXNetError, match="max_tokens"):
        feng.submit([1, 2], max_tokens=0)
    with pytest.raises(MXNetError, match="max_tokens"):
        feng.submit([1, 2], max_tokens=-3)
    with pytest.raises(MXNetError, match="eos_id"):
        feng.submit([1, 2], max_tokens=2, eos_id=[3, 4])
    with pytest.raises(MXNetError, match="eos_id"):
        feng.submit([1, 2], max_tokens=2, eos_id=2.5)
    with pytest.raises(MXNetError, match="eos_id"):
        feng.submit([1, 2], max_tokens=2, eos_id=-2)
    with pytest.raises(MXNetError, match="temperature"):
        feng.submit([1, 2], max_tokens=2, temperature=float("nan"))
    with pytest.raises(MXNetError, match="temperature"):
        feng.submit([1, 2], max_tokens=2, temperature=float("inf"))
    with pytest.raises(MXNetError, match="temperature"):
        feng.submit([1, 2], max_tokens=2, temperature=-0.5)
    with pytest.raises(MXNetError, match="temperature"):
        feng.submit([1, 2], max_tokens=2, temperature=[0.5, 0.9])
    # constructor knob validation (no engine is built on failure —
    # the Decoder is the module one, nothing compiles here)
    with pytest.raises(MXNetError, match="overload"):
        InferenceEngine(feng._dec, overload="drop")
    with pytest.raises(MXNetError, match="round_timeout_ms"):
        InferenceEngine(feng._dec, round_timeout_ms=-1)
    assert feng.idle


def test_poisoned_request_retires_alone(lm, ceng):
    """A per-request host-side failure (injected h2d fault) retires
    ONLY that request with a typed error; the co-resident request's
    output is byte-identical to a run without the poison, and prefix
    pins + slots drain back (acceptance criterion)."""
    rng = np.random.RandomState(6)
    pa = rng.randint(0, VOCAB, (7,))
    pb = rng.randint(0, VOCAB, (4,))
    t0 = _tm().get("request_errors", 0)
    r_ok = ceng.submit(pa, max_tokens=3)
    ceng.step()
    ceng.step()
    ceng.step()                  # all 3 chunks dispatched; decoding
    assert not ceng._chunking
    fi = FaultInjector()
    with fi.serving_h2d_failures(1):
        r_bad = ceng.submit(pb, max_tokens=6)
        ceng.serve_forever()
    assert r_bad.done and r_bad.retire_reason == "error"
    with pytest.raises(MXNetError, match="poisoned"):
        r_bad.result()
    assert fi.log == [("h2d_fail", r_bad.id)]
    np.testing.assert_array_equal(r_ok.result(), _oracle(lm, pa, 3))
    assert ceng._prefix.pinned == 0
    assert ceng.idle and len(ceng._free) == ceng.slots
    assert _tm()["request_errors"] - t0 == 1


def test_crash_mid_round_restore_byte_identical(lm, ceng):
    """THE tentpole scenario: kill mid-round (tokens dispatched but
    undrained), snapshot() the host scheduler, restore() onto a fresh
    engine — every request resumes and its greedy output is
    byte-identical to an uninterrupted run, for a plain request, a
    prefix-HIT request, a chunked-prefill request, and one whose
    resumed sequence exceeds the largest bucket. Pins and slots drain
    back on both engines; the compile contract holds on the restored
    engine."""
    rng = np.random.RandomState(7)
    base = rng.randint(0, VOCAB, (7,))
    cases = [
        (base, 3),                          # retained + chunked (3s)
        (base[:4].copy(), 6),               # prefix hit off the pool
        (rng.randint(0, VOCAB, (10,)), 3),  # beyond bucket: chunk-only
        (rng.randint(0, VOCAB, (2,)), 5),   # plain short
    ]
    t0 = _tm().get("restores", 0)
    rs = [ceng.submit(p, max_tokens=n) for p, n in cases]
    fi = FaultInjector()
    with fi.serving_crash_mid_round(1):
        with pytest.raises(InjectedCrash):
            for _ in range(20):
                ceng.step()
    assert fi.log[-1][0] == "crash"
    snap = ceng.snapshot()
    assert snap["requests"], "crash landed after everything finished"
    # the snapshot is plain JSON — what a supervisor would persist
    import json
    snap = json.loads(json.dumps(snap))

    eng2, handles = InferenceEngine.restore(snap, _mkdec(lm))
    assert eng2.prefill_chunk == ceng.prefill_chunk
    assert eng2.overload == ceng.overload
    # speculation knobs restore with the geometry (mid-sequence
    # resumes keep drafting — drafter context rebuilds at admission)
    assert eng2.spec_draft == "ngram" and eng2.spec_k == ceng.spec_k
    # fresh auto-drawn seeds never collide with resumed requests'
    assert eng2._auto_seed == ceng._auto_seed
    eng2.serve_forever()
    for (p, n), r in zip(cases, rs):
        h = handles.get(r.id, r)     # finished pre-crash: old handle
        np.testing.assert_array_equal(h.result(), _oracle(lm, p, n))
    assert eng2.stats["restores"] == 1
    assert _tm()["restores"] - t0 == 1
    if eng2._prefix is not None:
        assert eng2._prefix.pinned == 0
    assert len(eng2._free) == eng2.slots
    assert_compile_contract(eng2)
    # the crashed engine still drains clean too (same process: a REAL
    # kill would just drop it) — contract also pinned there
    ceng.serve_forever()
    assert ceng._prefix.pinned == 0
    assert len(ceng._free) == ceng.slots
    assert_compile_contract(ceng)
    eng2.close()


def test_restore_beyond_bucket_prefix_hit_chunking_off(lm, ceng):
    """A restored request whose resumed sequence exceeds the largest
    bucket still takes a prefix hit with chunking OFF: the
    hit-demotion cost proxy must split like dispatch does
    (bucket-sized pieces) instead of rejecting beyond-bucket lengths
    (regression: the lookup raised and the request was retired as
    "error", breaking restore's never-reject contract)."""
    rng = np.random.RandomState(9)
    p_long = rng.randint(0, VOCAB, (6,))
    p_short = p_long[:4].copy()         # shares p_long's first 4
    r_long = ceng.submit(p_long, max_tokens=8)    # admitted first:
    r_short = ceng.submit(p_short, max_tokens=6)  # runs ~3 ahead
    while len(r_long.tokens) < 5:       # resumes beyond bucket 8
        ceng.step()
    snap = ceng.snapshot()
    sz = {r["id"]: len(r["prompt"]) + len(r["tokens"])
          for r in snap["requests"]}
    assert sz.get(r_long.id, 0) > 8     # beyond the largest bucket
    assert 0 < sz.get(r_short.id, 9) <= 8     # retainable
    # a supervisor may reorder the plain-JSON request list; put the
    # short request first so that, with slots=1, it completes (and
    # RETAINS its <= bucket seq) before the beyond-bucket one admits
    # — whose lookup then walks that entry to depth >= 4
    snap["requests"].sort(key=lambda r: len(r["prompt"]))
    eng2, handles = InferenceEngine.restore(
        snap, _mkdec(lm), slots=1, prefill_chunk=0)
    eng2.serve_forever()
    np.testing.assert_array_equal(handles[r_short.id].result(),
                                  _oracle(lm, p_short, 6))
    np.testing.assert_array_equal(handles[r_long.id].result(),
                                  _oracle(lm, p_long, 8))
    assert handles[r_long.id].prefix_hit_tokens >= 4  # hit, not error
    assert eng2._prefix.pinned == 0 and len(eng2._free) == 1
    eng2.close()
    ceng.serve_forever()                # drain the source engine
    assert ceng._prefix.pinned == 0
    assert len(ceng._free) == ceng.slots


def test_snapshot_mid_speculative_verify_round(lm, ceng):
    """Fleet satellite (ISSUE 16): a crash that lands DURING a
    speculative verify round — draft tokens dispatched to the verify
    program but never drained — snapshots to the drained prefix only
    and restores byte-identically: speculation never makes a crash
    lossy beyond the round, and the restored engine keeps drafting."""
    p = np.array([0, 3, 3])            # ngram-friendly repetition
    r = ceng.submit(p, max_tokens=13)
    while len(r.tokens) < 5:           # drafting is established
        ceng.step()
    fi = FaultInjector()
    with fi.serving_crash_mid_round(1):
        with pytest.raises(InjectedCrash):
            for _ in range(10):
                ceng.step()
    # the cut round WAS a verify round: its dispatched-but-undrained
    # entry is still queued at the drain tail
    assert ceng._drain and ceng._drain[-1][0] == "verify"
    snap = ceng.snapshot()
    rec = {x["id"]: x for x in snap["requests"]}[r.id]
    assert 5 <= len(rec["tokens"]) < 13   # undrained tail NOT counted
    eng2, handles = InferenceEngine.restore(snap, _mkdec(lm))
    eng2.serve_forever()
    np.testing.assert_array_equal(handles[r.id].result(),
                                  _oracle(lm, p, 13))
    assert eng2.stats["spec_rounds"] > 0     # the successor drafts too
    assert len(eng2._free) == eng2.slots
    if eng2._prefix is not None:
        assert eng2._prefix.pinned == 0
    assert_compile_contract(eng2)
    eng2.close()
    ceng.serve_forever()               # the crashed engine drains clean
    assert ceng._prefix.pinned == 0
    assert len(ceng._free) == ceng.slots
    assert_compile_contract(ceng)


def test_flight_recorder_reconstructs_failed_request_over_http(lm,
                                                               feng):
    """ISSUE 9 acceptance: a fault-injected serving run leaves a
    ``/flight/<id>`` timeline that reconstructs the failed request's
    FULL lifecycle — submit through ``retire_reason`` — after
    retirement, served by the live exposition server; the co-resident
    request is unaffected and the observability plane compiles
    nothing (the close test's compile-contract pin runs after this)."""
    import json
    import urllib.request

    rng = np.random.RandomState(11)
    p_ok, p_bad = (rng.randint(0, VOCAB, (4,)) for _ in range(2))
    # explicit request ids: /requests and /flight/<id> aggregate over
    # EVERY live engine in the process, and auto ids are per-engine
    # ints — another engine lingering in a gc cycle (test_serving's
    # module fixtures) can retire the same small int and shadow this
    # engine's row in the keyed-table assertions below
    r_ok = feng.submit(p_ok, max_tokens=3, request_id="flight-ok")
    feng.step()                  # r_ok admitted before the fault arms
    fi = FaultInjector()
    with fi.serving_h2d_failures(1):
        r_bad = feng.submit(p_bad, max_tokens=3, deadline_ms=60000.0,
                            request_id="flight-bad")
        feng.serve_forever()
    assert r_bad.done and r_bad.retire_reason == "error"
    assert fi.log == [("h2d_fail", r_bad.id)]
    np.testing.assert_array_equal(r_ok.result(), _oracle(lm, p_ok, 3))

    srv = mx.telemetry.serve(port=0)
    try:
        with urllib.request.urlopen(
                srv.url + "/flight/%s" % r_bad.id, timeout=10) as resp:
            tl = json.load(resp)
        # the reconstruction: every transition in submission order,
        # with relative timestamps, available AFTER retirement
        assert not tl["live"]
        events = [e["event"] for e in tl["events"]]
        assert events[0] == "submit" and events[-1] == "retire"
        assert "staged" in events and "admitted" in events
        ts = [e["t_ms"] for e in tl["events"]]
        assert ts == sorted(ts) and ts[0] == 0.0
        assert tl["meta"]["prompt_len"] == 4
        assert tl["meta"]["deadline_ms"] == 60000.0
        assert tl["meta"]["retire_reason"] == "error"
        retire = tl["events"][-1]
        assert retire["reason"] == "error"
        assert "poisoned" in retire["error"]
        # the healthy survivor's timeline retired normally next to it
        with urllib.request.urlopen(
                srv.url + "/flight/%s" % r_ok.id, timeout=10) as resp:
            tl_ok = json.load(resp)
        assert tl_ok["meta"]["retire_reason"] == "length"
        assert [e["event"] for e in tl_ok["events"]][:4] == \
            ["submit", "staged", "admitted", "prefill_chunk"]
        # /requests shows both retirements; /healthz is 200 ok
        with urllib.request.urlopen(srv.url + "/requests",
                                    timeout=10) as resp:
            rows = json.load(resp)["requests"]
        by_id = {r["id"]: r for r in rows if r["state"] == "retired"}
        assert by_id[r_bad.id]["retire_reason"] == "error"
        assert by_id[r_ok.id]["retire_reason"] == "length"
        # every row names its owning engine and role (the multi-replica
        # /requests disambiguation, ISSUE 19)
        assert by_id[r_ok.id]["engine_id"] == feng.engine_id
        assert by_id[r_ok.id]["role"] == "unified"
        with urllib.request.urlopen(srv.url + "/healthz",
                                    timeout=10) as resp:
            assert json.load(resp)["status"] == "ok"
        # /metrics carries the serving SLO counters AND the engine's
        # introspected program/device gauges (ISSUE 9 acceptance) —
        # and the introspection refresh compiles nothing (the close
        # test's compile-contract pin runs after this scrape)
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=10) as resp:
            text = resp.read().decode()
        assert "mxnet_serving_slo_ttft_attained_total" in text
        assert "mxnet_program_serving_decode_flops" in text
        assert "mxnet_program_serving_prefill_b4_flops" in text
        assert "mxnet_device_live_array_bytes" in text
    finally:
        mx.telemetry.stop_server()
    assert feng.idle and len(feng._free) == feng.slots


def test_close_fails_pending_and_is_idempotent(lm, feng):
    """LAST test on the shared plain engine: close() fails every
    pending request with a typed EngineClosed (drained tokens stay
    readable), stops the stager, is idempotent, and gates submit/step/
    serve_forever; the engine works as a context manager. Also the
    final compile-contract check for everything this file ran on it."""
    rng = np.random.RandomState(8)
    p = rng.randint(0, VOCAB, (4,))
    c1 = feng.submit(p, max_tokens=6)
    feng.step()
    feng.step()
    feng.step()                  # > drain_depth: first token drains
    c2 = feng.submit(p, max_tokens=6)
    # every robustness path this file drove compiled NOTHING new (all
    # prompts in this file share bucket 4 — one program, ever; feng
    # serves spec-off, so verify never compiles)
    assert_compile_contract(feng, verify=0, prefill={4: 1}, copy={})
    feng.close()
    assert c1.done and c1.retire_reason == "closed"
    assert c2.done and c2.retire_reason == "closed"
    assert len(c1.tokens) >= 1               # drained tokens readable
    with pytest.raises(EngineClosed):
        c1.result()
    with pytest.raises(EngineClosed):
        feng.submit(p, max_tokens=2)
    with pytest.raises(EngineClosed):
        feng.step()
    with pytest.raises(EngineClosed):
        feng.serve_forever()
    feng.close()                             # idempotent
    assert len(feng._free) == feng.slots

    # context-manager form on a throwaway engine sharing the compiled
    # decoder... (a NEW engine: close is terminal) — one bucket only
    with InferenceEngine(_mkdec(lm), slots=1, prefill_buckets=(4,),
                         prefix_cache_mb=0) as e2:
        x = e2.submit(p, max_tokens=2)
        e2.serve_forever()
    assert e2._closed and x.retire_reason == "length"
    np.testing.assert_array_equal(x.result(), _oracle(lm, p, 2))
    with pytest.raises(EngineClosed):
        e2.submit(p, max_tokens=2)
