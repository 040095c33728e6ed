"""Disaggregated prefill/decode serving (ISSUE 18): role-specialized
engines with KV handoff through the fleet router — and the fleet
tracing plane stitched over it (ISSUE 19): every journey here that
crosses a role boundary, a retry, or a failover must reconstruct as
ONE ordered cross-replica timeline whose SLO decomposition sums to
the measured end-to-end time.

The correctness bar: a request prefilled on a ``role="prefill"``
engine, packaged (live KV rows + sampling identity + first emitted
token), shipped through ``FleetRouter``, and admitted on a
``role="decode"`` engine finishes with its greedy output
byte-identical to offline ``Decoder.generate`` — the handoff moves
state, it must not move a single token. Per-role compile contracts
ride along via ``assert_compile_contract``: a prefill specialist
compiles NO decode/verify program, a decode specialist compiles NO
prefill program, and both report the ``handoff`` family. Every
scenario — delivered, retried-then-deduped, and
failed-then-unified-fallback — drains clean: prefix-cache pins and
free slots return to their pre-test values on BOTH sides.

Runtime discipline (tier-1 budget): the same tiny 1-layer LM as
tests/test_fleet.py, module-scoped; every fleet here is built small
and closed by its test (role topologies and fault scripts differ per
test, so no shared fleet)."""
import os
import sys

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.serving import (InferenceEngine, FleetRouter,
                               load_capture, pack_rows, unpack_rows)
from mxnet_tpu.testing.faults import FaultInjector

from check_utils import assert_compile_contract

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tools import replay_serving  # noqa: E402

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


def _mkeng(lm, **kw):
    cfg = dict(slots=2, prefill_buckets=(4, 8), prefix_cache_mb=0.0042,
               max_queue=8)
    cfg.update(kw)
    return InferenceEngine(_mkdec(lm), **cfg)


def _mkfleet(lm, roles, eng_kw=None, **kw):
    engines = [_mkeng(lm, role=r, **(eng_kw or {})) for r in roles]
    cfg = dict(timeout_ms=40, max_retries=3, backoff_ms=1,
               heartbeat_ms=1e6)
    cfg.update(kw)
    return FleetRouter(engines, **cfg), engines


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


def _assert_clean(*engines):
    """Pins and free slots back to pre-test values — on BOTH sides of
    every handoff (the pin-accounting bar from PR 7 onward)."""
    for e in engines:
        if e._prefix is not None:
            assert e._prefix.pinned == 0, \
                "%s leaked %d pins" % (e.engine_id, e._prefix.pinned)
        assert len(e._free) == e.slots, \
            "%s leaked slots: %d free of %d" \
            % (e.engine_id, len(e._free), e.slots)


def _assert_role_contracts(prefills, decodes):
    """The per-role compile pins: specialists never compile the other
    phase's programs (acceptance: decode replicas never compile
    prefill)."""
    for e in prefills:
        assert_compile_contract(e, decode=0, verify=0)
    for e in decodes:
        assert_compile_contract(e, prefill={}, copy="once")


def test_role_knob_validation(lm, monkeypatch):
    """The role knob's edges: unknown roles refused at construction,
    the env default honored, narrowing a live specialist refused
    (only widening to unified — the failover promotion), a decode
    specialist refuses ALL submits (fresh and resumed: either would
    compile a prefill program), a prefill specialist refuses
    admit_handoff."""
    with pytest.raises(MXNetError, match="role"):
        _mkeng(lm, role="draining")
    monkeypatch.setenv("MXNET_SERVING_ROLE", "decode")
    e = _mkeng(lm)
    assert e.role == "decode"
    e.close()
    monkeypatch.delenv("MXNET_SERVING_ROLE")
    with pytest.raises(MXNetError, match="handoff_dtype"):
        _mkeng(lm, role="prefill", handoff_dtype="fp8")

    ep = _mkeng(lm, role="prefill")
    ed = _mkeng(lm, role="decode")
    try:
        with pytest.raises(MXNetError, match="widen"):
            ep.set_role("decode")
        with pytest.raises(MXNetError, match="role='decode'"):
            ed.submit(np.arange(3), max_tokens=2)
        with pytest.raises(MXNetError, match="role='prefill'"):
            ep.admit_handoff({"id": "nope"})
        ep.set_role("unified")          # widening is the promotion
        assert ep.role == "unified"
        ep.set_role("unified")          # idempotent
    finally:
        ep.close()
        ed.close()


def test_pack_rows_int8_roundtrip():
    """The transfer codec alone: int8 packing quantizes float KV rows
    per-row symmetric (integer leaves ship verbatim), lands near a
    quarter of the f32 wire bytes, and unpacks back within
    quantization tolerance; unknown dtypes refused."""
    rng = np.random.RandomState(7)
    rows = {"k": rng.randn(4, 64).astype(np.float32),
            "v": rng.randn(4, 64).astype(np.float32),
            "pos": np.arange(4, dtype=np.int32)}
    native, n_native = pack_rows(rows, "native")
    back = unpack_rows(native, rows)
    np.testing.assert_array_equal(back["k"], rows["k"])
    np.testing.assert_array_equal(back["pos"], rows["pos"])

    q, n_q = pack_rows(rows, "int8")
    float_bytes = rows["k"].nbytes + rows["v"].nbytes
    # int8 payload + one f32 scale per row vs f32 rows: ~0.25 + eps
    assert n_q - rows["pos"].nbytes < 0.3 * float_bytes, (n_q, n_native)
    deq = unpack_rows(q, rows)
    np.testing.assert_array_equal(deq["pos"], rows["pos"])
    for name in ("k", "v"):
        tol = np.abs(rows[name]).max(axis=-1, keepdims=True) / 127.0
        assert np.all(np.abs(deq[name] - rows[name]) <= tol + 1e-6)
    # zero rows survive (the scale guard: amax 0 -> scale 1, not 0/0)
    z, _ = pack_rows({"k": np.zeros((2, 3), np.float32)}, "int8")
    np.testing.assert_array_equal(
        unpack_rows(z, {"k": np.zeros((2, 3), np.float32)})["k"], 0.0)
    with pytest.raises(MXNetError, match="int8"):
        pack_rows(rows, "fp4")


def test_engine_level_handoff_byte_identity(lm):
    """The handoff machinery WITHOUT the router: a prefill specialist
    exports a package (prompt + sampling identity + first token + live
    KV rows), a decode specialist admits it, and the continued stream
    is byte-identical to offline generate. Double-resolving the
    package is refused loudly; both sides drain clean and hold their
    role contracts."""
    ep = _mkeng(lm, role="prefill")
    ed = _mkeng(lm, role="decode")
    rng = np.random.RandomState(11)
    p = rng.randint(0, VOCAB, (6,))
    try:
        ep.submit(p, max_tokens=5)
        pkgs = []
        for _ in range(40):
            ep.step()
            pkgs = ep.take_handoffs()
            if pkgs:
                break
        assert len(pkgs) == 1
        pkg = pkgs[0]
        payload = pkg.payload()
        assert payload["prefill_len"] == len(p)
        assert len(payload["tokens"]) == 1       # the first token
        assert payload["rows"] is not None
        req = ed.admit_handoff(payload)
        pkg.resolve()                            # frees the source slot
        with pytest.raises(MXNetError, match="twice"):
            pkg.resolve()
        ed.serve_forever()
        assert req.done and req.retire_reason == "length"
        np.testing.assert_array_equal(np.asarray(req.result()),
                                      _oracle(lm, p, 5))
        _assert_clean(ep, ed)
        _assert_role_contracts([ep], [ed])
        assert ep.stats["handoffs_out"] == 1
        assert ed.stats["handoffs_in"] == 1
    finally:
        ep.close()
        ed.close()


def test_fleet_1p1d_and_2p2d_byte_identity(lm):
    """THE tentpole drill: the same mixed prompt set through a 1P+1D
    fleet and a 2P+2D fleet retires byte-identical to offline
    generate — role-aware placement sends every prompt to a prefill
    replica, every package to a decode replica, and the router's
    bookkeeping compiles nothing. Pins/slots clean on all replicas,
    per-role contracts pinned (delivered-path pin accounting)."""
    rng = np.random.RandomState(5)
    cases = [(rng.randint(0, VOCAB, (n,)), m)
             for n, m in ((4, 3), (6, 4), (3, 2), (7, 5))]
    for roles in (("prefill", "decode"),
                  ("prefill", "prefill", "decode", "decode")):
        fleet, engines = _mkfleet(lm, roles)
        with fleet:
            hs = [fleet.submit(p, max_tokens=m) for p, m in cases]
            fleet.serve_forever()
            for h, (p, m) in zip(hs, cases):
                np.testing.assert_array_equal(np.asarray(h.result()),
                                              _oracle(lm, p, m))
            assert fleet.stats["handoffs"] == len(cases)
            assert fleet.stats["handoff_bytes"] > 0
            assert fleet.stats["failovers"] == 0
            _assert_clean(*engines)
            prefills = [e for e in engines if e.role == "prefill"]
            decodes = [e for e in engines if e.role == "decode"]
            assert sum(e.stats["handoffs_out"] for e in prefills) \
                == len(cases)
            assert sum(e.stats["handoffs_in"] for e in decodes) \
                == len(cases)
            _assert_role_contracts(prefills, decodes)


def test_handoff_retry_dedup_admits_once(lm):
    """Transport discipline on the handoff channel: a dropped delivery
    retries the SAME package within the channel budget and the decode
    side admits it exactly once (dedup by package id — the adoption
    path when the admit landed but the ack died on the wire). Output
    stays byte-identical; retried-then-deduped pin accounting."""
    fleet, (ep, ed) = _mkfleet(lm, ("prefill", "decode"))
    rng = np.random.RandomState(13)
    p = rng.randint(0, VOCAB, (5,))
    fi = FaultInjector()
    with fleet:
        with fi.fleet_handoff_failures(ed.engine_id, n=1):
            h = fleet.submit(p, max_tokens=4)
            fleet.serve_forever()
        assert ("handoff_fail", ed.engine_id) in fi.log
        np.testing.assert_array_equal(np.asarray(h.result()),
                                      _oracle(lm, p, 4))
        assert ed.stats["handoffs_in"] == 1      # exactly once
        assert fleet.stats["handoffs"] == 1
        assert fleet.stats["failovers"] == 0     # retry, not death
        _assert_clean(ep, ed)
        _assert_role_contracts([ep], [ed])


def test_decode_death_falls_back_to_unified(lm):
    """Failure of the decode side mid-handoff: the channel budget
    exhausts, the decode replica fails over, and with NO decode-capable
    replica left the router falls back to unified serving on the
    survivor — the prefill specialist widens to ``role="unified"``,
    the held request re-places there, and the output is STILL
    byte-identical. Failed-and-unified-fallback pin accounting: the
    abandoned package's source slot frees, the survivor drains
    clean."""
    fleet, (ep, ed) = _mkfleet(lm, ("prefill", "decode"),
                               max_retries=0)
    rng = np.random.RandomState(17)
    p = rng.randint(0, VOCAB, (6,))
    fi = FaultInjector()
    with fleet:
        with fi.fleet_handoff_failures(ed.engine_id, n=2):
            h = fleet.submit(p, max_tokens=5)
            fleet.serve_forever()
        np.testing.assert_array_equal(np.asarray(h.result()),
                                      _oracle(lm, p, 5))
        assert fleet.stats["failovers"] == 1
        assert fleet.stats["role_promotions"] == 1
        assert ep.role == "unified"              # the survivor widened
        assert fleet.replica_ids(live_only=True) == [ep.engine_id]
        _assert_clean(ep)
        # the promoted survivor decodes now; its prefill family stays
        assert ep.compile_counts["decode"] == 1
        assert_compile_contract(ep)
    ed.close()


def test_pool_hit_skips_transfer(lm):
    """Prefix affinity across the handoff: the first delivery parks
    the prefill in the DECODE replica's pool (decode-side retention),
    so a repeat of the same prompt ships identity only — the router's
    affinity probe sees full coverage, ``handoff_pool_hits`` ticks,
    and zero new bytes move (the target copies rows out of its own
    pool). Byte-identity and pin accounting hold on the rows-less
    path too."""
    fleet, (ep, ed) = _mkfleet(lm, ("prefill", "decode"))
    rng = np.random.RandomState(19)
    p = rng.randint(0, VOCAB, (6,))
    with fleet:
        h1 = fleet.submit(p, max_tokens=4)
        fleet.serve_forever()
        bytes_after_first = fleet.stats["handoff_bytes"]
        assert fleet.stats["handoffs"] == 1
        assert fleet.stats["handoff_pool_hits"] == 0
        assert bytes_after_first > 0
        h2 = fleet.submit(p.copy(), max_tokens=4)
        fleet.serve_forever()
        assert fleet.stats["handoffs"] == 2
        assert fleet.stats["handoff_pool_hits"] == 1
        assert fleet.stats["handoff_bytes"] == bytes_after_first
        want = _oracle(lm, p, 4)
        np.testing.assert_array_equal(np.asarray(h1.result()), want)
        np.testing.assert_array_equal(np.asarray(h2.result()), want)
        assert ed.stats["prefix_hits"] >= 1      # rows-less admission
        _assert_clean(ep, ed)
        _assert_role_contracts([ep], [ed])


def test_int8_handoff_halves_wire_bytes(lm):
    """The ``handoff_dtype="int8"`` knob on the exporting engine:
    the same request ships ~a quarter of the f32 wire bytes (int8
    payload + per-row scales vs f32 rows) and — at this toy scale —
    still decodes byte-identically. The quantization is transfer-only:
    the decode replica's cache stays in compute dtype."""
    p = np.random.RandomState(23).randint(0, VOCAB, (6,))
    sizes = {}
    for dtype in ("native", "int8"):
        fleet, engines = _mkfleet(lm, ("prefill", "decode"),
                                  eng_kw={"handoff_dtype": dtype})
        with fleet:
            h = fleet.submit(p, max_tokens=4)
            fleet.serve_forever()
            np.testing.assert_array_equal(np.asarray(h.result()),
                                          _oracle(lm, p, 4))
            sizes[dtype] = fleet.stats["handoff_bytes"]
            _assert_clean(*engines)
    assert 0 < sizes["int8"] < 0.35 * sizes["native"], sizes


def test_replay_roles_1p1d_verify_clean(lm, tmp_path):
    """The acceptance drill: a capture recorded on ONE unified engine
    replays ``--verify``-clean through a 1P+1D fleet — every output
    byte-identical to the capture even though every request now
    crosses a role boundary mid-flight (the ``--roles PxD`` topology
    in tools/replay_serving.py) — then AGAIN with a per-role rolling
    restart draining and replacing both specialists mid-replay (each
    replacement rebuilt with its predecessor's role)."""
    src = _mkeng(lm, capture_dir=str(tmp_path), role="unified")
    rng = np.random.RandomState(29)
    cases = [(rng.randint(0, VOCAB, (n,)), m)
             for n, m in ((4, 3), (6, 4), (3, 2), (7, 2))]
    for prompt, m in cases:
        src.submit(prompt, max_tokens=m)
    src.serve_forever()
    path = src.capture.path
    src.close()
    cap = load_capture(path)

    def mkreplica(role="unified"):
        return replay_serving.build_engine(cap, _mkdec(lm), role=role)

    fleet = FleetRouter([mkreplica(role="prefill"),
                         mkreplica(role="decode")], heartbeat_ms=1e6)
    with fleet:
        report = replay_serving.replay(cap, fleet, timing="max",
                                       verify=True)
        assert report["mismatches"] == []        # zero failed
        assert report["verified"] == len(cases)
        assert report["verify_skipped"] == 0
        assert fleet.stats["handoffs"] == len(cases)
        engines = [fleet.replica(r) for r in fleet.replica_ids()]
        _assert_clean(*engines)
        _assert_role_contracts([engines[0]], [engines[1]])

    requested = []

    def mkreplica_logged(role="unified"):
        requested.append(role)
        return mkreplica(role=role)

    fleet = FleetRouter([mkreplica(role="prefill"),
                         mkreplica(role="decode")], heartbeat_ms=1e6)
    with fleet:
        on_round = replay_serving.rolling_restart(fleet, cap,
                                                  mkreplica_logged,
                                                  per_role=True)
        report = replay_serving.replay(cap, fleet, timing="max",
                                       verify=True,
                                       on_round=on_round)
        assert report["mismatches"] == []        # zero failed
        assert report["verified"] == len(cases)
        assert fleet.stats["drains"] == 2        # both specialists
        # each replacement was built with its predecessor's ORIGINAL
        # role (snapshotted before the empty-phase promotions mutate
        # the survivors — draining half of a 1P+1D fleet widens the
        # other half to unified, twice)
        assert requested == ["prefill", "decode"]
        assert fleet.stats["role_promotions"] == 2
        live = [fleet.replica(r)
                for r in fleet.replica_ids(live_only=True)]
        assert "decode" in [e.role for e in live]
        _assert_clean(*live)


def test_capture_role_round_trip(lm, tmp_path):
    """Satellite S3 (ISSUE 19): the capture header names the role it
    was recorded on, and the fleet identity rides every record. A
    1P+1D fleet with capture armed yields a DECODE-specialist capture
    whose submits are all handoff admissions (resume_tokens present,
    hop 2, trace_id = the fleet request id); ``role_report`` flags a
    specialist capture replayed without ``--roles`` (and stays silent
    when the topology is reproduced); and the specialist capture
    replays ``--verify``-clean on ONE unified engine — byte-identical
    by the disaggregation contract, topology change noted, not
    hidden."""
    fleet, (ep, ed) = _mkfleet(lm, ("prefill", "decode"),
                               eng_kw={"capture_dir": str(tmp_path)})
    rng = np.random.RandomState(37)
    p = rng.randint(0, VOCAB, (5,))
    with fleet:
        h = fleet.submit(p, max_tokens=4)
        fleet.serve_forever()
        want = _oracle(lm, p, 4)
        np.testing.assert_array_equal(np.asarray(h.result()), want)
        dpath = ed.capture.path
        trace_id = h.id
        _assert_clean(ep, ed)
    # the decode side's capture: role in the header, fleet identity
    # in every submit, every submit a handoff admission
    cap = load_capture(dpath)
    assert cap["engine"]["role"] == "decode"
    subs = cap["submits"]
    assert len(subs) == 1
    assert subs[0]["trace_id"] == trace_id
    assert subs[0]["hop"] == 2
    assert subs[0]["resume_tokens"]          # admitted mid-journey
    # role_report: specialist capture without a role topology → note;
    # with the captured topology reproduced → silent
    role, note = replay_serving.role_report(cap)
    assert role == "decode"
    assert note is not None and "decode" in note and "--roles" in note
    role, note = replay_serving.role_report(cap, (1, 1))
    assert role == "decode" and note is None
    # the round trip: replay the specialist capture on one UNIFIED
    # engine — byte-identical even though no role boundary is crossed
    uni = replay_serving.build_engine(cap, _mkdec(lm), role="unified")
    report = replay_serving.replay(cap, uni, timing="max", verify=True)
    assert report["mismatches"] == []
    assert report["verified"] == 1
    assert report["verify_skipped"] == 0
    # the captured fleet identity survived the plain-engine replay
    rows = uni.request_table()
    assert [r["id"] for r in rows] == [trace_id]
    _assert_clean(uni)
    uni.close()


def _slo_sums(slo):
    """The decomposition's arithmetic pins: the five components sum to
    the measured end-to-end wall time, and the first two are EXACTLY
    the fleet TTFT window (tolerance covers per-component 0.001 ms
    rounding only — the sums hold by construction, not by luck)."""
    comps = ("router_queue", "prefill", "handoff_wait",
             "decode_admission", "decode")
    total = sum(slo[c] for c in comps)
    assert abs(total - slo["e2e_ms"]) <= 0.01, slo
    assert abs(slo["router_queue"] + slo["prefill"]
               - slo["ttft_ms"]) <= 0.01, slo
    assert all(slo[c] >= 0.0 for c in comps), slo


def test_fleet_trace_stitched_timeline_under_faults(lm):
    """THE ISSUE 19 acceptance drill: one request through a 1P+1D
    fleet with a forced handoff retry AND a decode-replica death
    mid-decode reconstructs — over HTTP, ``GET /fleet/flight/<id>`` —
    as a single ordered timeline: submit, role placement, the prefill
    hop's own events (first_token, handoff_export), the wire retry,
    the decode-side admission, the failover, the migration onto the
    promoted survivor, and the terminal retire, timestamps ascending
    on one clock. The TTFT decomposition in the journey's meta sums
    to the measured TTFT and end-to-end time; output stays
    byte-identical through all of it."""
    import json
    import urllib.request

    fleet, (ep, ed) = _mkfleet(lm, ("prefill", "decode"),
                               slo_ttft_ms=1e5, slo_cadence_ms=1e5)
    rng = np.random.RandomState(31)
    p = rng.randint(0, VOCAB, (6,))
    fi = FaultInjector()
    with fleet:
        with fi.fleet_handoff_failures(ed.engine_id, n=1):
            h = fleet.submit(p, max_tokens=6)
            for _ in range(200):
                fleet.step()
                if fleet.stats["handoffs"] == 1:
                    break
        assert fleet.stats["handoffs"] == 1      # retried, then landed
        assert not h.done                        # decode still running
        with fi.fleet_kill_replica(ed.engine_id):
            fleet.step()                         # decode dies mid-round
        fleet.serve_forever()
        assert fleet.stats["failovers"] == 1
        assert fleet.stats["role_promotions"] == 1
        assert ep.role == "unified"
        np.testing.assert_array_equal(np.asarray(h.result()),
                                      _oracle(lm, p, 6))

        tl = fleet.flight.timeline(h.id)
        assert tl is not None and not tl["live"]
        assert tl["dropped_events"] == 0
        assert tl["hops"] == [ep.engine_id, ed.engine_id, ep.engine_id]
        ts = [e["t_ms"] for e in tl["events"]]
        assert ts == sorted(ts) and ts[0] == 0.0   # one monotonic clock
        names = [e["event"] for e in tl["events"]]
        assert names[0] == "submit" and names[-1] == "retire"
        for must in ("placed", "first_token", "handoff_export",
                     "in_transit", "retried", "admitted",
                     "handoff_import", "failover", "migrated"):
            assert must in names, (must, names)
        # the journey's internal order — scope-qualified, because the
        # ENGINE hops also record an "admitted"/"submit" of their own
        # (slot admission vs the router's wire admission): placement
        # before the export, the wire retry before the decode
        # admission, the failover after it, the migration last
        def _first(name, scope=None):
            for i, e in enumerate(tl["events"]):
                if e["event"] == name and \
                        (scope is None or e["scope"] == scope):
                    return i, e
            raise AssertionError((name, scope, names))

        keyed = [("placed", "router"), ("handoff_export", None),
                 ("retried", "router"), ("admitted", "router"),
                 ("failover", "router"), ("migrated", "router")]
        order = [_first(n, s)[0] for n, s in keyed]
        assert order == sorted(order), \
            list(zip(order, (n for n, _ in keyed)))
        by = {n: _first(n, s)[1] for n, s in keyed}
        assert by["placed"]["reason"] == "role"
        assert by["placed"]["replica"] == ep.engine_id
        assert by["retried"]["op"] == "handoff"
        assert by["admitted"]["replica"] == ed.engine_id
        assert by["admitted"]["bytes"] > 0
        assert by["admitted"]["pool_hit"] is False
        assert by["failover"]["from"] == ed.engine_id
        assert by["migrated"]["to"] == ep.engine_id
        # per-engine events carry the trace context: same trace id,
        # hop 1 on the prefill side, hop 2 on the decode side
        eng_submits = [e for e in tl["events"]
                      if e["event"] == "submit" and e["scope"] != "router"]
        assert {e["trace"] for e in eng_submits} == {h.id}
        assert {(e["scope"], e["hop"]) for e in eng_submits} >= \
            {(ep.engine_id, 1), (ed.engine_id, 2)}

        # the SLO decomposition sums — and matches the handle's own
        # measured TTFT
        slo = tl["meta"]["slo"]
        _slo_sums(slo)
        assert abs(slo["ttft_ms"]
                   - (h.t_first - h.t_submit) * 1e3) <= 0.01
        assert "cadence_ms" in slo

        # the same journey over the wire: /fleet lists it, the
        # per-trace endpoint serves the identical stitched timeline,
        # and ?chrome=1 exports it for Perfetto
        import mxnet_tpu as mx
        srv = mx.telemetry.serve(port=0)
        try:
            with urllib.request.urlopen(srv.url + "/fleet",
                                        timeout=10) as resp:
                fleets = json.load(resp)["fleets"]
            assert len(fleets) == 1
            ft = fleets[0]
            assert h.id in ft["flight"]["retired"]
            assert ft["slo"]["ttft_ms"] == 1e5
            assert set(ft["slo"]["ttft_burn"]) == {"1m", "5m", "1h"}
            with urllib.request.urlopen(
                    srv.url + "/fleet/flight/%s" % h.id,
                    timeout=10) as resp:
                wire = json.load(resp)
            assert wire["events"] == json.loads(
                json.dumps(tl["events"]))
            assert wire["meta"]["slo"] == json.loads(
                json.dumps(slo))
            with urllib.request.urlopen(
                    srv.url + "/fleet/flight/%s?chrome=1" % h.id,
                    timeout=10) as resp:
                chrome = json.load(resp)
            assert chrome["otherData"]["trace_id"] == h.id
            spans = [e for e in chrome["traceEvents"]
                     if e.get("cat") == "fleet.slo"]
            assert [s["name"] for s in spans] == [
                "router_queue", "prefill", "handoff_wait",
                "decode_admission", "decode"]
        finally:
            mx.telemetry.stop_server()
        _assert_clean(ep)
        assert_compile_contract(ep)
    ed.close()


def test_fleet_trace_continuity_unified_fallback(lm):
    """Trace continuity through the OTHER fault shape (the
    test_decode_death_falls_back_to_unified script): channel budget
    exhausts with NO retry budget while the package is in transit, the
    decode replica is declared dead, and the journey continues on the
    promoted unified survivor — still ONE stitched timeline,
    ascending, with the mid-transit failover visible (reason: target
    died in transit) and the re-delivery landing as a hop-2 admission
    on the survivor, and the decomposition still summing."""
    fleet, (ep, ed) = _mkfleet(lm, ("prefill", "decode"),
                               max_retries=0)
    rng = np.random.RandomState(37)
    p = rng.randint(0, VOCAB, (6,))
    fi = FaultInjector()
    with fleet:
        with fi.fleet_handoff_failures(ed.engine_id, n=2):
            h = fleet.submit(p, max_tokens=5)
            fleet.serve_forever()
        np.testing.assert_array_equal(np.asarray(h.result()),
                                      _oracle(lm, p, 5))
        assert fleet.stats["failovers"] == 1
        assert fleet.stats["role_promotions"] == 1
        tl = fleet.flight.timeline(h.id)
        assert tl is not None and not tl["live"]
        ts = [e["t_ms"] for e in tl["events"]]
        assert ts == sorted(ts)
        names = [e["event"] for e in tl["events"]]
        assert names[0] == "submit" and names[-1] == "retire"
        assert "retried" not in names            # no retry budget
        routed = [e for e in tl["events"] if e["scope"] == "router"]
        fo = [e for e in routed if e["event"] == "failover"]
        assert len(fo) == 1
        assert fo[0]["reason"] == "target died in transit"
        assert fo[0]["from"] == ed.engine_id
        adm = [e for e in routed if e["event"] == "admitted"]
        assert len(adm) == 1
        # the re-delivery landed on the promoted survivor out of its
        # own pool, as the journey's hop 2
        assert adm[0]["replica"] == ep.engine_id
        assert adm[0]["pool_hit"] is True and adm[0]["hop"] == 2
        assert routed.index(fo[0]) < routed.index(adm[0])
        assert tl["hops"] == [ep.engine_id]      # consecutive collapse
        assert h.migrations == 0                 # re-delivered, not
        _slo_sums(tl["meta"]["slo"])             # re-prefilled
    ed.close()
