"""Fleet resilience (ISSUE 16): replica drain, live request migration,
and health-driven failover behind the admission router
(mxnet_tpu.serving.fleet.FleetRouter).

The correctness bar is inherited from the single-engine suites: every
request that survives a drain, a mid-round replica death, a heartbeat
partition, or a channel fault finishes with its greedy output
byte-identical to offline ``Decoder.generate`` — migration must not
change a single token — and the per-replica compile-count contract
({decode: 1, verify: <=1, prefill/bucket, copy/bucket}) is re-pinned
on every engine that served: the router is host-side bookkeeping and
compiles NOTHING. Every fault path also drains clean (free slots and
prefix-cache pins back to their pre-test values).

The acceptance drill is the last heavy test: a capture recorded on a
single engine replays through a 2-replica fleet while a rolling
restart drains-and-replaces every original replica mid-replay —
``verify`` passes with zero failed requests.

Runtime discipline (tier-1 budget): the same tiny 1-layer LM as
tests/test_serving_faults.py; ONE module-scoped 2-replica fleet serves
every non-destructive test (knobs flipped and restored per test; the
close test consumes it LAST); destructive scenarios (kill / drain /
blackhole / held-migration / rolling restart) build their own small
fleets because they end with replicas closed."""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.serving import (InferenceEngine, FleetRouter,
                               EngineOverloaded, EngineClosed,
                               load_capture)
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
    cfg = dict(slots=2, prefill_buckets=(4, 8), prefix_cache_mb=0,
               max_queue=8)
    cfg.update(kw)
    return InferenceEngine(_mkdec(lm), **cfg)


@pytest.fixture(scope="module")
def fleet(lm):
    """The shared 2-replica fleet (prefix caches ON — capacity-2 pools
    so affinity has tries to walk and a co-resident prompt's retention
    does not evict the entry under test). Tests flip knobs and MUST
    restore
    them and drain to idle; the close test (last in the file) consumes
    it. Heartbeats effectively off (nothing here tests liveness) and
    a short channel timeout so the slow-replica test is fast."""
    engines = [_mkeng(lm, prefix_cache_mb=0.0042) for _ in range(2)]
    fr = FleetRouter(engines, timeout_ms=40, max_retries=3,
                     backoff_ms=1, heartbeat_ms=1e6)
    yield fr
    fr.close()


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


def _reps(fleet):
    return [fleet.replica(r) for r in fleet.replica_ids()]


def test_routing_least_loaded_and_prefix_affinity(lm, fleet):
    """Placement order: rotation order on a fresh idle fleet,
    least-loaded when replicas differ, and prefix AFFINITY beating
    least-loaded — a prompt whose prefix one replica's trie retains
    lands there even though a peer is idle (the K/V rows are already
    resident; the engine takes the hit at admission)."""
    rng = np.random.RandomState(21)
    e0, e1 = _reps(fleet)
    base = rng.randint(0, VOCAB, (6,))
    h0 = fleet.submit(base, max_tokens=2)
    assert h0.replica_id == e0.engine_id       # both idle: order
    h1 = fleet.submit(rng.randint(0, VOCAB, (3,)), max_tokens=2)
    assert h1.replica_id == e1.engine_id       # least-loaded
    fleet.serve_forever()
    assert fleet.idle and fleet.queued() == 0
    # base (6 prompt + 2 tokens = bucket 8) is now retained in e0's
    # trie; load up e0 so least-loaded alone would pick e1 — affinity
    # must still win for a base-prefix prompt
    a0 = fleet.stats["affinity_hits"]
    busy = fleet.submit(rng.randint(0, VOCAB, (5,)), max_tokens=4)
    assert busy.replica_id == e0.engine_id
    p_hit = np.concatenate([base, rng.randint(0, VOCAB, (1,))])
    hit = fleet.submit(p_hit, max_tokens=2)
    assert hit.replica_id == e0.engine_id      # affinity beat load
    assert fleet.stats["affinity_hits"] > a0
    fleet.serve_forever()
    for h, (p, n) in ((h0, (base, 2)), (hit, (p_hit, 2))):
        np.testing.assert_array_equal(h.result(), _oracle(lm, p, n))
    assert hit.prefix_hit_tokens >= 4          # base's rows were resident
    assert fleet.health()["replicas_live"] == 2
    assert fleet.max_queue == e0.max_queue + e1.max_queue
    for e in (e0, e1):
        assert e._prefix.pinned == 0 and len(e._free) == e.slots
        assert_compile_contract(e)


def test_dedup_retried_submit_admits_exactly_once(lm, fleet):
    """(client_id, seq) is the exactly-once identity: a caller that
    retries a submit after an ambiguous failure gets the ORIGINAL
    handle back — one admission fleet-wide — and the pair is
    both-or-neither validated."""
    rng = np.random.RandomState(22)
    p = rng.randint(0, VOCAB, (4,))
    s0, d0 = fleet.stats["submitted"], fleet.stats["dedup_hits"]
    h = fleet.submit(p, max_tokens=3, client_id="alice", seq=7)
    h2 = fleet.submit(p, max_tokens=3, client_id="alice", seq=7)
    assert h2 is h                             # the SAME handle object
    assert fleet.stats["submitted"] == s0 + 1
    assert fleet.stats["dedup_hits"] == d0 + 1
    with pytest.raises(MXNetError, match="client_id and seq"):
        fleet.submit(p, max_tokens=3, client_id="alice")
    with pytest.raises(MXNetError, match="client_id and seq"):
        fleet.submit(p, max_tokens=3, seq=9)
    fleet.serve_forever()
    np.testing.assert_array_equal(h.result(), _oracle(lm, p, 3))
    h3 = fleet.submit(p, max_tokens=3, client_id="alice", seq=8)
    assert h3 is not h                         # new seq: new request
    fleet.serve_forever()
    assert fleet.idle


def test_draining_reported_and_guards_new_admission(lm, fleet):
    """The engine-level drain gate (fleet satellite): ``draining``
    flows through ``health()`` (and from there /healthz — pinned in
    test_observability.py), NEW submits to the draining engine are
    refused with a typed message, resumed (migration-shaped) submits
    still land — work folds INTO a stopping engine, never out through
    its admission gate — and the router simply routes around it."""
    rng = np.random.RandomState(23)
    e0, e1 = _reps(fleet)
    assert e0.health()["draining"] is False
    e0.draining = True
    try:
        assert e0.health()["draining"] is True
        assert fleet.health()["replicas"][e0.engine_id]["draining"] \
            is True
        p = rng.randint(0, VOCAB, (4,))
        with pytest.raises(MXNetError, match="draining"):
            e0.submit(p, max_tokens=2)
        h = fleet.submit(p, max_tokens=2)      # routed around
        assert h.replica_id == e1.engine_id
        resumed = e0.submit(
            p, max_tokens=2,
            _resume_tokens=(int(_oracle(lm, p, 2)[0]),))
        fleet.serve_forever()
        np.testing.assert_array_equal(h.result(), _oracle(lm, p, 2))
        np.testing.assert_array_equal(resumed.result(),
                                      _oracle(lm, p, 2))
    finally:
        e0.draining = False
    assert fleet.idle


def test_fleet_wide_overload_composes_typed_policies(lm, fleet):
    """A submit is refused only when EVERY healthy replica refuses,
    and the refusal stays typed: any shedding replica makes it
    :class:`EngineOverloaded` (fail fast / back off), all-block keeps
    the generic backpressure error (step() the router to drain)."""
    rng = np.random.RandomState(24)
    p = rng.randint(0, VOCAB, (3,))
    e0, e1 = _reps(fleet)
    saved = [(e.max_queue, e.overload) for e in (e0, e1)]
    try:
        for e in (e0, e1):
            e.max_queue = 0
            e.overload = "shed"
        with pytest.raises(EngineOverloaded, match="fleet-wide"):
            fleet.submit(p, max_tokens=2)
        e1.overload = "block"                  # mixed: typed still wins
        with pytest.raises(EngineOverloaded, match="fleet-wide"):
            fleet.submit(p, max_tokens=2)
        e0.overload = "block"                  # all-block: backpressure
        with pytest.raises(MXNetError, match="queue is full"):
            fleet.submit(p, max_tokens=2)
    finally:
        for e, (mq, ov) in zip((e0, e1), saved):
            e.max_queue, e.overload = mq, ov
    h = fleet.submit(p, max_tokens=2)          # knobs restored: admits
    fleet.serve_forever()
    np.testing.assert_array_equal(h.result(), _oracle(lm, p, 2))


def test_slow_replica_is_retried_not_failed_over(lm, fleet):
    """Dead-vs-slow: a channel stall past ``timeout_ms`` times the op
    out, but the ping probe answers — the router retries (no backoff
    sleep for a live peer) instead of declaring the replica dead; a
    stall UNDER the timeout just lands."""
    rng = np.random.RandomState(25)
    p1, p2 = (rng.randint(0, VOCAB, (4,)) for _ in range(2))
    fi = FaultInjector()
    r0, f0 = fleet.stats["retries"], fleet.stats["failovers"]
    with fi.fleet_slow_replica(None, seconds=0.2):   # 200ms > 40ms
        h1 = fleet.submit(p1, max_tokens=2)
    assert fleet.stats["retries"] == r0 + 1
    assert fleet.stats["failovers"] == f0            # alive: no death
    assert fi.log[-1][0] == "slow"
    assert len(fleet.replica_ids(live_only=True)) == 2
    r1 = fleet.stats["retries"]
    with fi.fleet_slow_replica(None, seconds=0.001):  # under timeout
        h2 = fleet.submit(p2, max_tokens=2)
    assert fleet.stats["retries"] == r1              # no retry needed
    fleet.serve_forever()
    np.testing.assert_array_equal(h1.result(), _oracle(lm, p1, 2))
    np.testing.assert_array_equal(h2.result(), _oracle(lm, p2, 2))


def test_submit_drop_retries_and_lost_reply_adopts(lm, fleet):
    """Channel discipline on the submit path: a dropped submit is
    retried with backoff and lands; and the lost-REPLY leg — the
    admission DID land, only the acknowledgement was lost — adopts the
    already-admitted request by id instead of double-admitting
    (exactly-once at the replica, below the router's dedup table)."""
    rng = np.random.RandomState(26)
    p = rng.randint(0, VOCAB, (4,))
    fi = FaultInjector()
    r0, f0 = fleet.stats["retries"], fleet.stats["failovers"]
    with fi.fleet_submit_failures(None, n=1):
        h = fleet.submit(p, max_tokens=3)
    assert fleet.stats["retries"] == r0 + 1
    assert fleet.stats["failovers"] == f0
    assert fi.log[-1][0] == "submit_fail"
    # lost reply: h is admitted on its replica; a resend over a faulty
    # channel must find it, not resubmit it
    rep = fleet._replicas[h.replica_id]
    n_active = len(rep.engine._active)
    sub0 = rep.engine.stats["submitted"]
    with fi.fleet_submit_failures(rep.id, n=1):
        got = fleet._channel_submit(rep, h)
    assert got is h._cur                       # adopted, not re-admitted
    assert len(rep.engine._active) == n_active
    assert rep.engine.stats["submitted"] == sub0
    fleet.serve_forever()
    np.testing.assert_array_equal(h.result(), _oracle(lm, p, 3))


# -- destructive scenarios (own fleets: they end with closed replicas)


def test_kill_replica_mid_round_fails_over_byte_identical(lm):
    """ISSUE acceptance: a replica killed MID-ROUND (tokens dispatched
    but undrained — the engine's own crash seam) is failed over: its
    in-flight requests migrate and complete on the peer
    byte-identically, a retried submit during the incident admits
    exactly once, and the survivor drains clean (prefix pins + free
    slots back to their pre-test values)."""
    engines = [_mkeng(lm, prefix_cache_mb=0.0021) for _ in range(2)]
    with FleetRouter(engines, heartbeat_ms=1e6, backoff_ms=1) as fleet:
        rng = np.random.RandomState(27)
        cases = [(rng.randint(0, VOCAB, (4,)), 6) for _ in range(4)]
        hs = [fleet.submit(p, max_tokens=n) for p, n in cases]
        for _ in range(3):
            fleet.step()
        victim_id = hs[0].replica_id
        survivor = next(e for e in engines
                        if e.engine_id != victim_id)
        fi = FaultInjector()
        with fi.fleet_kill_replica(victim_id):
            fleet.step()                       # the victim dies here
        assert ("kill_replica", victim_id) in fi.log
        assert fi.log[-1] == ("crash", None)
        assert fleet.stats["failovers"] == 1
        assert fleet.replica_ids(live_only=True) \
            == [survivor.engine_id]
        assert fleet.replica(victim_id)._closed
        # a caller retrying its submit during the incident: exactly one
        # admission (the dedup table returns the original handle)
        p5 = rng.randint(0, VOCAB, (4,))
        hd = fleet.submit(p5, max_tokens=3, client_id="c", seq=0)
        hd2 = fleet.submit(p5, max_tokens=3, client_id="c", seq=0)
        assert hd2 is hd and fleet.stats["dedup_hits"] == 1
        fleet.serve_forever()
        for (p, n), h in zip(cases, hs):
            np.testing.assert_array_equal(h.result(),
                                          _oracle(lm, p, n))
        np.testing.assert_array_equal(hd.result(), _oracle(lm, p5, 3))
        migrated = [h for h in hs if h.migrations]
        assert migrated                        # the victim had work
        assert fleet.stats["migrated_requests"] >= len(migrated)
        assert all(h.replica_id == survivor.engine_id for h in hs)
        health = fleet.health()
        assert health["replicas"][victim_id] \
            == {"closed": True, "dead": True}
        assert health["replicas_live"] == 1 and health["held"] == 0
        assert survivor._prefix.pinned == 0
        assert len(survivor._free) == survivor.slots
        assert_compile_contract(survivor)


def test_drain_migrates_live_and_successor_rejoins(lm):
    """The rolling-restart half: ``drain()`` stops admission, migrates
    the replica's in-flight requests to the peer (byte-identical
    continuations), closes it and returns the archived snapshot;
    ``add_replica`` brings a fresh successor into rotation — with
    duplicate-id and closed-engine submissions rejected."""
    engines = [_mkeng(lm) for _ in range(2)]
    with FleetRouter(engines, heartbeat_ms=1e6) as fleet:
        rng = np.random.RandomState(28)
        cases = [(rng.randint(0, VOCAB, (4,)), 6) for _ in range(4)]
        hs = [fleet.submit(p, max_tokens=n) for p, n in cases]
        for _ in range(2):
            fleet.step()
        victim_id = hs[0].replica_id
        survivor = next(e for e in engines
                        if e.engine_id != victim_id)
        snap = fleet.drain(victim_id)
        assert snap["engine_id"] == victim_id
        assert snap["requests"]                # it had in-flight work
        assert fleet.replica(victim_id)._closed
        assert fleet.stats["drains"] == 1
        assert fleet.stats["migrated_requests"] >= 1
        with pytest.raises(MXNetError, match="not a live replica"):
            fleet.drain(victim_id)             # already gone
        with pytest.raises(MXNetError, match="not a live replica"):
            fleet.drain("never-heard-of-it")
        fleet.serve_forever()
        for (p, n), h in zip(cases, hs):
            np.testing.assert_array_equal(h.result(),
                                          _oracle(lm, p, n))
        # migration never inflates the resume accounting: every token
        # of these requests was generated IN this run
        assert all(h.resumed == 0 for h in hs)
        # a fresh successor rejoins; bad joins are rejected
        succ = _mkeng(lm)
        fleet.add_replica(succ)
        assert len(fleet.replica_ids(live_only=True)) == 2
        with pytest.raises(MXNetError, match="already"):
            fleet.add_replica(succ)
        with pytest.raises(MXNetError, match="closed"):
            fleet.add_replica(fleet.replica(victim_id))
        p_a, p_b = (rng.randint(0, VOCAB, (4,)) for _ in range(2))
        ha = fleet.submit(p_a, max_tokens=3)   # order: survivor
        hb = fleet.submit(p_b, max_tokens=3)   # least-loaded: succ
        assert hb.replica_id == succ.engine_id
        fleet.serve_forever()
        np.testing.assert_array_equal(ha.result(), _oracle(lm, p_a, 3))
        np.testing.assert_array_equal(hb.result(), _oracle(lm, p_b, 3))
        for e in (survivor, succ):
            assert len(e._free) == e.slots
            assert_compile_contract(e, copy={})   # cache off: no copies


def test_heartbeat_blackhole_declares_dead_after_misses(lm):
    """Liveness: ONE unanswered ping is noise (miss counted, replica
    stays); a successful ping resets the count; ``heartbeat_misses``
    CONSECUTIVE unanswered pings declare the replica dead and its
    requests fail over and finish byte-identically on the peer."""
    engines = [_mkeng(lm) for _ in range(2)]
    with FleetRouter(engines, heartbeat_ms=0, heartbeat_misses=2,
                     backoff_ms=1) as fleet:
        rng = np.random.RandomState(29)
        p0, p1 = (rng.randint(0, VOCAB, (4,)) for _ in range(2))
        h0 = fleet.submit(p0, max_tokens=6)
        h1 = fleet.submit(p1, max_tokens=6)
        victim_id = h0.replica_id
        assert victim_id == engines[0].engine_id
        vrep = fleet._replicas[victim_id]
        fi = FaultInjector()
        with fi.fleet_heartbeat_blackhole(victim_id, n=1):
            fleet.step()
        assert vrep.alive and vrep.misses == 1     # noise, not death
        fleet.step()                               # answered: reset
        assert vrep.alive and vrep.misses == 0
        assert fleet.stats["heartbeat_misses"] == 1
        with fi.fleet_heartbeat_blackhole(victim_id, n=2):
            fleet.step()
            assert vrep.alive and vrep.misses == 1
            fleet.step()                           # threshold: dead
        assert not vrep.alive
        assert fleet.stats["failovers"] == 1
        assert fleet.stats["heartbeat_misses"] == 3
        assert fleet.replica_ids(live_only=True) \
            == [engines[1].engine_id]
        fleet.serve_forever()
        np.testing.assert_array_equal(h0.result(), _oracle(lm, p0, 6))
        np.testing.assert_array_equal(h1.result(), _oracle(lm, p1, 6))
        assert h0.migrations == 1
        assert h0.replica_id == engines[1].engine_id
        assert len(engines[1]._free) == engines[1].slots
        assert_compile_contract(engines[1], copy={})


def test_migration_target_dies_requests_held_then_recover(lm):
    """The mid-migration double fault: a drain whose only restore
    target's channel is dead. The target fails over too, the drained
    requests wait in the router's hold queue (tokens so far stay
    readable; result() says re-placement is pending), NEW submits are
    refused — and a fresh ``add_replica`` recovers everything
    byte-identically."""
    engines = [_mkeng(lm) for _ in range(2)]
    with FleetRouter(engines, heartbeat_ms=1e6, max_retries=0,
                     backoff_ms=1) as fleet:
        rng = np.random.RandomState(30)
        p = rng.randint(0, VOCAB, (4,))
        h = fleet.submit(p, max_tokens=6)
        assert h.replica_id == engines[0].engine_id
        for _ in range(2):
            fleet.step()
        fi = FaultInjector()
        with fi.fleet_submit_failures(engines[1].engine_id, n=1):
            snap = fleet.drain(engines[0])
        assert fleet.stats["drains"] == 1
        assert fleet.stats["failovers"] == 1       # the target died too
        assert fleet.replica_ids(live_only=True) == []
        assert fleet.health()["held"] == 1
        assert not h.done and h.replica_id is None
        # the migrated token prefix stays readable while held
        assert h.tokens == list(snap["requests"][0]["tokens"])
        with pytest.raises(MXNetError, match="awaiting re-placement"):
            h.result()
        with pytest.raises(MXNetError, match="no healthy replica"):
            fleet.submit(p, max_tokens=2)
        succ = _mkeng(lm)
        fleet.add_replica(succ)
        fleet.serve_forever()
        assert h.done and h.migrations == 1
        assert h.replica_id == succ.engine_id
        assert fleet.stats["migrated_requests"] == 1
        np.testing.assert_array_equal(h.result(), _oracle(lm, p, 6))
        assert len(succ._free) == succ.slots
        assert_compile_contract(succ, copy={})


def test_rolling_restart_replay_zero_failed(lm, tmp_path):
    """THE acceptance drill: a capture recorded on ONE engine replays
    through a 2-replica fleet while ``rolling_restart`` drains and
    replaces every original replica mid-replay — ``verify`` passes
    with zero failed requests (every output byte-identical to the
    capture), work visibly migrated, and the compile contract holds
    on every replica that served."""
    cap_dir = str(tmp_path)
    src = _mkeng(lm, capture_dir=cap_dir, prefix_cache_mb=0.0021,
                 prefill_chunk=3)
    rng = np.random.RandomState(31)
    base = rng.randint(0, VOCAB, (6,))
    cases = [
        (base, 2),                                  # retained
        (base[:4].copy(), 4),                       # prefix hit
        (rng.randint(0, VOCAB, (3,)), 5),           # miss
        (rng.randint(0, VOCAB, (10,)), 3),          # beyond bucket
        (rng.randint(0, VOCAB, (2,)), 6),           # plain short
        (base.copy(), 2),                           # full dup
    ]
    hs = [src.submit(p, max_tokens=n) for p, n in cases]
    done = src.serve_forever()
    assert len(done) == len(cases)
    path = src.capture.path
    src.close()
    cap = load_capture(path)

    def mkreplica():
        return replay_serving.build_engine(cap, _mkdec(lm))

    fleet = FleetRouter([mkreplica() for _ in range(2)],
                        heartbeat_ms=1e6)
    with fleet:
        originals = _reps(fleet)
        on_round = replay_serving.rolling_restart(fleet, cap,
                                                  mkreplica)
        report = replay_serving.replay(cap, fleet, timing="max",
                                       verify=True, on_round=on_round)
        assert report["mismatches"] == []          # zero failed
        assert report["replayed"] == report["requests"] == len(cases)
        assert report["verified"] == len(cases)
        assert report["verify_skipped"] == 0
        assert fleet.stats["drains"] == 2          # every original
        assert fleet.stats["migrated_requests"] > 0
        assert fleet.stats["failovers"] == 0       # drains, not deaths
        assert all(e._closed for e in originals)
        live = [fleet.replica(r)
                for r in fleet.replica_ids(live_only=True)]
        assert len(live) == 2
        assert not any(e in originals for e in live)
        for e in originals + live:
            if e.stats["steps"]:                   # it served rounds
                assert_compile_contract(e)
            else:                                  # idle spare: zero
                assert e.compile_counts["decode"] == 0
            if e._prefix is not None:
                assert e._prefix.pinned == 0


def test_fleet_close_fails_pending_and_is_idempotent(lm, fleet):
    """LAST (consumes the module fleet): close() shuts every replica
    down, pending work retires with the typed EngineClosed, further
    submits are refused, and a second close is a no-op. The module
    fleet's compile contract held through every test above."""
    rng = np.random.RandomState(32)
    p = rng.randint(0, VOCAB, (4,))
    h = fleet.submit(p, max_tokens=6)
    replicas = _reps(fleet)
    fleet.close()
    assert h.done
    with pytest.raises(EngineClosed):
        h.result()
    fleet.close()                                  # idempotent
    with pytest.raises(EngineClosed):
        fleet.submit(p, max_tokens=1)
    assert fleet.health()["closed"] is True
    assert all(e._closed for e in replicas)
    assert fleet.replica_ids(live_only=True) == []
    for e in replicas:
        assert_compile_contract(e)
    snap = mx.telemetry.snapshot()
    assert snap.get("fleet", {}).get("replicas_live") == 0
