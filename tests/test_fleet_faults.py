"""Randomized fleet chaos sweep (``make chaos``): a seeded random
schedule of replica kills, heartbeat partitions, channel drops/stalls,
and live drains against a 3-replica fleet under submit pressure.

The bar is the deterministic suite's (tests/test_fleet.py), held under
COMPOSED faults in random order: every admitted request finishes with
its greedy output byte-identical to offline ``Decoder.generate``, no
request is lost (zero failed), live replicas drain clean, and every
replica that served rounds keeps the compile-count contract. Marked
slow: the sweep builds replacement engines as the schedule destroys
them, which is compile-heavy for tier-1."""
import contextlib

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.serving import InferenceEngine, FleetRouter
from mxnet_tpu.testing.faults import FaultInjector

from check_utils import assert_compile_contract

pytestmark = [pytest.mark.faults, pytest.mark.slow]

VOCAB, T = 17, 16


@pytest.fixture(scope="module")
def lm():
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    sym = get_transformer_lm(VOCAB, num_layers=1, embed_dim=16,
                             num_heads=2, impl="dense")
    shapes = {"data": (2, T), "softmax_label": (2, T)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.3, 0.3, s)
                             .astype(np.float32))
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    return sym, params, Decoder(sym, params, max_len=T)


def _mkeng(lm):
    sym, params, _ = lm
    dec = Decoder(sym, params, max_len=T)
    return InferenceEngine(dec, slots=2, prefill_buckets=(4, 8),
                           prefix_cache_mb=0, max_queue=8)


def test_chaos_sweep_random_faults_zero_failed(lm):
    _, _, dec = lm
    rng = np.random.RandomState(123)
    fi = FaultInjector(seed=5)
    fleet = FleetRouter([_mkeng(lm) for _ in range(3)],
                        timeout_ms=40, max_retries=3, backoff_ms=1,
                        heartbeat_ms=0, heartbeat_misses=2)
    cases, handles = [], []
    with fleet:
        for _ in range(30):
            live = fleet.replica_ids(live_only=True)
            if len(live) < 2:          # the schedule destroyed too
                fleet.add_replica(_mkeng(lm))   # much: reinforce
                live = fleet.replica_ids(live_only=True)
            act = rng.rand()
            if act < 0.35 and len(handles) < 14:
                p = rng.randint(0, VOCAB, (int(rng.randint(2, 7)),))
                n = int(rng.randint(2, 6))
                f = rng.rand()
                ctx = contextlib.nullcontext()
                if f < 0.2:            # channel drops the submit
                    ctx = fi.fleet_submit_failures(None, n=1)
                elif f < 0.4:          # channel stalls past timeout
                    ctx = fi.fleet_slow_replica(None, seconds=0.2)
                try:
                    with ctx:
                        h = fleet.submit(p, max_tokens=n)
                except MXNetError:
                    continue           # fleet mid-incident: no target
                cases.append((p, n))
                handles.append(h)
            elif act < 0.45 and len(live) > 1:
                victim = live[int(rng.randint(len(live)))]
                with fi.fleet_kill_replica(victim):
                    fleet.step()
            elif act < 0.55 and len(live) > 1:
                victim = live[int(rng.randint(len(live)))]
                with fi.fleet_heartbeat_blackhole(victim, n=2):
                    fleet.step()
                    fleet.step()
            elif act < 0.65 and len(live) > 1:
                fleet.drain(live[int(rng.randint(len(live)))])
            else:
                fleet.step()
        fleet.serve_forever()

        # chaos actually happened (seeded schedule: deterministic)
        assert fleet.stats["failovers"] > 0
        assert fleet.stats["drains"] > 0
        assert fleet.stats["migrated_requests"] > 0
        assert cases
        # zero failed: every admitted request survived every incident
        # byte-identically
        for (p, n), h in zip(cases, handles):
            assert h.done and h.retire_reason in ("length", "eos")
            n_cap = min(n, T - len(p))
            np.testing.assert_array_equal(
                h.result(),
                np.asarray(dec.generate(
                    p[None], num_steps=n_cap))[0, len(p):])
        assert fleet.health()["held"] == 0
        for rid in fleet.replica_ids(live_only=True):
            e = fleet.replica(rid)
            assert e.idle and len(e._free) == e.slots
            if e.stats["steps"]:
                assert_compile_contract(e, copy={})
