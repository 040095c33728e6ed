"""Tensor-parallel serving (ISSUE 14): the slot-paged KV cache and
every compiled program family shard over a mesh's ``model`` axis on
the kv-head dimension, and greedy outputs stay BYTE-IDENTICAL to tp=1
— the oracle here is the offline single-device ``Decoder.generate``,
i.e. exactly the tp=1 compute every other serving test pins against.
Runs REAL tp=2 / tp=4 meshes on the 8-virtual-CPU-device harness
(tests/conftest.py forces ``--xla_force_host_platform_device_count=8``).

Compile-budget discipline (PR 4/5/9/10/11 precedent): ONE shared
module-scoped tp=2 engine carries the whole identity gauntlet (prefix
cache + eviction + chunked prefill + n-gram speculation); the tp=4 /
restore / int8 tests use the smallest configs that still exercise
their axis, and the validation test compiles nothing."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder, model_parallel_mesh
from mxnet_tpu.serving import InferenceEngine

from check_utils import assert_compile_contract

# 4 kv heads so the SAME symbol serves tp=2 and tp=4 (and tp=3 is the
# loud divisibility refusal); 1 layer keeps the compile bill small —
# the multi-layer plumbing is layer-count-agnostic and pinned offline
VOCAB, LAYERS, EMBED, HEADS = 17, 1, 32, 4
T = 16


def _lm(**kw):
    return get_transformer_lm(VOCAB, num_layers=LAYERS, embed_dim=EMBED,
                              num_heads=HEADS, impl="dense", **kw)


def _init_params(sym, rng):
    shapes = {"data": (2, T), "softmax_label": (2, T)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    return {n: jnp.asarray(rng.uniform(-0.3, 0.3, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}


@pytest.fixture(scope="module")
def lm():
    rng = np.random.RandomState(0)
    sym = _lm()
    params = _init_params(sym, rng)
    return sym, params, Decoder(sym, params, max_len=T)


def _engine(sym, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("prefix_cache_mb", 0)
    return InferenceEngine(Decoder(sym, params, max_len=T), **kw)


@pytest.fixture(scope="module")
def tp2_engine(lm):
    """THE shared tp=2 engine: prefix cache with a tiny (eviction-
    churning) pool, chunked prefill, and n-gram speculation all ON —
    every identity test below rides the same compiled programs."""
    sym, params, _ = lm
    return _engine(sym, params, tp=2, prefix_cache_mb=0.01,
                   prefill_chunk=3, draft="ngram", spec_k=3)


_ORACLE = {}


def _oracle(dec, prompt, n):
    prompt = np.asarray(prompt)
    n = min(n, T - len(prompt))
    key = (id(dec), prompt.tobytes(), len(prompt), n)
    if key not in _ORACLE:
        _ORACLE[key] = np.asarray(
            dec.generate(prompt[None], num_steps=n))[0, len(prompt):]
    return _ORACLE[key]


def _gauntlet_cases(rng):
    base = rng.randint(0, VOCAB, (7,))
    return [
        (base, 3),                                   # retained, 3 chunks
        (base[:4].copy(), 6),                        # prefix hit
        (np.concatenate([base[:4],
                         rng.randint(0, VOCAB, (3,))]), 3),  # partial
        (rng.randint(0, VOCAB, (2,)), 5),            # miss, 1 chunk
        (base.copy(), 3),                            # full hit -> P-1
        (rng.randint(0, VOCAB, (10,)), 3),           # beyond bucket
        (np.array([0, 3, 3]), 13),                   # accepts drafts
    ]


def test_tp2_gauntlet_byte_identical(lm, tp2_engine):
    """THE tentpole oracle at tp=2: prefix hits (full/partial/miss),
    1-slot pool eviction churn, chunk-boundary prompts, beyond-bucket
    chunked admission and accepted n-gram drafts all serve
    byte-identically to the offline tp=1 decoder, with the compile
    contract UNCHANGED ({decode:1, verify:<=1, prefill/bucket,
    copy/bucket}) — the programs are shard_map'd, not multiplied. A
    second reversed-order wave on the same engine compiles nothing
    new."""
    sym, params, dec = lm
    eng = tp2_engine
    assert eng.tp == 2 and eng._mesh is not None
    rng = np.random.RandomState(13)
    cases = _gauntlet_cases(rng)
    rs = [eng.submit(p, max_tokens=n) for p, n in cases]
    eng.serve_forever()
    for (p, n), r in zip(cases, rs):
        np.testing.assert_array_equal(r.result(), _oracle(dec, p, n))
    assert eng.stats["prefix_hits"] >= 1
    assert eng.stats["prefill_chunks"] > len(cases)
    assert eng.stats["spec_rounds"] >= 1
    assert eng.stats["spec_accepted"] >= 1
    assert eng._prefix.evictions >= 1        # the tiny pool churned
    cc = assert_compile_contract(eng)
    assert cc["copy"]                        # sharded copies dispatched

    # every cache buffer (pool included) really is sharded over the
    # model axis — each shard holds Hkv/2 heads of every row
    from jax.sharding import PartitionSpec as P
    for tree in (eng._caches, eng._pool):
        for leaf in jax.tree_util.tree_leaves(tree):
            spec = leaf.sharding.spec
            if leaf.ndim >= 3:
                assert tuple(spec) == (None, None, "model")
                assert leaf.addressable_shards[0].data.shape[2] \
                    == leaf.shape[2] // 2
            else:
                assert tuple(spec) in ((), (None,) * leaf.ndim)

    # telemetry: the tp info gauges (doc/observability.md)
    snap = mx.telemetry.snapshot()["serving"]
    assert snap["tp_degree"] == 2
    slot_bytes = sum(x.nbytes for x in
                     jax.tree_util.tree_leaves(eng._caches))
    assert snap["kv_bytes_per_shard"] == slot_bytes // 2
    # snapshot geometry carries the degree (restore rebuilds the mesh)
    assert eng.snapshot()["engine"]["tp"] == 2

    # second wave, reversed order: zero new programs, still exact
    log_len = len(eng._compile_log)
    rs2 = [eng.submit(p, max_tokens=n) for p, n in reversed(cases)]
    eng.serve_forever()
    for (p, n), r in zip(reversed(cases), rs2):
        np.testing.assert_array_equal(r.result(), _oracle(dec, p, n))
    assert len(eng._compile_log) == log_len
    assert eng.idle


def test_tp2_sampled_schedule_independent(lm, tp2_engine):
    """Sampled identity survives sharding: draws are keyed
    (seed, position) on the REPLICATED logits, so the same sampled
    request reproduces on the tp=2 engine whatever else is resident —
    and the engine reports valid token ids (no cross-shard rng
    divergence). No new compiles (shared engine)."""
    sym, params, _ = lm
    eng = tp2_engine
    rng = np.random.RandomState(6)
    p = rng.randint(0, VOCAB, (4,))
    log_len = len(eng._compile_log)
    a = eng.submit(p, max_tokens=6, temperature=0.9, seed=42)
    eng.serve_forever()
    b = eng.submit(p, max_tokens=6, temperature=0.9, seed=42)
    eng.submit(rng.randint(0, VOCAB, (5,)), max_tokens=4,
               temperature=0.5, seed=7)      # co-resident noise
    eng.serve_forever()
    np.testing.assert_array_equal(a.result(), b.result())
    out = a.result()
    assert out.shape == (6,) and (out >= 0).all() and (out < VOCAB).all()
    assert len(eng._compile_log) == log_len


def test_tp4_multi_step_rounds_snapshot_restore(lm):
    """tp=4 (each shard holds ONE kv head) with steps_per_round=3:
    byte-identity to the offline oracle holds through a mid-flight
    snapshot()/restore() cycle — the geometry carries tp, the restored
    engine rebuilds the mesh and resumes byte-identically on BOTH
    engines."""
    sym, params, dec = lm
    rng = np.random.RandomState(11)
    eng = _engine(sym, params, tp=4, steps_per_round=3)
    assert eng.tp == 4
    cases = [(rng.randint(0, VOCAB, (pl,)), n)
             for pl, n in [(2, 5), (6, 4), (4, 6), (3, 5)]]
    rs = [eng.submit(p, max_tokens=n) for p, n in cases]
    for _ in range(3):
        eng.step()                      # mid-flight: slots decoding
    snap = eng.snapshot()
    assert snap["engine"]["tp"] == 4
    eng2, handles = InferenceEngine.restore(
        snap, Decoder(sym, params, max_len=T))
    assert eng2.tp == 4 and eng2._mesh is not None
    eng.serve_forever()
    eng2.serve_forever()
    for (p, n), r in zip(cases, rs):
        want = _oracle(dec, p, n)
        np.testing.assert_array_equal(r.result(), want)
        h = handles.get(r.id, r)
        np.testing.assert_array_equal(h.result(), want)
    assert_compile_contract(eng, copy={})
    assert_compile_contract(eng2, copy={})


def test_tp2_int8_kv_byte_identical(lm):
    """int8 KV at tp=2: the quantized values AND their per-row scale
    buffers shard on the kv-head dim (quantization is per-(position,
    head) row, so each shard quantizes its own heads bitwise like
    tp=1 did) — outputs byte-match the int8 offline decoder."""
    sym, params, _ = lm
    rng = np.random.RandomState(5)
    dec8 = Decoder(sym, params, max_len=T, cache_dtype="int8")
    eng = InferenceEngine(
        Decoder(sym, params, max_len=T,
                cache_dtype="int8"),
        slots=2, prefill_buckets=(4,), prefix_cache_mb=0, tp=2)
    cases = [(rng.randint(0, VOCAB, (pl,)), n)
             for pl, n in [(3, 5), (4, 4), (2, 6)]]
    rs = [eng.submit(p, max_tokens=n) for p, n in cases]
    eng.serve_forever()
    for (p, n), r in zip(cases, rs):
        np.testing.assert_array_equal(r.result(), _oracle(dec8, p, n))
    # int8 entries carry 4 buffers/node (values + scales, K and V) —
    # all four sharded on their head dim
    for leaf in jax.tree_util.tree_leaves(eng._caches):
        assert tuple(leaf.sharding.spec) == (None, None, "model")
    assert_compile_contract(eng, verify=0, copy={})


def test_tp2_windowed_ring_byte_identical():
    """Windowed rings COMPOSE with tp (the doc/serving.md claim,
    pinned): the ring K/V shards on its head dim while the
    [S, window] position buffers replicate in full on every shard,
    chunked prefill's read-before-write ring math runs per shard, and
    the window branch's all-gather rebuilds the head output — outputs
    byte-match the offline windowed decoder. Speculation refuses
    loudly exactly as at tp=1 (ring precedent), and the
    kv_bytes_per_shard gauge counts the replicated position buffers
    at FULL size."""
    rng = np.random.RandomState(12)
    sym = _lm(window=6, pos_encoding="rope")
    params = _init_params(sym, rng)
    dec = Decoder(sym, params, max_len=T)
    with pytest.warns(UserWarning, match="windowed"):
        eng = InferenceEngine(
            Decoder(sym, params, max_len=T),
            slots=2, prefill_buckets=(4, 8), prefill_chunk=4,
            spec_k=3, draft="ngram", tp=2)
    assert eng.spec_draft == "off" and eng._prefix is None
    cases = [(rng.randint(0, VOCAB, (pl,)), n)
             for pl, n in [(3, 5), (6, 4), (4, 5)]]
    rs = [eng.submit(p, max_tokens=n) for p, n in cases]
    eng.serve_forever()
    for (p, n), r in zip(cases, rs):
        np.testing.assert_array_equal(r.result(), _oracle(dec, p, n))
    assert eng.stats["prefill_chunks"] > len(cases)   # chunking ran
    assert_compile_contract(eng, verify=0, copy={})
    leaves = jax.tree_util.tree_leaves(eng._caches)
    assert any(leaf.ndim == 2 for leaf in leaves)     # ring positions
    for leaf in leaves:
        want = (None, None, "model") if leaf.ndim >= 3 else ()
        assert tuple(leaf.sharding.spec)[:3] == want[:leaf.ndim] \
            or tuple(leaf.sharding.spec) == want
    assert mx.telemetry.snapshot()["serving"]["kv_bytes_per_shard"] \
        == sum(x.nbytes // 2 if x.ndim >= 3 else x.nbytes
               for x in leaves)


def test_tp2_paged_byte_identical_to_tp1_paged(lm):
    """The bounded read under tensor parallelism (ISSUE 15, closing
    the PR 14 follow-up): a tp=2 engine serves the Pallas kernel
    against its LOCAL cache shard — the kernel takes its kv-head count
    from the cache operand, so inside the shard_map it walks the
    shard's own kv heads — with no warning, byte-identical to the
    tp=1 engine on the one read there is AND to the offline oracle,
    which reads densely. Cache sharding asserted; compile contract
    unchanged at both degrees."""
    import warnings

    sym, params, dec = lm
    e1 = _engine(sym, params)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        e2 = _engine(sym, params, tp=2)
    assert e2._attn_pool_rows and e2.tp == 2    # the read is bounded
    rng = np.random.RandomState(23)
    cases = [(rng.randint(0, VOCAB, (pl,)), n)
             for pl, n in [(3, 5), (6, 4), (4, 6)]]
    rs1 = [e1.submit(p, max_tokens=n) for p, n in cases]
    rs2 = [e2.submit(p, max_tokens=n) for p, n in cases]
    e1.serve_forever()
    e2.serve_forever()
    for (p, n), a, b in zip(cases, rs1, rs2):
        want = _oracle(dec, p, n)
        np.testing.assert_array_equal(a.result(), want)
        np.testing.assert_array_equal(b.result(), want)
    for leaf in jax.tree_util.tree_leaves(e2._caches):
        assert tuple(leaf.sharding.spec) == (None, None, "model")
        assert leaf.addressable_shards[0].data.shape[2] \
            == leaf.shape[2] // 2
    assert_compile_contract(e1, verify=0, copy={})
    assert_compile_contract(e2, verify=0, copy={})


def test_tp_validation_and_refusals(lm):
    """Construction-time contracts, all compile-free: uneven kv-head
    splits refuse loudly (GQA groups must stay whole per shard), bad
    tp/mesh combinations refuse with pointers, the bounded read
    COMPOSES with tp since ISSUE 15 (no warning — construction
    compiles nothing, the serving identity is
    test_tp2_paged_byte_identical's), and MXNET_SERVING_TP is the env
    default for the knob."""
    import warnings

    sym, params, _ = lm
    with pytest.raises(MXNetError, match="divide evenly"):
        _engine(sym, params, tp=3)       # 4 kv heads, 3 shards
    with pytest.raises(MXNetError, match="tp must be >= 1"):
        _engine(sym, params, tp=0)
    with pytest.raises(MXNetError, match="visible devices"):
        _engine(sym, params, tp=64)
    with pytest.raises(MXNetError, match="'model' axis"):
        from mxnet_tpu.parallel import data_parallel_mesh
        _engine(sym, params, mesh=data_parallel_mesh(2))
    with pytest.raises(MXNetError, match="disagrees"):
        _engine(sym, params, mesh=model_parallel_mesh(2), tp=4)
    # an explicit mesh works and wins the degree
    eng = _engine(sym, params, mesh=model_parallel_mesh(2))
    assert eng.tp == 2
    # the bounded read x tp composes: no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ep = _engine(sym, params, tp=2)
    assert ep._attn_pool_rows and ep.tp == 2
    # env default (ctor only — nothing dispatches)
    import os
    old = os.environ.get("MXNET_SERVING_TP")
    os.environ["MXNET_SERVING_TP"] = "2"
    try:
        assert _engine(sym, params).tp == 2
    finally:
        if old is None:
            del os.environ["MXNET_SERVING_TP"]
        else:
            os.environ["MXNET_SERVING_TP"] = old
