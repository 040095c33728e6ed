"""ZAYA1's block on the normal path, each piece against the plain
reference of ``benchmark/families/zaya.py`` at toy size, seeded weights,
float32: the new ops, the routed experts, the CCA op's full forward, the
``Decoder``'s prefill-then-decode at every position over the boundaries
of the rolling state, the engine end to end, and the refusals by name."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
import mxnet_tpu.models  # noqa: F401
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-3          # of a logit's sigma: the toy cells' own limit
MAX_LEN, BUCKETS = 48, (8, 16)


@pytest.fixture(scope="module")
def H():
    import sys
    sys.path.insert(0, ROOT)
    from benchmark import harness
    return harness


@pytest.fixture(scope="module")
def toy(H):
    """(family, toy configuration, symbol, float32 weights from a seed,
    a way to hand the reference its leaves)."""
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "zaya1-8b.json")))
    cfg.update(cfg.pop("toy"))
    fam = H.load_module("families", "zaya")
    sym = fam.build_symbol(mx, cfg, {"attention": "dense"})
    w = H.make_weights(fam.param_specs(cfg), 7, jnp.float32)
    return fam, cfg, sym, w, lambda names: {n: w[n] for n in names}


def reference(toy, seqs):
    fam, cfg, _, _, leaves = toy
    with jax.default_matmul_precision("highest"):
        return np.asarray(fam.reference_logits(
            jnp.asarray(seqs, jnp.int32), leaves, cfg))


@pytest.fixture(scope="module")
def decoder(toy):
    _, _, sym, w, _ = toy
    return mx.parallel.Decoder(sym, w, max_len=MAX_LEN)


def worst_gap(toy, prompt, served):
    """How far a served token's logit lies below the reference's best,
    in sigmas of its row: 0 where the engine served the reference's
    own greedy choice at every position."""
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])[None]
    ref = reference(toy, seq)[0]
    rows = ref[len(prompt) - 1:len(prompt) - 1 + len(served)]
    got = rows[np.arange(len(served)), served]
    return float(np.max((rows.max(-1) - got) / rows.std(-1)))


# -- the small ops ---------------------------------------------------------

def test_rmsnorm_and_residual_merge_by_hand():
    rng = np.random.default_rng(0)
    x, y = rng.normal(size=(2, 3, 8)), rng.normal(size=(2, 3, 8))
    g, a, b, c = (rng.normal(size=(8,)) for _ in range(4))
    out = A.RMSNorm().forward({"eps": 1e-5}, [jnp.asarray(x),
                                              jnp.asarray(g)],
                              [], False, None)[0][0]
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(out, want, rtol=1e-5)
    m = A.ResidualMerge()
    full = m.forward({"affine": "full"}, [jnp.asarray(z) for z in
                                          (x, y, a, g, b, c)],
                     [], False, None)[0][0]
    np.testing.assert_allclose(full, (x + a) * g + (y + b) * c, rtol=1e-5)
    scale = m.forward({"affine": "scale"}, [jnp.asarray(z) for z in
                                            (x, y, g)], [], False, None)
    np.testing.assert_allclose(scale[0][0], x + y * g, rtol=1e-5)
    with pytest.raises(MXNetError, match="affine"):
        m.arguments({"affine": "half"})


def test_partial_rotary_turns_only_the_first_dims(toy):
    fam = toy[0]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 3, 16)),
                    jnp.float32)
    pos = jnp.arange(5)
    got = A.rope_rotate(x, pos, 5e6, rotary_dim=8)
    np.testing.assert_allclose(got, fam._rotary(x, 5e6, 8), atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(A.rope_rotate(x, pos, 5e6, rotary_dim=16),
                               A.rope_rotate(x, pos, 5e6), atol=0)
    # each batch row at its own position: the same turn, row by row
    per_row = A.rope_rotate(x, jnp.stack([pos, pos + 7]), 5e6, 8)
    np.testing.assert_allclose(per_row[0], got[0], atol=1e-6)
    np.testing.assert_allclose(
        per_row[1], A.rope_rotate(x[1:], pos + 7, 5e6, 8)[0], atol=1e-6)


# -- routed experts ----------------------------------------------------------

def _moe_inputs(gated, nx=6, e=16, h=24, n=(2, 9), seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    x = f(*n, e)
    if gated:
        return x, [f(nx, 2 * h, e), f(nx, e, h)]
    return x, [f(nx, h, e), f(nx, h), f(nx, e, h), f(nx, e)]


def _dense(p, ins):
    """The dense masked sum: every expert computes every token (what a
    custom product forces)."""
    return A.moe_ffn_math(
        p, ins, up_mm=lambda x, w: jnp.einsum("bte,xhe->btxh", x, w))


@pytest.mark.parametrize("case", ["top1", "top2", "empty_expert",
                                  "one_expert"])
@pytest.mark.parametrize("gated", [False, True])
def test_routed_experts_match_the_dense_masked_sum(case, gated):
    nx = 6
    x, experts = _moe_inputs(gated, nx)
    k = 2 if case == "top2" else 1
    p = A.MoEFFN().parse_params({"num_experts": nx, "hidden": 24,
                                 "top_k": k, "router": "given",
                                 "gated": gated})
    rng = np.random.default_rng(5)
    z = rng.normal(size=x.shape[:2] + (nx,))
    if case == "empty_expert":
        z[..., 2] = -50.0               # expert 2 is given no token
    if case == "one_expert":
        z[..., 4] = 50.0                # every token goes to expert 4
    probs = jax.nn.softmax(jnp.asarray(z, jnp.float32), -1)
    beta = jnp.asarray(rng.normal(size=(nx,)) * 0.01, jnp.float32)
    ins = [x, probs, beta] + experts
    stats = {}
    got = A.moe_ffn_math(p, ins, stats=stats)
    np.testing.assert_allclose(got, _dense(p, ins), rtol=2e-5, atol=2e-6)
    picked = np.asarray(jax.lax.top_k(probs + beta, k)[1])
    assert int(stats["experts_touched"]) == len(np.unique(picked))
    if case == "empty_expert":
        assert 2 not in picked
    if case == "one_expert":
        assert int(stats["experts_touched"]) == 1


@pytest.mark.parametrize("k", [1, 2])
def test_linear_gate_routes_and_differentiates_like_the_dense_form(k):
    """The op as ``get_transformer_lm(num_experts=..)`` uses it: its
    own gate, biased ReLU experts, kept gates renormalized; the routed
    form's gradients are the dense form's."""
    nx = 5
    x, experts = _moe_inputs(False, nx, seed=3)
    gate = jnp.asarray(np.random.default_rng(4).normal(size=(nx, 16)),
                       jnp.float32)
    p = A.MoEFFN().parse_params({"num_experts": nx, "hidden": 24,
                                 "top_k": k})
    ins = [x, gate] + experts
    np.testing.assert_allclose(A.moe_ffn_math(p, ins), _dense(p, ins),
                               rtol=2e-5, atol=2e-6)
    loss = lambda f: (lambda *a: jnp.sum(jnp.square(f(p, list(a)))))
    g_routed = jax.grad(loss(A.moe_ffn_math), argnums=(0, 1, 2, 4))(*ins)
    g_dense = jax.grad(loss(_dense), argnums=(0, 1, 2, 4))(*ins)
    for a, b in zip(g_routed, g_dense):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_routed_experts_compute_k_experts_a_token_not_all():
    """X=16, k=1: the lowered program's operations (XLA's cost
    analysis) stay under twice one expert's for every token, where the
    dense form, the parent's only one, needs sixteen times."""
    nx, e, h, n = 16, 64, 128, 512
    x, experts = _moe_inputs(True, nx, e, h, (1, n))
    probs = jax.nn.softmax(jnp.asarray(
        np.random.default_rng(0).normal(size=(1, n, nx)), jnp.float32))
    p = A.MoEFFN().parse_params({"num_experts": nx, "hidden": h,
                                 "top_k": 1, "router": "given",
                                 "gated": True})
    ins = [x, probs, jnp.zeros((nx,), jnp.float32)] + experts
    one_expert = 2.0 * n * (2 * h * e + e * h)
    flops = lambda f: jax.jit(lambda *a: f(p, list(a))).lower(*ins) \
        .cost_analysis()["flops"]
    routed, dense = flops(A.moe_ffn_math), flops(_dense)
    assert routed < 2.0 * one_expert, (routed, one_expert)
    assert dense > 15.0 * one_expert, (dense, one_expert)
    assert routed < 1.6 * one_expert, (routed, one_expert)
    assert A.routed_block_rows(n, nx) == 16
    assert A.routed_block_rows(32, 16) == 16      # a decode step's
    assert A.routed_block_rows(2048, 16) == 64
    assert A.routed_block_rows(10 ** 6, 16) == 128


def test_grouped_matmul_kernel_against_its_plain_form():
    """The Pallas kernel under the interpreter: each block against its
    own expert's matrix, blocks past the used ones zero."""
    rng = np.random.default_rng(2)
    rows, nb, k, n, nx = 16, 5, 128, 256, 4
    x = jnp.asarray(rng.normal(size=(nb * rows, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(nx, n, k)), jnp.float32)
    block_e = jnp.asarray([0, 0, 3, 3, 3], jnp.int32)
    used = jnp.int32(3)
    want = pk.grouped_matmul(x, w, block_e, used, rows)
    got = pk.grouped_matmul(x, w, block_e, used, rows, block_n=128,
                            interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    by_hand = np.asarray(x[2 * rows:3 * rows]) @ np.asarray(w[3]).T
    np.testing.assert_allclose(want[2 * rows:3 * rows], by_hand, rtol=1e-4,
                               atol=1e-4)
    assert not np.asarray(want[3 * rows:]).any()
    with pytest.raises(ValueError, match="whole blocks"):
        pk.grouped_matmul(x[:-1], w, block_e, used, rows)


def test_moe_docstring_and_refusals_of_the_given_router():
    assert "COMPUTE every token" not in A.MoEFFN.__doc__
    p = A.MoEFFN().parse_params({"num_experts": 4, "hidden": 8,
                                 "top_k": 1, "router": "given",
                                 "gated": True})
    x, experts = _moe_inputs(True, 4, 16, 8)
    probs = jnp.full(x.shape[:2] + (4,), 0.25)
    with pytest.raises(MXNetError, match="expert-parallel"):
        A.moe_ffn_math(p, [x, probs, jnp.zeros(4)] + experts,
                       ep=("expert", 2))
    with pytest.raises(MXNetError, match="router"):
        A.MoEFFN.given({"router": "learned"})


# -- the CCA op ----------------------------------------------------------------

def test_cca_full_forward_matches_the_reference(toy):
    fam, cfg, _, w, _ = toy
    names = ["qk_weight", "v_weight", "conv0_weight", "conv0_bias",
             "conv1_weight", "conv1_bias", "temp", "out_weight"]
    sym = mx.sym.CCAttention(
        data=mx.sym.Variable("data"), num_heads=4, num_kv_heads=2,
        head_dim=16, rotary_dim=8, rope_base=5e6, impl="flash",
        name="cca", **{n: mx.sym.Variable("cca_" + n) for n in names})
    h = np.random.default_rng(3).normal(size=(2, 19, 64)) \
        .astype(np.float32)
    args = {"cca_" + n: mx.nd.array(np.asarray(w["layer1_cca_" + n]))
            for n in names}
    args["data"] = mx.nd.array(h)
    ex = sym.bind(mx.cpu(), args)
    ex.forward(is_train=False)
    p = {"cca_" + n: w["layer1_cca_" + n] for n in names}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(fam.reference_cca(jnp.asarray(h), p, cfg))
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), want,
                               atol=1e-5 * np.abs(want).max() + 1e-6)
    with pytest.raises(MXNetError, match="even"):
        A.CCAttention.widths({"num_heads": 3, "num_kv_heads": 3,
                              "head_dim": 8})


def test_model_binds_and_runs_through_the_executor(toy):
    """``get_zaya_lm`` through the ordinary executor, logits against
    the reference at every position; the head is the embedding."""
    from mxnet_tpu.parallel.decode import _logits_symbol
    fam, cfg, sym, w, _ = toy
    assert set(fam.param_specs(cfg)) \
        == set(sym.list_arguments()) - {"data", "softmax_label"}
    assert "lm_head_weight" not in sym.list_arguments()
    toks = np.random.default_rng(0).integers(0, 320, (2, 21))
    args = {k: mx.nd.array(np.asarray(v)) for k, v in w.items()}
    args["data"] = mx.nd.array(toks.astype(np.float32))
    ex = _logits_symbol(sym).bind(mx.cpu(), args)
    ex.forward(is_train=False)
    ref = reference(toy, toks)
    assert np.abs(ex.outputs[0].asnumpy() - ref).max() < TOL * ref.std()


# -- the Decoder: prefill then decode, every position ------------------------

@pytest.mark.parametrize("plen", [1, 2, 3, BUCKETS[1] - 1, BUCKETS[1]])
def test_decoder_prefill_then_decode_matches_the_full_forward(
        toy, decoder, plen):
    total = plen + 9
    toks = np.random.default_rng(plen).integers(0, 320, (2, total)) \
        .astype(np.int32)
    ref = reference(toy, toks)
    caches = decoder.init_cache(2)
    logits, caches = decoder.prefill(caches, toks[:, :plen])
    worst = np.abs(np.asarray(logits) - ref[:, :plen]).max()
    for t in range(plen, total):
        step, caches = decoder.step(caches, t, toks[:, t])
        worst = max(worst, np.abs(np.asarray(step) - ref[:, t]).max())
    assert worst < TOL * ref.std()


@pytest.mark.parametrize("live,chunk", [
    ((1, 1, 1, 1, 1), 1), ((1, 0, 1, 0, 1), 1), ((0, 0, 0, 1, 0), 1),
    ((1, 0, 1, 0, 1), 3)],
    ids=["all_live", "some_dead", "one_live", "chunk_of_3"])
def test_bounded_read_equals_the_dense_read(decoder, monkeypatch, live,
                                            chunk):
    """The slot walk's read of CCAttention's rows (``_paged_read``,
    blocks of 16 of the toy's 48 rows) against the dense read of the
    same rows (``_lane_attn``, every row read and masked), each slot at
    its own position: lengths of under a block, of a block and a part,
    and up to the last row. Live slots agree within the toy limit and
    write the same rows and state; a slot that holds no request (length
    0, a stale position) gets zeros from the read, and finite logits."""
    S, C = len(live), chunk
    live = np.asarray(live, bool)
    rng = np.random.default_rng(5)
    caches = [tuple(jnp.asarray(rng.standard_normal(x.shape), x.dtype)
                    for x in entry) for entry in decoder.init_cache(S)]
    pos = jnp.asarray([20, 31, MAX_LEN - C, 15, 5], jnp.int32)
    lens = jnp.where(live, pos + C, 0)
    toks = jnp.asarray(rng.integers(0, 320, (S, C)), jnp.int32)
    reads = []

    def spy(read):
        def wrapped(*a, **kw):
            reads.append(read(*a, **kw))
            return reads[-1]
        return wrapped

    stats = {}
    monkeypatch.setattr(decoder, "_paged_read", spy(decoder._paged_read))
    got, cb = decoder._run_slots(decoder._params, decoder._aux, caches,
                                 pos, toks, lens=lens, stats=stats)
    assert len(reads) == 2                       # one a CCA node
    assert int(stats["attn_rows_read"]) == 2 * int(
        pk.paged_rows_fetched(lens, MAX_LEN, 16))
    monkeypatch.setattr(
        decoder, "_paged_read",
        lambda q, entry, pos, kv, lens=None, stats=None:
        decoder._lane_attn(q, entry, pos, kv))
    want, cd = decoder._run_slots(decoder._params, decoder._aux, caches,
                                  pos, toks, lens=lens)
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want)[live].max() < TOL * want[live].std()
    assert np.isfinite(got).all()
    for o in reads:
        assert not np.asarray(o)[~live].any()
    for a, b in zip(jax.tree_util.tree_leaves(cb),
                    jax.tree_util.tree_leaves(cd)):
        np.testing.assert_allclose(np.asarray(a)[live], np.asarray(b)[live],
                                   rtol=1e-5, atol=1e-5)


def test_decoder_cache_declares_the_rolling_state(decoder):
    from mxnet_tpu.parallel.decode import STATE_ROWS
    caches = decoder.init_cache(3)
    assert len(caches) == 2                       # one entry a CCA node
    k, v, state = caches[0]
    assert k.shape == v.shape == (3, MAX_LEN, 32)      # Hkv * D lanes
    assert state.shape == (3, STATE_ROWS * (64 + 32 + 16))
    specs = decoder.cache_specs(caches)
    from jax.sharding import PartitionSpec as P
    assert specs[0][0] == P(None, None, "model") and specs[0][2] == P()
    assert decoder._slots_batched


# -- the engine -------------------------------------------------------------------

def make_engine(decoder, **kw):
    kw.setdefault("prefix_cache_mb", 0)
    return mx.serving.InferenceEngine(
        decoder, slots=3, prefill_buckets=BUCKETS, steps_per_round=4, **kw)


def test_engine_end_to_end_two_buckets_staggered_arrivals(toy, decoder):
    """Prompts on both sides of both buckets arriving while others
    decode, more requests than slots (so slots are reused, a short
    request after a longer one): every served token is the reference's
    greedy choice, nothing compiles twice, and the tie of embedding and
    head does not make a request repeat one token."""
    eng = make_engine(decoder)
    rng = np.random.default_rng(1)
    lens = [15, 1, 16, 2, 9, 3, 8, 7, 12, 1]
    outs = [14, 6, 9, 12, 5, 16, 7, 10, 4, 8]
    prompts = [rng.integers(0, 320, n).astype(np.int32) for n in lens]
    before = mx.telemetry.counter("serving.moe_layer_steps").value
    handles = []
    for p, n in zip(prompts, outs):
        handles.append(eng.submit(p, max_tokens=n))
        eng.step()
    eng.serve_forever()
    again = total = 0
    for h, p, n in zip(handles, prompts, outs):
        assert len(h.tokens) == n
        assert worst_gap(toy, p, h.tokens) < TOL
        fed = [int(p[-1])] + list(h.tokens[:-1])    # each step's input
        again += sum(a == b for a, b in zip(fed, h.tokens))
        total += n
    # the head is the embedding: were the embedding a large share of
    # the final stream, every step would answer with its own input
    assert again < 0.2 * total, (again, total)
    counts = eng.compile_counts
    assert counts["decode"] == 1
    assert counts["prefill"] == {8: 1, 16: 1}
    steps = mx.telemetry.counter("serving.moe_layer_steps").value - before
    assert steps == 2 * 4 * eng.stats["steps"]      # layers x steps x rounds
    eng.close()


def test_engine_counts_the_rows_its_bounded_reads_fetch(decoder):
    """``serving.attn_rows_read`` / ``serving.attn_rows_pool`` cover
    CCAttention's K rows: the pool is slots x rows x layers for every
    decode step, and the reads fetch some of it (a request's rows,
    block-rounded), never more."""
    read = mx.telemetry.counter("serving.attn_rows_read")
    pool = mx.telemetry.counter("serving.attn_rows_pool")
    r0, p0 = read.value, pool.value
    eng = make_engine(decoder)
    assert eng._attn_pool_rows == 3 * MAX_LEN * 2
    rng = np.random.default_rng(6)
    for n in (9, 2):
        eng.submit(rng.integers(0, 320, n).astype(np.int32), max_tokens=10)
    eng.serve_forever()
    assert pool.value - p0 == 3 * MAX_LEN * 2 * 4 * eng.stats["steps"]
    assert 0 < read.value - r0 <= pool.value - p0
    # two of three slots ever hold a request, of at most two blocks
    assert read.value - r0 <= 2 * 32 * 2 * 4 * eng.stats["steps"]
    eng.close()


def test_slot_reused_after_a_longer_request(toy, decoder):
    """One slot: a long request fills its rows and its state, then a
    prompt of one token takes the slot over. Nothing of the first
    request may show: position 0 reads no state at all."""
    eng = mx.serving.InferenceEngine(decoder, slots=1,
                                     prefill_buckets=BUCKETS,
                                     steps_per_round=4, prefix_cache_mb=0)
    rng = np.random.default_rng(2)
    for n, k in ((16, 20), (1, 12), (2, 12), (3, 9)):
        p = rng.integers(0, 320, n).astype(np.int32)
        h = eng.submit(p, max_tokens=k)
        eng.serve_forever()
        assert worst_gap(toy, p, h.tokens) < TOL, (n, k)
    eng.close()


def test_state_is_carried_across_prefill_chunks(toy, decoder):
    """Chunked prefill: pieces of 8 through the bucket programs, decode
    rounds of other slots between them."""
    eng = make_engine(decoder, prefill_chunk=8)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 320, n).astype(np.int32)
               for n in (16, 13, 9, 5)]
    handles = [eng.submit(p, max_tokens=7) for p in prompts]
    eng.serve_forever()
    assert eng.stats["prefill_chunks"] > len(prompts)
    for h, p in zip(handles, prompts):
        assert worst_gap(toy, p, h.tokens) < TOL
    eng.close()


REFUSED = {
    "prefix pool": dict(prefix_cache_mb=4),
    "speculation": dict(draft="ngram"),
    "handoff": dict(role="prefill"),
    "tp": dict(tp=2),
    "ep": dict(ep=2),
    "int8 weights": dict(weight_dtype="int8"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_refuses_by_name_what_cannot_carry_the_state(decoder,
                                                            feature):
    match = "MoEFFN" if feature == "ep" else "CCAttention"
    with pytest.raises(MXNetError, match=match):
        make_engine(decoder, **REFUSED[feature])


@pytest.mark.parametrize("option", [dict(cache_dtype="int8"),
                                    dict(weight_dtype="int4")])
def test_decoder_refuses_by_name(toy, option):
    _, _, sym, w, _ = toy
    with pytest.raises(MXNetError, match="CCAttention"):
        mx.parallel.Decoder(sym, w, max_len=MAX_LEN, **option)


def test_default_prefix_pool_is_off_for_a_rolling_state(decoder):
    eng = mx.serving.InferenceEngine(decoder, slots=2,
                                     prefill_buckets=BUCKETS)
    assert eng.prefix_cache_mb == 0 and eng._pool is None
    eng.close()


def test_bfloat16_serving_stays_near_the_reference(toy):
    """The served types: bfloat16 weights, rows and state, the router in
    float32. Not the toy limit (a routing flip moves a logit by a good
    part of a sigma); the served tokens still lie near the top."""
    fam, cfg, sym, w, _ = toy
    wb = {k: v.astype(jnp.bfloat16) for k, v in w.items()}
    dec = mx.parallel.Decoder(sym, wb, max_len=MAX_LEN,
                              compute_dtype="bfloat16")
    assert dec.init_cache(1)[0][2].dtype == jnp.bfloat16
    eng = make_engine(dec)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 320, n).astype(np.int32) for n in (9, 4)]
    handles = [eng.submit(p, max_tokens=8) for p in prompts]
    eng.serve_forever()
    w32 = {k: v.astype(jnp.float32) for k, v in wb.items()}
    served = (fam, cfg, sym, w32, lambda names: {n: w32[n] for n in names})
    for h, p in zip(handles, prompts):
        assert worst_gap(served, p, h.tokens) < 1.0
    eng.close()
