"""Pallas paged-attention decode kernel (ISSUE 11): the decode/verify
hot path reads ONLY each slot's live KV rows — grid over (slot,
kv-block), the per-slot position vector bounds the kv-block walk,
online-softmax accumulation, int8 dequantized IN the kernel from the
side scales (the cache is read once at 1 byte/elem instead of being
dequantized to a full float copy first).

Identity contract (the dense read of the offline step is the oracle:
``Decoder._run`` at one scalar position, ``Decoder.generate``): float
flavors are
byte-identical at the TOKEN level through the engine gauntlet (greedy
argmax — online softmax is a reassociation of the same f32 math);
int8 flavors carry the quantized-cache tolerance contract of the
existing flavor tests. Runs entirely under the Pallas INTERPRETER on
CPU; tests/test_chip_compile.py compiles the same kernel for the chip.

Compile frugality (tier-1 budget): ONE module-scoped lm/decoder pair,
ONE shared paged engine (1 layer, E=16, max_len 16), oracle outputs
memoized, and the windowed-refusal test compiles nothing (engine
construction builds no programs)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.serving import InferenceEngine

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
def paged_engine(lm):
    """ONE shared paged engine exercising the whole composition:
    prefix cache + chunked prefill + n-gram speculation +
    steps_per_round>1 — every identity test below reuses its compiled
    programs."""
    sym, params, _ = lm
    return InferenceEngine(
        Decoder(sym, params, max_len=T),
        slots=2, prefill_buckets=(4, 8), prefix_cache_mb=0.0021,
        prefill_chunk=3, draft="ngram", spec_k=3, steps_per_round=2)


@pytest.fixture(scope="module")
def int8_dec(lm):
    """ONE int8 decoder shared by the int8-tolerance and
    read-cache-clamp tests (compile frugality)."""
    sym, params, _ = lm
    return Decoder(sym, params, max_len=T,
                   cache_dtype="int8")


_ORACLE = {}


def _oracle(dec, prompt, n):
    prompt = np.asarray(prompt)
    n = min(n, T - len(prompt))
    key = (id(dec), prompt.tobytes(), len(prompt), n)
    if key not in _ORACLE:
        _ORACLE[key] = np.asarray(
            dec.generate(prompt[None], num_steps=n))[0, len(prompt):]
    return _ORACLE[key]


# -- kernel vs dense reference ----------------------------------------

def _ref_attention(q, k, v, pos):
    """Dense masked reference: per-slot causal read of rows
    [0, pos + C)."""
    s_, c, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    kf = np.repeat(np.asarray(k, np.float32), g, axis=2)
    vf = np.repeat(np.asarray(v, np.float32), g, axis=2)
    out = np.zeros((s_, c, h, d), np.float32)
    for si in range(s_):
        for ci in range(c):
            qp = int(pos[si]) + ci
            sc = np.einsum("hd,thd->ht",
                           np.asarray(q[si, ci], np.float32),
                           kf[si, :qp + 1]) / np.sqrt(d)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[si, ci] = np.einsum("ht,thd->hd", p, vf[si, :qp + 1])
    return out


@pytest.mark.parametrize("shape", [
    (3, 1, 2, 2, 8, 16),    # plain decode step
    (3, 4, 4, 2, 8, 16),    # chunked verify width, GQA group 2
    (2, 3, 6, 3, 8, 48),    # wider GQA, non-power-of-two cache
])
def test_paged_kernel_matches_dense_reference(shape):
    """The kernel itself, against a dense per-slot reference, at MIXED
    per-slot positions: fp exact to f32 tolerance; int8 operands with
    in-kernel dequant match the dequantize-first reference on the SAME
    quantized values (the dequant arithmetic is identical — the kernel
    just never materializes the float copy)."""
    from mxnet_tpu.ops.pallas_kernels import paged_attention
    from mxnet_tpu.parallel.decode import fold_heads

    s_, c, h, kv, d, l_ = shape
    rng = np.random.RandomState(7)
    q = rng.randn(s_, c, h, d).astype(np.float32)
    k = rng.randn(s_, l_, kv, d).astype(np.float32)
    v = rng.randn(s_, l_, kv, d).astype(np.float32)
    pos = rng.randint(0, l_ - c, (s_,)).astype(np.int32)
    # the kernel takes the cache as the decoder stores it
    got = np.asarray(paged_attention(jnp.asarray(q), fold_heads(k),
                                     fold_heads(v), pos, kv_heads=kv))
    np.testing.assert_allclose(got, _ref_attention(q, k, v, pos),
                               rtol=2e-5, atol=2e-5)

    def quant(x):
        xf = np.asarray(x, np.float32)
        s = np.max(np.abs(xf), axis=-1) / 127.0
        s = np.where(s > 0, s, 1.0)
        return (np.round(xf / s[..., None]).astype(np.int8),
                s.astype(np.float32))

    k8, ks = quant(k)
    v8, vs = quant(v)
    got8 = np.asarray(paged_attention(
        jnp.asarray(q), fold_heads(k8), fold_heads(v8), pos,
        kv_heads=kv, k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs)))
    want8 = _ref_attention(q, k8.astype(np.float32) * ks[..., None],
                           v8.astype(np.float32) * vs[..., None], pos)
    np.testing.assert_allclose(got8, want8, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cache", ["bfloat16", "int8"])
@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("heads", [(4, 4, 64), (8, 2, 128)],
                         ids=["mha_d64", "gqa_d128"])
def test_bounded_read_matches_lane_attn(heads, chunk, cache):
    """The read bounded by each slot's LENGTH against the dense read
    (``Decoder._lane_attn``) on the same stored rows, at the lengths
    that matter: 0 (a slot that holds no request, at a stale position:
    finite output, no row counted, and none of its rows — all NaN here
    — computed on), the shortest a chunk allows (``C`` rows), a block
    edge, the whole cache; ``Hkv = H`` at D=64 and a GQA group of 4 at
    D=128; bf16 rows and int8 rows with their side scales."""
    import types

    from mxnet_tpu.ops.pallas_kernels import (paged_attention,
                                              paged_rows_fetched)
    from mxnet_tpu.parallel.decode import fold_heads

    h, kv, d = heads
    c, l_, bk = chunk, 32, 8
    rng = np.random.RandomState(31)
    int8 = cache == "int8"
    qdt = jnp.float32 if int8 else jnp.bfloat16
    #           dead      shortest  block edge  mid        whole cache
    pos = np.array([l_ - c - 3, 0, 16 - c, 21 - c, l_ - c], np.int32)
    lens = np.where(np.arange(5) == 0, 0, pos + c).astype(np.int32)
    s_ = len(pos)
    q = jnp.asarray(rng.randn(s_, c, h, d), qdt)
    k = rng.randn(s_, l_, kv, d).astype(np.float32)
    v = rng.randn(s_, l_, kv, d).astype(np.float32)
    if int8:
        k8, ks = Decoder._quantize_rows(jnp.asarray(k))
        v8, vs = Decoder._quantize_rows(jnp.asarray(v))
        entry = (fold_heads(k8), ks, fold_heads(v8), vs)
        scales = dict(k_scale=ks, v_scale=vs)
    else:
        entry = (fold_heads(jnp.asarray(k, jnp.bfloat16)),
                 fold_heads(jnp.asarray(v, jnp.bfloat16)))
        scales = {}
    want = np.asarray(Decoder._lane_attn(
        types.SimpleNamespace(_cache_int8=int8), q, entry,
        jnp.asarray(pos), kv), np.float32)
    # the dead slot's rows belong to nobody: poison them (floats only;
    # an int8 row has no NaN, its scales do)
    poisoned = tuple(
        buf.at[0].set(jnp.nan) if jnp.issubdtype(buf.dtype, jnp.floating)
        else buf for buf in entry)
    ck, cv = poisoned[0], poisoned[2 if int8 else 1]
    if int8:
        scales = dict(k_scale=poisoned[1], v_scale=poisoned[3])
    got = np.asarray(paged_attention(
        q, ck, cv, pos, kv_heads=kv, lens=lens, block_k=bk, **scales),
        np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[0], 0.0)
    tol = 1e-5 if int8 else 2e-2     # bf16: p is rounded before / after
    np.testing.assert_allclose(got[1:], want[1:], rtol=tol, atol=tol)
    # rows counted: each length rounded up to whole blocks, none for
    # the dead slot
    assert int(paged_rows_fetched(lens, l_, bk)) == int(
        sum(-(-int(n) // bk) * bk for n in lens))
    assert int(paged_rows_fetched(lens[:1], l_, bk)) == 0


def _one_slot_walks(dec, caches, pos, tokens):
    """The oracle of the slot walk, written here: one ``_run`` a slot
    at ITS scalar position on that slot's rows (the dense read of the
    offline step). Returns (logits [S, C, V], the slots' caches)."""
    logits, subs = [], []
    for s in range(tokens.shape[0]):
        sub = Decoder.slot_slice(caches, jnp.int32(s))
        lg, sub = dec._run(dec._params, dec._aux, sub, int(pos[s]),
                           tokens[s:s + 1])
        logits.append(np.asarray(lg)[0])
        subs.append(sub)
    return np.stack(logits), subs


def test_run_slots_paged_matches_dense_mixed_positions(lm):
    """Decoder level: ``_run_slots`` (the batched walk + kernel)
    against a loop of one-slot dense walks at mixed per-slot
    positions, decode width AND verify width — logits match to f32
    tolerance, argmax exactly (greedy byte-identity's microscopic
    form). Composes with rope via the GQA+rope symbol."""
    rng = np.random.RandomState(3)
    sym = _lm(pos_encoding="rope", num_kv_heads=1)
    params = _init_params(sym, rng)
    dec = Decoder(sym, params, max_len=T)
    S = 3
    caches = dec.init_cache(S)
    # fill every slot with the same 8-token prefix (one compile), then
    # step at MIXED per-slot positions so the block bound differs per
    # lane
    toks = jnp.asarray(rng.randint(0, VOCAB, (S, 8)), jnp.int32)
    walk = jax.jit(lambda c, p, t: dec._run_slots(
        dec._params, dec._aux, c, p, t))
    _, caches = walk(caches, jnp.zeros((S,), jnp.int32), toks)
    pos = jnp.asarray([4, 2, 7], jnp.int32)
    step = jnp.asarray(rng.randint(0, VOCAB, (S, 1)), jnp.int32)
    ld, subs = _one_slot_walks(dec, caches, pos, step)
    lp, cp = walk(Decoder.clone_cache(caches), pos, step)
    np.testing.assert_allclose(ld, np.asarray(lp), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(ld.argmax(-1),
                                  np.asarray(lp).argmax(-1))
    # the rows written by both walks are identical (same write math)
    for s_, sub in enumerate(subs):
        for a, b in zip(jax.tree_util.tree_leaves(sub),
                        jax.tree_util.tree_leaves(cp)):
            np.testing.assert_allclose(np.asarray(a)[0], np.asarray(b)[s_],
                                       rtol=1e-6, atol=1e-6)
    # verify-width chunk [S, 3] at mixed positions
    chunk = jnp.asarray(rng.randint(0, VOCAB, (S, 3)), jnp.int32)
    ldc, _ = _one_slot_walks(dec, caches, pos, chunk)
    lpc, _ = walk(Decoder.clone_cache(caches), pos, chunk)
    np.testing.assert_allclose(ldc, np.asarray(lpc), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(ldc.argmax(-1),
                                  np.asarray(lpc).argmax(-1))


def test_run_slots_paged_int8_tolerance(int8_dec):
    """int8 flavor at the decoder level: the kernel applies the side
    scales in-kernel; logits match the one-slot dense walks (the same
    scales on scores and weights) within the quantized-cache
    tolerance, argmax exactly on this config."""
    dec = int8_dec
    S = 2
    rng = np.random.RandomState(5)
    caches = dec.init_cache(S)
    toks = jnp.asarray(rng.randint(0, VOCAB, (S, 6)), jnp.int32)
    walk = jax.jit(lambda c, p, t: dec._run_slots(
        dec._params, dec._aux, c, p, t))
    _, caches = walk(caches, jnp.zeros((S,), jnp.int32), toks)
    pos = jnp.asarray([3, 5], jnp.int32)
    step = jnp.asarray(rng.randint(0, VOCAB, (S, 1)), jnp.int32)
    ld, _ = _one_slot_walks(dec, caches, pos, step)
    lp, _ = walk(Decoder.clone_cache(caches), pos, step)
    np.testing.assert_allclose(ld, np.asarray(lp), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(ld.argmax(-1),
                                  np.asarray(lp).argmax(-1))


# -- the read follows the cache kind ------------------------------------

def _kind_decoder(kind):
    """(decoder, whether its slot walk takes the bounded read)."""
    rng = np.random.RandomState(31)
    if kind == "cca":
        from mxnet_tpu.models import get_zaya_lm
        sym = get_zaya_lm(VOCAB, 1, 16, 4, 2, 8, 2, 16, 8, rotary_dim=4,
                          impl="dense")
        return Decoder(sym, _init_params(sym, rng), max_len=T), True
    lm_kw, dec_kw = {
        "linear": ({}, {}),
        "gqa_rope": (dict(pos_encoding="rope", num_kv_heads=1), {}),
        "int8_kv": ({}, dict(cache_dtype="int8")),
        "ring": (dict(window=6, pos_encoding="rope"), {}),
        "moe": (dict(num_experts=2, moe_top_k=1), {}),
    }[kind]
    sym = _lm(**lm_kw)
    return (Decoder(sym, _init_params(sym, rng), max_len=T, **dec_kw),
            kind != "ring")


@pytest.mark.parametrize("kind", ["linear", "gqa_rope", "int8_kv", "ring",
                                  "cca", "moe"])
def test_read_follows_the_cache_kind(kind):
    """The table above ``Decoder._cached_mha``, by what is traced: the
    bounded read (a ``pallas_call``) is in the slot walk exactly where
    the cached nodes hold linear rows (MultiHeadAttention over a linear
    cache, CCAttention), and never in the offline step. No option
    chooses."""
    dec, bounded = _kind_decoder(kind)
    S = 2
    i32 = jnp.int32
    walk = jax.make_jaxpr(lambda c, p, t: dec._run_slots(
        dec._params, dec._aux, c, p, t))(
        dec.init_cache(S), jnp.zeros((S,), i32), jnp.zeros((S, 1), i32))
    assert ("pallas_call" in str(walk)) == bounded
    offline = jax.make_jaxpr(lambda c, p, t: dec._run(
        dec._params, dec._aux, c, p, t))(
        dec.init_cache(S), i32(3), jnp.zeros((S, 1), i32))
    assert "pallas_call" not in str(offline)


@pytest.mark.parametrize("rows,row_bytes,want", [
    (1024, 4096, 256), (2048, 512, 1024), (2048, 2048, 512),
    (2048, 4096, 256), (1000, 4096, 8), (1001, 4096, 1001)])
def test_default_paged_block_k_from_shapes(rows, row_bytes, want):
    """The block is a pure function of the stored shapes: the largest
    listed divisor of the rows within 1 MB a block, else the rows."""
    from mxnet_tpu.ops.pallas_kernels import default_paged_block_k
    assert default_paged_block_k(rows, row_bytes) == want


# -- the engine gauntlet ----------------------------------------------

def test_engine_paged_identity_gauntlet(lm, paged_engine):
    """Greedy serving outputs byte-identical between the engine (the
    bounded read) and the offline ``Decoder.generate``, which reads
    densely, across the identity gauntlet: prefix-cache hits +
    eviction, chunked prefill, speculation on (the accepting prompt),
    steps_per_round>1, mixed admission — and the compile contract is
    unchanged."""
    sym, params, dec = lm
    rng = np.random.RandomState(13)
    eng = paged_engine
    assert eng._attn_pool_rows                # the read is bounded
    base = rng.randint(0, VOCAB, (7,))
    cases = {
        "miss_long": (base, 3),
        "prefix_of": (base[:4].copy(), 6),
        "partial": (np.concatenate([base[:4],
                                    rng.randint(0, VOCAB, (3,))]), 3),
        "unrelated": (rng.randint(0, VOCAB, (2,)), 5),
        "full_dup": (base.copy(), 3),
        "accepting": (np.array([0, 3, 3]), 13),   # n-gram drafts land
        "beyond_bucket": (rng.randint(0, VOCAB, (10,)), 3),
    }
    rs = {k: eng.submit(*v) for k, v in cases.items()}
    eng.serve_forever()
    for k, (p, n) in cases.items():
        np.testing.assert_array_equal(rs[k].result(), _oracle(dec, p, n),
                                      err_msg=k)
    assert_compile_contract(eng)
    assert eng.stats["prefix_hits"] >= 1
    assert eng.stats["prefill_chunks"] > len(cases)
    assert eng.stats["spec_rounds"] >= 1
    assert eng.stats["spec_accepted"] >= 1
    assert eng.idle


def test_engine_restores_a_snapshot_with_a_stale_attn_impl_key(
        lm, paged_engine):
    """A snapshot written by an older tree names the read it took
    (``"attn_impl"``): restore() reads the key and ignores it, and
    continues byte-identically (mid-flight crash point, prefix cache +
    chunking + speculation still on)."""
    sym, params, dec = lm
    rng = np.random.RandomState(17)
    eng = paged_engine
    p1 = rng.randint(0, VOCAB, (4,))
    p2 = np.array([0, 3, 3])
    r1 = eng.submit(p1, max_tokens=6)
    r2 = eng.submit(p2, max_tokens=13)
    for _ in range(3):
        eng.step()                       # mid-flight
    snap = eng.snapshot()
    assert "attn_impl" not in snap["engine"]
    snap["engine"]["attn_impl"] = "dense"
    eng2, handles = InferenceEngine.restore(snap, eng._dec)
    eng2.serve_forever()
    np.testing.assert_array_equal(handles[r1.id].result(),
                                  _oracle(dec, p1, 6))
    np.testing.assert_array_equal(handles[r2.id].result(),
                                  _oracle(dec, p2, 13))
    # drain the module engine back to idle for later tests
    eng.serve_forever()
    assert eng.idle


def test_engine_windowed_ring_takes_its_walk(lm, recwarn):
    """Ring flavor: a windowed RING stores rows at wrapped positions,
    so the bounded read has no meaning there. The decoder and the
    engine build without a warning, the slot walk is the ring's own,
    and the engine matches the offline oracle."""
    rng = np.random.RandomState(19)
    sym = _lm(window=6, pos_encoding="rope")
    params = _init_params(sym, rng)
    dec = Decoder(sym, params, max_len=T)
    eng = InferenceEngine(dec, slots=2, prefill_buckets=(4, 8),
                          prefix_cache_mb=0)
    assert not recwarn.list
    assert not dec._slots_batched and not eng._attn_pool_rows
    p = rng.randint(0, VOCAB, (5,))
    r = eng.submit(p, max_tokens=8)
    eng.serve_forever()
    np.testing.assert_array_equal(r.result(), _oracle(dec, p, 8))


# -- satellite: dense _read_cache clamp --------------------------------

def test_read_cache_static_clamp_value_identity(int8_dec):
    """Satellite fix: the dense path's whole-cache dequant/gather is
    clamped to the max live row where the dispatch position is STATIC
    (offline generate/beam prefill at pos 0) — `_run` with a python-int
    pos must produce value-identical logits to the traced-pos program
    that reads (and masks) all max_len rows. int8 config: the clamp
    skips dequantizing dead rows entirely."""
    dec = int8_dec
    rng = np.random.RandomState(29)
    toks = jnp.asarray(rng.randint(0, VOCAB, (1, 5)), jnp.int32)
    # python-int pos=0: the clamp applies (limit = 5 live rows)
    want_logits, _ = dec._run(dec._params, dec._aux, dec.init_cache(1),
                              0, toks)
    # traced pos: no static bound — the full masked read
    full = jax.jit(lambda c, p, t: dec._run(dec._params, dec._aux, c,
                                            p, t))
    got_logits, _ = full(dec.init_cache(1), jnp.int32(0), toks)
    np.testing.assert_allclose(np.asarray(want_logits),
                               np.asarray(got_logits),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(want_logits).argmax(-1),
                                  np.asarray(got_logits).argmax(-1))


@pytest.mark.parametrize("stored", [576, 640])
@pytest.mark.parametrize("chunk", [1, 3])
def test_latent_read_matches_the_plain_form(chunk, stored):
    """The latent read in interpret mode at a row width of 576 (512
    values + 64 rotary, not whole lane tiles), stored as it is and padded
    to 640 as the decoder stores it: one block of rows serves scores
    (all 576 columns) and values (the first 512); a dead slot emits zeros
    and its (poisoned) rows are never read; lanes past 576 are not
    contracted."""
    from mxnet_tpu.ops.pallas_kernels import latent_paged_attention
    s_, h, w, r, l_ = 4, 8, 576, 512, 64
    rng = np.random.default_rng(chunk)
    q = jnp.asarray(rng.normal(size=(s_, chunk, h, w)) * 0.1, jnp.float32)
    rows = rng.normal(size=(s_, l_, stored)).astype(np.float32)
    rows[1] = np.nan                              # nobody's rows
    rows[:, :, w:] = 1e3                          # a store's padding
    rows = jnp.asarray(rows)
    pos = jnp.asarray([5, 40, 33, l_ - chunk], jnp.int32)
    lens = jnp.where(jnp.arange(s_) == 1, 0, pos + chunk)
    with jax.default_matmul_precision("highest"):
        got = latent_paged_attention(q, rows, pos, v_width=r, scale=0.3,
                                     lens=lens, block_k=16)
        sc = jnp.einsum("schw,slw->shcl", q, rows[..., :w]) * 0.3
        qpos = pos[:, None] + jnp.arange(chunk)[None]
        mask = jnp.arange(l_)[None, None, None] <= qpos[:, None, :, None]
        want = jnp.einsum(
            "shcl,slr->schr",
            jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1),
            rows[..., :r])
    assert got.shape == (s_, chunk, h, r)
    live = np.asarray([0, 2, 3])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-6)
    assert np.array_equal(np.asarray(got)[1], np.zeros((chunk, h, r)))
    with pytest.raises(ValueError, match="stored cache"):
        latent_paged_attention(q, rows[..., :500], pos, v_width=r, scale=1.0)
