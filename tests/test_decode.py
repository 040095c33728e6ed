"""KV-cache decoder (parallel/decode.py): the incremental program derived
from the Symbol graph must match the full dense forward bit-for-bit in
what it argmaxes — the oracle is the ordinary training graph itself
(make_graph_fn), so any drift between cached and full attention math
fails here."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder, make_graph_fn

VOCAB, LAYERS, EMBED, HEADS = 17, 2, 16, 2


def _lm(impl="dense", **kw):
    return get_transformer_lm(VOCAB, num_layers=LAYERS, embed_dim=EMBED,
                              num_heads=HEADS, impl=impl, **kw)


def _init_params(sym, seq_len, batch, rng):
    shapes = {"data": (batch, seq_len)}
    if "softmax_label" in sym.list_arguments():
        shapes["softmax_label"] = (batch, seq_len)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    return {n: jnp.asarray(rng.uniform(-0.3, 0.3, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}


def _full_logits(sym, params, tokens):
    """Oracle: full forward of the logits head on the whole sequence."""
    logits_sym = sym.get_internals()["lm_head_output"]
    fn = make_graph_fn(logits_sym)
    args = [params[n] if n != "data" else jnp.asarray(tokens, jnp.float32)
            for n in logits_sym.list_arguments()]
    outs, _ = fn(args, [], False, jax.random.PRNGKey(0))
    return np.asarray(outs[0])  # [B, T, V]


def test_decode_matches_full_forward():
    """Greedy generate == iterated full-forward argmax, and the cached
    logits equal the full-forward logits at every decoded position."""
    rng = np.random.RandomState(0)
    T = 12
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)

    prompt = rng.randint(0, VOCAB, (2, 4))
    out = np.asarray(dec.generate(prompt, num_steps=6))
    assert out.shape == (2, 10)
    np.testing.assert_array_equal(out[:, :4], prompt)

    # oracle: grow the sequence one token at a time with FULL forwards
    seq = prompt.copy()
    for _ in range(6):
        logits = _full_logits(sym, params, np.pad(
            seq, ((0, 0), (0, T - seq.shape[1]))))
        nxt = logits[:, seq.shape[1] - 1].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(seq.dtype)], 1)
    np.testing.assert_array_equal(out, seq)


def test_decode_logits_close_to_full():
    """prefill+step logits agree numerically with the full forward."""
    rng = np.random.RandomState(1)
    T = 10
    sym = _lm()
    params = _init_params(sym, T, 3, rng)
    dec = Decoder(sym, params, max_len=T)

    toks = rng.randint(0, VOCAB, (3, T))
    want = _full_logits(sym, params, toks)

    caches = dec.init_cache(3)
    got_pre, caches = dec.prefill(caches, toks[:, :6])
    np.testing.assert_allclose(np.asarray(got_pre), want[:, :6],
                               rtol=1e-5, atol=1e-5)
    pos = 6
    for t in range(6, T):
        logits, caches = dec.step(caches, pos, toks[:, t])
        np.testing.assert_allclose(np.asarray(logits), want[:, t],
                                   rtol=1e-5, atol=1e-5)
        pos += 1


def test_decode_loss_headed_and_flash_symbol():
    """Loss-headed symbols re-head at the logits automatically, and the
    decoder is impl-agnostic (flash trains, cached-dense decodes)."""
    rng = np.random.RandomState(2)
    T = 8
    plain = _lm()
    for kw in (dict(), dict(loss_layout="ce")):
        sym = get_transformer_lm(VOCAB, num_layers=LAYERS,
                                 embed_dim=EMBED, num_heads=HEADS,
                                 impl="flash", **kw)
        params = _init_params(sym, T, 2, rng)
        dec = Decoder(sym, params, max_len=T)
        prompt = rng.randint(0, VOCAB, (2, 3))
        out = np.asarray(dec.generate(prompt, num_steps=4))
        # same params through the plain dense graph give the same tokens
        oracle = Decoder(plain, params, max_len=T)
        np.testing.assert_array_equal(
            out, np.asarray(oracle.generate(prompt, num_steps=4)))


def test_decode_sampling_and_determinism():
    rng = np.random.RandomState(3)
    T = 8
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    prompt = rng.randint(0, VOCAB, (2, 2))
    k = jax.random.PRNGKey(7)
    a = np.asarray(dec.generate(prompt, 5, rng=k, temperature=1.0))
    b = np.asarray(dec.generate(prompt, 5, rng=k, temperature=1.0))
    np.testing.assert_array_equal(a, b)  # same key, same draw
    c = np.asarray(dec.generate(prompt, 5, rng=jax.random.PRNGKey(8),
                                temperature=1.0))
    assert a.shape == c.shape == (2, 7)
    assert (a >= 0).all() and (a < VOCAB).all()


def test_decode_errors():
    rng = np.random.RandomState(4)
    sym = _lm()
    params = _init_params(sym, 8, 1, rng)

    # max_len beyond the trained positional table
    with pytest.raises(mx.MXNetError, match="max_len"):
        Decoder(sym, params, max_len=64)

    # prompt + steps beyond max_len
    dec = Decoder(sym, params, max_len=8)
    with pytest.raises(mx.MXNetError, match="exceeds max_len"):
        dec.generate(np.zeros((1, 5), np.int64), num_steps=4)

    # non-causal attention refuses to decode
    import mxnet_tpu.symbol as S
    d = S.Variable("data")
    e = S.Embedding(data=d, input_dim=VOCAB, output_dim=EMBED,
                    name="embed")
    att = S.MultiHeadAttention(
        data=e, qkv_weight=S.Variable("a_qkv_weight"),
        qkv_bias=S.Variable("a_qkv_bias"),
        out_weight=S.Variable("a_proj_weight"),
        out_bias=S.Variable("a_proj_bias"),
        num_heads=HEADS, causal=False, impl="dense", name="a")
    head = S.FullyConnected(data=att, num_hidden=VOCAB, flatten=False,
                            name="lm_head")
    ncp = {"embed_weight": jnp.zeros((VOCAB, EMBED)),
           "a_qkv_weight": jnp.zeros((3 * EMBED, EMBED)),
           "a_qkv_bias": jnp.zeros((3 * EMBED,)),
           "a_proj_weight": jnp.zeros((EMBED, EMBED)),
           "a_proj_bias": jnp.zeros((EMBED,)),
           "lm_head_weight": jnp.zeros((VOCAB, EMBED)),
           "lm_head_bias": jnp.zeros((VOCAB,))}
    with pytest.raises(mx.MXNetError, match="non-causal"):
        Decoder(head, ncp, max_len=4)

    # unsupported (non-positionwise) op refuses loudly
    conv = S.Convolution(data=S.Variable("data"), num_filter=2,
                         kernel=(1, 1), name="c",
                         weight=S.Variable("c_weight"),
                         bias=S.Variable("c_bias"))
    with pytest.raises(mx.MXNetError, match="position-wise"):
        Decoder(conv, {"c_weight": jnp.zeros((2, 1, 1, 1)),
                       "c_bias": jnp.zeros((2,))}, max_len=4)


def test_decode_step_prefill_bounds():
    """step()/prefill() refuse positions past the cache end —
    dynamic_update_slice would silently clamp and overwrite the last
    K/V slot otherwise."""
    rng = np.random.RandomState(11)
    T = 8
    sym = _lm()
    params = _init_params(sym, T, 1, rng)
    dec = Decoder(sym, params, max_len=T)

    with pytest.raises(mx.MXNetError, match="exceeds max_len"):
        dec.prefill(dec.init_cache(1), np.zeros((1, T + 1), np.int64))

    caches = dec.init_cache(1)
    _, caches = dec.prefill(caches, np.zeros((1, T), np.int64))
    with pytest.raises(mx.MXNetError, match="outside the cache"):
        dec.step(caches, T, np.zeros((1,), np.int64))
    with pytest.raises(mx.MXNetError, match="outside the cache"):
        dec.step(caches, -1, np.zeros((1,), np.int64))


@pytest.mark.parametrize("max_len", [1024, 2000])
def test_offline_step_long_cache_matches_full_forward(max_len):
    """The offline step reads densely at every ``max_len`` (at 1024 the
    old "auto" picked a blocked read, at 2000 it did not): its logits
    match the full forward at positions on both sides of a 128-row
    edge, early in the cache and late."""
    rng = np.random.RandomState(12)
    sym = get_transformer_lm(VOCAB, num_layers=1, embed_dim=EMBED,
                             num_heads=HEADS, impl="dense",
                             seq_len=max_len)
    params = _init_params(sym, max_len, 1, rng)
    dec = Decoder(sym, params, max_len=max_len)
    toks = rng.randint(0, VOCAB, (1, max_len))
    want = _full_logits(sym, params, toks)
    late = (max_len - 8) // 128 * 128   # the last edge with room after
    for start in (126, late - 2):
        _, caches = dec.prefill(dec.init_cache(1), toks[:, :start])
        for pos in range(start, start + 4):
            logits, caches = dec.step(caches, pos, toks[:, pos])
            np.testing.assert_allclose(np.asarray(logits), want[:, pos],
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=str(pos))


def test_decode_cache_block_keyword_refused():
    """``cache_block`` is accepted as ``None`` only (the benchmark's
    driver still passes it); any value names the removal."""
    rng = np.random.RandomState(13)
    T = 12
    sym = _lm()
    params = _init_params(sym, T, 1, rng)
    assert Decoder(sym, params, max_len=T, cache_block=None).max_len == T
    with pytest.raises(mx.MXNetError, match="cache_block=4.*removed"):
        Decoder(sym, params, max_len=T, cache_block=4)


def test_decode_int8_kv_cache():
    """cache_dtype="int8": per-(position, head)-row symmetric quantized
    K/V. Not exact, but the error is bounded by the row amax/254 per
    element, so step logits on this O(1)-logit model stay within a
    small absolute band of the exact decoder, and generate/clone_cache
    compose with the 4-leaf cache entries."""
    rng = np.random.RandomState(21)
    T = 16
    sym = _lm()
    params = _init_params(sym, T, 2, rng)

    toks = rng.randint(0, VOCAB, (2, T))
    want = _full_logits(sym, params, toks)
    q = Decoder(sym, params, max_len=T, cache_dtype="int8")
    caches = q.init_cache(2)
    assert len(caches[0]) == 4 and caches[0][0].dtype == jnp.int8
    got, caches = q.prefill(caches, toks[:, :8])
    np.testing.assert_allclose(np.asarray(got), want[:, :8], atol=0.05)
    for pos in range(8, T):
        logits, caches = q.step(caches, pos, toks[:, pos])
        np.testing.assert_allclose(np.asarray(logits), want[:, pos],
                                   atol=0.05)

    dec = Decoder(sym, params, max_len=T, cache_dtype="int8")
    prompt = rng.randint(0, VOCAB, (2, 4))
    out, caches = dec.generate(prompt, num_steps=4, return_cache=True)
    out = np.asarray(out)
    assert out.shape == (2, 8)
    np.testing.assert_array_equal(out[:, :4], prompt)
    branch = Decoder.clone_cache(caches)
    l1, _ = dec.step(branch, 7, out[:, -1])
    l2, _ = dec.step(caches, 7, out[:, -1])
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))

    seqs, scores = dec.beam_search(prompt, num_steps=3, beam_size=2)
    assert np.asarray(seqs).shape == (2, 2, 7)

    with pytest.raises(mx.MXNetError, match="cache_dtype"):
        Decoder(sym, params, max_len=T, cache_dtype="int32")
    with pytest.raises(mx.MXNetError, match="cache_dtype"):
        Decoder(sym, params, max_len=T, cache_dtype="not-a-dtype")
    # the dtype OBJECT is as good as the string
    assert Decoder(sym, params, max_len=T,
                   cache_dtype=np.int8)._cache_int8


def _gqa_kv_cache_case(h, kv, extra, rng):
    """One grouped-query decode identity case: kv-head-sized cache,
    logits vs the iterated full-forward oracle at every step, int8
    prefill within tolerance."""
    T = 12
    sym = get_transformer_lm(VOCAB, num_layers=2, embed_dim=EMBED,
                             num_heads=h, impl="dense",
                             num_kv_heads=kv, **extra)
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    assert dec.init_cache(2)[0][0].shape == (2, T, kv * (EMBED // h))

    toks = rng.randint(0, VOCAB, (2, T))
    want = _full_logits(sym, params, toks)
    caches = dec.init_cache(2)
    got, caches = dec.prefill(caches, toks[:, :6])
    np.testing.assert_allclose(np.asarray(got), want[:, :6],
                               rtol=1e-5, atol=1e-5)
    for pos in range(6, T):
        logits, caches = dec.step(caches, pos, toks[:, pos])
        np.testing.assert_allclose(np.asarray(logits), want[:, pos],
                                   rtol=1e-5, atol=1e-5, err_msg=str(pos))

    q8 = Decoder(sym, params, max_len=T, cache_dtype="int8")
    got8, _ = q8.prefill(q8.init_cache(2), toks[:, :6])
    np.testing.assert_allclose(np.asarray(got8), want[:, :6],
                               atol=0.05)


def test_decode_gqa_kv_cache_core():
    """Grouped-query attention decodes against a kv-head-sized cache:
    the h=4/kv=2 + rope case — the regime where BOTH the kv axis and
    the group axis are non-trivial, which is what catches a
    (g, kv)-vs-(kv, g) head-order mixup in the grouped einsums — stays
    tier-1; the full (heads, kv) sweep moved to the slow sweep (PR 11
    budget relief, PR 4/5/9/10 precedent; further tier-1 GQA coverage:
    test_transformer_gqa_lm_trains and test_paged_attention's
    GQA+rope decoder-level identity)."""
    _gqa_kv_cache_case(4, 2, dict(pos_encoding="rope"),
                       np.random.RandomState(31))


@pytest.mark.slow
def test_decode_gqa_kv_cache():
    """The remaining (heads, kv) grid: kv=1 (MQA), kv==h (degenerate),
    h=4/kv=2 plain, MQA+rope — each the same oracle gauntlet as the
    tier-1 core case."""
    rng = np.random.RandomState(31)
    for h, kv, extra in [(HEADS, 1, {}), (HEADS, 2, {}), (4, 2, {}),
                         (HEADS, 1, dict(pos_encoding="rope"))]:
        _gqa_kv_cache_case(h, kv, extra, rng)


@pytest.mark.slow
def test_decode_sliding_window_ring_cache():
    """Sliding-window decode: the cache is a WINDOW-slot ring buffer
    (O(window) memory regardless of generation length), and the
    derived program — chunked prefill through the read-before-write
    ring, then single-token steps — matches the training graph's own
    windowed forward exactly. Composes with rope, GQA, and int8.

    Slow sweep (tier-1 budget, PR 10): ~30s of compiles across the 4
    flavor cases; windowed decode keeps tier-1 coverage via
    test_serving's window-flavor test (engine byte-compared against
    this same offline windowed generate, rope included) and
    test_window_prefill_pad_rows_do_not_corrupt_ring (exact ring K/V
    and position equality against the dense forward)."""
    rng = np.random.RandomState(41)
    T, W = 16, 4
    cases = [dict(), dict(pos_encoding="rope"),
             dict(num_kv_heads=1), dict(pos_encoding="rope",
                                        num_kv_heads=1)]
    for extra in cases:
        sym = get_transformer_lm(VOCAB, num_layers=2, embed_dim=EMBED,
                                 num_heads=HEADS, impl="dense",
                                 window=W, **extra)
        params = _init_params(sym, T, 2, rng)
        dec = Decoder(sym, params, max_len=T)
        caches = dec.init_cache(2)
        kv = extra.get("num_kv_heads", 0) or HEADS
        assert caches[0][0].shape == (2, W, kv * (EMBED // HEADS))
        assert caches[0][-1].shape == (2, W)  # slot-position buffer

        toks = rng.randint(0, VOCAB, (2, T))
        want = _full_logits(sym, params, toks)
        # prefill a chunk LONGER than the window (exercises the
        # tail-write path), then step through the rest
        got, caches = dec.prefill(caches, toks[:, :9])
        np.testing.assert_allclose(np.asarray(got), want[:, :9],
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=str(extra))
        for pos in range(9, T):
            logits, caches = dec.step(caches, pos, toks[:, pos])
            np.testing.assert_allclose(np.asarray(logits), want[:, pos],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg="%s pos %d" % (extra, pos))

        # greedy generate equals iterated full-forward argmax
        prompt = rng.randint(0, VOCAB, (2, 3))
        out = np.asarray(dec.generate(prompt, num_steps=8))
        seq = prompt.copy()
        for _ in range(8):
            logits = _full_logits(sym, params, np.pad(
                seq, ((0, 0), (0, T - seq.shape[1]))))
            nxt = logits[:, seq.shape[1] - 1].argmax(-1)
            seq = np.concatenate([seq, nxt[:, None].astype(seq.dtype)], 1)
        np.testing.assert_array_equal(out, seq, err_msg=str(extra))

    # int8 ring: close, and beam search runs on the 5-leaf entries
    sym = get_transformer_lm(VOCAB, num_layers=2, embed_dim=EMBED,
                             num_heads=HEADS, impl="dense", window=W)
    params = _init_params(sym, T, 2, rng)
    q8 = Decoder(sym, params, max_len=T, cache_dtype="int8")
    toks = rng.randint(0, VOCAB, (2, T))
    want = _full_logits(sym, params, toks)
    got, caches = q8.prefill(q8.init_cache(2), toks[:, :9])
    np.testing.assert_allclose(np.asarray(got), want[:, :9], atol=0.05)
    seqs, scores = q8.beam_search(toks[:, :3], num_steps=4, beam_size=2)
    assert np.asarray(seqs).shape == (2, 2, 7)


def test_decode_int8_quantize_rows():
    """The quantizer is exact on rows already on the int8 grid and
    bounded by amax/254 elsewhere; zero rows round-trip to zero."""
    rng = np.random.RandomState(22)
    x = jnp.asarray(rng.uniform(-2, 2, (2, 3, 4, 8)).astype(np.float32))
    q, s = Decoder._quantize_rows(x)
    np.testing.assert_allclose(
        np.asarray(q, np.float32) * np.asarray(s)[..., None],
        np.asarray(x), atol=float(np.abs(np.asarray(x)).max()) / 254.0)
    grid = jnp.asarray([[-127.0, 64.0, 0.0, 1.0]]) * 0.03
    q, s = Decoder._quantize_rows(grid[None, None])
    np.testing.assert_allclose(
        np.asarray(q, np.float32) * np.asarray(s)[..., None],
        np.asarray(grid[None, None]), rtol=1e-6)
    q, s = Decoder._quantize_rows(jnp.zeros((1, 1, 1, 4)))
    assert np.all(np.asarray(q) == 0) and np.all(np.asarray(s) == 1.0)


def test_decode_rejects_rank3_batchnorm():
    """BatchNorm normalizes axis 1 — the time axis for [B, T, E] LM
    data — so it is NOT position-wise on rank-3 data; the decoder must
    refuse instead of broadcasting length-T moving stats into garbage."""
    import mxnet_tpu.symbol as S
    d = S.Variable("data")
    e = S.Embedding(data=d, input_dim=VOCAB, output_dim=EMBED,
                    name="embed")
    bn = S.BatchNorm(data=e, gamma=S.Variable("bn_gamma"),
                     beta=S.Variable("bn_beta"), name="bn")
    head = S.FullyConnected(data=bn, num_hidden=VOCAB, flatten=False,
                            name="lm_head")
    T = 6
    params = {"embed_weight": jnp.zeros((VOCAB, EMBED)),
              "bn_gamma": jnp.ones((T,)), "bn_beta": jnp.zeros((T,)),
              "lm_head_weight": jnp.zeros((VOCAB, EMBED)),
              "lm_head_bias": jnp.zeros((VOCAB,))}
    dec = Decoder(head, params, max_len=T,
                  aux_params={"bn_moving_mean": jnp.zeros((T,)),
                              "bn_moving_var": jnp.ones((T,))})
    with pytest.raises(mx.MXNetError, match="not position-wise"):
        dec.prefill(dec.init_cache(1), np.zeros((1, 3), np.int64))


def test_decode_moe_lm():
    """MoE blocks decode too (MoEFFN is position-wise)."""
    rng = np.random.RandomState(5)
    T = 8
    sym = get_transformer_lm(VOCAB, num_layers=1, embed_dim=EMBED,
                             num_heads=HEADS, impl="dense",
                             num_experts=2, moe_top_k=1)
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    prompt = rng.randint(0, VOCAB, (2, 3))
    out = np.asarray(dec.generate(prompt, num_steps=4))

    seq = prompt.copy()
    for _ in range(4):
        logits = _full_logits(sym, params, np.pad(
            seq, ((0, 0), (0, T - seq.shape[1]))))
        nxt = logits[:, seq.shape[1] - 1].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(seq.dtype)], 1)
    np.testing.assert_array_equal(out, seq)


def test_generate_resume():
    """return_cache=True resumption recipe (docstring): re-step the last
    returned token at its own position, then continue — the resumed
    continuation must equal one longer uninterrupted generate."""
    rng = np.random.RandomState(6)
    T = 14
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    prompt = rng.randint(0, VOCAB, (2, 3))
    P = prompt.shape[1]

    full = np.asarray(dec.generate(prompt, num_steps=8))

    short, caches = dec.generate(prompt, num_steps=4, return_cache=True)
    short = np.asarray(short)
    np.testing.assert_array_equal(short, full[:, :P + 4])
    seq = short
    pos = P + 4 - 1
    logits, caches = dec.step(caches, pos, seq[:, -1])
    for _ in range(4):
        nxt = np.asarray(jnp.argmax(logits, -1)).astype(np.int32)
        seq = np.concatenate([seq, nxt[:, None]], 1)
        pos += 1
        logits, caches = dec.step(caches, pos, nxt)
    np.testing.assert_array_equal(seq, full)


def test_decode_tp_sharded_params():
    """Multi-chip serving: tp-sharded parameters decode through the same
    jitted program (GSPMD partitions the cached-attention math; Megatron
    tp_rules shard QKV/FFN columns) and produce the same tokens as the
    single-device decoder."""
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models.transformer import tp_rules

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    rng = np.random.RandomState(7)
    T = 10
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    prompt = rng.randint(0, VOCAB, (2, 3))
    want = np.asarray(Decoder(sym, params, max_len=T)
                      .generate(prompt, num_steps=5))

    mesh = par.build_mesh({"tp": 2}, jax.devices()[:2])
    rules = par.ShardingRules(mesh, param_rules=tp_rules())
    sharded = {k: jax.device_put(v, rules.param_sharding(k, v.shape))
               for k, v in params.items()}
    got = np.asarray(Decoder(sym, sharded, max_len=T)
                     .generate(prompt, num_steps=5))
    np.testing.assert_array_equal(got, want)


def test_decoder_from_checkpoint(tmp_path):
    """FeedForward-format checkpoints decode without re-describing the
    model (Decoder.from_checkpoint)."""
    rng = np.random.RandomState(8)
    T = 8
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    prefix = str(tmp_path / "lm")
    mx.model.save_checkpoint(
        prefix, 3, sym,
        {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}, {})

    dec = Decoder.from_checkpoint(prefix, 3, max_len=T)
    prompt = rng.randint(0, VOCAB, (2, 2))
    want = np.asarray(Decoder(sym, params, max_len=T)
                      .generate(prompt, num_steps=4))
    np.testing.assert_array_equal(
        np.asarray(dec.generate(prompt, num_steps=4)), want)


def test_sampled_generate_auto_key_varies():
    """generate(rng=None, temperature>0) must not return identical
    'samples' on repeated calls (internal key advances)."""
    rng = np.random.RandomState(9)
    T = 10
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    prompt = rng.randint(0, VOCAB, (2, 2))
    draws = [np.asarray(dec.generate(prompt, 6, temperature=2.0))
             for _ in range(4)]
    assert any(not np.array_equal(draws[0], d) for d in draws[1:])


def test_clone_cache_branching():
    """Branch-from-one-prefix decoding: prefill once, clone, explore two
    continuations — each must match a from-scratch decode of its path."""
    rng = np.random.RandomState(10)
    T = 10
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    toks = rng.randint(0, VOCAB, (2, 4))

    caches = dec.init_cache(2)
    _, caches = dec.prefill(caches, toks[:, :3])
    branch = Decoder.clone_cache(caches)

    a = np.asarray(dec.step(caches, 3, toks[:, 3])[0])
    alt = (toks[:, 3] + 1) % VOCAB
    b = np.asarray(dec.step(branch, 3, alt)[0])

    want_a = _full_logits(sym, params, np.pad(toks, ((0, 0), (0, T - 4))))
    np.testing.assert_allclose(a, want_a[:, 3], rtol=1e-5, atol=1e-5)
    alt_seq = np.concatenate([toks[:, :3], alt[:, None]], 1)
    want_b = _full_logits(sym, params,
                          np.pad(alt_seq, ((0, 0), (0, T - 4))))
    np.testing.assert_allclose(b, want_b[:, 3], rtol=1e-5, atol=1e-5)


def _np_beam_search(sym, params, prompt, num_steps, k, T):
    """Independent numpy beam search driven by FULL forwards — the
    oracle for the incremental implementation's cache/bookkeeping."""
    B, P = prompt.shape
    beams = [[(prompt[b].tolist(), 0.0)] for b in range(B)]
    for step in range(num_steps):
        new = []
        for b in range(B):
            cand = []
            for seq, score in beams[b]:
                arr = np.zeros((1, T), np.int64)
                arr[0, :len(seq)] = seq
                logits = _full_logits(sym, params, arr)[0, len(seq) - 1]
                logits = logits.astype(np.float64)
                logp = logits - np.log(np.exp(
                    logits - logits.max()).sum()) - logits.max()
                for vtok in range(len(logp)):
                    cand.append((seq + [vtok], score + logp[vtok]))
            cand.sort(key=lambda c: -c[1])
            new.append(cand[:k])
        beams = new
    seqs = np.array([[c[0] for c in row] for row in beams])
    scores = np.array([[c[1] for c in row] for row in beams])
    return seqs, scores


def test_beam_search_matches_numpy_reference():
    """Incremental beam search == an independent full-forward numpy
    implementation (sequences exactly, scores numerically)."""
    rng = np.random.RandomState(13)
    T = 9
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    prompt = rng.randint(0, VOCAB, (2, 3))

    seqs, scores = dec.beam_search(prompt, num_steps=4, beam_size=3)
    want_seqs, want_scores = _np_beam_search(sym, params, prompt, 4, 3, T)
    np.testing.assert_array_equal(np.asarray(seqs), want_seqs)
    np.testing.assert_allclose(np.asarray(scores), want_scores,
                               rtol=1e-4, atol=1e-4)


def test_beam_size_one_is_greedy():
    rng = np.random.RandomState(14)
    T = 10
    sym = _lm()
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    prompt = rng.randint(0, VOCAB, (2, 2))
    greedy = np.asarray(dec.generate(prompt, num_steps=5))
    seqs, scores = dec.beam_search(prompt, num_steps=5, beam_size=1)
    np.testing.assert_array_equal(np.asarray(seqs)[:, 0], greedy)
    assert np.isfinite(np.asarray(scores)).all()


def test_beam_search_eos_freezes():
    """Beams that emit eos stop expanding: their score freezes and the
    remaining slots fill with token 0."""
    rng = np.random.RandomState(15)
    T = 10
    sym = _lm()
    params = _init_params(sym, T, 1, rng)
    dec = Decoder(sym, params, max_len=T)
    prompt = rng.randint(0, VOCAB, (1, 2))

    base_seqs, base_scores = dec.beam_search(prompt, 5, beam_size=VOCAB)
    # pick the eos id as the token the best beam emits at the first step
    eos = int(np.asarray(base_seqs)[0, 0, 2])
    seqs, scores = dec.beam_search(prompt, 5, beam_size=VOCAB,
                                   eos_id=eos)
    seqs, scores = np.asarray(seqs), np.asarray(scores)
    # some beam ends with eos followed by only pad zeros
    hit = [i for i in range(seqs.shape[1])
           if eos in seqs[0, i, 2:]]
    assert hit, seqs
    i = hit[0]
    e = list(seqs[0, i, 2:]).index(eos) + 2
    assert (seqs[0, i, e + 1:] == 0).all()
    assert np.isfinite(scores[0, i])


def test_decode_rope_matches_full_forward():
    """RoPE LM: the decoder's incremental rotation (cache stores
    post-rotation K at traced positions) must match the full forward's
    whole-sequence rotation exactly — greedy tokens AND logits."""
    rng = np.random.RandomState(16)
    T = 12
    sym = _lm(pos_encoding="rope")
    params = _init_params(sym, T, 2, rng)
    dec = Decoder(sym, params, max_len=T)
    assert "pos_embed" not in params  # rope has no table

    prompt = rng.randint(0, VOCAB, (2, 4))
    out = np.asarray(dec.generate(prompt, num_steps=6))
    seq = prompt.copy()
    for _ in range(6):
        logits = _full_logits(sym, params, np.pad(
            seq, ((0, 0), (0, T - seq.shape[1]))))
        nxt = logits[:, seq.shape[1] - 1].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(seq.dtype)], 1)
    np.testing.assert_array_equal(out, seq)

    toks = rng.randint(0, VOCAB, (2, T))
    want = _full_logits(sym, params, toks)
    caches = dec.init_cache(2)
    got, caches = dec.prefill(caches, toks[:, :5])
    np.testing.assert_allclose(np.asarray(got), want[:, :5],
                               rtol=1e-5, atol=1e-5)
    for t in range(5, T):
        logits, caches = dec.step(caches, t, toks[:, t])
        np.testing.assert_allclose(np.asarray(logits), want[:, t],
                                   rtol=1e-5, atol=1e-5)
