"""Granite 4.0-H's block on the normal path, against the plain reference of
``benchmark/families/granite.py`` at toy size, seeded weights, float32: the
full forward and prefill in pieces followed by decode, the chunked SSD
against the sequential recurrence, the decode step's kernel against the
recurrence on live slots, the state kind's three contracts for the second
mixer that keeps one (a padded bucket, a slot that is not live, a reused
slot), the engine end to end with slots reused, the refusals by name, and
the attention's ``scale`` reaching every read."""
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
TOL = 1e-4          # of the logits' spread
MAX_LEN, BUCKETS = 64, (8, 16)


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
                                      "granite-4.0-h-micro.json")))
    cfg.update(cfg.pop("toy"))
    fam = H.load_module("families", "granite")
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


def tokens(toy, shape, seed=3):
    return np.random.default_rng(seed).integers(
        0, toy[1]["vocab_size"], shape).astype(np.int32)


# -- program against reference ---------------------------------------------

def test_layer_pattern_and_cache_kinds(toy, decoder):
    """The kinds follow ``layer_types``; an attention entry is K and V
    rows of bias-free GQA heads, a Mamba-2 entry holds NO rows: a float32
    state [B, H, P, N] and the convolution's three rows of xBC."""
    _, cfg, _, _, _ = toy
    kinds = [n.spec.name for n in decoder._cached]
    assert kinds == ["Mamba2", "MultiHeadAttention", "Mamba2", "Mamba2"]
    assert decoder._mha[0].params["no_bias"]
    caches = decoder.init_cache(3)
    fw = cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        + 2 * cfg["mamba_d_state"]
    state, conv = caches[0]
    assert state.shape == (3, cfg["mamba_n_heads"], cfg["mamba_d_head"],
                           cfg["mamba_d_state"])
    assert state.dtype == jnp.float32 and conv.shape == (3, 3 * fw)
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    rows = (3, MAX_LEN, cfg["num_key_value_heads"] * d)
    assert [x.shape for x in caches[1]] == [rows, rows]
    assert len(decoder.row_buffers(caches)) == 1 and decoder.has_state
    assert not [n for n in decoder._params
                if n.endswith(("_qkv_bias", "_out_bias"))]


def test_full_forward_agrees_with_the_reference(toy, decoder):
    seqs = tokens(toy, (2, 50))
    ref = reference(toy, seqs)
    with jax.default_matmul_precision("highest"):
        got, _ = decoder.prefill(decoder.init_cache(2), seqs)
    assert np.abs(np.asarray(got) - ref).max() <= TOL * ref.std()


def test_executor_forward_agrees_with_the_reference(toy):
    """The ops' own full-sequence forward (the graph bound like any zoo
    model), not the decoder's cached walk."""
    _, _, sym, w, _ = toy
    seqs = tokens(toy, (2, 37), seed=5)
    ref = reference(toy, seqs)
    from mxnet_tpu.parallel.decode import _logits_symbol
    from mxnet_tpu.parallel.graph import make_graph_fn
    logits = _logits_symbol(sym)
    fn = make_graph_fn(logits)
    vals = dict(w, data=jnp.asarray(seqs))
    with jax.default_matmul_precision("highest"):
        outs, _ = fn([vals[n] for n in logits.list_arguments()], [], False,
                     jax.random.PRNGKey(0))
    got = np.asarray(outs[0])
    assert np.abs(got - ref).max() <= TOL * ref.std()


@pytest.mark.parametrize("pieces", [(50,), (16, 16, 7), (3, 8, 1, 20)])
def test_prefill_in_pieces_then_decode_agrees_with_the_reference(
        toy, decoder, pieces):
    """The prompt enters in pieces, the state carried from piece to
    piece, then every further token through a decode step: the logits at
    every position are the reference's one full forward."""
    seqs = tokens(toy, (2, 50), seed=11)
    ref = reference(toy, seqs)
    caches = decoder.init_cache(2)
    outs, at = [], 0
    with jax.default_matmul_precision("highest"):
        for n in pieces:
            lg, caches = decoder._step_jit(
                decoder._params, decoder._aux, caches, at,
                jnp.asarray(seqs[:, at:at + n]))
            outs.append(np.asarray(lg))
            at += n
        for t in range(at, seqs.shape[1]):
            lg, caches = decoder.step(caches, t, seqs[:, t])
            outs.append(np.asarray(lg)[:, None])
    got = np.concatenate(outs, axis=1)
    assert np.abs(got - ref).max() <= TOL * ref.std()


# -- the recurrence -----------------------------------------------------------

def _ssd_inputs(t, seed=0, b=2, h=3, p=4, n=8):
    rng = np.random.default_rng(seed)
    f = np.float32
    return (jnp.asarray(rng.normal(size=(b, h, p, n)).astype(f)),
            jnp.asarray(rng.normal(size=(b, t, h, p)).astype(f)),
            jnp.asarray(rng.normal(size=(b, t, n)).astype(f)),
            jnp.asarray(rng.normal(size=(b, t, n)).astype(f)),
            jnp.asarray(rng.uniform(0.001, 0.3, (b, t, h)).astype(f)),
            jnp.asarray(-rng.uniform(1.0, 8.0, (h,)).astype(f)))


@pytest.mark.parametrize("t,chunk", [(1, 16), (5, 16), (63, 16), (64, 16),
                                     (65, 16), (100, 32), (130, 256),
                                     (200, 64)])
def test_chunked_ssd_equals_the_sequential_recurrence(t, chunk):
    """The sequential recurrence is the definition; the chunked SSD form
    is held to it over lengths that are and are not whole chunks."""
    s0, x, b, c, dt, a = _ssd_inputs(t, seed=t)
    s1, y1 = A.ssd_sequential(s0, x, b, c, dt, a)
    s2, y2 = A.ssd_chunked(s0, x, b, c, dt, a, chunk)
    np.testing.assert_allclose(y2, y1, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-5, rtol=2e-5)


def test_chunked_ssd_across_a_piece_boundary():
    """Two pieces, the state handed from one to the next, equal one
    pass; the boundary falls inside a chunk."""
    s0, x, b, c, dt, a = _ssd_inputs(150, seed=9)
    s_all, y_all = A.ssd_sequential(s0, x, b, c, dt, a)
    cut = 87
    sa, ya = A.ssd_chunked(s0, x[:, :cut], b[:, :cut], c[:, :cut],
                           dt[:, :cut], a, 32)
    sb, yb = A.ssd_chunked(sa, x[:, cut:], b[:, cut:], c[:, cut:],
                           dt[:, cut:], a, 32)
    np.testing.assert_allclose(jnp.concatenate([ya, yb], 1), y_all,
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(sb, s_all, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("real", [1, 23, 39])
def test_padding_leaves_the_ssd_state(real):
    """Padded positions (``dt = 0``) leave the state of the last real
    one, whatever ``x``, B and C hold there."""
    s0, x, b, c, dt, a = _ssd_inputs(40, seed=4)
    pad = jnp.arange(40)[None, :, None] >= real
    s_pad, y_pad = A.ssd_chunked(s0, x, b, c, jnp.where(pad, 0.0, dt), a,
                                 16)
    s_real, y_real = A.ssd_sequential(s0, x[:, :real], b[:, :real],
                                      c[:, :real], dt[:, :real], a)
    np.testing.assert_allclose(s_pad, s_real, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(y_pad[:, :real], y_real, atol=2e-5,
                               rtol=2e-5)


# -- the decode step's kernel: live slots only, in place ---------------------

_STEP_CASES = {
    # lens of the slots, the slots flagged "starts at position 0", and
    # the widths (heads, P, N) where they are not (4, 8, 16)
    "some_live": ([7, 0, 0, 3, 0, 11], []),
    "none_live": ([0, 0, 0, 0, 0, 0], [2]),
    "all_live": ([5, 1, 9, 2, 64, 3], []),
    "position_0": ([1, 0, 1, 8, 0, 0], [0, 2, 4]),
    "not_live_first": ([0, 0, 4, 0, 6, 2], [4]),
    # three live slots of seven; six heads of 16, so y's rows hold six
    # heads (96 lanes) or, in blocks of two, two
    "odd_live": ([0, 5, 0, 2, 9, 0, 0], [3], (6, 16, 32)),
    # the cell's P and N: two heads of 64 to each 128-lane row of y
    "two_heads_a_row": ([4, 0, 7, 1, 0, 2], [3], (4, 64, 128)),
}


@pytest.mark.parametrize("block_h", [None, 2])
@pytest.mark.parametrize("case", sorted(_STEP_CASES))
def test_ssd_state_step_kernel_is_the_recurrence_for_the_live_slots(
        case, block_h):
    """``ssd_state_step`` against ``ssd_step`` on the same inputs: a live
    slot's state and output are the definition's, one flagged as
    starting at position 0 starts from zeros whatever it held (``inf``
    included), and a slot that is not live comes back bit for bit with
    zeros for an output, in the step with no live slot too and whatever
    the order of the lengths, the widths or the heads to a row of y."""
    lens, zeroed, *widths = _STEP_CASES[case]
    h, p, n = widths[0] if widths else (4, 8, 16)
    lens = np.asarray(lens, np.int32)
    fresh = np.zeros(len(lens), bool)
    fresh[zeroed] = True
    s0, x, b, c, dt, a = _ssd_inputs(1, seed=len(case), b=len(lens), h=h,
                                     p=p, n=n)
    x, b, c, dt = x[:, 0], b[:, 0], c[:, 0], dt[:, 0]
    live = lens > 0
    start = jnp.where((fresh & live)[:, None, None, None], 0, s0)
    want_s, want_y = A.ssd_step(start, x, b, c, dt, a)
    got_s, got_y = jax.jit(
        lambda *z: pk.ssd_state_step(*z, block_h=block_h))(
            s0, x, b, c, dt, a, jnp.asarray(lens), jnp.asarray(fresh))
    got_s, got_y = np.asarray(got_s), np.asarray(got_y)
    np.testing.assert_allclose(got_s[live], np.asarray(want_s)[live],
                               atol=2e-6)
    np.testing.assert_allclose(got_y[live], np.asarray(want_y)[live],
                               atol=2e-5)
    assert np.array_equal(got_s[~live], np.asarray(s0)[~live])
    assert not got_y[~live].any()
    if fresh[live].any():       # zeros, not the old state times its decay
        dirty = s0.at[np.flatnonzero(fresh & live)].set(jnp.inf)
        again, y_again = pk.ssd_state_step(
            dirty, x, b, c, dt, a, jnp.asarray(lens), jnp.asarray(fresh),
            block_h=block_h)
        assert np.array_equal(np.asarray(again)[live], got_s[live])
        assert np.array_equal(np.asarray(y_again)[live], got_y[live])


def _kernel_calls(jaxpr):
    """The ``pallas_call``s a program makes, a jaxpr that it calls (a
    kernel behind a ``jit`` of its own, traced once for every layer)
    counted at each call."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, ClosedJaxpr):
                    n += _kernel_calls(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    n += _kernel_calls(sub)
    return n


def test_state_step_kernel_runs_where_a_slot_walk_steps_one_position(
        toy, decoder):
    """One position under a slot walk's ``lens`` advances every Mamba-2
    layer's state through the kernel (beside the attention layer's
    bounded read); the same step without ``lens`` and a longer chunk run
    ``ssd_step`` / ``ssd_chunked``."""
    def calls(chunk, lens):
        caches = decoder.init_cache(2)
        pos = jnp.asarray([8, 8], jnp.int32)
        toks = jnp.zeros((2, chunk), jnp.int32)
        return _kernel_calls(jax.make_jaxpr(lambda c: decoder._run_slots(
            decoder._params, decoder._aux, c, pos, toks, lens=lens))(
                caches).jaxpr)

    lens = jnp.asarray([9, 0], jnp.int32)
    others = calls(1, None)             # the attention layer's read
    assert calls(1, lens) == others + len(decoder._ssm) == 4
    assert calls(3, lens + 2) == calls(3, None)
    p = decoder._ssm[0].params
    assert A.ssd_steps_in_place(p, 1, lens)
    assert not A.ssd_steps_in_place(p, 2, lens)
    assert not A.ssd_steps_in_place(p, 1, None)


# -- the state kind's contracts ---------------------------------------------

def _prefill(decoder, caches, toks, valid_len=None):
    fn = jax.jit(lambda c, t, v: decoder._run(
        decoder._params, decoder._aux, c, 0, t, valid_len=v))
    return fn(caches, jnp.asarray(toks), valid_len)


def _ssm_entries(decoder, caches):
    return [e for n, e in zip(decoder._cached, caches)
            if n.spec.name == "Mamba2"]


@pytest.mark.parametrize("real", [1, 2, 5, 13])
def test_padded_bucket_leaves_the_state_of_its_last_real_token(
        toy, decoder, real):
    """A right-padded chunk (``valid_len``) leaves the SSD state and the
    convolution's window of its last real token, whatever the padding
    holds; and the next token's logits are the reference's."""
    seq = tokens(toy, (1, real + 1), seed=20 + real)
    padded = np.concatenate(
        [seq[:, :real], tokens(toy, (1, 16 - real), seed=1)], axis=1)
    with jax.default_matmul_precision("highest"):
        _, exact = _prefill(decoder, decoder.init_cache(1), seq[:, :real])
        _, caches = _prefill(decoder, decoder.init_cache(1), padded,
                             jnp.int32(real))
        for a, b in zip(_ssm_entries(decoder, exact),
                        _ssm_entries(decoder, caches)):
            np.testing.assert_allclose(b[0], a[0], atol=1e-5)
            np.testing.assert_allclose(b[1], a[1], atol=1e-6)
        lg, _ = decoder.step(caches, real, seq[:, real])
    ref = reference(toy, seq)
    assert np.abs(np.asarray(lg) - ref[:, real]).max() <= TOL * ref.std()


def test_slot_that_is_not_live_keeps_its_state(toy, decoder):
    """The slot walk with ``lens`` 0 for a slot: its Mamba-2 entries come
    back bit for bit, a live slot's do not; the walk counts one advanced
    slot a layer."""
    seqs = tokens(toy, (2, 9), seed=31)
    with jax.default_matmul_precision("highest"):
        _, caches = _prefill(decoder, decoder.init_cache(2), seqs[:, :8])
        before = [tuple(np.asarray(x) for x in e)
                  for e in _ssm_entries(decoder, caches)]

        def walk(caches, toks):
            stats = {}
            _, after = decoder._run_slots(
                decoder._params, decoder._aux, caches,
                jnp.asarray([8, 8], jnp.int32), toks,
                lens=jnp.asarray([9, 0], jnp.int32), stats=stats)
            return after, stats

        after, stats = jax.jit(walk)(caches, jnp.asarray(seqs[:, 8:9]))
    for b, a in zip(before, _ssm_entries(decoder, after)):
        for x, y in zip(b, a):
            y = np.asarray(y)
            assert np.array_equal(x[1], y[1])           # not live: as it was
            assert not np.array_equal(x[0], y[0])       # live: advanced
    assert int(stats["state_advanced"]) == 3
    assert int(stats["state_in_place"]) == 3


def test_reused_slot_starts_from_the_zero_state(toy, decoder):
    """A prefill at position 0 starts from zeros whatever the slot held:
    its logits and its entries equal a fresh cache's."""
    seqs = tokens(toy, (1, 12), seed=41)
    dirty = jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, 3) if x.dtype != jnp.int32 else x,
        decoder.init_cache(1))
    with jax.default_matmul_precision("highest"):
        want, clean = _prefill(decoder, decoder.init_cache(1), seqs)
        got, reused = _prefill(decoder, dirty, seqs)
    np.testing.assert_allclose(got, want, atol=1e-6)
    for a, b in zip(_ssm_entries(decoder, clean),
                    _ssm_entries(decoder, reused)):
        np.testing.assert_allclose(b[0], a[0], atol=1e-6)
        np.testing.assert_allclose(b[1], a[1], atol=1e-6)


# -- the engine ---------------------------------------------------------------

@pytest.fixture()
def engine(decoder):
    eng = mx.serving.InferenceEngine(
        decoder, slots=3, prefill_buckets=BUCKETS, steps_per_round=4)
    yield eng
    eng.close()


def test_engine_serves_the_references_greedy_choice(toy, engine):
    """Six prompts through three slots (slots reused, requests finishing
    at different steps): every served token is the reference's best at
    its position, to 1e-3 of a sigma; the counters by hand."""
    tele = mx.telemetry
    names = ("serving.state_slots_advanced", "serving.state_slots_pool",
             "serving.state_steps_in_place")
    before = {n: tele.counter(n).value for n in names}
    prompts = [tokens(toy, (n,), seed=50 + n) for n in (5, 15, 9, 3, 12, 7)]
    handles = [engine.submit(p, max_tokens=m)
               for p, m in zip(prompts, (10, 6, 9, 4, 8, 5))]
    engine.serve_forever()
    for p, h in zip(prompts, handles):
        seq = np.concatenate([p, np.asarray(h.tokens, np.int32)])[None]
        ref = reference(toy, seq)[0]
        rows = ref[len(p) - 1:len(p) - 1 + len(h.tokens)]
        got = rows[np.arange(len(h.tokens)), h.tokens]
        assert float(np.max((rows.max(-1) - got) / rows.std(-1))) <= 1e-3
    got = {n.split(".")[1]: tele.counter(n).value - before[n]
           for n in names}
    steps = engine.stats["steps"] * engine.steps_per_round
    # three Mamba-2 layers; every token but each request's first (from
    # its prefill) is one step that advanced its slot in all three
    assert got["state_slots_pool"] == 3 * 3 * steps
    assert got["state_slots_advanced"] == 3 * sum(
        len(h.tokens) - 1 for h in handles)
    assert got["state_steps_in_place"] == 3 * steps


def test_refusals_name_the_nodes_own_kind(toy, decoder):
    """What cannot carry the recurrent state refuses by the node's own
    kind and says what its state is."""
    _, cfg, sym, w, _ = toy
    shape = "[%d, %d, %d]" % (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                              cfg["mamba_d_state"])
    for kw in (dict(prefix_cache_mb=8), dict(draft="ngram"), dict(tp=2),
               dict(weight_dtype="int8"), dict(role="prefill")):
        with pytest.raises(MXNetError) as err:
            mx.serving.InferenceEngine(decoder, slots=2,
                                       prefill_buckets=(8,), **kw)
        msg = str(err.value)
        assert "Mamba2" in msg and "layer0_mamba" in msg
        assert "recurrent state" in msg and shape in msg
        assert "GatedDeltaNet" not in msg
    with pytest.raises(MXNetError, match="Mamba2"):
        mx.parallel.Decoder(sym, w, max_len=MAX_LEN, cache_dtype="int8")


# -- the attention's scale ------------------------------------------------------

def _scaled_attention(scale, impl="dense", no_bias=True):
    import mxnet_tpu.symbol as S
    ins = dict(data=S.Variable("data"), qkv_weight=S.Variable("qkv"),
               out_weight=S.Variable("wo"))
    if not no_bias:
        ins.update(qkv_bias=S.Variable("bq"), out_bias=S.Variable("bo"))
    att = S.MultiHeadAttention(num_heads=4, num_kv_heads=2, causal=True,
                               impl=impl, scale=scale, no_bias=no_bias,
                               name="att", **ins)
    return att


def _attention_weights(seed=0, e=32, kv=2, h=4, no_bias=True):
    rng = np.random.default_rng(seed)
    f = e + 2 * kv * (e // h)
    w = {"qkv": rng.normal(size=(f, e)).astype(np.float32) * 0.6,
         "wo": rng.normal(size=(e, e)).astype(np.float32) / np.sqrt(e)}
    if not no_bias:
        w["bq"] = rng.normal(size=(f,)).astype(np.float32) * 0.1
        w["bo"] = rng.normal(size=(e,)).astype(np.float32) * 0.1
    return w


def _plain_attention(x, w, scale, h=4, kv=2):
    """By hand, float64: causal grouped-query attention, scores times
    ``scale``."""
    x = np.asarray(x, np.float64)
    b, t, e = x.shape
    d = e // h
    qkv = x @ w["qkv"].T + w.get("bq", 0)
    q = qkv[..., :e].reshape(b, t, h, d)
    k = np.repeat(qkv[..., e:e + kv * d].reshape(b, t, kv, d), h // kv, 2)
    v = np.repeat(qkv[..., e + kv * d:].reshape(b, t, kv, d), h // kv, 2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, e)
    return o @ w["wo"].T + w.get("bo", 0)


@pytest.mark.parametrize("impl", ["dense", "flash", "blockwise"])
@pytest.mark.parametrize("scale", [0.0, 1.0 / 64])
def test_attention_scale_reaches_every_impl(impl, scale):
    """``scale`` (0: 1/sqrt(D)) multiplies the scores of the dense
    einsums, the flash kernel and the blockwise recurrence alike, with
    and without biases."""
    from mxnet_tpu.parallel.decode import _logits_symbol
    from mxnet_tpu.parallel.graph import make_graph_fn
    x = np.random.default_rng(1).normal(size=(2, 24, 32)).astype(np.float32)
    for no_bias in (True, False):
        w = _attention_weights(no_bias=no_bias)
        att = _scaled_attention(scale, impl, no_bias)
        fn = make_graph_fn(_logits_symbol(att))
        vals = dict(w, data=jnp.asarray(x))
        with jax.default_matmul_precision("highest"):
            outs, _ = fn([vals[n] for n in att.list_arguments()], [], False,
                         jax.random.PRNGKey(0))
        want = _plain_attention(x, w, scale or 1.0 / np.sqrt(8))
        np.testing.assert_allclose(np.asarray(outs[0]), want, atol=2e-4)


def test_attention_scale_reaches_the_decoders_reads():
    """The decoder's prefill read (the heads' products), its read at one
    position (``_lane_attn``) and the bounded read of the slot walk
    (``paged_attention``) all scale by the node's ``scale``."""
    scale = 1.0 / 64
    w = _attention_weights(seed=2)
    x = np.random.default_rng(3).normal(size=(2, 20, 32)).astype(np.float32)
    want = _plain_attention(x, w, scale)
    att = _scaled_attention(scale)
    dec = mx.parallel.Decoder(att, w, max_len=32)
    node = dec._mha[0]
    with jax.default_matmul_precision("highest"):
        caches = dec.init_cache(2)
        pre, caches = jax.jit(lambda c, z: dec._cached_mha(
            node, [z, *(jnp.asarray(w[k]) for k in ("qkv", "wo"))], c[0],
            0))(caches, jnp.asarray(x[:, :16]))
        np.testing.assert_allclose(np.asarray(pre), want[:, :16], atol=2e-4)
        entry = caches
        for t in range(16, 20):
            one, entry = jax.jit(lambda c, z, t=t: dec._cached_mha(
                node, [z, *(jnp.asarray(w[k]) for k in ("qkv", "wo"))], c,
                t))(entry, jnp.asarray(x[:, t:t + 1]))
            np.testing.assert_allclose(np.asarray(one)[:, 0], want[:, t],
                                       atol=2e-4)
        pos = jnp.asarray([19, 19], jnp.int32)
        _, entry2 = jax.jit(lambda c, z: dec._cached_mha(
            node, [z, *(jnp.asarray(w[k]) for k in ("qkv", "wo"))], c,
            0))(dec.init_cache(2)[0], jnp.asarray(x[:, :19]))
        slot, _ = jax.jit(lambda c, z: dec._cached_mha(
            node, [z, *(jnp.asarray(w[k]) for k in ("qkv", "wo"))], c, pos,
            lens=pos + 1))(entry2, jnp.asarray(x[:, 19:20]))
    np.testing.assert_allclose(np.asarray(slot)[:, 0], want[:, 19],
                               atol=2e-4)
