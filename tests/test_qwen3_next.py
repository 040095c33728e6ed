"""Qwen3-Next's block on the normal path, against the plain reference of
``benchmark/families/qwen3_next.py`` at toy size, seeded weights, float32:
the full forward and prefill in pieces followed by decode, the chunked
Gated DeltaNet against the sequential one, the state kind's three contracts
(a padded bucket, a slot that is not live, a reused slot), the shares of the
routed experts adding up to the uncut layer, the grouped product with more
groups than rows, the engine's counters by hand, and the refusals by
name."""
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
                                      "qwen3-next-80b-a3b.json")))
    cfg.update(cfg.pop("toy"))
    fam = H.load_module("families", "qwen3_next")
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
    """Layer i is an attention layer where (i + 1) % interval == 0; an
    attention entry is K and V rows, a DeltaNet entry holds NO rows: a
    float32 state [B, Hv, Dk, Dv] and the convolution's three rows of
    inputs."""
    _, cfg, _, _, _ = toy
    kinds = [n.spec.name for n in decoder._cached]
    assert kinds == ["GatedDeltaNet", "GatedAttention"] * 2
    caches = decoder.init_cache(3)
    fw = 2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"] \
        + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    state, conv = caches[0]
    assert state.shape == (3, cfg["linear_num_value_heads"],
                           cfg["linear_key_head_dim"],
                           cfg["linear_value_head_dim"])
    assert state.dtype == jnp.float32 and conv.shape == (3, 3 * fw)
    rows = (3, MAX_LEN, cfg["num_key_value_heads"] * cfg["head_dim"])
    assert [x.shape for x in caches[1]] == [rows, rows]
    assert len(decoder.row_buffers(caches)) == 2 and decoder.has_state


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

def _recurrence_inputs(t, seed=0, b=2, h=3, dk=8, dv=16):
    rng = np.random.default_rng(seed)
    f = np.float32
    k = rng.normal(size=(b, t, h, dk)).astype(f)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    return (jnp.asarray(rng.normal(size=(b, h, dk, dv)).astype(f)),
            jnp.asarray(rng.normal(size=(b, t, h, dk)).astype(f)),
            jnp.asarray(k),
            jnp.asarray(rng.normal(size=(b, t, h, dv)).astype(f)),
            jnp.asarray(rng.uniform(size=(b, t, h)).astype(f)),
            jnp.asarray(-rng.uniform(0.001, 0.5, (b, t, h)).astype(f)))


@pytest.mark.parametrize("t", [1, 5, 63, 64, 65, 100, 130, 200])
def test_chunked_recurrence_equals_the_sequential_one(t):
    """The sequential recurrence is the definition; the chunked form is
    held to it over lengths that are and are not whole chunks of 64."""
    s0, q, k, v, beta, g = _recurrence_inputs(t, seed=t)
    s1, o1 = A.gdn_sequential(s0, q, k, v, beta, g)
    s2, o2 = A.gdn_chunked(s0, q, k, v, beta, g)
    np.testing.assert_allclose(o2, o1, atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-5)


def test_chunked_recurrence_across_a_piece_boundary():
    """Two pieces, the state handed from one to the next, equal one
    pass; the boundary falls inside a chunk of 64."""
    s0, q, k, v, beta, g = _recurrence_inputs(150, seed=9)
    s_all, o_all = A.gdn_sequential(s0, q, k, v, beta, g)
    cut = 87
    sa, oa = A.gdn_chunked(s0, q[:, :cut], k[:, :cut], v[:, :cut],
                           beta[:, :cut], g[:, :cut])
    sb, ob = A.gdn_chunked(sa, q[:, cut:], k[:, cut:], v[:, cut:],
                           beta[:, cut:], g[:, cut:])
    np.testing.assert_allclose(jnp.concatenate([oa, ob], 1), o_all,
                               atol=2e-5)
    np.testing.assert_allclose(sb, s_all, atol=2e-5)


def test_padding_leaves_the_recurrence_state():
    """Positions of beta = 0, g = 0 leave the state as it is."""
    s0, q, k, v, beta, g = _recurrence_inputs(40, seed=4)
    real = 23
    pad = jnp.arange(40)[None, :, None] >= real
    s_pad, _ = A.gdn_chunked(s0, q, k, v, jnp.where(pad, 0.0, beta),
                             jnp.where(pad, 0.0, g))
    s_real, _ = A.gdn_sequential(s0, q[:, :real], k[:, :real], v[:, :real],
                                 beta[:, :real], g[:, :real])
    np.testing.assert_allclose(s_pad, s_real, atol=2e-5)


# -- the decode step's kernel: live slots only, in place ---------------------

def _step_inputs(slots, seed=0, h=4, dk=8, dv=16):
    s0, q, k, v, beta, g = _recurrence_inputs(1, seed=seed, b=slots, h=h,
                                              dk=dk, dv=dv)
    return s0, q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0]


_STEP_CASES = {
    # lens of the slots, the slots flagged "starts at position 0"
    "some_live": ([7, 0, 0, 3, 0, 11], []),
    "none_live": ([0, 0, 0, 0, 0, 0], [2]),
    "all_live": ([5, 1, 9, 2, 64, 3], []),
    "position_0": ([1, 0, 1, 8, 0, 0], [0, 2, 4]),
    "not_live_first": ([0, 0, 4, 0, 6, 2], [4]),
}


@pytest.mark.parametrize("block_h", [None, 2])
@pytest.mark.parametrize("case", sorted(_STEP_CASES))
def test_state_step_kernel_is_gdn_step_for_the_live_slots(case, block_h):
    """``gdn_state_step`` against ``gdn_step`` on the same inputs: a live
    slot's state and output are the definition's, one flagged as
    starting at position 0 starts from zeros whatever it held, and a
    slot that is not live comes back bit for bit with zeros for an
    output, in the step with no live slot too and whatever the order of
    the lengths."""
    lens, zeroed = _STEP_CASES[case]
    lens = np.asarray(lens, np.int32)
    fresh = np.zeros(len(lens), bool)
    fresh[zeroed] = True
    s0, q, k, v, beta, g = _step_inputs(len(lens), seed=len(case))
    live = lens > 0
    start = jnp.where((fresh & live)[:, None, None, None], 0, s0)
    want_s, want_o = A.gdn_step(start, q, k, v, beta, g)
    got_s, got_o = jax.jit(
        lambda *a: pk.gdn_state_step(*a, block_h=block_h))(
            s0, q, k, v, beta, g, jnp.asarray(lens), jnp.asarray(fresh))
    got_s, got_o = np.asarray(got_s), np.asarray(got_o)
    np.testing.assert_allclose(got_s[live], np.asarray(want_s)[live],
                               atol=2e-6)
    np.testing.assert_allclose(got_o[live], np.asarray(want_o)[live],
                               atol=2e-6)
    assert np.array_equal(got_s[~live], np.asarray(s0)[~live])
    assert not got_o[~live].any()
    if fresh[live].any():       # zeros, not the old state times nought
        dirty = s0.at[np.flatnonzero(fresh & live)[0]].set(jnp.inf)
        again, _ = pk.gdn_state_step(dirty, q, k, v, beta, g,
                                     jnp.asarray(lens), jnp.asarray(fresh))
        assert np.array_equal(np.asarray(again)[live], got_s[live])


def test_state_step_kernel_runs_where_a_slot_walk_steps_one_position(
        toy, decoder):
    """The dispatch is by what the walk sees: one position under a slot
    walk's ``lens`` advances both DeltaNet layers' states through the
    kernel (beside the two attention layers' bounded reads); the same
    step without ``lens`` (offline ``generate``) and a longer chunk
    (a prefill piece) run ``gdn_step`` / ``gdn_chunked``."""
    def calls(chunk, lens):
        caches = decoder.init_cache(2)
        pos = jnp.asarray([8, 8], jnp.int32)
        toks = jnp.zeros((2, chunk), jnp.int32)
        text = str(jax.make_jaxpr(lambda c: decoder._run_slots(
            decoder._params, decoder._aux, c, pos, toks, lens=lens))(
                caches))
        return text.count("pallas_call")

    lens = jnp.asarray([9, 0], jnp.int32)
    others = calls(1, None)             # the attention layers' reads
    assert calls(1, lens) == others + len(decoder._gdn) == 4
    assert calls(3, lens + 2) == calls(3, None)
    assert A.gdn_steps_in_place(decoder._gdn[0].params, 1, lens)
    assert not A.gdn_steps_in_place(decoder._gdn[0].params, 2, lens)
    assert not A.gdn_steps_in_place(decoder._gdn[0].params, 1, None)


# -- the state kind's contracts ---------------------------------------------

def _prefill(decoder, caches, toks, valid_len=None):
    """One jitted chunk at position 0 (eager, the walk takes a minute)."""
    fn = jax.jit(lambda c, t, v: decoder._run(
        decoder._params, decoder._aux, c, 0, t, valid_len=v))
    return fn(caches, jnp.asarray(toks), valid_len)


def _gdn_entries(decoder, caches):
    return [e for n, e in zip(decoder._cached, caches)
            if n.spec.name == "GatedDeltaNet"]


@pytest.mark.parametrize("real", [1, 2, 5, 13])
def test_padded_bucket_leaves_the_state_of_its_last_real_token(
        toy, decoder, real):
    """A right-padded chunk (``valid_len``) leaves the matrix state and
    the convolution's window of its last real token, whatever the
    padding holds; and the next token's logits are the reference's."""
    seq = tokens(toy, (1, real + 1), seed=20 + real)
    padded = np.concatenate(
        [seq[:, :real], tokens(toy, (1, 16 - real), seed=1)], axis=1)
    with jax.default_matmul_precision("highest"):
        _, exact = _prefill(decoder, decoder.init_cache(1), seq[:, :real])
        _, caches = _prefill(decoder, decoder.init_cache(1), padded,
                             jnp.int32(real))
        for a, b in zip(_gdn_entries(decoder, exact),
                        _gdn_entries(decoder, caches)):
            np.testing.assert_allclose(b[0], a[0], atol=1e-5)
            np.testing.assert_allclose(b[1], a[1], atol=1e-6)
        lg, _ = decoder.step(caches, real, seq[:, real])
    ref = reference(toy, seq)
    assert np.abs(np.asarray(lg) - ref[:, real]).max() <= TOL * ref.std()


def test_slot_that_is_not_live_keeps_its_state(toy, decoder):
    """The slot walk with ``lens`` 0 for a slot (it holds no request:
    finished, or parked between prefill pieces): its DeltaNet entries
    come back bit for bit, a live slot's do not."""
    seqs = tokens(toy, (2, 9), seed=31)
    with jax.default_matmul_precision("highest"):
        _, caches = _prefill(decoder, decoder.init_cache(2), seqs[:, :8])
        before = [tuple(np.asarray(x) for x in e)
                  for e in _gdn_entries(decoder, caches)]

        def walk(caches, toks):
            stats = {}
            _, after = decoder._run_slots(
                decoder._params, decoder._aux, caches,
                jnp.asarray([8, 8], jnp.int32), toks,
                lens=jnp.asarray([9, 0], jnp.int32), stats=stats)
            return after, stats

        after, stats = jax.jit(walk)(caches, jnp.asarray(seqs[:, 8:9]))
    for b, a in zip(before, _gdn_entries(decoder, after)):
        for x, y in zip(b, a):
            y = np.asarray(y)
            assert np.array_equal(x[1], y[1])           # not live: as it was
            assert not np.array_equal(x[0], y[0])       # live: advanced
    # one slot advanced in each of the two DeltaNet layers
    assert int(stats["state_advanced"]) == 2


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
    for a, b in zip(_gdn_entries(decoder, clean),
                    _gdn_entries(decoder, reused)):
        np.testing.assert_allclose(b[0], a[0], atol=1e-6)
        np.testing.assert_allclose(b[1], a[1], atol=1e-6)


# -- the experts: shares, the shared expert, many small groups ------------------

def _moe_inputs(seed, nx=16, e=24, f=12, sh=8, n=(2, 9)):
    rng = np.random.default_rng(seed)
    f32 = np.float32

    def mat(*shape):
        return jnp.asarray((rng.normal(size=shape)
                            / np.sqrt(shape[-1])).astype(f32))
    return {"x": jnp.asarray(rng.normal(size=n + (e,)).astype(f32)),
            "gate": mat(nx, e) * 3.0, "w1": mat(nx, 2 * f, e),
            "w2": mat(nx, e, f), "sw1": mat(2 * sh, e), "sw2": mat(e, sh),
            "sg": mat(1, e)}


def test_shares_of_the_routed_sum_add_up_to_the_uncut_layer():
    """Four holders of four experts each route over all sixteen and
    compute their own pairs, gates normalized over all of a token's
    choices: their sum, with the shared expert counted once, is the
    uncut layer; and the uncut layer is the plain mixture by hand."""
    m = _moe_inputs(3)
    nx, k, f, sh = 16, 5, 12, 8
    base = {"num_experts": nx, "hidden": f, "top_k": k, "gated": True,
            "router": "linear"}
    with jax.default_matmul_precision("highest"):
        whole = A.moe_ffn_math(
            dict(base, shared_hidden=sh),
            [m["x"], m["gate"], m["w1"], m["w2"], m["sw1"], m["sw2"],
             m["sg"]])
        parts = [A.moe_ffn_math(
            dict(base, experts_held=4, expert_first=first),
            [m["x"], m["gate"], m["w1"][first:first + 4],
             m["w2"][first:first + 4]]) for first in range(0, nx, 4)]
        shared = A._shared_expert(m["x"], m["sw1"], m["sw2"], m["sg"])
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    # by hand: softmax over all sixteen, the five largest renormalized
    x = np.asarray(m["x"], np.float64)
    p = np.exp(x @ np.asarray(m["gate"], np.float64).T)
    p /= p.sum(-1, keepdims=True)
    want = np.zeros_like(x)
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            top = np.argsort(-p[b, t])[:k]
            for j in top:
                up = np.asarray(m["w1"][j], np.float64) @ x[b, t]
                act = up[:f] / (1 + np.exp(-up[:f])) * up[f:]
                want[b, t] += p[b, t, j] / p[b, t, top].sum() \
                    * (np.asarray(m["w2"][j], np.float64) @ act)
    np.testing.assert_allclose(whole - shared, want, atol=2e-5)


@pytest.mark.parametrize("tokens", [32, 27])
def test_a_long_chunk_goes_through_the_experts_in_passes(monkeypatch,
                                                         tokens):
    """More pairs than a pass lays out: the chunk goes in passes of a
    power-of-two number of tokens and the sum is the single pass's; the
    counts add up over the passes. A chunk that is not whole passes
    (2 x 27 tokens: three passes of 16 and one of 6) is filled up with
    tokens whose pairs are absent, so no pass is larger than the limit
    and the fill counts nowhere."""
    m = _moe_inputs(7, n=(2, tokens))
    p = {"num_experts": 16, "hidden": 12, "top_k": 5, "gated": True,
         "router": "linear", "experts_held": 8, "expert_first": 4}
    ins = [m["x"], m["gate"], m["w1"][4:12], m["w2"][4:12]]
    one, many = {}, {}
    laid_out = []
    inner = A._routed_experts

    def seen(x, idx, *rest):
        laid_out.append(idx.size)
        return inner(x, idx, *rest)

    with jax.default_matmul_precision("highest"):
        want = A.moe_ffn_math(p, ins, stats=one)
        monkeypatch.setattr(A, "_ROUTED_PASS_PAIRS", 5 * 16)
        monkeypatch.setattr(A, "_routed_experts", seen)
        got = A.moe_ffn_math(p, ins, stats=many)       # four passes of 16
    assert laid_out == [5 * 16]                        # one traced body
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert int(many["pairs_held"]) == int(one["pairs_held"])
    assert int(many["experts_touched"]) >= int(one["experts_touched"])


def test_a_share_counts_its_own_experts_and_pairs():
    """``stats``: the held experts given a token and the pairs that fell
    on them, by hand from the router's choice."""
    m = _moe_inputs(5)
    p = {"num_experts": 16, "hidden": 12, "top_k": 5, "gated": True,
         "router": "linear", "experts_held": 4, "expert_first": 8}
    stats = {}
    A.moe_ffn_math(p, [m["x"], m["gate"], m["w1"][8:12], m["w2"][8:12]],
                   stats=stats)
    score = np.asarray(m["x"]) @ np.asarray(m["gate"]).T
    top = np.argsort(-score, axis=-1)[..., :5]
    mine = (top >= 8) & (top < 12)
    assert int(stats["pairs_held"]) == int(mine.sum())
    assert int(stats["experts_touched"]) == len(set(top[mine].tolist()))


def test_share_and_shared_expert_refuse_the_dense_forms():
    m = _moe_inputs(6)
    p = {"num_experts": 16, "hidden": 12, "top_k": 0, "gated": True,
         "router": "linear", "experts_held": 4, "expert_first": 0}
    with pytest.raises(MXNetError, match="routed form only"):
        A.moe_ffn_math(p, [m["x"], m["gate"], m["w1"][:4], m["w2"][:4]])
    with pytest.raises(MXNetError, match="not among num_experts"):
        A.MoEFFN.held(dict(p, expert_first=14))


@pytest.mark.parametrize("interpret", [None, True])
def test_grouped_matmul_with_more_groups_than_rows(interpret):
    """A hundred groups of one or two rows (640 token-expert pairs over
    128 held experts look like this): every block its own expert, most
    of each block padding; the plain form and the kernel under the
    interpreter agree with a loop by hand."""
    rng = np.random.default_rng(8)
    nx, kdim, n, rows = 100, 32, 48, 16
    sizes = rng.integers(0, 3, nx)                  # 0, 1 or 2 rows each
    used = int((sizes > 0).sum())
    nb = used + 5                                   # some blocks unused
    block_e = np.zeros(nb, np.int32)
    x = np.zeros((nb * rows, kdim), np.float32)
    w = rng.normal(size=(nx, n, kdim)).astype(np.float32)
    want = np.zeros((nb * rows, n), np.float32)
    for b, e in enumerate(np.flatnonzero(sizes)):
        block_e[b] = e
        x[b * rows:b * rows + sizes[e]] = rng.normal(size=(sizes[e], kdim))
        want[b * rows:(b + 1) * rows] = x[b * rows:(b + 1) * rows] @ w[e].T
    block_e[used:] = block_e[used - 1]
    assert used > rows                      # more groups than a block's rows
    with jax.default_matmul_precision("highest"):
        got = pk.grouped_matmul(jnp.asarray(x), jnp.asarray(w),
                                jnp.asarray(block_e), jnp.int32(used), rows,
                                interpret=interpret)
    np.testing.assert_allclose(got, want, atol=1e-4)


# -- the engine ---------------------------------------------------------------

@pytest.fixture()
def engine(decoder):
    eng = mx.serving.InferenceEngine(
        decoder, slots=3, prefill_buckets=BUCKETS, steps_per_round=4,
        prefill_chunk=8)
    yield eng
    eng.close()


def test_engine_serves_the_references_greedy_choice(toy, engine):
    """Prompts shorter and longer than a piece through the engine
    (chunked prefill, slots reused, requests finishing at different
    steps): every served token is the reference's best at its position,
    to TOL of a sigma."""
    prompts = [tokens(toy, (n,), seed=50 + n) for n in (5, 19, 30, 9, 3, 27)]
    handles = [engine.submit(p, max_tokens=10) for p in prompts]
    engine.serve_forever()
    assert max(h.prefill_chunks for h in handles) == 4
    for p, h in zip(prompts, handles):
        seq = np.concatenate([p, np.asarray(h.tokens, np.int32)])[None]
        ref = reference(toy, seq)[0]
        rows = ref[len(p) - 1:len(p) - 1 + len(h.tokens)]
        got = rows[np.arange(len(h.tokens)), h.tokens]
        assert float(np.max((rows.max(-1) - got) / rows.std(-1))) <= 1e-3


def test_engine_counters_by_hand(toy, engine):
    """One request alone in three slots: every decode step advances ONE
    slot's state in each of the two DeltaNet layers, of three in the
    pool; every step routes slots x top_k pairs in each of the four
    routed layers, a part of which falls on held experts."""
    tele = mx.telemetry
    names = ("serving.state_slots_advanced", "serving.state_slots_pool",
             "serving.state_steps_in_place",
             "serving.moe_pairs_held", "serving.moe_pairs_routed",
             "serving.moe_experts_touched", "serving.moe_layer_steps")
    before = {n: tele.counter(n).value for n in names}
    h = engine.submit(tokens(toy, (6,), seed=61), max_tokens=9)
    engine.serve_forever()
    got = {n.split(".")[1]: tele.counter(n).value - before[n]
           for n in names}
    steps = engine.stats["steps"] * engine.steps_per_round
    assert len(h.tokens) == 9 and steps >= 8
    assert got["state_slots_pool"] == 3 * 2 * steps
    # the first token comes from the prefill; the other eight each from
    # one step that advanced the slot's state in both DeltaNet layers
    assert got["state_slots_advanced"] == 2 * 8
    # every step of every round advanced both layers' states through
    # the kernel, the rounds in which no slot was live included
    assert got["state_steps_in_place"] == 2 * steps
    assert got["moe_layer_steps"] == 4 * steps
    top_k = toy[1]["num_experts_per_tok"]
    assert got["moe_pairs_routed"] == 3 * top_k * 4 * steps
    assert 0 < got["moe_pairs_held"] < got["moe_pairs_routed"]
    assert 0 < got["moe_experts_touched"] <= got["moe_pairs_held"]


def test_refusals_name_the_nodes_own_kind(toy, decoder):
    """What cannot carry the recurrent state refuses by the node's own
    kind and says what its state is."""
    _, _, sym, w, _ = toy
    for kw in (dict(prefix_cache_mb=8), dict(draft="ngram"), dict(tp=2),
               dict(weight_dtype="int8"), dict(role="prefill")):
        with pytest.raises(MXNetError) as err:
            mx.serving.InferenceEngine(decoder, slots=2,
                                       prefill_buckets=(8,), **kw)
        msg = str(err.value)
        assert "GatedDeltaNet" in msg and "layer0_gdn" in msg
        assert "recurrent state" in msg and "[4, 8, 8]" in msg
        assert "CCAttention" not in msg
    with pytest.raises(MXNetError, match="GatedDeltaNet"):
        mx.parallel.Decoder(sym, w, max_len=MAX_LEN, cache_dtype="int8")
