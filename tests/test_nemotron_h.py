"""Nemotron-H's layers on the normal path, against the plain reference of
``benchmark/families/nemotron_h.py`` at toy size, seeded weights, float32:
Mamba-2 with groups of B and C (the chunked SSD and the decode step's
kernel against the sequential recurrence, dead and fresh slots included),
the latent relu² experts with their shared expert, four shares of the
experts adding up to the uncut layer, the full forward and prefill in
pieces followed by decode, the engine end to end with its round counters,
and the graphs that set none of the new options left as they were."""
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
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "nemotron-3-super-120b-a12b.json")))
    cfg.update(cfg.pop("toy"))
    fam = H.load_module("families", "nemotron_h")
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

def test_pattern_and_cache_kinds(toy, decoder):
    """One node a letter of the pattern; a Mamba-2 entry holds a float32
    state and the convolution's three rows over the GROUPED width
    ``D_in + 2 G N``; the attention entry K and V rows of bias-free
    heads; the LatentMoE layers none."""
    _, cfg, _, _, _ = toy
    assert cfg["hybrid_override_pattern"] == "ME*EM"
    assert [n.spec.name for n in decoder._cached] \
        == ["Mamba2", "MultiHeadAttention", "Mamba2"]
    moe = [n for n in decoder._topo
           if not n.is_var and n.spec.name == "MoEFFN"]
    assert len(moe) == 2 and moe[0].params["latent"] == 16
    assert moe[0].params["relu2"] and not moe[0].params["shared_gated"]
    caches = decoder.init_cache(3)
    fw = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    state, conv = caches[0]
    assert state.shape == (3, 8, 4, 8) and state.dtype == jnp.float32
    assert conv.shape == (3, 3 * fw) and fw == 64
    assert [x.shape for x in caches[1]] == [(3, MAX_LEN, 16)] * 2


def test_full_forward_agrees_with_the_reference(toy, decoder):
    seqs = tokens(toy, (2, 50))
    ref = reference(toy, seqs)
    with jax.default_matmul_precision("highest"):
        got, _ = decoder.prefill(decoder.init_cache(2), seqs)
    assert np.abs(np.asarray(got) - ref).max() <= TOL * ref.std()


@pytest.mark.parametrize("pieces", [(50,), (16, 16, 7), (3, 8, 1, 20)])
def test_prefill_in_pieces_then_decode_agrees_with_the_reference(
        toy, decoder, pieces):
    """The prompt enters in pieces (the chunked grouped SSD, the state
    carried from piece to piece), then every further token through a
    decode step: the logits at every position are the reference's one
    full forward."""
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


# -- Mamba-2 with groups of B and C ------------------------------------------

def _ssd_inputs(t, seed=0, b=2, h=8, p=4, n=8, g=2):
    rng = np.random.default_rng(seed)
    f = np.float32
    return (jnp.asarray(rng.normal(size=(b, h, p, n)).astype(f)),
            jnp.asarray(rng.normal(size=(b, t, h, p)).astype(f)),
            jnp.asarray(rng.normal(size=(b, t, g, n)).astype(f)),
            jnp.asarray(rng.normal(size=(b, t, g, n)).astype(f)),
            jnp.asarray(rng.uniform(0.001, 0.3, (b, t, h)).astype(f)),
            jnp.asarray(-rng.uniform(1.0, 8.0, (h,)).astype(f)))


def _by_hand(s0, x, b, c, dt, a):
    """The grouped recurrence in float64, head by head: head h reads
    group h // (H / G)."""
    s = np.asarray(s0, np.float64).copy()
    x, b, c, dt, a = (np.asarray(z, np.float64) for z in (x, b, c, dt, a))
    hh, g = s.shape[1], b.shape[2]
    y = np.zeros(x.shape)
    for t in range(x.shape[1]):
        for h in range(hh):
            gi = h // (hh // g)
            s[:, h] = s[:, h] * np.exp(dt[:, t, h] * a[h])[:, None, None] \
                + (dt[:, t, h, None] * x[:, t, h])[:, :, None] \
                * b[:, t, gi, None, :]
            y[:, t, h] = np.einsum("bpn,bn->bp", s[:, h], c[:, t, gi])
    return s, y


@pytest.mark.parametrize("t,chunk,g", [(1, 16, 2), (37, 16, 2), (64, 16, 4),
                                       (70, 32, 8), (45, 8, 1)])
def test_grouped_ssd_is_the_recurrence_head_by_head(t, chunk, g):
    """The sequential recurrence and the chunked SSD with G groups (G = 1
    given as [.., 1, N] too) against the recurrence written out head by
    head, each head reading its own group's B and C."""
    s0, x, b, c, dt, a = _ssd_inputs(t, seed=t + g, g=g)
    want_s, want_y = _by_hand(s0, x, b, c, dt, a)
    s1, y1 = A.ssd_sequential(s0, x, b, c, dt, a)
    s2, y2 = A.ssd_chunked(s0, x, b, c, dt, a, chunk)
    for s, y in ((s1, y1), (s2, y2)):
        np.testing.assert_allclose(y, want_y, atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(s, want_s, atol=5e-5, rtol=5e-5)


def test_one_group_in_either_shape_is_the_same_recurrence():
    """B and C of one group as [.., N] (Granite's, the path that was
    there) and as [.., 1, N] (the grouped one) give one recurrence."""
    s0, x, b, c, dt, a = _ssd_inputs(50, seed=5, g=1)
    flat = A.ssd_chunked(s0, x, b[:, :, 0], c[:, :, 0], dt, a, 16)
    grouped = A.ssd_chunked(s0, x, b, c, dt, a, 16)
    for u, v in zip(flat, grouped):
        np.testing.assert_allclose(u, v, atol=2e-5, rtol=2e-5)
    one = A.ssd_step(s0, x[:, 0], b[:, 0, 0], c[:, 0, 0], dt[:, 0], a)
    one_g = A.ssd_step(s0, x[:, 0], b[:, 0], c[:, 0], dt[:, 0], a)
    for u, v in zip(one, one_g):
        np.testing.assert_allclose(u, v, atol=1e-6)


_STEP_CASES = {
    # lens of the slots, the slots flagged "starts at position 0", and
    # (heads, P, N, groups)
    "some_live": ([7, 0, 0, 3, 0, 11], [], (8, 8, 16, 2)),
    "none_live": ([0, 0, 0, 0, 0, 0], [2], (8, 8, 16, 4)),
    "position_0": ([1, 0, 1, 8, 0, 0], [0, 2], (8, 8, 16, 2)),
    # the cell's P and N, a group of four heads: two heads a row of y
    "two_heads_a_row": ([4, 0, 7, 1, 0, 2], [3], (16, 64, 128, 4)),
}


@pytest.mark.parametrize("block_h", [None, 2])
@pytest.mark.parametrize("case", sorted(_STEP_CASES))
def test_grouped_ssd_state_step_kernel_is_the_recurrence_for_the_live_slots(
        case, block_h):
    """``ssd_state_step`` with B and C [S, G, N] against ``ssd_step``: a
    live slot's state and output are the definition's, a fresh one starts
    from zeros whatever it held (``inf`` included), a dead one comes back
    bit for bit with zeros for an output; every block of heads lies in
    one group and reads that group's B and C."""
    lens, zeroed, (h, p, n, g) = _STEP_CASES[case]
    lens = np.asarray(lens, np.int32)
    fresh = np.zeros(len(lens), bool)
    fresh[zeroed] = True
    s0, x, b, c, dt, a = _ssd_inputs(1, seed=len(case), b=len(lens), h=h,
                                     p=p, n=n, g=g)
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
    if fresh[live].any():
        dirty = s0.at[np.flatnonzero(fresh & live)].set(jnp.inf)
        again, _ = pk.ssd_state_step(
            dirty, x, b, c, dt, a, jnp.asarray(lens), jnp.asarray(fresh),
            block_h=block_h)
        assert np.array_equal(np.asarray(again)[live], got_s[live])


def test_a_block_of_heads_may_not_cross_a_group():
    s0, x, b, c, dt, a = _ssd_inputs(1, b=2, h=8, g=4)
    with pytest.raises(ValueError, match="heads of a group"):
        pk.ssd_state_step(s0, x[:, 0], b[:, 0], c[:, 0], dt[:, 0], a,
                          jnp.asarray([1, 1]), jnp.asarray([False, False]),
                          block_h=4)
    assert pk.default_state_block_h(128 // 8, 64, 128) == 16
    with pytest.raises(MXNetError, match="n_groups"):
        A.Mamba2.widths({"num_heads": 6, "head_dim": 4, "state_size": 8,
                         "n_groups": 4})


def test_grouped_norm_runs_over_each_group():
    """The gated norm with G groups normalizes each group's D_in / G
    channels on its own: scaling one group's gate leaves the other
    groups' outputs as they were."""
    p = A.Mamba2().parse_params({"num_heads": 4, "head_dim": 4,
                                 "state_size": 4, "n_groups": 2,
                                 "chunk_size": 8})
    d_in, f = A.Mamba2.widths(p)
    assert (d_in, f) == (16, 32)
    rng = np.random.default_rng(2)
    e = 8
    w = [jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.3)
         for s in ((d_in + f + 4, e), (f, 4), (f,), (4,), (4,), (4,),
                   (d_in,), (d_in, d_in))]
    x = jnp.asarray(rng.normal(size=(1, 5, e)).astype(np.float32))
    state = jnp.zeros((1, 4, 4, 4), jnp.float32)
    prev = jnp.zeros((1, 3, f), jnp.float32)
    # an identity output projection shows the normalized channels
    w[-1] = jnp.eye(d_in, dtype=jnp.float32)
    out, _, _ = A.mamba_mix(p, x, w, state, prev)
    w2 = list(w)
    w2[0] = w[0].at[:8].multiply(3.0)        # z of the first group only
    out2, _, _ = A.mamba_mix(p, x, w2, state, prev)
    np.testing.assert_allclose(out2[..., 8:], out[..., 8:], atol=1e-6)
    assert np.abs(np.asarray(out2[..., :8] - out[..., :8])).max() > 1e-3


# -- the latent relu² experts ---------------------------------------------------

def _moe_inputs(seed, b=2, t=6, e=16, nx=16, f=12, lat=8, sh=10):
    rng = np.random.default_rng(seed)

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32)
                           / np.sqrt(shape[-1]))
    x = jnp.asarray(rng.normal(size=(b, t, e)).astype(np.float32))
    return {"x": x, "probs": jax.nn.sigmoid(x @ mat(nx, e).T),
            "bias": jnp.asarray(rng.normal(size=nx).astype(np.float32))
            * 0.02, "down": mat(lat, e), "up": mat(e, lat),
            "w1": mat(nx, f, lat), "w2": mat(nx, lat, f),
            "sw1": mat(sh, e), "sw2": mat(e, sh)}


LATENT = {"num_experts": 16, "hidden": 12, "top_k": 5, "router": "given",
          "renormalize": True, "route_scale": 5.0, "relu2": True,
          "latent": 8}


def _latent_by_hand(m, held=range(16)):
    """float64: top 5 of ``s + b``, weights ``5 s / sum s``; the held
    experts' ``W2 relu(W1 u)^2`` on ``u = W_down x``, summed, projected by
    ``W_up``; plus ``Ws2 relu(Ws1 x)^2``."""
    f64 = lambda z: np.asarray(z, np.float64)
    x, s = f64(m["x"]), f64(m["probs"])
    top = np.argsort(-(s + f64(m["bias"])), axis=-1, kind="stable")[..., :5]
    u = x @ f64(m["down"]).T
    r = np.zeros(u.shape)
    for bi in range(x.shape[0]):
        for t in range(x.shape[1]):
            tot = s[bi, t, top[bi, t]].sum()
            for j in top[bi, t]:
                if j in held:
                    act = np.maximum(f64(m["w1"][j]) @ u[bi, t], 0) ** 2
                    r[bi, t] += 5.0 * s[bi, t, j] / tot \
                        * (f64(m["w2"][j]) @ act)
    shared = np.maximum(x @ f64(m["sw1"]).T, 0) ** 2 @ f64(m["sw2"]).T
    return r @ f64(m["up"]).T, shared


def test_latent_relu2_experts_with_their_shared_expert_by_hand():
    m = _moe_inputs(4)
    p = dict(LATENT, shared_hidden=10, shared_gated=False)
    ins = [m["x"], m["probs"], m["bias"], m["down"], m["up"], m["w1"],
           m["w2"], m["sw1"], m["sw2"]]
    assert A.MoEFFN().arguments(A.MoEFFN().parse_params(p)) == [
        "data", "probs", "select_bias", "latent_down", "latent_up",
        "expert_w1", "expert_w2", "shared_w1", "shared_w2"]
    routed, shared = _latent_by_hand(m)
    stats = {}
    with jax.default_matmul_precision("highest"):
        got = A.moe_ffn_math(p, ins, stats=stats)
    np.testing.assert_allclose(got, routed + shared, atol=2e-5)
    assert int(stats["pairs_held"]) == 2 * 6 * 5
    assert np.abs(shared).max() > 1e-2 and np.abs(routed).max() > 1e-2
    ins_shapes, out, _ = A.MoEFFN().infer_shape(
        A.MoEFFN().parse_params(p), [(2, 6, 16)] + [None] * 8)
    assert out == [(2, 6, 16)]
    assert ins_shapes[3:] == [(8, 16), (16, 8), (16, 12, 8), (16, 8, 12),
                              (10, 16), (16, 10)]


def test_four_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """Four holders of four experts each route over all sixteen, compute
    their own pairs in the latent space and project them back: their sum,
    with the shared expert counted once, is the uncut layer."""
    m = _moe_inputs(3)
    route = [m["x"], m["probs"], m["bias"], m["down"], m["up"]]
    with jax.default_matmul_precision("highest"):
        whole = A.moe_ffn_math(
            dict(LATENT, shared_hidden=10, shared_gated=False),
            route + [m["w1"], m["w2"], m["sw1"], m["sw2"]])
        parts = [A.moe_ffn_math(
            dict(LATENT, experts_held=4, expert_first=first),
            route + [m["w1"][first:first + 4], m["w2"][first:first + 4]])
            for first in range(0, 16, 4)]
        shared = A._shared_expert(m["x"], m["sw1"], m["sw2"], None,
                                  relu2=True)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    held, _ = _latent_by_hand(m, held=range(4, 8))
    np.testing.assert_allclose(parts[1], held, atol=2e-5)


def test_latent_and_relu2_refuse_what_they_do_not_compose_with():
    m = _moe_inputs(1)
    ins = [m["x"], m["probs"], m["bias"], m["down"], m["up"], m["w1"],
           m["w2"]]
    with pytest.raises(MXNetError, match="routed form only"):
        A.moe_ffn_math(dict(LATENT, top_k=0), ins)
    with pytest.raises(MXNetError, match="ungated"):
        A.moe_ffn_math(dict(LATENT, gated=True), ins)


def test_new_options_leave_the_old_graphs_as_they_were():
    """With their defaults the groups, the latent space and relu2 trace
    nothing: the jaxprs of a one-group Mamba-2 mixer and of the routed
    experts of the kinds the other models use are the ones without the
    parameters."""
    m = _moe_inputs(7)
    rng = np.random.default_rng(1)
    w = [jnp.asarray(rng.normal(size=s).astype(np.float32))
         for s in ((16, 24, 16), (16, 16, 12), (10, 16), (16, 5))]
    ins = [m["x"], m["probs"], m["bias"]] + w
    old = {"num_experts": 16, "hidden": 12, "top_k": 4, "gated": True,
           "router": "given", "renormalize": True, "route_scale": 2.0,
           "shared_hidden": 5, "shared_gated": False}
    a = jax.make_jaxpr(lambda *z: A.moe_ffn_math(old, list(z)))(*ins)
    b = jax.make_jaxpr(lambda *z: A.moe_ffn_math(
        dict(old, latent=0, relu2=False), list(z)))(*ins)
    assert str(a) == str(b)
    p = {"num_heads": 4, "head_dim": 4, "state_size": 8, "conv_kernel": 4,
         "chunk_size": 8, "eps": 1e-5}
    d_in, f = A.Mamba2.widths(p)
    rng = np.random.default_rng(0)
    w = [jnp.asarray(rng.normal(size=s).astype(np.float32))
         for s in ((d_in + f + 4, 8), (f, 4), (f,), (4,), (4,), (4,),
                   (d_in,), (8, d_in))]
    args = (jnp.ones((2, 5, 8)), w, jnp.zeros((2, 4, 4, 8)),
            jnp.zeros((2, 3, f)))
    for c in (5, 1):
        z = (args[0][:, :c],) + args[1:]
        a = jax.make_jaxpr(lambda *v: A.mamba_mix(p, *v))(*z)
        b = jax.make_jaxpr(lambda *v: A.mamba_mix(
            dict(p, n_groups=1), *v))(*z)
        assert str(a) == str(b)


# -- the engine ---------------------------------------------------------------

@pytest.fixture()
def engine(decoder):
    eng = mx.serving.InferenceEngine(
        decoder, slots=3, prefill_buckets=BUCKETS, steps_per_round=4)
    yield eng
    eng.close()


def test_engine_serves_the_references_greedy_choice_and_counts(toy, engine):
    """Six prompts through three slots (slots reused, requests finishing
    at different steps): every served token is the reference's best at
    its position, to 1e-3 of a sigma; the round counters count the
    LatentMoE and the Mamba-2 layers beside the attention rows."""
    tele = mx.telemetry
    names = ("serving.state_slots_advanced", "serving.state_slots_pool",
             "serving.moe_experts_touched", "serving.moe_layer_steps",
             "serving.moe_pairs_routed")
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
    # two Mamba-2 layers and two LatentMoE layers of top-3
    assert got["state_slots_pool"] == 3 * 2 * steps
    assert got["state_slots_advanced"] == 2 * sum(
        len(h.tokens) - 1 for h in handles)
    assert got["moe_layer_steps"] == 2 * steps
    assert got["moe_pairs_routed"] == 3 * 2 * 3 * steps
    assert 0 < got["moe_experts_touched"] <= 4 * 2 * steps
