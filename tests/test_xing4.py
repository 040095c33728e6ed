"""Xing4.0's block on the normal path, against the plain reference of
``benchmark/families/xing4.py`` at toy size, seeded weights, float32: the
latent attention's forward, prefill in pieces followed by decode through the
cache of latent rows (one position, a position vector, the slot walk with
dead slots, a right-padded piece), absorbed against expanded, the
hyper-connection's doubly stochastic mix, YaRN's frequencies, the sigmoid
router with its balancing bias, the shares of the routed experts adding up
to the uncut layer, the engine's counters by hand, and the refusals by
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
from mxnet_tpu.parallel.decode import Decoder

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
                                      "xing4.0-29b-a4b.json")))
    cfg.update(cfg.pop("toy"))
    fam = H.load_module("families", "xing4")
    sym = fam.build_symbol(mx, cfg, {"attention": "dense"})
    w = H.make_weights(fam.param_specs(cfg), 7, jnp.float32)
    return fam, cfg, sym, w, lambda names: {n: w[n] for n in names}


def reference(toy, seqs):
    fam, cfg, _, _, leaves = toy
    with jax.default_matmul_precision("highest"):
        out = fam.reference_logits(jnp.asarray(seqs, jnp.int32), leaves, cfg)
    # float32 all the way: the package runs under x64, where one float64
    # constant would promote the stream (and on the chip lay it out
    # eight times over)
    assert out.dtype == jnp.float32
    return np.asarray(out)


@pytest.fixture(scope="module")
def decoder(toy):
    _, _, sym, w, _ = toy
    return mx.parallel.Decoder(sym, w, max_len=MAX_LEN)


def tokens(toy, shape, seed=3):
    return np.random.default_rng(seed).integers(
        0, toy[1]["vocab_size"], shape).astype(np.int32)


def attn_params(cfg, impl="dense"):
    rs = cfg["rope_scaling"]
    return {"num_heads": cfg["num_attention_heads"],
            "q_lora_rank": cfg["q_lora_rank"],
            "kv_lora_rank": cfg["kv_lora_rank"],
            "nope_dim": cfg["qk_nope_head_dim"],
            "rope_dim": cfg["qk_rope_head_dim"],
            "v_dim": cfg["v_head_dim"], "rope_base": 10000.0,
            "yarn_factor": float(rs["factor"]),
            "yarn_original_max": rs["original_max_position_embeddings"],
            "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0,
            "mscale_all_dim": 1.0, "eps": 1e-6, "impl": impl}


def attn_weights(toy, layer=1):
    fam, _, _, w, _ = toy
    return {s: w["layer%d_%s" % (layer, s)] for s in fam.ATTN_LEAVES}


# -- program against reference ---------------------------------------------

def test_layer_pattern_and_cache_kind(toy, decoder):
    """The first layer is dense, the others routed; every layer's cache
    entry is ONE buffer of latent rows without a head axis, R + Dr numbers
    a token stored in whole lane tiles."""
    _, cfg, sym, _, _ = toy
    args = sym.list_arguments()
    assert "layer0_ffn_gate_weight" in args and "layer0_expert_w1" not in args
    assert "layer1_expert_w1" in args and "layer1_ffn_gate_weight" not in args
    assert "layer1_shared_gate" not in args         # the shared expert: ungated
    assert [n.spec.name for n in decoder._cached] == ["LatentAttention"] * 3
    caches = decoder.init_cache(3)
    assert cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] == 24
    assert [tuple(x.shape for x in e) for e in caches] \
        == [((3, MAX_LEN, 128),)] * 3
    assert Decoder.latent_row_lanes({"kv_lora_rank": 512, "rope_dim": 64}) \
        == 640
    assert len(decoder.row_buffers(caches)) == 3
    assert decoder.has_latent and not decoder.has_state


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_latent_attention_forward_agrees_with_the_reference(toy, impl):
    """The op's own full forward (the expanded form) on a normalized
    input against the family's reference attention."""
    fam, cfg, _, _, _ = toy
    wts = attn_weights(toy)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg["hidden_size"]),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = fam.reference_attention(x, wts, cfg)
        (got,), _ = A.LatentAttention().forward(
            attn_params(cfg, impl),
            [x] + [wts[s] for s in fam.ATTN_LEAVES], [], False, None)
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * float(want.std()) \
        * (1 if impl == "dense" else 20)


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
    assert np.abs(np.asarray(outs[0]) - ref).max() <= TOL * ref.std()


@pytest.mark.parametrize("pieces", [(50,), (16, 16, 7), (3, 8, 1, 20)])
def test_prefill_in_pieces_then_decode_agrees_with_the_reference(
        toy, decoder, pieces, monkeypatch):
    """The prompt enters in pieces, each reading the earlier pieces'
    latent rows in the expanded form (blocks of 16 rows here, so a piece
    walks several; a piece of more than 8 through the kernel, a shorter
    one absorbed), then every further token through a decode step at one
    position (the absorbed form over all rows): the logits at every
    position are the reference's one full forward."""
    monkeypatch.setattr(Decoder, "_LATENT_BLOCK", 16)
    seqs = tokens(toy, (2, 50), seed=11)
    ref = reference(toy, seqs)
    caches = decoder.init_cache(2)
    outs, at = [], 0
    with jax.default_matmul_precision("highest"):
        for n in pieces:
            lg, caches = jax.jit(decoder._run)(
                decoder._params, decoder._aux, caches, jnp.int32(at),
                jnp.asarray(seqs[:, at:at + n]))
            outs.append(np.asarray(lg))
            at += n
        for t in range(at, seqs.shape[1]):
            lg, caches = decoder.step(caches, t, seqs[:, t])
            outs.append(np.asarray(lg)[:, None])
    got = np.concatenate(outs, axis=1)
    assert np.abs(got - ref).max() <= TOL * ref.std()


@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("pos,c", [(0, 12), (20, 10), (12, 24), (40, 24)],
                         ids=["at_0", "inside_a_block", "across_blocks",
                              "to_max_len"])
def test_prefill_kernel_equals_the_einsums(toy, decoder, b, pos, c,
                                           monkeypatch):
    """The expanded read of a piece through the kernel against the
    einsum body of ``_latent_attn_blocks`` on the same queries and rows
    (blocks of 16 rows of 64): a piece at position 0, one inside a block,
    one across two block boundaries, one that ends at ``max_len``; the
    position traced as the engine's prefill has it. Agree to 1e-5 of the
    output's spread in float32 at full precision (the same function,
    other orders of the sums)."""
    monkeypatch.setattr(Decoder, "_LATENT_BLOCK", 16)
    _, cfg, _, _, _ = toy
    p = attn_params(cfg)
    h, dn, dr = p["num_heads"], p["nope_dim"], p["rope_dim"]
    r = p["kv_lora_rank"]
    key = jax.random.split(jax.random.PRNGKey(b * 100 + pos), 2)
    q = jax.random.normal(key[0], (b, c, h, dn + dr), jnp.float32)
    rows = jnp.pad(jax.random.normal(key[1], (b, MAX_LEN, r + dr),
                                     jnp.float32),
                   [(0, 0), (0, 0), (0, 128 - r - dr)])
    wukv = attn_weights(toy)["attn_kv_up_weight"]
    scale = A.mla_softmax_scale(p)
    assert Decoder.latent_read_in_kernel(p, c)

    def read():
        # a new function a trace: jit would reuse the first one's
        return jax.jit(lambda q, rows, pos: decoder._latent_attn_blocks(
            p, q, rows, wukv, pos, scale))(q, rows, jnp.int32(pos))

    with jax.default_matmul_precision("highest"):
        got = read()
        monkeypatch.setattr(Decoder, "latent_read_in_kernel",
                            staticmethod(lambda p, c: False))
        want = read()
    assert got.shape == want.shape == (b, c, h, p["v_dim"])
    assert np.abs(np.asarray(got - want)).max() \
        <= 1e-5 * float(want.std())


def test_prefill_pieces_take_the_kernel_and_decode_steps_do_not(
        toy, decoder, monkeypatch):
    """Which read a chunk at one position takes, by what the code sees:
    a chunk of more than 8 positions the kernel (in every latent layer),
    one of 8 or fewer the einsums; on the chip heads of whole lane tiles
    only (the published 128 and 128, not the toy's 8)."""
    from mxnet_tpu.ops import pallas_kernels as pk
    calls = []
    real = pk.latent_prefill_attention
    monkeypatch.setattr(pk, "latent_prefill_attention",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    caches = decoder.init_cache(1)
    for c, kernels in ((9, 3), (8, 0), (1, 0)):
        del calls[:]
        jax.jit(decoder._run).lower(
            decoder._params, decoder._aux, caches, jnp.int32(0),
            jnp.zeros((1, c), jnp.int32))
        assert len(calls) == kernels, c
    p = attn_params(toy[1])
    assert [Decoder.latent_read_in_kernel(p, c) for c in (8, 9)] \
        == [False, True]
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    assert not Decoder.latent_read_in_kernel(p, 1024)
    assert Decoder.latent_read_in_kernel(dict(p, nope_dim=128, v_dim=128),
                                         1024)


def test_slot_walk_with_dead_slots_and_a_padded_piece(toy, decoder):
    """Three slots at their own positions, the middle one dead: the first
    prompt enters right-padded in a bucket of 16 (its padding rows sit past
    its true length until decode overwrites them), the other whole; then
    decode steps over the position VECTOR (the bounded latent read, ``lens``
    0 for the dead slot). The live slots' logits are the reference's; the
    dead slot's rows are never read and its logits are finite."""
    lens = (11, 0, 16)
    seqs = tokens(toy, (3, 24), seed=21)
    ref = reference(toy, seqs)
    caches = decoder.init_cache(3)
    junk = jnp.full(caches[0][0].shape[1:], 1e4, jnp.float32)
    caches = [(e[0].at[1].set(junk),) for e in caches]   # a stale slot
    with jax.default_matmul_precision("highest"):
        for slot, n in enumerate(lens):
            if not n:
                continue
            sub = Decoder.slot_slice(caches, jnp.int32(slot))
            padded = np.zeros((1, 16), np.int32)
            padded[0, :n] = seqs[slot, :n]
            lg, sub = decoder._run(decoder._params, decoder._aux, sub,
                                   jnp.int32(0), jnp.asarray(padded),
                                   valid_len=jnp.int32(n))
            caches = Decoder.slot_update(caches, jnp.int32(slot), sub)
            assert np.abs(np.asarray(lg)[0, :n] - ref[slot, :n]).max() \
                <= TOL * ref.std()
        pos = np.asarray(lens, np.int32)
        live = pos > 0
        for step in range(6):
            tok = seqs[np.arange(3), pos][:, None]
            stats = {}
            lg, caches = decoder._run_slots(
                decoder._params, decoder._aux, caches, jnp.asarray(pos),
                jnp.asarray(tok), stats=stats,
                lens=jnp.asarray(np.where(live, pos + 1, 0)))
            lg = np.asarray(lg)[:, 0]
            assert np.isfinite(lg).all()
            for s in (0, 2):
                assert np.abs(lg[s] - ref[s, pos[s]]).max() \
                    <= TOL * ref.std()
            # the true lengths of the live slots, in each of three layers
            assert int(stats["latent_rows_live"]) \
                == 3 * int((pos + 1)[live].sum())
            pos = np.where(live, pos + 1, pos)


def test_a_rerun_step_is_idempotent_and_a_reused_slot_needs_no_clearing(
        toy, decoder):
    """The contracts of rows hold for latent rows: re-running a step at
    its position rewrites the row with the same values; a slot that held a
    longer sequence serves a new one with no clearing."""
    seqs = tokens(toy, (1, 30), seed=31)
    other = tokens(toy, (1, 12), seed=32)
    ref = reference(toy, other)
    with jax.default_matmul_precision("highest"):
        _, caches = decoder.prefill(decoder.init_cache(1), seqs)
        lg, caches = decoder.prefill(caches, other[:, :8])    # reused
        a, caches = decoder.step(caches, 8, other[:, 8])
        b, caches = decoder.step(caches, 8, other[:, 8])      # re-run
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(lg)[0] - ref[0, :8]).max() <= TOL * ref.std()
    assert np.abs(np.asarray(a)[0] - ref[0, 8]).max() <= TOL * ref.std()


def test_absorbed_equals_expanded(toy):
    """The two forms of the read on the same rows: scores and outputs
    agree to 1e-5 of the output's spread in float32 at full precision
    (the same function, other roundings)."""
    fam, cfg, _, _, _ = toy
    p = attn_params(cfg)
    wts = attn_weights(toy, 2)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, cfg["hidden_size"]),
                          jnp.float32)
    wukv = wts["attn_kv_up_weight"]
    with jax.default_matmul_precision("highest"):
        q, rows = A.mla_down(p, x, [wts[s] for s in fam.ATTN_LEAVES[:5]],
                             jnp.arange(20, dtype=jnp.int32)[None])
        scale = A.mla_softmax_scale(p)
        mask = jnp.tril(jnp.ones((20, 20), bool))[None, None]
        kn, v, kr = A.mla_expand(p, rows, wukv)
        dn = p["nope_dim"]
        s1 = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kn)
              + jnp.einsum("bqhd,bkd->bhqk", q[..., dn:], kr)) * scale
        o1 = jnp.einsum("bhqk,bkhd->bqhd",
                        jax.nn.softmax(jnp.where(mask, s1, -jnp.inf), -1), v)
        qa = A.mla_absorb_q(p, q, wukv)
        s2 = jnp.einsum("bqhw,bkw->bhqk", qa, rows) * scale
        o2 = A.mla_absorb_out(
            p, jnp.einsum("bhqk,bkr->bqhr",
                          jax.nn.softmax(jnp.where(mask, s2, -jnp.inf), -1),
                          rows[..., :p["kv_lora_rank"]]), wukv)
    assert abs(scale - 16 ** -0.5 * (0.1 * np.log(64) + 1) ** 2) < 1e-7
    assert np.abs(np.asarray(s1 - s2)).max() <= 1e-5 * float(s1.std())
    assert np.abs(np.asarray(o1 - o2)).max() <= 1e-5 * float(o1.std())


# -- the hyper-connection -----------------------------------------------------

def test_sinkhorn_mix_is_doubly_stochastic_and_varies_by_token(toy):
    """Under the recipe ``H_res``'s rows and columns sum to 1 within 1e-3,
    it differs from token to token, and it is neither the identity nor
    uniform; the op's coefficients are the reference's."""
    fam, cfg, _, w, _ = toy
    n = cfg["hc_mult"]
    p = {"lanes": n, "iters": 20, "eps": 1e-6, "clamp": 30.0,
         "res_diag": cfg["hc_res_diag"], "norm_eps": 1e-6}
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 9, n * cfg["hidden_size"]), jnp.float32)
    leaves = {"attn_hc_" + s: w["layer1_attn_hc_" + s]
              for s in ("phi", "alpha", "bias")}
    with jax.default_matmul_precision("highest"):
        pre, post, res = A.hc_coefficients(p, x, *leaves.values())
        want = fam.reference_hc(x.reshape(2, 9, n, -1), leaves, cfg, "attn")
    res = np.asarray(res)
    assert np.abs(res.sum(-1) - 1).max() < 1e-3
    assert np.abs(res.sum(-2) - 1).max() < 1e-3
    flat = res.reshape(-1, n, n)
    assert np.abs(flat - flat[0]).max() > 0.05          # by token
    diag = flat[:, np.arange(n), np.arange(n)]
    assert 0.3 < diag.mean() < 0.9 and np.abs(flat - 1 / n).max() > 0.2
    for got, ref in zip((pre, post, res), want):
        np.testing.assert_allclose(got, ref, atol=1e-5)
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2


def test_hyper_connection_ops_write_the_stream(toy):
    """``X <- H_res X + outer(H_post, y)`` and ``u = H_pre X`` by hand from
    the op's own coefficients; the stream's ends copy and sum."""
    _, cfg, _, w, _ = toy
    n, e = cfg["hc_mult"], cfg["hidden_size"]
    p = {"lanes": n, "iters": 20, "eps": 1e-6, "clamp": 30.0,
         "res_diag": 2.0, "norm_eps": 1e-6}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 5, n * e), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(9), (2, 5, e), jnp.float32)
    hc = [w["layer0_ffn_hc_" + s] for s in ("phi", "alpha", "bias")]
    with jax.default_matmul_precision("highest"):
        pre, post, res = A.hc_coefficients(p, x, *hc)
        (u, mix), _ = A.HyperConnectionPre().forward(p, [x] + hc, [], False,
                                                     None)
        (out,), _ = A.HyperConnectionPost().forward(
            {"lanes": n}, [x, y, mix], [], False, None)
    xl = np.asarray(x).reshape(2, 5, n, e)
    np.testing.assert_allclose(
        u, np.einsum("btn,btne->bte", pre, xl), atol=1e-5)
    want = np.einsum("btij,btje->btie", res, xl) \
        + np.asarray(post)[..., None] * np.asarray(y)[:, :, None]
    np.testing.assert_allclose(out, want.reshape(2, 5, n * e), atol=1e-5)
    assert mix.dtype == jnp.float32 and mix.shape == (2, 5, n + n * n)
    lanes = A.StreamLanes()
    (copied,), _ = lanes.forward({"lanes": n, "mode": "copy"}, [y], [],
                                 False, None)
    (summed,), _ = lanes.forward({"lanes": n, "mode": "sum"}, [copied], [],
                                 False, None)
    assert copied.shape == (2, 5, n * e)
    np.testing.assert_allclose(summed, n * np.asarray(y), rtol=1e-6)


# -- positions ------------------------------------------------------------------

def test_yarn_frequencies_and_rotation_beyond_the_original_range(toy):
    """The program's frequencies are the formula's (the family's, computed
    apart), and a rotation at positions past 4,096 turns each pair by
    position times its blended frequency."""
    fam, cfg, _, _, _ = toy
    real = dict(cfg, qk_rope_head_dim=64)
    want = fam.yarn_inv_freq(real)
    got = A.yarn_frequencies(32, 10000.0, 64.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    theta = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:11], theta[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], theta[23:] / 64, rtol=1e-6)
    assert (got[11:23] < theta[11:23]).all() \
        and (got[11:23] > theta[11:23] / 64).all()
    pos = np.array([4095, 4096, 5000, 9215], np.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 2, 64), jnp.float32)
    out = np.asarray(A.rope_rotate(x, jnp.asarray(pos), 10000.0,
                                   yarn=(64.0, 4096, 32.0, 1.0)))
    ang = pos[:, None].astype(np.float64) * want
    a, b = np.asarray(x)[0, :, :, :32], np.asarray(x)[0, :, :, 32:]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    np.testing.assert_allclose(out[0, :, :, :32], a * cos - b * sin,
                               atol=2e-3)
    np.testing.assert_allclose(out[0, :, :, 32:], b * cos + a * sin,
                               atol=2e-3)
    plain = np.asarray(A.rope_rotate(x, jnp.asarray(pos), 10000.0))
    assert np.abs(plain - out).max() > 0.1


# -- the router and the shares ----------------------------------------------------

def _moe_inputs(seed, b=2, t=6, e=16, nx=16, f=12, sh=8):
    rng = np.random.default_rng(seed)

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32)
                           / np.sqrt(shape[-1]))
    x = jnp.asarray(rng.normal(size=(b, t, e)).astype(np.float32))
    router = mat(nx, e)
    return {"x": x, "probs": jax.nn.sigmoid(x @ router.T),
            "bias": jnp.asarray(rng.normal(size=nx).astype(np.float32))
            * 0.02, "w1": mat(nx, 2 * f, e), "w2": mat(nx, e, f),
            "sw1": mat(2 * sh, e), "sw2": mat(e, sh)}


BASE = {"num_experts": 16, "hidden": 12, "top_k": 4, "gated": True,
        "router": "given", "renormalize": True, "route_scale": 2.0}


def test_sigmoid_router_choice_and_weights_with_a_bias_that_flips_a_choice():
    """The four largest of ``s + b`` are chosen, weighted ``s / sum s * 2``
    (never ``s + b``); a bias large enough on the fifth expert of a token
    swaps it in, and its weight is still its own score's share."""
    m = _moe_inputs(5)
    x, probs = m["x"], np.asarray(m["probs"], np.float64)
    f = 12

    def by_hand(bias):
        want = np.zeros(x.shape)
        chosen = np.zeros(probs.shape, bool)
        for b in range(x.shape[0]):
            for t in range(x.shape[1]):
                top = np.argsort(-(probs[b, t] + bias), kind="stable")[:4]
                chosen[b, t, top] = True
                for j in top:
                    up = np.asarray(m["w1"][j], np.float64) \
                        @ np.asarray(x[b, t], np.float64)
                    act = up[:f] / (1 + np.exp(-up[:f])) * up[f:]
                    want[b, t] += probs[b, t, j] / probs[b, t, top].sum() \
                        * 2.0 * (np.asarray(m["w2"][j], np.float64) @ act)
        return want, chosen

    bias = np.asarray(m["bias"], np.float64)
    fifth = int(np.argsort(-(probs[0, 0] + bias))[4])
    flipped = bias.copy()
    flipped[fifth] += 1.0
    (want, chose), (want2, chose2) = by_hand(bias), by_hand(flipped)
    assert not chose[0, 0, fifth] and chose2[0, 0, fifth]
    with jax.default_matmul_precision("highest"):
        for bb, ww in ((bias, want), (flipped, want2)):
            got = A.moe_ffn_math(BASE, [x, m["probs"],
                                        jnp.asarray(bb, jnp.float32),
                                        m["w1"], m["w2"]])
            np.testing.assert_allclose(got, ww, atol=2e-5)
    assert np.abs(want - want2).max() > 1e-3


def test_four_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """Four holders of four experts each route over all sixteen and
    compute their own pairs, weights renormalized over ALL of a token's
    four choices and scaled: their sum, with the ungated shared expert
    counted once, is the uncut layer."""
    m = _moe_inputs(3)
    route = [m["x"], m["probs"], m["bias"]]
    with jax.default_matmul_precision("highest"):
        whole = A.moe_ffn_math(
            dict(BASE, shared_hidden=8, shared_gated=False),
            route + [m["w1"], m["w2"], m["sw1"], m["sw2"]])
        parts = [A.moe_ffn_math(
            dict(BASE, experts_held=4, expert_first=first),
            route + [m["w1"][first:first + 4], m["w2"][first:first + 4]])
            for first in range(0, 16, 4)]
        shared = A._shared_expert(m["x"], m["sw1"], m["sw2"], None)
        one_share = A.moe_ffn_math(
            dict(BASE, experts_held=4, expert_first=4, shared_hidden=8,
                 shared_gated=False),
            route + [m["w1"][4:8], m["w2"][4:8], m["sw1"], m["sw2"]])
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    np.testing.assert_allclose(parts[1] + shared, one_share, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 1e-3


def test_new_router_parameters_leave_the_old_graphs_as_they_were():
    """With their defaults the given router's renormalization and scale
    trace nothing: the jaxpr of ZAYA's kind of node is the one without the
    parameters; they belong to the given router alone; an ungated shared
    expert takes no ``shared_gate`` argument."""
    m = _moe_inputs(7)
    old = {"num_experts": 16, "hidden": 12, "top_k": 1, "gated": True,
           "router": "given"}
    ins = [m["x"], m["probs"], m["bias"], m["w1"], m["w2"]]
    a = jax.make_jaxpr(lambda *z: A.moe_ffn_math(old, list(z)))(*ins)
    b = jax.make_jaxpr(lambda *z: A.moe_ffn_math(
        dict(old, renormalize=False, route_scale=1.0, shared_gated=True),
        list(z)))(*ins)
    assert str(a) == str(b)
    with pytest.raises(MXNetError, match="router='given'"):
        A.moe_ffn_math({"num_experts": 16, "hidden": 12, "top_k": 2,
                        "gated": True, "renormalize": True},
                       [m["x"], m["w1"][:, 0, :], m["w1"], m["w2"]])
    spec = A.MoEFFN()
    assert "shared_gate" in spec.arguments(dict(old, shared_hidden=8))
    assert "shared_gate" not in spec.arguments(
        dict(old, shared_hidden=8, shared_gated=False))


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
    to 1e-3 of a sigma; the default is no prefix pool."""
    assert engine.prefix_cache_mb == 0
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
    """One request alone in three slots: every decode step reads ONE
    slot's rows in each of three layers (its true length summed, whole
    blocks fetched), routes the live slot's four choices in each of the
    two routed layers, and masks the two dead slots."""
    tele = mx.telemetry
    names = ("serving.latent_rows_live", "serving.attn_rows_read",
             "serving.attn_rows_pool", "serving.moe_pairs_held",
             "serving.moe_pairs_routed", "serving.moe_experts_touched",
             "serving.moe_layer_steps", "serving.moe_rows_masked")
    before = {n: tele.counter(n).value for n in names}
    h = engine.submit(tokens(toy, (6,), seed=61), max_tokens=9)
    engine.serve_forever()
    got = {n.split(".")[1]: tele.counter(n).value - before[n]
           for n in names}
    steps = engine.stats["steps"] * engine.steps_per_round
    assert len(h.tokens) == 9 and steps >= 8
    assert got["attn_rows_pool"] == 3 * MAX_LEN * 3 * steps
    # the first token comes from the prefill; the other eight each from one
    # step that read rows [0, 7), [0, 8) ... [0, 14) in three layers
    assert got["latent_rows_live"] == 3 * sum(range(7, 15))
    assert got["attn_rows_read"] == 3 * 8 * MAX_LEN        # one block each
    assert got["moe_layer_steps"] == 2 * steps
    assert got["moe_pairs_routed"] == 3 * 4 * 2 * steps
    assert 0 < got["moe_pairs_held"] <= 4 * 2 * 8
    assert 0 < got["moe_experts_touched"] <= got["moe_pairs_held"]
    assert got["moe_rows_masked"] == 2 * 3 * steps - 2 * 8


def test_latent_prefill_blocks_by_hand(toy, decoder, monkeypatch):
    """Prompts in pieces of up to 16 (blocks of 16 rows): a piece the
    program runs as a chunk of 16 reads ceil((pos + 16) / 16) blocks
    through the kernel in each of three layers, one run as a chunk of 8
    takes the einsums and counts nothing; the served tokens are the
    reference's best."""
    monkeypatch.setattr(Decoder, "_LATENT_BLOCK", 16)
    counter = mx.telemetry.counter("serving.latent_prefill_blocks")
    before = counter.value
    eng = mx.serving.InferenceEngine(
        decoder, slots=2, prefill_buckets=BUCKETS, steps_per_round=4,
        prefill_chunk=16)
    prompts = [tokens(toy, (n,), seed=80 + n) for n in (40, 20)]
    handles = [eng.submit(q, max_tokens=4) for q in prompts]
    eng.serve_forever()
    eng.close()
    # 40: chunks of 16 at 0 and 16, 8 at 32; 20: 16 at 0, 8 at 16
    pieces = [(0, 16), (16, 16), (0, 16)]
    assert counter.value - before \
        == 3 * sum(-(-(pos + c) // 16) for pos, c in pieces) == 12
    assert decoder.latent_prefill_blocks(32, 8) == 0
    for q, h in zip(prompts, handles):
        seq = np.concatenate([q, np.asarray(h.tokens, np.int32)])[None]
        ref = reference(toy, seq)[0]
        rows = ref[len(q) - 1:len(q) - 1 + len(h.tokens)]
        got = rows[np.arange(len(h.tokens)), h.tokens]
        assert float(np.max((rows.max(-1) - got) / rows.std(-1))) <= 1e-3


def test_speculation_carries_latent_rows(toy, decoder):
    """A verify chunk writes and reads latent rows like K/V rows (junk
    above the head is masked until overwritten): n-gram drafts change how
    many tokens arrive a round, never which."""
    prompt = np.tile(tokens(toy, (5,), seed=70), 4)
    out = []
    for draft in ("off", "ngram"):
        eng = mx.serving.InferenceEngine(
            decoder, slots=2, prefill_buckets=(32,), steps_per_round=2,
            draft=draft, spec_k=3)
        h = eng.submit(prompt, max_tokens=12)
        eng.serve_forever()
        out.append(list(h.tokens))
        eng.close()
    assert out[0] == out[1] and len(out[0]) == 12


def test_refusals_name_the_latent_rows(toy, decoder):
    """What moves or re-types K/V rows by their layout refuses by the
    node's own kind and says what its row is."""
    _, _, sym, w, _ = toy
    for kw in (dict(prefix_cache_mb=8), dict(tp=2),
               dict(weight_dtype="int8"), dict(role="prefill"),
               dict(role="decode")):
        with pytest.raises(MXNetError) as err:
            mx.serving.InferenceEngine(decoder, slots=2,
                                       prefill_buckets=(8,), **kw)
        msg = str(err.value)
        assert "LatentAttention" in msg and "layer0_attn" in msg
        assert "latent rows" in msg and "24 numbers a token" in msg
    for kw in (dict(cache_dtype="int8"), dict(weight_dtype="int8")):
        with pytest.raises(MXNetError, match="LatentAttention"):
            mx.parallel.Decoder(sym, w, max_len=MAX_LEN, **kw)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.raises(MXNetError, match="LatentAttention"):
        decoder.init_cache(2, kv_sharding=NamedSharding(
            mesh, P(None, None, "model")))
    with pytest.raises(MXNetError, match="per-slot positions"):
        decoder._run_slots(decoder._params, decoder._aux,
                           decoder.init_cache(2), jnp.zeros(2, jnp.int32),
                           jnp.zeros((2, 9), jnp.int32))
