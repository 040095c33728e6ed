"""A batch row that holds no request routes nothing (``moe_ffn_math``'s
``live``): the node alone, the decoder's slot walk under ``lens``, and
the engine's counters, each over a ZAYA-like layer (top-1, every expert
held, biased ReLU experts, a router from the graph), a Qwen3-Next-like
one (top-k of a held share, SiLU-gated experts, a shared expert) and a
Xing4.0-like one (sigmoid scores from the graph under a balancing bias,
renormalized over the chosen and scaled, a held share, an ungated shared
expert), float32 on the CPU."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
import mxnet_tpu.models  # noqa: F401
from mxnet_tpu.ops import attention as A

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LEN, BUCKETS = 48, (8, 16)
KINDS = ("zaya", "qwen3_next", "xing4")


# -- the node ---------------------------------------------------------------

def node(kind, shape=(6, 2), seed=0):
    """(params, inputs, each pair's index among the held experts or -1,
    the shared expert's inputs or None)."""
    rng = np.random.default_rng(seed)
    nx, e, h = 8, 16, 12

    def f(*s):
        return jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)

    x = f(*shape, e)
    if kind == "zaya":
        p = {"num_experts": nx, "hidden": h, "top_k": 1,
             "router": "given", "gated": False}
        probs = jax.nn.softmax(f(*shape, nx) * 6.0, -1)
        beta = f(nx) * 0.01
        ins = [x, probs, beta, f(nx, h, e), f(nx, h), f(nx, e, h), f(nx, e)]
        choice = np.asarray(jax.lax.top_k(probs + beta, 1)[1])
        return p, ins, choice, None
    if kind == "xing4":
        p = {"num_experts": nx, "hidden": h, "top_k": 3, "router": "given",
             "gated": True, "renormalize": True, "route_scale": 2.0,
             "experts_held": 4, "expert_first": 2, "shared_hidden": 8,
             "shared_gated": False}
        probs = jax.nn.sigmoid(f(*shape, nx) * 4.0)
        beta = f(nx) * 0.05
        shared = (f(16, e), f(e, 8), None)
        ins = [x, probs, beta, f(4, 2 * h, e), f(4, e, h)] + list(shared[:2])
        choice = np.asarray(jax.lax.top_k(probs + beta, 3)[1]) - 2
        choice = np.where((choice >= 0) & (choice < 4), choice, -1)
        return p, ins, choice, shared
    p = {"num_experts": nx, "hidden": h, "top_k": 3, "router": "linear",
         "gated": True, "experts_held": 4, "expert_first": 2,
         "shared_hidden": 8}
    gate = f(nx, e) * 4.0
    shared = (f(16, e), f(e, 8), f(1, e))
    ins = [x, gate, f(4, 2 * h, e), f(4, e, h)] + list(shared)
    score = jnp.einsum("bte,xe->btx", x, gate)
    choice = np.asarray(jax.lax.top_k(score, 3)[1]) - 2
    choice = np.where((choice >= 0) & (choice < 4), choice, -1)
    return p, ins, choice, shared


@pytest.mark.parametrize("dead", [(1, 4, 5), (0,), (), (0, 1, 2, 3, 4, 5)])
@pytest.mark.parametrize("kind", KINDS)
def test_live_rows_keep_their_values_and_dead_rows_route_nothing(kind,
                                                                 dead):
    p, ins, choice, shared = node(kind)
    live = np.ones(6, bool)
    live[list(dead)] = False
    want = np.asarray(A.moe_ffn_math(p, ins))
    stats = {}
    got = np.asarray(A.moe_ffn_math(p, ins, stats=stats,
                                    live=jnp.asarray(live)))
    # a live row's pairs, gates and products are what they were
    assert np.array_equal(got[live], want[live])
    # a dead row gets no expert: the shared expert's part, or zeros
    rest = np.asarray(A._shared_expert(ins[0], *shared))[~live] \
        if shared else 0.0
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[~live], rest, atol=1e-6)
    pairs = choice[live]
    assert int(stats["rows_masked"]) == len(dead)
    assert int(stats["pairs_held"]) == int((pairs >= 0).sum())
    assert int(stats["experts_touched"]) == len(set(pairs[pairs >= 0]))
    if not live.any():
        assert int(stats["experts_touched"]) == 0


@pytest.mark.parametrize("kind", KINDS)
def test_without_live_the_traced_program_is_the_one_without_the_argument(
        kind):
    p, ins, _, _ = node(kind)

    def traced(**kw):
        stats = {}
        text = str(jax.make_jaxpr(
            lambda *a: A.moe_ffn_math(p, list(a), stats=stats, **kw))(*ins))
        return text, set(stats)

    plain, keys = traced()
    assert traced(live=None) == (plain, keys)
    assert keys == {"experts_touched", "pairs_held"}
    masked, keys = traced(live=jnp.ones(6, bool))
    assert masked != plain and "rows_masked" in keys


@pytest.mark.parametrize("kind", KINDS)
def test_the_dense_forms_ignore_live(kind):
    """Soft routing and a custom product compute every expert on every
    token whatever the gates say: ``live`` changes nothing there."""
    p, ins, _, _ = node(kind)
    if kind != "zaya":                  # a share has the routed form only
        p = dict(p, experts_held=0, expert_first=0, shared_hidden=0)
        rng = np.random.default_rng(1)
        ins = ins[:3 if kind == "xing4" else 2] \
            + [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
               for s in ((8, 24, 16), (8, 16, 12))]
    up = lambda x, w: jnp.einsum("bte,xhe->btxh", x, w)
    live = jnp.asarray([True, False] * 3)
    want = A.moe_ffn_math(p, ins, up_mm=up)
    assert np.array_equal(A.moe_ffn_math(p, ins, up_mm=up, live=live), want)


# -- the slot walk and the engine ---------------------------------------------

@pytest.fixture(scope="module")
def harness():
    import sys
    sys.path.insert(0, ROOT)
    from benchmark import harness
    return harness


@pytest.fixture(scope="module", params=KINDS)
def model(request, harness):
    """(toy configuration, decoder) of a family, float32 weights from a
    seed."""
    name = {"zaya": "zaya1-8b", "qwen3_next": "qwen3-next-80b-a3b",
            "xing4": "xing4.0-29b-a4b"}[request.param]
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      name + ".json")))
    cfg.update(cfg.pop("toy"))
    fam = harness.load_module("families", request.param)
    sym = fam.build_symbol(mx, cfg, {"attention": "dense"})
    w = harness.make_weights(fam.param_specs(cfg), 7, jnp.float32)
    return cfg, mx.parallel.Decoder(sym, w, max_len=MAX_LEN)


def routed(decoder):
    return [n for n in decoder._topo if not n.is_var
            and n.spec.name == "MoEFFN" and n.params["top_k"] > 0]


def test_live_slots_do_not_see_what_a_dead_slot_carries(model):
    """Three slots after a prefill of eight tokens, the middle one dead
    (``lens`` 0): whatever stale token it carries, the live slots'
    logits and cache rows are the same, and they are those of a walk in
    which every slot is live."""
    cfg, decoder = model
    seqs = np.random.default_rng(11).integers(
        0, cfg["vocab_size"], (3, 9)).astype(np.int32)
    pos = jnp.full((3,), 8, jnp.int32)

    @jax.jit
    def walk(caches, toks, lens):
        stats = {}
        logits, after = decoder._run_slots(
            decoder._params, decoder._aux, caches, pos, toks, lens=lens,
            stats=stats)
        return logits, after, stats

    with jax.default_matmul_precision("highest"):
        _, caches = jax.jit(lambda c, t: decoder._run(
            decoder._params, decoder._aux, c, 0, t))(
                decoder.init_cache(3), jnp.asarray(seqs[:, :8]))
        dead = jnp.asarray([9, 0, 9], jnp.int32)
        runs = []
        for stale in (int(seqs[1, 8]), (int(seqs[1, 8]) + 1) % 7):
            toks = jnp.asarray(seqs[:, 8:9]).at[1, 0].set(stale)
            runs.append(walk(caches, toks, dead))
        whole = walk(caches, jnp.asarray(seqs[:, 8:9]), pos + 1)
    (la, ca, sa), (lb, cb, sb) = runs
    ours = np.asarray([0, 2])
    for other in (lb, whole[0]):
        assert np.array_equal(np.asarray(la)[ours], np.asarray(other)[ours])
    assert np.all(np.isfinite(np.asarray(la)))
    for x, y in zip(jax.tree_util.tree_leaves(ca),
                    jax.tree_util.tree_leaves(cb)):
        assert np.array_equal(np.asarray(x)[ours], np.asarray(y)[ours])
    layers = len(routed(decoder))
    top_k = routed(decoder)[0].params["top_k"]
    for stats in (sa, sb):
        assert int(stats["rows_masked"]) == layers
        assert int(stats["experts_touched"]) <= 2 * top_k * layers
    assert int(whole[2]["rows_masked"]) == 0
    assert int(whole[2]["experts_touched"]) >= int(sa["experts_touched"])


def serve(decoder, slots, prompts, outs):
    eng = mx.serving.InferenceEngine(
        decoder, slots=slots, prefill_buckets=BUCKETS, steps_per_round=4,
        prefix_cache_mb=0)
    handles = [eng.submit(p, max_tokens=n) for p, n in zip(prompts, outs)]
    eng.serve_forever()
    steps = eng.stats["steps"] * eng.steps_per_round
    eng.close()
    return [list(h.tokens) for h in handles], steps


def test_engine_with_most_slots_empty_counts_what_it_masks(model):
    """Two requests in six slots: the slots that hold no request are
    counted as masked in every routed layer and step, the experts
    touched stay under the live rows' pairs, and the tokens served are
    those of an engine whose two slots the same requests fill."""
    cfg, decoder = model
    tele = mx.telemetry
    names = ("moe_rows_masked", "moe_layer_steps", "moe_experts_touched",
             "moe_pairs_held")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (11, 5)]
    outs = (13, 7)
    before = {n: tele.counter("serving." + n).value for n in names}
    tokens, steps = serve(decoder, 6, prompts, outs)
    got = {n: tele.counter("serving." + n).value - before[n]
           for n in names}
    assert [len(t) for t in tokens] == list(outs)
    layers = len(routed(decoder))
    top_k = routed(decoder)[0].params["top_k"]
    # the first token of a request comes from its prefill; every other
    # from one decode step in which its slot was live
    live_rows = sum(n - 1 for n in outs)
    assert got["moe_layer_steps"] == layers * steps
    assert got["moe_rows_masked"] + live_rows * layers \
        == 6 * got["moe_layer_steps"]
    assert 0 < got["moe_experts_touched"] <= got["moe_pairs_held"] \
        <= live_rows * top_k * layers
    assert serve(decoder, 2, prompts, outs)[0] == tokens
