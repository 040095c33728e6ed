"""The decode round's device counters through one registry
(``serving/engine.py`` ``_ROUND_COUNTERS``): for every serving family at
toy size the counters add up to what the hand-wired columns before the
registry gave on the same weights and requests; a round's counts land on
the profiler's clock as ``serving.round_counts`` beside the
``serving.decode_round`` span of the round they came from; and
``serving.state_steps_in_place``, a fact of the program's trace, keeps
its value without a column of the round's tokens."""
import glob
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
import mxnet_tpu.models  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness as H  # noqa: E402
from benchmark import scopes as S  # noqa: E402

MAX_LEN, BUCKETS = 64, (8, 16)
COUNTERS = ("moe_experts_touched", "moe_layer_steps", "moe_pairs_held",
            "moe_pairs_routed", "moe_rows_masked", "state_slots_advanced",
            "state_slots_pool", "state_steps_in_place", "latent_rows_live",
            "attn_rows_read", "attn_rows_pool")
# (configuration, family, engine keywords) of each serving cell's family
FAMILIES = {
    "opt": ("opt-1.3b", "opt", dict(prefix_cache_mb=0)),
    "zaya": ("zaya1-8b", "zaya", dict(prefix_cache_mb=0)),
    "qwen3_next": ("qwen3-next-80b-a3b", "qwen3_next",
                   dict(prefill_chunk=8)),
    "xing4": ("xing4.0-29b-a4b", "xing4", dict(prefill_chunk=8)),
}
# what the hand-wired columns of 889d5c1 (the parent of the registry)
# counted for ``serve``'s requests on the same weights; a counter left
# out stayed 0
PARENT = {
    "opt": {"attn_rows_read": 3840, "attn_rows_pool": 10752},
    "zaya": {"moe_experts_touched": 50, "moe_layer_steps": 56,
             "moe_pairs_held": 60, "moe_pairs_routed": 168,
             "moe_rows_masked": 108, "attn_rows_read": 3840,
             "attn_rows_pool": 10752},
    "qwen3_next": {"moe_experts_touched": 88, "moe_layer_steps": 144,
                   "moe_pairs_held": 91, "moe_pairs_routed": 1296,
                   "moe_rows_masked": 312, "state_slots_advanced": 60,
                   "state_slots_pool": 216, "state_steps_in_place": 72,
                   "attn_rows_read": 3840, "attn_rows_pool": 13824},
    "xing4": {"moe_experts_touched": 55, "moe_layer_steps": 72,
              "moe_pairs_held": 61, "moe_pairs_routed": 864,
              "moe_rows_masked": 156, "latent_rows_live": 1182,
              "attn_rows_read": 5760, "attn_rows_pool": 20736},
}


def decoder(family):
    config, name, _ = FAMILIES[family]
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      config + ".json")))
    cfg.update(cfg.pop("toy"))
    fam = H.load_module("families", name)
    sym = fam.build_symbol(mx, cfg, {"attention": "dense"})
    w = H.make_weights(fam.param_specs(cfg), 7, jnp.float32)
    return mx.parallel.Decoder(sym, w, max_len=MAX_LEN), cfg


def serve(family, trace_dir=None):
    """Four requests of staggered lengths into three slots, served to
    the end (under a ``jax.profiler`` trace into ``trace_dir`` if
    given): {counter's short name: what the run added to it, where
    nonzero}, the engine (not closed)."""
    dec, cfg = decoder(family)
    eng = mx.serving.InferenceEngine(
        dec, slots=3, prefill_buckets=BUCKETS, steps_per_round=4,
        **FAMILIES[family][2])
    tele = mx.telemetry
    before = {c: tele.counter("serving." + c).value for c in COUNTERS}
    rng = np.random.default_rng(11)
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        for n, k in ((5, 10), (16, 7), (9, 12), (3, 5)):
            eng.submit(rng.integers(0, cfg["vocab_size"],
                                    (n,)).astype(np.int32), max_tokens=k)
        eng.serve_forever()
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    got = {c: tele.counter("serving." + c).value - before[c]
           for c in COUNTERS}
    return {c: v for c, v in got.items() if v}, eng


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_registry_counts_what_the_hand_wired_columns_counted(family):
    got, eng = serve(family)
    eng.close()
    assert got == PARENT[family]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Qwen3-Next's toy engine, whose step counts the most, served
    under a profiler trace read back as the benchmark reads it."""
    where = str(tmp_path_factory.mktemp("trace"))
    got, eng = serve("qwen3_next", trace_dir=where)
    path, = glob.glob(os.path.join(where, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    yield S.read(path), got, eng
    eng.close()


def test_every_decode_round_has_its_counts_on_the_trace(traced):
    sc, got, eng = traced
    rounds = [st["round"] for _, _, st in sc.spans("serving.decode_round")]
    counts = [st for _, _, st in sc.spans("serving.round_counts")]
    assert len(rounds) == eng.stats["steps"] == len(counts)
    assert sorted(rounds) == sorted(st["round"] for st in counts) \
        == list(range(eng.stats["steps"]))
    # each round's counts were opened when that round drained, after
    # it was dispatched
    start = {st["round"]: s for _, s, _, st in
             ((n, s, e, st) for n, s, e, st in sc.host
              if n == "serving.decode_round")}
    for s, _, st in sc.spans("serving.round_counts"):
        assert s > start[st["round"]]
    summed = {}
    for st in counts:
        for k, v in st.items():
            if k != "round":
                summed[k] = summed.get(k, 0) + v
    assert {k: v for k, v in summed.items() if v} == got \
        == PARENT["qwen3_next"]


def test_a_trace_time_fact_is_counted_without_a_column(traced):
    """``state_steps_in_place`` is a Python int the walk adds at trace
    time: the step returns the tokens and one column for each device
    count, and the counter still reads two layers a step."""
    _, got, eng = traced
    cols = [rc.key for rc in eng._round_cols]
    assert cols == ["experts_touched", "pairs_held", "rows_masked",
                    "state_advanced", "attn_rows_read"]
    assert [(rc.key, n) for rc, n in eng._round_facts.items()] \
        == [("state_in_place", 2)]
    _, _, outs = jax.eval_shape(eng._step_fn, eng._params, eng._aux,
                                eng._caches, eng._state)
    assert outs.shape == (eng.steps_per_round, eng.slots + len(cols))
    assert got["state_steps_in_place"] \
        == 2 * eng.stats["steps"] * eng.steps_per_round
