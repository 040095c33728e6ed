"""Names inside the compiled programs and on the host (ISSUE 26): every
Symbol node, the trainer's three parts and the decoder's cache/attend
parts carry a ``jax.named_scope`` in the program's metadata (and change
nothing else of the program); one ``telemetry.span`` lands in the Chrome
capture AND on the profiler's host plane with its arguments; the engine's
round phases are such spans and still sum to the round's wall time.

CPU, toy sizes. The profiler-side readers that turn these names into
per-layer metrics are the benchmark's (``benchmark/scopes.py``, tested in
``benchmark/tests``).
"""
import contextlib
import glob
import json
import os
import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
import mxnet_tpu.models  # noqa: F401
from mxnet_tpu import profiler
from mxnet_tpu import telemetry as tele


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _has(names, part):
    return any(part in n for n in names)


def _trainer(kind):
    if kind == "resnet":
        sym = mx.models.get_resnet_cifar(10, n=1, image_hw=16)
        shapes = {"data": (4, 3, 16, 16), "softmax_label": (4,)}
        opt, kw = "sgd", {"momentum": 0.9}
    else:
        sym = mx.models.get_transformer_lm(
            50, num_layers=1, embed_dim=32, num_heads=2, seq_len=8)
        shapes = {"data": (2, 8), "softmax_label": (2, 8)}
        opt, kw = "adamw", {}
    tr = mx.parallel.ParallelTrainer(
        sym, shapes, optimizer=opt, optimizer_params=kw,
        mesh=mx.parallel.data_parallel_mesh(1), clip_grad_norm=1.0)
    tr.init_params()
    batch = {k: np.zeros(v, np.float32) for k, v in shapes.items()}
    return tr, batch


def _lowered_step(tr, batch):
    tr._jit_step = tr._build_step()          # a fresh trace every time
    with tr.mesh:
        return tr._jit_step.lower(
            tr.params, tr.opt_state, tr.aux, tr._shard_batch(batch, "step"),
            np.float32(0.1), np.int32(1), tr._rng)


@pytest.mark.parametrize("kind,wanted", [
    # BatchNorm has a backward rule of its own (custom_vjp): JAX names
    # such a backward transpose(mx.grads)/jvp(<scope>), a plain one
    # transpose(jvp(<scope>)); both hold "transpose("
    ("resnet", ["mx.grads/jvp(Convolution/", "mx.grads/jvp(BatchNorm/",
                "mx.grads/transpose(jvp(Convolution/",
                "mx.grads/transpose(mx.grads)/jvp(BatchNorm/",
                "mx.grads/jvp(FullyConnected/fc1)", "mx.optimizer/",
                "mx.clip/"]),
    ("lm", ["mx.grads/jvp(MultiHeadAttention/",
            "jvp(SoftmaxOutput/", "mx.grads/transpose(jvp(LayerNorm/",
            "mx.optimizer/", "mx.clip/"]),
])
def test_step_program_names_forward_backward_and_optimizer(kind, wanted):
    tr, batch = _trainer(kind)
    names = _op_names(_lowered_step(tr, batch).compile().as_text())
    for part in wanted:
        assert _has(names, part), (part, sorted(names)[:40])
    # backward operations are told from forward ones by transpose( alone
    assert any("transpose(" in n for n in names if "mx.grads" in n)
    assert not any("transpose(" in n for n in names if "mx.optimizer" in n)


def test_scopes_change_nothing_of_the_program_but_its_metadata(monkeypatch):
    """The compiled step with scopes and the one traced with every
    ``jax.named_scope`` a no-op are the same text once ``metadata={...}``
    is taken out: a scope costs nothing at run time."""
    def strip(text):
        # the tables of source locations that the metadata points into
        # go with it
        text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n\n", "\n",
                      text, flags=re.S)
        return re.sub(r",? ?metadata=\{[^}]*\}", "", text)

    tr, batch = _trainer("resnet")
    scoped = _lowered_step(tr, batch).compile().as_text()
    assert "mx.grads" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lowered_step(tr, batch).compile().as_text()
    assert "mx.grads" not in bare and "Convolution/" not in bare
    assert strip(scoped) == strip(bare)


@pytest.fixture(scope="module")
def toy_engine():
    sym = mx.models.get_transformer_lm(50, num_layers=1, embed_dim=32,
                                       num_heads=2, seq_len=32)
    tr = mx.parallel.ParallelTrainer(
        sym, {"data": (2, 32), "softmax_label": (2, 32)}, optimizer="sgd",
        mesh=mx.parallel.data_parallel_mesh(1))
    tr.init_params()
    params = {k: np.asarray(v) for k, v in tr.params.items()}
    dec = mx.parallel.Decoder(sym, params, max_len=32)
    eng = mx.serving.InferenceEngine(dec, slots=2, prefill_buckets=(8,),
                                     steps_per_round=2, prefix_cache_mb=0)
    eng.submit(np.arange(5), max_tokens=4)
    eng.serve_forever()                     # both programs compiled
    yield eng
    eng.close()


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_decoder_programs_name_attention_cache_and_attend(toy_engine, which):
    eng = toy_engine
    if which == "decode":
        low = eng._step_fn.lower(eng._params, eng._aux, eng._caches,
                                 eng._state)
    else:
        low = eng._prefill_fn(8).lower(
            eng._params, eng._aux, eng._caches, eng._state, np.int32(0),
            np.zeros((1, 8), np.int32), np.int32(0), np.int32(5),
            np.bool_(True), np.float32(0.0),
            jax.random.key_data(jax.random.PRNGKey(0)), np.int32(-1),
            np.int32(4))
    names = _op_names(low.compile().as_text())
    mha = [n for n in names if "MultiHeadAttention/layer0_attn" in n]
    assert any("/attend/" in n for n in mha), sorted(names)[:40]
    assert any("/cache/" in n for n in mha)
    # the projections sit directly under the node's scope
    assert any("/attend" not in n and "/cache" not in n for n in mha)
    assert _has(names, "FullyConnected/lm_head")
    assert _has(names, "LayerNorm/lnf")


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("t26.", "serving.", "train.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_one_span_lands_in_both_captures_with_its_arguments(tmp_path):
    hist = tele.histogram("t26.span_ms")
    n0 = hist.count
    chrome = tele.start_trace(str(tmp_path / "chrome"))
    jax.profiler.start_trace(str(tmp_path / "xla"))
    try:
        with tele.span("t26.region", cat="t26", hist=hist, slots_busy=3,
                       live_rows=77) as sp:
            jax.block_until_ready(jax.numpy.ones((8, 8)) * 2)
    finally:
        jax.profiler.stop_trace()
        tele.stop_trace()
    assert hist.count == n0 + 1 and sp.dt > 0
    evs = [e for e in json.load(open(chrome))["traceEvents"]
           if e["name"] == "t26.region"]
    assert len(evs) == 1 and evs[0]["cat"] == "t26"
    assert evs[0]["args"] == {"slots_busy": 3, "live_rows": 77}
    host = [h for h in _host_events(str(tmp_path / "xla"))
            if h[0] == "t26.region"]
    assert len(host) == 1
    assert host[0][3]["slots_busy"] == 3 and host[0][3]["live_rows"] == 77
    # the same region, to the clocks' agreement: the annotation encloses
    # the perf_counter pair
    assert (host[0][2] - host[0][1]) / 1e3 >= evs[0]["dur"] * 0.5
    # profiler.scope IS the span
    assert profiler.scope is tele.span


def test_disabled_telemetry_enters_no_annotation(monkeypatch):
    entered = []

    class Fake:
        def __init__(self, name, **kw):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tele, "_Annotation", Fake)
    hist = tele.histogram("t26.off_ms")
    n0 = hist.count
    tele.enable(False)          # what MXNET_TELEMETRY=0 sets at import
    try:
        with tele.span("t26.off", hist=hist, k=1) as sp:
            pass
    finally:
        tele.enable(True)
    assert entered == [] and hist.count == n0
    assert sp.dt >= 0           # the two clock reads remain
    with tele.span("t26.on", hist=hist):
        pass
    assert entered == ["t26.on"] and hist.count == n0 + 1


def test_engine_round_spans_are_disjoint_inside_the_round(toy_engine,
                                                          tmp_path):
    """Every phase of a round is a ``serving.<phase>`` span; the spans of
    one round are disjoint, lie inside its ``serving.round``, and the
    ledger they feed still sums to the round's wall time with ``sched``
    as the remainder."""
    eng = toy_engine
    first = eng.round_table()[-1]["round"] if eng.round_table() else 0
    path = tele.start_trace(str(tmp_path))
    try:
        eng.submit(np.arange(6), max_tokens=5)
        eng.submit(np.arange(3), max_tokens=3)
        eng.serve_forever()
    finally:
        tele.stop_trace()
    evs = [e for e in json.load(open(path))["traceEvents"]
           if e["name"].startswith("serving.") and e["ph"] == "X"]
    rounds = [e for e in evs if e["name"] == "serving.round"]
    # a decode round's counts are no phase: they lie inside the drain
    # of that round's tokens (tests/test_round_counters.py)
    counts = [e for e in evs if e["name"] == "serving.round_counts"]
    phases = [e for e in evs
              if e["name"] not in ("serving.round", "serving.round_counts")]
    assert {"serving.prefill", "serving.decode_round", "serving.drain",
            "serving.h2d"} <= {e["name"] for e in phases}
    dec = [e for e in phases if e["name"] == "serving.decode_round"]
    assert all(1 <= e["args"]["slots_busy"] <= 2 for e in dec)
    assert all(set(e["args"]) == {"slots_busy", "round"} for e in dec)
    assert sorted(e["args"]["round"] for e in counts) \
        == sorted(e["args"]["round"] for e in dec)
    drains = [e for e in phases if e["name"] == "serving.drain"]
    eps = 1e-3                               # microseconds
    for c in counts:
        assert any(d["ts"] - eps <= c["ts"] and c["ts"] + c["dur"]
                   <= d["ts"] + d["dur"] + eps for d in drains), c
    for p in phases:
        inside = [r for r in rounds if r["ts"] - eps <= p["ts"]
                  and p["ts"] + p["dur"] <= r["ts"] + r["dur"] + eps]
        assert len(inside) == 1, p
    for r in rounds:
        mine = sorted((p["ts"], p["ts"] + p["dur"]) for p in phases
                      if r["ts"] - eps <= p["ts"] <= r["ts"] + r["dur"])
        for (_, e0), (s1, _) in zip(mine, mine[1:]):
            assert e0 <= s1 + eps            # disjoint
    rows = [r for r in eng.round_table() if r["round"] > first]
    assert rows and len(rows) <= len(rounds)
    for row in rows:
        assert sum(row["phases_ms"].values()) == pytest.approx(
            row["wall_ms"], abs=2e-3)
        assert row["phases_ms"]["sched"] >= 0
    assert any("dispatch" in r["phases_ms"] and "prefill" in r["phases_ms"]
               for r in rows)
    # the ledger's wall time IS the span's: one clock
    by_len = sorted(r["dur"] / 1e3 for r in rounds)
    for row in rows:
        assert min(abs(row["wall_ms"] - d) for d in by_len) < 1e-3


def test_training_host_spans_in_the_chrome_capture(tmp_path):
    """``train.step`` and ``train.device_wait`` are spans (the device wait
    is the one new host name on the training side)."""
    sym = mx.models.get_resnet_cifar(10, n=1, image_hw=16)
    it = mx.io.NDArrayIter(np.zeros((8, 3, 16, 16), np.float32),
                           np.zeros((8,), np.float32), batch_size=4)
    model = mx.model.FeedForward(sym, ctx=mx.tpu(), num_epoch=1,
                                 optimizer="sgd", learning_rate=0.01)
    wait0 = tele.histogram("train.device_wait_ms").count
    input0 = tele.histogram("train.input_wait_ms").count
    path = tele.start_trace(str(tmp_path))
    try:
        model.fit(it, eval_metric="acc")
    finally:
        tele.stop_trace()
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert names.count("train.step") == 2
    assert names.count("train.device_wait") == 2
    assert names.count("train.epoch") == 1
    assert tele.histogram("train.device_wait_ms").count == wait0 + 2
    # two batches are two waits for input: the epoch's end is neither a
    # sample nor an event
    assert tele.histogram("train.input_wait_ms").count == input0 + 2
    assert names.count("io.input_wait") == 2


def test_program_stats_come_from_the_compiled_executable():
    """On the TPU a lowering reports no cost; ``compile=True`` reads cost
    AND memory (temporaries included) from the compiled executable."""
    import jax.numpy as jnp

    class NoCost:
        """A jitted function whose lowering reports nothing, as the TPU's
        does."""

        def __init__(self, fn):
            self.fn = fn

        def lower(self, *a):
            low = self.fn.lower(*a)

            class Low:
                def cost_analysis(self):
                    return None

                def compile(self):
                    return low.compile()
            return Low()

    jf = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((32, 32), jnp.float32)
    jf(x).block_until_ready()
    fn = NoCost(jf)
    profiler.register_program("t26_prog", fn, (x,), eager=False)
    assert profiler.collect_program_stats().get("t26_prog") is None
    deep = profiler.collect_program_stats(compile=True)["t26_prog"]
    assert deep["flops"] > 0 and deep["bytes_accessed"] > 0
    assert deep["temp_bytes"] >= 0 and deep["argument_bytes"] == 32 * 32 * 4
    assert tele.snapshot()["program"]["t26_prog"]["flops"] == deep["flops"]
    assert not hasattr(profiler, "record_step")
    assert not hasattr(profiler, "benchmark_chain")


def test_a_cache_warmed_without_scopes_does_not_hide_them(tmp_path):
    """JAX's persistent cache leaves metadata out of its key by default,
    so an executable cached by a tree without scopes would be served to
    the tree that has them, names lost. ``import mxnet_tpu`` puts
    metadata into the key: two processes, one cache, the second sees its
    own names."""
    import subprocess
    import sys
    script = r'''
import contextlib, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
if sys.argv[2] == "lib":
    import mxnet_tpu
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
scope = jax.named_scope if sys.argv[3] == "scoped" \
    else (lambda name: contextlib.nullcontext())
def f(x):
    with scope("mx.grads"):
        return jnp.tanh(x @ x).sum()
text = jax.jit(f).lower(jnp.ones((64, 64), jnp.float32)).compile().as_text()
print("NAMES", "mx.grads" in text)
'''
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(cache, lib, scoped):
        out = subprocess.run(
            [sys.executable, "-c", script, str(cache), lib, scoped],
            cwd=root, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert out.returncode == 0, out.stderr[-2000:]
        return "NAMES True" in out.stdout

    # JAX alone: the warm cache hides the names (the default this guards)
    assert run(tmp_path / "a", "jax", "bare") is False
    assert run(tmp_path / "a", "jax", "scoped") is False
    # with the library imported, the same sequence keeps them
    assert run(tmp_path / "b", "lib", "bare") is False
    assert run(tmp_path / "b", "lib", "scoped") is True
    assert jax.config.jax_compilation_cache_include_metadata_in_key
