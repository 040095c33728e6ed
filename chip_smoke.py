#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: every phase below
    python chip_smoke.py --chips 4  # four chips: the sharded paths only

One process, JAX imported once, no child that needs the chip. Phases,
each through the public API (``import mxnet_tpu as mx``):

* ``device``   — refuses anything but a TPU; prints what JAX found.
* ``train``    — ResNet-50 224x224, bf16 compute, through
  ``mx.model.FeedForward(ctx=mx.tpu()).fit``: the fused step ran,
  compiled once, parameters live on the device, the loss falls.
* ``clock``    — the same ResNet step timed two ways (ended by
  ``block_until_ready`` / by a value fetch), both printed.
* ``lm_train`` — the 124M LM (12x768, T=1024, flash attention) through
  ``ParallelTrainer.step``: Pallas kernels in the compiled step, the
  loss falls.
* ``serve``    — ``InferenceEngine`` over the 124M ``Decoder`` (32
  slots, max_len 1024). In bf16 (the serving dtype) every greedy token
  is held to the reference decoder's logits (``score``) and the compile
  contract is checked; in float32 at full matmul precision the same
  requests must come out byte-identical to ``Decoder.generate``
  (``serve_float32``: rounding cannot flip an argmax there, so a read
  of a donated buffer or of a padded bucket row would show); then the
  opt-in kernels (int8/int4 weights through the Pallas matmul).

``--chips 4`` runs only what exists across chips: a dp=2 x tp=2
``ParallelTrainer`` step against the one-device step, and an
``InferenceEngine(tp=4)`` against ``tp=1`` — scored in bf16,
byte-identical in float32 — with the placement of every parameter and
cache shard checked.

Any failed check raises: the exit code is non-zero and the last line
is never printed. The LAST line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Weights and data are random, made from ``--seed``. Depth may be cut by
a test (``SIZES``); the widths are the models' own.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# The platform every phase insists on. The CPU rehearsal test
# (tests/test_chip_smoke.py) patches this and SIZES from inside the
# test; the program itself has no option or variable that relaxes it.
PLATFORM = "tpu"

SIZES = {
    # train / clock: ResNet-50 at ImageNet shape
    "resnet_layers": 50, "resnet_classes": 1000, "image": 224,
    "train_batch": 256, "train_steps": 5, "clock_steps": 6,
    # lm_train and serve: the 124M LM
    "vocab": 32000, "layers": 12, "embed": 768, "heads": 12,
    "seq": 1024, "lm_batch": 8, "lm_steps": 4,
    # serve: the bench_serving defaults (bench.py bench_serving)
    "max_len": 1024, "slots": 32, "buckets": (64, 128, 256),
    "steps_per_round": 8,
    # (prompt length, tokens to generate); the last two arrive while
    # the first six are already decoding
    "requests": ((24, 24), (48, 16), (96, 24), (200, 16), (24, 24),
                 (120, 16), (48, 16), (96, 24)),
    "arm_requests": ((24, 16), (48, 12), (24, 16)),
    # prompt+output are scored in one padded reference pass this long
    "score_len": 256,
    # --chips 4
    "mesh_steps": 3, "tp_slots": 8,
}


# A bf16 token counts as a near-tie of the reference argmax when its
# logit is within this share of the largest |logit| of the scored rows.
# An estimate of what two differently-shaped bf16 programs can differ
# by: the residual stream is rounded to bf16 (2^-9 relative) after each
# of the 2 x 12 sub-layers, so the final hidden state carries about
# sqrt(24) x 2^-9 of relative noise, and a logit (a sum of 768 products
# of random sign, the largest of 32000 some 3-4 deviations out) about
# 2^-8.5 of the largest |logit|. 2^-6 allows six times that; every run
# prints the worst gap it saw as a share of this allowance. What rules
# out a real fault is not this number but the float32 arm, where the
# same requests must be byte-identical.
TIE = 2.0 ** -6


class Smoke:
    """Phase bookkeeping: wall seconds, backend-compile seconds and
    persistent-cache hits per phase, from jax.monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.report = {}

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def run(self, name, fn, *args):
        c0, n0, h0 = self.compile_s, self.compiles, self.cache_hits
        t0 = time.perf_counter()
        print("[%s] start" % name, flush=True)
        out = fn(*args)
        self.report[name] = {
            "seconds": round(time.perf_counter() - t0, 2),
            "compile_seconds": round(self.compile_s - c0, 2),
            "backend_compiles": self.compiles - n0,
            "cache_hits": self.cache_hits - h0}
        print("[%s] ok %s" % (name, json.dumps(self.report[name])),
              flush=True)
        return out


def check(cond, what):
    if not cond:
        raise AssertionError("chip_smoke: " + what)


def peak_gb():
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else round(peak / 2**30, 2)


def on_platform(tree, what):
    """Every array leaf of ``tree`` lives on PLATFORM devices."""
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            plats = {d.platform for d in leaf.devices()}
            check(plats == {PLATFORM},
                  "%s: an array lives on %s, want %s"
                  % (what, sorted(plats), PLATFORM))


def has_kernels(text, what, at_least=1):
    """The program handed to the chip's compiler holds Mosaic kernels
    (``tpu_custom_call`` sites in its lowered text — asking for the
    compiled text means compiling the whole program once more, ~30 s
    for the LM step). Off
    the chip (the CPU rehearsal) kernels run under the Pallas
    interpreter, which inlines them: nothing to look for there."""
    n = text.count("tpu_custom_call")
    if PLATFORM == "tpu":
        check(n >= at_least, "%s: %d tpu_custom_call in the lowered "
              "program, want >= %d" % (what, n, at_least))
    return n


def falls(losses, what):
    check(all(np.isfinite(losses)), "%s: loss not finite: %r"
          % (what, losses))
    check(losses[-1] < losses[0], "%s: loss did not fall: %r"
          % (what, losses))


# -- device ------------------------------------------------------------

def phase_device(args, cache_dir):
    import jax
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                       # not installed off the chip
        libtpu = None
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print("[device] %s jax=%s jaxlib=%s libtpu=%s compile_cache=%s"
          % (json.dumps(dev), jax.__version__, jaxlib.__version__,
             libtpu, cache_dir), flush=True)
    if dev["platform"] != PLATFORM:
        raise SystemExit("chip_smoke: needs a %s, JAX found %s"
                         % (PLATFORM, dev["platform"]))
    if dev["count"] < args.chips:
        raise SystemExit("chip_smoke: --chips %d but JAX found %d "
                         "device(s)" % (args.chips, dev["count"]))
    from mxnet_tpu.ops import pallas_kernels as pk
    check(pk._use_interpret() == (PLATFORM != "tpu"),
          "Pallas kernels would run interpreted on this backend")
    return dev


# -- train + clock -----------------------------------------------------

def phase_train(mx, seed):
    """ResNet-50 through FeedForward.fit on one chip. Returns the fused
    trainer and one staged batch for the clock phase."""
    sz = SIZES
    batch, steps = sz["train_batch"], sz["train_steps"]
    rng = np.random.RandomState(seed)
    data = rng.rand(batch, 3, sz["image"], sz["image"]).astype(np.float32)
    label = rng.randint(0, sz["resnet_classes"], (batch,)) \
        .astype(np.float32)
    # one batch, one epoch per step: the per-epoch metric is that
    # step's loss on the SAME batch, which has to fall
    it = mx.io.NDArrayIter(data, label, batch_size=batch)
    seen = {"loss": [], "trainer": None, "batch": None}

    def on_batch(param):
        seen["loss"].append(float(param.eval_metric.get()[1]))
        seen["trainer"] = param.locals.get("trainer")
        seen["batch"] = param.locals.get("dev_batch")

    steps0 = mx.telemetry.snapshot().get("train", {}).get("steps", 0)
    model = mx.model.FeedForward(
        mx.models.get_resnet(num_classes=sz["resnet_classes"],
                             num_layers=sz["resnet_layers"]),
        ctx=mx.tpu(), num_epoch=steps, optimizer="sgd",
        initializer=mx.initializer.Xavier(factor_type="in",
                                          magnitude=2.34),
        compute_dtype="bfloat16", learning_rate=0.01, momentum=0.9,
        wd=1e-4)
    model.fit(it, eval_metric="ce", batch_end_callback=on_batch)
    trainer = seen["trainer"]
    check(isinstance(trainer, mx.parallel.ParallelTrainer),
          "train: FeedForward.fit ran the legacy executor loop, not "
          "the fused ParallelTrainer step")
    ran = mx.telemetry.snapshot()["train"]["steps"] - steps0
    check(ran == steps, "train: %d fused steps ran, want %d"
          % (ran, steps))
    check(trainer._jit_step._cache_size() == 1,
          "train: the step compiled %d times, want once"
          % trainer._jit_step._cache_size())
    on_platform((trainer.params, trainer.opt_state, trainer.aux),
                "train state")
    check(str(trainer.compute_dtype) == "bfloat16",
          "train: compute dtype is %s" % trainer.compute_dtype)
    falls(seen["loss"], "train")
    print("[train] batch=%d loss=%s peak_gb=%s"
          % (batch, [round(x, 4) for x in seen["loss"]], peak_gb()),
          flush=True)
    on_platform(seen["batch"], "staged batch")
    return trainer, seen["batch"]


def phase_clock(trainer, batch):
    """N ResNet steps ended by block_until_ready against N ended by a
    value fetch. If the two agree, block_until_ready is honest here and
    one timing method is enough."""
    import jax
    n = SIZES["clock_steps"]

    def chain(end):
        t0 = time.perf_counter()
        outs = None
        for _ in range(n):
            outs = trainer.step(batch)
        end(outs)
        return (time.perf_counter() - t0) / n

    def by_block(outs):
        jax.block_until_ready((outs, trainer.params))

    def by_fetch(outs):
        # a value of the LAST step's output and of the carried params
        np.asarray(outs[0][(0,) * outs[0].ndim])
        np.asarray(next(iter(trainer.params.values())).ravel()[0])

    chain(by_block)                                   # settle
    block = [chain(by_block) for _ in range(3)]
    fetch = [chain(by_fetch) for _ in range(3)]
    b, f = sorted(block)[1], sorted(fetch)[1]
    check(b > 0 and f > 0, "clock: non-positive time")
    print("[clock] steps=%d block_until_ready_ms_per_step=%s "
          "value_fetch_ms_per_step=%s ratio=%.3f"
          % (n, [round(x * 1e3, 2) for x in block],
             [round(x * 1e3, 2) for x in fetch], f / b), flush=True)


# -- lm_train ----------------------------------------------------------

def lm_symbol(mx):
    sz = SIZES
    return mx.models.get_transformer_lm(
        sz["vocab"], num_layers=sz["layers"], embed_dim=sz["embed"],
        num_heads=sz["heads"], impl="flash")


def lm_batch(seed):
    sz = SIZES
    rng = np.random.RandomState(seed + 1)
    shape = (sz["lm_batch"], sz["seq"])
    toks = rng.randint(0, sz["vocab"], shape)
    return {"data": toks.astype(np.float32),
            "softmax_label": np.roll(toks, -1, 1).astype(np.float32)}


def lm_trainer(mx, mesh, seed, rules=None):
    sz = SIZES
    shape = (sz["lm_batch"], sz["seq"])
    return mx.parallel.ParallelTrainer(
        lm_symbol(mx), {"data": shape, "softmax_label": shape},
        optimizer="sgd", mesh=mesh, rules=rules, seed=seed,
        compute_dtype="bfloat16",
        initializer=mx.initializer.Uniform(0.02),
        # rescale_grad: the softmax head SUMS its gradient over the
        # B*T tokens; the step below is on their mean
        optimizer_params={"learning_rate": 0.5, "momentum": 0.9,
                          "rescale_grad": 1.0 / (shape[0] * shape[1])})


def lm_loss(outs, batch):
    """Mean next-token cross-entropy of the step's softmax output
    ([B, V, T], the class axis second), computed on the device."""
    import jax
    import jax.numpy as jnp
    label = jnp.asarray(batch["softmax_label"]).astype(jnp.int32)
    picked = jnp.take_along_axis(outs[0], label[:, None, :], axis=1)
    return float(jax.device_get(
        -jnp.mean(jnp.log(jnp.maximum(picked.astype(jnp.float32),
                                      1e-30)))))


def step_text(trainer, batch):
    """Lowered text of the trainer's step program."""
    lr = np.float32(trainer.optimizer.lr)
    with trainer.mesh:
        return trainer._jit_step.lower(
            trainer.params, trainer.opt_state, trainer.aux,
            trainer._shard_batch(batch, "step"), lr,
            np.int32(trainer._t), trainer._rng).as_text()


def phase_lm_train(mx, seed):
    trainer = lm_trainer(mx, mx.parallel.data_parallel_mesh(1), seed)
    trainer.init_params()
    batch = lm_batch(seed)
    losses = [lm_loss(trainer.step(batch), batch)
              for _ in range(SIZES["lm_steps"])]
    on_platform((trainer.params, trainer.opt_state), "lm_train state")
    check(trainer._jit_step._cache_size() == 1,
          "lm_train: the step compiled %d times, want once"
          % trainer._jit_step._cache_size())
    falls(losses, "lm_train")
    # flash attention forward + dQ + dK/dV per layer
    n = has_kernels(step_text(trainer, batch), "lm_train step",
                    at_least=3 * SIZES["layers"])
    print("[lm_train] loss=%s pallas_kernels=%d peak_gb=%s"
          % ([round(x, 4) for x in losses], n, peak_gb()), flush=True)


# -- serve ---------------------------------------------------------------

def lm_params(mx, seed):
    import jax.numpy as jnp
    sz = SIZES
    sym = lm_symbol(mx)
    shapes = {"data": (8, sz["max_len"]),
              "softmax_label": (8, sz["max_len"])}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    rng = np.random.RandomState(seed + 2)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    return sym, params


def make_prompts(spec, seed):
    rng = np.random.RandomState(seed + 3)
    return [(rng.randint(0, SIZES["vocab"], (p,)), n) for p, n in spec]


def offline(dec, prompt, n):
    return np.asarray(dec.generate(prompt[None], num_steps=n)
                      )[0, len(prompt):]


def serve_requests(engine, reqs, late=0):
    """Submit all but the last ``late`` requests, step until the first
    tokens are out, submit the rest mid-stream, run dry."""
    handles = [engine.submit(p, max_tokens=n)
               for p, n in reqs[:len(reqs) - late]]
    if late:
        while not any(len(h.tokens) > 0 for h in handles):
            engine.step()
        handles += [engine.submit(p, max_tokens=n)
                    for p, n in reqs[len(reqs) - late:]]
    engine.serve_forever()
    return [np.asarray(h.result()) for h in handles]


def compile_contract(engine, what):
    """One program per family and used bucket
    (tests/check_utils.py:assert_compile_contract, restated)."""
    cc = engine.compile_counts
    check(cc["decode"] == 1, "%s: decode compiled %d times — %r"
          % (what, cc["decode"], cc))
    check(cc["verify"] <= 1, "%s: verify compiled %d times — %r"
          % (what, cc["verify"], cc))
    for fam in ("prefill", "copy"):
        check(all(v == 1 for v in cc[fam].values()),
              "%s: a %s bucket compiled twice — %r" % (what, fam, cc))
    check(cc["prefill"], "%s: no prefill program ran — %r" % (what, cc))
    return cc


def decode_text(engine):
    """Lowered text of the engine's decode program."""
    return engine._step_fn.lower(
        engine._params, engine._aux, engine._caches,
        engine._state).as_text()


def make_engine(mx, dec, **kw):
    sz = SIZES
    return mx.serving.InferenceEngine(
        dec, slots=kw.pop("slots", sz["slots"]),
        prefill_buckets=sz["buckets"],
        steps_per_round=sz["steps_per_round"], prefix_cache_mb=0,
        prefill_chunk=0, **kw)


def score(dec, prompt, tokens, what):
    """Hold greedy ``tokens`` to the reference ``dec`` by teacher
    forcing: one ``Decoder.prefill`` pass over prompt+tokens gives the
    reference logits at every position, and each emitted token must be
    the argmax there or within ``TIE`` of it. Byte-identity with
    ``Decoder.generate`` is the contract in float32 (the tests pin it);
    in bf16 on the chip two programs of different shape round a
    near-tie differently, and after one such flip the continuations
    part for good, so tokens are judged against the logits of THEIR OWN
    prefix. Returns the number of tokens that were not the argmax
    (near-ties that went the other way) and the largest gap as a share
    of the allowance."""
    p, n = len(prompt), len(tokens)
    seq = np.zeros((1, SIZES["score_len"]), np.int32)
    seq[0, :p] = prompt
    seq[0, p:p + n] = tokens
    logits, _ = dec.prefill(dec.init_cache(1), seq)
    rows = np.asarray(logits[0, p - 1:p - 1 + n], np.float32)  # [n, V]
    check(np.isfinite(rows).all(), "%s: reference logits not finite"
          % what)
    gap = rows.max(-1) - rows[np.arange(n), tokens]
    tol = TIE * np.abs(rows).max()
    check((gap <= tol).all(), "%s: token(s) %r at step(s) %r are %r "
          "below the reference argmax (a near-tie is <= %.4f)"
          % (what, tokens[gap > tol].tolist(),
             np.nonzero(gap > tol)[0].tolist(),
             np.round(gap[gap > tol], 4).tolist(), tol))
    return int((gap > 0).sum()), float(gap.max() / tol)


def judge(dec, reqs, got, what, want=None):
    """Score every request's tokens against the bf16 ``dec`` (see
    ``score``); with ``want``, also count the requests byte-identical
    to it (printed, not required: see ``identical`` for where it is).
    Returns "<n> near-tie tokens, worst gap <x> of the allowance[, <k>
    of <n> requests byte-identical]" for the phase's report line."""
    flips, worst = 0, 0.0
    for i, ((prompt, n), g) in enumerate(zip(reqs, got)):
        check(len(g) == n, "%s: request %d returned %d tokens of %d"
              % (what, i, len(g), n))
        f, w = score(dec, prompt, g, "%s request %d" % (what, i))
        flips, worst = flips + f, max(worst, w)
    said = "%d near-tie tokens of %d, worst gap %.2f of the allowance" \
        % (flips, sum(n for _, n in reqs), worst)
    if want is not None:
        said += ", %d of %d requests byte-identical" % (
            sum(np.array_equal(g, w) for g, w in zip(got, want)),
            len(reqs))
    return said


def identical(got, want, what):
    """Every request's tokens equal the reference's, byte for byte."""
    for i, (g, w) in enumerate(zip(got, want)):
        check(np.array_equal(g, w), "%s: request %d of %d differs from "
              "the reference: %r against %r"
              % (what, i, len(got), g.tolist(), w.tolist()))


def float32_decoder(mx, sym, params):
    return mx.parallel.Decoder(sym, params, max_len=SIZES["max_len"],
                               compute_dtype="float32",
                               weight_dtype="float")


def phase_serve(mx, seed):
    import jax
    sz = SIZES
    sym, params = lm_params(mx, seed)
    dec = mx.parallel.Decoder(sym, params, max_len=sz["max_len"],
                              compute_dtype="bfloat16",
                              weight_dtype="float")
    reqs = make_prompts(sz["requests"], seed)
    # what the quantized engines' tokens are set beside, further down
    arm = make_prompts(sz["arm_requests"], seed + 10)

    engine = make_engine(mx, dec)
    on_platform((engine._params, engine._caches), "serve engine state")
    got = serve_requests(engine, reqs, late=2)
    float_got = serve_requests(engine, arm, late=1)
    cc = compile_contract(engine, "serve")
    # a linear cache: the decode read is the bounded one, a kernel
    n = has_kernels(decode_text(engine), "decode program",
                    at_least=sz["layers"])
    engine.close()
    said = judge(dec, reqs, got, "serve",
                 want=[offline(dec, p, n) for p, n in reqs])
    judge(dec, arm, float_got, "serve/arm")
    print("[serve] bf16 against Decoder.generate: %d requests, every "
          "token within the allowance of the reference argmax, %s; "
          "pallas_kernels=%d compiles=%s peak_gb=%s"
          % (len(reqs), said, n, json.dumps(cc, default=str),
             peak_gb()), flush=True)

    # -- float32, full matmul precision: byte-identity -----------------
    with jax.default_matmul_precision("highest"):
        dec32 = float32_decoder(mx, sym, params)
        e32 = make_engine(mx, dec32)
        got = serve_requests(e32, reqs, late=2)
        cc = compile_contract(e32, "serve_float32")
        e32.close()
        identical(got, [offline(dec32, p, n) for p, n in reqs],
                  "serve_float32")
    del dec32, e32
    print("[serve] float32 against Decoder.generate: %d of %d "
          "requests byte-identical (%d tokens), compiles=%s"
          % (len(reqs), len(reqs), sum(n for _, n in reqs),
             json.dumps(cc, default=str)), flush=True)

    # -- the opt-in kernels --------------------------------------------
    for wd in ("int8", "int4"):
        # a quantized engine is held to the quantized offline decoder;
        # against float weights the contract is argmax stability, which
        # random weights (near-flat logits) cannot show: the agreeing
        # prefix per request is printed, not asserted
        qdec = mx.parallel.Decoder(
            sym, params, max_len=sz["max_len"],
            compute_dtype="bfloat16",
            weight_dtype=wd, matmul_impl="pallas")
        qeng = make_engine(mx, dec, weight_dtype=wd,
                           matmul_impl="pallas")
        got = serve_requests(qeng, arm, late=1)
        compile_contract(qeng, "serve/" + wd)
        n = has_kernels(decode_text(qeng), wd + " decode program",
                        at_least=4 * sz["layers"])
        qeng.close()
        said = judge(qdec, arm, got, "serve/" + wd)
        agree = [int(len(g) if (g == f).all() else np.argmin(g == f))
                 for g, f in zip(got, float_got)]
        print("[serve] %s/pallas against the %s reference decoder: %d "
              "requests, %s, tokens agreeing with the float engine per "
              "request=%r of %r, pallas_kernels=%d"
              % (wd, wd, len(arm), said, agree, [n_ for _, n_ in arm],
                 n), flush=True)


# -- four chips ----------------------------------------------------------

def shard_devices(tree, what, want_ids):
    """Print where every leaf's shards live; every device in
    ``want_ids`` must hold a share of every leaf."""
    import jax
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    per_dev = {}
    for path, leaf in leaves:
        ids = sorted(s.device.id for s in leaf.addressable_shards)
        check(set(ids) == set(want_ids), "%s%s lives on devices %r, "
              "want %r" % (what, jax.tree_util.keystr(path), ids,
                           sorted(want_ids)))
        for s in leaf.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) \
                + s.data.nbytes
    print("[placement] %s: %d arrays, bytes per device %s"
          % (what, len(leaves),
             json.dumps({str(k): v for k, v in sorted(per_dev.items())})),
          flush=True)
    return per_dev


def phase_mesh_train(mx, seed):
    """dp=2 x tp=2 ParallelTrainer step of the 124M LM against the
    one-device step on the same global batch."""
    import jax
    from mxnet_tpu.models.transformer import tp_rules
    devs = jax.devices()[:4]
    batch = lm_batch(seed)
    k = SIZES["mesh_steps"]

    one = lm_trainer(mx, mx.parallel.build_mesh({"dp": 1}, devs[:1]),
                     seed)
    one.init_params()
    init = one.get_params()[0]        # host copies, before any step
    ref = [lm_loss(one.step(batch), batch) for _ in range(k)]
    del one

    mesh = mx.parallel.build_mesh({"dp": 2, "tp": 2}, devs)
    par4 = lm_trainer(mx, mesh, seed,
                      rules=mx.parallel.ShardingRules(
                          mesh, param_rules=tp_rules()))
    par4.init_params(init)
    got = [lm_loss(par4.step(batch), batch) for _ in range(k)]
    print("[mesh_train] one-device loss=%s dp2xtp2 loss=%s"
          % ([round(x, 5) for x in ref], [round(x, 5) for x in got]),
          flush=True)
    falls(got, "mesh_train")
    # same initial weights, same batch: the sharded step only
    # reorders bf16 sums
    tol = 1e-2
    for i, (a, b) in enumerate(zip(ref, got)):
        check(abs(a - b) <= tol * max(1.0, abs(a)),
              "mesh_train: step %d loss %.5f vs one-device %.5f "
              "(tolerance %.0e relative)" % (i, b, a, tol))
    ids = [d.id for d in devs]
    per_dev = shard_devices(par4.params, "mesh_train params", ids)
    total = sum(v.nbytes for v in par4.params.values())
    # tp halves the big matrices: no device holds the whole model
    check(max(per_dev.values()) < total,
          "mesh_train: a device holds %d parameter bytes of %d — "
          "nothing is sharded" % (max(per_dev.values()), total))
    check(par4._jit_step._cache_size() == 1,
          "mesh_train: the step compiled %d times"
          % par4._jit_step._cache_size())


def phase_tp_serve(mx, seed):
    """InferenceEngine(tp=4) against tp=1 on the same requests."""
    import jax
    sz = SIZES
    sym, params = lm_params(mx, seed)
    dec = mx.parallel.Decoder(sym, params, max_len=sz["max_len"],
                              compute_dtype="bfloat16",
                              weight_dtype="float")
    reqs = make_prompts(sz["requests"], seed)
    e1 = make_engine(mx, dec, slots=sz["tp_slots"])
    want = serve_requests(e1, reqs, late=2)
    compile_contract(e1, "tp_serve/tp1")
    e1.close()
    e4 = make_engine(mx, dec, slots=sz["tp_slots"], tp=4)
    got = serve_requests(e4, reqs, late=2)
    cc = compile_contract(e4, "tp_serve/tp4")
    said = judge(dec, reqs, got, "tp_serve", want=want)
    ids = [d.id for d in jax.devices()[:4]]
    shard_devices(e4._params, "tp_serve params", ids)
    per_dev = shard_devices(e4._caches, "tp_serve kv cache", ids)
    whole = sum(leaf.nbytes
                for leaf in jax.tree_util.tree_leaves(e4._caches))
    check(max(per_dev.values()) * 4 == whole,
          "tp_serve: the kv cache is not split four ways (%r of %d)"
          % (per_dev, whole))
    print("[tp_serve] bf16 tp=4 against tp=1: %d requests, every "
          "token within the allowance of the reference argmax, %s; "
          "compiles=%s" % (len(reqs), said, json.dumps(cc, default=str)),
          flush=True)
    e4.close()

    # float32, full matmul precision: tp=4 byte-identical to tp=1
    with jax.default_matmul_precision("highest"):
        dec32 = float32_decoder(mx, sym, params)
        got = {}
        for tp in (1, 4):
            eng = make_engine(mx, dec32, slots=sz["tp_slots"], tp=tp)
            got[tp] = serve_requests(eng, reqs, late=2)
            cc = compile_contract(eng, "tp_serve_float32/tp%d" % tp)
            eng.close()
        identical(got[4], got[1], "tp_serve_float32")
    print("[tp_serve] float32 tp=4 against tp=1: %d of %d requests "
          "byte-identical (%d tokens), compiles=%s"
          % (len(reqs), len(reqs), sum(n for _, n in reqs),
             json.dumps(cc, default=str)), flush=True)


# -- main ------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every one-chip phase (default). 4: only "
                    "the dp x tp trainer and the tp=4 engine, each "
                    "against its one-device reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    import mxnet_tpu as mx
    import mxnet_tpu.models                       # noqa: F401 (mx.models)
    from mxnet_tpu import compile_cache
    with Smoke() as smoke:
        dev = smoke.run("device", phase_device, args,
                        compile_cache.cache_dir())
        compile_cache.enable()   # only once the platform is the right one
        # every weight comes from --seed: the initializers draw from the
        # library's global key (ParallelTrainer's seed= is its dropout
        # rng)
        mx.random.seed(args.seed)
        if args.chips == 4:
            smoke.run("mesh_train", phase_mesh_train, mx, args.seed)
            smoke.run("tp_serve", phase_tp_serve, mx, args.seed)
        else:
            trainer, batch = smoke.run("train", phase_train, mx,
                                       args.seed)
            smoke.run("clock", phase_clock, trainer, batch)
            del trainer, batch
            smoke.run("lm_train", phase_lm_train, mx, args.seed)
            smoke.run("serve", phase_serve, mx, args.seed)
    total = {"seconds": round(time.perf_counter() - t0, 1),
             "compile_seconds": round(smoke.compile_s, 1),
             "backend_compiles": smoke.compiles,
             "cache_hits": smoke.cache_hits, "phases": smoke.report}
    print("[total] %s" % json.dumps(total), flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
