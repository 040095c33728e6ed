"""Headline benchmark — the BASELINE.json north star.

Primary metric: ResNet-50 ImageNet-shape training throughput on one chip
(fused ParallelTrainer step: forward+backward+SGD in ONE XLA program,
bf16 compute / f32 master params, device-resident synthetic data).
North-star target: >=2,000 img/s/chip (BASELINE.md; the reference's own
published anchor is Inception-BN at ~113 img/s/GPU on 4x Titan X,
example/image-classification/README.md:247-257).

Also measured (reported in the same JSON line under "extra"):
* resnet50 batch-128 variant and an MFU estimate (model FLOPs / peak),
* the round-1 CIFAR Inception-BN-28-small metric (vs 842 img/s GTX 980),
* input-pipeline throughput: fresh host batches fed through
  trainer.prefetch (h2d overlap on the real chip) instead of a resident
  batch, and the C++ ImageRecordIOIter on synthetic packed RecordIO,
* telemetry overhead: the fused step with mx.telemetry collection on
  vs off, asserted within 2% (doc/observability.md); the run's full
  telemetry snapshot is recorded into BENCH_extra.json.

Prints ONE JSON line: {"metric","value","unit","vs_baseline","extra"}.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import jax
import numpy as np

NORTH_STAR_IMG_PER_SEC = 2000.0   # ResNet-50 target, img/s/chip
CIFAR_BASELINE = 842.0            # Inception-BN-28-small, 1x GTX 980
# Inception-BN ImageNet: 2,844 s/epoch on 4x Titan X = ~113 img/s/GPU
# (reference example/image-classification/README.md:254)
INCEPTION_BN_TITANX_BASELINE = 113.0

# ResNet-50 @224: ~4.1 GFLOP forward per image; backward ~2x forward.
_RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.1e9

_PEAK_FLOPS = {
    # bf16 peak per chip
    "TPU v4": 275e12,
    "TPU v5e": 197e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
}


def _peak_flops(dev):
    kind = getattr(dev, "device_kind", "")
    for k, v in _PEAK_FLOPS.items():
        if kind.lower().startswith(k.lower()):
            return v
    # an unknown device is an error, not a default: an MFU against a
    # guessed peak is not a number
    raise RuntimeError(
        "bench: no peak FLOP/s on record for device_kind %r (known: %s)"
        % (kind, ", ".join(sorted(_PEAK_FLOPS))))


def _require_tpu():
    """bench.py measures the chip or nothing: no CPU numbers under
    device-metric names."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("bench.py: needs a TPU, JAX found %s (%s)"
                         % (dev.platform, dev.device_kind))
    _peak_flops(dev)
    return dev


def _timed_steps(trainer, batch, steps):
    """Seconds per `steps` training steps.

    Times two chain lengths that END IN A REAL VALUE FETCH (which
    provably forces completion of the whole donated-param dependency
    chain) and differences them, cancelling the constant fetch/dispatch
    overhead. Whether a plain ``block_until_ready`` is as honest on the
    current chip is what ``chip_smoke.py``'s clock phase reports; the
    benchmark PR (ROADMAP S1) keeps one method.
    """
    def chain(n):
        tic = time.perf_counter()
        outs = None
        for _ in range(n):
            outs = trainer.step(batch)
        np.asarray(outs[0][(0,) * outs[0].ndim])  # force completion
        return time.perf_counter() - tic

    chain(3)  # warmup/compile
    for _ in range(3):
        t1 = chain(steps)
        t2 = chain(2 * steps)
        if t2 - t1 > 0.02 * t1:  # sane difference, not host jitter
            return t2 - t1
    # no sane difference in three tries: fall back to the conservative
    # whole-chain time (includes the fixed flush cost -> underestimates
    # throughput)
    return t2 / 2.0


def _make_trainer_and_batches(sym, shapes, n_classes, compute_dtype,
                              opt_params, int_data=False):
    """Shared setup: fused trainer + synthetic host/device batches."""
    import jax
    from mxnet_tpu import parallel as par

    trainer = par.ParallelTrainer(
        sym, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
        compute_dtype=compute_dtype, optimizer_params=opt_params)
    trainer.init_params()
    rng = np.random.RandomState(0)
    batch = shapes["data"][0]
    if int_data:  # token ids (LM): data AND label are class indices
        hostb = {"data": rng.randint(0, n_classes, shapes["data"]
                                     ).astype(np.float32),
                 "softmax_label": rng.randint(
                     0, n_classes, shapes["softmax_label"]
                 ).astype(np.float32)}
    else:
        hostb = {"data": rng.rand(*shapes["data"]).astype(np.float32),
                 "softmax_label": rng.randint(0, n_classes, (batch,)
                                              ).astype(np.float32)}
    devb = {k: jax.device_put(v, trainer._data_sh[k])
            for k, v in hostb.items()}
    return trainer, hostb, devb


def bench_resnet50(batch, steps=20):
    from mxnet_tpu.models import get_resnet

    sym = get_resnet(num_classes=1000, num_layers=50)
    shapes = {"data": (batch, 3, 224, 224), "softmax_label": (batch,)}
    trainer, hostb, devb = _make_trainer_and_batches(
        sym, shapes, 1000, "bfloat16",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
    # device-resident batch: the compute-bound number
    dt = _timed_steps(trainer, devb, steps)
    ips = batch * steps / dt

    # fresh host batches through the double-buffered prefetcher: proves
    # h2d overlap (the reference overlaps IO via its Prefetcher thread);
    # same two-length difference method as _timed_steps
    def host_stream(n):
        for _ in range(n):
            yield hostb

    def chain_h2d(n):
        tic = time.perf_counter()
        outs = None
        for db in trainer.prefetch(host_stream(n)):
            outs = trainer.step(db)
        np.asarray(outs[0][(0,) * outs[0].ndim])
        return time.perf_counter() - tic

    chain_h2d(2)
    ips_h2d = None
    for _ in range(3):
        t1 = chain_h2d(steps // 2)
        t2 = chain_h2d(steps)
        if t2 - t1 > 0.02 * t1:
            ips_h2d = batch * (steps - steps // 2) / (t2 - t1)
            break
    if ips_h2d is None:  # no sane difference: whole-chain rate
        ips_h2d = batch * steps / t2

    mfu = ips * _RESNET50_TRAIN_FLOPS_PER_IMG / _peak_flops(jax.devices()[0])
    return ips, ips_h2d, mfu


def bench_inception_bn(batch=128, steps=15):
    """Inception-BN ImageNet-shape (the reference's BIG published
    table — INCEPTION_BN_TITANX_BASELINE img/s/GPU)."""
    from mxnet_tpu.models import get_inception_bn

    sym = get_inception_bn(num_classes=1000)
    shapes = {"data": (batch, 3, 224, 224), "softmax_label": (batch,)}
    trainer, _, devb = _make_trainer_and_batches(
        sym, shapes, 1000, "bfloat16",
        {"learning_rate": 0.1, "momentum": 0.9})
    dt = _timed_steps(trainer, devb, steps)
    return batch * steps / dt


def bench_cifar(batch=128, steps=200):
    """CIFAR Inception-BN-28-small training vs the GTX 980 baseline
    (BASELINE.md: 842 img/s). The step is sub-ms, so per-step host
    dispatch swamps it. The whole chain runs INSIDE one compiled program
    (ParallelTrainer.multi_step = lax.scan over the fused step with
    donated params — the same transform that fixed the GEMM
    calibration), timed as the N-vs-2N program difference ending in a
    real value fetch. 200 steps, not 30: the increment has to be long
    against per-chain host jitter for repeats to agree. Returns
    (img_per_sec, relative_spread)."""
    from mxnet_tpu.models import get_inception_bn_small

    sym = get_inception_bn_small(num_classes=10)
    shapes = {"data": (batch, 3, 28, 28), "softmax_label": (batch,)}
    trainer, _, devb = _make_trainer_and_batches(
        sym, shapes, 10, None,
        {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4})
    probe = trainer.param_names[0]

    def run(n):
        tic = time.perf_counter()
        trainer.multi_step(devb, n)
        w = trainer.params[probe]
        np.asarray(w[(0,) * w.ndim])  # force completion of the chain
        return time.perf_counter() - tic

    run(steps)       # compile both program lengths
    run(2 * steps)
    diffs = []
    for _ in range(3):
        t1, t2 = run(steps), run(2 * steps)
        if t2 - t1 > 0.02 * t1:
            diffs.append((t2 - t1) / steps)
    if not diffs:
        return None, None
    per_step = sorted(diffs)[len(diffs) // 2]
    spread = (max(diffs) - min(diffs)) / per_step
    return batch / per_step, spread


def bench_transformer_lm(batch=8, seq=1024, layers=12, embed=768,
                         heads=12, vocab=32000, steps=8):
    """Long-context flagship: transformer LM train step (flash-attention
    Pallas kernels, bf16) — tokens/s on one chip. The reference has no
    attention-era baseline; this anchors the long-context stack's
    single-chip number (multi-chip sp/ring scaling is exercised by
    dryrun_multichip and test_parallel)."""
    from mxnet_tpu.models import get_transformer_lm

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="flash")
    shapes = {"data": (batch, seq), "softmax_label": (batch, seq)}
    trainer, _, devb = _make_trainer_and_batches(
        sym, shapes, vocab, "bfloat16",
        {"learning_rate": 1e-3, "momentum": 0.9}, int_data=True)
    dt = _timed_steps(trainer, devb, steps)
    tokens_per_step = batch * seq
    # 6*N FLOPs/token (fwd+bwd) for N non-embedding params + attention
    n_params = layers * (12 * embed * embed) + vocab * embed
    flops_per_tok = 6.0 * n_params + 12.0 * layers * embed * seq
    tps = tokens_per_step * steps / dt
    import jax as _jax
    mfu = tps * flops_per_tok / _peak_flops(_jax.devices()[0])
    return tps, mfu


def bench_decode(prompt=64, layers=12, embed=768,
                 heads=12, vocab=32000, max_len=1024):
    """KV-cache autoregressive decode (parallel/decode.py): per-token
    latency of the 124M LM generating with donated caches, the whole
    loop one compiled lax.scan program. Timed as the N-vs-2N-steps
    difference (prefill and dispatch cancel).

    Chains are LONG (448 steps at max_len 1024, 1024 at 4096): a
    64-step chain's N-vs-2N increment is ~50 ms, inside host jitter.
    Long chains also fill the cache to near max_len, the
    serving-relevant regime. Prompts are FRESH random values every
    run.

    Arms: the offline step's dense read at b8 and a batch sweep
    (b1/8/32) at max_len 1024, the long cache at max_len 4096 (the
    read touches the whole 1.2 GB buffer every step), and the
    int8-quantized cache (measured SLOWER — kept as a memory knob,
    see doc/performance.md). Returns a dict of arms:
    {name: {"ms_per_token": x, "tokens_per_sec": y}}."""
    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder

    if wall_reps is None:
        wall_reps = 3 if jax.default_backend() == "tpu" else 0
    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="flash")
    rng = np.random.RandomState(0)
    # infer params at the LONGEST arm's length so one pos_embed table
    # serves every decoder (a larger table than max_len is valid)
    shapes = {"data": (8, 4 * max_len),
              "softmax_label": (8, 4 * max_len)}
    def init_params(s):
        arg_shapes, _, _ = s.infer_shape(**shapes)
        return {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                               .astype(np.float32))
                for n, sh in zip(s.list_arguments(), arg_shapes)
                if n not in shapes}

    params = init_params(sym)
    # (max_len - prompt) // 2 // 64 * 64 silently floors to 0 when the
    # prompt nearly fills max_len, and measure() then returns None for
    # every arm — misconfiguration must fail loudly instead
    assert max_len - prompt >= 128, (
        "bench_decode: max_len (%d) must exceed prompt (%d) by >= 128 "
        "tokens to leave a measurable decode chain" % (max_len, prompt))
    steps_short = (max_len - prompt) // 2 // 64 * 64  # 448 at 1024
    steps_long = max_len                              # 1024 at L4096

    def measure(dec, steps, batch):
        def run(n):
            ptoks = rng.randint(0, vocab, (batch, prompt))
            tic = time.perf_counter()
            np.asarray(dec.generate(ptoks, n))
            return time.perf_counter() - tic

        run(steps)
        run(2 * steps)  # compile both programs
        diffs = []
        for _ in range(3):
            t1, t2 = run(steps), run(2 * steps)
            if t2 - t1 > 0.02 * t1:
                diffs.append((t2 - t1) / steps)
        if not diffs:
            return None
        per_tok = float(np.median(diffs))
        return {"ms_per_token": round(per_tok * 1e3, 3),
                "tokens_per_sec": round(batch / per_tok, 0)}

    full = Decoder(sym, params, max_len=max_len,
                   compute_dtype="bfloat16")
    arms = {"full_b8": measure(full, steps_short, 8)}
    for bs in (1, 32):
        arms["full_b%d" % bs] = measure(full, steps_short, bs)
    # long-cache story: at 4x the cache the read pays for the whole
    # buffer every step
    long_full = Decoder(sym, params, max_len=4 * max_len,
                        compute_dtype="bfloat16")
    arms["full_b8_L%d" % (4 * max_len)] = measure(long_full,
                                                  steps_long, 8)
    # int8 KV (memory knob): halves cache bytes, measured slower
    int8_full = Decoder(sym, params, max_len=max_len,
                        compute_dtype="bfloat16",
                        cache_dtype="int8")
    int8_long = Decoder(sym, params, max_len=4 * max_len,
                        compute_dtype="bfloat16", cache_dtype="int8")
    arms["int8_full_b8"] = measure(int8_full, steps_short, 8)
    arms["int8_full_b8_L%d" % (4 * max_len)] = measure(int8_long,
                                                       steps_long, 8)
    # GQA (num_kv_heads=2 of 12): K/V cache 6x smaller — the grouped
    # projection also drops ~12M params, both cuts honest decode wins
    gqa_sym = get_transformer_lm(vocab, num_layers=layers,
                                 embed_dim=embed, num_heads=heads,
                                 num_kv_heads=2, impl="flash")
    gqa_long = Decoder(gqa_sym, init_params(gqa_sym),
                       max_len=4 * max_len, compute_dtype="bfloat16")
    arms["gqa2_full_b8_L%d" % (4 * max_len)] = measure(gqa_long,
                                                       steps_long, 8)
    return arms


def bench_serving(slots=32, layers=12, embed=768, heads=12, vocab=32000,
                  max_len=1024, n_requests=96, seed=0, arrival_ms=1.0,
                  cache_dtype=None, weight_dtype=None, matmul_impl=None):
    """Continuous-batching serving engine (mxnet_tpu/serving/) under
    SATURATING load: Poisson arrivals far above service capacity (the
    queue never empties), mixed prompt lengths across the bucket set
    and mixed output budgets — the ISSUE 3 headline. Same 124M LM as
    bench_decode, so ``tokens_per_sec`` reads directly against the
    static ``full_b8`` arm: the static decoder serves b=8 rectangular
    batches that stall on their slowest member, the engine keeps
    ``slots`` sequences resident and refills each slot the moment it
    frees (iteration-level scheduling).

    Exactly TWO compiled program families run the whole workload (one
    fused decode step + one prefill per used bucket) — asserted here,
    not just documented. Latency is reported as per-token DECODE
    cadence per request, (t_done - t_first)/(n_tokens - 1): the p99
    tail is what co-residency costs a request, independent of queue
    wait (which saturating arrivals make unbounded by construction).

    ``cache_dtype`` selects the fp (bf16 compute) or int8-KV flavor —
    same workload, same seeds, compile contract asserted per arm. The
    returned dict also carries ``decode_bytes_accessed`` /
    ``decode_flops`` from the XLA cost analysis of THIS arm's decode
    program (PR 9 program gauges).

    Returns {"tokens_per_sec", "p50_ms_per_token", "p99_ms_per_token",
    "slots", "requests", "tokens", "compile_programs", ...}.
    """
    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import InferenceEngine

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="flash")
    rng = np.random.RandomState(seed)
    shapes = {"data": (8, max_len), "softmax_label": (8, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    # capped at max_len so smoke geometries below the chip-default
    # 256 top bucket stay constructible (identical at the default)
    buckets = tuple(b for b in (64, 128, 256) if b <= max_len) \
        or (max_len,)
    # decoder pinned float: weight_dtype is an ENGINE-level axis here
    # (an env-int8 decoder would refuse an explicit fp arm)
    dec = Decoder(sym, params, max_len=max_len,
                  compute_dtype="bfloat16",
                  cache_dtype=cache_dtype, weight_dtype="float")

    def workload(n, rs):
        """(prompt, max_tokens) mix: prompts spread over the bucket
        set, output budgets 32..160 — deliberately ragged so static
        batching's stall-on-slowest cost is visible."""
        out = []
        for _ in range(n):
            p = min(int(rs.choice([24, 48, 96, 120, 200, 256])),
                    buckets[-1], max_len - 1)  # no-op at the default
            t = int(rs.choice([32, 64, 96, 160]))
            out.append((rs.randint(0, vocab, (p,)), t))
        return out

    def run(n, rs, engine):
        reqs = workload(n, rs)
        # Poisson arrivals, mean interarrival ``arrival_ms``: the 1 ms
        # default is far above service capacity, so the queue never
        # empties (saturating regime — the headline criterion);
        # tools/bench_serving.py sweeps slower rates for the
        # latency-vs-load curve
        arrivals = np.cumsum(rs.exponential(arrival_ms * 1e-3, size=n))
        t0 = time.perf_counter()
        handles, i = [], 0
        while i < len(reqs) or not engine.idle:
            now = time.perf_counter() - t0
            while i < len(reqs) and arrivals[i] <= now \
                    and engine.queued() < engine.max_queue:
                prompt, mt = reqs[i]
                handles.append(engine.submit(prompt, max_tokens=mt))
                i += 1
            engine.step()
        dt = time.perf_counter() - t0
        toks = sum(len(h.tokens) for h in handles)
        tpot = [(h.t_done - h.t_first) / (len(h.tokens) - 1) * 1e3
                for h in handles if len(h.tokens) > 1]
        return toks, dt, tpot

    # steps_per_round=8: each dispatched round decodes 8 tokens per
    # slot inside one lax.scan program, amortizing the per-dispatch
    # host overhead over 8 tokens (what that overhead is on the
    # current chip: not measured).
    # Prefix cache OFF here: this arm is the raw continuous-batching
    # headline (comparable across rounds); bench_serving_prefix
    # measures the cache and chunking on a workload built for them.
    engine = InferenceEngine(dec, slots=slots, prefill_buckets=buckets,
                             max_queue=4 * slots, steps_per_round=8,
                             prefix_cache_mb=0, prefill_chunk=0,
                             weight_dtype=weight_dtype,
                             matmul_impl=matmul_impl)
    # warmup compiles BOTH program families for every bucket up front
    # (one prompt per bucket), so the timed run measures execution only
    wrs = np.random.RandomState(seed + 1)
    for b in buckets:
        engine.submit(wrs.randint(0, vocab, (b - 8,)), max_tokens=8)
    engine.serve_forever()
    toks, dt, tpot = run(n_requests, np.random.RandomState(seed + 2),
                         engine)
    cc = engine.compile_counts
    programs = cc["decode"] + sum(cc["prefill"].values())
    assert cc["decode"] == 1 and all(v == 1
                                     for v in cc["prefill"].values()) \
        and not cc["copy"], \
        "compile-count contract violated: %r" % (cc,)
    # this arm's decode-program cost analysis (the PR 9 program
    # gauges, re-registered by THIS engine's first dispatch)
    from mxnet_tpu import profiler as _prof
    import mxnet_tpu as _mx
    _prof.collect_program_stats()
    prog = _mx.telemetry.snapshot().get("program", {}) \
        .get("serving_decode", {})
    return {
        "tokens_per_sec": round(toks / dt, 0),
        "p50_ms_per_token": round(float(np.percentile(tpot, 50)), 3),
        "p99_ms_per_token": round(float(np.percentile(tpot, 99)), 3),
        "slots": slots,
        "requests": n_requests,
        "tokens": toks,
        "compile_programs": programs,
        "cache_dtype": cache_dtype or "bf16",
        "weight_dtype": engine.weight_dtype,
        "weight_bytes": engine.weight_bytes,
        "matmul_impl": engine.matmul_impl,
        "decode_bytes_accessed": prog.get("bytes_accessed"),
        "decode_flops": prog.get("flops"),
    }


def bench_serving_tp(tp=1, slots=16, layers=12, embed=768, heads=12,
                     vocab=32000, max_len=1024, n_requests=48, seed=0,
                     arrival_ms=2.0, steps_per_round=8):
    """Tensor-parallel serving sweep arm (ISSUE 14): the SAME workload
    and seeds at every degree — the engine contract makes greedy
    outputs byte-identical across tp, so each arm returns a digest of
    its token streams and ``main()`` asserts the sweep agrees before
    reporting any number. Reported per arm: tokens/s, p99 decode
    cadence, per-shard decode-program ``bytes_accessed`` (the sharded
    program's XLA cost analysis carries the shard_map body's LOCAL
    shapes, so the PR 9 ``program.serving_decode`` gauge IS the
    per-shard read — the multi-chip win condition: decode is
    memory-bound and the KV read is what shards), and the
    ``serving.kv_bytes_per_shard`` residency gauge. ``heads`` must
    divide every swept degree (12 covers tp in {1, 2, 4})."""
    import hashlib

    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import InferenceEngine

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="dense")
    rng = np.random.RandomState(seed)
    shapes = {"data": (8, max_len), "softmax_label": (8, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    buckets = tuple(b for b in (64, 128, 256) if b <= max_len) \
        or (max_len,)
    dec = Decoder(sym, params, max_len=max_len,
                  compute_dtype="bfloat16")
    engine = InferenceEngine(dec, slots=slots, prefill_buckets=buckets,
                             max_queue=4 * slots,
                             steps_per_round=steps_per_round,
                             prefix_cache_mb=0, prefill_chunk=0, tp=tp)
    wrs = np.random.RandomState(seed + 1)
    for b in buckets:           # warm every program family up front
        engine.submit(wrs.randint(0, vocab, (b - 8,)), max_tokens=8)
    engine.serve_forever()

    reqs = []
    rs = np.random.RandomState(seed + 2)
    for _ in range(n_requests):
        p = min(int(rs.choice([24, 48, 96, 120, 200, 256])),
                buckets[-1], max_len - 1)
        t = int(rs.choice([32, 64, 96]))
        reqs.append((rs.randint(0, vocab, (p,)), t))
    arrivals = np.cumsum(rs.exponential(arrival_ms * 1e-3,
                                        size=n_requests))
    t0 = time.perf_counter()
    handles, i = [], 0
    while i < len(reqs) or not engine.idle:
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now \
                and engine.queued() < engine.max_queue:
            prompt, mt = reqs[i]
            handles.append(engine.submit(prompt, max_tokens=mt))
            i += 1
        engine.step()
    dt = time.perf_counter() - t0
    toks = sum(len(h.tokens) for h in handles)
    tpot = [(h.t_done - h.t_first) / (len(h.tokens) - 1) * 1e3
            for h in handles if len(h.tokens) > 1]
    cc = engine.compile_counts
    assert cc["decode"] == 1 and all(v == 1
                                     for v in cc["prefill"].values()) \
        and not cc["copy"], \
        "compile-count contract violated at tp=%d: %r" % (tp, cc)
    digest = hashlib.sha256()
    for h in handles:
        digest.update(np.asarray(h.tokens, np.int64).tobytes())
    from mxnet_tpu import profiler as _prof
    import mxnet_tpu as _mx
    _prof.collect_program_stats()
    snap = _mx.telemetry.snapshot()
    prog = snap.get("program", {}).get("serving_decode", {})
    return {
        "tp": tp,
        "tokens_per_sec": round(toks / dt, 1),
        "p50_ms_per_token": round(float(np.percentile(tpot, 50)), 3),
        "p99_ms_per_token": round(float(np.percentile(tpot, 99)), 3),
        "tokens": toks,
        "requests": n_requests,
        "decode_bytes_accessed_per_shard": prog.get("bytes_accessed"),
        "decode_flops_per_shard": prog.get("flops"),
        "kv_bytes_per_shard":
            snap.get("serving", {}).get("kv_bytes_per_shard"),
        "digest": digest.hexdigest(),
    }


def bench_serving_quant_bytes(layers=12, embed=768, heads=12,
                              vocab=32000, max_len=1024, slots=32,
                              steps_per_round=8, cache_dtype=None,
                              hbm_gb=16.0, wall_reps=None):
    """Decode-bytes probe at the SERVING-BATCH geometry (ISSUE 15's
    headline config — the 124M LM, the KV side already cut by the
    bounded read): lower the fp and int8-weight
    decode programs and read their XLA cost analysis WITHOUT running
    traffic — the PR 9 gauge arithmetic at a geometry the CPU box
    could never serve end-to-end.

    Two ratios per arm pair, both recorded because they answer
    different questions:

    * ``forward_bytes_*`` / ``forward_ratio``: the slot-walk decode
      forward (``Decoder._run_slots`` — embedding, every projection,
      the attention read, the head), i.e. the bytes a GREEDY round
      actually executes. This is the honest weight-stream read: the
      weight matmuls dominate it at serving batch.
    * ``program_bytes_*`` / ``program_ratio``: the full serving_decode
      program — what the live ``program.serving_decode`` gauge shows.
      It is DILUTED by the sampling branch: the engine wraps the
      per-slot categorical in ``lax.cond`` so greedy rounds never
      execute it, but XLA's static cost model counts both branches —
      ~S x vocab of threefry/categorical arithmetic that scales with
      slots, not with the model. The same static-model caveat family
      as PR 11's "the interpreter executes every grid step".

    Also derives ``slots_at_hbm``: (hbm - weight bytes) / KV bytes
    per slot — the max-resident-slots read at a fixed HBM budget (the
    slots-per-chip lever the ROADMAP names; the weight cut frees HBM
    that converts to resident slots at any model scale).

    PR 17 widens the arm set beyond the fp/int8-fori pair: the int8
    Pallas ``quant_matmul`` arm (dequant-in-VMEM, no chunk-loop HLO),
    and the int4 arm (packed nibbles + per-group scales). Three byte
    columns per arm, because they answer different questions:

    * ``weight_stream_bytes`` / ``weight_stream_ratio_*``: the
      ANALYTIC stored bytes one greedy decode step actually streams —
      every matmul weight at its stored width (bf16 for fp, int8 +
      per-channel f32 scales, packed nibbles + per-group scales) plus
      only the GATHERED embedding rows (the table itself is never
      read by a decode step). This is the headline: it is exact,
      impl-invariant (``pallas`` walks the same stored stream as
      ``dense``, staging bounded in VMEM), and
      it is what HBM serves on hardware. int4 lands at ~0.27x fp
      (0.5 nibble + group-scale overhead vs. 2-byte bf16), int8 at
      ~0.51x — the ISSUE 17 / ISSUE 15 numbers.
    * ``forward_bytes`` / ``program_bytes``: the XLA static cost
      model of the compiled program (on the TPU a ``Lowered`` reports
      none), kept for continuity with the PR 15 column. On the quantized arms it is NOT comparable across
      impls: the cost model caps ``fori_loop`` trip counts (it
      under-counts the dense arms' weight stream at high chunk
      counts) and, on the kernel arms, the CPU interpreter's HLO
      materializes full-size dequant/unpack temporaries that live in
      VMEM on hardware (it over-counts, the PR 11 static-model caveat
      family). Read the stream column for cross-impl claims.

    Each arm also reports ``wall_ms`` — the median wall clock of the
    compiled decode forward (``wall_reps`` timed runs; default:
    skipped off-TPU, where the interpreter executes every grid step
    and a 124M compile takes tens of minutes — pass ``wall_reps=3``
    to force)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import InferenceEngine

    if wall_reps is None:
        wall_reps = 3 if jax.default_backend() == "tpu" else 0
    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="flash")
    rng = np.random.RandomState(0)
    shapes = {"data": (8, max_len), "softmax_label": (8, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}

    def cost(compiled):
        # of the COMPILED program: on the TPU a Lowered's
        # cost_analysis() is None
        c = compiled.cost_analysis()
        if isinstance(c, list):
            c = c[0]
        if not c or "bytes accessed" not in c:
            raise RuntimeError("the compiled program reports no "
                               "bytes-accessed cost (got %r)" % (c,))
        return c["bytes accessed"]

    def weight_stream(eng):
        """Analytic stored bytes one greedy decode step streams:
        every matmul weight at stored width; embedding tables
        contribute only the ``slots`` gathered rows (one token per
        slot per step)."""
        from mxnet_tpu.serving.quant import QuantizedTensor
        gather = dec._embedding_weight_names()
        total = 0
        for n, v in eng._params.items():
            leaves = ((v.q, v.scale) if isinstance(v, QuantizedTensor)
                      else jax.tree_util.tree_leaves(v))
            nbytes = sum(x.nbytes for x in leaves)
            if n in gather:
                rows = max(x.shape[0] for x in leaves)
                nbytes = slots * (nbytes // rows)
            total += nbytes
        return total

    out = {"config": {"layers": layers, "embed": embed, "vocab": vocab,
                      "max_len": max_len, "slots": slots,
                      "cache_dtype": cache_dtype or "bf16"}}
    # ONE float decoder serves both engine arms (the supported
    # pattern: the int8 engine quantizes its own parameter copy);
    # pinned float regardless of the env default — an env-int8
    # decoder would refuse the fp arm
    dec = Decoder(sym, params, max_len=max_len,
                  compute_dtype="bfloat16",
                  cache_dtype=cache_dtype, weight_dtype="float")
    buckets = tuple(b for b in (64, 128, 256) if b <= max_len) \
        or (max_len,)
    arms = [("fp", "float", "dense"),
            ("int8", "int8", "dense"),
            ("int8_pallas", "int8", "pallas"),
            ("int4", "int4", "pallas")]
    for key, wd, mi in arms:
        eng = InferenceEngine(
            dec, slots=slots, prefill_buckets=buckets,
            max_queue=4 * slots, steps_per_round=steps_per_round,
            prefix_cache_mb=0, prefill_chunk=0, weight_dtype=wd,
            matmul_impl=mi)
        prog = jax.jit(eng._make_step()).lower(
            eng._params, eng._aux, eng._caches, eng._state).compile()
        pos = jnp.zeros((slots,), jnp.int32)
        toks = jnp.zeros((slots, 1), jnp.int32)
        fwd = jax.jit(
            lambda p, a, c, po, t, _mi=mi: dec._run_slots(
                p, a, c, po, t, mm_impl=_mi)).lower(
            eng._params, eng._aux, eng._caches, pos, toks).compile()
        kv_bytes = sum(x.nbytes for x in
                       jax.tree_util.tree_leaves(eng._caches))
        # wall clock of the compiled single-step forward: warm once,
        # report the median of wall_reps timed runs
        wall = None
        if wall_reps:
            args = (eng._params, eng._aux, eng._caches, pos, toks)
            jax.block_until_ready(fwd(*args))
            ts = []
            for _ in range(wall_reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fwd(*args))
                ts.append(time.perf_counter() - t0)
            wall = round(sorted(ts)[len(ts) // 2] * 1e3, 1)
        out[key] = {
            "program_bytes": cost(prog),
            "forward_bytes": cost(fwd),
            "weight_stream_bytes": weight_stream(eng),
            "weight_bytes": eng.weight_bytes,
            "kv_bytes_per_slot": kv_bytes // slots,
            "slots_at_hbm": int((hbm_gb * 1e9 - eng.weight_bytes)
                                // (kv_bytes / slots)),
            "wall_ms": wall,
        }
    for k in ("program", "forward"):
        f, q = out["fp"][k + "_bytes"], out["int8"][k + "_bytes"]
        out[k + "_ratio"] = None if not f or not q else round(q / f, 3)
    fp_fwd = out["fp"]["forward_bytes"]
    for key, _, _ in arms[2:]:
        q = out[key]["forward_bytes"]
        out["forward_ratio_%s" % key] = \
            None if not fp_fwd or not q else round(q / fp_fwd, 3)
    fp_stream = out["fp"]["weight_stream_bytes"]
    for key, _, _ in arms[1:]:
        out["weight_stream_ratio_%s" % key] = round(
            out[key]["weight_stream_bytes"] / fp_stream, 3)
    out["weight_bytes_ratio"] = round(
        out["int8"]["weight_bytes"] / out["fp"]["weight_bytes"], 3)
    out["weight_bytes_ratio_int4"] = round(
        out["int4"]["weight_bytes"] / out["fp"]["weight_bytes"], 3)
    return out


def bench_serving_quant(slots=32, layers=12, embed=768, heads=12,
                        vocab=32000, max_len=1024, n_requests=96,
                        seed=0):
    """Weight-only int8 quantization A/B (ISSUE 15): the SAME
    saturating bench_serving workload served with float (bf16
    compute) weights and with int8 weights + per-output-channel f32
    scales (doc/serving.md "Quantized weights") — compile contract
    asserted inside each arm. The headline is the decode program's
    ``bytes_accessed`` ratio int8/fp (PR 9 cost gauges): at serving
    batch the weight stream dominates decode bytes, and the chunked
    scale-fused matmul reads it at 1 byte/elem with no materialized
    float copy. ``weight_bytes_ratio`` is the stored-footprint cut
    (more resident slots per HBM byte); tokens/s is the wall-clock
    read, with the PR 11/14 caveat — on the CPU box the chunked
    dequant loop serializes work XLA would overlap on chip, so the
    bytes cut is the honest CPU metric and wall clock is the TPU
    lever."""
    # both arms pin their dtype explicitly: with
    # MXNET_SERVING_WEIGHT_DTYPE=int8 exported (the knob this arm
    # documents) a None here would silently serve int8 on BOTH sides
    # and report ~1.0 ratios
    fp = bench_serving(slots=slots, layers=layers, embed=embed,
                       heads=heads, vocab=vocab, max_len=max_len,
                       n_requests=n_requests, seed=seed,
                       weight_dtype="float")
    q8 = bench_serving(slots=slots, layers=layers, embed=embed,
                       heads=heads, vocab=vocab, max_len=max_len,
                       n_requests=n_requests, seed=seed,
                       weight_dtype="int8")
    ba_f, ba_q = fp.get("decode_bytes_accessed"), \
        q8.get("decode_bytes_accessed")
    return {
        "fp": fp,
        "int8": q8,
        "bytes_accessed_ratio":
            None if not ba_f or not ba_q else round(ba_q / ba_f, 3),
        "weight_bytes_ratio":
            None if not fp.get("weight_bytes")
            else round(q8["weight_bytes"] / fp["weight_bytes"], 3),
        "tokens_per_sec_ratio":
            None if not fp.get("tokens_per_sec")
            else round(q8["tokens_per_sec"] / fp["tokens_per_sec"], 2),
    }


def bench_serving_prefix(slots=16, layers=12, embed=768, heads=12,
                         vocab=32000, max_len=1024, n_requests=48,
                         seed=0, arrival_ms=6.0, hit_rate=0.9,
                         shared_len=192, tail_len=32, long_frac=0.25,
                         long_len=512, out_tokens=(32, 48, 64),
                         chunk=0, prefix_cache_mb=256,
                         steps_per_round=8):
    """ONE serving-engine config under a shared-system-prompt workload
    (the ISSUE 5 arm): a ``hit_rate`` fraction of requests start with
    the same ``shared_len``-token system prompt (unique ``tail_len``
    tails), the rest are unique — and ``long_frac`` of THOSE are
    ``long_len``-token prompts, the chunked-prefill stressor (a
    monolithic long prefill stalls every resident decode slot; chunked,
    the stall is bounded by one ``chunk``). Arrivals are Poisson at a
    SUB-saturating ``arrival_ms`` so TTFT measures prefill work, not
    unbounded queue wait.

    Called with cache on vs off (same workload, same seed) the TTFT
    delta is the prefix cache's saved prefill FLOPs; with ``chunk`` on
    vs off the cadence p99 delta is what long-prompt admission costs
    co-resident requests. ``tools/bench_serving.py`` sweeps
    hit-rate x chunk over this same function.

    ``prefix_cache_mb`` defaults to 256 HERE (not the engine's 64):
    one pool slot of the 124M/max_len-1024 bf16 geometry is ~37 MiB,
    and a 1-slot pool would measure eviction churn (every unique-
    prompt retention evicts the shared entry), not steady-state hits.

    Returns {"ttft_p50_ms", "ttft_mean_ms", "cadence_p50_ms",
    "cadence_p99_ms", "tokens_per_sec", "prefix_hit_tokens",
    "prefill_chunks", "compile_programs", ...config echo}.
    """
    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import InferenceEngine

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="flash")
    rng = np.random.RandomState(seed)
    shapes = {"data": (8, max_len), "softmax_label": (8, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    # power-of-2 buckets capped at max_len (smoke configs shrink
    # max_len below the chip-default 512 top bucket)
    buckets = tuple(b for b in (64, 128, 256, 512) if b <= max_len)
    if not buckets or buckets[-1] < min(max_len, 512):
        buckets += (max_len,)
    dec = Decoder(sym, params, max_len=max_len,
                  compute_dtype="bfloat16")
    engine = InferenceEngine(dec, slots=slots, prefill_buckets=buckets,
                             max_queue=4 * slots,
                             steps_per_round=steps_per_round,
                             prefix_cache_mb=prefix_cache_mb,
                             prefill_chunk=chunk)

    wl_rng = np.random.RandomState(seed + 1)
    shared = wl_rng.randint(0, vocab, (shared_len,))

    def workload(n, rs):
        out = []
        for _ in range(n):
            if rs.uniform() < hit_rate:
                p = np.concatenate(
                    [shared, rs.randint(0, vocab, (tail_len,))])
            elif rs.uniform() < long_frac:
                p = rs.randint(0, vocab, (long_len,))
            else:
                p = rs.randint(0, vocab, (shared_len + tail_len,))
            out.append((p, int(rs.choice(out_tokens))))
        return out

    # warmup: compile every program family this workload can touch
    # (prefill buckets, decode, and — cache on — the hit/retention
    # copies, by serving the shared prefix twice) and leave the cache
    # in steady state so the timed run measures hits, not cold misses
    wrs = np.random.RandomState(seed + 2)
    for p, t in workload(6, wrs) + [
            (np.concatenate([shared, wrs.randint(0, vocab,
                                                 (tail_len,))]), 8),
            (wrs.randint(0, vocab, (long_len,)), 8)]:
        engine.submit(p, max_tokens=t)
    engine.serve_forever()

    hit0 = engine.stats["prefix_hit_tokens"]
    chunks0 = engine.stats["prefill_chunks"]
    reqs = workload(n_requests, np.random.RandomState(seed + 3))
    arrivals = np.cumsum(
        np.random.RandomState(seed + 4).exponential(
            arrival_ms * 1e-3, size=n_requests))
    t0 = time.perf_counter()
    handles, i = [], 0
    while i < len(reqs) or not engine.idle:
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now \
                and engine.queued() < engine.max_queue:
            prompt, mt = reqs[i]
            handles.append(engine.submit(prompt, max_tokens=mt))
            i += 1
        engine.step()
    dt = time.perf_counter() - t0
    toks = sum(len(h.tokens) for h in handles)
    ttft = [(h.t_first - h.t_submit) * 1e3 for h in handles]
    tpot = [(h.t_done - h.t_first) / (len(h.tokens) - 1) * 1e3
            for h in handles if len(h.tokens) > 1]
    cc = engine.compile_counts
    assert cc["decode"] == 1 \
        and all(v == 1 for v in cc["prefill"].values()) \
        and all(v == 1 for v in cc["copy"].values()), \
        "compile-count contract violated: %r" % (cc,)
    return {
        "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 3),
        "ttft_mean_ms": round(float(np.mean(ttft)), 3),
        "cadence_p50_ms": round(float(np.percentile(tpot, 50)), 3),
        "cadence_p99_ms": round(float(np.percentile(tpot, 99)), 3),
        "tokens_per_sec": round(toks / dt, 0),
        "prefix_hit_tokens": engine.stats["prefix_hit_tokens"] - hit0,
        "prefill_chunks": engine.stats["prefill_chunks"] - chunks0,
        "compile_programs": cc["decode"] + sum(cc["prefill"].values())
                            + sum(cc["copy"].values()),
        "hit_rate": hit_rate,
        "chunk": chunk,
        "prefix_cache_mb": engine.prefix_cache_mb,
        "requests": n_requests,
    }


def bench_serving_spec(slots=16, layers=12, embed=768, heads=12,
                       vocab=32000, max_len=1024, n_requests=48,
                       seed=0, arrival_ms=6.0, block_len=24, repeats=4,
                       tail_len=8, out_tokens=(48, 64, 96), spec_k=0,
                       steps_per_round=8, weight_scale=0.15):
    """ONE serving-engine config under a REPETITION-FRIENDLY workload
    (the ISSUE 10 arm): few-shot-style prompts — a ``block_len``-token
    block tiled ``repeats`` times plus a unique tail — whose periodic
    structure (and the greedy decode's own self-repetition) is exactly
    what the n-gram drafter proposes from. Arrivals are Poisson at a
    SUB-saturating ``arrival_ms`` so the cadence tail measures decode
    behavior, not queue wait.

    ``spec_k=0`` serves the spec-OFF baseline; ``spec_k>0`` serves
    n-gram drafting at that K. ``weight_scale`` defaults to 0.15, NOT
    the 0.05 of the other serving arms: at 0.05 a random-weight LM's
    greedy outputs are far less self-consistent than any trained
    model's (they hop between attractors), which under-measures the
    accept rate the mechanism gets on real weights; at 0.15 greedy
    outputs settle into stable continuations — a closer proxy for a
    trained model's predictability — while the per-dispatch COSTS
    being measured are weight-value-independent. Called with both
    arms on the same workload and seeds, the A/B isolates what
    draft-and-verify buys:
    ``accept_per_step`` is mean tokens emitted per slot per verify
    dispatch (accepted drafts + the corrected token — every one the
    target's own choice, so outputs are byte-identical across arms;
    the headline "accepted tokens per target-model step") and the
    tokens/s ratio is the speedup at equal correctness. p99 cadence is
    reported so the chunkier drain cadence is visibly bounded
    (acceptance: <= 1.1x the spec-off p99).

    Returns {"tokens_per_sec", "cadence_p50_ms", "cadence_p99_ms",
    "accept_per_step", "accept_rate", "spec_rounds",
    "fallback_rounds", "compile_programs", ...config echo}.
    """
    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import InferenceEngine

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="flash")
    rng = np.random.RandomState(seed)
    shapes = {"data": (8, max_len), "softmax_label": (8, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(
        rng.uniform(-weight_scale, weight_scale, sh).astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    buckets = tuple(b for b in (64, 128, 256) if b <= max_len) \
        or (max_len,)
    dec = Decoder(sym, params, max_len=max_len,
                  compute_dtype="bfloat16")
    engine = InferenceEngine(
        dec, slots=slots, prefill_buckets=buckets,
        max_queue=4 * slots, steps_per_round=steps_per_round,
        prefix_cache_mb=0, prefill_chunk=0,
        draft="ngram" if spec_k else "off",
        spec_k=spec_k or None)

    wl_rng = np.random.RandomState(seed + 1)

    def workload(n, rs):
        out = []
        for _ in range(n):
            block = rs.randint(0, vocab, (block_len,))
            p = np.concatenate([np.tile(block, repeats),
                                rs.randint(0, vocab, (tail_len,))])
            p = p[:min(buckets[-1], max_len - max(out_tokens) - 1)]
            out.append((p, int(rs.choice(out_tokens))))
        return out

    # warmup compiles every program family (prefill buckets, decode,
    # verify once a draft fires — the repetitive prompt guarantees
    # proposals) so the timed run measures execution only
    for p, t in workload(4, np.random.RandomState(seed + 2)):
        engine.submit(p, max_tokens=t)
    engine.serve_forever()

    import mxnet_tpu as _mx

    def _accept_hist():
        s = _mx.telemetry.snapshot().get("serving", {})
        h = s.get("spec_accepted_per_step", {"count": 0, "sum": 0})
        return h.get("count", 0), h.get("sum", 0)

    rounds0 = engine.stats["spec_rounds"]
    fall0 = engine.stats["spec_fallback_rounds"]
    drafted0 = engine.stats["spec_drafted"]
    acc0 = engine.stats["spec_accepted"]
    hist_n0, hist_sum0 = _accept_hist()
    reqs = workload(n_requests, np.random.RandomState(seed + 3))
    arrivals = np.cumsum(
        np.random.RandomState(seed + 4).exponential(
            arrival_ms * 1e-3, size=n_requests))
    t0 = time.perf_counter()
    handles, i = [], 0
    while i < len(reqs) or not engine.idle:
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now \
                and engine.queued() < engine.max_queue:
            prompt, mt = reqs[i]
            handles.append(engine.submit(prompt, max_tokens=mt))
            i += 1
        engine.step()
    dt = time.perf_counter() - t0
    toks = sum(len(h.tokens) for h in handles)
    tpot = [(h.t_done - h.t_first) / (len(h.tokens) - 1) * 1e3
            for h in handles if len(h.tokens) > 1]
    spec_rounds = engine.stats["spec_rounds"] - rounds0
    drafted = engine.stats["spec_drafted"] - drafted0
    accepted = engine.stats["spec_accepted"] - acc0
    cc = engine.compile_counts
    assert cc["decode"] == 1 and cc["verify"] == (1 if spec_k else 0) \
        and all(v == 1 for v in cc["prefill"].values()) \
        and not cc["copy"], \
        "compile-count contract violated: %r" % (cc,)
    # accepted tokens per target-model step: accepted drafts + the
    # corrected token each drafted slot emits per verify dispatch —
    # every emitted token is the target's own choice. The per-slot
    # shape rides the serving.spec_accepted_per_step histogram; its
    # count delta is exactly the drafted slot-steps of the timed run.
    # Spec-off arms report 1.0 (one token per slot-step, by definition
    # of the plain decode program).
    hist_n, hist_sum = _accept_hist()
    n_slot_steps = hist_n - hist_n0
    accept_per_step = round(
        1.0 + (hist_sum - hist_sum0) / float(n_slot_steps)
        if spec_k and n_slot_steps else 1.0, 3)
    return {
        "tokens_per_sec": round(toks / dt, 0),
        "cadence_p50_ms": round(float(np.percentile(tpot, 50)), 3),
        "cadence_p99_ms": round(float(np.percentile(tpot, 99)), 3),
        "accept_per_step": accept_per_step,
        "accept_rate": None if not drafted
        else round(accepted / float(drafted), 3),
        "spec_rounds": spec_rounds,
        "fallback_rounds": engine.stats["spec_fallback_rounds"] - fall0,
        "drafted_tokens": drafted,
        "accepted_tokens": accepted,
        "compile_programs": cc["decode"] + cc["verify"]
                            + sum(cc["prefill"].values()),
        "spec_k": spec_k,
        "requests": n_requests,
        "tokens": toks,
    }


def bench_serving_overload(slots=16, layers=12, embed=768, heads=12,
                           vocab=32000, max_len=512, n_requests=64,
                           seed=0, prompt_len=96, out_tokens=32,
                           slo_factor=3.0):
    """Overload-policy A/B (ISSUE 7): ONE engine — same weights, same
    compiled programs, policy knobs flipped between arms — serves an
    IDENTICAL 2x-saturating Poisson arrival schedule twice:

    * ``overload='block'``, queue deep enough for the whole run: every
      request is accepted and ages in the queue; its SLO deadline
      keeps ticking, so backlogged requests die at the round sweep
      (cheap) or mid-flight after wasting prefill + decode slot-time.
    * ``overload='shed'``, queue bounded at ``slots``: excess submits
      fail fast with ``EngineOverloaded`` (zero engine work wasted —
      the router would retry another replica); admitted requests keep
      most of their deadline budget and complete.

    Saturation is CALIBRATED, not assumed: a full-batch warm pass
    measures the service rate, arrivals run at 2x it, and the SLO is
    ``slo_factor`` x the full-batch service time. Goodput counts
    tokens of requests that COMPLETED (eos/length) per wall second —
    deadline-retired work is wasted capacity, shed requests cost
    nothing. Headline: ``serving_shed_goodput_ratio`` = shed goodput /
    block goodput (> 1 when shedding protects the serving capacity).

    Returns {"goodput_ratio", "block": {...}, "shed": {...},
    "slo_ms", "service_req_per_s", "compile_programs"}.
    """
    import jax.numpy as jnp
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import InferenceEngine, EngineOverloaded

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="flash")
    rng = np.random.RandomState(seed)
    shapes = {"data": (8, max_len), "softmax_label": (8, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    prompt_len = min(prompt_len, max_len - out_tokens - 1)
    bucket = next(b for b in (64, 128, 256, max_len)
                  if b >= prompt_len and b <= max_len)
    dec = Decoder(sym, params, max_len=max_len,
                  compute_dtype="bfloat16")
    engine = InferenceEngine(dec, slots=slots,
                             prefill_buckets=(bucket,),
                             max_queue=4 * n_requests,
                             steps_per_round=8, prefix_cache_mb=0)

    wl = np.random.RandomState(seed + 1)
    prompts = [wl.randint(0, vocab, (prompt_len,))
               for _ in range(n_requests)]

    # warmup (compiles) + calibration: a full batch of `slots`
    # concurrent requests measures the service rate the arrival
    # process must double
    for p in prompts[:slots]:
        engine.submit(p, max_tokens=out_tokens)
    engine.serve_forever()        # includes the compile; re-run timed
    for p in prompts[:slots]:
        engine.submit(p, max_tokens=out_tokens)
    t0 = time.perf_counter()
    engine.serve_forever()
    batch_s = time.perf_counter() - t0
    service_rate = slots / batch_s              # req/s at capacity
    slo_ms = slo_factor * batch_s * 1e3
    inter = 1.0 / (2.0 * service_rate)          # 2x saturation

    def run_arm(policy, max_queue):
        engine.overload, engine.max_queue = policy, max_queue
        arrivals = np.cumsum(np.random.RandomState(seed + 2)
                             .exponential(inter, size=n_requests))
        handles, shed, i = [], 0, 0
        t0 = time.perf_counter()
        while i < n_requests or not engine.idle:
            now = time.perf_counter() - t0
            while i < n_requests and arrivals[i] <= now:
                try:
                    handles.append(engine.submit(
                        prompts[i], max_tokens=out_tokens,
                        deadline_ms=slo_ms))
                except EngineOverloaded:
                    shed += 1
                except MXNetError:
                    break       # block backpressure: drain first
                i += 1
            for h in engine.step():
                pass
        dt = time.perf_counter() - t0
        good = [h for h in handles
                if h.retire_reason in ("eos", "length")]
        missed = sum(1 for h in handles
                     if h.retire_reason == "deadline")
        return {
            "goodput_tokens_per_sec":
                round(sum(len(h.tokens) for h in good) / dt, 1),
            "completed": len(good),
            "deadline_missed": missed,
            "shed": shed,
            "wall_s": round(dt, 3),
        }

    block = run_arm("block", 4 * n_requests)
    shed = run_arm("shed", slots)
    engine.overload, engine.max_queue = "block", 4 * n_requests
    cc = engine.compile_counts
    assert cc["decode"] == 1 \
        and all(v == 1 for v in cc["prefill"].values()) \
        and not cc["copy"], \
        "compile-count contract violated: %r" % (cc,)
    ratio = None if not block["goodput_tokens_per_sec"] else round(
        shed["goodput_tokens_per_sec"]
        / block["goodput_tokens_per_sec"], 3)
    return {
        "goodput_ratio": ratio,
        "block": block,
        "shed": shed,
        "slo_ms": round(slo_ms, 1),
        "service_req_per_s": round(service_rate, 2),
        "arrival_req_per_s": round(2 * service_rate, 2),
        "compile_programs": cc["decode"] + sum(cc["prefill"].values()),
    }


def bench_serving_replay(slots=8, layers=12, embed=768, heads=12,
                         vocab=32000, max_len=1024, n_requests=64,
                         seed=0, burst=6, burst_gap_ms=80.0,
                         shared_len=96, tail_len=16, long_len=384,
                         out_tokens=(24, 32, 48), chunk=128,
                         spec_k=4, steps_per_round=8,
                         prefix_cache_mb=256):
    """Day-in-the-life replay arm (ISSUE 13, the capture/replay bench
    ROADMAP item 5 asks for): capture a BURSTY mixed-traffic run once
    — arrivals in synchronized bursts of ``burst`` (the p99-hostile
    shape Poisson smooths away), a mix of shared-prefix, long-prompt
    and short unique requests — then replay the SAME capture with
    ``tools/replay_serving.py``'s machinery on fresh engines per
    config, ``verify`` on: every replay must reproduce the captured
    tokens byte-identically while the config under test (speculation
    off; chunking off) moves only the latencies.

    The record run serves with the full stack armed (prefix cache +
    chunked prefill + n-gram speculation + capture). Reported per
    arm: tokens/s, TTFT p50, cadence p99, verified counts (asserted
    complete), and the compile contract. ``capture_overhead_frac``
    is a clean A/B of the rolling tape: the same-config WARM replay
    with capture off vs an identical warm replay with capture armed
    (same schedule, same prefix-cache state — comparing against the
    record run instead would confound capture cost with cache
    warmth)."""
    import shutil
    import tempfile

    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import InferenceEngine, load_capture
    from tools import replay_serving

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="flash")
    rng = np.random.RandomState(seed)
    shapes = {"data": (8, max_len), "softmax_label": (8, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    buckets = tuple(b for b in (64, 128, 256, 512) if b <= max_len) \
        or (max_len,)
    shared_len = min(shared_len, max_len // 4)
    long_len = min(long_len, max_len // 2)
    chunk = min(chunk, buckets[-1])

    def decoder():
        return Decoder(sym, params, max_len=max_len,
                       compute_dtype="bfloat16")

    base_cfg = dict(slots=slots, prefill_buckets=buckets,
                    max_queue=4 * max(slots, burst),
                    steps_per_round=steps_per_round,
                    prefix_cache_mb=prefix_cache_mb,
                    prefill_chunk=chunk, draft="ngram", spec_k=spec_k)

    wl_rng = np.random.RandomState(seed + 1)
    shared = wl_rng.randint(0, vocab, (shared_len,))

    def workload(n, rs):
        """Bursty mixed day-in-the-life traffic: arrival offsets come
        in bursts (every member of a burst arrives at the same
        instant), prompts mix shared-prefix / long / short-unique."""
        reqs, arrivals, t = [], [], 0.0
        for i in range(n):
            if i % burst == 0 and i:
                t += float(rs.exponential(burst_gap_ms * 1e-3))
            arrivals.append(t)
            u = rs.uniform()
            if u < 0.5:
                p = np.concatenate(
                    [shared, rs.randint(0, vocab, (tail_len,))])
            elif u < 0.75:
                p = rs.randint(0, vocab, (long_len,))
            else:
                p = rs.randint(0, vocab, (tail_len * 3,))
            reqs.append((p, int(rs.choice(out_tokens))))
        return reqs, arrivals

    cap_dir = tempfile.mkdtemp(prefix="mx_bench_capture_")
    try:
        engine = InferenceEngine(decoder(), capture_dir=cap_dir,
                                 **base_cfg)
        # warmup compiles every program family up front (captured too
        # — the replay arms then re-serve the warmup, which keeps the
        # record-vs-replay comparison apples-to-apples); the shared
        # prefix is served once so the timed run starts with the
        # cache warm, like bench_serving_prefix
        wrs = np.random.RandomState(seed + 2)
        for b in buckets:
            engine.submit(wrs.randint(0, vocab, (min(b - 8,
                                                     max_len - 64),)),
                          max_tokens=8)
        engine.submit(np.concatenate(
            [shared, wrs.randint(0, vocab, (tail_len,))]),
            max_tokens=8)
        engine.serve_forever()

        reqs, arrivals = workload(n_requests,
                                  np.random.RandomState(seed + 3))
        t0 = time.perf_counter()
        handles, i = [], 0
        while i < len(reqs) or not engine.idle:
            now = time.perf_counter() - t0
            while i < len(reqs) and arrivals[i] <= now \
                    and engine.queued() < engine.max_queue:
                prompt, mt = reqs[i]
                handles.append(engine.submit(prompt, max_tokens=mt))
                i += 1
            engine.step()
        dt = time.perf_counter() - t0
        toks = sum(len(h.tokens) for h in handles)
        cc = engine.compile_counts
        assert cc["decode"] == 1 and cc["verify"] <= 1 \
            and all(v == 1 for v in cc["prefill"].values()) \
            and all(v == 1 for v in cc["copy"].values()), \
            "compile-count contract violated: %r" % (cc,)
        cap_path = engine.capture.path
        cap_bytes = engine.capture.bytes_written
        engine.close()
        cap = load_capture(cap_path)
        # record-run throughput measured from the CAPTURE itself, over
        # the full captured timeline (warmup included) — the same
        # window and submit stream the replay arms span, so
        # capture_overhead_frac diffs like against like; `toks`/`dt`
        # from the timed loop above cover only the bursty window and
        # would over-read the record run by the warmup gap
        first_t = min(s["t"] for s in cap["submits"])
        last_t = max(r["t"] for r in cap["retires"].values())
        rec_toks = sum(len(r["tokens"])
                       for r in cap["retires"].values())
        record = {
            "tokens_per_sec": round(rec_toks / (last_t - first_t), 1),
            "burst_window_tokens_per_sec": round(toks / dt, 1),
            "requests": n_requests,
            "capture_bytes": cap_bytes,
            "capture_records": len(cap["submits"])
            + len(cap["retires"]) + 1,
            **replay_serving.recorded_latency(cap),
        }

        arms = {}
        total_verified = total_mismatch = 0
        for name, overrides in (
                ("same_config", {}),
                ("spec_off", {"draft": "off"}),
                ("chunk_off", {"prefill_chunk": 0})):
            eng = replay_serving.build_engine(cap, decoder(),
                                              **overrides)
            # two passes: the first pays this fresh engine's compiles
            # inside the replay window (verify still on), the SECOND
            # is the warm latency/throughput read — comparable to the
            # record run, which also ran warmed (the compile contract
            # pins that pass 2 added zero programs)
            cold = replay_serving.replay(cap, eng, timing="recorded",
                                         verify=True)
            rep = replay_serving.replay(cap, eng, timing="recorded",
                                        verify=True)
            cc = eng.compile_counts
            assert cc["decode"] == 1 and cc["verify"] <= 1 \
                and all(v == 1 for v in cc["prefill"].values()) \
                and all(v == 1 for v in cc["copy"].values()), \
                "replay %s compile contract violated: %r" % (name, cc)
            eng.close()
            total_verified += rep["verified"] + rep["verified_prefix"]
            total_mismatch += len(cold["mismatches"]) \
                + len(rep["mismatches"])
            arms[name] = {k: rep[k] for k in
                          ("tokens_per_sec", "ttft_p50_ms",
                           "cadence_p50_ms", "cadence_p99_ms",
                           "verified", "verified_prefix",
                           "verify_skipped")}
            arms[name]["mismatches"] = len(rep["mismatches"])
            arms[name]["cold_ttft_p50_ms"] = cold["ttft_p50_ms"]
        assert total_mismatch == 0, \
            "replay verify found %d mismatches" % total_mismatch
        # capture-overhead A/B: the cost of the rolling tape measured
        # like against like — same config, same recorded schedule,
        # both on their WARM pass (the capture-off side is the
        # same_config arm above; comparing either against the record
        # run would confound capture cost with prefix-cache state,
        # since a second service of the same stream takes hits the
        # first never had). Positive = capture costs wall time.
        cap2_dir = tempfile.mkdtemp(prefix="mx_bench_capture_ab_")
        try:
            eng_on = replay_serving.build_engine(cap, decoder(),
                                                 capture_dir=cap2_dir)
            replay_serving.replay(cap, eng_on, timing="recorded")
            rep_on = replay_serving.replay(cap, eng_on,
                                           timing="recorded")
            eng_on.close()
        finally:
            shutil.rmtree(cap2_dir, ignore_errors=True)
        same_tps = arms["same_config"]["tokens_per_sec"]
        on_tps = rep_on["tokens_per_sec"]
        return {
            "record": record,
            **arms,
            "verified_total": total_verified,
            "capture_on_warm_tokens_per_sec": on_tps,
            "capture_overhead_frac":
                None if not on_tps
                else round(same_tps / on_tps - 1.0, 4),
        }
    finally:
        shutil.rmtree(cap_dir, ignore_errors=True)


def bench_serving_fleet(replicas=2, slots=4, layers=2, embed=128,
                        heads=4, vocab=4000, max_len=128,
                        n_requests=32, seed=11, shared_len=24,
                        tail_len=8, out_tokens=(8, 12, 16)):
    """Fleet-resilience arm (ISSUE 16): capture a mixed-traffic run on
    ONE engine, then replay it twice — (a) through a single fresh
    replica (the control), and (b) through a ``replicas``-wide
    :class:`FleetRouter` while every replica is drained and replaced
    in turn mid-replay (the rolling-restart drill), byte-identity
    verified both times. The headline pair: ``zero_failed_restart``
    (1 = every request completed and verified byte-identical through
    the restart — the ISSUE 16 acceptance bar) and
    ``failover_p99_ms`` (p99 wall cost of one drain: snapshot +
    live migration + successor join — the pause an operator's
    rolling deploy injects per replica). Deliberately small model:
    the metrics are host-side scheduling costs, not device math."""
    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import (InferenceEngine, FleetRouter,
                                   load_capture)
    from tools import replay_serving
    import shutil
    import tempfile

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="dense")
    rng = np.random.RandomState(seed)
    shapes = {"data": (4, max_len), "softmax_label": (4, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    buckets = (32, 64)

    def decoder():
        return Decoder(sym, params, max_len=max_len)

    base_cfg = dict(slots=slots, prefill_buckets=buckets,
                    max_queue=4 * slots, prefix_cache_mb=1,
                    prefill_chunk=16)
    shared = rng.randint(0, vocab, (shared_len,))
    cap_dir = tempfile.mkdtemp(prefix="mx_bench_fleet_")
    try:
        engine = InferenceEngine(decoder(), capture_dir=cap_dir,
                                 **base_cfg)
        for i in range(n_requests):
            p = np.concatenate(
                [shared, rng.randint(0, vocab, (tail_len,))]) \
                if rng.uniform() < 0.5 \
                else rng.randint(0, vocab, (tail_len * 2,))
            while engine.queued() >= engine.max_queue:
                engine.step()        # backpressure: drain, then admit
            engine.submit(p, max_tokens=int(rng.choice(out_tokens)))
        engine.serve_forever()
        cap_path = engine.capture.path
        engine.close()
        cap = load_capture(cap_path)

        # control: one fresh replica, no restarts
        ctrl = replay_serving.build_engine(cap, decoder())
        single = replay_serving.replay(cap, ctrl, timing="max",
                                       verify=True)
        ctrl.close()

        # the drill: a fleet, every replica drained+replaced mid-replay
        fleet = FleetRouter(
            [replay_serving.build_engine(cap, decoder())
             for _ in range(replicas)],
            heartbeat_ms=50)
        drain_ms = []
        base_hook = replay_serving.rolling_restart(
            fleet, cap,
            lambda: replay_serving.build_engine(cap, decoder()))

        def on_round(submitted, eng):
            live_before = len(fleet.replica_ids(live_only=True))
            t0 = time.perf_counter()
            base_hook(submitted, eng)
            if len(fleet.replica_ids(live_only=True)) != live_before \
                    or fleet.stats["drains"] > len(drain_ms):
                drain_ms.append((time.perf_counter() - t0) * 1e3)

        rep = replay_serving.replay(cap, fleet, timing="max",
                                    verify=True, on_round=on_round)
        # per-replica compile contract on the survivors (each replica
        # compiles its own families; the fleet adds no programs) — a
        # spare that joined after the last milestone and never served
        # a round has compiled nothing at all
        for rid in fleet.replica_ids(live_only=True):
            rep_eng = fleet.replica(rid)
            cc = rep_eng.compile_counts
            if not rep_eng.stats["steps"]:
                assert cc["decode"] == 0, \
                    "idle fleet spare compiled: %r" % (cc,)
                continue
            assert cc["decode"] == 1 and cc["verify"] <= 1 \
                and all(v == 1 for v in cc["prefill"].values()) \
                and all(v == 1 for v in cc["copy"].values()), \
                "fleet replica compile contract violated: %r" % (cc,)
        stats = dict(fleet.stats)
        fleet.close()
        zero_failed = int(not rep["mismatches"]
                          and rep["replayed"] == rep["requests"]
                          and stats.get("drains", 0) >= replicas
                          and stats.get("migrated_requests", 0) > 0)
        return {
            "replicas": replicas,
            "requests": n_requests,
            "single": {k: single[k] for k in
                       ("tokens_per_sec", "ttft_p50_ms",
                        "cadence_p99_ms", "verified",
                        "verified_prefix")},
            "fleet_restart": {
                **{k: rep[k] for k in
                   ("tokens_per_sec", "ttft_p50_ms", "cadence_p99_ms",
                    "verified", "verified_prefix")},
                "mismatches": len(rep["mismatches"]),
                "drains": stats.get("drains", 0),
                "migrated_requests": stats.get("migrated_requests", 0),
                "affinity_hits": stats.get("affinity_hits", 0),
            },
            "failover_p99_ms":
                None if not drain_ms
                else round(float(np.percentile(drain_ms, 99)), 3),
            "zero_failed_restart": zero_failed,
        }
    finally:
        shutil.rmtree(cap_dir, ignore_errors=True)


def bench_serving_disagg(slots=4, layers=2, embed=128, heads=4,
                         vocab=4000, max_len=160, n_requests=36,
                         seed=13, short_len=12, long_len=112,
                         short_out=16, long_out=6, long_every=4):
    """Disaggregated prefill/decode arm (ISSUE 18): the SAME
    long-prompt adversarial mix — a steady stream of short decodes
    with a near-max-bucket prompt every ``long_every`` submits, the
    traffic shape whose chunked prefill rounds steal decode cadence —
    served by (a) a 2-replica UNIFIED fleet and (b) a 1 prefill + 1
    decode specialist fleet at the same chip count, outputs
    byte-compared request-by-request. Headline pair:
    ``disagg_decode_p99_ratio`` = disagg cadence p99 / unified cadence
    p99 (lower is better; <= ~1 is the acceptance bar — decode
    specialists never dispatch a prefill round, so long prompts stop
    blocking everyone else's cadence) and
    ``disagg_handoff_bytes_per_req`` (the transfer cost one request's
    KV handoff ships). A third int8-transfer arm re-runs the disagg
    fleet with ``handoff_dtype="int8"`` to pin the ~half-fp-bytes
    encoding ratio. Small model on purpose: the contention being
    measured is scheduling, not device math."""
    import jax.numpy as jnp
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import (InferenceEngine, FleetRouter,
                                   EngineOverloaded)

    sym = get_transformer_lm(vocab, num_layers=layers, embed_dim=embed,
                             num_heads=heads, impl="dense")
    rng = np.random.RandomState(seed)
    shapes = {"data": (4, max_len), "softmax_label": (4, max_len)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.05, 0.05, sh)
                             .astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    base_cfg = dict(slots=slots, prefill_buckets=(32, 128),
                    max_queue=4 * slots, prefix_cache_mb=1,
                    prefill_chunk=16)

    def decoder():
        return Decoder(sym, params, max_len=max_len)

    # one fixed adversarial schedule, shared by every arm
    traffic = []
    for i in range(n_requests):
        if i % long_every == long_every - 1:
            traffic.append((rng.randint(0, vocab, (long_len,)),
                            long_out))
        else:
            traffic.append((rng.randint(0, vocab, (short_len,)),
                            short_out))

    # warmup: two long + two short requests per arm, submitted
    # back-to-back so least-loaded placement gives EVERY replica one
    # of each — traces every program family (prefill/copy/handoff at
    # both buckets, decode) before the measured window, so cadence
    # percentiles read scheduling contention rather than one-time
    # compile stalls
    warmup = [(rng.randint(0, vocab, (long_len,)), 2),
              (rng.randint(0, vocab, (long_len,)), 2),
              (rng.randint(0, vocab, (short_len,)), 2),
              (rng.randint(0, vocab, (short_len,)), 2)]

    def run_arm(roles, handoff_dtype="native"):
        engines = [InferenceEngine(decoder(), role=r,
                                   handoff_dtype=handoff_dtype,
                                   **base_cfg) for r in roles]
        fleet = FleetRouter(engines, heartbeat_ms=1e6)
        for prompt, out in warmup:
            fleet.submit(prompt, max_tokens=out)
        fleet.serve_forever()
        handles = []
        for prompt, out in traffic:
            while True:
                # backpressure: in a role fleet only the prefill
                # replica admits, so its queue (not the fleet-wide
                # sum) is the bound — drain until the submit lands
                try:
                    handles.append(
                        fleet.submit(prompt, max_tokens=out))
                    break
                except (EngineOverloaded, MXNetError):
                    fleet.step()
        t0 = time.perf_counter()
        fleet.serve_forever()
        wall = time.perf_counter() - t0
        cadence = [(h.t_done - h.t_first) / (len(h.tokens) - 1) * 1e3
                   for h in handles
                   if h.t_first is not None and h.t_done is not None
                   and len(h.tokens) > 1]
        toks = sum(len(h.tokens) for h in handles)
        stats = dict(fleet.stats)
        for e in engines:
            cc = e.compile_counts
            if e.role == "prefill":
                assert cc["decode"] == 0 and cc["verify"] == 0, \
                    "prefill specialist compiled decode: %r" % (cc,)
            elif e.role == "decode":
                assert not cc["prefill"], \
                    "decode specialist compiled prefill: %r" % (cc,)
        tokens_out = [list(h.tokens) for h in handles]
        fleet.close()
        return {
            "cadence_p50_ms": round(float(np.percentile(cadence, 50)),
                                    3),
            "cadence_p99_ms": round(float(np.percentile(cadence, 99)),
                                    3),
            "tokens_per_sec": round(toks / wall, 1) if wall else None,
            "stats": stats,
        }, tokens_out

    unified, toks_u = run_arm(("unified", "unified"))
    disagg, toks_d = run_arm(("prefill", "decode"))
    assert toks_u == toks_d, \
        "disaggregation changed tokens (byte-identity violated)"
    int8_arm, toks_q = run_arm(("prefill", "decode"),
                               handoff_dtype="int8")

    def per_req(stats):
        n = stats.get("handoffs", 0) - stats.get("handoff_pool_hits",
                                                 0)
        return None if not n \
            else round(stats.get("handoff_bytes", 0) / float(n))

    native_bytes = per_req(disagg["stats"])
    int8_bytes = per_req(int8_arm["stats"])
    return {
        "requests": n_requests,
        "long_prompt_len": long_len,
        "unified": {k: unified[k] for k in
                    ("cadence_p50_ms", "cadence_p99_ms",
                     "tokens_per_sec")},
        "disagg_1p1d": {
            **{k: disagg[k] for k in
               ("cadence_p50_ms", "cadence_p99_ms",
                "tokens_per_sec")},
            "handoffs": disagg["stats"].get("handoffs", 0),
            "handoff_pool_hits":
                disagg["stats"].get("handoff_pool_hits", 0),
        },
        "byte_identical": 1,     # asserted above, both topologies
        "disagg_decode_p99_ratio":
            round(disagg["cadence_p99_ms"]
                  / unified["cadence_p99_ms"], 3)
            if unified["cadence_p99_ms"] else None,
        "disagg_handoff_bytes_per_req": native_bytes,
        "handoff_bytes_per_req_int8": int8_bytes,
        "handoff_int8_bytes_ratio":
            None if not native_bytes or not int8_bytes
            else round(int8_bytes / float(native_bytes), 3),
        "int8_transfer_tokens_match": int(toks_q == toks_d),
    }


def bench_recordio_io():
    """C++ ImageRecordIOIter: run tools/bench_io.py in a CLEAN
    subprocess (no jax): this process's runtime threads contend with
    the decode workers for the host's cores. The subprocess measures
    the pipeline; the in-process number is reported separately as the
    contended figure. This process holds the chip, so the child is
    pinned to the CPU (``JAX_PLATFORMS=cpu``): a child that reached for
    the chip would fail or hang. Returns (modes_dict,
    contended_img_per_sec)."""
    import subprocess
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(here, "tools", "bench_io.py")],
        capture_output=True, text=True, timeout=900, env=env, cwd=here)
    if r.returncode != 0:
        raise RuntimeError("tools/bench_io.py exited %d: %s"
                           % (r.returncode, r.stderr[-1000:]))
    modes = None
    for line in reversed(r.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            modes = json.loads(line)
            break
    # contended: same 480x360-source jpeg pipeline measured in THIS
    # process, where the TPU runtime threads share the cores
    import cv2  # noqa: F401
    import mxnet_tpu as mx
    from mxnet_tpu import recordio as rec

    tmpd = tempfile.mkdtemp(prefix="benchrec")
    path = os.path.join(tmpd, "bench.rec")
    rng = np.random.RandomState(0)
    w = rec.MXRecordIO(path, "w")
    base = (rng.rand(24, 32, 3) * 255).astype(np.uint8)
    img = cv2.resize(base, (480, 360), interpolation=cv2.INTER_CUBIC)
    for i in range(256):
        hdr = rec.IRHeader(0, float(i % 10), i, 0)
        w.write(rec.pack_img(hdr, img, quality=85))
    w.close()
    it = mx.ImageRecordIter(path_imgrec=path, data_shape=(3, 224, 224),
                            batch_size=128, resize=256, rand_crop=True,
                            rand_mirror=True, shuffle=False)
    for _ in it.iter_numpy():
        pass
    it.reset()
    tic = time.perf_counter()
    n = 0
    for _ in it.iter_numpy():
        n += 128
    contended = n / (time.perf_counter() - tic)
    return modes, contended


def bench_resnet50_from_records(batch=128, workers=2, n_imgs=512):
    """End-to-end ResNet-50 training fed from packed 480x360 JPEG
    records through the FULL parallel pipeline (the ISSUE 2 tentpole):
    num_workers decode pool (uint8 device-augment batches collated in
    shared memory) → DeviceAugmentIter (crop/flip/normalize on-chip) →
    staged_batches (batch i+1's h2d dispatched under step i) → fused
    train step. The number includes real decode, so it is input-bound
    on this container (2 cores shared with the jax runtime threads) —
    compare against recordio_io's exclusive-subprocess decode rates and
    the device-resident resnet50_b256 compute ceiling."""
    import tempfile

    import cv2
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu import recordio as rec
    from mxnet_tpu.models import get_resnet

    tmpd = tempfile.mkdtemp(prefix="benchrec_e2e")
    path = os.path.join(tmpd, "e2e.rec")
    rng = np.random.RandomState(0)
    w = rec.MXRecordIO(path, "w")
    base = (rng.rand(24, 32, 3) * 255).astype(np.uint8)
    img = cv2.resize(base, (480, 360), interpolation=cv2.INTER_CUBIC)
    img = cv2.add(img, rng.randint(0, 12, img.shape).astype(np.uint8))
    for i in range(n_imgs):
        w.write(rec.pack_img(rec.IRHeader(0, float(i % 1000), i, 0), img,
                             quality=85))
    w.close()

    sym = get_resnet(num_classes=1000, num_layers=50)
    trainer = par.ParallelTrainer(
        sym, {"data": (batch, 3, 224, 224), "softmax_label": (batch,)},
        optimizer="sgd", mesh=par.data_parallel_mesh(1),
        compute_dtype="bfloat16",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    trainer.init_params()

    it = mx.ImageRecordIter(path, (3, 256, 256), batch_size=batch,
                            resize=256, device_augment=True,
                            shuffle=True, seed=0, num_workers=workers)
    dev = mx.DeviceAugmentIter(it, crop_shape=(224, 224), rand_crop=True,
                               rand_mirror=True, scale=1.0 / 255)
    staged = trainer.staged_batches(dev, ["data"], ["softmax_label"])

    def epoch_pass():
        staged.reset()
        outs, nb = None, 0
        for _, dev_batch in staged:
            outs = trainer.step(dev_batch)
            nb += 1
        np.asarray(outs[0][(0,) * outs[0].ndim])  # force completion
        return nb

    try:
        epoch_pass()  # warmup: pool spin-up, compile, page cache
        tic = time.perf_counter()
        nb = epoch_pass() + epoch_pass()
        dt = time.perf_counter() - tic
    finally:
        it.close()
    return batch * nb / dt


def bench_telemetry_overhead(batch=256, chain_steps=10, pairs=40,
                             scrape_interval_s=0.2):
    """ISSUE 4 acceptance arm: the fused train step with telemetry ON
    must be within 2% of telemetry OFF — asserted, not just reported.

    The instrumentation on the step path is pure host work (two
    perf_counter reads, a handful of lock'd adds — no device sync,
    nothing traced into the program): measured ~10-15 µs/step cold
    against a multi-ms step. Measurement discipline, learned on the
    noisy 2-core CI box: the effect under test is 100x smaller than
    per-chain load noise, so the A/B runs as MANY short alternating
    off/on chain pairs (load phases hit both configs), each ending in
    a real value fetch, compared by 25%-trimmed means; a verdict over
    budget is re-measured up to twice before the assert fires (an
    unlucky load phase spanning one whole attempt must not fail the
    arm). Both configs run the SAME compiled trainer —
    ``telemetry.enable`` only flips the collection flag.

    Since PR 9 the A/B runs with the HTTP exposition server up and an
    active scraper hitting ``/metrics`` every ``scrape_interval_s``
    (the deployed configuration: a Prometheus scraper is always
    there). The scraper load lands on BOTH configs — the contract
    stays "collection costs <= 2% of the step", now measured under
    live exposition. Since ISSUE 13 the serving traffic capture is
    ALSO armed process-wide (``MXNET_SERVING_CAPTURE_DIR``) for the
    A/B — capture writes ride the serving submit/retire paths, never
    the train step, and this pins that arming the knob alone costs
    the step path nothing (the serving-path cost of a ROLLING capture
    is measured by ``bench_serving_replay``'s
    ``capture_overhead_frac``). Since ISSUE 19 FLEET tracing is armed
    too: a live 1P+1D router with stitched journeys in its flight
    ring, the scraper cycling the /fleet plane in with /metrics."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry as tele

    data = mx.symbol.Variable("data")
    fc1 = mx.symbol.FullyConnected(data=data, num_hidden=1024,
                                   name="fc1")
    act = mx.symbol.Activation(data=fc1, act_type="relu", name="relu1")
    fc2 = mx.symbol.FullyConnected(data=act, num_hidden=10, name="fc2")
    sym = mx.symbol.SoftmaxOutput(data=fc2, name="softmax")
    shapes = {"data": (batch, 512), "softmax_label": (batch,)}
    trainer, _, devb = _make_trainer_and_batches(
        sym, shapes, 10, None, {"learning_rate": 0.1})

    def chain():
        tic = time.perf_counter()
        outs = None
        for _ in range(chain_steps):
            outs = trainer.step(devb)
        np.asarray(outs[0][(0,) * outs[0].ndim])  # force completion
        return (time.perf_counter() - tic) / chain_steps

    def trimmed(xs, frac=0.25):
        xs = sorted(xs)
        k = int(len(xs) * frac)
        return float(np.mean(xs[k:len(xs) - k]))

    was_enabled = tele.enabled()
    # pause any armed trace capture (MXNET_TRACE_DIR): the contract
    # under test is metrics collection alone — with a capture armed the
    # ON chains would additionally pay per-step trace-event emission
    # (a different configuration) and flood the user's trace file with
    # thousands of bench-internal train.step spans. Paused before
    # the warmup chain too: its steps are just as much bench-internal.
    pause = tele.tracing_paused()
    pause.__enter__()
    # live exposition under the A/B: ephemeral-port server + a scraper
    # daemon polling /metrics on a fixed cadence, stopped in finally.
    # A server the USER already started (MXNET_TELEMETRY_PORT) is
    # reused and left running — serve() is a process singleton and
    # replacing it would tear down their endpoint.
    import threading
    import urllib.request
    from mxnet_tpu import telemetry_http
    own_server = telemetry_http._server is None
    srv = tele.serve(port=0) if own_server else telemetry_http._server
    # Since ISSUE 19 the A/B ALSO runs with fleet tracing armed: a
    # live 1P+1D FleetRouter whose flight ring holds real stitched
    # cross-replica journeys (served once, before the chains), and
    # the scraper polls the fleet plane (/fleet aggregation + a
    # per-trace /fleet/flight stitch) alongside /metrics. The fleet
    # idles during the chains — the contract being pinned is that an
    # ARMED tracing plane (ring retention, SLO windows ticking under
    # _refresh, stitching under scrape) costs the train step nothing.
    import jax.numpy as jnp
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.parallel import Decoder
    from mxnet_tpu.serving import FleetRouter, InferenceEngine
    fvocab, flen = 17, 16
    fsym = get_transformer_lm(fvocab, num_layers=1, embed_dim=16,
                              num_heads=2, impl="dense")
    fshapes = {"data": (2, flen), "softmax_label": (2, flen)}
    farg_shapes, _, _ = fsym.infer_shape(**fshapes)
    frng = np.random.RandomState(0)
    fparams = {n: jnp.asarray(frng.uniform(-0.3, 0.3, s)
                              .astype(np.float32))
               for n, s in zip(fsym.list_arguments(), farg_shapes)
               if n not in fshapes}

    def _feng(role):
        return InferenceEngine(
            Decoder(fsym, fparams, max_len=flen),
            slots=2, prefill_buckets=(4, 8), max_queue=8,
            prefix_cache_mb=0.0042, role=role)

    fleet = FleetRouter([_feng("prefill"), _feng("decode")],
                        heartbeat_ms=1e6)
    fhandles = [fleet.submit(frng.randint(0, fvocab, (5,)),
                             max_tokens=4) for _ in range(4)]
    fleet.serve_forever()
    scrape_paths = ["/metrics", "/fleet"] \
        + ["/fleet/flight/%s" % h.id for h in fhandles[:2]]
    stop_scraper = threading.Event()
    scrapes = [0]

    def scraper():
        while not stop_scraper.wait(scrape_interval_s):
            try:
                path = scrape_paths[scrapes[0] % len(scrape_paths)]
                with urllib.request.urlopen(srv.url + path,
                                            timeout=5) as resp:
                    resp.read()
                scrapes[0] += 1
            except Exception:     # a failed scrape is load lost,
                pass              # not a bench failure

    scraper_thread = threading.Thread(target=scraper, daemon=True,
                                      name="bench-scraper")
    scraper_thread.start()
    cap_dir = tempfile.mkdtemp(prefix="mx_bench_overhead_capture_")
    prev_cap = os.environ.get("MXNET_SERVING_CAPTURE_DIR")
    os.environ["MXNET_SERVING_CAPTURE_DIR"] = cap_dir
    try:
        chain()  # warmup/compile
        for attempt in range(3):
            offs, ons = [], []
            for i in range(pairs):
                first_off = i % 2 == 0  # alternate within-pair order
                for flag in ((False, True) if first_off
                             else (True, False)):
                    tele.enable(flag)
                    (ons if flag else offs).append(chain())
            off_ms = trimmed(offs) * 1e3
            on_ms = trimmed(ons) * 1e3
            overhead = on_ms / off_ms - 1.0
            if overhead <= 0.02:
                break
    finally:
        tele.enable(was_enabled)
        stop_scraper.set()
        scraper_thread.join(timeout=5)
        fleet.close()
        if own_server:
            tele.stop_server()
        pause.__exit__(None, None, None)
        if prev_cap is None:
            os.environ.pop("MXNET_SERVING_CAPTURE_DIR", None)
        else:
            os.environ["MXNET_SERVING_CAPTURE_DIR"] = prev_cap
        shutil.rmtree(cap_dir, ignore_errors=True)
    assert overhead <= 0.02, (
        "telemetry-on fused step is %.2f%% slower than telemetry-off "
        "(budget: 2%%) — off %.3f ms/step, on %.3f ms/step "
        "(exposition server up, %d scrapes)"
        % (overhead * 100, off_ms, on_ms, scrapes[0]))
    return {
        "off_ms_per_step": round(off_ms, 4),
        "on_ms_per_step": round(on_ms, 4),
        "overhead_frac": round(overhead, 4),
        "asserted_within": 0.02,
        "exposition_server": True,
        "capture_armed": True,
        "fleet_tracing_armed": True,
        "fleet_journeys": len(fhandles),
        "scrape_interval_s": scrape_interval_s,
        "scrapes": scrapes[0],
    }


def bench_gemm_calibration(steps=8):
    """This chip's PRACTICAL compute ceiling: chained dependent 8192^3
    bf16 GEMMs (the best program the chip can run). The chain lives
    INSIDE one program as a ``lax.scan`` of dependent matmuls (no
    per-step dispatch; compile excluded by warmup), timed as the
    k-vs-2k program difference with fresh input values per
    repetition."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = 8192
    w = jnp.ones((n, n), jnp.bfloat16) * jnp.bfloat16(1.0 / n)

    def make(k):
        @jax.jit
        def run(a):
            def body(c, _):
                return jnp.dot(c, w), None
            out, _ = lax.scan(body, a, None, length=k)
            return out[0, 0]
        return run

    run1, run2 = make(steps), make(2 * steps)

    def timed(fn, seed):
        a = jnp.full((n, n), 1.0 + seed * 1e-3, jnp.bfloat16)
        tic = time.perf_counter()
        np.asarray(fn(a))
        return time.perf_counter() - tic

    timed(run1, 99)  # compile+warm both programs
    timed(run2, 98)
    diffs = []
    for rep in range(3):
        t1 = timed(run1, rep * 2)
        t2 = timed(run2, rep * 2 + 1)
        if t2 - t1 > 0.02 * t1:
            diffs.append((t2 - t1) / steps)
    if not diffs:
        return None
    sec = sorted(diffs)[len(diffs) // 2]
    return 2.0 * n * n * n / sec


def _io_pipeline_extra(io_modes, e2e_rec):
    """BENCH_extra block for the num_workers decode pool: the clean-
    subprocess img/s-vs-worker-count sweep (tools/bench_io.py) plus the
    end-to-end from-records ResNet-50 number."""
    pipe = (io_modes or {}).get("io_pipeline")
    out = {
        "resnet50_from_records_img_per_sec":
            None if e2e_rec is None else round(e2e_rec, 1),
        "e2e_note": "decode pool (2 workers, u8 shm batches) -> "
                    "DeviceAugmentIter (on-chip augment) -> staged h2d "
                    "-> fused step; in-process, so decode contends "
                    "with the jax runtime threads on this container's "
                    "2 cores",
    }
    if pipe:
        workers = {k: round(v, 1) for k, v in pipe.items()
                   if k[0] == "w" and "_" not in k}
        out["img_per_sec_by_workers"] = workers
        out["serial_py_img_per_sec"] = round(pipe.get("serial_py", 0), 1)
        out["u8_device_augment"] = {
            k: round(v, 1) for k, v in pipe.items() if k.endswith("_u8")}
        out["ncpu"] = pipe.get("ncpu")
        if "w4" in workers and "w1" in workers and workers["w1"]:
            out["speedup_w4_vs_w1"] = round(workers["w4"] / workers["w1"],
                                            2)
        out["caveat"] = (
            "clean-subprocess measurement (no jax threads), same "
            "discipline as recordio_io; scaling is core-bound — this "
            "container exposes %s CPUs, so the worker curve saturates "
            "there and the >=3x-at-4-workers figure needs a >=4-core "
            "host" % pipe.get("ncpu"))
    return out


def main():
    dev = _require_tpu()
    from mxnet_tpu import compile_cache
    compile_cache.enable()
    # arms that raised: each is reported with its traceback, its keys
    # stay null, and the exit code is non-zero — a caught arm is a
    # failed run, not a quiet null
    failed = []

    def arm_failed(arm):
        traceback.print_exc()
        failed.append(arm)

    ceiling = bench_gemm_calibration()
    peak = _peak_flops(dev)
    r50_256, r50_256_h2d, mfu = bench_resnet50(256)
    r50_128, _, _ = bench_resnet50(128)
    incbn = bench_inception_bn()
    # Defensive from here on: an auxiliary arm that raises does not
    # cost the headline capture — but it does fail the run (arm_failed).
    try:
        cifar, cifar_spread = bench_cifar()
    except Exception:
        arm_failed("cifar")
        cifar = cifar_spread = None
    try:
        lm_tps, lm_mfu = bench_transformer_lm()
    except Exception:
        arm_failed("lm_124M")
        lm_tps = lm_mfu = None
    # GPT-2-medium-class arm: shows MFU RISES with model size (the 124M
    # number is model-scale-limited — head_dim 64 / E=768 underfill the
    # MXU — not framework-limited).
    try:
        lm350_tps, lm350_mfu = bench_transformer_lm(layers=24, embed=1024,
                                                    heads=16, steps=6)
    except Exception:
        arm_failed("lm_350M")
        lm350_tps = lm350_mfu = None
    try:
        dec_arms = bench_decode()
    except Exception:
        arm_failed("decode")
        dec_arms = None
    try:
        serving = bench_serving()
    except Exception:
        arm_failed("serving")
        serving = None
    # prefix-cache + chunked-prefill A/B (ISSUE 5): same workload,
    # same seeds — cache on vs off moves TTFT (saved prefill FLOPs),
    # chunking on vs off moves cadence p99 (bounded decode stalls
    # under long-prompt admission)
    try:
        pfx_on = bench_serving_prefix(prefix_cache_mb=256, chunk=0)
        pfx_off = bench_serving_prefix(prefix_cache_mb=0, chunk=0)
        pfx_chunked = bench_serving_prefix(prefix_cache_mb=0, chunk=128)
        serving_prefix = {
            "cache_on": pfx_on,
            "cache_off": pfx_off,
            "chunked_128": pfx_chunked,
            "ttft_speedup": None if not pfx_on["ttft_p50_ms"]
            else round(pfx_off["ttft_p50_ms"] / pfx_on["ttft_p50_ms"],
                       2),
            "note": "shared-system-prompt workload (90% of requests "
                    "share a 192-token prefix; 25% of the rest are "
                    "512-token long prompts), sub-saturating Poisson "
                    "arrivals; ttft_speedup = cache-off p50 TTFT / "
                    "cache-on (prefix K/V row copies replace prefill "
                    "FLOPs); chunked_128 bounds each decode stall to "
                    "one 128-token prefill piece — compare its "
                    "cadence_p99_ms against cache_off's (both cache-"
                    "off, chunking isolated); "
                    "tools/bench_serving.py sweeps hit-rate x chunk",
        }
    except Exception:
        arm_failed("serving_prefix")
        serving_prefix = None
    # speculative-decoding A/B (ISSUE 10): spec-off vs n-gram K=4/8 on
    # a repetition-friendly workload, same seeds — outputs are
    # byte-identical across arms, only tokens-per-dispatch changes
    try:
        spec_off = bench_serving_spec(spec_k=0)
        spec_k4 = bench_serving_spec(spec_k=4)
        spec_k8 = bench_serving_spec(spec_k=8)
        serving_spec = {
            "spec_off": spec_off,
            "ngram_k4": spec_k4,
            "ngram_k8": spec_k8,
            "speedup_k4": None if not spec_off["tokens_per_sec"]
            else round(spec_k4["tokens_per_sec"]
                       / spec_off["tokens_per_sec"], 2),
            "speedup_k8": None if not spec_off["tokens_per_sec"]
            else round(spec_k8["tokens_per_sec"]
                       / spec_off["tokens_per_sec"], 2),
            "note": "few-shot-style repetition-friendly prompts "
                    "(24-token block tiled 4x + unique tail), "
                    "sub-saturating Poisson arrivals, n-gram "
                    "(prompt-lookup) drafting; accept_per_step = "
                    "accepted drafts + 1 corrected token per drafted "
                    "slot per verify dispatch — tokens per "
                    "target-model step; outputs byte-identical to "
                    "spec_off by construction (verification gates "
                    "every token); weight_scale=0.15 proxies a "
                    "trained model's self-consistency (see the "
                    "bench_serving_spec docstring); "
                    "tools/bench_serving.py --spec-ks sweeps K",
        }
    except Exception:
        arm_failed("serving_spec")
        serving_spec = None
    # overload-policy A/B (ISSUE 7): shed vs block goodput at a
    # calibrated 2x saturation, every request under the same SLO
    try:
        serving_overload = bench_serving_overload()
    except Exception:
        arm_failed("serving_overload")
        serving_overload = None
    # weight-only int8 quantization A/B (ISSUE 15): fp vs int8
    # weights on the same saturating workload; the decode-program
    # bytes_accessed ratio is the serving-batch weight-stream cut
    try:
        # the lowering-only probe gets its own guard: a probe failure
        # (e.g. a Pallas lowering quirk on an exotic backend) must not
        # discard the minutes-long serving A/B that already completed
        try:
            quant_probe = bench_serving_quant_bytes()
        except Exception:
            arm_failed("serving_quant_bytes")
            quant_probe = None
        serving_quant = {
            **bench_serving_quant(),
            "serving_batch_probe": quant_probe,
            "note": "weight_dtype='int8' (per-output-channel scales, "
                    "chunked scale-fused dequant inside the programs "
                    "— doc/serving.md 'Quantized weights') vs float "
                    "weights, identical workload/seeds, compile "
                    "contract asserted per arm; serving_batch_probe "
                    "lowers the 124M decode programs at the "
                    "serving-batch geometry and reads their cost "
                    "analysis: forward_ratio = int8/fp bytes of the "
                    "decode forward a greedy round actually executes "
                    "(the weight-stream cut — the headline), "
                    "program_ratio = the live gauge's full-program "
                    "number, diluted by the lax.cond sampling branch "
                    "the static cost model counts but greedy rounds "
                    "never run (PR 11 static-model caveat family); "
                    "weight_bytes_ratio = stored-footprint cut, "
                    "slots_at_hbm = resident-slot budget at fixed "
                    "HBM; on the CPU box the chunked dequant loop "
                    "serializes work the chip overlaps, so the bytes "
                    "cut is the honest CPU metric and wall clock the "
                    "TPU lever (PR 11/14 precedent); PR 17 arms: "
                    "int8_pallas/int4 = the quant_matmul kernel "
                    "(dequant-in-VMEM, int4 = packed nibbles + "
                    "per-group scales), each with wall_ms; "
                    "tools/bench_serving.py --weight-dtypes / "
                    "--matmul-impls sweep these axes; "
                    "weight_stream_ratio_* = the analytic stored "
                    "bytes a decode step streams (matmul weights at "
                    "stored width + gathered embedding rows only) — "
                    "exact and impl-invariant where the static HLO "
                    "cost model caps fori trip counts and counts the "
                    "interpreter's VMEM-resident dequant temporaries, "
                    "so it is the cross-impl headline (int4 ~0.27x, "
                    "int8 ~0.51x)",
        }
    except Exception:
        arm_failed("serving_quant")
        serving_quant = None
    # capture/replay day-in-the-life (ISSUE 13): bursty mixed traffic
    # captured once, replayed per config with byte-identity verified
    try:
        serving_replay = bench_serving_replay()
    except Exception:
        arm_failed("serving_replay")
        serving_replay = None
    # fleet resilience (ISSUE 16): the same capture replayed through a
    # 2-replica fleet under a rolling restart — zero failed requests,
    # byte-identical, with the per-drain migration pause as the cost
    try:
        serving_fleet = bench_serving_fleet()
    except Exception:
        arm_failed("serving_fleet")
        serving_fleet = None
    # disaggregated prefill/decode (ISSUE 18): long-prompt adversarial
    # mix on a 1P+1D specialist fleet vs a 2-unified fleet at matched
    # chip count — decode p99 isolation + the per-request KV transfer
    try:
        serving_disagg = bench_serving_disagg()
    except Exception:
        arm_failed("serving_disagg")
        serving_disagg = None
    # tensor-parallel sweep (ISSUE 14): same workload/seeds at
    # tp in {1, 2, 4}; outputs byte-identical across degrees
    # (digest-asserted), per-shard decode bytes_accessed is the cut
    try:
        import jax as _jax
        tp_arms, tp_digests = {}, {}
        for tpd in (1, 2, 4):
            if tpd > len(_jax.devices()):
                break
            arm = bench_serving_tp(tp=tpd)
            tp_digests[tpd] = arm.pop("digest")
            tp_arms["tp%d" % tpd] = arm
        assert len(set(tp_digests.values())) == 1, \
            "tp sweep outputs diverged: %r" % (tp_digests,)
        base_ba = tp_arms.get("tp1", {}) \
            .get("decode_bytes_accessed_per_shard")
        for tpd in (2, 4):
            arm = tp_arms.get("tp%d" % tpd)
            ba = arm and arm.get("decode_bytes_accessed_per_shard")
            tp_arms["bytes_per_shard_ratio_tp%d" % tpd] = \
                None if not ba or not base_ba \
                else round(ba / base_ba, 3)
        serving_tp = {
            **tp_arms,
            "outputs_byte_identical": True,
            "note": "InferenceEngine(tp=N): KV cache + every compiled "
                    "program family sharded over the mesh's model "
                    "axis on the kv-head dim (one shard_map program "
                    "per family — doc/serving.md 'Tensor-parallel "
                    "serving'); same workload/seeds per degree, "
                    "greedy token streams digest-asserted identical "
                    "across tp; bytes_per_shard_ratio = per-shard "
                    "decode-program bytes_accessed vs tp=1 (the "
                    "sharded program's cost analysis carries local "
                    "shapes) — the memory-bound win condition; on the "
                    "CPU box wall-clock pays collective overhead the "
                    "ICI-attached chip run amortizes, so the bytes "
                    "cut is the honest CPU metric (PR 11 precedent); "
                    "tools/bench_serving.py --tps sweeps this axis",
        }
    except Exception:
        arm_failed("serving_tp")
        serving_tp = None
    def _dec_best_ms():
        if not dec_arms:
            return None
        b8 = [v["ms_per_token"] for k, v in dec_arms.items()
              if v and k.endswith("_b8")]
        return min(b8) if b8 else None
    try:
        io_modes, io_contended = bench_recordio_io()
    except Exception:
        arm_failed("recordio_io")
        io_modes = io_contended = None
    try:
        e2e_rec = bench_resnet50_from_records()
    except Exception:
        arm_failed("resnet50_from_records")
        e2e_rec = None
    try:
        tele_overhead = bench_telemetry_overhead()
    except Exception:
        # includes the <=2% assertion failing: the arm reports null and
        # the traceback names the measured overhead
        arm_failed("telemetry_overhead")
        tele_overhead = None

    def vs_ceiling(nominal_mfu):
        if ceiling is None:
            return None
        return round(nominal_mfu * peak / ceiling, 3)

    extra = {
        "resnet50_b256_bf16": round(r50_256, 1),
        "resnet50_b128_bf16": round(r50_128, 1),
        "resnet50_mfu_nominal": round(mfu, 3),
        "resnet50_mfu_vs_measured_ceiling": vs_ceiling(mfu),
        "inception-bn_imagenet_b128": round(incbn, 1),
        "inception-bn_vs_titanx_per_gpu":
            round(incbn / INCEPTION_BN_TITANX_BASELINE, 1),
        "transformer_lm_124M_T1024_tokens_per_sec":
            None if lm_tps is None else round(lm_tps, 0),
        "transformer_lm_mfu_nominal":
            None if lm_mfu is None else round(lm_mfu, 3),
        "transformer_lm_mfu_vs_measured_ceiling":
            None if lm_mfu is None else vs_ceiling(lm_mfu),
        "transformer_lm_350M_T1024_tokens_per_sec":
            None if lm350_tps is None else round(lm350_tps, 0),
        "transformer_lm_350M_mfu_nominal":
            None if lm350_mfu is None else round(lm350_mfu, 3),
        "decode_124M_kvcache": None if dec_arms is None else {
            "arms": dec_arms,
            "note": "greedy KV-cache decode, whole loop one compiled "
                    "lax.scan program, bf16; full = attends all "
                    "max_len cache rows each step (the offline "
                    "step's one read)",
        },
        "serving_124M_continuous_batching": None if serving is None else {
            **serving,
            "static_full_b8_tokens_per_sec":
                None if not dec_arms or not dec_arms.get("full_b8")
                else dec_arms["full_b8"]["tokens_per_sec"],
            "note": "slot-paged continuous batching (mxnet_tpu/serving) "
                    "at saturating Poisson load, mixed prompt/output "
                    "lengths; compare tokens_per_sec against the static "
                    "full_b8 decode arm (same 124M LM, bf16) — the "
                    "ISSUE 3 criterion; latency = per-request decode "
                    "cadence (t_done-t_first)/(n-1), p50/p99 across "
                    "requests; tools/bench_serving.py sweeps slots and "
                    "arrival rates",
        },
        "serving_prefix_cache_chunked_prefill": serving_prefix,
        "serving_speculative_decoding": serving_spec,
        "serving_weight_quant": serving_quant,
        "serving_tensor_parallel": serving_tp,
        "serving_time_machine_replay": None if serving_replay is None
        else {
            **serving_replay,
            "note": "bursty mixed traffic (bursts of 6, shared-prefix/"
                    "long/short mix) captured once via "
                    "MXNET_SERVING_CAPTURE_DIR machinery, then "
                    "replayed at recorded inter-arrival gaps on fresh "
                    "engines per config with --verify semantics: every "
                    "arm reproduces the captured tokens "
                    "byte-identically (asserted), only latencies move; "
                    "capture_overhead_frac = record-run wall cost of "
                    "the rolling tape vs the capture-off same-config "
                    "replay; tools/replay_serving.py replays any "
                    "production capture the same way",
        },
        "serving_fleet_resilience": None if serving_fleet is None
        else {
            **serving_fleet,
            "note": "FleetRouter over 2 InferenceEngine replicas "
                    "(doc/fault_tolerance.md 'Fleet resilience'): one "
                    "captured trace replayed through the fleet while "
                    "every replica is drained and replaced in turn "
                    "(rolling restart); zero_failed_restart = 1 iff "
                    "every request completed byte-identical to the "
                    "capture with drains and live migrations actually "
                    "exercised; failover_p99_ms = p99 wall cost of "
                    "one drain (snapshot + migrate + successor join) "
                    "— the pause a rolling deploy injects per "
                    "replica; tools/replay_serving.py --replicas N "
                    "--rolling-restart runs the same drill on any "
                    "production capture",
        },
        "serving_disagg": None if serving_disagg is None
        else {
            **serving_disagg,
            "note": "disaggregated prefill/decode (doc/serving.md "
                    "'Disaggregated prefill/decode'): the same "
                    "long-prompt adversarial mix served by a "
                    "2-unified fleet and a 1 prefill + 1 decode "
                    "specialist fleet at matched chip count, outputs "
                    "byte-compared (byte_identical=1 asserted); "
                    "disagg_decode_p99_ratio = specialist cadence p99 "
                    "/ unified cadence p99 (lower better — decode "
                    "replicas never dispatch prefill rounds, so long "
                    "prompts stop stealing cadence); "
                    "disagg_handoff_bytes_per_req = KV bytes one "
                    "request's handoff ships (pool-affinity hits ship "
                    "zero); handoff_int8_bytes_ratio pins the "
                    "MXNET_SERVING_HANDOFF_DTYPE=int8 encoding at "
                    "~half fp bytes; tools/replay_serving.py --roles "
                    "PxD replays any capture through the same "
                    "topology",
        },
        "serving_overload_shed_vs_block": None if serving_overload is None
        else {
            **serving_overload,
            "note": "ONE engine, policy knobs flipped between arms, "
                    "identical 2x-saturating Poisson schedule (rate "
                    "calibrated from a full-batch service pass), every "
                    "request under the same SLO deadline; goodput = "
                    "tokens of COMPLETED requests per wall second "
                    "(deadline-retired work is wasted capacity, shed "
                    "requests cost nothing); goodput_ratio = shed / "
                    "block — doc/serving.md 'Serving under hostile "
                    "traffic'",
        },
        "calibration": {
            "gemm_8192_bf16_tflops":
                None if ceiling is None else round(ceiling / 1e12, 1),
            "datasheet_peak_tflops": round(peak / 1e12, 1),
            "note": "measured ceiling of a chained 8192^3 bf16 GEMM; "
                    "MFUs reported vs both this and the datasheet "
                    "number",
        },
        # --- numbers that need caveats to be interpretable ------------
        "resnet50_b256_bf16_host_infeed": {
            "value": round(r50_256_h2d, 1),
            "caveat": "fresh host batches through trainer.prefetch: "
                      "bounded by the host-to-device link as much as "
                      "by the framework",
        },
        "cifar10_inception-bn-28-small": None if cifar is None else {
            "value": round(cifar, 1),
            "vs_gtx980_baseline": round(cifar / CIFAR_BASELINE, 3),
            "spread": round(cifar_spread, 3),
            "method": "200 train steps per compiled program "
                      "(multi_step lax.scan, donated params), "
                      "N-vs-2N difference; spread = (max-min)/median "
                      "per-step time over 3 reps",
        },
        "recordio_io": {
            "img_per_sec":
                None if io_modes is None
                else round(io_modes.get("jpeg_scaled", 0), 1),
            "caveat": "exclusive: measured in a clean subprocess (no "
                      "jax runtime threads); 480x360-source JPEGs, "
                      "resize 256, random crop+mirror to 224, 1 CPU "
                      "core",
            "in_process_img_per_sec":
                None if io_contended is None else round(io_contended, 1),
            "in_process_caveat": "same pipeline measured inside the "
                                 "bench process (jax initialized): "
                                 "its runtime threads share the "
                                 "host's cores with the decoders; "
                                 "compare against the exclusive number "
                                 "above",
            "modes": io_modes,
        },
        "io_pipeline": _io_pipeline_extra(io_modes, e2e_rec),
        "telemetry_overhead": tele_overhead if tele_overhead else {
            "note": "arm failed or exceeded the 2% budget — see the "
                    "driver log traceback"},
    }
    # the full telemetry snapshot of THIS bench run: every arm above
    # fed the registry (train.* step/input/device split, serving.*
    # TTFT/cadence, io.* decode pool), so future BENCH_* files carry
    # the breakdowns next to the headline numbers
    # (tools/dump_telemetry.py pretty-prints it)
    import mxnet_tpu as _mx
    extra["telemetry"] = _mx.telemetry.snapshot()
    # Full detail goes to BENCH_extra.json (written anew by every run,
    # git-ignored); the final stdout line is a compact headline.
    extra_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "BENCH_extra.json")
    with open(extra_path, "w") as f:
        json.dump(extra, f, indent=1, sort_keys=True)
    print("full per-benchmark detail + caveats: %s" % extra_path)
    headline = {
        "metric": "resnet50_imagenet_train_throughput",
        "value": round(r50_256, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(r50_256 / NORTH_STAR_IMG_PER_SEC, 3),
        "extra": {
            "lm_124M_tokens_per_sec":
                None if lm_tps is None else round(lm_tps, 0),
            "lm_mfu_nominal":
                None if lm_mfu is None else round(lm_mfu, 3),
            "decode_b8_ms_per_token": _dec_best_ms(),
            "serving_tokens_per_sec":
                None if serving is None else serving["tokens_per_sec"],
            "serving_p99_ms":
                None if serving is None else serving["p99_ms_per_token"],
            "serving_prefix_ttft_speedup":
                None if serving_prefix is None
                else serving_prefix["ttft_speedup"],
            "serving_chunked_p99_ms":
                None if serving_prefix is None
                else serving_prefix["chunked_128"]["cadence_p99_ms"],
            "serving_shed_goodput_ratio":
                None if serving_overload is None
                else serving_overload["goodput_ratio"],
            "serving_spec_accept_per_step":
                None if serving_spec is None
                else serving_spec["ngram_k4"]["accept_per_step"],
            "serving_spec_speedup":
                None if serving_spec is None
                else serving_spec["speedup_k4"],
            "serving_replay_verified":
                None if serving_replay is None
                else serving_replay["verified_total"],
            "serving_quant_bytes_ratio":
                None if serving_quant is None
                else (serving_quant.get("serving_batch_probe")
                      or {}).get("forward_ratio"),
            "serving_quant_tokens_per_sec":
                None if serving_quant is None
                else serving_quant["int8"]["tokens_per_sec"],
            "serving_int4_bytes_ratio":
                None if serving_quant is None
                else (serving_quant.get("serving_batch_probe")
                      or {}).get("weight_stream_ratio_int4"),
            "serving_tp2_bytes_ratio":
                None if serving_tp is None
                else serving_tp.get("bytes_per_shard_ratio_tp2"),
            "serving_tp4_tokens_per_sec":
                None if not (serving_tp or {}).get("tp4")
                else serving_tp["tp4"]["tokens_per_sec"],
            "serving_replay_p99_ms":
                None if serving_replay is None
                else serving_replay["same_config"]["cadence_p99_ms"],
            "fleet_failover_p99_ms":
                None if serving_fleet is None
                else serving_fleet["failover_p99_ms"],
            "fleet_zero_failed_restart":
                None if serving_fleet is None
                else serving_fleet["zero_failed_restart"],
            "disagg_decode_p99_ratio":
                None if serving_disagg is None
                else serving_disagg["disagg_decode_p99_ratio"],
            "disagg_handoff_bytes_per_req":
                None if serving_disagg is None
                else serving_disagg["disagg_handoff_bytes_per_req"],
            "cifar10_img_per_sec":
                None if cifar is None else round(cifar, 1),
            "cifar10_vs_gtx980":
                None if cifar is None else round(cifar / CIFAR_BASELINE, 2),
            "io_img_per_sec":
                None if io_modes is None
                else round(io_modes.get("jpeg_scaled", 0), 1),
            "io_pipeline_w4":
                None if not (io_modes or {}).get("io_pipeline")
                else round(io_modes["io_pipeline"].get("w4", 0), 1),
            "resnet50_from_records":
                None if e2e_rec is None else round(e2e_rec, 1),
            "gemm_calib_tflops":
                None if ceiling is None else round(ceiling / 1e12, 1),
            "telemetry_overhead_pct":
                None if not tele_overhead
                else round(tele_overhead["overhead_frac"] * 100, 2),
            "detail": "BENCH_extra.json",
        },
    }
    line = json.dumps(headline)
    assert len(line) < 1500, "headline JSON must fit the driver capture"
    print(line)
    if failed:
        sys.exit("bench.py: %d arm(s) raised: %s"
                 % (len(failed), ", ".join(failed)))


if __name__ == "__main__":
    main()
