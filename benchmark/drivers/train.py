"""The training driver: one compiled step with its state, driven from the
seed through its first steps, then through the timed window, then held to
the family's plain reference.

What a cell needs is data: the family file says how the program is asked
for the model and what its reference computes; the traffic file gives the
batch, the optimizer and the ring of device-resident batches.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from benchmark import harness as H

FIRST_STEPS = 3          # the steps the reference follows
RUN_AHEAD = 3            # steps the host may dispatch ahead of the device


# -- the program -------------------------------------------------------------

def optimizer_params(fam, cfg, traffic):
    opt = dict(traffic["optimizer"])
    opt.pop("name")
    opt["rescale_grad"] = 1.0 / fam.loss_rows(cfg, traffic)
    return opt


def build_trainer(mx, fam, cfg, traffic):
    """(the trainer the cell names, a batch as it stages one or None),
    through the public API. ``entry``
    "fit": the one ``FeedForward.fit(compute_dtype=...)`` builds (taken
    from its batch callback after one step on a throw-away batch, as
    ``chip_smoke.phase_train`` takes it); "trainer": ``ParallelTrainer``
    built directly."""
    sym = fam.build_symbol(mx, cfg, traffic)
    shapes = fam.input_shapes(cfg, traffic)
    name = traffic["optimizer"]["name"]
    if traffic["entry"] == "trainer":
        return mx.parallel.ParallelTrainer(
            sym, shapes, optimizer=name,
            mesh=mx.parallel.data_parallel_mesh(1),
            compute_dtype=cfg["compute_dtype"],
            optimizer_params=optimizer_params(fam, cfg, traffic)), None
    if traffic["entry"] != "fit":
        raise H.BenchError("train: unknown entry %r" % traffic["entry"])
    import jax
    # one throw-away batch for ``fit`` to build its trainer on
    first = jax.device_get(fam.make_batch(H.key_for(0, stream=3), cfg,
                                          traffic))
    data, label = first["data"], first["softmax_label"]
    it = mx.io.NDArrayIter(data, label, batch_size=shapes["data"][0])
    seen = {}

    def on_batch(param):
        seen["trainer"] = param.locals.get("trainer")
        seen["batch"] = param.locals.get("dev_batch")

    opt = {k: v for k, v in traffic["optimizer"].items() if k != "name"}
    model = mx.model.FeedForward(
        sym, ctx=mx.tpu(), num_epoch=1, optimizer=name,
        initializer=mx.initializer.Uniform(0.01),
        compute_dtype=cfg["compute_dtype"], **opt)
    model.fit(it, eval_metric="ce", batch_end_callback=on_batch)
    trainer = seen.get("trainer")
    if not isinstance(trainer, mx.parallel.ParallelTrainer):
        raise H.BenchError("train: FeedForward.fit ran the legacy executor "
                           "loop, not the fused ParallelTrainer step")
    return trainer, seen.get("batch")


def place_like(ring, like):
    """The ring's batches laid out as ``fit`` laid out its own staged
    batch, so that the step it compiled is the step the window drives
    (another layout is another entry in the step's cache)."""
    import jax
    if not like:
        return ring
    sh = {k: getattr(v, "sharding", None) for k, v in like.items()}
    return [{k: (jax.device_put(v, sh[k]) if sh.get(k) is not None else v)
             for k, v in b.items()} for b in ring]


def optimizer_rule(traffic):
    """``optimizers/<name>.py``: the optimizer as published in plain
    jax.numpy (the reference's own) and how the first gradient is read
    from the program's state. A new optimizer is a new file."""
    return H.load_module("optimizers", traffic["optimizer"]["name"])


def _norm(x):
    import jax.numpy as jnp
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _norms(fam, cfg, leaves):
    """name -> norm of every leaf; a leaf the family says is several
    matrices fused along its first axis (``norm_parts``) gives one norm a
    part, ``name#i``: a part whose gradient is nought (a key's bias under
    softmax) must not hide in the norm of the whole."""
    parts = fam.norm_parts(cfg) if hasattr(fam, "norm_parts") else {}
    out = {}
    for n, x in leaves.items():
        k = parts.get(n, 1)
        if k == 1:
            out[n] = _norm(x)
        else:
            rows = x.shape[0] // k
            for i in range(k):
                out["%s#%d" % (n, i)] = _norm(x[i * rows:(i + 1) * rows])
    return out


class Probe:
    """Per-leaf norms of the program's first gradient and of its
    parameters' change, computed on the device beside the program's state
    (the initial weights are made again from the seed, never kept)."""

    def __init__(self, fam, cfg, traffic):
        import jax
        specs = fam.param_specs(cfg)
        opt = traffic["optimizer"]
        rule = optimizer_rule(traffic)
        order = {n: i for i, n in enumerate(sorted(specs))}

        def w0_of(key, n):
            import jax.numpy as jnp
            return H._leaf(jax.random.fold_in(key, order[n]), specs[n][0],
                           specs[n][1], jnp.float32)

        def grads(key, state):
            return _norms(fam, cfg, {
                n: rule.first_gradient(opt, state[n], w0_of(key, n))
                for n in specs})

        def delta(key, params):
            return _norms(fam, cfg, {n: params[n] - w0_of(key, n)
                                     for n in specs})

        self.grads = jax.jit(grads)
        self.delta = jax.jit(delta)


def ring_batches(fam, cfg, traffic, seed, like=None):
    import jax
    key = H.key_for(seed, stream=2)
    make = jax.jit(lambda k: fam.make_batch(k, cfg, traffic))
    return place_like([make(jax.random.fold_in(key, i))
                       for i in range(traffic["ring"])], like)


def program_first_steps(trainer, probe, fam, cfg, traffic, seed, ring):
    """(Re)start ``trainer`` from the seed's weights and drive it through
    the first steps by the window's own call. Returns the readings the
    reference is compared with."""
    import jax
    import jax.numpy as jnp
    weights = H.make_weights(fam.param_specs(cfg), seed, jnp.float32)
    aux = H.make_weights(fam.aux_specs(cfg), seed, jnp.float32) \
        if fam.aux_specs(cfg) else None
    trainer.init_params(weights, aux)
    del weights, aux
    key = H.key_for(seed, stream=1)
    rows_fn = jax.jit(fam.row_losses)
    rows, gnorm = [], None
    for i in range(FIRST_STEPS):
        outs = trainer.step(ring[i % len(ring)])
        rows.append(rows_fn(outs, ring[i % len(ring)]))
        if i == 0:
            gnorm = probe.grads(key, trainer.opt_state)
    dnorm = probe.delta(key, trainer.params)
    return _readings(jax.device_get((rows, gnorm, dnorm)))


def _readings(got):
    rows, gnorm, dnorm = got
    return {"loss": [float(np.mean(r, dtype=np.float64)) for r in rows],
            "rows": np.asarray(rows[0], np.float64),
            "grad": {k: float(v) for k, v in gnorm.items()},
            "delta": {k: float(v) for k, v in dnorm.items()}}


# -- the reference -------------------------------------------------------------

FAULTS = {"half_batch": None, "lr_x1.3": ("learning_rate", 1.3)}


def reference_first_steps(fam, cfg, traffic, seed, ring=None,
                          precision=None, fault=None):
    """The plain reference through the same first steps: float32 at full
    matmul precision, the same weights and batches from the seed.
    ``precision`` makes it the control; ``fault`` plants one of the
    faults a training cell can have in it: "half_batch" (the second half
    of every batch replaced by the first) and "lr_x1.3" (a wrong update:
    the learning rate 30% up). The optimizer's numbers are arguments of
    the compiled step, so a wrong update costs no compile."""
    import jax
    import jax.numpy as jnp
    if fault is not None and fault not in FAULTS:
        raise H.BenchError("train: unknown fault %r" % fault)
    rule = optimizer_rule(traffic)
    names = {k: v for k, v in traffic["optimizer"].items()
             if isinstance(v, str)}
    hyper = {k: jnp.float32(v) for k, v in traffic["optimizer"].items()
             if k not in names}
    if FAULTS.get(fault):
        key, factor = FAULTS[fault]
        hyper[key] = hyper[key] * factor
    ring = ring or ring_batches(fam, cfg, traffic, seed)

    def halve(batch):
        out = {}
        for k, v in batch.items():
            h = v.shape[0] // 2
            out[k] = jnp.concatenate([v[:h], v[:h]], axis=0)
        return out

    def one(w, state, batch, t, hyper):
        if fault == "half_batch":
            batch = halve(batch)
        (_, rows), g = jax.value_and_grad(
            lambda p: fam.reference_loss(p, batch, cfg, precision),
            has_aux=True)(w)
        opt = dict(names, **hyper)
        new_w, new_state = {}, {}
        for n in w:
            new_w[n], new_state[n] = rule.update(opt, w[n], g[n], state[n],
                                                 t)
        return new_w, new_state, rows, _norms(fam, cfg, g)

    with jax.default_matmul_precision("highest"):
        w = H.make_weights(fam.param_specs(cfg), seed, jnp.float32)
        state = jax.jit(lambda p: {n: rule.init(v) for n, v in p.items()})(w)
        step = jax.jit(one, donate_argnums=(0, 1))
        rows, gnorm = [], None
        for i in range(FIRST_STEPS):
            w, state, r, gn = step(w, state, ring[i % len(ring)],
                                   jnp.float32(i + 1), hyper)
            rows.append(r)
            if i == 0:
                gnorm = gn
        del state
        w0 = H.make_weights(fam.param_specs(cfg), seed, jnp.float32)
        dnorm = jax.jit(lambda a, b: _norms(
            fam, cfg, {n: a[n] - b[n] for n in a}))(w, w0)
        got = jax.device_get((rows, gnorm, dnorm))
    return _readings(got)


def compare(prog, ref):
    """The numbers a training cell is judged by: name -> (value, leaf).
    ``row_loss1`` is the steady form of ``loss1``: the first step's loss
    row by row, the root mean square of the rows' gaps over the mean
    loss -- signed gaps of rows cancel in a mean, and how far they
    cancel swings from seed to seed."""
    out = {}
    for i in range(FIRST_STEPS):
        out["loss%d" % (i + 1)] = (
            abs(prog["loss"][i] - ref["loss"][i]) / abs(ref["loss"][i]),
            None)
    out["row_loss1"] = (float(np.sqrt(np.mean(np.square(
        prog["rows"] - ref["rows"])))) / abs(ref["loss"][0]), None)
    out["grad_gap"] = H.worst_leaf_gap(prog["grad"], ref["grad"])
    # leaves whose gradient is nought to rounding in the reference move,
    # under Adam, by round-off alone: left out of the change by a rule on
    # the reference's gradient, not by name
    med = float(np.median(list(ref["grad"].values())))
    skip = {n for n, g in ref["grad"].items() if g < 1e-3 * med}
    out["delta_gap"] = H.worst_leaf_gap(prog["delta"], ref["delta"], skip)
    # the same gaps for the median leaf: steady from seed to seed where
    # the worst leaf of a deep BatchNorm net swings (PERF.md section 2)
    out["grad_gap_median"] = (H.median_leaf_gap(prog["grad"], ref["grad"]),
                              None)
    out["delta_gap_median"] = (H.median_leaf_gap(prog["delta"], ref["delta"],
                                                 skip), None)
    return out


def leaf_gaps(prog, ref, what, top=6):
    """[(leaf, gap)] of the ``top`` worst leaves of ``what`` ("grad" or
    "delta"), by the contract's measure: for reading by hand."""
    med = float(np.median(list(ref[what].values())))
    gaps = [(n, abs(prog[what][n] - r) / max(r, med))
            for n, r in ref[what].items()]
    return sorted(gaps, key=lambda x: -x[1])[:top]


# -- one run ---------------------------------------------------------------------

def _window(trainer, ring, start, seconds, ann):
    """Dispatch steps for ``seconds``, at most RUN_AHEAD ahead of the
    device; the window closes when the last step's state is ready."""
    import jax
    inflight = collections.deque()
    dispatch = []
    n = 0
    t0 = time.perf_counter()
    while True:
        batch = ring[(start + n) % len(ring)]
        ts = time.perf_counter()
        with ann("bench.trainer_step"):
            outs = trainer.step(batch)
        dispatch.append(time.perf_counter() - ts)
        n += 1
        inflight.append(outs[0])
        if len(inflight) > RUN_AHEAD:
            with ann("bench.wait_device"):
                jax.block_until_ready(inflight.popleft())
        if time.perf_counter() - t0 >= seconds:
            break
    with ann("bench.wait_device"):
        jax.block_until_ready((outs, trainer.params))
    return time.perf_counter() - t0, n, dispatch


def step_temp_bytes(trainer, batch):
    """Temporaries of the compiled step program, which the allocator's
    peak leaves out: the persistent cache holds the program, so this is a
    read, not a compile."""
    import jax
    lr = np.float32(trainer.optimizer.lr)
    with trainer.mesh:
        compiled = trainer._jit_step.lower(
            trainer.params, trainer.opt_state, trainer.aux,
            trainer._shard_batch(batch, "step"), lr, np.int32(trainer._t),
            trainer._rng).compile()
    ma = compiled.memory_analysis()
    return int(getattr(ma, "temp_size_in_bytes", 0) or 0)


def run(ctx):
    import jax
    import mxnet_tpu as mx
    from benchmark import trace as T

    cellinfo, args = ctx["cell"], ctx["args"]
    fam, cfg, traffic = cellinfo["family"], cellinfo["cfg"], \
        cellinfo["traffic"]
    seed = args.seed
    mx.random.seed(seed % (2 ** 31))
    counter = ctx["counter"]

    # -- set-up: one object, driven from the seed through its first steps
    trainer, like = build_trainer(mx, fam, cfg, traffic)
    probe = Probe(fam, cfg, traffic)
    ring = ring_batches(fam, cfg, traffic, seed, like)
    del like
    prog = program_first_steps(trainer, probe, fam, cfg, traffic, seed,
                               ring)
    n0 = FIRST_STEPS
    for i in range(traffic.get("warm_steps", 2)):
        outs = trainer.step(ring[(n0 + i) % len(ring)])
    n0 += traffic.get("warm_steps", 2)
    jax.block_until_ready((outs, trainer.params))
    setup_s = ctx["clock"]()
    compiles0 = counter.compiles

    # -- the window
    null = T.null_annotation
    traced = None
    if args.trace:
        tsec = min(traffic.get("trace_seconds", 2.0), args.seconds / 2)
        window_s, steps, dispatch = _window(trainer, ring, n0,
                                            args.seconds - tsec, null)
        with T.Capture(ctx["trace_dir"]) as cap:
            tw, tn, _ = _window(trainer, ring, n0 + steps, tsec,
                                T.annotation)
        traced = cap
        steps_all = steps + tn
    else:
        window_s, steps, dispatch = _window(trainer, ring, n0,
                                            args.seconds, null)
        steps_all = steps
    compiles_in_window = counter.compiles - compiles0
    step_ms = window_s / steps * 1e3

    # -- memory, then free the program's state before the reference runs
    peak, live = H.memory_now(ctx["devices"])
    t_mem = time.perf_counter()
    temp = step_temp_bytes(trainer, ring[0])
    t_mem = time.perf_counter() - t_mem
    cache_size = trainer._jit_step._cache_size()
    del trainer, probe, outs
    print("train: steps=%d window_s=%.4f step_ms=%.4f setup_s=%.2f "
          "allocator_peak=%d live=%d step_temporaries=%d (read in %.1f s) "
          "step_programs=%d"
          % (steps_all, window_s, step_ms, setup_s, peak, live, temp, t_mem,
             cache_size), flush=True)

    # -- correct: the timed object's first steps against the reference
    t_ref = time.perf_counter()
    ref = reference_first_steps(fam, cfg, traffic, seed, ring)
    print("train: reference took %.1f s" % (time.perf_counter() - t_ref),
          flush=True)
    checks = H.Checks(cellinfo["limits"])
    for name, (value, leaf) in compare(prog, ref).items():
        if name in checks.limits:
            checks.add(name, value)
        if leaf:
            print("train: %s worst leaf %s" % (name, leaf), flush=True)
    checks.add("compiles_in_window", compiles_in_window, 0)

    spans = {"dispatch_s": dispatch, "steps": steps, "window_s": window_s,
             "step_ms": step_ms}
    return {"checks": checks, "attempted": steps_all, "failed": 0,
            "end_to_end": {"train_step_ms": step_ms, "setup_s": setup_s},
            "spans": spans, "capture": traced,
            "memory_peak_bytes": max(peak, live + temp)}
