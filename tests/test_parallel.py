"""Parallel subsystem tests on the virtual 8-device CPU mesh.

Oracle strategy (SURVEY.md §4): exact-value checks of the sharded fused
train step against the single-device Executor + eager optimizer path (the
reference's CPU-vs-GPU consistency harness, re-aimed at
replicated-vs-sharded), plus reference-math checks for ring attention
against dense attention.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.parallel import P
from jax import shard_map


def _mlp_symbol():
    data = mx.symbol.Variable("data")
    fc1 = mx.symbol.FullyConnected(data=data, name="fc1", num_hidden=32)
    act = mx.symbol.Activation(data=fc1, name="relu1", act_type="relu")
    fc2 = mx.symbol.FullyConnected(data=act, name="fc2", num_hidden=10)
    return mx.symbol.SoftmaxOutput(data=fc2, name="softmax")


def test_build_mesh():
    mesh = par.build_mesh({"dp": 4, "tp": 2})
    assert mesh.shape == {"dp": 4, "tp": 2}
    mesh = par.build_mesh({"dp": -1, "tp": 2})
    assert mesh.shape["dp"] * 2 == len(jax.devices())
    with pytest.raises(mx.MXNetError):
        par.build_mesh({"dp": 999})


def test_sharding_rules_fallback():
    mesh = par.build_mesh({"dp": 4, "tp": 2})
    rules = par.ShardingRules(mesh, param_rules=[
        (r"fc\d+_weight$", P("tp", None)),
    ])
    # divisible dim -> sharded
    assert rules.param_spec("fc1_weight", (32, 784)) == P("tp")
    # non-divisible dim -> dropped back to replication
    assert rules.param_spec("fc1_weight", (33, 784)) == P()
    # unmatched name -> replicated
    assert rules.param_spec("fc1_bias", (32,)) == P()
    # data: batch divisible by dp
    assert rules.data_spec("data", (64, 784)) == P("dp")
    assert rules.data_spec("data", (6, 784)) == P()


def _train_reference(sym, data, label, lr, momentum, steps):
    """Single-device Executor + eager SGD — the oracle."""
    batch = data.shape[0]
    ctx = mx.cpu()
    arg_names = sym.list_arguments()
    shapes = {"data": data.shape, "softmax_label": label.shape}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    rng = np.random.RandomState(7)
    args = {}
    for n, s in zip(arg_names, arg_shapes):
        if n in shapes:
            args[n] = mx.nd.zeros(s, ctx)
        else:
            args[n] = mx.nd.array(rng.uniform(-0.07, 0.07, s).astype("f"))
    grads = {n: mx.nd.zeros(s, ctx) for n, s in zip(arg_names, arg_shapes)
             if n not in shapes}
    exe = sym.bind(ctx, args, args_grad=grads)
    opt = mx.optimizer.create("sgd", rescale_grad=1.0 / batch,
                              learning_rate=lr, momentum=momentum)
    updater = mx.optimizer.get_updater(opt)
    param_names = [n for n in arg_names if n not in shapes]
    args["data"][:] = data
    args["softmax_label"][:] = label
    for _ in range(steps):
        exe.forward(is_train=True)
        exe.backward()
        for i, n in enumerate(param_names):
            updater(i, grads[n], args[n])
    return {n: args[n].asnumpy() for n in param_names}


@pytest.mark.parametrize("mesh_axes", [{"dp": 8}, {"dp": 4, "tp": 2}])
def test_fused_step_matches_executor(mesh_axes):
    """The sharded fused train step must produce the same parameters as
    the single-device executor loop (the dist_sync exact-value oracle)."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(0)
    data = rng.randn(16, 64).astype(np.float32)
    label = rng.randint(0, 10, (16,)).astype(np.float32)
    lr, momentum, steps = 0.1, 0.9, 3

    ref = _train_reference(sym, data, label, lr, momentum, steps)

    mesh = par.build_mesh(mesh_axes)
    rules = par.ShardingRules(mesh, param_rules=[
        # tensor-parallel FC: shard num_hidden (output) dim over tp
        (r"_weight$", P("tp", None)),
        (r"_bias$", P("tp")),
    ])
    trainer = par.ParallelTrainer(
        sym, {"data": data.shape, "softmax_label": label.shape},
        optimizer="sgd", mesh=mesh, rules=rules,
        optimizer_params={"learning_rate": lr, "momentum": momentum})
    init_rng = np.random.RandomState(7)
    arg_shapes, _, _ = sym.infer_shape(data=data.shape,
                                       softmax_label=label.shape)
    arg_params = {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n not in ("data", "softmax_label"):
            arg_params[n] = mx.nd.array(
                init_rng.uniform(-0.07, 0.07, s).astype("f"))
    trainer.init_params(arg_params)
    for _ in range(steps):
        trainer.step({"data": data, "softmax_label": label})
    got, _ = trainer.get_params()
    for n in ref:
        np.testing.assert_allclose(got[n].asnumpy(), ref[n],
                                   rtol=2e-4, atol=2e-5, err_msg=n)


def test_fused_step_adam():
    """Functional Adam inside the fused step matches eager Adam."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(1)
    data = rng.randn(8, 32).astype(np.float32)
    label = rng.randint(0, 10, (8,)).astype(np.float32)

    # eager oracle
    ctx = mx.cpu()
    shapes = {"data": data.shape, "softmax_label": label.shape}
    arg_names = sym.list_arguments()
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    init = np.random.RandomState(3)
    params0 = {n: init.uniform(-0.1, 0.1, s).astype("f")
               for n, s in zip(arg_names, arg_shapes) if n not in shapes}
    args = {n: mx.nd.array(params0[n]) if n in params0 else mx.nd.zeros(s)
            for n, s in zip(arg_names, arg_shapes)}
    grads = {n: mx.nd.zeros(params0[n].shape) for n in params0}
    exe = sym.bind(ctx, args, args_grad=grads)
    opt = mx.optimizer.create("adam", rescale_grad=1.0 / 8)
    updater = mx.optimizer.get_updater(opt)
    args["data"][:] = data
    args["softmax_label"][:] = label
    pnames = [n for n in arg_names if n in params0]
    for _ in range(2):
        exe.forward(is_train=True)
        exe.backward()
        for i, n in enumerate(pnames):
            updater(i, grads[n], args[n])

    mesh = par.data_parallel_mesh()
    trainer = par.ParallelTrainer(
        sym, shapes, optimizer="adam", mesh=mesh)
    trainer.init_params({n: mx.nd.array(v) for n, v in params0.items()})
    for _ in range(2):
        trainer.step({"data": data, "softmax_label": label})
    got, _ = trainer.get_params()
    for n in pnames:
        np.testing.assert_allclose(got[n].asnumpy(), args[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)


def test_trainer_fit_converges():
    """Small-model convergence oracle (reference tests/python/train)."""
    rng = np.random.RandomState(42)
    n = 512
    x = rng.randn(n, 16).astype(np.float32)
    w_true = rng.randn(16, 3).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1).astype(np.float32)

    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc", num_hidden=3)
    sym = mx.symbol.SoftmaxOutput(data=fc, name="softmax")

    train_iter = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=False)
    mesh = par.data_parallel_mesh()
    trainer = par.ParallelTrainer(
        sym, {"data": (64, 16), "softmax_label": (64,)},
        optimizer="sgd", mesh=mesh,
        optimizer_params={"learning_rate": 0.5})
    trainer.init_params()
    trainer.fit(train_iter, num_epoch=10)
    # evaluate
    train_iter.reset()
    correct = total = 0
    for b in train_iter:
        out = trainer.forward({"data": b.data[0],
                               "softmax_label": b.label[0]})
        pred = np.argmax(np.asarray(out[0]), axis=1)
        correct += (pred == b.label[0].asnumpy()).sum()
        total += len(pred)
    assert correct / total > 0.9, correct / total


def test_batchnorm_global_stats_in_dp():
    """BatchNorm under dp sharding uses GLOBAL batch statistics — one
    logical program semantics (better than the reference's per-device
    stats; this pins the behavior)."""
    data = mx.symbol.Variable("data")
    bn = mx.symbol.BatchNorm(data=data, name="bn")
    sym = mx.symbol.LinearRegressionOutput(
        data=bn, label=mx.symbol.Variable("label"), name="lro")
    rng = np.random.RandomState(0)
    x = rng.randn(16, 4).astype(np.float32) * 3 + 1
    lbl = np.zeros((16, 4), np.float32)
    mesh = par.data_parallel_mesh()
    tr = par.ParallelTrainer(sym, {"data": x.shape, "label": lbl.shape},
                             optimizer="sgd", mesh=mesh)
    tr.init_params()
    out = tr.step({"data": x, "label": lbl})
    got = np.asarray(out[0])
    expect = (x - x.mean(0)) / np.sqrt(x.var(0) + 1e-3)
    gamma = tr.params["bn_gamma"]
    np.testing.assert_allclose(got, expect * np.asarray(gamma)[None, :],
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# ring attention / blockwise attention

def _dense_attention(q, k, v, causal):
    B, T, H, D = q.shape
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention(causal):
    rng = np.random.RandomState(0)
    q = rng.randn(2, 24, 2, 8).astype(np.float32)
    k = rng.randn(2, 24, 2, 8).astype(np.float32)
    v = rng.randn(2, 24, 2, 8).astype(np.float32)
    out = par.blockwise_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                                  causal=causal, block_size=7)
    np.testing.assert_allclose(np.asarray(out),
                               _dense_attention(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention(causal):
    rng = np.random.RandomState(1)
    n = 8
    q = rng.randn(2, 4 * n, 2, 8).astype(np.float32)
    k = rng.randn(2, 4 * n, 2, 8).astype(np.float32)
    v = rng.randn(2, 4 * n, 2, 8).astype(np.float32)
    mesh = par.build_mesh({"sp": n})
    out = jax.jit(lambda a, b, c: par.ring_attention(
        a, b, c, mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               _dense_attention(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)


def test_ring_self_attention_runs():
    rng = np.random.RandomState(2)
    E, H = 16, 4
    x = rng.randn(2, 16, E).astype(np.float32)
    ws = [rng.randn(E, E).astype(np.float32) * 0.1 for _ in range(4)]
    mesh = par.build_mesh({"dp": 2, "sp": 4})
    out = par.ring_self_attention(jnp.array(x), *map(jnp.array, ws),
                                  mesh=mesh, num_heads=H)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# pipeline

def test_pipeline_spmd():
    """4-stage pipeline of y = x @ w_s must equal the sequential product."""
    n_stage, M, mb, d = 4, 6, 2, 8
    rng = np.random.RandomState(3)
    ws = rng.randn(n_stage, d, d).astype(np.float32) * 0.3
    x = rng.randn(M, mb, d).astype(np.float32)
    mesh = par.build_mesh({"pp": n_stage})

    def stage(w, xb):
        return xb @ w[0]  # w arrives with a leading stage dim of size 1

    def run(ws, x):
        out = par.pipeline_spmd(stage, ws, x, axis_name="pp")
        # broadcast the last stage's result to all: sum over pp (others zero)
        return jax.lax.psum(out, "pp")

    mapped = shard_map(run, mesh=mesh,
                       in_specs=(P("pp"), P()), out_specs=P(),
                       check_vma=False)
    got = np.asarray(mapped(jnp.array(ws), jnp.array(x)))
    expect = x
    for s in range(n_stage):
        expect = expect @ ws[s]
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)


def test_collectives_exact_values():
    """Exact-value collective test à la tests/nightly/dist_sync_kvstore.py:
    psum of rank+1 over n ranks == n(n+1)/2."""
    n = 8
    mesh = par.build_mesh({"dp": n})

    def f(x):
        r = jax.lax.axis_index("dp").astype(jnp.float32) + 1.0
        return par.collectives.psum(r * x, "dp")

    out = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                    check_vma=False)(jnp.ones(()))
    assert float(out) == n * (n + 1) / 2


def test_trainer_bf16_mixed_precision_converges():
    """compute_dtype=bfloat16: forward/backward run in bf16 while master
    params/opt state stay f32 (grad flows back through the cast vjp);
    convergence must match the f32 oracle to coarse tolerance."""
    import jax.numpy as jnp
    rng = np.random.RandomState(7)
    n = 512
    x = rng.randn(n, 16).astype(np.float32)
    w_true = rng.randn(16, 3).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1).astype(np.float32)

    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc", num_hidden=3)
    sym = mx.symbol.SoftmaxOutput(data=fc, name="softmax")

    train_iter = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=False)
    trainer = par.ParallelTrainer(
        sym, {"data": (64, 16), "softmax_label": (64,)},
        optimizer="sgd", mesh=par.data_parallel_mesh(),
        optimizer_params={"learning_rate": 0.5},
        compute_dtype="bfloat16")
    trainer.init_params()
    trainer.fit(train_iter, num_epoch=10)
    assert trainer.params["fc_weight"].dtype == jnp.float32  # master stays f32
    train_iter.reset()
    correct = total = 0
    for b in train_iter:
        out = trainer.forward({"data": b.data[0],
                               "softmax_label": b.label[0]})
        pred = np.argmax(np.asarray(out[0]), axis=1)
        correct += (pred == b.label[0].asnumpy()).sum()
        total += len(pred)
    assert correct / total > 0.85, correct / total


def test_remat_step_matches_plain():
    """Gradient mirroring (MXNET_BACKWARD_DO_MIRROR ≙ jax.checkpoint)
    must not change the numerics — only the memory/compute tradeoff."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(0)
    data = rng.randn(8, 64).astype(np.float32)
    label = rng.randint(0, 10, (8,)).astype(np.float32)
    shapes = {"data": data.shape, "softmax_label": label.shape}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    arg_params = {n: mx.nd.array(
        np.random.RandomState(5).uniform(-0.07, 0.07, s).astype("f"))
        for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in shapes}
    results = []
    for remat in (False, True):
        trainer = par.ParallelTrainer(
            sym, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            remat=remat)
        trainer.init_params({k: v.copy() for k, v in arg_params.items()})
        for _ in range(2):
            trainer.step({"data": data, "softmax_label": label})
        got, _ = trainer.get_params()
        results.append({k: v.asnumpy() for k, v in got.items()})
    for n in results[0]:
        np.testing.assert_allclose(results[0][n], results[1][n],
                                   rtol=1e-5, atol=1e-6, err_msg=n)


def test_sequence_parallel_trainer_matches_dense():
    """Long-context path: transformer LM trained with ring attention
    over a dp=2 x sp=4 mesh must produce the same parameters as the
    single-device dense-attention fused step — the exact-value oracle
    for sequence/context parallelism."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 12, 4, 16, 8
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    steps = 2

    def init_for(sym):
        # infer on GLOBAL shapes with the dense symbol for param shapes
        arg_shapes, _, _ = sym.infer_shape(**shapes)
        prng = np.random.RandomState(3)
        return {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
                for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in shapes}

    # reference: single-device dense attention
    dense_sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                                   num_heads=2, impl="dense")
    ref_tr = par.ParallelTrainer(
        dense_sym, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9})
    init = init_for(dense_sym)
    ref_tr.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(steps):
        ref_tr.step({"data": data, "softmax_label": label})
    want, _ = ref_tr.get_params()

    # sequence-parallel: ring attention over sp=4, batch over dp=2
    ring_sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                                  num_heads=2, impl="ring")
    mesh = par.build_mesh({"dp": 2, "sp": 4})
    sp_tr = par.SequenceParallelTrainer(
        ring_sym, shapes, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                          "rescale_grad": 1.0 / B})
    sp_tr.init_params({k: v.copy() for k, v in init.items()})
    losses = []
    for _ in range(steps):
        losses.append(sp_tr.step({"data": data, "softmax_label": label}))
    got = sp_tr.get_params()

    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)
    assert losses[1] < losses[0]  # it is actually learning


def test_sequence_parallel_adam_finite():
    """Adam's bias correction needs the 1-based update count — the first
    sp step must stay finite (regression: t=0 divided by 1-beta^0=0)."""
    from mxnet_tpu.models import get_transformer_lm
    sym = get_transformer_lm(8, num_layers=1, embed_dim=8, num_heads=2,
                             impl="ring")
    mesh = par.build_mesh({"dp": 2, "sp": 4})
    tr = par.SequenceParallelTrainer(
        sym, {"data": (4, 8), "softmax_label": (4, 8)}, mesh,
        optimizer="adam", optimizer_params={"learning_rate": 1e-3})
    tr.init_params()
    rng = np.random.RandomState(0)
    nll = tr.step({"data": rng.randint(0, 8, (4, 8)).astype(np.float32),
                   "softmax_label": rng.randint(0, 8, (4, 8)
                                                ).astype(np.float32)})
    assert np.isfinite(float(nll))
    for v in tr.params.values():
        assert np.isfinite(np.asarray(jax.device_get(v))).all()


def test_sequence_parallel_sgld_replicated_params_consistent():
    """Stochastic optimizers must apply IDENTICAL noise to every shard
    of a replicated param (regression: the shard-folded dropout rng was
    passed to opt_update, silently diverging the replica buffers under
    check_vma=False)."""
    from mxnet_tpu.models import get_transformer_lm
    sym = get_transformer_lm(8, num_layers=1, embed_dim=8, num_heads=2,
                             impl="ring")
    mesh = par.build_mesh({"dp": 2, "sp": 4})
    tr = par.SequenceParallelTrainer(
        sym, {"data": (4, 8), "softmax_label": (4, 8)}, mesh,
        optimizer="sgld", optimizer_params={"learning_rate": 1e-2})
    tr.init_params()
    rng = np.random.RandomState(0)
    for _ in range(2):
        tr.step({"data": rng.randint(0, 8, (4, 8)).astype(np.float32),
                 "softmax_label": rng.randint(0, 8, (4, 8)
                                              ).astype(np.float32)})
    for name, v in tr.params.items():
        shards = [np.asarray(s.data) for s in v.addressable_shards
                  if s.index == v.addressable_shards[0].index]
        for s in shards[1:]:
            np.testing.assert_array_equal(
                shards[0], s, err_msg="%s replica divergence" % name)


def test_moe_expert_parallel_matches_single_device():
    """Expert parallelism: MoE transformer trained with experts sharded
    over ep=4 must match the unsharded single-device step exactly."""
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.models.transformer import ep_rules

    vocab, B, T, E = 10, 4, 8, 8
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                             num_heads=2, impl="dense", num_experts=4)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    prng = np.random.RandomState(5)
    init = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}

    results = []
    for mesh_axes, rules in [({"dp": 1}, None),
                             ({"dp": 2, "ep": 4},
                              par.ShardingRules(par.build_mesh(
                                  {"dp": 2, "ep": 4}), param_rules=ep_rules()))]:
        mesh = par.build_mesh(mesh_axes) if rules is None else rules.mesh
        tr = par.ParallelTrainer(
            sym, shapes, optimizer="sgd", mesh=mesh, rules=rules,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        tr.init_params({k: v.copy() for k, v in init.items()})
        for _ in range(2):
            tr.step({"data": data, "softmax_label": label})
        got, _ = tr.get_params()
        results.append({k: v.asnumpy() for k, v in got.items()})
    for n in results[0]:
        np.testing.assert_allclose(results[0][n], results[1][n],
                                   rtol=2e-4, atol=2e-5, err_msg=n)


def test_sharded_checkpoint_roundtrip(tmp_path):
    """Per-process sharded checkpoints (parallel/checkpoint.py): a
    dp x tp trainer saves shard files + manifest, a fresh trainer
    restores them, and training continues bit-identically."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(0)
    data = rng.randn(16, 64).astype(np.float32)
    label = rng.randint(0, 10, (16,)).astype(np.float32)
    shapes = {"data": data.shape, "softmax_label": label.shape}
    mesh = par.build_mesh({"dp": 2, "tp": 4})
    rules = par.ShardingRules(mesh, param_rules=[
        (r"_weight$", P("tp", None)), (r"_bias$", P("tp"))])

    def make():
        return par.ParallelTrainer(
            sym, shapes, optimizer="sgd", mesh=mesh, rules=rules,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})

    tr = make()
    tr.init_params()
    for _ in range(2):
        tr.step({"data": data, "softmax_label": label})
    prefix = str(tmp_path / "ckpt")
    tr.save_sharded_checkpoint(prefix)
    assert (tmp_path / "ckpt-manifest.json").exists()
    assert (tmp_path / "ckpt-shards-p0.npz").exists()

    # continue original
    tr.step({"data": data, "softmax_label": label})
    want, _ = tr.get_params()

    # restore into a FRESH trainer (no init_params) and continue
    tr2 = make()
    tr2.restore_sharded_checkpoint(prefix)
    assert tr2._t == 2
    # restored shardings match the rules
    for n, v in tr2.params.items():
        assert v.sharding.spec == tr.params[n].sharding.spec, n
    tr2.step({"data": data, "softmax_label": label})
    got, _ = tr2.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=1e-6, atol=1e-7, err_msg=n)


def test_sharded_checkpoint_adafactor_fsdp(tmp_path):
    """Sharded checkpoints round-trip AdaFactor's FACTORED optimizer
    state (lower-rank moment leaves) under fsdp — training continues
    bit-identically from the restore."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(3)
    data = rng.randn(16, 64).astype(np.float32)
    label = rng.randint(0, 10, (16,)).astype(np.float32)
    shapes = {"data": data.shape, "softmax_label": label.shape}

    def make():
        return par.ParallelTrainer(
            sym, shapes, optimizer="adafactor",
            mesh=par.build_mesh({"dp": 8}), fsdp=True,
            optimizer_params={"learning_rate": 0.02})

    tr = make()
    tr.init_params()
    for _ in range(2):
        tr.step({"data": data, "softmax_label": label})
    prefix = str(tmp_path / "afck")
    tr.save_sharded_checkpoint(prefix)
    tr.step({"data": data, "softmax_label": label})
    want, _ = tr.get_params()

    tr2 = make()
    tr2.restore_sharded_checkpoint(prefix)
    assert tr2._t == 2
    # the factored moment leaves came back with their shapes + dtypes
    for a, b in zip(jax.tree_util.tree_leaves(tr.opt_state["fc1_weight"]),
                    jax.tree_util.tree_leaves(tr2.opt_state["fc1_weight"])):
        assert a.shape == b.shape and a.dtype == b.dtype
    tr2.step({"data": data, "softmax_label": label})
    got, _ = tr2.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=1e-6, atol=1e-7, err_msg=n)


def test_sp_sharded_checkpoint_roundtrip(tmp_path):
    """SequenceParallelTrainer sharded save/restore continues
    bit-identically (incl. the sequence-sharded positional embedding)."""
    from mxnet_tpu.models import get_transformer_lm
    vocab, B, T, E = 10, 4, 8, 8
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    mesh = par.build_mesh({"dp": 2, "sp": 4})
    sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                             num_heads=2, impl="ring")

    def make():
        return par.SequenceParallelTrainer(
            sym, shapes, mesh, optimizer="adam",
            optimizer_params={"learning_rate": 1e-2})

    tr = make()
    tr.init_params()
    tr.step({"data": data, "softmax_label": label})
    prefix = str(tmp_path / "sp")
    tr.save_sharded_checkpoint(prefix)
    tr.step({"data": data, "softmax_label": label})
    want = {k: v.asnumpy() for k, v in tr.get_params().items()}

    tr2 = make()
    tr2.restore_sharded_checkpoint(prefix)
    assert tr2._t == 1
    tr2.step({"data": data, "softmax_label": label})
    got = {k: v.asnumpy() for k, v in tr2.get_params().items()}
    for n in want:
        np.testing.assert_allclose(got[n], want[n], rtol=1e-6, atol=1e-7,
                                   err_msg=n)


def test_trainer_prefetch_matches_direct():
    """Double-buffered infeed (trainer.prefetch) must feed exactly the
    same batches in order — parameters after training match the
    unprefetched loop."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(0)
    host_batches = [{"data": rng.randn(16, 64).astype(np.float32),
                     "softmax_label": rng.randint(0, 10, (16,)
                                                  ).astype(np.float32)}
                    for _ in range(5)]
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    init = {n: mx.nd.array(np.random.RandomState(5)
                           .uniform(-0.07, 0.07, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}

    results = []
    for use_prefetch in (False, True):
        tr = par.ParallelTrainer(
            sym, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        tr.init_params({k: v.copy() for k, v in init.items()})
        if use_prefetch:
            for dev_batch in tr.prefetch(host_batches, depth=2):
                tr.step(dev_batch)
        else:
            for b in host_batches:
                tr.step(b)
        got, _ = tr.get_params()
        results.append({k: v.asnumpy() for k, v in got.items()})
    for n in results[0]:
        np.testing.assert_allclose(results[0][n], results[1][n],
                                   rtol=1e-6, atol=1e-7, err_msg=n)


def test_pipeline_trainer_matches_single_device():
    """ctx_group-staged transformer trained through the SPMD GPipe
    schedule (PipelineTrainer) must produce the SAME parameters as the
    single-device fused step — the exact-value oracle for pipeline
    parallelism (VERDICT r1 weak #6: pp must run a real model, with
    symbol-level stage partitioning, not an 8x8 matmul)."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 11, 8, 12, 16
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    steps = 2

    def init_for(sym):
        arg_shapes, _, _ = sym.infer_shape(**shapes)
        prng = np.random.RandomState(3)
        return {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
                for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in shapes}

    # oracle: single-device fused trainer on the same (untagged) model
    dense = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                               num_heads=2, impl="dense")
    ref = par.ParallelTrainer(
        dense, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9})
    init = init_for(dense)
    ref.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(steps):
        ref.step({"data": data, "softmax_label": label})
    want, _ = ref.get_params()

    # pipelined: 2 stages (embed+block0 | block1+head), 4 microbatches
    staged = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                                num_heads=2, impl="dense",
                                pipeline_stages=2)
    mesh = par.build_mesh({"pp": 2})
    pp = par.PipelineTrainer(
        staged, shapes, mesh, num_microbatches=4, optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                          "rescale_grad": 1.0 / B})
    pp.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(steps):
        out = pp.step({"data": data, "softmax_label": label})
    assert out.shape[0] == B
    got = pp.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)


def test_pipeline_partition_validation():
    """Bad cuts fail loudly: untagged symbols and skip-edges."""
    from mxnet_tpu.parallel.pipeline import partition_stages
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc", num_hidden=4)
    out = mx.symbol.SoftmaxOutput(data=fc, name="softmax")
    with pytest.raises(mx.base.MXNetError, match="ctx_group"):
        partition_stages(out)


@pytest.mark.slow
def test_pipeline_unequal_stages():
    """Stages with different layer counts (3 blocks over 2 stages) and
    therefore different parameter sets still train correctly — per-stage
    programs, not shape-padded clones.

    Slow sweep (tier-1 budget, PR 10): ~19s of compiles; tier-1
    pipeline coverage stays broad via trainer_matches_single_device,
    dp_pp_matches_single_device, multi_head, remat,
    1f1b_activation_memory_bounded and pp_sharded_big_params."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 7, 4, 8, 8
    rng = np.random.RandomState(1)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}

    staged = get_transformer_lm(vocab, num_layers=3, embed_dim=E,
                                num_heads=2, impl="dense",
                                pipeline_stages=2)
    dense = get_transformer_lm(vocab, num_layers=3, embed_dim=E,
                               num_heads=2, impl="dense")
    arg_shapes, _, _ = dense.infer_shape(**shapes)
    prng = np.random.RandomState(5)
    init = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
            for n, s in zip(dense.list_arguments(), arg_shapes)
            if n not in shapes}

    ref = par.ParallelTrainer(
        dense, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
        optimizer_params={"learning_rate": 0.1})
    ref.init_params({k: v.copy() for k, v in init.items()})
    ref.step({"data": data, "softmax_label": label})
    want, _ = ref.get_params()

    pp = par.PipelineTrainer(
        staged, shapes, par.build_mesh({"pp": 2}), num_microbatches=2,
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1,
                          "rescale_grad": 1.0 / B})
    pp.init_params({k: v.copy() for k, v in init.items()})
    pp.step({"data": data, "softmax_label": label})
    got = pp.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)


def test_collectives_broadcast_ring_bucketed():
    """broadcast/ring_exchange/bucketed_psum exact values on the CPU
    mesh (bucketed_psum must equal per-leaf psum regardless of bucket
    packing)."""
    from mxnet_tpu.parallel import collectives as coll
    from jax.sharding import PartitionSpec

    mesh = par.build_mesh({"dp": 8})
    x = np.arange(8, dtype=np.float32)

    def f(xs):
        r = coll.axis_index("dp").astype(np.float32)
        b = coll.broadcast(r * 10.0, "dp", root=3)
        ring = coll.ring_exchange(xs, "dp", shift=1)
        grads = {"a": xs * 2.0, "b": jnp.ones((3,)) * r,
                 "c": xs.reshape(1, 1) + r}
        red = coll.bucketed_psum(grads, "dp", bucket_bytes=8)
        ref = {k: coll.psum(v, "dp") for k, v in grads.items()}
        diff = sum(jnp.abs(red[k] - ref[k]).sum() for k in grads)
        return b, ring, diff

    b, ring, diff = jax.jit(shard_map(
        f, mesh=mesh, in_specs=PartitionSpec("dp"),
        out_specs=(PartitionSpec(), PartitionSpec("dp"),
                   PartitionSpec())))(x)
    np.testing.assert_allclose(np.asarray(b), 30.0)  # root 3's value
    np.testing.assert_allclose(np.asarray(ring),
                               np.roll(np.arange(8, dtype=np.float32), 1))
    np.testing.assert_allclose(np.asarray(diff), 0.0)


def test_pipeline_dp_pp_matches_single_device():
    """dp x pp composition: batch sharded over dp replica groups, each
    running its own pipeline; gradients psum over (dp, pp). Must equal
    the single-device fused step exactly."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 9, 8, 8, 8
    rng = np.random.RandomState(2)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}

    dense = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                               num_heads=2, impl="dense")
    staged = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                                num_heads=2, impl="dense",
                                pipeline_stages=2)
    arg_shapes, _, _ = dense.infer_shape(**shapes)
    prng = np.random.RandomState(6)
    init = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
            for n, s in zip(dense.list_arguments(), arg_shapes)
            if n not in shapes}

    ref = par.ParallelTrainer(
        dense, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9})
    ref.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(2):
        ref.step({"data": data, "softmax_label": label})
    want, _ = ref.get_params()

    pp = par.PipelineTrainer(
        staged, shapes, par.build_mesh({"dp": 2, "pp": 2}),
        num_microbatches=2, optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                          "rescale_grad": 1.0 / B})
    pp.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(2):
        out = pp.step({"data": data, "softmax_label": label})
    assert out.shape[0] == B
    got = pp.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)


def test_pipeline_multi_head():
    """Group-headed symbols pipeline correctly: every head's input is
    gated on fill/drain ticks (loss heads inject cotangent-independent
    gradients, so ungated extras would corrupt training); params must
    match the single-device trainer and the monitoring head's output
    must match the reference forward."""
    data = mx.symbol.Variable("data")
    fc1 = mx.symbol.FullyConnected(data=data, name="fc1", num_hidden=16)
    r1 = mx.symbol.Activation(data=fc1, act_type="relu", name="r1")
    with mx.AttrScope(ctx_group="stage1"):
        fc2 = mx.symbol.FullyConnected(data=r1, name="fc2", num_hidden=5)
        loss = mx.symbol.SoftmaxOutput(data=fc2, name="softmax")
        probe = mx.symbol.BlockGrad(data=fc2, name="probe")
    grouped = mx.symbol.Group([loss, probe])
    # tag the trunk
    for n in grouped._topo():
        if not n.is_var and n.attrs.get("ctx_group") is None:
            n.attrs["ctx_group"] = "stage0"

    B = 8
    rng = np.random.RandomState(3)
    datav = rng.randn(B, 12).astype(np.float32)
    label = rng.randint(0, 5, (B,)).astype(np.float32)
    shapes = {"data": (B, 12), "softmax_label": (B,)}
    arg_shapes, _, _ = grouped.infer_shape(**shapes)
    prng = np.random.RandomState(4)
    init = {n: mx.nd.array(prng.uniform(-0.2, 0.2, s).astype("f"))
            for n, s in zip(grouped.list_arguments(), arg_shapes)
            if n not in shapes}

    ref = par.ParallelTrainer(
        grouped, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9})
    ref.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(2):
        ref_outs = ref.step({"data": datav, "softmax_label": label})
    want, _ = ref.get_params()

    pp = par.PipelineTrainer(
        grouped, shapes, par.build_mesh({"pp": 2}), num_microbatches=4,
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                          "rescale_grad": 1.0 / B})
    pp.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(2):
        outs = pp.step({"data": datav, "softmax_label": label})
    assert isinstance(outs, list) and len(outs) == 2
    got = pp.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)
    np.testing.assert_allclose(np.asarray(outs[1]),
                               np.asarray(ref_outs[1]),
                               rtol=2e-4, atol=2e-5)


def test_zero1_optimizer_state_sharding():
    """ZeRO-1: optimizer state sharded over dp must produce EXACTLY the
    params of the replicated-state trainer (GSPMD derives the
    reduce-scatter/all-gather dataflow from out_shardings), while the
    state buffers actually live 1/dp per device."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(0)
    data = rng.randn(16, 64).astype(np.float32)
    label = rng.randint(0, 10, (16,)).astype(np.float32)
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    prng = np.random.RandomState(7)
    init = {n: mx.nd.array(prng.uniform(-0.07, 0.07, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}

    def train(zero1):
        mesh = par.build_mesh({"dp": 8})
        tr = par.ParallelTrainer(
            sym, shapes, optimizer="adam", mesh=mesh, zero1=zero1,
            optimizer_params={"learning_rate": 1e-2})
        tr.init_params({k: v.copy() for k, v in init.items()})
        for _ in range(3):
            tr.step({"data": data, "softmax_label": label})
        return tr

    plain = train(False)
    z1 = train(True)
    want, _ = plain.get_params()
    got, _ = z1.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)
    # the Adam moments are genuinely dp-sharded for divisible params
    mean_leaf = jax.tree_util.tree_leaves(z1.opt_state["fc1_weight"])[0]
    assert "dp" in str(mean_leaf.sharding.spec), mean_leaf.sharding
    # per-device bytes: sharded leaf holds 1/8th of the elements
    shard = mean_leaf.addressable_shards[0]
    assert shard.data.size * 8 == mean_leaf.size


def test_fsdp_param_sharding_matches_dense():
    """FSDP (ZeRO-3): params/optimizer state sharded over dp must train
    to the same weights as the replicated trainer (GSPMD inserts the
    use-site all-gathers and gradient reduce-scatter from the sharding
    annotations alone), while the param buffers actually live 1/dp per
    device."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(0)
    data = rng.randn(16, 64).astype(np.float32)
    label = rng.randint(0, 10, (16,)).astype(np.float32)
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    prng = np.random.RandomState(7)
    init = {n: mx.nd.array(prng.uniform(-0.07, 0.07, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}

    def train(fsdp):
        mesh = par.build_mesh({"dp": 8})
        tr = par.ParallelTrainer(
            sym, shapes, optimizer="adam", mesh=mesh, fsdp=fsdp,
            optimizer_params={"learning_rate": 1e-2})
        tr.init_params({k: v.copy() for k, v in init.items()})
        for _ in range(3):
            tr.step({"data": data, "softmax_label": label})
        return tr

    plain = train(False)
    sh = train(True)
    want, _ = plain.get_params()
    got, _ = sh.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)
    # the weights and Adam moments are genuinely dp-sharded
    w = sh.params["fc1_weight"]
    assert "dp" in str(w.sharding.spec), w.sharding
    assert w.addressable_shards[0].data.size * 8 == w.size
    mean_leaf = jax.tree_util.tree_leaves(sh.opt_state["fc1_weight"])[0]
    assert mean_leaf.sharding == w.sharding
    # eval path reads the sharded params in place
    out = sh.forward({"data": data, "softmax_label": label})
    assert np.asarray(out[0]).shape == (16, 10)


def test_fsdp_all_none_spec_sharded_like_replicated():
    """A rule-derived spec that is ALL None (e.g. P(None, None) when a
    tp rule failed to fit the mesh) is replicated in effect — FSDP must
    still give those params the 1/dp sharding instead of silently
    skipping them (round-5 advisor finding)."""
    from jax.sharding import NamedSharding

    sym = _mlp_symbol()
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    mesh = par.build_mesh({"dp": 8})

    class AllNoneRules(par.ShardingRules):
        def param_sharding(self, name, shape):
            return NamedSharding(self.mesh, P(*([None] * len(shape))))

    tr = par.ParallelTrainer(
        sym, shapes, optimizer="sgd", mesh=mesh,
        rules=AllNoneRules(mesh), fsdp=True,
        optimizer_params={"learning_rate": 1e-2})
    for n in tr.param_names:
        if any(d % 8 == 0 and d >= 8 for d in tr.arg_shapes[n]):
            assert "dp" in str(tr._param_sh[n].spec), \
                (n, tr._param_sh[n].spec)
    # and it actually trains: params live 1/dp per device
    tr.init_params()
    rng = np.random.RandomState(0)
    tr.step({"data": rng.randn(16, 64).astype(np.float32),
             "softmax_label": rng.randint(0, 10, (16,)).astype("f")})
    w = tr.params["fc1_weight"]
    assert w.addressable_shards[0].data.size * 8 == w.size


def test_grad_accum_matches_full_batch():
    """grad_accum=A scans microbatches inside one program and applies
    ONE update on the summed gradients — numerically the full-batch
    step (loss grads are batch sums, so partial sums compose); outputs
    come back batch-major."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(0)
    data = rng.randn(16, 64).astype(np.float32)
    label = rng.randint(0, 10, (16,)).astype(np.float32)
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    prng = np.random.RandomState(7)
    init = {n: mx.nd.array(prng.uniform(-0.07, 0.07, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}

    def train(accum):
        tr = par.ParallelTrainer(
            sym, shapes, optimizer="sgd", mesh=par.build_mesh({"dp": 4}),
            grad_accum=accum,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        tr.init_params({k: v.copy() for k, v in init.items()})
        outs = None
        for _ in range(3):
            outs = tr.step({"data": data, "softmax_label": label})
        return tr, np.asarray(outs[0])

    plain, out1 = train(1)
    accum, out4 = train(4)
    want, _ = plain.get_params()
    got, _ = accum.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)
    np.testing.assert_allclose(out4, out1, rtol=2e-5, atol=2e-6)


def test_sharded_checkpoint_async_write(tmp_path):
    """async_write=True snapshots device state synchronously (donated
    buffers may be overwritten by the next step) and writes on a
    background thread; the restored checkpoint reflects the state AT
    SAVE TIME, not at finalize time."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(0)
    data = rng.randn(16, 64).astype(np.float32)
    label = rng.randint(0, 10, (16,)).astype(np.float32)
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    mesh = par.build_mesh({"dp": 8})
    tr = par.ParallelTrainer(sym, shapes, optimizer="sgd", mesh=mesh,
                             optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9})
    tr.init_params()
    tr.step({"data": data, "softmax_label": label})
    want, _ = tr.get_params()
    prefix = str(tmp_path / "ck")
    fin = tr.save_sharded_checkpoint(prefix, async_write=True)
    # keep training WHILE the writer runs (donation overwrites buffers)
    for _ in range(3):
        tr.step({"data": data, "softmax_label": label})
    fin()
    tr2 = par.ParallelTrainer(sym, shapes, optimizer="sgd", mesh=mesh,
                              optimizer_params={"learning_rate": 0.1,
                                                "momentum": 0.9})
    tr2.restore_sharded_checkpoint(prefix)
    assert tr2._t == 1
    for n, v in tr2.params.items():
        np.testing.assert_array_equal(np.asarray(jax.device_get(v)),
                                      want[n].asnumpy(), err_msg=n)


def test_sharded_checkpoint_resume_roundtrip(tmp_path):
    """Crash-resume surface over sharded checkpoints: latest_step sees
    only COMPLETE checkpoints, save_sharded(async_write=True)+finalize()
    then load_sharded restores bit-identical arrays (params AND
    optimizer state), and resume_sharded_checkpoint returns the step
    (or None on a fresh/incomplete prefix)."""
    import json
    import os

    sym = _mlp_symbol()
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    rng = np.random.RandomState(5)
    data = rng.randn(16, 64).astype(np.float32)
    label = rng.randint(0, 10, (16,)).astype(np.float32)
    mesh = par.build_mesh({"dp": 8})
    tr = par.ParallelTrainer(sym, shapes, optimizer="sgd", mesh=mesh,
                             optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9})
    tr.init_params()
    prefix = str(tmp_path / "rs")
    assert par.latest_step(prefix) is None  # nothing there yet

    for _ in range(2):
        tr.step({"data": data, "softmax_label": label})
    fin = tr.save_sharded_checkpoint(prefix, async_write=True)
    fin()
    assert par.latest_step(prefix) == 2

    # the flat saved state (params + opt/ + aux/) round-trips exactly
    from mxnet_tpu.parallel.checkpoint import (flatten_train_state,
                                               load_sharded)
    want = {k: np.asarray(v) for k, v in flatten_train_state(
        tr.params, tr.opt_state, tr.aux_names, tr.aux).items()}
    flat, step, _ = load_sharded(prefix, mesh)
    assert step == 2
    assert set(flat) == set(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(flat[k]), want[k],
                                      err_msg=k)

    # resume: a fresh trainer picks the checkpoint up and reports step
    tr2 = par.ParallelTrainer(sym, shapes, optimizer="sgd", mesh=mesh,
                              optimizer_params={"learning_rate": 0.1,
                                                "momentum": 0.9})
    assert tr2.resume_sharded_checkpoint(prefix) == 2
    assert tr2._t == 2
    # both trainers take the SAME next step (momentum state restored)
    tr.step({"data": data, "softmax_label": label})
    tr2.step({"data": data, "softmax_label": label})
    a, _ = tr.get_params()
    b, _ = tr2.get_params()
    for n in a:
        np.testing.assert_allclose(b[n].asnumpy(), a[n].asnumpy(),
                                   rtol=1e-6, atol=1e-7, err_msg=n)

    # a manifest whose shard files are gone is NOT resumable
    missing = str(tmp_path / "gone")
    with open("%s-manifest.json" % missing, "w") as f:
        json.dump({"step": 9, "nprocs": 1, "params": {}}, f)
    assert par.latest_step(missing) is None
    tr3 = par.ParallelTrainer(sym, shapes, optimizer="sgd", mesh=mesh)
    assert tr3.resume_sharded_checkpoint(missing) is None
    assert os.path.exists("%s-manifest.json" % missing)


def test_fit_device_metric_matches_host_metric():
    """device_metric=True accumulates accuracy as device ops (no host
    sync inside the epoch) and must report the same value as the host
    metric path."""
    rng = np.random.RandomState(42)
    n = 256
    x = rng.randn(n, 16).astype(np.float32)
    w_true = rng.randn(16, 3).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1).astype(np.float32)
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc", num_hidden=3)
    sym = mx.symbol.SoftmaxOutput(data=fc, name="softmax")

    def run(device_metric):
        it = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=False)
        tr = par.ParallelTrainer(
            sym, {"data": (64, 16), "softmax_label": (64,)},
            optimizer="sgd", mesh=par.data_parallel_mesh(),
            optimizer_params={"learning_rate": 0.5})
        prng = np.random.RandomState(5)
        tr.init_params({"fc_weight": mx.nd.array(
            prng.uniform(-0.1, 0.1, (3, 16)).astype("f")),
            "fc_bias": mx.nd.zeros((3,))})
        tr.fit(it, num_epoch=3, device_metric=device_metric)
        return tr.last_train_metric

    name_d, val_d = run(True)
    name_h, val_h = run(False)
    assert name_d == name_h == "accuracy"
    assert abs(val_d - val_h) < 1e-6, (val_d, val_h)


def test_fit_device_metric_topk_and_ce_match_host():
    """The device-side metric accumulator covers top-k accuracy and
    cross-entropy too, matching the host metric path bit-for-bit at f32
    tolerance."""
    rng = np.random.RandomState(7)
    n, nclass = 256, 6
    x = rng.randn(n, 16).astype(np.float32)
    w_true = rng.randn(16, nclass).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1).astype(np.float32)
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc", num_hidden=nclass)
    sym = mx.symbol.SoftmaxOutput(data=fc, name="softmax")

    def run(metric, device_metric):
        it = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=False)
        tr = par.ParallelTrainer(
            sym, {"data": (64, 16), "softmax_label": (64,)},
            optimizer="sgd", mesh=par.data_parallel_mesh(),
            optimizer_params={"learning_rate": 0.5})
        prng = np.random.RandomState(5)
        tr.init_params({"fc_weight": mx.nd.array(
            prng.uniform(-0.1, 0.1, (nclass, 16)).astype("f")),
            "fc_bias": mx.nd.zeros((nclass,))})
        tr.fit(it, num_epoch=2, eval_metric=metric,
               device_metric=device_metric)
        return tr.last_train_metric

    for make in (lambda: mx.metric.TopKAccuracy(top_k=2),
                 lambda: mx.metric.CrossEntropy()):
        name_d, val_d = run(make(), True)
        name_h, val_h = run(make(), False)
        assert name_d == name_h
        assert abs(val_d - val_h) < 1e-5, (name_d, val_d, val_h)

    with pytest.raises(mx.base.MXNetError):
        run(mx.metric.MSE(), True)

    # loss-emitting head (SoftmaxCELoss) + Loss metric: device and host
    # accumulators agree
    sym_ce = mx.symbol.SoftmaxCELoss(data=fc, name="softmax")

    def run_ce(device_metric):
        it = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=False)
        tr = par.ParallelTrainer(
            sym_ce, {"data": (64, 16), "softmax_label": (64,)},
            optimizer="sgd", mesh=par.data_parallel_mesh(),
            optimizer_params={"learning_rate": 0.5})
        prng = np.random.RandomState(5)
        tr.init_params({"fc_weight": mx.nd.array(
            prng.uniform(-0.1, 0.1, (nclass, 16)).astype("f")),
            "fc_bias": mx.nd.zeros((nclass,))})
        tr.fit(it, num_epoch=2, eval_metric=mx.metric.Loss(),
               device_metric=device_metric)
        return tr.last_train_metric

    name_d, val_d = run_ce(True)
    name_h, val_h = run_ce(False)
    assert name_d == name_h == "loss"
    assert abs(val_d - val_h) < 1e-5, (val_d, val_h)


def test_bf16_compute_preserves_integer_inputs():
    """compute_dtype='bfloat16' must not cast index-valued inputs:
    bfloat16 spaces integers 4 apart near 1000, so casting labels or
    embedding token ids silently retargets every id above 256 (999
    becomes 1000). Pin: with class/token id 999, the updated bias row
    and embedding row are EXACTLY row 999."""
    nclass = 1024
    # label path: FC logits over 1024 classes, every sample labelled 999
    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    y = np.full((8,), 999.0, np.float32)
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc",
                                  num_hidden=nclass)
    sym = mx.symbol.SoftmaxOutput(data=fc, name="softmax")
    tr = par.ParallelTrainer(
        sym, {"data": (8, 16), "softmax_label": (8,)},
        optimizer="sgd", mesh=par.data_parallel_mesh(),
        compute_dtype="bfloat16",
        optimizer_params={"learning_rate": 1.0})
    tr.init_params({"fc_weight": mx.nd.zeros((nclass, 16)),
                    "fc_bias": mx.nd.zeros((nclass,))})
    tr.step({"data": x, "softmax_label": y})
    bias = np.asarray(tr.params["fc_bias"])
    assert int(np.argmax(bias)) == 999, int(np.argmax(bias))

    # embedding path: token id 999 must update embedding row 999
    vocab, E = 1024, 8
    toks = np.full((4, 3), 999.0, np.float32)
    lab = np.zeros((4, 3), np.float32)
    d2 = mx.symbol.Variable("data")
    emb = mx.symbol.Embedding(data=d2, input_dim=vocab, output_dim=E,
                              name="embed")
    fc2 = mx.symbol.FullyConnected(data=emb, num_hidden=4, name="fc2",
                                   flatten=False)
    flat = mx.symbol.Reshape(data=fc2, shape=(-1, 4), name="flat")
    flab = mx.symbol.Reshape(data=mx.symbol.Variable("softmax_label"),
                             shape=(-1,), name="flab")
    sym2 = mx.symbol.SoftmaxOutput(data=flat, label=flab, name="softmax")
    tr2 = par.ParallelTrainer(
        sym2, {"data": (4, 3), "softmax_label": (4, 3)},
        optimizer="sgd", mesh=par.data_parallel_mesh(),
        compute_dtype="bfloat16",
        optimizer_params={"learning_rate": 1.0})
    tr2.init_params()
    before = np.asarray(tr2.params["embed_weight"]).copy()
    tr2.step({"data": toks, "softmax_label": lab})
    after = np.asarray(tr2.params["embed_weight"])
    changed = np.where(np.abs(after - before).sum(axis=1) > 1e-6)[0]
    assert changed.tolist() == [999], changed.tolist()


def test_fit_device_metric_ce_warns_on_logits_output(caplog):
    """device_metric cross-entropy assumes probability outputs; a symbol
    whose monitored output is raw scores (here LinearRegressionOutput,
    which passes activations through) must trigger the first-batch
    row-sum warning instead of silently reporting garbage CE."""
    import logging as _logging
    rng = np.random.RandomState(3)
    x = rng.randn(64, 8).astype(np.float32)
    y = np.zeros((64,), np.float32)
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc", num_hidden=1)
    sym = mx.symbol.LinearRegressionOutput(data=fc, name="softmax")
    it = mx.io.NDArrayIter(x, y, batch_size=32, shuffle=False)
    tr = par.ParallelTrainer(
        sym, {"data": (32, 8), "softmax_label": (32,)},
        optimizer="sgd", mesh=par.data_parallel_mesh(),
        optimizer_params={"learning_rate": 0.0})
    tr.init_params({"fc_weight": mx.nd.zeros((1, 8)),
                    "fc_bias": mx.nd.array(np.full((1,), 5.0, "f"))})
    with caplog.at_level(_logging.WARNING):
        tr.fit(it, num_epoch=1, eval_metric=mx.metric.CrossEntropy(),
               device_metric=True)
    assert any("probability outputs" in r.message for r in caplog.records)


def _per_device_param_bytes(tr):
    """Bytes of params+optimizer state resident on ONE device."""
    total = 0
    for a in jax.tree.leaves((tr.params, tr.opt_state)):
        sh = a.addressable_shards[0]
        total += sh.data.size * np.dtype(sh.data.dtype).itemsize
    return total


@pytest.mark.slow
def test_pipeline_per_stage_placement_memory_and_values():
    # moved to the slow sweep (PR 5): the suite's heaviest test (~43 s)
    # in a tier-1 run brushing the 870 s timeout; per-stage placement
    # VALUE coverage stays tier-1 via test_pipeline_pp_sharded_big_params
    # and test_pipeline_trainer_matches_single_device
    """param_placement='stage' (default) holds each stage's params and
    optimizer state ONLY on its own pp device (~1/S of the replicated
    footprint, VERDICT r2 next #4 — reference graph_executor.cc:341-458
    places each sub-graph's arrays per-device) and trains to the same
    parameters as the replicated form."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 11, 8, 12, 16
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    staged_sym = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                                    num_heads=2, impl="dense",
                                    pipeline_stages=2)
    arg_shapes, _, _ = staged_sym.infer_shape(**shapes)
    prng = np.random.RandomState(3)
    init = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
            for n, s in zip(staged_sym.list_arguments(), arg_shapes)
            if n not in shapes}

    mesh = par.build_mesh({"pp": 2})

    def run(placement):
        pp = par.PipelineTrainer(
            staged_sym, shapes, mesh, num_microbatches=4,
            optimizer="sgd", param_placement=placement,
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                              "rescale_grad": 1.0 / B})
        pp.init_params({k: v.copy() for k, v in init.items()})
        for _ in range(2):
            pp.step({"data": data, "softmax_label": label})
        return pp, _per_device_param_bytes(pp)

    pp_s, bytes_staged = run("stage")
    pp_r, bytes_repl = run("replicated")

    # per-device residency: staged holds ~max-stage bytes, replicated
    # holds the whole model (+ momentum) on every device
    assert bytes_staged < 0.75 * bytes_repl, (bytes_staged, bytes_repl)

    got_s, got_r = pp_s.get_params(), pp_r.get_params()
    assert set(got_s) == set(got_r)
    for n in got_s:
        np.testing.assert_allclose(got_s[n].asnumpy(),
                                   got_r[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)

    # compiled per-device argument bytes, when the backend reports them
    # (the memory_analysis assertion from the verdict)
    try:
        lowered = pp_s._jit_step.lower(
            pp_s.params, pp_s.opt_state,
            {"data": jnp.asarray(data)}, jnp.asarray(label),
            np.float32(0.2), np.int32(2))
        ma = lowered.compile().memory_analysis()
        staged_args = ma.argument_size_in_bytes
    except Exception:
        staged_args = None
    if staged_args is not None:
        lowered_r = pp_r._jit_step.lower(
            pp_r.params, pp_r.opt_state,
            {"data": jnp.asarray(data)}, jnp.asarray(label),
            np.float32(0.2), np.int32(2))
        repl_args = lowered_r.compile().memory_analysis() \
                             .argument_size_in_bytes
        assert staged_args < repl_args, (staged_args, repl_args)


def test_striped_ring_attention_matches_dense():
    """Striped (balanced) causal ring == dense causal attention, values
    AND gradients — the half-block Pallas pair kernel + logaddexp merge
    must be exact at f32 tolerance (VERDICT r2 next #5)."""
    rng = np.random.RandomState(2)
    n, C = 4, 8
    T = n * C
    q = rng.randn(2, T, 2, 8).astype(np.float32)
    k = rng.randn(2, T, 2, 8).astype(np.float32)
    v = rng.randn(2, T, 2, 8).astype(np.float32)
    w = rng.randn(2, T, 2, 8).astype(np.float32)  # cotangent probe
    mesh = par.build_mesh({"sp": n})

    out = jax.jit(lambda a, b, c: par.striped_ring_attention(
        a, b, c, mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               _dense_attention(q, k, v, True),
                               rtol=1e-4, atol=1e-5)

    def dense_jax(a, b, c):
        s = jnp.einsum("bqhd,bkhd->bhqk", a, b) / np.float32(np.sqrt(8))
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, c)

    def loss_striped(a, b, c):
        return jnp.sum(par.striped_ring_attention(a, b, c, mesh) * w)

    def loss_dense(a, b, c):
        return jnp.sum(dense_jax(a, b, c) * w)

    gs = jax.jit(jax.grad(loss_striped, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg="d%s" % name)


def test_sequence_parallel_trainer_striped_matches_dense():
    """MultiHeadAttention(impl='ring_striped') under
    SequenceParallelTrainer — the in-shard all_to_all re-deal plus the
    balanced ring — trains to the same parameters as single-device
    dense attention."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 12, 4, 16, 8
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    steps = 2

    def init_for(sym):
        arg_shapes, _, _ = sym.infer_shape(**shapes)
        prng = np.random.RandomState(3)
        return {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
                for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in shapes}

    dense_sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                                   num_heads=2, impl="dense")
    ref_tr = par.ParallelTrainer(
        dense_sym, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9})
    init = init_for(dense_sym)
    ref_tr.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(steps):
        ref_tr.step({"data": data, "softmax_label": label})
    want, _ = ref_tr.get_params()

    striped_sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                                     num_heads=2, impl="ring_striped")
    mesh = par.build_mesh({"dp": 2, "sp": 4})
    sp_tr = par.SequenceParallelTrainer(
        striped_sym, shapes, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                          "rescale_grad": 1.0 / B})
    sp_tr.init_params({k: v.copy() for k, v in init.items()})
    losses = []
    for _ in range(steps):
        losses.append(sp_tr.step({"data": data, "softmax_label": label}))
    got = sp_tr.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)
    assert losses[1] < losses[0]


def test_pipeline_remat_matches_no_remat():
    """remat=True (checkpointed stage branches — the GPipe activation-
    memory mitigation) is value-preserving: identical trained params."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 11, 8, 12, 16
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    staged = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                                num_heads=2, impl="dense",
                                pipeline_stages=2)
    arg_shapes, _, _ = staged.infer_shape(**shapes)
    prng = np.random.RandomState(3)
    init = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
            for n, s in zip(staged.list_arguments(), arg_shapes)
            if n not in shapes}
    mesh = par.build_mesh({"pp": 2})

    def run(remat):
        pp = par.PipelineTrainer(
            staged, shapes, mesh, num_microbatches=4, optimizer="sgd",
            remat=remat,
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                              "rescale_grad": 1.0 / B})
        pp.init_params({k: v.copy() for k, v in init.items()})
        for _ in range(2):
            pp.step({"data": data, "softmax_label": label})
        return pp.get_params()

    got_r, got_n = run(True), run(False)
    for n in got_n:
        np.testing.assert_allclose(got_r[n].asnumpy(),
                                   got_n[n].asnumpy(),
                                   rtol=1e-5, atol=1e-6, err_msg=n)


@pytest.mark.slow
def test_pipeline_1f1b_matches_gpipe():
    # moved to the slow sweep (PR 5, ~41 s — see the note above):
    # 1f1b keeps tier-1 coverage via
    # test_pipeline_1f1b_activation_memory_bounded, which steps the
    # schedule end to end; the gpipe-equality oracle runs in slow
    """schedule='1f1b' (explicit interleaved fwd/bwd, activation memory
    bounded by 2S-1 in-flight microbatches instead of GPipe's M) trains
    to the same parameters as the GPipe schedule — on a pure-pp mesh
    with the pp-sharded big-param path forced on, and on a dp x pp
    mesh."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 11, 16, 12, 16
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    staged = get_transformer_lm(vocab, num_layers=4, embed_dim=E,
                                num_heads=2, impl="dense",
                                pipeline_stages=4)
    arg_shapes, _, _ = staged.infer_shape(**shapes)
    prng = np.random.RandomState(3)
    init = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
            for n, s in zip(staged.list_arguments(), arg_shapes)
            if n not in shapes}

    def run(mesh, schedule, **kw):
        pp = par.PipelineTrainer(
            staged, shapes, mesh, num_microbatches=8, optimizer="sgd",
            schedule=schedule,
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                              "rescale_grad": 1.0 / B}, **kw)
        pp.init_params({k: v.copy() for k, v in init.items()})
        for _ in range(2):
            out = pp.step({"data": data, "softmax_label": label})
        assert out.shape[0] == B
        return pp.get_params()

    mesh = par.build_mesh({"pp": 4})
    # pp_shard_min_size=64 pushes the embedding (and head) through the
    # pp-sharded big-param path, covering 1f1b's manual psum_scatter
    # transpose of the all_gather
    want = run(mesh, "gpipe", pp_shard_min_size=64)
    got = run(mesh, "1f1b", pp_shard_min_size=64)
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)

    mesh2 = par.build_mesh({"dp": 2, "pp": 2})
    # dropout pins the backward's RNG tick replay: the 1f1b backward
    # recomputes the stage forward at tick tt = mb + stage, so the
    # dropout masks must match the forward's bit-for-bit or gradients
    # (and thus trained params) diverge from GPipe's
    staged2 = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                                 num_heads=2, impl="dense", dropout=0.2,
                                 pipeline_stages=2)
    arg_shapes2, _, _ = staged2.infer_shape(**shapes)
    init2 = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
             for n, s in zip(staged2.list_arguments(), arg_shapes2)
             if n not in shapes}

    def run2(schedule):
        pp = par.PipelineTrainer(
            staged2, shapes, mesh2, num_microbatches=4, optimizer="sgd",
            schedule=schedule,
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                              "rescale_grad": 1.0 / B})
        pp.init_params({k: v.copy() for k, v in init2.items()})
        for _ in range(2):
            pp.step({"data": data, "softmax_label": label})
        return pp.get_params()

    want2, got2 = run2("gpipe"), run2("1f1b")
    for n in want2:
        np.testing.assert_allclose(got2[n].asnumpy(),
                                   want2[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)

    with pytest.raises(mx.base.MXNetError, match="1f1b"):
        par.PipelineTrainer(staged2, shapes, mesh2, schedule="1f1b",
                            param_placement="replicated")


def test_pipeline_1f1b_activation_memory_bounded():
    """The point of 1F1B: compiled temp (activation) memory stays flat
    as the microbatch count grows, while GPipe's reverse pass keeps one
    boundary residual per tick (O(M)). Measured from XLA's own
    memory_analysis on the compiled step."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, T, E = 11, 32, 64
    mesh = par.build_mesh({"pp": 2})
    staged = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                                num_heads=2, impl="dense",
                                pipeline_stages=2)

    def temp_bytes(schedule, M, mb=4):
        B = M * mb
        shapes = {"data": (B, T), "softmax_label": (B, T)}
        pp = par.PipelineTrainer(
            staged, shapes, mesh, num_microbatches=M,
            optimizer="sgd", schedule=schedule,
            remat=(schedule == "gpipe"),
            optimizer_params={"learning_rate": 0.1})
        pp.init_params()
        pp._jit_step = pp._build_step()
        data = np.zeros((B, T), np.float32)
        label = np.zeros((B, T), np.float32)
        # trace/compile errors must FAIL the test; only a backend that
        # can't report temp bytes downgrades to a skip
        compiled = pp._jit_step.lower(
            pp.params, pp.opt_state, {"data": jnp.asarray(data)},
            jnp.asarray(label), np.float32(0.1), np.int32(0)).compile()
        try:
            return compiled.memory_analysis().temp_size_in_bytes
        except Exception:
            return None

    g = temp_bytes("1f1b", 32)
    gp = temp_bytes("gpipe", 32)
    g_small = temp_bytes("1f1b", 4)
    if None in (g, gp, g_small):
        pytest.skip("backend does not report temp_size_in_bytes")
    # GPipe-with-remat still carries one boundary residual per tick;
    # 1f1b's in-flight window is schedule-depth-bounded
    assert g < 0.8 * gp, (g, gp)
    # and 1f1b temp memory is (near-)flat in M
    assert g < 3.0 * g_small, (g, g_small)


def test_moe_top_k_routing():
    """MoEFFN top_k: only the k largest gates carry weight (renormalized
    among themselves), output matches a numpy oracle, the op stays
    differentiable, and an ep-sharded top-k MoE LM trains."""
    from mxnet_tpu.models import get_transformer_lm
    from mxnet_tpu.models.transformer import ep_rules

    rng = np.random.RandomState(0)
    B, T, E, X, H, K = 2, 3, 4, 4, 8, 2
    x = rng.randn(B, T, E).astype(np.float32)
    gate_w = rng.randn(X, E).astype(np.float32)
    w1 = rng.randn(X, H, E).astype(np.float32) * 0.1
    b1 = np.zeros((X, H), np.float32)
    w2 = rng.randn(X, E, H).astype(np.float32) * 0.1
    b2 = np.zeros((X, E), np.float32)

    data = mx.symbol.Variable("data")
    moe = mx.symbol.MoEFFN(
        data=data, gate_weight=mx.symbol.Variable("g"),
        expert_w1=mx.symbol.Variable("w1"),
        expert_b1=mx.symbol.Variable("b1"),
        expert_w2=mx.symbol.Variable("w2"),
        expert_b2=mx.symbol.Variable("b2"),
        num_experts=X, hidden=H, top_k=K, name="moe")
    exe = moe.bind(mx.cpu(), {
        "data": mx.nd.array(x), "g": mx.nd.array(gate_w),
        "w1": mx.nd.array(w1), "b1": mx.nd.array(b1),
        "w2": mx.nd.array(w2), "b2": mx.nd.array(b2)})
    exe.forward()
    got = exe.outputs[0].asnumpy()

    # numpy oracle
    logits = np.einsum("bte,xe->btx", x, gate_w)
    out_ref = np.zeros((B, T, E), np.float32)
    for b in range(B):
        for t in range(T):
            order = np.argsort(logits[b, t])[::-1][:K]
            kept = logits[b, t, order]
            gs = np.exp(kept - kept.max())
            gs /= gs.sum()
            for g_, xi in zip(gs, order):
                hpre = np.maximum(w1[xi] @ x[b, t] + b1[xi], 0)
                out_ref[b, t] += g_ * (w2[xi] @ hpre + b2[xi])
    np.testing.assert_allclose(got, out_ref, rtol=1e-4, atol=1e-5)

    with pytest.raises(mx.base.MXNetError, match="top_k"):
        mx.symbol.MoEFFN(data=data,
                         gate_weight=mx.symbol.Variable("g2"),
                         expert_w1=mx.symbol.Variable("w12"),
                         expert_b1=mx.symbol.Variable("b12"),
                         expert_w2=mx.symbol.Variable("w22"),
                         expert_b2=mx.symbol.Variable("b22"),
                         num_experts=X, hidden=H, top_k=X,
                         name="moe2").bind(mx.cpu(), {
                             "data": mx.nd.array(x),
                             "g2": mx.nd.array(gate_w),
                             "w12": mx.nd.array(w1),
                             "b12": mx.nd.array(b1),
                             "w22": mx.nd.array(w2),
                             "b22": mx.nd.array(b2)}).forward()

    # end-to-end: ep-sharded top-2 MoE LM still trains
    vocab = 8
    lm = get_transformer_lm(vocab, num_layers=1, embed_dim=8,
                            num_heads=2, impl="dense", num_experts=4,
                            moe_top_k=2)
    mesh = par.build_mesh({"dp": 2, "ep": 4})
    tr = par.ParallelTrainer(
        lm, {"data": (4, 4), "softmax_label": (4, 4)},
        optimizer="sgd", mesh=mesh,
        rules=par.ShardingRules(mesh, param_rules=ep_rules()),
        optimizer_params={"learning_rate": 0.1})
    tr.init_params()
    d = rng.randint(0, vocab, (4, 4)).astype(np.float32)
    lab = rng.randint(0, vocab, (4, 4)).astype(np.float32)
    outs = tr.step({"data": d, "softmax_label": lab})
    assert np.isfinite(np.asarray(outs[0])).all()


def test_moe_top_k_tie_breaking():
    """Tied gate logits (e.g. zero-initialized gate weights) must still
    route to EXACTLY k experts (index order, like lax.top_k) — not fall
    back to dense routing."""
    import jax
    from mxnet_tpu.ops.registry import REGISTRY

    rng = np.random.RandomState(1)
    B, T, E, X, H, K = 1, 2, 4, 4, 8, 2
    x = rng.randn(B, T, E).astype(np.float32)
    gate_w = np.zeros((X, E), np.float32)  # all logits tie at 0
    w1 = rng.randn(X, H, E).astype(np.float32) * 0.1
    b1 = np.zeros((X, H), np.float32)
    w2 = rng.randn(X, E, H).astype(np.float32) * 0.1
    b2 = np.zeros((X, E), np.float32)

    spec = REGISTRY["MoEFFN"]
    p = spec.parse_params({"num_experts": X, "hidden": H, "top_k": K})
    (out,), _ = spec.forward(p, [jnp.asarray(v) for v in
                                 (x, gate_w, w1, b1, w2, b2)],
                             [], True, jax.random.PRNGKey(0))
    got = np.asarray(out)

    # oracle: experts 0..K-1 (tie-break by index) at weight 1/K each
    ref = np.zeros((B, T, E), np.float32)
    for b in range(B):
        for t in range(T):
            for xi in range(K):
                h = np.maximum(w1[xi] @ x[b, t] + b1[xi], 0)
                ref[b, t] += (w2[xi] @ h + b2[xi]) / K
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_pipeline_pp_sharded_big_params():
    """A stage-0-heavy cut (big embedding): params larger than an
    average stage persist as pp-SHARDED chunks (ZeRO-3 in the pipe), so
    per-device memory stays ~total/S instead of paying stage 0's row
    everywhere (VERDICT r3 #7). Exact-value vs replicated, and the
    padding-imbalance warning fires when the sharded path is disabled."""
    import warnings as _warnings
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 257, 8, 12, 16  # embedding 257*16 dominates
    rng = np.random.RandomState(1)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}
    sym = get_transformer_lm(vocab, num_layers=2, embed_dim=E,
                             num_heads=2, impl="dense",
                             pipeline_stages=2)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    prng = np.random.RandomState(3)
    init = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}
    mesh = par.build_mesh({"pp": 2})

    def run(placement, **kw):
        pp = par.PipelineTrainer(
            sym, shapes, mesh, num_microbatches=4,
            optimizer="sgd", param_placement=placement,
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                              "rescale_grad": 1.0 / B}, **kw)
        pp.init_params({k: v.copy() for k, v in init.items()})
        for _ in range(2):
            pp.step({"data": data, "softmax_label": label})
        return pp

    pp_s = run("stage")
    # the heavy params actually took the sharded path
    assert pp_s._big_meta, "expected pp-sharded big params"
    big_names = {m[0] for m in pp_s._big_meta}
    assert any("embed" in n or "weight" in n for n in big_names)
    # exact-value oracle vs replicated
    pp_r = run("replicated")
    got_s, got_r = pp_s.get_params(), pp_r.get_params()
    assert set(got_s) == set(got_r)
    for n in got_s:
        np.testing.assert_allclose(got_s[n].asnumpy(),
                                   got_r[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)
    # padded path (sharding disabled) must still be numerically correct
    pp_pad = run("stage", pp_shard_min_size=None)
    assert not pp_pad._big_meta
    got_p = pp_pad.get_params()
    for n in got_s:
        np.testing.assert_allclose(got_p[n].asnumpy(),
                                   got_r[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)
    # per-stage byte report exists and covers all params
    assert len(pp_s.stage_param_bytes) == 2
    assert sum(pp_s.stage_param_bytes) >= 4 * (vocab * E)


def _imbalanced_fc_sym():
    from mxnet_tpu.symbol import AttrScope

    data = mx.symbol.Variable("data")
    with AttrScope(ctx_group="stage0"):
        big = mx.symbol.FullyConnected(data=data, name="bigfc",
                                       num_hidden=512)
        a = mx.symbol.Activation(data=big, act_type="relu", name="a0")
    with AttrScope(ctx_group="stage1"):
        small = mx.symbol.FullyConnected(data=a, name="smallfc",
                                         num_hidden=4)
        return mx.symbol.SoftmaxOutput(data=small, name="softmax")


def test_pipeline_imbalanced_memory_and_warning():
    """A stage-0-heavy cut: with pp-sharding (default) per-device
    persistent bytes drop well below the padded [S, P_max] cost that
    charges stage 0's row to every device (VERDICT r3 #7); with the
    sharded path disabled, construction warns with per-stage byte
    counts."""
    import warnings as _warnings

    sym = _imbalanced_fc_sym()
    shapes = {"data": (8, 32), "softmax_label": (8,)}
    mesh = par.build_mesh({"pp": 2})
    rng = np.random.RandomState(0)
    batch = {"data": rng.randn(8, 32).astype(np.float32),
             "softmax_label": rng.randint(0, 4, (8,)).astype(np.float32)}

    def run(**kw):
        with _warnings.catch_warnings(record=True) as rec:
            _warnings.simplefilter("always")
            pp = par.PipelineTrainer(
                sym, shapes, mesh, num_microbatches=4,
                optimizer="sgd", param_placement="stage",
                optimizer_params={"learning_rate": 0.1}, **kw)
            msgs = [str(w.message) for w in rec]
        pp.init_params()
        pp.step(batch)
        return pp, msgs

    pp_s, msgs_s = run()
    assert pp_s._big_meta, "bigfc_weight should take the sharded path"
    assert not any("imbalanced" in m for m in msgs_s), msgs_s
    pp_pad, msgs_p = run(pp_shard_min_size=None)
    assert any("imbalanced" in m for m in msgs_p), msgs_p
    assert any("per-stage bytes" in m for m in msgs_p), msgs_p
    bytes_sharded = _per_device_param_bytes(pp_s)
    bytes_padded = _per_device_param_bytes(pp_pad)
    assert bytes_sharded < 0.7 * bytes_padded, (bytes_sharded,
                                                bytes_padded)


def test_fused_step_adafactor():
    """AdaFactor: the fused functional path matches the eager oracle,
    the factored second moment actually stores O(n+m) floats for rank-2
    weights, and the state shards under zero1 AND fsdp (the factored
    leaves are LOWER-RANK than their params — exactly what the
    leaf-shape-aware sharding rules exist for)."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(13)
    data = rng.randn(8, 64).astype(np.float32)
    label = rng.randint(0, 10, (8,)).astype(np.float32)
    shapes = {"data": data.shape, "softmax_label": label.shape}
    arg_names = sym.list_arguments()
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    init = np.random.RandomState(5)
    params0 = {n: init.uniform(-0.1, 0.1, s).astype("f")
               for n, s in zip(arg_names, arg_shapes) if n not in shapes}

    # eager oracle
    args = {n: mx.nd.array(params0[n]) if n in params0 else mx.nd.zeros(s)
            for n, s in zip(arg_names, arg_shapes)}
    grads = {n: mx.nd.zeros(params0[n].shape) for n in params0}
    exe = sym.bind(mx.cpu(), args, args_grad=grads)
    opt = mx.optimizer.create("adafactor", rescale_grad=1.0 / 8, wd=0.01)
    updater = mx.optimizer.get_updater(opt)
    args["data"][:] = data
    args["softmax_label"][:] = label
    pnames = [n for n in arg_names if n in params0]
    for _ in range(3):
        exe.forward(is_train=True)
        exe.backward()
        for i, n in enumerate(pnames):
            updater(i, grads[n], args[n])

    trainer = par.ParallelTrainer(
        sym, shapes, optimizer="adafactor", mesh=par.data_parallel_mesh(),
        optimizer_params={"wd": 0.01})
    trainer.init_params({n: mx.nd.array(v) for n, v in params0.items()})
    for _ in range(3):
        trainer.step({"data": data, "softmax_label": label})
    got, _ = trainer.get_params()
    for n in pnames:
        np.testing.assert_allclose(got[n].asnumpy(), args[n].asnumpy(),
                                   rtol=2e-5, atol=2e-6, err_msg=n)

    # factored memory: state for a [H, 64] weight is H + 64 floats
    w_shape = dict(zip(arg_names, arg_shapes))["fc1_weight"]
    leaves = jax.tree_util.tree_leaves(trainer.opt_state["fc1_weight"])
    assert sum(l.size for l in leaves) == w_shape[0] + w_shape[1], leaves
    assert all(l.ndim == 1 for l in leaves)
    # f32, not f64: the package enables x64, so bare jnp.zeros would
    # silently promote params through the update
    assert all(l.dtype == jnp.float32 for l in leaves), leaves
    assert all(v.dtype == jnp.float32 for v in trainer.params.values())

    # zero1 and fsdp build leaf-shaped shardings without error and step;
    # looser tolerance than the elementwise optimizers: AdaFactor's
    # row/col means and global RMS reassociate under sharding (observed
    # ~5e-4 relative over 3 steps), where Adam's update reassociates
    # only through the gradient sum
    for kw in (dict(zero1=True), dict(fsdp=True)):
        tr = par.ParallelTrainer(
            sym, shapes, optimizer="adafactor",
            mesh=par.build_mesh({"dp": 8}), **kw)
        tr.init_params({n: mx.nd.array(v) for n, v in params0.items()})
        for _ in range(3):
            tr.step({"data": data, "softmax_label": label})
        got_s, _ = tr.get_params()
        for n in pnames:
            np.testing.assert_allclose(
                got_s[n].asnumpy(), args[n].asnumpy(),
                rtol=2e-3, atol=2e-6, err_msg="%s/%s" % (kw, n))


def test_fused_step_adamw():
    """Functional AdamW (decoupled wd) matches eager AdamW, and differs
    from Adam-with-L2 on the same stream (the decoupling is real)."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(11)
    data = rng.randn(8, 32).astype(np.float32)
    label = rng.randint(0, 10, (8,)).astype(np.float32)

    ctx = mx.cpu()
    shapes = {"data": data.shape, "softmax_label": label.shape}
    arg_names = sym.list_arguments()
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    init = np.random.RandomState(5)
    params0 = {n: init.uniform(-0.1, 0.1, s).astype("f")
               for n, s in zip(arg_names, arg_shapes) if n not in shapes}
    args = {n: mx.nd.array(params0[n]) if n in params0 else mx.nd.zeros(s)
            for n, s in zip(arg_names, arg_shapes)}
    grads = {n: mx.nd.zeros(params0[n].shape) for n in params0}
    exe = sym.bind(ctx, args, args_grad=grads)
    opt = mx.optimizer.create("adamw", rescale_grad=1.0 / 8, wd=0.05)
    updater = mx.optimizer.get_updater(opt)
    args["data"][:] = data
    args["softmax_label"][:] = label
    pnames = [n for n in arg_names if n in params0]
    for _ in range(2):
        exe.forward(is_train=True)
        exe.backward()
        for i, n in enumerate(pnames):
            updater(i, grads[n], args[n])

    trainer = par.ParallelTrainer(
        sym, shapes, optimizer="adamw", mesh=par.data_parallel_mesh(),
        optimizer_params={"wd": 0.05})
    trainer.init_params({n: mx.nd.array(v) for n, v in params0.items()})
    for _ in range(2):
        trainer.step({"data": data, "softmax_label": label})
    got, _ = trainer.get_params()
    for n in pnames:
        np.testing.assert_allclose(got[n].asnumpy(), args[n].asnumpy(),
                                   rtol=2e-6, atol=2e-6, err_msg=n)

    # decoupling sanity: plain adam with the same wd lands elsewhere
    t2 = par.ParallelTrainer(
        sym, shapes, optimizer="adam", mesh=par.data_parallel_mesh(),
        optimizer_params={"wd": 0.05})
    t2.init_params({n: mx.nd.array(v) for n, v in params0.items()})
    for _ in range(2):
        t2.step({"data": data, "softmax_label": label})
    g2, _ = t2.get_params()
    assert any(not np.allclose(g2[n].asnumpy(), got[n].asnumpy())
               for n in pnames)


def test_clip_grad_norm():
    """Global-norm clipping: with SGD lr=1/wd=0/momentum=0 the update
    IS the (rescaled) gradient, so the clipped trainer's delta must be
    the unclipped delta scaled by min(1, c/||g||) — one shared factor
    across ALL parameters."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(12)
    data = rng.randn(8, 32).astype(np.float32)
    label = rng.randint(0, 10, (8,)).astype(np.float32)
    shapes = {"data": data.shape, "softmax_label": label.shape}
    arg_names = sym.list_arguments()
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    init = np.random.RandomState(6)
    params0 = {n: init.uniform(-0.1, 0.1, s).astype("f")
               for n, s in zip(arg_names, arg_shapes) if n not in shapes}

    def run(clip):
        tr = par.ParallelTrainer(
            sym, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(),
            clip_grad_norm=clip,
            optimizer_params={"learning_rate": 1.0, "wd": 0.0,
                              "momentum": 0.0})
        tr.init_params({n: mx.nd.array(v) for n, v in params0.items()})
        tr.step({"data": data, "softmax_label": label})
        got, _ = tr.get_params()
        return {n: params0[n] - got[n].asnumpy() for n in params0}

    g = run(None)           # delta == rescaled gradient
    gnorm = np.sqrt(sum(np.sum(v.astype(np.float64) ** 2)
                        for v in g.values()))
    c = gnorm / 3.0         # force clipping by 1/3
    clipped = run(c)
    for n in g:
        np.testing.assert_allclose(clipped[n], g[n] * (c / gnorm),
                                   rtol=1e-4, atol=1e-7, err_msg=n)
    # a generous threshold must be a no-op
    loose = run(gnorm * 10)
    for n in g:
        np.testing.assert_allclose(loose[n], g[n], rtol=1e-6, atol=1e-8)

    with pytest.raises(mx.MXNetError, match="positive"):
        par.ParallelTrainer(sym, shapes, optimizer="sgd",
                            mesh=par.data_parallel_mesh(),
                            clip_grad_norm=-1.0)


def test_sequence_parallel_rope_matches_dense():
    """RoPE under ring attention: each sp shard rotates its tokens with
    the shard's GLOBAL offset (lax.axis_index), so trained parameters
    must match the single-device dense rope LM exactly — the oracle for
    position bookkeeping under sequence parallelism."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 12, 4, 16, 8
    rng = np.random.RandomState(1)
    data = rng.randint(0, vocab, (B, T)).astype(np.float32)
    label = rng.randint(0, vocab, (B, T)).astype(np.float32)
    shapes = {"data": (B, T), "softmax_label": (B, T)}

    def init_for(sym):
        arg_shapes, _, _ = sym.infer_shape(**shapes)
        prng = np.random.RandomState(4)
        return {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
                for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in shapes}

    dense_sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                                   num_heads=2, impl="dense",
                                   pos_encoding="rope")
    ref_tr = par.ParallelTrainer(
        dense_sym, shapes, optimizer="sgd",
        mesh=par.data_parallel_mesh(1),
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9})
    init = init_for(dense_sym)
    ref_tr.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(2):
        ref_tr.step({"data": data, "softmax_label": label})
    want, _ = ref_tr.get_params()

    ring_sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                                  num_heads=2, impl="ring",
                                  pos_encoding="rope")
    mesh = par.build_mesh({"dp": 2, "sp": 4})
    sp_tr = par.SequenceParallelTrainer(
        ring_sym, shapes, mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.2, "momentum": 0.9,
                          "rescale_grad": 1.0 / B})
    sp_tr.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(2):
        sp_tr.step({"data": data, "softmax_label": label})
    got = sp_tr.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)


def test_multi_step_matches_steps():
    """multi_step(batch, N) (one lax.scan program) must reproduce N
    step() calls exactly: same rng folding, same step counter, same lr
    schedule, bit-identical parameters."""
    sym = _mlp_symbol()
    rng = np.random.RandomState(3)
    batch = {"data": rng.randn(16, 64).astype(np.float32),
             "softmax_label": rng.randint(0, 10, (16,)
                                          ).astype(np.float32)}
    shapes = {k: v.shape for k, v in batch.items()}

    def make():
        # fresh scheduler per trainer: FactorScheduler is stateful
        sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
        t = par.ParallelTrainer(
            sym, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(),
            seed=11,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "lr_scheduler": sched})
        arg_shapes, _, _ = sym.infer_shape(**shapes)
        init_rng = np.random.RandomState(7)
        t.init_params({n: mx.nd.array(
            init_rng.uniform(-0.07, 0.07, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes})
        return t

    looped = make()
    for _ in range(5):
        looped.step(batch)
    fused = make()
    fused.multi_step(batch, 5)
    assert fused._t == looped._t
    want, _ = looped.get_params()
    got, _ = fused.get_params()
    for n in want:
        np.testing.assert_array_equal(got[n].asnumpy(),
                                      want[n].asnumpy(), err_msg=n)


def test_three_axis_dp_tp_sp_matches_dense():
    """3-axis mesh composition in ONE pjit program: batch over dp,
    megatron-style tp on attention/FFN weights, sequence over sp
    (GSPMD inserts the gathers) — 2x2x2 over the 8-device mesh must
    reproduce the single-device dense model's parameters. Pairwise
    (dp,tp) and (dp,sp) were proven before; real pods run all three at
    once, so this is the composition oracle."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, B, T, E = 12, 4, 16, 8
    rng = np.random.RandomState(5)
    batch = {"data": rng.randint(0, vocab, (B, T)).astype(np.float32),
             "softmax_label": rng.randint(0, vocab, (B, T)
                                          ).astype(np.float32)}
    shapes = {k: v.shape for k, v in batch.items()}
    sym = get_transformer_lm(vocab, num_layers=1, embed_dim=E,
                             num_heads=2, impl="dense")
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    prng = np.random.RandomState(9)
    init = {n: mx.nd.array(prng.uniform(-0.1, 0.1, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}
    steps, opt = 3, {"learning_rate": 0.2, "momentum": 0.9}

    ref = par.ParallelTrainer(
        sym, shapes, optimizer="sgd", mesh=par.data_parallel_mesh(1),
        optimizer_params=opt)
    ref.init_params({k: v.copy() for k, v in init.items()})
    for _ in range(steps):
        ref.step(batch)
    want, _ = ref.get_params()

    from mxnet_tpu.models.transformer import tp_rules
    mesh = par.build_mesh({"dp": 2, "tp": 2, "sp": 2})
    rules = par.ShardingRules(
        mesh,
        param_rules=tp_rules() + [(r"pos_embed$", P("sp", None))],
        data_axes=("dp",), seq_axes=("sp",))
    three = par.ParallelTrainer(sym, shapes, optimizer="sgd", mesh=mesh,
                                rules=rules, optimizer_params=opt)
    three.init_params({k: v.copy() for k, v in init.items()})
    # the data really is sharded over all three axes' worth of devices
    sh = three._data_sh["data"]
    assert sh.spec == P("dp", "sp"), sh.spec
    for _ in range(steps):
        three.step(batch)
    got, _ = three.get_params()
    for n in want:
        np.testing.assert_allclose(got[n].asnumpy(), want[n].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=n)
