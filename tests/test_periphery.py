"""Periphery tests: visualization, predictor, rtc (Pallas user kernels),
torch interop."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import visualization, rtc, predict


def _net():
    data = mx.symbol.Variable("data")
    fc1 = mx.symbol.FullyConnected(data=data, name="fc1", num_hidden=16)
    act = mx.symbol.Activation(data=fc1, name="relu1", act_type="relu")
    fc2 = mx.symbol.FullyConnected(data=act, name="fc2", num_hidden=4)
    return mx.symbol.SoftmaxOutput(data=fc2, name="softmax")


def test_network_dot():
    dot = visualization.network_dot(_net(), shape={"data": (2, 8),
                                                   "softmax_label": (2,)})
    assert "digraph" in dot
    assert "fc1" in dot and "SoftmaxOutput" in dot
    assert "2x16" in dot  # edge shape annotation


def test_print_summary(capsys):
    total = visualization.print_summary(
        _net(), shape={"data": (2, 8), "softmax_label": (2,)})
    out = capsys.readouterr().out
    assert "fc1" in out and "Total params" in out
    # fc1: 8*16+16, fc2: 16*4+4
    assert total == 8 * 16 + 16 + 16 * 4 + 4


def test_predictor(tmp_path):
    """Round-trip: train-side checkpoint -> deploy-side Predictor."""
    sym = _net()
    shapes = {"data": (3, 8), "softmax_label": (3,)}
    exe = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
    rng = np.random.RandomState(0)
    arg_params = {}
    for name, arr in exe.arg_dict.items():
        if name not in shapes:
            v = rng.uniform(-0.3, 0.3, arr.shape).astype(np.float32)
            arr[:] = v
            arg_params[name] = mx.nd.array(v)
    x = rng.randn(3, 8).astype(np.float32)
    exe.forward(is_train=False, data=x)
    want = exe.outputs[0].asnumpy()

    prefix = str(tmp_path / "model")
    mx.model.save_checkpoint(prefix, 1, sym, arg_params, {})
    with open(prefix + "-symbol.json") as f:
        sym_json = f.read()
    with open(prefix + "-0001.params", "rb") as f:
        param_bytes = f.read()
    pred = predict.Predictor(sym_json, param_bytes, {"data": (3, 8)})
    pred.forward(data=x)
    np.testing.assert_allclose(pred.get_output(0), want, rtol=1e-5,
                               atol=1e-6)


def test_predictor_preserves_integer_inputs(tmp_path):
    """Predictor.forward must not blanket-cast inputs to float32: an
    LM predictor's token ids reach the graph at their integer dtype
    (f32 would silently round ids above 2^24); only float inputs are
    normalized to the f32 compute dtype."""
    from mxnet_tpu.models import get_transformer_lm

    vocab, t = 17, 8
    sym = get_transformer_lm(vocab, num_layers=1, embed_dim=8,
                             num_heads=2, impl="dense")
    shapes = {"data": (1, t), "softmax_label": (1, t)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    rng = np.random.RandomState(0)
    params = {"arg:%s" % n: mx.nd.array(
        rng.uniform(-0.3, 0.3, s).astype(np.float32))
        for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in shapes}
    logits = sym.get_internals()["lm_head_output"]
    pred = predict.Predictor(logits.tojson(), params, {"data": (1, t)})

    seen = {}
    orig = pred._run
    pred._run = lambda arrs: (seen.update(arrs), orig(arrs))[1]
    ids = rng.randint(0, vocab, (1, t)).astype(np.int64)
    out_int = pred.forward(data=ids).get_output(0)
    assert seen["data"].dtype.kind in "iu"      # ids NOT cast to float
    out_f32 = pred.forward(data=ids.astype(np.float32)).get_output(0)
    assert seen["data"].dtype == np.float32
    np.testing.assert_allclose(out_int, out_f32, rtol=1e-6)
    out_f64 = pred.forward(data=ids.astype(np.float64)).get_output(0)
    assert seen["data"].dtype == np.float32     # floats normalize to f32
    np.testing.assert_allclose(out_f64, out_f32, rtol=1e-6)

    # the flip side: integer-typed inputs into a FLOAT graph (uint8
    # image batches into an FC/conv net) must still be normalized to
    # f32 — only INDEX-semantic inputs keep their dtype
    fsym = _net()
    fshapes = {"data": (2, 8), "softmax_label": (2,)}
    exe = fsym.simple_bind(mx.cpu(), grad_req="null", **fshapes)
    fparams = {}
    for name, arr in exe.arg_dict.items():
        if name not in fshapes:
            v = rng.uniform(-0.3, 0.3, arr.shape).astype(np.float32)
            fparams["arg:" + name] = mx.nd.array(v)
    fpred = predict.Predictor(fsym.tojson(), fparams, {"data": (2, 8)})
    u8 = rng.randint(0, 255, (2, 8)).astype(np.uint8)
    out_u8 = fpred.forward(data=u8).get_output(0)      # must not crash
    out_ff = fpred.forward(data=u8.astype(np.float32)).get_output(0)
    np.testing.assert_allclose(out_u8, out_ff, rtol=1e-6)


def test_pallas_op_push():
    def scale_kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    op = rtc.PallasOp("scale2", scale_kernel,
                      out_shapes=lambda shapes: [shapes[0]])
    x = mx.nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    (y,) = op.push([x])
    np.testing.assert_allclose(y.asnumpy(), x.asnumpy() * 2)


def test_torch_module_op():
    torch = pytest.importorskip("torch")
    from mxnet_tpu.torch import TorchModuleOp, to_torch, from_torch

    lin = torch.nn.Linear(6, 3)
    op = TorchModuleOp(lin)
    sym = op.get_symbol(mx.symbol.Variable("data"), name="tmod")
    exe = sym.simple_bind(mx.cpu(), data=(2, 6))
    rng = np.random.RandomState(0)
    x = rng.randn(2, 6).astype(np.float32)
    exe.forward(is_train=True, data=x)
    with torch.no_grad():
        want = lin(torch.from_numpy(x)).numpy()
    np.testing.assert_allclose(exe.outputs[0].asnumpy(), want, rtol=1e-5,
                               atol=1e-6)
    # gradient flows back into the graph
    exe.backward([mx.nd.array(np.ones((2, 3), np.float32))])
    g = exe.grad_dict["data"].asnumpy()
    want_g = np.ones((2, 3), np.float32) @ lin.weight.detach().numpy()
    np.testing.assert_allclose(g, want_g, rtol=1e-5, atol=1e-6)
    # tensor conversion helpers
    t = to_torch(mx.nd.array(x))
    np.testing.assert_array_equal(from_torch(t).asnumpy(), x)


def test_misc_factor_scheduler():
    """Legacy misc.FactorScheduler parity (reference python/mxnet/misc.py)."""
    sched = mx.misc.FactorScheduler(step=10, factor=0.1)
    sched.base_lr = 1.0
    assert sched(0) == 1.0
    assert abs(sched(10) - 0.1) < 1e-12
    assert abs(sched(25) - 0.01) < 1e-12
    import pytest
    with pytest.raises(ValueError):
        mx.misc.FactorScheduler(step=0)
    with pytest.raises(ValueError):
        mx.misc.FactorScheduler(step=1, factor=1.5)


def test_profiler_trace(tmp_path):
    """mx.profiler wraps jax.profiler: trace capture + named scopes."""
    import jax.numpy as jnp
    mx.profiler.start(str(tmp_path))
    with mx.profiler.scope("region"):
        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    mx.profiler.stop()
    traces = list(tmp_path.rglob("*"))
    assert traces, "no trace files written"


def test_executor_debug_str_memory_plan():
    """debug_str reports the XLA buffer plan (GraphExecutor::Print
    parity: graph dump + 'Total N MB')."""
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc", num_hidden=4)
    out = mx.symbol.SoftmaxOutput(data=fc, name="softmax")
    exe = out.simple_bind(mx.cpu(), data=(2, 8))
    s = exe.debug_str()
    assert "Total" in s and "MB" in s


def test_reference_api_shims():
    """Small reference-parity surfaces: ctypes helpers (base.py:79-186),
    metric.check_label_shapes / metric.Torch, rtc.Rtc alias."""
    import ctypes
    import pytest
    assert mx.base.c_str("ab").value == b"ab"
    arr = mx.base.c_array(ctypes.c_int, [1, 2, 3])
    assert list(arr) == [1, 2, 3]
    buf = (ctypes.c_char * 3)(b"x", b"y", b"z")
    got = mx.base.ctypes2buffer(ctypes.cast(buf,
                                            ctypes.POINTER(ctypes.c_char)), 3)
    assert bytes(got) == b"xyz"
    fl = (ctypes.c_float * 4)(1, 2, 3, 4)
    view = mx.base.ctypes2numpy_shared(
        ctypes.cast(fl, ctypes.POINTER(ctypes.c_float)), (2, 2))
    np.testing.assert_array_equal(view, [[1, 2], [3, 4]])
    doc = mx.base.ctypes2docstring(2, ["a", "b"], ["int", "float"],
                                   ["first", ""])
    assert "a : int" in doc and "first" in doc

    with pytest.raises(ValueError):
        mx.metric.check_label_shapes([1], [1, 2])
    m = mx.metric.Torch()
    m.update(None, [mx.nd.array(np.full((2, 2), 3.0, np.float32))])
    assert m.get()[1] == 3.0
    assert issubclass(mx.rtc.Rtc, mx.rtc.PallasOp)


def test_profiler_compiled_stats_executor():
    """compiled_stats reports XLA memory/cost analysis for an Executor
    (the example/memcost capability: the reference dumps its memory
    planner's totals, graph_executor.cc:852-853)."""
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data=data, name="fc", num_hidden=16)
    net = mx.symbol.SoftmaxOutput(data=fc, name="softmax")
    shapes = {"data": (8, 32), "softmax_label": (8,)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    args = {n: mx.nd.zeros(s)
            for n, s in zip(net.list_arguments(), arg_shapes)}
    exe = net.bind(mx.cpu(), args)
    stats = mx.profiler.compiled_stats(exe)
    assert stats, "no stats reported"
    assert any(k.endswith("_in_bytes") or k == "flops" for k in stats)


def test_cosine_and_poly_schedulers():
    from mxnet_tpu.lr_scheduler import CosineScheduler, PolyScheduler
    s = CosineScheduler(max_update=100, final_lr=0.01, warmup_steps=10)
    s.base_lr = 0.1
    assert s(0) == 0.0                       # warmup starts at 0
    assert abs(s(5) - 0.05) < 1e-9           # linear to base_lr
    assert abs(s(10) - 0.1) < 1e-9           # warmup done
    assert abs(s(100) - 0.01) < 1e-9         # decayed to final
    mid = s(55)                              # halfway: mean of ends
    assert abs(mid - 0.055) < 1e-9
    # monotone decreasing after warmup
    vals = [s(i) for i in range(10, 101)]
    assert all(a >= b - 1e-12 for a, b in zip(vals, vals[1:]))

    p = PolyScheduler(max_update=10, power=1.0, final_lr=0.0)
    p.base_lr = 1.0
    assert abs(p(5) - 0.5) < 1e-9 and p(10) == 0.0 and p(20) == 0.0
    # works end-to-end through an optimizer + fused trainer step
    opt = mx.optimizer.create("sgd", learning_rate=0.1,
                              lr_scheduler=CosineScheduler(max_update=50))
    assert opt.lr_scheduler is not None


def test_topk_accuracy_metric():
    """TopKAccuracy: label within the k best scores counts as correct;
    k=1 equals plain accuracy."""
    import numpy as np
    pred = mx.nd.array(np.array([[0.1, 0.5, 0.4],
                                 [0.6, 0.3, 0.1],
                                 [0.3, 0.2, 0.6]], np.float32))
    label = mx.nd.array(np.array([2, 1, 0], np.float32))
    m = mx.metric.TopKAccuracy(top_k=2)
    m.update([label], [pred])
    # row0: top2 = {1,2} contains 2; row1: {0,1} contains 1; row2: {0,2}
    # contains 0 -> 3/3
    assert m.get()[1] == 1.0
    m1 = mx.metric.TopKAccuracy(top_k=1)
    m1.update([label], [pred])
    acc = mx.metric.Accuracy()
    acc.update([label], [pred])
    assert m1.get()[1] == acc.get()[1]
    assert mx.metric.create("top_k_accuracy").top_k == 5


def test_loss_metric():
    """Loss metric: mean of the monitored outputs (the fit-compatible
    metric for loss-emitting heads like SoftmaxCELoss)."""
    import numpy as np
    losses = mx.nd.array(np.array([1.0, 3.0, 5.0], np.float32))
    m = mx.metric.create("loss")
    m.update([None], [losses])
    assert m.get() == ("loss", 3.0)
    m.update([None], [mx.nd.array(np.array([7.0], np.float32))])
    assert m.get()[1] == 4.0


def test_reference_module_aliases():
    """The reference package exposes short aliases (mx.init, mx.viz,
    mx.mon, mx.rnd, mx.th, mx.nd, mx.sym, mx.kv —
    /root/reference/python/mxnet/__init__.py); scripts using them port
    unchanged."""
    for alias, mod in [("init", "initializer"), ("viz", "visualization"),
                       ("mon", "monitor"), ("rnd", "random"),
                       ("th", "torch"), ("nd", "ndarray"),
                       ("sym", "symbol"), ("kv", "kvstore")]:
        assert getattr(mx, alias) is getattr(mx, mod), alias


def test_user_opspec_late_registration():
    """An OpSpec registered AFTER import (the doc/tutorial/new_op_howto
    path) gets its mx.symbol constructor installed immediately."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import OpSpec, Param, register

    from mxnet_tpu.ops.registry import REGISTRY

    try:
        @register
        class _TutorialScaledTanh(OpSpec):
            name = "_TutorialScaledTanh"
            params = {"alpha": Param("float", 1.0)}

            def arguments(self, p):
                return ["data"]

            def infer_shape(self, p, in_shapes):
                return list(in_shapes), [in_shapes[0]], []

            def forward(self, p, ins, aux, is_train, rng):
                return [p["alpha"] * jnp.tanh(ins[0])], []

        y = mx.symbol._TutorialScaledTanh(data=mx.symbol.Variable("data"),
                                          alpha=2.0)
        exe = y.simple_bind(mx.cpu(), grad_req="write", data=(2, 3))
        x = np.random.RandomState(0).randn(2, 3).astype("f")
        exe.forward(is_train=False, data=x)
        np.testing.assert_allclose(exe.outputs[0].asnumpy(),
                                   2.0 * np.tanh(x), rtol=1e-6)
    finally:
        # the global registry outlives this test: later tests gate the
        # live op enumeration against doc/api_manifest.json
        REGISTRY.pop("_TutorialScaledTanh", None)
        mx.symbol.__dict__.pop("_TutorialScaledTanh", None)
