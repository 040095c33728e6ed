"""Backend-consistency harness: the same net, CPU interpreter vs the real
TPU chip, outputs and gradients compared.

Parity: the reference's GPU test suite (tests/python/gpu/
test_operator_gpu.py) runs every symbol on CPU and GPU and compares;
here the pair is XLA-CPU vs XLA-TPU. Each backend runs in its own
subprocess: the test process itself is held to the CPU
(tests/conftest.py), and a chip belongs to one process at a time — so
the TPU child gets an environment WITHOUT ``JAX_PLATFORMS``, reports
the platform it really ran on, and the test skips unless that is
``tpu``. Under ``MXNET_TPU_TEST_ON_TPU=1`` the pytest process holds
the chip itself and no child can have it: the tests skip, loudly.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

DRIVER = r"""
import sys, json
import numpy as np
import mxnet_tpu as mx

out_path = sys.argv[1]

data = mx.symbol.Variable("data")
net = mx.symbol.Convolution(data=data, name="conv", kernel=(3, 3),
                            num_filter=8, pad=(1, 1))
net = mx.symbol.BatchNorm(data=net, name="bn")
net = mx.symbol.Activation(data=net, name="relu", act_type="relu")
net = mx.symbol.Pooling(data=net, name="pool", pool_type="max",
                        kernel=(2, 2), stride=(2, 2))
net = mx.symbol.Flatten(data=net)
net = mx.symbol.FullyConnected(data=net, name="fc", num_hidden=5)
net = mx.symbol.SoftmaxOutput(data=net, name="softmax")

shapes = {"data": (4, 3, 8, 8)}
exe = net.simple_bind(mx.cpu(), grad_req="write", **shapes)
rng = np.random.RandomState(42)
for name, arr in exe.arg_dict.items():
    if name == "softmax_label":
        arr[:] = rng.randint(0, 5, arr.shape).astype(np.float32)
    else:
        arr[:] = rng.uniform(-0.5, 0.5, arr.shape).astype(np.float32)
exe.forward(is_train=True)
exe.backward()
import jax
result = {"platform": jax.devices()[0].platform,
          "out": exe.outputs[0].asnumpy().tolist()}
for name, g in exe.grad_dict.items():
    if g is not None and name != "softmax_label":
        result["grad_" + name] = g.asnumpy().tolist()
with open(out_path, "w") as f:
    json.dump(result, f)
"""


def _chip_env():
    """Environment of a child that may take the chip: the parent's,
    minus the CPU pin conftest put there."""
    if os.environ.get("MXNET_TPU_TEST_ON_TPU") == "1":
        pytest.skip("MXNET_TPU_TEST_ON_TPU=1: this pytest process holds "
                    "the chip, a child cannot")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_backend(tmp_path, tag, env):
    script = tmp_path / ("driver_%s.py" % tag)
    script.write_text(DRIVER)
    out = tmp_path / ("out_%s.json" % tag)
    env = dict(env)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script), str(out)],
                       capture_output=True, text=True, timeout=600,
                       cwd=ROOT, env=env)
    if r.returncode != 0:
        return None, r.stderr
    with open(out) as f:
        return json.load(f), None


@pytest.mark.slow
def test_cpu_vs_tpu_consistency(tmp_path):
    chip_env = _chip_env()
    cpu_res, err = _run_backend(tmp_path, "cpu",
                                dict(os.environ, JAX_PLATFORMS="cpu"))
    assert cpu_res is not None, err
    assert cpu_res.pop("platform") == "cpu"

    tpu_res, err = _run_backend(tmp_path, "tpu", chip_env)
    if tpu_res is None:
        pytest.skip("TPU backend unavailable: %s" % (err or "")[-200:])
    platform = tpu_res.pop("platform")
    if platform != "tpu":
        pytest.skip("no TPU: the second child ran on %r" % platform)

    for key in cpu_res:
        a = np.asarray(cpu_res[key], np.float64)
        b = np.asarray(tpu_res[key], np.float64)
        # TPU f32 convs/matmuls accumulate through bf16 passes; scale
        # tolerance to the tensor's magnitude
        tol = 5e-2 * max(np.abs(a).max(), 1e-3)
        assert np.abs(a - b).max() < tol, (
            key, np.abs(a - b).max(), tol)


PALLAS_DRIVER = r"""
import sys, json
import numpy as np
import jax, jax.numpy as jnp
from mxnet_tpu.ops.pallas_kernels import flash_attention, fused_linear

out = {}
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(2, 200, 4, 64).astype(np.float32))
k = jnp.asarray(rng.randn(2, 200, 4, 64).astype(np.float32))
v = jnp.asarray(rng.randn(2, 200, 4, 64).astype(np.float32))
for causal in (False, True):
    o = jax.jit(lambda a, b, c: flash_attention(a, b, c,
                                                causal=causal))(q, k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64)
    if causal:
        m = jnp.tril(jnp.ones((200, 200), bool))
        s = jnp.where(m[None, None], s, -jnp.inf)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    out["flash_causal_%s" % causal] = float(jnp.abs(o - ref).max())
x = jnp.asarray(rng.randn(250, 128).astype(np.float32))
w = jnp.asarray(rng.randn(128, 500).astype(np.float32))
b = jnp.asarray(rng.randn(500).astype(np.float32))
y = jax.jit(lambda a, bb, c: fused_linear(a, bb, c, act="gelu"))(x, w, b)
out["fused_linear"] = float(jnp.abs(y - jax.nn.gelu(x @ w + b)).max())
out["platform"] = jax.devices()[0].platform
with open(sys.argv[1], "w") as f:
    json.dump(out, f)
"""


@pytest.mark.slow
def test_pallas_kernels_on_tpu(tmp_path):
    """The Mosaic-compiled kernels must run on the real chip and agree
    with dense references (regression: i64 literals under x64 broke
    Mosaic lowering while interpret-mode tests stayed green)."""
    script = tmp_path / "pallas_driver.py"
    script.write_text(PALLAS_DRIVER)
    out = tmp_path / "out.json"
    env = _chip_env()
    # probe the backend FIRST: a kernel compile failure must FAIL the
    # test, not be mistaken for "no TPU available"
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    platform = (probe.stdout or "").strip().splitlines()[-1] \
        if probe.returncode == 0 and probe.stdout.strip() else ""
    if probe.returncode != 0 or platform in ("", "cpu"):
        pytest.skip("no accelerator backend (platform=%r)" % platform)
    r = subprocess.run([sys.executable, str(script), str(out)],
                       capture_output=True, text=True, timeout=580,
                       cwd=ROOT, env=env)
    assert r.returncode == 0, (
        "pallas kernels failed on %s backend: %s"
        % (platform, r.stderr[-1500:]))
    res = json.loads(out.read_text())
    res.pop("platform")
    for name, diff in res.items():
        assert diff < 2e-2, (name, diff)
