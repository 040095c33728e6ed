"""Record the small trace ``tests/data/scoped.xplane.pb`` on the chip: three
calls of one program that names its parts the way ``mxnet_tpu`` does (a
forward and a backward under ``mx.grads`` through two ``FullyConnected/*``
scopes and a loss, an update under ``mx.optimizer`` kept apart from the
backward by a barrier), each call inside the
host spans an engine round has (``serving.round`` holding
``serving.decode_round`` with ``slots_busy=`` and ``serving.drain``). Plain
JAX: the fixture checks the reader, not the program. Run on the accelerator
(``python3 benchmark/tests/record_scopes_fixture.py <out>``); the values in
``scoped.json`` are read by hand from what it prints."""
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out):
    import jax
    import jax.numpy as jnp
    from benchmark import scopes as S
    from benchmark import trace as T

    def scoped_step(w, x):
        def loss(w):
            with jax.named_scope("FullyConnected/fc1"):
                h = jnp.tanh(x @ w["fc1"])
            with jax.named_scope("FullyConnected/fc2"):
                y = h @ w["fc2"]
            with jax.named_scope("SoftmaxOutput/softmax"):
                return jnp.mean(jnp.square(y.astype(jnp.float32)))

        with jax.named_scope("mx.grads"):
            value, g = jax.value_and_grad(loss)(w)
        # the barrier keeps the update a fusion of its own: without it XLA
        # fuses it into the backward's matmuls, and a fusion carries ONE
        # path (the first recording read mx.optimizer 0: PERF.md, PR 26)
        g = jax.lax.optimization_barrier(g)
        with jax.named_scope("mx.optimizer"):
            w = {k: w[k] - (0.01 * g[k]).astype(w[k].dtype) for k in w}
        return w, value

    step = jax.jit(scoped_step)
    key = jax.random.PRNGKey(0)
    w = {"fc1": jax.random.normal(key, (1024, 2048), jnp.bfloat16) * 0.02,
         "fc2": jax.random.normal(key, (2048, 512), jnp.bfloat16) * 0.02}
    x = jnp.ones((512, 1024), jnp.bfloat16)
    w, value = step(w, x)
    jax.block_until_ready(value)
    with T.Capture(os.path.join(out, "trace")) as cap:
        # two arguments, to check that a span's stats are read: the
        # engine's own decode_round span carries ``slots_busy`` alone
        for busy in (3, 5, 4):
            with jax.profiler.TraceAnnotation("serving.round"):
                with jax.profiler.TraceAnnotation("serving.decode_round",
                                                  slots_busy=busy,
                                                  live_rows=100 * busy):
                    w, value = step(w, x)
                time.sleep(0.002)           # the host's own work
                with jax.profiler.TraceAnnotation("serving.drain"):
                    jax.block_until_ready(value)
        with jax.profiler.TraceAnnotation("serving.round"):
            time.sleep(0.001)               # a round that dispatched none
    shutil.copy(cap.path, os.path.join(out, "scoped.xplane.pb"))
    print("fixture bytes", os.path.getsize(cap.path))
    S.describe(cap.path, top=30)
    sc = S.read(cap.path)
    for pid, ops in sc.ops.items():
        for name, row in sorted(ops.items(), key=lambda kv: -kv[1]["seconds"]):
            print("OP", pid, name, row["calls"], "%.9f" % row["seconds"],
                  row["path"])
    for h in sc.host:
        print("HOST", h)


if __name__ == "__main__":
    main(sys.argv[1])
