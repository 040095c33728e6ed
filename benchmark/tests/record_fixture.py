"""Record the small trace ``tests/data/tiny.xplane.pb`` on the chip: two
named programs, a few calls each, with a host pause between them. Run on
the accelerator (``python3 benchmark/tests/record_fixture.py <out>``); the
test reads what it left."""
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out):
    import jax
    import jax.numpy as jnp
    from benchmark import trace as T

    def alpha(x):
        return jnp.tanh(x @ x)

    def beta(x):
        return jnp.sum(x * 2.0)

    fa, fb = jax.jit(alpha), jax.jit(beta)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready((fa(x), fb(x)))
    with T.Capture(os.path.join(out, "trace")) as cap:
        for _ in range(3):
            with T.annotation("bench.alpha"):
                jax.block_until_ready(fa(x))
        with T.annotation("bench.pause"):
            time.sleep(0.02)
        for _ in range(2):
            with T.annotation("bench.beta"):
                jax.block_until_ready(fb(x))
    shutil.copy(cap.path, os.path.join(out, "tiny.xplane.pb"))
    print("fixture bytes", os.path.getsize(cap.path))
    T.describe(cap.path)
    print(T.reduce(cap.path))


if __name__ == "__main__":
    main(sys.argv[1])
