"""Device time of the backward pass per training step: the operations of
the step program under ``mx.grads`` whose path holds ``transpose(``
(``fwd_ms.train`` says how JAX names them), and with them the updates of
the parameters that XLA fused into the gradients' fusions."""
from benchmark import scopes as S
from benchmark.harness import load_module


def read(ctx):
    parts = load_module("metrics", "fwd_ms.train").PARTS
    return S.per_call_ms(ctx, "step", parts["bwd"])
