"""Device time of the forward pass per training step: the operations of
the step program (``programs.step``) traced under ``mx.grads`` whose path
holds no ``transpose(`` (JAX names backward operations ``transpose(jvp(
<scope>))``; a kernel with its own backward rule, ``transpose(mx.grads)/
jvp(<scope>)``). Also prints the run's ``scopes:`` line: forward, backward,
what the optimizer kept in fusions of its own, and what no scope claims.
XLA fuses a parameter's update into the fusion that produces its gradient,
and a fusion carries one path: those updates read as backward, so there is
no metric of the optimizer alone.)"""
from benchmark import scopes as S

GRADS = S.under("mx.grads")
PARTS = {
    "fwd": lambda p: GRADS(p) and "transpose(" not in p,
    "bwd": lambda p: GRADS(p) and "transpose(" in p,
    "optimizer_unfused": lambda p: S.under("mx.optimizer")(p)
    or S.under("mx.clip")(p),
}


def read(ctx):
    S.print_split(ctx, "step", PARTS)
    return S.per_call_ms(ctx, "step", PARTS["fwd"])
