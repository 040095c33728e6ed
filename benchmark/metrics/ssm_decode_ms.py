"""Device time of the Mamba-2 layers per decode round: the operations of
the decode program (``programs.decode``) under ``Mamba2/<node>``, all such
layers and all steps of the round: ``proj`` (the input and the output
projections), ``conv`` (the causal convolution, ``dt`` and the decay),
``state`` (the recurrence: the state's read, its update and its write,
and the convolution window's) and ``norm`` (the ``D`` skip and the gated
norm). Also prints the run's ``scopes:`` line for the decode program with
the parts apart, the attention layers and the MLPs beside them, and so the
scoped share of the program. A program without such scopes (one from
before the Mamba-2 op) gives None."""
from benchmark import scopes as S

SSM = S.under("Mamba2/")
MHA = S.under("MultiHeadAttention/")
PARTS = {
    "ssm_proj": lambda p: SSM(p) and "/proj" in p,
    "ssm_conv": lambda p: SSM(p) and "/conv" in p,
    "ssm_state": lambda p: SSM(p) and "/state" in p,
    "ssm_norm": lambda p: SSM(p) and "/norm" in p,
    "ssm_other": SSM,
    "attn_cache": lambda p: MHA(p) and "/cache" in p,
    "attn_attend": lambda p: MHA(p) and "/attend" in p,
    "attn_other": MHA,
    "fc_dots": S.under("FullyConnected/"),
    "other_scoped": lambda p: bool(S.NODE.search(p)),
}


def value(ctx, which="decode", predicate=SSM):
    return S.per_call_ms(ctx, which, predicate)


def read(ctx):
    S.print_split(ctx, "decode", PARTS)
    return value(ctx)
