"""Device time of the Gated DeltaNet layers per decode round: the
operations of the decode program (``programs.decode``) under
``GatedDeltaNet/<node>``, all such layers and all steps of the round:
``proj`` (the two input projections and the output projection), ``conv``
(the causal convolution, the norms of q and k, ``beta`` and ``g``),
``state`` (the recurrence: the state's read, its update and its write)
and ``norm`` (the gated output norm). Also prints the run's ``scopes:``
line for the decode program with the parts apart, the other kinds of layer
beside them, and so the scoped share of the program. A program without
such scopes (the parent of the PR that added them) gives None."""
from benchmark import scopes as S

GDN = S.under("GatedDeltaNet/")
GATTN = S.under("GatedAttention/")
MOE = S.under("MoEFFN/")
PARTS = {
    "gdn_proj": lambda p: GDN(p) and "/proj" in p,
    "gdn_conv": lambda p: GDN(p) and "/conv" in p,
    "gdn_state": lambda p: GDN(p) and "/state" in p,
    "gdn_norm": lambda p: GDN(p) and "/norm" in p,
    "gdn_other": GDN,
    "gattn_proj": lambda p: GATTN(p) and "/proj" in p,
    "gattn_cache": lambda p: GATTN(p) and "/cache" in p,
    "gattn_attend": lambda p: GATTN(p) and "/attend" in p,
    "gattn_other": GATTN,
    "moe_route": lambda p: MOE(p) and "/route" in p,
    "moe_experts": lambda p: MOE(p) and "/experts" in p,
    "moe_shared": lambda p: MOE(p) and "/shared" in p,
    "moe_other": MOE,
    "fc_dots": S.under("FullyConnected/"),
    "other_scoped": lambda p: bool(S.NODE.search(p)),
}


def value(ctx, which="decode"):
    return S.per_call_ms(ctx, which, GDN)


def read(ctx):
    S.print_split(ctx, "decode", PARTS)
    return value(ctx)
