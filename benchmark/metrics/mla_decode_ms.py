"""Device time of the latent attention layers per decode round: the
operations of the decode program (``programs.decode``) under
``LatentAttention/<node>``, all layers and all steps of the round: ``down``
(both down-projections, their norms, the queries' up-projection, the rotary
turn), ``absorb`` (``W_uk`` folded into the query, ``W_uv`` out of the mix
of latents), ``cache`` (the new row's write), ``attend`` (the bounded read:
one fetch of a block of latent rows for scores and values) and ``out`` (the
output projection). Also prints the run's ``scopes:`` line for the decode
program with the parts apart, the hyper-connection's and the experts' beside
them, and so the scoped share of the program. A program without such scopes
(the parent of the PR that added them) gives None."""
import re

from benchmark import scopes as S

MLA = S.under("LatentAttention/")
_HC = re.compile(r"(^|[/(])(HyperConnection(Pre|Post)|StreamLanes)/")
MOE = S.under("MoEFFN/")


def HC(path):
    return bool(_HC.search(path))


PARTS = {
    "mla_down": lambda p: MLA(p) and "/down" in p,
    "mla_absorb": lambda p: MLA(p) and "/absorb" in p,
    "mla_expand": lambda p: MLA(p) and "/expand" in p,
    "mla_cache": lambda p: MLA(p) and "/cache" in p,
    "mla_attend": lambda p: MLA(p) and "/attend" in p,
    "mla_out": lambda p: MLA(p) and "/out" in p,
    "mla_other": MLA,
    "hc_coef": lambda p: HC(p) and "/coef" in p,
    "hc_sinkhorn": lambda p: HC(p) and "/sinkhorn" in p,
    "hc_mix": lambda p: HC(p) and "/mix" in p,
    "hc_other": HC,
    "moe_route": lambda p: MOE(p) and "/route" in p,
    "moe_experts": lambda p: MOE(p) and "/experts" in p,
    "moe_shared": lambda p: MOE(p) and "/shared" in p,
    "moe_other": MOE,
    "fc_dots": S.under("FullyConnected/"),
    "other_scoped": lambda p: bool(S.NODE.search(p)),
}


def value(ctx, which="decode"):
    return S.per_call_ms(ctx, which, MLA)


def read(ctx):
    S.print_split(ctx, "decode", PARTS)
    return value(ctx)
