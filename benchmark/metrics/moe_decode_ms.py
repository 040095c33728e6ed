"""Device time of the routed experts per decode round: the operations of
the decode program (``programs.decode``) under ``MoEFFN/<node>``, all
layers and all steps of the round: ``route`` (the choice, the sort by
expert, the rows to their blocks and back) and ``experts`` (the two
grouped products). The router's own small layers are ordinary nodes and
are not in it. Also prints the run's ``scopes:`` line for the decode
program with the parts apart."""
from benchmark import scopes as S

MOE = S.under("MoEFFN/")
PARTS = {
    "moe_route": lambda p: MOE(p) and "/route" in p,
    "moe_experts": lambda p: MOE(p) and "/experts" in p,
    "moe_other": MOE,
    "cca": S.under("CCAttention/"),
    "fc_dots": S.under("FullyConnected/"),
    "other_scoped": lambda p: bool(S.NODE.search(p)),
}


def value(ctx):
    return S.per_call_ms(ctx, "decode", MOE)


def read(ctx):
    S.print_split(ctx, "decode", PARTS)
    return value(ctx)
