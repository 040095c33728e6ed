"""Device time of attention per decode round: the operations of the decode
program (``programs.decode``) under ``MultiHeadAttention/<node>``, all
layers and all steps of the round: the projections, the ``cache`` part
(the write of the new rows, the read of the stored ones) and the
``attend`` part (scores, softmax, values). Also prints the run's
``scopes:`` line for the decode program, with the parts apart."""
from benchmark import scopes as S

MHA = S.under("MultiHeadAttention/")
PARTS = {
    "attn_cache": lambda p: MHA(p) and "/cache" in p,
    "attn_attend": lambda p: MHA(p) and "/attend" in p,
    "attn_proj": MHA,
    "fc_dots": S.under("FullyConnected/"),
    "other_scoped": lambda p: bool(S.NODE.search(p)),
}


def read(ctx):
    S.print_split(ctx, "decode", PARTS)
    return S.per_call_ms(ctx, "decode", MHA)
