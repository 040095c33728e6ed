"""Device time of compressed convolutional attention per decode round:
the operations of the decode program (``programs.decode``) under
``CCAttention/<node>``, all layers and all steps of the round: ``proj``
(the three projections), ``conv`` (the two convolutions, the mean, the
norms, the rotary turn), ``cache`` (the rolling state's read and write,
the new K/V rows) and ``attend`` (scores, softmax, values off the stored
rows). Also prints the parts apart, as ``decode_attn_ms`` does."""
from benchmark import scopes as S

CCA = S.under("CCAttention/")
PARTS = {
    "cca_proj": lambda p: CCA(p) and "/proj" in p,
    "cca_conv": lambda p: CCA(p) and "/conv" in p,
    "cca_cache": lambda p: CCA(p) and "/cache" in p,
    "cca_attend": lambda p: CCA(p) and "/attend" in p,
    "cca_other": CCA,
    "moe": S.under("MoEFFN/"),
    "other_scoped": lambda p: bool(S.NODE.search(p)),
}


def read(ctx):
    S.print_split(ctx, "decode", PARTS)
    return S.per_call_ms(ctx, "decode", CCA)
