"""Device time of the hyper-connections per decode round: the operations of
the decode program (``programs.decode``) under ``HyperConnectionPre/<node>``
and ``HyperConnectionPost/<node>`` (two of each a layer) and the stream's
two ends (``StreamLanes/<node>``), all layers and all steps of the round:
``coef`` (the stream's norm and ``Phi``), ``sinkhorn`` (the rounds over
``[4, 4, tokens]``) and ``mix`` (the lanes collapsed to the sublayer's
input; the sublayer's output written back to the lanes). The parts are
printed apart by ``mla_decode_ms``. A program without such scopes gives
None."""
from benchmark import scopes as S
from benchmark.harness import load_module


def read(ctx):
    return S.per_call_ms(ctx, "decode",
                         load_module("metrics", "mla_decode_ms").HC)
