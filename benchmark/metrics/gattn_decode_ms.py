"""Device time of the gated attention layers per decode round: the
operations of the decode program (``programs.decode``) under
``GatedAttention/<node>``, all such layers and all steps of the round:
``proj`` (q with its gate, k, v, the norms, the rotary turn, the gated
output projection), ``cache`` (the new rows' write) and ``attend`` (the
bounded read off the stored rows). The parts are printed apart by
``gdn_decode_ms``. A program without such scopes gives None."""
from benchmark import scopes as S
from benchmark.harness import load_module


def read(ctx):
    return S.per_call_ms(ctx, "decode",
                         load_module("metrics", "gdn_decode_ms").GATTN)
