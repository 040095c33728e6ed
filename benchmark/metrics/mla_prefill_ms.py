"""Device time of the latent attention layers per prefill call (all buckets
together: one program name, ``programs.prefill``): the operations under
``LatentAttention/<node>``, whose read is there the EXPANDED form (``expand``:
a block of latent rows up-projected to per-head keys and values; ``attend``:
scores, the online softmax, values). Also prints the prefill programs'
``scopes:`` line with the parts apart. None where the trace holds no such
operation."""
from benchmark import scopes as S
from benchmark.harness import load_module


def read(ctx):
    mla = load_module("metrics", "mla_decode_ms")
    S.print_split(ctx, "prefill", mla.PARTS)
    return mla.value(ctx, "prefill")
