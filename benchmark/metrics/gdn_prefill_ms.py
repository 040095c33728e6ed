"""Device time of the Gated DeltaNet layers per prefill call (all buckets
together: one program name, ``programs.prefill``): the operations under
``GatedDeltaNet/<node>``, whose ``state`` part is there the chunked form of
the recurrence. Also prints the prefill programs' ``scopes:`` line with the
parts apart. None where the trace holds no such operation."""
from benchmark import scopes as S
from benchmark.harness import load_module


def read(ctx):
    gdn = load_module("metrics", "gdn_decode_ms")
    S.print_split(ctx, "prefill", gdn.PARTS)
    return gdn.value(ctx, "prefill")
