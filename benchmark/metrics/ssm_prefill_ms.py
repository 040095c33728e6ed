"""Device time of the Mamba-2 layers per prefill call (all buckets
together: one program name, ``programs.prefill``): the operations under
``Mamba2/<node>``, whose ``state`` part is there the chunked (SSD) form of
the recurrence. Also prints the prefill programs' ``scopes:`` line with
the parts apart. None where the trace holds no such operation."""
from benchmark import scopes as S
from benchmark.harness import load_module


def read(ctx):
    ssm = load_module("metrics", "ssm_decode_ms")
    S.print_split(ctx, "prefill", ssm.PARTS)
    return ssm.value(ctx, "prefill")
