"""Device time of the prefill programs per call, from the trace (all
buckets together: one program name, ``programs.prefill``)."""
from benchmark.harness import load_module


def read(ctx):
    return load_module("metrics", "decode_round_ms").per_call_ms(ctx,
                                                                  "prefill")
