"""1 - the union of the device-busy intervals over the traced window, in
per cent: the same reading as ``idle_share.train``, under the name whose
cells report the serving metrics."""
from benchmark.harness import load_module


def read(ctx):
    return load_module("metrics", "idle_share.train").read(ctx)
