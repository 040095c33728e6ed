"""The decode round's share of the chip's bf16 peak, in percent: the
operations of one round (mean live slots x steps tokens, each attending
to its live cache rows) over the round's device time x peak."""
from benchmark.harness import load_module


def read(ctx):
    ms = load_module("metrics", "decode_round_ms").read(ctx)
    sp = ctx["spans"]
    if not ms or not sp.get("rounds"):
        return None
    steps = sp["steps_per_round"]
    slots = sp["live_slots"] / sp["rounds"]
    rows = sp["live_rows"] / sp["rounds"]
    flops = ctx["family"].decode_flops(ctx["cfg"], slots * steps,
                                       rows * steps)
    return 100.0 * flops / (ms * 1e-3 * ctx["peaks"]["flops_bf16"]
                            * ctx["chips"])
