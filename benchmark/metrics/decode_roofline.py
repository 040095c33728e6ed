"""The decode round's share of its roofline, in percent. Decode is bound
by memory: each of a round's steps has to read the bf16 weights once and
the live cache rows once. Least time = steps x (weight bytes + mean live
rows x bytes a row) / peak bytes/s, over the round's device time."""
from benchmark.harness import load_module


def read(ctx):
    ms = load_module("metrics", "decode_round_ms").read(ctx)
    sp = ctx["spans"]
    if not ms or not sp.get("rounds"):
        return None
    fam, cfg = ctx["family"], ctx["cfg"]
    rows = sp["live_rows"] / sp["rounds"]
    nbytes = sp["steps_per_round"] * (
        fam.decode_weight_bytes(cfg)
        + rows * fam.decode_cache_bytes_per_row(cfg))
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
