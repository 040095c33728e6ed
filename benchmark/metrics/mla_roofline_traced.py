"""The latent attention layers' share of their roofline in a decode round,
in per cent, with counts and time over the SAME rounds. As
``mla_decode_roofline``: least time = steps x ``family.mla_decode_bytes(cfg,
live_rows, live_slots)`` / peak bytes/s, over ``mla_decode_ms``; but both
counts are taken over the traced rounds alone, joined by ``round`` as
``moe_roofline_traced`` joins them: ``live_rows`` = the joined rounds'
``latent_rows_live`` over their latent layer-steps (``attn_rows_pool`` /
(slots x max_len)), ``live_slots`` = the joined ``serving.decode_round``
spans' mean ``slots_busy``. The run-wide counts are printed beside them and
read by nothing. A trace without the engine's round counts (the parent of
the PR that added them) gives None, never 0."""
from benchmark.harness import load_module

_moe = load_module("metrics", "moe_roofline_traced")


def read(ctx):
    t = ctx["traffic"]
    pool_rows = float(t.get("slots", 0) * t.get("max_len", 0))
    fam = ctx.get("family")
    pairs = _moe.joined(ctx)
    layer_steps = _moe.summed(pairs, "attn_rows_pool") / pool_rows \
        if pool_rows else 0
    if not layer_steps or not hasattr(fam, "mla_decode_bytes"):
        return None
    ms = load_module("metrics", "mla_decode_ms").value(ctx)
    if not ms:
        return None
    rows = _moe.summed(pairs, "latent_rows_live") / layer_steps
    slots = sum(r.get("slots_busy", 0) for r, _ in pairs) / len(pairs)
    per_round = ctx["spans"]["steps_per_round"]
    least_s = per_round * fam.mla_decode_bytes(ctx["cfg"], rows, slots) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    run_steps = _moe.run_wide("attn_rows_pool") / pool_rows
    sp = ctx["spans"]
    print("counters: rounds joined=%d; latent rows live per layer and step "
          "= %.1f over %.2f busy slots of them, %s over %s live slots of "
          "the run; least %.3f ms a round of %d steps"
          % (len(pairs), rows, slots,
             "%.1f" % (_moe.run_wide("latent_rows_live") / run_steps)
             if run_steps else "none",
             "%.2f" % (sp["live_slots"] / sp["rounds"])
             if sp.get("rounds") else "none", least_s * 1e3, per_round),
          flush=True)
    return 100.0 * least_s / (ms * 1e-3)
