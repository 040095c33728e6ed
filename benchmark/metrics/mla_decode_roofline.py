"""The latent attention layers' share of their roofline in a decode round,
in per cent. A decode step of theirs is bound by memory: it has to read each
such layer's weights once, each live slot's latent rows ONCE (a row is key
and value at once) and write each live slot's new row. Least time = steps x
``family.mla_decode_bytes(cfg, live_rows, live_slots)`` / peak bytes/s, over
``mla_decode_ms``. ``live_rows`` is the program's own count: the engine's
counter ``serving.latent_rows_live`` (the live slots' true lengths, summed
on the device over the latent layers and decode steps) over the layer-steps
it was summed over (``serving.attn_rows_pool`` / (slots x max_len)), both
over the whole run; ``live_slots`` is the driver's mean of live slots after
a round. A program without the counter (the parent of the PR that added it)
gives None, never 0."""
from benchmark.harness import load_module


def _count(name):
    import mxnet_tpu as mx
    return mx.telemetry.counter("serving." + name).value


def live_rows_per_layer_step(ctx):
    """Mean number of latent rows the live slots hold, per latent layer
    and decode step; None where the program has counted nothing."""
    live, pool = _count("latent_rows_live"), _count("attn_rows_pool")
    t = ctx["traffic"]
    if not live or not pool or not t.get("slots") or not t.get("max_len"):
        return None
    return live / (pool / float(t["slots"] * t["max_len"]))


def read(ctx):
    ms = load_module("metrics", "mla_decode_ms").value(ctx)
    rows = live_rows_per_layer_step(ctx)
    fam = ctx.get("family")
    sp = ctx.get("spans") or {}
    if not ms or rows is None or not hasattr(fam, "mla_decode_bytes") \
            or not sp.get("rounds"):
        return None
    steps = sp["steps_per_round"]
    slots = sp["live_slots"] / sp["rounds"]
    nbytes = steps * fam.mla_decode_bytes(ctx["cfg"], rows, slots)
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    print("counters: latent rows live per layer and step = %.1f over %.2f "
          "live slots; rows fetched (block-rounded) = %d; least %.3f ms a "
          "round of %d steps"
          % (rows, slots,
             _count("attn_rows_read"),
             least_s * 1e3, steps), flush=True)
    return 100.0 * least_s / (ms * 1e-3)
