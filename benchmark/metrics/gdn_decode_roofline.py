"""The Gated DeltaNet layers' share of their roofline in a decode round, in
per cent. A decode step of theirs is bound by memory: it has to read each
such layer's weights once and, for every slot whose state it advances, read
that state once and write it once. Least time = steps x
``family.gdn_decode_bytes(cfg, advanced)`` / peak bytes/s, over
``gdn_decode_ms``. ``advanced`` is the program's own count: the engine's
counter ``serving.state_slots_advanced`` (slots whose state a step
advanced, summed on the device over the DeltaNet layers and steps) over
the layer-steps it was summed over (``serving.state_slots_pool`` / slots),
both over the whole run. A program without the counter (the parent of the
PR that added it) gives None, never 0."""
from benchmark.harness import load_module


def advanced_per_layer_step(ctx):
    """Mean number of slots whose state one DeltaNet layer advanced in one
    decode step; None where the program has counted nothing."""
    import mxnet_tpu as mx
    pool = mx.telemetry.counter("serving.state_slots_pool").value
    slots = ctx["traffic"].get("slots")
    if not pool or not slots:
        return None
    return mx.telemetry.counter("serving.state_slots_advanced").value \
        / (pool / float(slots))


def read(ctx):
    ms = load_module("metrics", "gdn_decode_ms").value(ctx)
    advanced = advanced_per_layer_step(ctx)
    fam = ctx.get("family")
    if not ms or advanced is None or not hasattr(fam, "gdn_decode_bytes"):
        return None
    steps = ctx["spans"]["steps_per_round"]
    nbytes = steps * fam.gdn_decode_bytes(ctx["cfg"], advanced)
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    print("counters: state slots advanced per DeltaNet layer and step = "
          "%.3f of %d; least %.3f ms a round of %d steps"
          % (advanced, ctx["traffic"]["slots"], least_s * 1e3, steps),
          flush=True)
    return 100.0 * least_s / (ms * 1e-3)
