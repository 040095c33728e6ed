"""The routed experts' share of their roofline in a decode round, in per
cent. They are bound by memory: each step has to read, in every layer,
the three matrices of every expert that was given a token, once. Least
time = steps x ``family.moe_decode_bytes(cfg, touched)`` / peak bytes/s,
over ``moe_decode_ms``. ``touched`` is the program's own count: the
engine's counter ``serving.moe_experts_touched`` (experts given a token,
summed on the device over layers and steps) over
``serving.moe_layer_steps``, both over the whole run. A program without
the counter (the parent of the PR that added it) gives None, never 0."""
from benchmark.harness import load_module


def touched_per_layer_step():
    """Mean number of experts given a token, per routed layer and decode
    step; None where the program has counted nothing."""
    import mxnet_tpu as mx
    steps = mx.telemetry.counter("serving.moe_layer_steps").value
    if not steps:
        return None
    return mx.telemetry.counter("serving.moe_experts_touched").value \
        / float(steps)


def read(ctx):
    ms = load_module("metrics", "moe_decode_ms").value(ctx)
    touched = touched_per_layer_step()
    if not ms or touched is None:
        return None
    nbytes = ctx["spans"]["steps_per_round"] \
        * ctx["family"].moe_decode_bytes(ctx["cfg"], touched)
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    print("counters: moe experts touched per layer and step = %.3f of %d; "
          "least %.3f ms a round of %d steps"
          % (touched, ctx["cfg"]["num_experts"], least_s * 1e3,
             ctx["spans"]["steps_per_round"]), flush=True)
    return 100.0 * least_s / (ms * 1e-3)
