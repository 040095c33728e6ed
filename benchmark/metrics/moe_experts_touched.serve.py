"""Experts that were given a token, per routed layer and decode step: the
engine's counter ``serving.moe_experts_touched`` (summed on the device over
the routed layers and the decode steps) over ``serving.moe_layer_steps``,
both over the whole run. A decode step streams the matrices of every
expert it touches, so this count is the experts' part of a step's work
(``moe_decode_roofline`` takes its bytes from the same quotient). A
program that has counted nothing gives None, never 0.

Where the program also counts the slots that routed nothing
(``serving.moe_rows_masked``: a slot that holds no request gives its
tokens no expert), their share of slots x layer-steps is printed beside
the share of the slots that held no request after the window's rounds
(100 - ``batch_occupancy.serve``), to be compared by hand."""
from benchmark.harness import load_module


def read(ctx):
    import mxnet_tpu as mx
    touched = load_module(
        "metrics", "moe_decode_roofline").touched_per_layer_step()
    if touched is None:
        return None
    masked = mx.telemetry.counter("serving.moe_rows_masked").value
    slots = ctx["traffic"].get("slots")
    if masked and slots:
        pool = slots * mx.telemetry.counter("serving.moe_layer_steps").value
        sp = ctx.get("spans") or {}
        empty = 100.0 - 100.0 * sp["live_slots"] / sp["rounds"] / slots \
            if sp.get("rounds") else float("nan")
        print("counters: moe rows masked = %d of %d slots x layer-steps "
              "(%.2f%%); slots that held no request after a round = %.2f%%"
              % (masked, pool, 100.0 * masked / pool, empty), flush=True)
    return touched
