"""The share of the pool of recurrent states that the decode steps advance,
in per cent: 100 x ``serving.state_slots_advanced`` (slots whose state a
decode step advanced, summed on the device over the Gated DeltaNet layers
and steps) over ``serving.state_slots_pool`` (slots x those layers x
steps), both over the whole run. A slot that holds no request keeps its
state untouched and is not counted; the step still reads and writes its
state's bytes (there is no kernel that skips them), so this is also the
share of the state traffic that is work. A program without the counters
(the parent of the PR that added them) gives None, never 100."""


def read(ctx):
    import mxnet_tpu as mx
    pool = mx.telemetry.counter("serving.state_slots_pool").value
    if not pool:
        return None
    adv = mx.telemetry.counter("serving.state_slots_advanced").value
    held = mx.telemetry.counter("serving.moe_pairs_held").value
    routed = mx.telemetry.counter("serving.moe_pairs_routed").value
    print("counters: state slots advanced = %d of %d over the same layers "
          "and decode steps; token-expert pairs on held experts = %d of %d "
          "routed" % (adv, pool, held, routed), flush=True)
    return 100.0 * adv / float(pool)
