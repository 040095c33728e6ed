"""The Mamba-2 state's share of its roofline in a decode round, in per
cent, with counts and time over the SAME rounds: least time = the bytes
that the advanced slots' states have to move (each read once and written
once: ``family.ssm_state_bytes(cfg)`` per slot and layer, the float32
state and the convolution's window) / peak bytes/s, over the device time
of the decode program under ``Mamba2/<node>/state`` alone. The weights
are no part of it, on either side. The advanced slots are counted over
the traced rounds alone: the engine's ``serving.round_counts`` span of
each drained round is joined by ``round`` to the ``serving.decode_round``
span of the same round, as ``moe_roofline_traced`` joins them, and their
``state_slots_advanced`` (summed on the device over the state layers and
steps) is taken per joined round. A trace without the engine's round
counts, or a family without ``ssm_state_bytes`` (a program from before
them) gives None, never 0."""
from benchmark.harness import load_module

_moe = load_module("metrics", "moe_roofline_traced")


def read(ctx):
    fam = ctx.get("family")
    pairs = _moe.joined(ctx)
    advanced = _moe.summed(pairs, "state_slots_advanced")
    if not advanced or not hasattr(fam, "ssm_state_bytes"):
        return None
    ssm = load_module("metrics", "ssm_decode_ms")
    ms = ssm.value(ctx, "decode",
                   lambda p: ssm.SSM(p) and "/state" in p)
    if not ms:
        return None
    per_round = advanced / float(len(pairs))
    least_s = per_round * 2 * fam.ssm_state_bytes(ctx["cfg"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    run_adv = _moe.run_wide("state_slots_advanced")
    print("counters: rounds joined=%d; state slots advanced per round = "
          "%.1f (summed over the state layers and steps), %d over the "
          "run; least %.3f ms a round against %.3f ms under Mamba2/*/state"
          % (len(pairs), per_round, run_adv, least_s * 1e3, ms),
          flush=True)
    return 100.0 * least_s / (ms * 1e-3)
