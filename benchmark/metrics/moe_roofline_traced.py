"""The routed experts' share of their roofline in a decode round, in per
cent, with counts and time over the SAME rounds. As ``moe_decode_roofline``:
least time = steps x ``family.moe_decode_bytes(cfg, touched)`` / peak
bytes/s, over ``moe_decode_ms``; but ``touched`` is taken over the traced
rounds alone: the engine's ``serving.round_counts`` span of each drained
round (its ``round`` and what that round added to each counter) is joined
to the ``serving.decode_round`` span of the same ``round``, and ``touched``
= the joined rounds' ``moe_experts_touched`` over their ``moe_layer_steps``.
The run-wide counter is printed beside it and read by nothing. A trace
without the engine's round counts (the parent of the PR that added them)
gives None, never 0."""
from benchmark import scopes as S
from benchmark.harness import load_module


def joined(ctx):
    """[(``serving.decode_round`` arguments, ``serving.round_counts``
    arguments)] of the rounds that have both in the run's trace, in the
    order of dispatch; [] where there is no trace or no round counts."""
    sc = S.of_run(ctx)
    if sc is None:
        return []
    counts = {st["round"]: st for _, _, st in sc.spans("serving.round_counts")
              if "round" in st}
    return [(st, counts[st["round"]])
            for _, _, st in sc.spans("serving.decode_round")
            if st.get("round") in counts]


def summed(pairs, name):
    """What the joined rounds added to the counter ``serving.<name>``."""
    return sum(c.get(name, 0) for _, c in pairs)


def run_wide(name):
    """The counter ``serving.<name>`` over the whole run."""
    import mxnet_tpu as mx
    return mx.telemetry.counter("serving." + name).value


def read(ctx):
    pairs = joined(ctx)
    steps = summed(pairs, "moe_layer_steps")
    if not steps:
        return None
    ms = load_module("metrics", "moe_decode_ms").value(ctx)
    if not ms:
        return None
    fam, cfg = ctx["family"], ctx["cfg"]
    touched = summed(pairs, "moe_experts_touched") / float(steps)
    per_round = ctx["spans"]["steps_per_round"]
    least_s = per_round * fam.moe_decode_bytes(cfg, touched) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    dims = fam._dims(cfg)
    run_steps = run_wide("moe_layer_steps")
    print("counters: rounds joined=%d; moe experts touched per layer and "
          "step = %.3f of %d held over them (%d of %d layer-steps), %s over "
          "the run; least %.3f ms a round of %d steps"
          % (len(pairs), touched, dims.get("held", dims["x"]),
             summed(pairs, "moe_experts_touched"), steps,
             "%.3f" % (run_wide("moe_experts_touched") / float(run_steps))
             if run_steps else "none", least_s * 1e3, per_round),
          flush=True)
    return 100.0 * least_s / (ms * 1e-3)
