"""The whole training step's share of the chip's peak, in percent: the
operations one step needs (forward and backward from shapes, 2 per MAC,
nothing recomputed: the family's ``train_flops_per_step``) over the step's
wall time in the window times the bf16 peak of the chips used."""


def read(ctx):
    step_ms = ctx["spans"].get("step_ms")
    if not step_ms:
        return None
    flops = ctx["family"].train_flops_per_step(ctx["cfg"], ctx["traffic"])
    peak = ctx["peaks"]["flops_bf16"] * ctx["chips"]
    return 100.0 * flops / (step_ms * 1e-3 * peak)
