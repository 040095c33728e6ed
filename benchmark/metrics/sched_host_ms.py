"""Host time of the scheduler per decode round: the engine's own round
phases ``sched`` + ``prefix_lookup`` + ``h2d`` + ``copy`` summed over the
window (its ``serving.round_phase_ms.*`` histograms, read before and after),
over the rounds dispatched in it."""


def read(ctx):
    ph = ctx["spans"].get("phase_ms")
    rounds = ctx["spans"].get("rounds_in_window")
    if not ph or not rounds:
        return None
    return sum(ph[k] for k in ("sched", "prefix_lookup", "h2d", "copy")) \
        / rounds
