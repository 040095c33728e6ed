"""Host time of one engine round: every phase of the engine's round but
``drain`` (the wait for the device's results), summed over the window and
divided by the rounds dispatched in it. The phases are the engine's
``serving.<phase>`` spans, which feed its ``serving.round_phase_ms.*``
histograms (read before and after the window) and sum to each round's
wall time (``serving.round``); so this is the host's own work per round,
the dispatches of prefills and decode rounds included, which
``sched_host_ms`` leaves out."""


def read(ctx):
    ph = ctx["spans"].get("phase_ms")
    rounds = ctx["spans"].get("rounds_in_window")
    if not ph or not rounds:
        return None
    return (sum(ph.values()) - ph["drain"]) / rounds
