"""The share of the engine's slots that hold a decoding request, in per
cent: the driver's count of admitted, unfinished requests after every
round of the WHOLE window (``spans.live_slots`` over ``spans.rounds``: the
``live_slots_mean`` of the run's ``serve:`` line), over the traffic file's
``slots``. The traced seconds hold some two dozen rounds, too few to
repeat (a burst decides them); where the trace has the engine's
``serving.decode_round`` spans, their mean ``slots_busy`` is printed
beside the window's count, to be compared by hand."""
from benchmark import scopes as S


def read(ctx):
    sp = ctx["spans"]
    slots = ctx["traffic"].get("slots")
    if not slots or not sp.get("rounds"):
        return None
    live = sp["live_slots"] / sp["rounds"]
    sc = S.of_run(ctx)
    busy = [st["slots_busy"] for _, _, st in
            (sc.spans("serving.decode_round") if sc is not None else ())
            if "slots_busy" in st]
    if busy:
        print("scopes: live_slots_mean=%.3f over %d rounds of the window; "
              "serving.decode_round spans=%d of the traced seconds "
              "slots_busy mean=%.3f min=%d max=%d"
              % (live, sp["rounds"], len(busy), sum(busy) / len(busy),
                 min(busy), max(busy)), flush=True)
    return 100.0 * live / slots
