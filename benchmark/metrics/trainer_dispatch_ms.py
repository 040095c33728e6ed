"""Host clock around ``ParallelTrainer.step`` until it returns, un-blocked:
mean per step over the window (the benchmark's own span)."""


def read(ctx):
    spans = ctx["spans"].get("dispatch_s")
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
