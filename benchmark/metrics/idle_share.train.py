"""1 - the union of the device-busy intervals over the traced window, in
percent, averaged over the chips used."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
