"""Device time of the decode program per dispatched round, from the
trace's ``XLA Modules`` line. The program's name is the traffic file's
``programs.decode``."""


def per_call_ms(ctx, which):
    tr = ctx.get("trace")
    name = ctx["traffic"].get("programs", {}).get(which)
    if not tr or not name:
        return None
    row = tr["programs"].get(name)
    if not row or not row["calls"]:
        return None
    return row["seconds"] / row["calls"] * 1e3


def read(ctx):
    return per_call_ms(ctx, "decode")
