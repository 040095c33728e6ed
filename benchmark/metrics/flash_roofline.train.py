"""The flash-attention kernels' share of their roofline in a training
step, in percent: the least time the chip could take for their operations
and bytes (the family's ``flash_train_cost``, from shapes), over their
summed device time per step in the trace. The kernels are the operations
whose HLO line matches the traffic file's ``kernels.flash``. At 2048-token
rows the bound is compute (PERF.md section 3 says which)."""
import re


def read(ctx):
    tr = ctx.get("trace")
    pat = ctx["traffic"].get("kernels", {}).get("flash")
    step = ctx["traffic"].get("programs", {}).get("step")
    if not tr or not pat or not step:
        return None
    calls = tr["programs"].get(step, {}).get("calls")
    secs = sum(v["seconds"] for v in tr["ops"].values()
               if re.search(pat, v["text"]))
    if not calls or secs <= 0:
        return None
    flops, nbytes = ctx["family"].flash_train_cost(ctx["cfg"],
                                                   ctx["traffic"])
    least = max(flops / ctx["peaks"]["flops_bf16"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (secs / calls)
