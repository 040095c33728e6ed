#!/usr/bin/env python3
"""Read the two ends a limit is set between, on the chip at the cell's own
size: the program's numbers over many seeds (the lower reading), and the
control's and the planted faults' over a few (the upper reading; for a
serving cell the fault is one served token altered, read at the same
positions as the control).

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3,... \
        --control-seeds 1,2,3 [--seconds 12]
    python3 benchmark/calibrate.py --workload <serving cell> --seeds 1 \
        --rates 2,4,6,8,10 --seconds 30        # the knee sweep

One process reads all seeds, so set-up is paid once where the program's
state can be reused (training), and per seed where the weights are the
seed's (serving). The benchmark's own runs never call this; PERF.md
section 2 records what it read, and ``limits/<cell>.json`` what was set.
Each reading is printed as one JSON line starting ``reading``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness as H  # noqa: E402


def say(kind, seed, numbers):
    print("reading " + json.dumps({"kind": kind, "seed": seed,
                                   "numbers": numbers}), flush=True)


def through_limits(cell, kind, seed, nums):
    """The control and the faults have to come out NOT correct by the
    cell's own limits: say which numbers failed them, on the chip."""
    limits = cell["limits"]["limits"]
    failed = sorted(k for k in limits if k in nums and not nums[k] <= limits[k])
    print("verdict " + json.dumps({"kind": kind, "seed": seed,
                                   "correct": not failed,
                                   "failed": failed}), flush=True)


def train(cell, seeds, control_seeds, faults=None):
    import mxnet_tpu as mx
    from benchmark.drivers import train as D
    fam, cfg, traffic = cell["family"], cell["cfg"], cell["traffic"]
    control = cell["limits"].get("control_precision", "fp8")
    trainer, like = D.build_trainer(mx, fam, cfg, traffic)
    probe = D.Probe(fam, cfg, traffic)
    prog = {}
    for seed in seeds:
        ring = D.ring_batches(fam, cfg, traffic, seed, like)
        prog[seed] = D.program_first_steps(trainer, probe, fam, cfg,
                                           traffic, seed, ring)
        del ring
    del trainer, probe, like
    gc.collect()
    kinds = [("control", {"precision": control})] \
        + [(f, {"fault": f}) for f in D.FAULTS
           if faults is None or f in faults]
    out = {"program": []}
    for seed in seeds:
        ring = D.ring_batches(fam, cfg, traffic, seed)
        ref = D.reference_first_steps(fam, cfg, traffic, seed, ring)
        nums = {k: v[0] for k, v in D.compare(prog[seed], ref).items()}
        say("program", seed, nums)
        through_limits(cell, "program", seed, nums)
        out["program"].append(nums)
        if seed in control_seeds:
            # which leaves carry the gaps, the program's beside the
            # control's: for reading by hand (PERF.md section 2)
            for what in ("grad", "delta"):
                print("leaves " + json.dumps({
                    "kind": "program", "seed": seed, "what": what,
                    "worst": D.leaf_gaps(prog[seed], ref, what)}),
                    flush=True)
            for kind, kw in kinds:
                got = D.reference_first_steps(fam, cfg, traffic, seed, ring,
                                              **kw)
                nums = {k: v[0] for k, v in D.compare(got, ref).items()}
                say(kind, seed, nums)
                through_limits(cell, kind, seed, nums)
                out.setdefault(kind, []).append(nums)
                if kind == "control":
                    for what in ("grad", "delta"):
                        print("leaves " + json.dumps({
                            "kind": kind, "seed": seed, "what": what,
                            "worst": D.leaf_gaps(got, ref, what)}),
                            flush=True)
        del ring
    return out


def serve(cell, seeds, control_seeds, seconds):
    import mxnet_tpu as mx
    from benchmark import generate as G
    from benchmark import trace as T
    from benchmark.drivers import serve as D
    fam, cfg, traffic = cell["family"], cell["cfg"], cell["traffic"]
    control = cell["limits"].get("control_precision", "fp8")
    out = {"program": [], "control": [], "altered": []}
    for seed in seeds:
        engine, _ = D.build_engine(mx, fam, cfg, traffic, seed)
        reqs = G.requests(traffic, cfg["vocab_size"], seed, seconds)
        recs, c = D.open_loop(engine, reqs, seconds, T.null_annotation,
                              mx.telemetry)
        picked = D.sample(recs, seed, traffic["check_requests"])
        ttft, tpot, wait, _ = D.latencies(recs, c["t0"],
                                          (seconds + D.DRAIN_S) * 1e3)
        print("tails " + json.dumps({
            "seed": seed, "seconds": seconds, "requests": len(recs),
            "tok_per_s": c["tokens_in_window"] / c["closed_at"],
            "ttft_p50_ms": float(sorted(ttft)[len(ttft) // 2]),
            "ttft_p90_ms": H.p90(ttft), "tpot_p90_ms": H.p90(tpot),
            "queue_wait_p90_ms": H.p90(wait),
            "mean_live_slots": c["live_slots"] / max(1, c["rounds"]),
            "mean_live_rows": c["live_rows"] / max(1, c["rounds"]),
            "peak_live_rows": c["peak_rows"]}), flush=True)
        engine.close()
        del engine
        gc.collect()
        ends = seed in control_seeds
        got = D.judge(cell, seed, picked, control if ends else None,
                      altered=ends)
        nums = {k: got[k] for k in D.GAP_NUMBERS}
        say("program", seed, dict(nums, tokens=got["tokens"],
                                  requests=len(recs)))
        through_limits(cell, "program", seed, nums)
        out["program"].append(nums)
        # the two upper ends: the control's tokens, and the served ones
        # with one of them altered (drivers/serve.altered_token_gaps)
        for kind in ("control", "altered"):
            if got.get(kind):
                say(kind, seed, dict(got[kind], tokens=got["tokens"]))
                nums = {k: got[kind][k] for k in D.GAP_NUMBERS}
                through_limits(cell, kind, seed, nums)
                out[kind].append(nums)
    return out


def sweep(cell, plan):
    """The knee sweep of a fixed-rate cell, and the spread of its tails
    from seed to seed: the same mix through each (rate, seconds, seed) of
    ``plan`` in one process, on one set-up (the first seed's weights: the
    times do not depend on their values)."""
    import mxnet_tpu as mx
    from benchmark import generate as G
    from benchmark import trace as T
    from benchmark.drivers import serve as D
    fam, cfg, traffic = cell["family"], cell["cfg"], cell["traffic"]
    engine, _ = D.build_engine(mx, fam, cfg, traffic, plan[0][2])
    for rate, seconds, seed in plan:
        t = dict(traffic, rate_per_s=rate)
        reqs = G.requests(t, cfg["vocab_size"], seed, seconds)
        recs, c = D.open_loop(engine, reqs, seconds, T.null_annotation,
                              mx.telemetry)
        ttft, tpot, wait, late = D.latencies(recs, c["t0"],
                                             (seconds + D.DRAIN_S) * 1e3)
        half = len(wait) // 2
        print("sweep " + json.dumps({
            "rate_per_s": rate, "seconds": seconds, "seed": seed,
            "requests": len(recs),
            "offered_tok_per_s": sum(r.max_tokens for r in recs) / seconds,
            "tok_per_s": c["tokens_in_window"] / c["closed_at"],
            "ttft_p50_ms": float(sorted(ttft)[len(ttft) // 2]),
            "ttft_p90_ms": H.p90(ttft), "tpot_p90_ms": H.p90(tpot),
            "queue_wait_p90_ms": H.p90(wait),
            "queue_wait_p90_first_half_ms": H.p90(wait[:half]),
            "queue_wait_p90_second_half_ms": H.p90(wait[half:]),
            "lateness_p90_ms": H.p90(late),
            "refused": sum(r.refused for r in recs),
            "rounds": c["rounds"],
            "mean_live_slots": c["live_slots"] / max(1, c["rounds"]),
            "mean_live_rows": c["live_rows"] / max(1, c["rounds"]),
            "peak_live_rows": c["peak_rows"]}),
            flush=True)
    engine.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--faults", default=None,
                    help="training cells: the planted faults to read "
                    "(default: all of drivers/train.FAULTS)")
    ap.add_argument("--rates", default="",
                    help="serving cells: sweep these rates (requests/s) "
                    "on the first seed and exit")
    ap.add_argument("--plan", default="",
                    help="serving cells: rate:seconds:seed,... to run after "
                    "the sweep in the same process (the spread of the tails "
                    "from seed to seed at one rate and window)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    cell = H.load_cell(args.workload)
    H.require_device(cell["cell"]["chips"])
    H.enable_compile_cache()
    if args.rates or args.plan:
        plan = [(float(r), args.seconds, seeds[0])
                for r in args.rates.split(",") if r]
        for item in args.plan.split(","):
            if item:
                rate, secs, seed = item.split(":")
                plan.append((float(rate), float(secs), int(seed)))
        sweep(cell, plan)
        return 0
    if cell["traffic"]["driver"] == "train":
        out = train(cell, seeds, control,
                    None if args.faults is None else args.faults.split(","))
    else:
        out = serve(cell, seeds, control, args.seconds)
    for name in sorted({k for rows in out.values() for r in rows
                        for k in r}):
        line = {"number": name}
        if out["program"]:
            line["lower"] = max(r[name] for r in out["program"])
        for kind in out:
            if kind != "program" and out[kind]:
                line[kind + "_min"] = min(r[name] for r in out[kind])
        print("summary " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
