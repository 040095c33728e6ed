#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print the result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process per run. It measures on the accelerator it is started on
or exits non-zero without a result: there is no CPU fallback. Set-up (the
clock starts before the first import) covers imports, weights made on the
device from ``--seed``, compiles or cache reads and the warm-up of the
cell's own shapes; then the window of ``--seconds`` is measured; then the
timed path's output is held to the family's plain reference. The last line
of standard output is one JSON object (``harness.result_line``); the
numbers compared, each beside its limit, are also the last lines of
standard error.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_T0 = time.perf_counter()      # set-up starts here, before any heavy import

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness as H  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                    "run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, rctx):
    """Every per-layer metric of this cell through its own reader. A
    reader that finds nothing to read returns None, and the metric is
    left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        value = H.load_module("metrics", m["name"]).read(rctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    clock = H.Stopwatch(_T0)
    cell = H.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell["run_seconds"])
    devices, peaks = H.require_device(cell["cell"]["chips"])
    cache = H.enable_compile_cache()
    driver = H.load_module("drivers", cell["traffic"]["driver"])
    print("benchmark: %s seed=%d seconds=%g trace=%d device=%s x%d "
          "compile_cache=%s" % (args.workload, args.seed, args.seconds,
                                args.trace, devices[0].device_kind,
                                len(devices), cache), flush=True)
    with H.CompileCounter() as counter:
        ctx = {"cell": cell, "args": args, "devices": devices,
               "peaks": peaks, "clock": clock, "counter": counter,
               "trace_dir": os.path.join(H.ROOT, ".cache", "trace")}
        res = driver.run(ctx)
        print("benchmark: backend_compiles=%d compile_s=%.1f cache_hits=%d"
              % (counter.compiles, counter.compile_s, counter.cache_hits),
              flush=True)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    breakdown = None
    if args.trace:
        from benchmark import trace as T
        cap = res.get("capture")
        reduced = T.reduce(cap.path) if cap is not None and cap.path \
            else None
        if reduced is None or reduced["busy_s"] <= 0:
            raise SystemExit("benchmark: the traced window holds no "
                             "device operation")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]
        rctx = {"cell": cell, "cfg": cell["cfg"],
                "traffic": cell["traffic"], "family": cell["family"],
                "peaks": peaks, "chips": len(devices),
                "e2e": res["end_to_end"], "spans": res["spans"],
                "trace": reduced}
        metrics = per_layer(cell, rctx)
    else:
        metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    checks = res["checks"]
    line = H.result_line(checks, res["attempted"], res["failed"], metrics,
                         device, breakdown)
    sys.stdout.flush()
    checks.print(sys.stderr)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
