#!/usr/bin/env python
"""Inspect telemetry artifacts offline: pretty-print a snapshot JSON
(what ``mx.telemetry.snapshot()`` returns — e.g. the ``telemetry``
block bench.py writes into BENCH_extra.json) or summarize a Chrome
``trace_event`` file captured via ``MXNET_TRACE_DIR``.

Usage::

    python tools/dump_telemetry.py BENCH_extra.json      # snapshot tree
    python tools/dump_telemetry.py /tmp/tr/mx_trace_1.json  # trace table
    python tools/dump_telemetry.py trace.json --names io. train.
    python tools/dump_telemetry.py BENCH_extra.json --serving
    python tools/dump_telemetry.py BENCH_extra.json --fleet
    python tools/dump_telemetry.py --url http://host:9100   # live server
    python tools/dump_telemetry.py --url http://host:9100 --watch 2
    python tools/dump_telemetry.py --url http://host:9100 --fleet --trace f3

``--url`` reads a LIVE process instead of a file: it fetches
``/snapshot`` from the exposition server ``mx.telemetry.serve`` /
``MXNET_TELEMETRY_PORT`` started (doc/observability.md) — every
snapshot view (``--serving`` included) works unchanged. ``--watch N``
re-reads and re-prints the source every N seconds until interrupted —
a poor man's dashboard for a serving box.

The file kind is auto-detected (a trace has a ``traceEvents`` list).
Snapshot histograms print as one ``count/mean/p50/p99 [min..max]``
line; traces print a per-span-name table (count, total/mean/max ms)
plus instant-event counts — the quick "where did the time go" read
for benchmark and fault-injection runs without opening Perfetto.

``--serving`` narrows to the serving engine: request latencies (queue
wait / TTFT / token cadence) tabulated NEXT TO the prefix-cache and
chunked-prefill stats that explain them (hit tokens saved, lookup
cost, chunks per request, pool bytes, compile counts) — the one-look
answer to "did the cache/chunking actually move TTFT and p99". On a
trace file it filters to ``serving.`` spans. Since ISSUE 13 it also
prints the round-phase breakdown (``serving.round_phase_ms.*`` —
drain / prefill / dispatch / host-sched shares of the round wall
time) and the traffic-capture counters.

``--fleet`` narrows to the FleetRouter's counters (``fleet.*`` —
doc/fault_tolerance.md "Fleet resilience"): live replicas, failovers,
drains, migrated requests, channel retries, dedup hits, heartbeat
misses, and affinity placements — the one-look answer to "did the
fleet actually fail anything over, and did placement keep prefixes
warm". ``--fleet --trace <id>`` instead prints one request's STITCHED
cross-replica journey — router, wire, and per-engine flight events on
one clock plus the end-to-end SLO decomposition — fetched from
``/fleet/flight/<id>`` with ``--url`` (or a saved timeline JSON);
``--watch`` composes, re-printing a live journey as it unfolds.
"""
from __future__ import annotations

import argparse
import json
import sys


def _fmt_hist(d):
    return ("count=%d mean=%.3g p50=%s p99=%s [%.3g..%.3g] sum=%.6g"
            % (d["count"], d.get("mean", 0), d.get("p50"), d.get("p99"),
               d.get("min", 0), d.get("max", 0), d.get("sum", 0)))


def _is_histogram(v):
    return isinstance(v, dict) and "count" in v and (
        "buckets" in v or set(v) == {"count"})


def print_snapshot(snap, indent=0, out=None):
    out = out or sys.stdout
    pad = "  " * indent
    for key in sorted(snap):
        v = snap[key]
        if _is_histogram(v):
            if v["count"]:
                out.write("%s%-28s %s\n" % (pad, key, _fmt_hist(v)))
            else:
                out.write("%s%-28s (empty)\n" % (pad, key))
        elif isinstance(v, dict):
            out.write("%s%s:\n" % (pad, key))
            print_snapshot(v, indent + 1, out)
        elif isinstance(v, float):
            out.write("%s%-28s %.6g\n" % (pad, key, v))
        else:
            out.write("%s%-28s %s\n" % (pad, key, v))


def print_serving(snap, out=None):
    """Serving-focused table: per-request latency histograms beside
    the prefix/chunk stats (doc/serving.md "Measuring it")."""
    out = out or sys.stdout
    s = snap.get("serving")
    if not isinstance(s, dict) or not s:
        out.write("(no serving metrics in this snapshot)\n")
        return
    hits = s.get("prefix_hits", 0)
    misses = s.get("prefix_misses", 0)
    out.write("serving requests: completed=%s tokens=%s "
              "retired_eos=%s retired_length=%s\n"
              % (s.get("completed", 0), s.get("tokens", 0),
                 s.get("retired_eos", 0), s.get("retired_length", 0)))
    out.write("prefix cache:     hits=%d misses=%d hit_rate=%s "
              "hit_tokens=%s bytes=%s evictions=%s skipped=%s\n"
              % (hits, misses,
                 "n/a" if not hits + misses
                 else "%.2f" % (hits / float(hits + misses)),
                 s.get("prefix_hit_tokens", 0),
                 s.get("prefix_cache_bytes", 0),
                 s.get("prefix_evictions", 0),
                 s.get("prefix_insert_skipped", 0)))
    out.write("robustness:       shed=%s deadline_missed=%s "
              "cancelled=%s errors=%s watchdog_trips=%s restores=%s\n"
              % (s.get("shed", 0), s.get("deadline_missed", 0),
                 s.get("cancelled", 0), s.get("request_errors", 0),
                 s.get("watchdog_trips", 0), s.get("restores", 0)))
    drafted = s.get("spec_drafted_tokens", 0)
    if s.get("spec_rounds", 0) or drafted:
        accepted = s.get("spec_accepted_tokens", 0)
        out.write("speculation:      rounds=%s fallback_rounds=%s "
                  "drafted=%s accepted=%s accept_rate=%s "
                  "sources ngram=%s model=%s\n"
                  % (s.get("spec_rounds", 0),
                     s.get("spec_fallback_rounds", 0), drafted,
                     accepted,
                     "n/a" if not drafted
                     else "%.2f" % (accepted / float(drafted)),
                     s.get("spec_drafts_ngram", 0),
                     s.get("spec_drafts_model", 0)))
    if s.get("slo_ttft_attained", 0) or s.get("slo_ttft_missed", 0) \
            or s.get("slo_cadence_attained", 0) \
            or s.get("slo_cadence_missed", 0):
        out.write("slo:              ttft attained=%s missed=%s "
                  "burn(1m/5m/1h)=%s/%s/%s\n"
                  "                  cadence attained=%s missed=%s "
                  "burn(1m/5m/1h)=%s/%s/%s\n"
                  % (s.get("slo_ttft_attained", 0),
                     s.get("slo_ttft_missed", 0),
                     s.get("slo_ttft_burn_1m", 0),
                     s.get("slo_ttft_burn_5m", 0),
                     s.get("slo_ttft_burn_1h", 0),
                     s.get("slo_cadence_attained", 0),
                     s.get("slo_cadence_missed", 0),
                     s.get("slo_cadence_burn_1m", 0),
                     s.get("slo_cadence_burn_5m", 0),
                     s.get("slo_cadence_burn_1h", 0)))
    # tensor-parallel sharding (ISSUE 14): degree + per-shard KV
    # residency (the multi-chip win condition — decode is
    # memory-bound, so each chip's cache slice is what scales down);
    # the axis is always the mesh's "model" axis
    tpd = s.get("tp_degree")
    if tpd and int(tpd) > 1:     # tp=1 engines have no mesh/axis
        out.write("sharding:         axis=model tp=%d "
                  "kv_bytes_per_shard=%s\n"
                  % (int(tpd),
                     "n/a" if s.get("kv_bytes_per_shard") is None
                     else "%d" % s["kv_bytes_per_shard"]))
    # weight quantization (ISSUE 15): storage dtype + the engine's
    # total stored weight bytes — the serving-batch bytes/token lever
    # (doc/serving.md "Quantized weights")
    wd = s.get("weight_dtype")
    if wd is not None:
        out.write("quantization:     weights=%s weight_bytes=%s\n"
                  % ("int8" if wd else "float",
                     "n/a" if s.get("weight_bytes") is None
                     else "%d" % s["weight_bytes"]))
    # decode memory traffic: the PR 9 program gauges give the decode
    # program's bytes per dispatched round, and tokens/rounds
    # approximates tokens per dispatch — their quotient is the
    # ~bytes/token the bounded read exists to cut
    prog = snap.get("program") if isinstance(snap, dict) else None
    decp = (prog or {}).get("serving_decode", {})
    ba = decp.get("bytes_accessed")
    if ba is not None:
        rounds = s.get("rounds", 0)
        toks = s.get("tokens", 0)
        per_tok = ("%.3g" % (ba * rounds / toks)
                   if ba and rounds and toks else "n/a")
        out.write("attention:        decode bytes_accessed=%.6g"
                  "/dispatch ~%s/token\n" % (ba, per_tok))
    if s.get("capture_records", 0) or s.get("capture_skipped", 0):
        out.write("capture:          records=%s skipped=%s bytes=%s\n"
                  % (s.get("capture_records", 0),
                     s.get("capture_skipped", 0),
                     s.get("capture_bytes", 0)))
    # disaggregated prefill/decode (ISSUE 18): the engine's role and
    # how long finished prefills waited in the router's transit queue
    # before a decode slot took them (doc/serving.md "Disaggregated
    # prefill/decode") — a growing wait says decode capacity, not
    # prefill, is the bottleneck
    role = s.get("role")
    wait = s.get("handoff_wait_ms")
    wait_live = _is_histogram(wait) and wait["count"]
    if (role is not None and int(role)) or wait_live:
        out.write("disaggregation:   role=%s handoff_wait_ms=%s\n"
                  % ({0: "unified", 1: "prefill", 2: "decode"}.get(
                      int(role or 0), "?"),
                     _fmt_hist(wait) if wait_live else "(empty)"))
    out.write("compiles:         decode=%s prefill=%s copy=%s "
              "handoff=%s\n"
              % (s.get("compiles_decode", 0),
                 s.get("compiles_prefill", 0),
                 s.get("compiles_copy", 0),
                 s.get("compiles_handoff", 0)))
    # round-phase breakdown (ISSUE 13): where a scheduling round's
    # wall time went, as total-ms shares — the one-look answer to
    # "is the engine device-bound or stuck in host scheduling"
    phases = s.get("round_phase_ms")
    if isinstance(phases, dict) and any(
            _is_histogram(v) and v["count"] for v in phases.values()):
        total = sum(v.get("sum", 0) for v in phases.values()
                    if _is_histogram(v))
        out.write("\n%-16s %8s %12s %10s %10s %7s\n"
                  % ("round phase", "rounds", "total_ms", "mean_ms",
                     "p99_ms", "share"))
        for name in sorted(phases,
                           key=lambda n: -(phases[n].get("sum", 0)
                                           if _is_histogram(phases[n])
                                           else 0)):
            v = phases[name]
            if not _is_histogram(v) or not v["count"]:
                continue
            out.write("%-16s %8d %12.3f %10.4f %10.4f %6.1f%%\n"
                      % (name, v["count"], v["sum"],
                         v["sum"] / v["count"], v.get("p99") or 0,
                         100.0 * v["sum"] / total if total else 0))
        wall = s.get("round_wall_ms")
        if _is_histogram(wall) and wall["count"]:
            out.write("%-16s %8d %12.3f %10.4f %10.4f\n"
                      % ("(round wall)", wall["count"], wall["sum"],
                         wall["sum"] / wall["count"],
                         wall.get("p99") or 0))
    out.write("\n%-28s %s\n" % ("per-request", "distribution"))
    for key in ("queue_wait_ms", "ttft_ms", "token_cadence_ms",
                "prefix_lookup_ms", "prefill_chunks_per_request",
                "spec_accepted_per_step",
                "admitted_per_round", "slots_busy_per_round"):
        v = s.get(key)
        if _is_histogram(v):
            out.write("%-28s %s\n"
                      % (key, _fmt_hist(v) if v["count"] else "(empty)"))


def print_fleet(snap, out=None):
    """Fleet-router view: the resilience counters on one line each —
    what a post-incident (or post-drill) read needs first."""
    out = out or sys.stdout
    s = snap.get("fleet")
    if not isinstance(s, dict) or not s:
        out.write("(no fleet metrics in this snapshot)\n")
        return
    out.write("fleet replicas:   live=%s\n"
              % int(s.get("replicas_live", 0)))
    out.write("resilience:       failovers=%s drains=%s "
              "migrated_requests=%s\n"
              % (s.get("failovers", 0), s.get("drains", 0),
                 s.get("migrated_requests", 0)))
    out.write("channel:          retries=%s dedup_hits=%s "
              "heartbeat_misses=%s\n"
              % (s.get("retries", 0), s.get("dedup_hits", 0),
                 s.get("heartbeat_misses", 0)))
    out.write("placement:        affinity_hits=%s\n"
              % s.get("affinity_hits", 0))
    # KV handoff (disaggregated prefill/decode — ISSUE 18): volume,
    # bytes actually shipped (pool hits ship none), and per-delivery
    # admit latency
    hms = s.get("handoff_ms")
    hms_live = _is_histogram(hms) and hms["count"]
    if s.get("handoff_count", 0) or hms_live:
        out.write("handoff:          count=%s bytes=%s ms=%s\n"
                  % (int(s.get("handoff_count", 0)),
                     int(s.get("handoff_bytes", 0)),
                     _fmt_hist(hms) if hms_live else "(empty)"))


def print_fleet_trace(tl, out=None):
    """One stitched cross-replica journey (``/fleet/flight/<id>``):
    the ordered event timeline with the scope that recorded each one,
    then the SLO decomposition — the components sum to the end-to-end
    wall time by construction, so the table reads as "where the
    request's life went"."""
    out = out or sys.stdout
    out.write("trace %s  %s" % (tl.get("id"),
                                "LIVE" if tl.get("live")
                                else "retired(%s)"
                                % tl.get("meta", {}).get(
                                    "retire_reason")))
    hops = tl.get("hops") or []
    if hops:
        out.write("  hops: %s" % " -> ".join(str(h) for h in hops))
    out.write("\n")
    if tl.get("dropped_events"):
        out.write("WARNING: %d events dropped at the per-request cap\n"
                  % tl["dropped_events"])
    out.write("%10s  %-14s %-16s %s\n"
              % ("t_ms", "scope", "event", "detail"))
    for ev in tl.get("events", ()):
        detail = " ".join(
            "%s=%s" % (k, v) for k, v in ev.items()
            if k not in ("t_ms", "scope", "event", "slo"))
        out.write("%10.3f  %-14s %-16s %s\n"
                  % (ev.get("t_ms", 0), ev.get("scope", "?"),
                     ev.get("event", "?"), detail))
    slo = tl.get("meta", {}).get("slo")
    if slo:
        out.write("\nslo decomposition (sums to e2e):\n")
        for comp in ("router_queue", "prefill", "handoff_wait",
                     "decode_admission", "decode"):
            if comp in slo:
                out.write("  %-18s %10.3f ms\n" % (comp, slo[comp]))
        for total in ("e2e_ms", "ttft_ms", "cadence_ms"):
            if total in slo:
                out.write("  %-18s %10.3f ms\n" % (total, slo[total]))


def print_trace(doc, name_filters=(), out=None):
    out = out or sys.stdout
    evs = doc.get("traceEvents", [])
    spans, instants = {}, {}
    for e in evs:
        name = e.get("name", "?")
        if name_filters and not any(name.startswith(f)
                                    for f in name_filters):
            continue
        if e.get("ph") == "X":
            agg = spans.setdefault(name, [0, 0.0, 0.0])  # n, sum, max
            dur_ms = e.get("dur", 0) / 1e3
            agg[0] += 1
            agg[1] += dur_ms
            agg[2] = max(agg[2], dur_ms)
        elif e.get("ph") == "i":
            instants[name] = instants.get(name, 0) + 1
    out.write("%d trace events\n" % len(evs))
    if doc.get("mxnetDroppedEvents"):
        out.write("WARNING: %d events dropped at the buffer cap\n"
                  % doc["mxnetDroppedEvents"])
    if spans:
        out.write("\n%-28s %8s %12s %10s %10s\n"
                  % ("span", "count", "total_ms", "mean_ms", "max_ms"))
        for name in sorted(spans, key=lambda n: -spans[n][1]):
            n, total, mx_ = spans[name]
            out.write("%-28s %8d %12.3f %10.3f %10.3f\n"
                      % (name, n, total, total / n, mx_))
    if instants:
        out.write("\n%-28s %8s\n" % ("instant event", "count"))
        for name in sorted(instants):
            out.write("%-28s %8d\n" % (name, instants[name]))


def _load(args):
    """One document from the configured source: a file path, or a
    live exposition server's ``/snapshot``."""
    if args.url:
        import urllib.request
        url = args.url.rstrip("/")
        if getattr(args, "trace", None):
            with urllib.request.urlopen(
                    "%s/fleet/flight/%s" % (url, args.trace),
                    timeout=10) as resp:
                return json.load(resp)
        last = url.rsplit("/", 1)[-1]
        if last == "metrics":
            # a copied Prometheus scrape URL: the text exposition is
            # not JSON — read the JSON twin instead
            url = url[:-len("metrics")] + "snapshot"
        elif last != "snapshot":
            url += "/snapshot"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.load(resp)
    with open(args.file) as f:
        return json.load(f)


def _print(doc, args, out=None):
    if getattr(args, "trace", None) or (
            isinstance(doc, dict) and "events" in doc and "id" in doc
            and "meta" in doc):
        # a stitched fleet journey (GET /fleet/flight/<id>, or the
        # same JSON saved to a file)
        print_fleet_trace(doc, out)
        return
    if isinstance(doc, dict) and isinstance(doc.get("traceEvents"),
                                            list):
        names = tuple(args.names)
        if args.serving:
            names += ("serving.",)
        if args.fleet:
            names += ("fleet.",)
        print_trace(doc, names, out)
        return
    # snapshot, possibly wrapped (BENCH_extra.json carries it under
    # the "telemetry" key)
    if isinstance(doc, dict) and "telemetry" in doc \
            and isinstance(doc["telemetry"], dict):
        doc = doc["telemetry"]
    if args.serving or args.fleet:
        if args.serving:
            print_serving(doc, out)
        if args.fleet:
            print_fleet(doc, out)
        return
    print_snapshot(doc, 0, out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Pretty-print a telemetry snapshot / summarize a "
                    "Chrome trace file (doc/observability.md)")
    ap.add_argument("file", nargs="?",
                    help="snapshot JSON or trace_event JSON")
    ap.add_argument("--url", default=None,
                    help="read a live /snapshot endpoint instead of a "
                         "file (mx.telemetry.serve / "
                         "MXNET_TELEMETRY_PORT server base URL)")
    ap.add_argument("--names", nargs="*", default=(),
                    help="only trace spans whose name starts with one "
                         "of these prefixes (e.g. --names io. train.)")
    ap.add_argument("--serving", action="store_true",
                    help="serving-engine view: request latency "
                         "histograms tabulated next to the prefix-"
                         "cache/chunked-prefill stats (snapshots), or "
                         "serving.* spans only (traces)")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet-router view: failover/drain/migration "
                         "and channel counters (fleet.* — "
                         "doc/fault_tolerance.md 'Fleet resilience'); "
                         "composes with --serving")
    ap.add_argument("--trace", default=None, metavar="ID",
                    help="print one request's stitched cross-replica "
                         "journey (fetched from /fleet/flight/<ID> "
                         "with --url, or a saved timeline JSON file); "
                         "--watch composes")
    ap.add_argument("--watch", type=float, default=None, metavar="SEC",
                    help="re-read and re-print the source every SEC "
                         "seconds until interrupted")
    ap.add_argument("--watch-count", type=int, default=None,
                    help=argparse.SUPPRESS)  # test hook: stop after N
    args = ap.parse_args(argv)
    if (args.file is None) == (args.url is None):
        ap.error("pass exactly one source: a file, or --url")
    if args.watch is None:
        _print(_load(args), args)
        return
    import time
    n = 0
    try:
        while args.watch_count is None or n < args.watch_count:
            if n:
                time.sleep(args.watch)
            sys.stdout.write("\x1b[2J\x1b[H" if sys.stdout.isatty()
                             else "--- refresh %d ---\n" % n)
            try:
                _print(_load(args), args)
            except Exception as e:   # noqa: BLE001 — keep watching
                print("(source unavailable: %s)" % e)
            sys.stdout.flush()
            n += 1
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
