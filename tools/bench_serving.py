"""Serving-engine sweeps: slot counts x arrival rates, and (ISSUE 5)
prefix-cache hit-rate x prefill-chunk size.

Drives ``bench.bench_serving`` (the continuous-batching engine under
Poisson arrivals with mixed prompt/output lengths) over a grid of
``slots`` and mean interarrival times, with the same spread-reporting
discipline as bench_decode: each cell runs ``--reps`` times, reports
the MEDIAN tokens/s and the relative spread ``(max-min)/median`` —
a cell whose spread exceeds ~0.2 is dispatch-jitter, not signal.

``--hit-rates``/``--chunk-sizes`` add a second grid over
``bench.bench_serving_prefix`` (shared-system-prompt workload): each
cell serves the same request stream with the given fraction sharing a
192-token system prefix and the given ``prefill_chunk`` (0 = off),
reporting p50 TTFT, cadence p99, tokens/s and hit tokens — the
hit-rate axis shows where prefix-copy reuse starts paying off over
re-prefilling, the chunk axis what bounding decode stalls costs in
throughput. ``--no-prefix-sweep`` skips it.

``--weight-dtypes float int8 int4`` adds one cell per weight storage
dtype (ISSUE 15/17): float weights vs int8 + per-output-channel scales
vs int4 packed nibbles + per-group scales, same stream per seed — each
cell reports tokens/s, cadence p50/p99, stored ``weight_bytes`` and
the decode program's ``bytes_accessed`` per dispatch (the
weight-stream cut — at serving batch the weights, not the KV, dominate
decode bytes; doc/serving.md "Quantized weights").

``--matmul-impls dense pallas`` adds one cell per quantized matmul
lowering (PR 17) with int8 weights pinned: the chunked host-level
fori loop vs the Pallas ``quant_matmul`` kernel (dequant-in-VMEM;
doc/serving.md "Fused quantized kernels").

``--tps 1 2 4`` adds a tensor-parallel sweep over
``bench.bench_serving_tp`` (ISSUE 14): one cell per degree on the
SAME stream/seed — greedy outputs are byte-identical across degrees
by the engine contract (digest-asserted), so the cells differ only in
tokens/s, cadence p99 and the PER-SHARD decode ``bytes_accessed``
(the sharded program's cost analysis carries local shapes — the
memory-traffic cut is the multi-chip win condition; CPU wall clock
pays collective overhead an ICI-attached chip amortizes). ``--heads``
must divide every swept degree.

``--spec-ks`` adds a third sweep over ``bench.bench_serving_spec``
(repetition-friendly few-shot-style workload): one cell per draft
length K (0 = speculation off), same stream per seed, reporting
tokens/s, cadence p99 and accepted tokens per target-model step —
where the accept rate holds, tokens/s climbs with K at FLAT or better
p99 (the draft-and-verify win); where drafts stop being accepted the
wasted chunk width shows up as tokens/s falling below the K=0 cell.
``--no-spec-sweep`` skips it.

Run from the repo root::

    python tools/bench_serving.py                      # 124M, chip
    python tools/bench_serving.py --layers 2 --embed 64 \
        --heads 2 --vocab 256 --max-len 256 --requests 24   # smoke/CPU

Prints one JSON dict::

  {"s<slots>_a<arrival_ms>": {"tokens_per_sec": median over reps,
                              "spread": (max-min)/median,
                              "p50_ms_per_token": ..., "p99_ms_per_token": ...,
                              "compile_programs": ...},
   "h<hit_rate>_c<chunk>": {"ttft_p50_ms": ..., "cadence_p99_ms": ...,
                            "tokens_per_sec": ..., "prefix_hit_tokens": ...},
   ..., "config": {...}}

The slot sweep is the capacity knob (decode cost per step is nearly
flat until the chip saturates, so tokens/s should climb with slots);
the arrival sweep shows the latency/throughput trade: saturating rates
maximize tokens/s, sub-saturating rates buy back p99 decode cadence.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--arrival-ms", type=float, nargs="+",
                    default=[1.0, 20.0],
                    help="mean Poisson interarrival per rate arm "
                         "(1 ms saturates; larger trades throughput "
                         "for tail latency)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--embed", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--hit-rates", type=float, nargs="+",
                    default=[0.0, 0.5, 0.9],
                    help="prefix-sweep axis: fraction of requests "
                         "sharing the system prompt")
    ap.add_argument("--chunk-sizes", type=int, nargs="+",
                    default=[0, 128],
                    help="prefix-sweep axis: prefill_chunk per cell "
                         "(0 = monolithic prefill)")
    ap.add_argument("--prefix-requests", type=int, default=48,
                    help="requests per prefix-sweep cell")
    ap.add_argument("--no-prefix-sweep", action="store_true")
    ap.add_argument("--spec-ks", type=int, nargs="+", default=[0, 4, 8],
                    help="speculation sweep axis: draft length per "
                         "cell (0 = spec off); n-gram drafting on a "
                         "repetition-friendly workload")
    ap.add_argument("--spec-requests", type=int, default=32,
                    help="requests per speculation-sweep cell")
    ap.add_argument("--no-spec-sweep", action="store_true")
    ap.add_argument("--tps", type=int, nargs="+", default=[],
                    help="tensor-parallel sweep axis (e.g. 1 2 4): "
                         "one bench_serving_tp cell per degree — KV "
                         "cache + programs sharded over the mesh's "
                         "model axis; outputs digest-asserted "
                         "byte-identical across cells; reports "
                         "per-shard decode bytes_accessed. Needs that "
                         "many devices (CPU smoke: export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--weight-dtypes", nargs="+", default=[],
                    choices=("float", "int8", "int4"),
                    help="weight-storage sweep axis (e.g. float "
                         "int8 int4): one bench_serving cell per "
                         "dtype at the first slots/arrival setting — "
                         "int8 = per-output-channel quantized weights "
                         "with chunked scale-fused dequant "
                         "in-program, int4 = packed nibbles + "
                         "per-group scales; cells report tokens/s, "
                         "cadence p50/p99, stored weight bytes, and "
                         "the decode program's bytes_accessed per "
                         "dispatch (the weight-stream cut)")
    ap.add_argument("--matmul-impls", nargs="+", default=[],
                    choices=("dense", "pallas"),
                    help="quantized-matmul impl sweep axis (PR 17): "
                         "one bench_serving cell per impl at the "
                         "first slots/arrival setting, int8 weights "
                         "pinned so the cells compare like-for-like "
                         "— dense = the chunked host-level fori "
                         "loop, pallas = the quant_matmul kernel "
                         "(dequant-in-VMEM)")
    args = ap.parse_args()

    import bench
    from mxnet_tpu import compile_cache

    bench._require_tpu()          # rates from a CPU run are not rates
    compile_cache.enable()

    out = {"config": {"layers": args.layers, "embed": args.embed,
                      "heads": args.heads, "vocab": args.vocab,
                      "max_len": args.max_len,
                      "requests": args.requests, "reps": args.reps}}
    for slots in args.slots:
        for arrival in args.arrival_ms:
            reps = []
            for rep in range(args.reps):
                # fresh seed per rep
                reps.append(bench.bench_serving(
                    slots=slots, layers=args.layers, embed=args.embed,
                    heads=args.heads, vocab=args.vocab,
                    max_len=args.max_len, n_requests=args.requests,
                    seed=17 * rep + 3, arrival_ms=arrival))
            tps = sorted(r["tokens_per_sec"] for r in reps)
            med = tps[len(tps) // 2]
            cell = {
                "tokens_per_sec": med,
                "spread": None if med == 0
                else round((tps[-1] - tps[0]) / med, 3),
                "p50_ms_per_token": float(np.median(
                    [r["p50_ms_per_token"] for r in reps])),
                "p99_ms_per_token": float(np.median(
                    [r["p99_ms_per_token"] for r in reps])),
                "compile_programs": reps[0]["compile_programs"],
            }
            out["s%d_a%g" % (slots, arrival)] = cell
            print("s%d_a%g: %r" % (slots, arrival, cell),
                  file=sys.stderr)

    # hit-rate x chunk-size grid over the shared-system-prompt arm:
    # one engine config per cell (cache ON; chunk as given), same
    # request stream per seed so cells are comparable
    if not args.no_prefix_sweep:
        # geometry scales with max_len so smoke configs stay valid
        # (chunk included: a chunk past the largest bucket is rejected
        # by the engine, and the largest bench bucket is <= max_len)
        shared = min(192, args.max_len // 4)
        long_len = min(512, args.max_len // 2)
        seen = set()
        for hr in args.hit_rates:
            for chunk in args.chunk_sizes:
                chunk = min(chunk, args.max_len // 2)
                if (hr, chunk) in seen:
                    continue
                seen.add((hr, chunk))
                r = bench.bench_serving_prefix(
                    slots=max(args.slots[0], 2), layers=args.layers,
                    embed=args.embed, heads=args.heads,
                    vocab=args.vocab, max_len=args.max_len,
                    n_requests=args.prefix_requests, hit_rate=hr,
                    shared_len=shared, tail_len=max(8, shared // 6),
                    long_len=long_len, chunk=chunk, seed=11)
                cell = {k: r[k] for k in
                        ("ttft_p50_ms", "cadence_p99_ms",
                         "tokens_per_sec", "prefix_hit_tokens",
                         "prefill_chunks", "compile_programs")}
                out["h%g_c%d" % (hr, chunk)] = cell
                print("h%g_c%d: %r" % (hr, chunk, cell),
                      file=sys.stderr)
    # speculation sweep: spec-off vs n-gram drafting at each K on the
    # SAME repetition-friendly stream (byte-identical outputs across
    # cells — only tokens-per-dispatch changes)
    if not args.no_spec_sweep:
        for k in args.spec_ks:
            r = bench.bench_serving_spec(
                slots=max(args.slots[0], 2), layers=args.layers,
                embed=args.embed, heads=args.heads, vocab=args.vocab,
                max_len=args.max_len, n_requests=args.spec_requests,
                spec_k=k, seed=7)
            cell = {key: r[key] for key in
                    ("tokens_per_sec", "cadence_p50_ms",
                     "cadence_p99_ms", "accept_per_step",
                     "accept_rate", "fallback_rounds",
                     "compile_programs")}
            out["spec_k%d" % k] = cell
            print("spec_k%d: %r" % (k, cell), file=sys.stderr)
    # weight-dtype sweep (ISSUE 15): float vs int8 weights on the
    # same stream/seed — bytes_accessed and weight_bytes are the
    # traffic/footprint cuts (the honest CPU metrics; the chunked
    # dequant loop serializes work the chip overlaps)
    for wd in args.weight_dtypes:
        r = bench.bench_serving(
            slots=args.slots[0], layers=args.layers, embed=args.embed,
            heads=args.heads, vocab=args.vocab, max_len=args.max_len,
            n_requests=args.requests, seed=3,
            arrival_ms=args.arrival_ms[0], weight_dtype=wd)
        cell = {k: r[k] for k in
                ("tokens_per_sec", "p50_ms_per_token",
                 "p99_ms_per_token", "decode_bytes_accessed",
                 "weight_bytes", "compile_programs")}
        out["weights_%s" % wd] = cell
        print("weights_%s: %r" % (wd, cell), file=sys.stderr)
    # quantized-matmul impl sweep (PR 17): dense fori vs the Pallas
    # quant_matmul kernel, int8 weights pinned so cells differ only in
    # the matmul lowering
    for mi in args.matmul_impls:
        r = bench.bench_serving(
            slots=args.slots[0], layers=args.layers, embed=args.embed,
            heads=args.heads, vocab=args.vocab, max_len=args.max_len,
            n_requests=args.requests, seed=3,
            arrival_ms=args.arrival_ms[0], weight_dtype="int8",
            matmul_impl=mi)
        cell = {k: r[k] for k in
                ("tokens_per_sec", "p50_ms_per_token",
                 "p99_ms_per_token", "decode_bytes_accessed",
                 "weight_bytes", "compile_programs")}
        out["matmul_%s" % mi] = cell
        print("matmul_%s: %r" % (mi, cell), file=sys.stderr)
    # tensor-parallel sweep (ISSUE 14): same stream/seed per degree,
    # byte-identity digest-asserted across cells before any number is
    # trusted; bytes_accessed is PER SHARD (the multi-chip cut)
    digests = {}
    for tpd in args.tps:
        r = bench.bench_serving_tp(
            tp=tpd, slots=args.slots[0], layers=args.layers,
            embed=args.embed, heads=args.heads, vocab=args.vocab,
            max_len=args.max_len, n_requests=args.requests, seed=3)
        digests[tpd] = r.pop("digest")
        cell = {k: r[k] for k in
                ("tokens_per_sec", "p50_ms_per_token",
                 "p99_ms_per_token", "decode_bytes_accessed_per_shard",
                 "kv_bytes_per_shard")}
        out["tp%d" % tpd] = cell
        print("tp%d: %r" % (tpd, cell), file=sys.stderr)
    if digests:
        assert len(set(digests.values())) == 1, \
            "tp sweep outputs diverged: %r" % (digests,)
    print(json.dumps(out, sort_keys=True))


if __name__ == "__main__":
    main()
