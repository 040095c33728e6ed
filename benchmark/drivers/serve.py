"""The serving driver: an open loop at the rate fixed in the traffic file,
through ``InferenceEngine.submit`` / ``step`` on one thread, every latency
taken from the time a request was DUE; then a sample of what was served is
held to the family's plain reference.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import generate as G
from benchmark import harness as H

DRAIN_S = 60.0        # how long past the close an answer is waited for
PHASES = ("sched", "prefix_lookup", "h2d", "prefill", "copy", "dispatch",
          "drain")


# -- the program -----------------------------------------------------------------

def build_engine(mx, fam, cfg, traffic, seed):
    """(the engine the cell names, on the seed's weights, warmed; the
    bytes of those weights on the device)."""
    import jax.numpy as jnp
    sym = fam.build_symbol(mx, cfg, dict(traffic, loss_layout="reference"))
    weights = H.make_weights(fam.param_specs(cfg), seed,
                             jnp.dtype(cfg["compute_dtype"]))
    dec = mx.parallel.Decoder(sym, weights, max_len=traffic["max_len"],
                              compute_dtype=cfg["compute_dtype"],
                              weight_dtype="float")
    weight_bytes = sum(int(v.nbytes) for v in weights.values())
    del weights
    engine = mx.serving.InferenceEngine(
        dec, slots=traffic["slots"],
        prefill_buckets=tuple(traffic["prefill_buckets"]),
        steps_per_round=traffic["steps_per_round"],
        max_queue=traffic.get("max_queue", 256), **traffic["engine"])
    # warm the cell's own buckets and the decode round, and no others
    for prompt, n in G.warm_requests(traffic, cfg["vocab_size"], seed):
        engine.submit(prompt, max_tokens=n)
    engine.serve_forever()
    return engine, weight_bytes


def decode_temp_bytes(engine):
    """Temporaries of the compiled decode program, which the allocator's
    peak leaves out (as ``chip_smoke.decode_text`` lowers it; the compile
    is a cache read)."""
    compiled = engine._step_fn.lower(engine._params, engine._aux,
                                     engine._caches, engine._state).compile()
    ma = compiled.memory_analysis()
    return int(getattr(ma, "temp_size_in_bytes", 0) or 0)


class Record:
    """One request of the window, as the benchmark saw it."""

    __slots__ = ("due", "prompt", "max_tokens", "sent", "handle", "refused")

    def __init__(self, due, prompt, max_tokens):
        self.due, self.prompt, self.max_tokens = due, prompt, max_tokens
        self.sent = self.handle = None
        self.refused = False


def open_loop(engine, reqs, seconds, ann, tele, marks=()):
    """Offer ``reqs`` at their due times for ``seconds``, then step until
    every accepted request is answered or DRAIN_S has passed. ``marks``:
    [(second, callable)], each called once when its second has come (the
    profiler's start and stop). Returns (records, counters); times are
    seconds since the window opened."""
    recs = [Record(*r) for r in reqs]
    phase0 = {k: tele.histogram("serving.round_phase_ms." + k).sum
              for k in PHASES}
    rounds0 = engine.stats["steps"]
    live = []                      # accepted, not yet done
    c = {"rounds": 0, "live_rows": 0, "live_slots": 0, "peak_rows": 0,
         "tokens_in_window": None, "steps_calls": 0}
    i = 0
    held = 0.0                     # seconds spent inside ``marks``
    marks = sorted(marks, key=lambda m: m[0])
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds and c["tokens_in_window"] is None:
            # the window closes: what was emitted by now counts
            c["tokens_in_window"] = sum(
                len(r.handle.tokens) for r in recs if r.handle is not None)
            c["closed_at"] = now
            c["phase_ms"] = {
                k: tele.histogram("serving.round_phase_ms." + k).sum
                - phase0[k] for k in PHASES}
            c["rounds_in_window"] = engine.stats["steps"] - rounds0
        while marks and now >= marks[0][0]:
            # stopping the profiler holds this loop for tens of seconds:
            # that time is not the engine's, and the wait for late
            # answers is extended by it
            t_mark = time.perf_counter()
            marks.pop(0)[1]()
            held += time.perf_counter() - t_mark
        while i < len(recs) and recs[i].due <= now:
            r = recs[i]
            i += 1
            if engine.queued() >= engine.max_queue:
                r.refused = True         # a full queue refuses: a miss
                continue
            with ann("bench.submit"):
                r.handle = engine.submit(r.prompt, max_tokens=r.max_tokens)
            r.sent = time.perf_counter() - t0
            live.append(r)
        if c["tokens_in_window"] is not None and i >= len(recs) \
                and (not live or now >= seconds + DRAIN_S + held):
            break
        if not live and i < len(recs):
            # nothing resident: wait for the next arrival off the engine
            with ann("bench.wait_arrival"):
                time.sleep(max(0.0, min(recs[i].due - now, 0.002)))
            continue
        before = engine.stats["steps"]
        with ann("bench.engine_step"):
            engine.step()
        c["steps_calls"] += 1
        live = [r for r in live if not r.handle.done]
        if engine.stats["steps"] != before:
            c["rounds"] += 1
            rows = [len(r.prompt) + len(r.handle.tokens) for r in live
                    if r.handle.t_admit is not None]
            c["live_rows"] += sum(rows)
            c["peak_rows"] = max(c["peak_rows"], sum(rows))
            c["live_slots"] += len(rows)
    c["t0"] = t0
    return recs, c


def latencies(recs, t0, miss_ms):
    """Per-request times in ms from the DUE time; a request refused,
    failed or without a first token counts as a miss at the top
    (``miss_ms``: the window and the wait past its close)."""
    ttft, tpot, wait, late = [], [], [], []
    for r in recs:
        h = r.handle
        if h is None or h.t_first is None or h.error is not None:
            ttft.append(miss_ms)
            continue
        ttft.append((h.t_first - t0 - r.due) * 1e3)
        late.append((r.sent - r.due) * 1e3)
        if h.t_admit is not None:
            wait.append((h.t_admit - t0 - r.due) * 1e3)
        if h.done and len(h.tokens) > 1:
            tpot.append((h.t_done - h.t_first) / (len(h.tokens) - 1) * 1e3)
    return ttft, tpot, wait, late


# -- the reference -------------------------------------------------------------------

def sample(recs, seed, k):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    done = [r for r in recs if r.handle is not None and r.handle.done
            and r.handle.error is None and len(r.handle.tokens) > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.handle.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 7)
    pick = [rest[j] for j in rng.permutation(len(rest))[:max(0, k - 1)]]
    return [longest] + pick


def reference_logits(fam, cfg, seed, seqs, precision=None):
    """The family's plain reference over ``seqs`` ([K, L] int32): float32
    at full matmul precision, on weights made again from the seed in the
    served type. The family walks its own leaves and asks for them by
    name, a few at a time, so one layer's weights are on the device at a
    time. ``precision`` makes it the control."""
    import jax
    import jax.numpy as jnp
    specs = fam.param_specs(cfg)
    served = jnp.dtype(cfg["compute_dtype"])

    def make_leaves(names):
        w = H.make_weights(specs, seed, served, names=names)
        return {n: v.astype(jnp.float32) for n, v in w.items()}

    with jax.default_matmul_precision("highest"):
        return fam.reference_logits(jnp.asarray(seqs), make_leaves, cfg,
                                    precision)


def pack(picked, length):
    """Prompts with their served tokens, padded to one shape: (the rows
    the reference reads, the token served after each position, the
    positions that are judged)."""
    seqs = np.zeros((len(picked), length), np.int32)
    nxt = np.zeros((len(picked), length), np.int32)
    judged = np.zeros((len(picked), length), bool)
    for j, r in enumerate(picked):
        toks = np.asarray(r.handle.tokens, np.int32)
        p, k = len(r.prompt), len(toks)
        seqs[j, :p] = r.prompt
        seqs[j, p:p + k] = toks
        nxt[j, p - 1:p - 1 + k] = toks
        judged[j, p - 1:p - 1 + k] = True
    return seqs, nxt, judged


GAP_NUMBERS = ("logit_gap", "logit_gap_mean", "logit_gap_p99")


def token_gaps(logits, tokens):
    """By how far each token's logit lies below the reference's best at
    its position, in units of that row's standard deviation: [K, L]."""
    import jax
    import jax.numpy as jnp

    def gaps(lg, tok):
        got = jnp.take_along_axis(lg, tok[..., None], -1)[..., 0]
        gap = (jnp.max(lg, -1) - got) / jnp.std(lg, -1)
        return jnp.where(jnp.isfinite(gap), gap, jnp.inf)
    return np.asarray(jax.jit(gaps)(logits, jnp.asarray(tokens)), np.float64)


def logit_gaps(logits, tokens, judged):
    """``token_gaps`` over the judged positions: the widest gap
    (``logit_gap``), the mean and the 99th percentile (an observed
    value). ``tokens`` are the served ones, or those the control puts
    first. Every serving cell is held to the widest: it is one token's,
    so it is the number that sees ONE wrong token among thousands (and,
    in a routed model, one routing cascade). The other two read what a
    precision does to all tokens; a limits file may hold its cell to
    them as well, never instead."""
    return _numbers(token_gaps(logits, tokens)[np.asarray(judged, bool)])


def _numbers(g):
    if g.size == 0:
        return dict.fromkeys(GAP_NUMBERS, 0.0)
    return {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean()),
            "logit_gap_p99": float(np.percentile(g, 99, method="higher"))}


def logit_gap(logits, tokens, judged):
    """The widest of ``logit_gaps``."""
    return logit_gaps(logits, tokens, judged)["logit_gap"]


def altered_token_gaps(logits, tokens, judged, vocab):
    """The fault "one token altered where it is produced", read without a
    run of its own: ``tokens`` with ONE of them replaced by the next id
    up. The altered token's gap depends on where it falls, so the three
    numbers are given at the position where it reads the 1st percentile
    (an observed value: at 99 of 100 positions it reads that or more),
    with the least and the median over the positions beside them."""
    judged = np.asarray(judged, bool)
    own = token_gaps(logits, tokens)[judged]
    alt = token_gaps(logits, (np.asarray(tokens) + 1) % vocab)[judged]
    if alt.size == 0:
        return {}
    i = int(np.argsort(alt)[alt.size // 100])
    own[i] = alt[i]
    return dict(_numbers(own), altered_least=float(alt.min()),
                altered_median=float(np.median(alt)))


def judge(cell, seed, picked, control=None, altered=False):
    """Hold ``picked`` (finished requests) to the reference. Returns
    ``logit_gaps`` of the served tokens, "tokens", with ``control`` (a
    precision) "control": the gaps of the token that the reference in
    that precision puts first, at the same positions, and with
    ``altered`` "altered": ``altered_token_gaps`` of the served tokens."""
    import jax.numpy as jnp
    fam, cfg, traffic = cell["family"], cell["cfg"], cell["traffic"]
    if not picked:
        return dict(dict.fromkeys(GAP_NUMBERS, float("inf")), tokens=0)
    length = traffic["prompt"]["max"] + traffic["output"]["max"]
    seqs, nxt, judged = pack(picked, length)
    logits = reference_logits(fam, cfg, seed, seqs)
    out = dict(logit_gaps(logits, nxt, judged), tokens=int(judged.sum()))
    if altered:
        out["altered"] = altered_token_gaps(logits, nxt, judged,
                                            cfg["vocab_size"])
    if control is not None:
        low = reference_logits(fam, cfg, seed, seqs, precision=control)
        out["control"] = logit_gaps(logits, jnp.argmax(low, axis=-1),
                                    judged)
    return out


# -- one run ---------------------------------------------------------------------------

def run(ctx):
    import mxnet_tpu as mx
    from benchmark import trace as T

    cell, args = ctx["cell"], ctx["args"]
    fam, cfg, traffic = cell["family"], cell["cfg"], cell["traffic"]
    seed, seconds = args.seed, args.seconds
    mx.random.seed(seed % (2 ** 31))
    counter = ctx["counter"]
    tele = mx.telemetry

    # -- set-up: weights on the device from the seed, the cell's own
    # buckets and the decode round warmed
    engine, weight_bytes = build_engine(mx, fam, cfg, traffic, seed)
    reqs = G.requests(traffic, cfg["vocab_size"], seed, seconds)
    setup_s = ctx["clock"]()
    compiles0 = counter.compiles

    # -- the window
    cap = None
    if args.trace:
        tsec = min(traffic.get("trace_seconds", 4.0), seconds / 2)
        cap = T.Capture(ctx["trace_dir"])
        recs, c = open_loop(
            engine, reqs, seconds, T.annotation, tele,
            marks=[(seconds - tsec, cap.__enter__),
                   (seconds, lambda: cap.__exit__(None, None, None))])
    else:
        recs, c = open_loop(engine, reqs, seconds, T.null_annotation, tele)
    compiles_in_window = counter.compiles - compiles0

    miss_ms = (seconds + DRAIN_S) * 1e3
    ttft, tpot, wait, late = latencies(recs, c["t0"], miss_ms)
    refused = sum(r.refused for r in recs)
    unanswered = sum(1 for r in recs if r.handle is not None
                     and not r.handle.done)
    errored = sum(1 for r in recs if r.handle is not None
                  and r.handle.error is not None)
    short = sum(1 for r in recs if r.handle is not None and r.handle.done
                and r.handle.error is None
                and len(r.handle.tokens) != r.max_tokens)
    print("serve: requests=%d refused=%d unanswered=%d errored=%d short=%d "
          "tokens_in_window=%d tok_per_s=%.3f rounds=%d engine_step_calls=%d "
          "closed_at=%.3f generator_lateness_p90_ms=%.3f compiles=%s"
          % (len(recs), refused, unanswered, errored, short,
             c["tokens_in_window"], c["tokens_in_window"] / c["closed_at"],
             c["rounds"], c["steps_calls"], c["closed_at"],
             H.p90(late) if late else float("nan"),
             engine.compile_counts), flush=True)
    print("serve: ttft_ms p50=%.1f p90=%.1f max=%.1f queue_wait_ms p50=%.1f "
          "p90=%.1f max=%.1f live_slots_mean=%.2f live_rows_mean=%.0f "
          "live_rows_peak=%d"
          % (float(np.median(ttft)), H.p90(ttft), max(ttft),
             float(np.median(wait)) if wait else -1,
             H.p90(wait) if wait else -1, max(wait) if wait else -1,
             c["live_slots"] / max(1, c["rounds"]),
             c["live_rows"] / max(1, c["rounds"]), c["peak_rows"]),
          flush=True)
    # memory: the weights and the cache rows the traffic FILLED at its
    # fullest. The pool the engine reserves beyond them and the decode
    # program's lane-padded copies of it are real bytes on the chip but
    # hold nothing a request needs: they are printed, not counted.
    peak, live = H.memory_now(ctx["devices"])
    temp = decode_temp_bytes(engine)
    filled = c["peak_rows"] * fam.decode_cache_bytes_per_row(cfg)
    print("serve: setup_s=%.2f weights=%d filled_cache_peak=%d "
          "allocator_peak=%d live(with reserved pool)=%d "
          "decode_temporaries=%d"
          % (setup_s, weight_bytes, filled, peak, live, temp), flush=True)
    picked = sample(recs, seed, traffic["check_requests"])
    spans = {"phase_ms": c["phase_ms"], "rounds_in_window":
             c["rounds_in_window"], "rounds": c["rounds"],
             "live_rows": c["live_rows"], "live_slots": c["live_slots"],
             "queue_wait_ms": wait, "lateness_ms": late,
             "steps_per_round": traffic["steps_per_round"]}

    # -- free the program's state, then hold the sample to the reference
    engine.close()
    del engine
    gc.collect()
    checks = H.Checks(cell["limits"])
    t_ref = time.perf_counter()
    got = judge(cell, seed, picked)
    print("serve: reference judged %d tokens of %d requests in %.1f s; %s"
          % (got["tokens"], len(picked), time.perf_counter() - t_ref,
             " ".join("%s=%.6g" % (k, got[k]) for k in GAP_NUMBERS)),
          flush=True)
    # the widest gap is held in every serving cell; the mean and the 99th
    # percentile beside it where the limits file names them
    checks.add("logit_gap", got["logit_gap"])
    for k in GAP_NUMBERS[1:]:
        if k in cell["limits"]["limits"]:
            checks.add(k, got[k])
    checks.add("never_answered", unanswered + errored + short, 0)
    checks.add("compiles_in_window", compiles_in_window, 0)

    # tokens/s and the first-token tails are printed above and not
    # reported: below the knee the one follows the offered rate, the
    # others swing with the seed's order of arrivals (PERF.md section 2)
    e2e = {"tpot_p90_ms": H.p90(tpot) if tpot else miss_ms,
           "setup_s": setup_s}
    return {"checks": checks, "attempted": len(recs),
            "failed": refused + unanswered + errored + short,
            "end_to_end": e2e, "spans": spans, "capture": cap,
            "memory_peak_bytes": weight_bytes + filled}
