"""Continuous-batching serving engine (mxnet_tpu/serving/): the oracle
is the offline KV-cache Decoder itself — greedy engine outputs must be
BYTE-IDENTICAL per request to ``Decoder.generate`` regardless of
admission order, slot assignment, bucket padding, or co-resident
requests, across every cache flavor. Also pins the compile-count
contract ({decode: 1, verify: <=1, prefill: 1/bucket, copy: 1/bucket})
and the PR's decode-cache satellite (temperature is a traced operand).

Speculative decoding is ON (``draft="ngram"``) for the default
``_engine`` config and both shared engines, so nearly every identity
test here ALSO pins "speculation changes nothing but speed": the
oracle is the offline decoder, i.e. the spec-off output, and the
admission-order / mid-stream / sampling / eos / chunked-prefix
scenarios all run through the verify program whenever the drafter
proposes. The spec-off engine is pinned by the from_checkpoint test
(constructors default off) and by every pre-spec BENCH arm.

Runtime discipline: every distinct ``(prompt_len, num_steps)`` oracle
call and every engine compiles programs, which dominates this file on
CPU — workloads reuse a small set of shapes, oracle outputs are cached,
and one default-config engine is shared by the tests that only READ
behavior (each still drains to idle); the first test's workload runs
on the shared engine too (its compile pin holds for the whole
module)."""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.serving import InferenceEngine

from check_utils import assert_compile_contract

# 1 layer keeps this file's compile bill inside the tier-1 budget; the
# multi-node cache-list plumbing the engine reuses is pinned offline by
# test_decode.py (2 layers), and every identity oracle here is
# layer-count-agnostic
VOCAB, LAYERS, EMBED, HEADS = 17, 1, 16, 2
T = 16  # max_len everywhere here


def _lm(**kw):
    return get_transformer_lm(VOCAB, num_layers=LAYERS, embed_dim=EMBED,
                              num_heads=HEADS, impl="dense", **kw)


def _init_params(sym, rng):
    shapes = {"data": (2, T), "softmax_label": (2, T)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    return {n: jnp.asarray(rng.uniform(-0.3, 0.3, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in shapes}


@pytest.fixture(scope="module")
def lm():
    rng = np.random.RandomState(0)
    sym = _lm()
    params = _init_params(sym, rng)
    return sym, params, Decoder(sym, params, max_len=T)


def _engine(sym, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_buckets", (4, 8))
    # prefix cache off unless a test opts in: the cache-on tests below
    # pin its behavior; everything else pins the base engine (and the
    # random prompts here would make copy-program compile counts
    # draw-dependent)
    kw.setdefault("prefix_cache_mb", 0)
    # speculation ON by default (n-gram drafting): the oracle below IS
    # the spec-off output, so every identity test doubles as a
    # speculation byte-identity pin
    kw.setdefault("draft", "ngram")
    kw.setdefault("spec_k", 3)
    return InferenceEngine(Decoder(sym, params, max_len=T), **kw)


@pytest.fixture(scope="module")
def shared_engine(lm):
    """One default-config engine reused by read-only behavior tests
    (each drains it back to idle); tests asserting per-engine stats or
    compile logs build their own."""
    sym, params, _ = lm
    return _engine(sym, params)


@pytest.fixture(scope="module")
def second_engine(lm):
    """A SECOND independent default-config engine, for tests comparing
    two admission schedules against each other."""
    sym, params, _ = lm
    return _engine(sym, params)


def _noop_ctx():
    return contextlib.nullcontext()


_ORACLE = {}


def _oracle(dec, prompt, n):
    """Offline greedy continuation, truncated the way the engine
    truncates (at the cache end); memoized — repeated shapes must not
    recompile or re-run the scan program."""
    prompt = np.asarray(prompt)
    n = min(n, T - len(prompt))
    key = (id(dec), prompt.tobytes(), len(prompt), n)
    if key not in _ORACLE:
        _ORACLE[key] = np.asarray(
            dec.generate(prompt[None], num_steps=n))[0, len(prompt):]
    return _ORACLE[key]


def test_engine_mixed_lengths_slot_reuse_byte_identical(lm,
                                                        shared_engine):
    """More requests than slots, mixed prompt/output lengths: every
    request byte-matches offline greedy decode; slots are recycled; the
    whole run (and a SECOND wave on the same engine) compiles exactly
    one decode program, ONE verify program (speculation is on — the
    engineered repetitive prompt guarantees the drafter proposes) and
    one prefill program per used bucket. Runs on the module's shared
    engine — first in the file, so the pin covers a cold engine; later
    tests reuse the same programs (the contract holds module-wide)."""
    sym, params, dec = lm
    rng = np.random.RandomState(1)
    eng = shared_engine
    cases = [(2, 5), (4, 6), (7, 3), (4, 6), (2, 5), (7, 3), (6, 2)]
    reqs = [(p, n, eng.submit(p, max_tokens=n))
            for pl, n in cases
            for p in [rng.randint(0, VOCAB, (pl,))]]
    # engineered speculation cases: a periodic prompt (the n-gram
    # drafter must propose from the repeated suffix — verify compiles
    # deterministically) and a prompt whose greedy continuation is
    # self-repetitive enough to ACCEPT drafts (probed; seed-stable)
    p_rep = np.array([1, 2, 3, 1, 2, 3, 1])
    p_acc = np.array([0, 3, 3])
    reqs.append((p_rep, 3, eng.submit(p_rep, max_tokens=3)))
    reqs.append((p_acc, 13, eng.submit(p_acc, max_tokens=13)))
    done = eng.serve_forever()
    assert len(done) == len(reqs)
    assert eng.stats["prefills"] == len(reqs) > eng.slots  # slot reuse
    for p, n, r in reqs:
        np.testing.assert_array_equal(r.result(), _oracle(dec, p, n))
    assert_compile_contract(eng, verify=1, prefill={4: 1, 8: 1},
                            copy={})
    # the tentpole's point: drafts were proposed AND accepted — tokens
    # landed more-than-one per verify dispatch, byte-identically
    assert eng.stats["spec_rounds"] >= 1
    assert eng.stats["spec_drafted"] >= 1
    assert eng.stats["spec_accepted"] >= 1

    # PR 4 (telemetry): the per-request latency breakdown is fully
    # populated and ordered; every request here retires on its token
    # budget. The registry (global, shared across tests) must carry a
    # non-trivial serving snapshot — lower bounds, not exact counts.
    for p, n, r in reqs:
        assert r.t_admit is not None and r.retire_reason == "length"
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
    snap = mx.telemetry.snapshot()["serving"]
    assert snap["ttft_ms"]["count"] >= len(cases)
    assert snap["queue_wait_ms"]["count"] >= len(cases)
    assert snap["token_cadence_ms"]["count"] >= 1
    assert snap["tokens"] >= sum(n for _, n in cases)
    assert snap["retired_length"] >= len(cases)
    assert snap["slots_busy_per_round"]["count"] >= 1
    # compile_counts re-exported as telemetry (trace-time increments)
    assert snap["compiles_decode"] >= 1
    assert snap["compiles_prefill"] >= 2     # buckets 4 and 8
    assert snap["compiles_verify"] >= 1
    # speculation telemetry (doc/observability.md catalog)
    assert snap["spec_rounds"] >= 1
    assert snap["spec_drafted_tokens"] >= snap["spec_accepted_tokens"]
    assert snap["spec_accepted_tokens"] >= 1
    assert snap["spec_drafts_ngram"] >= 1
    assert snap["spec_accepted_per_step"]["count"] >= 1

    # second wave on the SAME engine: zero new compiles, still exact
    wave2 = [(p, n, eng.submit(p, max_tokens=n))
             for pl, n in [(2, 5), (4, 6), (7, 3)]
             for p in [rng.randint(0, VOCAB, (pl,))]]
    eng.serve_forever()
    for p, n, r in wave2:
        np.testing.assert_array_equal(r.result(), _oracle(dec, p, n))
    assert_compile_contract(eng, verify=1, prefill={4: 1, 8: 1},
                            copy={})
    assert eng.idle


def test_engine_multi_step_rounds_byte_identical(lm):
    """steps_per_round>1 (the dispatch-amortized decode round, one
    lax.scan program) changes scheduling granularity only: outputs
    stay byte-identical, including requests that retire MID-round
    (budgets deliberately not multiples of the round length). With
    speculation ON (the _engine default), rounds with drafts dispatch
    the verify program and draftless rounds fall back to the 3-step
    scan — both interleave in this workload and the accepting prompt
    pins that multi-token verify drains compose with multi-token scan
    drains."""
    sym, params, dec = lm
    rng = np.random.RandomState(11)
    eng = _engine(sym, params, steps_per_round=3)
    reqs = [(p, n, eng.submit(p, max_tokens=n))
            for pl, n in [(2, 5), (6, 2), (2, 5), (6, 2), (4, 1)]
            for p in [rng.randint(0, VOCAB, (pl,))]]
    reqs.append((np.array([0, 3, 3]), 13,
                 eng.submit(np.array([0, 3, 3]), max_tokens=13)))
    eng.serve_forever()
    for p, n, r in reqs:
        np.testing.assert_array_equal(r.result(), _oracle(dec, p, n))
    assert_compile_contract(eng)
    assert eng.stats["spec_rounds"] >= 1      # verify rounds ran
    assert eng.stats["spec_fallback_rounds"] >= 1  # and scan rounds
    assert eng.idle


def test_engine_dead_slot_reads_no_row(lm, monkeypatch):
    """The decode step reads only the rows its live requests hold
    (``len = live ? pos + 1 : 0``, made in the step program). A slot
    finishes MID-round, stays dead for rounds beside a live one, and
    is reused by a shorter request: every token equals the offline
    ``Decoder.generate``'s, which reads densely (every row masked by
    position). Once the new tenant decodes, the previous
    tenant's stale rows past its block are poisoned with NaN: none
    reaches a score. Then the step program itself, on a state made by
    hand: the rows a step fetches are the live slot's length in whole
    blocks, the dead slot's stale position adds none, and the
    telemetry counters carry the same count."""
    sym, params, dec = lm
    # two blocks of 8 rows a slot, so a length bounds the read at toy
    # size too (within the kernel's own 1 MB a block all 16 rows are
    # one): the cap set to 8 of this cache's float32 rows
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_PAGED_BLOCK_BYTES", 8 * EMBED * 4)
    rng = np.random.RandomState(41)
    pa, pb, pc = (rng.randint(0, VOCAB, (n,)) for n in (8, 2, 2))

    def run():
        eng = _engine(sym, params, steps_per_round=2, draft=None)
        ra = eng.submit(pa, max_tokens=5)    # 8 + 5 rows: both blocks,
        rb = eng.submit(pb, max_tokens=13)   # done in the middle of a
        while not ra.done:                   # round of 2 steps
            eng.step()
        slot_b = eng._mirror.index(rb)
        for _ in range(2):                   # dead beside a live slot
            eng.step()
        assert not rb.done
        rc = eng.submit(pc, max_tokens=5)    # 2 + 5 rows: block 0 only
        eng.step()                           # its prefill, densely read
        eng._caches = [
            tuple(buf.at[1 - slot_b, 8:].set(jnp.nan) for buf in e)
            for e in eng._caches]
        eng.serve_forever()
        assert eng.idle
        return eng, [r.result() for r in (ra, rb, rc)]

    read = mx.telemetry.counter("serving.attn_rows_read")
    pool = mx.telemetry.counter("serving.attn_rows_pool")
    before = read.value, pool.value
    eng, got = run()
    for g, (p, n) in zip(got, [(pa, 5), (pb, 13), (pc, 5)]):
        np.testing.assert_array_equal(g, _oracle(dec, p, n))
    rows = read.value - before[0], pool.value - before[1]
    assert rows[1] == 2 * T * LAYERS * eng.stats["steps"] * 2
    assert 0 < rows[0] < 0.75 * rows[1]
    assert_compile_contract(eng)

    # the step program on a hand-made state: slot 0 dead at a stale
    # position, slot 1 live at position 9
    pos, tok, live, temp, keys, eos, last = eng._state
    state = (jnp.asarray([13, 9], jnp.int32), tok,
             jnp.asarray([False, True]), temp, keys,
             jnp.full_like(eos, -1), jnp.full_like(last, T - 1))
    _, state, outs = eng._step_fn(eng._params, eng._aux, eng._caches,
                                  state)
    outs = np.asarray(outs)                     # [2 steps, S + 1]
    assert (outs[:, 0] == -1).all() and (outs[:, 1] >= 0).all()
    np.testing.assert_array_equal(outs[:, -1], [16 * LAYERS] * 2)
    _, _, outs = eng._step_fn(
        eng._params, eng._aux, eng._caches,
        state[:2] + (jnp.asarray([False, False]),) + state[3:])
    np.testing.assert_array_equal(np.asarray(outs)[:, -1], [0, 0])


def test_engine_admission_order_and_midstream_submit(lm, shared_engine,
                                                     second_engine):
    """Per-request outputs are independent of admission order and of
    requests submitted MID-STREAM while others are decoding."""
    sym, params, dec = lm
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, VOCAB, (pl,)) for pl in (3, 6, 2, 3, 6)]

    # order A: all up front, on the shared engine
    ra = [shared_engine.submit(p, max_tokens=5) for p in prompts]
    shared_engine.serve_forever()

    # order B: independent engine, reversed, trickled in mid-decode
    eng_b = second_engine
    rb = {}
    rb[4] = eng_b.submit(prompts[4], max_tokens=5)
    for _ in range(3):
        eng_b.step()                      # decoding is underway
    for i in (3, 2):
        rb[i] = eng_b.submit(prompts[i], max_tokens=5)
    eng_b.step()
    for i in (1, 0):
        rb[i] = eng_b.submit(prompts[i], max_tokens=5)
    eng_b.serve_forever()

    for i, p in enumerate(prompts):
        want = _oracle(dec, p, 5)
        np.testing.assert_array_equal(ra[i].result(), want)
        np.testing.assert_array_equal(rb[i].result(), want)


def test_engine_eos_limits_and_truncation(lm, shared_engine):
    """eos_id retires a sequence the moment it appears (eos included in
    the output); max_tokens=1 retires at prefill; an over-long token
    budget is truncated at the cache end — all byte-equal to the
    offline continuation's prefix."""
    sym, params, dec = lm
    rng = np.random.RandomState(3)
    p = rng.randint(0, VOCAB, (4,))
    full = _oracle(dec, p, T - len(p))   # the longest continuation

    eos = int(full[3])
    r_eos = shared_engine.submit(p, max_tokens=12, eos_id=eos)
    r_one = shared_engine.submit(p, max_tokens=1)
    r_cap = shared_engine.submit(p, max_tokens=100)  # > room: truncated
    shared_engine.serve_forever()

    stop = int(np.where(full == eos)[0][0])
    np.testing.assert_array_equal(r_eos.result(), full[:stop + 1])
    np.testing.assert_array_equal(r_one.result(), full[:1])
    assert len(r_cap.tokens) == T - len(p)
    np.testing.assert_array_equal(r_cap.result(), full)
    # telemetry satellite: the retirement reason names WHY each ended
    assert r_eos.retire_reason == "eos"
    assert r_one.retire_reason == "length"
    assert r_cap.retire_reason == "length"


def test_engine_backpressure(lm):
    """max_queue bounds submitted-but-not-admitted requests: submit
    raises MXNetError when full and succeeds again once the engine
    drains."""
    sym, params, dec = lm
    rng = np.random.RandomState(4)
    # 1 slot + queue 2: a third WAITING request must bounce
    eng = _engine(sym, params, slots=1, max_queue=2, stage_depth=1)
    held = [eng.submit(rng.randint(0, VOCAB, (4,)), max_tokens=6)
            for _ in range(2)]  # queue at capacity (admission is lazy)
    extra = rng.randint(0, VOCAB, (4,))
    with pytest.raises(MXNetError, match="queue is full"):
        eng.submit(extra, max_tokens=2)
    eng.step()                  # admits one into the slot: room again
    held.append(eng.submit(rng.randint(0, VOCAB, (4,)), max_tokens=6))
    with pytest.raises(MXNetError, match="queue is full"):
        eng.submit(extra, max_tokens=2)
    eng.serve_forever()
    assert all(r.done for r in held)
    late = eng.submit(extra, max_tokens=2)  # drained: accepted again
    eng.serve_forever()
    np.testing.assert_array_equal(late.result(), _oracle(dec, extra, 2))


@pytest.mark.parametrize("flavor", ["int8", "window"])
def test_engine_cache_flavors_match_offline(flavor):
    """The slot-paged engine reuses the Decoder's cache layouts
    verbatim: int8-quantized entries and sliding-window rings (with
    rope, plus the ring-position reset on slot reuse) both byte-match
    their own offline decoder — WITH the prefix cache and chunked
    prefill requested. int8 entries copy their row scales alongside
    (real hits asserted); windowed models BYPASS the prefix cache
    (ring eviction invalidates absolute-position reuse — pinned here)
    but still chunk their prefills exactly (the ring's read-before-
    write chunk math at nonzero start positions)."""
    rng = np.random.RandomState(5)
    if flavor == "int8":
        sym, deckw = _lm(), dict(cache_dtype="int8")
    else:
        sym, deckw = _lm(window=6, pos_encoding="rope"), {}
    params = _init_params(sym, rng)
    dec = Decoder(sym, params, max_len=T, **deckw)
    # speculation requested on BOTH flavors: int8 verifies through the
    # quantized cache; the windowed model must refuse LOUDLY (the
    # verify chunk would wrap rejected drafts onto live ring rows —
    # prefix-cache precedent) and serve with draft="off"
    ctx = (pytest.warns(UserWarning, match="windowed")
           if flavor == "window" else _noop_ctx())
    with ctx:
        eng = InferenceEngine(
            Decoder(sym, params, max_len=T, **deckw),
            slots=2, prefill_buckets=(4, 8),
            prefix_cache_mb=0.01, prefill_chunk=4,
            spec_k=3, draft="ngram")
    # shared prefixes ON PURPOSE: the repeats hit the cache (int8),
    # same (prompt_len, max_tokens) shapes as before for oracle reuse
    base = rng.randint(0, VOCAB, (6,))
    cases = [(rng.randint(0, VOCAB, (3,)), 5), (base, 4),
             (base[:3].copy(), 5), (base.copy(), 4),
             (np.concatenate([base[:3], rng.randint(0, VOCAB, (3,))]),
              4)]
    reqs = [(p, n, eng.submit(p, max_tokens=n)) for p, n in cases]
    eng.serve_forever()
    assert eng.stats["prefills"] > eng.slots  # reuse exercised the reset
    for p, n, r in reqs:
        np.testing.assert_array_equal(r.result(), _oracle(dec, p, n))
    if flavor == "int8":
        assert eng.stats["prefix_hit_tokens"] > 0  # scales copied too
        assert assert_compile_contract(eng)["copy"]
        assert eng.spec_draft == "ngram"       # int8 speculates
    else:
        assert eng._prefix is None and eng._pool is None  # the bypass
        assert_compile_contract(eng, verify=0, copy={})
        assert eng.stats["prefill_chunks"] > len(cases)  # chunks ran
        assert eng.spec_draft == "off"         # the loud ring bypass
        assert eng.stats["spec_rounds"] == 0


def test_engine_draft_model_speculation(lm):
    """draft="model": a draft decoder sharing the slot-paged layout
    proposes K tokens per round (its own per-bucket prefill + ONE
    proposal program), the target verifies — byte-identical outputs,
    and with the draft sharing the target's weights every proposal
    matches, so tokens land (accepted + 1) per verify dispatch (the
    speedup mechanism, pinned as accepted > verify rounds). The
    compile contract extends by exactly {draft: 1,
    draft_prefill: 1/bucket}."""
    sym, params, dec = lm
    rng = np.random.RandomState(21)
    eng = _engine(sym, params, draft="model",
                  draft_decoder=Decoder(sym, params, max_len=T))
    cases = [(rng.randint(0, VOCAB, (2,)), 5),
             (rng.randint(0, VOCAB, (4,)), 6),
             (rng.randint(0, VOCAB, (7,)), 3),
             (np.array([0, 3, 3]), 13)]
    reqs = [(p, n, eng.submit(p, max_tokens=n)) for p, n in cases]
    eng.serve_forever()
    for p, n, r in reqs:
        np.testing.assert_array_equal(r.result(), _oracle(dec, p, n))
    assert_compile_contract(eng, verify=1, prefill={4: 1, 8: 1},
                            copy={}, draft=1,
                            draft_prefill={4: 1, 8: 1})
    # same weights -> drafts always match until a budget/eos stop:
    # strictly more than one token per verify dispatch on average
    assert eng.stats["spec_accepted"] > eng.stats["spec_rounds"] >= 1
    assert mx.telemetry.snapshot()["serving"]["spec_drafts_model"] >= 1
    # the snapshot carries the speculation knobs (restore() needs
    # draft_decoder= handed back in overrides — plain JSON cannot
    # carry weights)
    geo = eng.snapshot()["engine"]
    assert geo["draft"] == "model" and geo["spec_k"] == 3
    assert eng.idle


def test_engine_prefix_cache_chunked_byte_identical(lm):
    """THE tentpole oracle: with the prefix cache AND chunked prefill
    on, greedy outputs stay byte-identical to the offline decoder (=
    the cache-off engine pinned by every other test here) across full
    hits, partial hits, misses, chunk-boundary prompts, LRU eviction
    under a one-slot byte budget, and a second admission order on the
    same engine — while the compile contract extends to exactly one
    copy program per used bucket."""
    sym, params, dec = lm
    rng = np.random.RandomState(13)
    base = rng.randint(0, VOCAB, (7,))
    # (prompt, max_tokens) — shapes reuse the module's oracle compiles;
    # prompt lengths 3/4/6/7 straddle the chunk size 3 (exact multiple,
    # one-over, one-under) and share engineered prefixes
    cases = {
        "miss_long": (base, 3),                      # retained; 3 chunks
        "prefix_of": (base[:4].copy(), 6),           # hit 3 of 4
        "partial": (np.concatenate([base[:4],
                                    rng.randint(0, VOCAB, (3,))]), 3),
        "unrelated": (rng.randint(0, VOCAB, (2,)), 5),   # miss, 1 chunk
        "full_dup": (base.copy(), 3),                # full hit -> P-1
        "boundary": (rng.randint(0, VOCAB, (6,)), 2),    # exactly 2 chunks
        # past the largest bucket (8): only CHUNKED admission can
        # serve it (monolithic submit would reject); not retained
        "beyond_bucket": (rng.randint(0, VOCAB, (10,)), 3),
    }
    # pool budget = ONE slot (1-layer f32 K+V slot is 2 KiB): every
    # retention past the first EVICTS — identity must survive serving
    # from, and losing, any entry
    eng = _engine(sym, params, prefix_cache_mb=0.0021, prefill_chunk=3)
    assert eng._prefix is not None and eng._prefix.capacity == 1
    order1 = ["miss_long", "prefix_of", "partial", "unrelated",
              "full_dup", "boundary", "beyond_bucket"]
    rs = {k: eng.submit(*cases[k]) for k in order1}
    eng.serve_forever()
    for k, (p, n) in cases.items():
        np.testing.assert_array_equal(rs[k].result(), _oracle(dec, p, n))
    assert eng.stats["prefix_hits"] >= 1          # some reuse happened
    assert eng.stats["prefill_chunks"] > len(cases)   # chunking ran
    assert sum(r.prefill_chunks for r in rs.values()) \
        == eng.stats["prefill_chunks"]
    assert eng._prefix.evictions >= 1             # the 1-slot pool churned
    # speculation rode the whole gauntlet (the _engine default is
    # draft="ngram"): verify compiled at most once, and verify rounds
    # actually served prefix-hit/chunked traffic byte-identically
    assert assert_compile_contract(eng)["copy"]
    assert eng.stats["spec_rounds"] + eng.stats["spec_fallback_rounds"] \
        > 0

    # second wave, REVERSED admission order, same engine (zero new
    # compiles): hit/miss patterns differ completely, outputs must not
    log_len = len(eng._compile_log)
    rs2 = {k: eng.submit(*cases[k]) for k in reversed(order1)}
    eng.serve_forever()
    for k, (p, n) in cases.items():
        np.testing.assert_array_equal(rs2[k].result(),
                                      _oracle(dec, p, n))
    assert len(eng._compile_log) == log_len
    assert eng.idle

    # telemetry satellite: the new serving.prefix_*/chunk metrics are
    # populated in the process-wide snapshot (lower bounds — shared
    # registry)
    snap = mx.telemetry.snapshot()["serving"]
    assert snap["prefix_hit_tokens"] >= 1
    assert snap["prefix_lookup_ms"]["count"] >= len(cases)
    assert snap["prefix_cache_bytes"] >= 0
    assert snap["prefill_chunks_per_request"]["count"] >= len(cases)
    assert snap["compiles_copy"] >= 1

    # near-cache-end guard regression: a prompt so long its head sits
    # within spec_k+2 of max_len admits while an ACCEPTING co-resident
    # keeps proposing drafts — the rounds carrying it (including the
    # one where its final prefill entry is still undrained, the
    # mirror-blind window) must fall back to plain decode instead of
    # letting the fixed-width verify chunk write clamp onto its live
    # rows. Corruption would break byte-identity below.
    r_acc = eng.submit(np.array([0, 3, 3]), max_tokens=13)
    for _ in range(3):
        eng.step()                       # drafts begin flowing
    p_end = rng.randint(0, VOCAB, (13,))
    r_end = eng.submit(p_end, max_tokens=2)
    eng.serve_forever()
    np.testing.assert_array_equal(r_acc.result(),
                                  _oracle(dec, np.array([0, 3, 3]), 13))
    np.testing.assert_array_equal(r_end.result(), _oracle(dec, p_end, 2))
    assert len(eng._compile_log) == log_len  # still zero new programs


def test_window_prefill_pad_rows_do_not_corrupt_ring():
    """Bucketed prefill on a WINDOWED model: the ring write must honor
    the true prompt length, not the padded chunk length. Two distinct
    failure modes hide behind argmax (review finding — the flavor test
    above can pass by luck): pad rows wrapping into ``p % win`` slots
    EVICT real in-window keys, and the last-win-chunk-rows tail SKIPS
    real keys displaced before the pad tail. Compare the padded
    ``valid_len`` prefill against the exact-length prefill: ring
    positions, ring K/V, and last-real-position logits must all match
    exactly (not just the argmax)."""
    import jax.numpy as jnp_

    rng = np.random.RandomState(12)
    win = 4
    sym = _lm(window=win, pos_encoding="rope")
    params = _init_params(sym, rng)
    dec = Decoder(sym, params, max_len=T)
    P, L = 6, 8                   # 2 pad rows; win < P: both modes bite
    toks = rng.randint(0, VOCAB, (1, P)).astype(np.int32)
    padded = np.zeros((1, L), np.int32)
    padded[0, :P] = toks

    want_logits, want_caches = dec._run(
        dec._params, dec._aux, dec.init_cache(1), 0,
        jnp_.asarray(toks))
    got_logits, got_caches = dec._run(
        dec._params, dec._aux, dec.init_cache(1), 0,
        jnp_.asarray(padded), valid_len=jnp_.int32(P))

    np.testing.assert_array_equal(np.asarray(got_logits)[0, P - 1],
                                  np.asarray(want_logits)[0, P - 1])
    for want_e, got_e in zip(want_caches, got_caches):
        # (ck, cv, cpos) float layout under the default cache dtype
        np.testing.assert_array_equal(np.asarray(got_e[-1]),
                                      np.asarray(want_e[-1]))  # cpos
        np.testing.assert_array_equal(np.asarray(got_e[0]),
                                      np.asarray(want_e[0]))   # K ring
        np.testing.assert_array_equal(np.asarray(got_e[1]),
                                      np.asarray(want_e[1]))   # V ring


def test_engine_sampling_schedule_independent(lm, shared_engine,
                                              second_engine):
    """Sampled outputs depend only on (seed, position): the same
    request draws the same tokens whatever else is resident and
    whenever it is admitted (both engines carry different prior slot
    churn from earlier tests — which must not matter either)."""
    sym, params, _ = lm
    rng = np.random.RandomState(6)
    p = rng.randint(0, VOCAB, (4,))
    noise = [rng.randint(0, VOCAB, (5,)) for _ in range(2)]

    def run(eng, order):
        h = None
        for tag in order:
            if tag == "x":
                h = eng.submit(p, max_tokens=6, temperature=0.9, seed=42)
            else:
                eng.submit(noise[tag], max_tokens=4, temperature=0.5,
                           seed=100 + tag)
            eng.step()
        eng.serve_forever()
        return h.result()

    a = run(shared_engine, ["x", 0, 1])
    b = run(second_engine, [0, 1, "x"])
    np.testing.assert_array_equal(a, b)
    assert a.shape == (6,) and (a >= 0).all() and (a < VOCAB).all()


def test_spec_multi_token_cadence_wall_clock_truth(lm, shared_engine):
    """Satellite: K accepted tokens landing in ONE drain must not skew
    the cadence metric. ``serving.token_cadence_ms`` divides the
    request's decode wall time by its INTERVAL count (tokens − 1), so
    a verify drain delivering several tokens at one instant still
    reports the true per-token wall rate (the PR 9 restore-cadence
    precedent: divide by what actually happened, not by drain events);
    flight decode-progress events carry explicit ``tokens=`` counts
    that keep ascending across multi-token drains."""
    sym, params, dec = lm
    eng = shared_engine
    p = np.array([0, 3, 3])        # probed: its greedy continuation
    acc0 = eng.stats["spec_accepted"]      # accepts n-gram drafts
    before = mx.telemetry.snapshot()["serving"]["token_cadence_ms"]
    old_sample = eng.flight.token_sample
    eng.flight.token_sample = 2            # dense progress sampling
    try:
        r = eng.submit(p, max_tokens=13)
        eng.serve_forever()
    finally:
        eng.flight.token_sample = old_sample
    np.testing.assert_array_equal(r.result(), _oracle(dec, p, 13))
    assert len(r.tokens) == 13
    assert eng.stats["spec_accepted"] > acc0   # multi-token drains ran
    after = mx.telemetry.snapshot()["serving"]["token_cadence_ms"]
    assert after["count"] == before["count"] + 1
    # the one new observation is wall-clock truth for THIS request
    # (approx: the delta subtracts a long-accumulated float sum)
    want = (r.t_done - r.t_first) / (len(r.tokens) - 1) * 1e3
    got = after["sum"] - before["sum"]
    assert got == pytest.approx(want, rel=1e-6, abs=1e-5)
    # flight progress: explicit ascending token counts, every
    # 2-crossing recorded even though several tokens share a drain
    tl = eng.flight.timeline(r.id)
    decode = [e["tokens"] for e in tl["events"]
              if e["event"] == "decode"]
    assert decode == [2, 4, 6, 8, 10, 12]
    assert eng.idle


def test_engine_from_checkpoint_and_estimator(lm, tmp_path):
    """Checkpoint → engine (InferenceEngine.from_checkpoint) and
    estimator → engine (FeedForward.as_serving_engine) both serve
    byte-identically to the offline decoder built from the same
    weights."""
    sym, params, dec = lm
    rng = np.random.RandomState(7)
    prefix = str(tmp_path / "lm")
    mx.model.save_checkpoint(
        prefix, 3, sym,
        {k: mx.nd.array(np.asarray(v)) for k, v in params.items()}, {})
    p = rng.randint(0, VOCAB, (4,))
    want = _oracle(dec, p, 5)

    eng = InferenceEngine.from_checkpoint(prefix, 3, max_len=T, slots=2,
                                          prefill_buckets=(4, 8))
    r = eng.submit(p, max_tokens=5)
    eng.serve_forever()
    np.testing.assert_array_equal(r.result(), want)

    ff = mx.FeedForward.load(prefix, 3)
    eng2 = ff.as_serving_engine(max_len=T, slots=2,
                                prefill_buckets=(4, 8))
    r2 = eng2.submit(p, max_tokens=5)
    eng2.serve_forever()
    np.testing.assert_array_equal(r2.result(), want)


def test_engine_serve_forever_arrival_stream(lm, shared_engine):
    """serve_forever drives an ONLINE arrival process: a generator may
    yield None ("nothing arrived yet") between submissions and the
    engine keeps serving residents meanwhile."""
    sym, params, dec = lm
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, VOCAB, (pl,)) for pl in (3, 6, 2)]

    def arrivals():
        yield dict(prompt=prompts[0], max_tokens=5)
        for _ in range(3):
            yield None                     # engine steps in between
        yield dict(prompt=prompts[1], max_tokens=5)
        yield None
        yield (prompts[2], dict(max_tokens=5))

    done = shared_engine.serve_forever(arrivals())
    assert len(done) == 3
    by_len = {len(r.prompt): r for r in done}
    for p in prompts:
        np.testing.assert_array_equal(by_len[len(p)].result(),
                                      _oracle(dec, p, 5))


def test_engine_validation(lm, shared_engine):
    sym, params, dec = lm
    eng = shared_engine
    with pytest.raises(MXNetError, match="needs a Decoder"):
        InferenceEngine(object())
    with pytest.raises(MXNetError, match="ascending"):
        _engine(sym, params, prefill_buckets=(8, 4))
    with pytest.raises(MXNetError, match="empty prompt"):
        eng.submit([], max_tokens=2)
    # dtype/rank validation (PR satellite): a 2-D prompt or float ids
    # used to flow into the compiled programs and die as opaque
    # shape/dtype errors rounds later
    with pytest.raises(MXNetError, match="1-D"):
        eng.submit(np.ones((2, 3), np.int32), max_tokens=2)
    with pytest.raises(MXNetError, match="integers"):
        eng.submit(np.array([1.5, 2.0]), max_tokens=2)
    with pytest.raises(MXNetError, match="prefill_chunk"):
        _engine(sym, params, prefill_chunk=-1)
    with pytest.raises(MXNetError, match="prefix_cache_mb"):
        _engine(sym, params, prefix_cache_mb=-1)
    with pytest.raises(MXNetError, match="no room"):
        eng.submit(np.zeros(T, np.int32), max_tokens=2)
    with pytest.raises(MXNetError, match="largest .* bucket"):
        eng.submit(np.zeros(9, np.int32), max_tokens=2)  # buckets (4,8)
    with pytest.raises(MXNetError, match="max_tokens"):
        eng.submit([1, 2], max_tokens=0)
    with pytest.raises(MXNetError, match="not finished"):
        eng.submit([1, 2], max_tokens=2).result()
    # speculation knobs (PR satellite): bad source, useless K, and
    # draft="model" without its decoder all fail at construction
    with pytest.raises(MXNetError, match="draft must be"):
        _engine(sym, params, draft="bogus")
    with pytest.raises(MXNetError, match="spec_k"):
        _engine(sym, params, spec_k=0)
    with pytest.raises(MXNetError, match="draft_decoder"):
        _engine(sym, params, draft="model")
    eng.serve_forever()  # leave the shared engine idle


def test_generate_temperature_is_traced_operand(lm):
    """PR satellite: Decoder._gen_jit no longer keys on temperature —
    a temperature sweep reuses ONE compiled program per
    (batch, prompt, steps) shape, and the traced greedy path stays
    byte-identical to before (the offline oracle of every other test
    here)."""
    sym, params, dec = lm   # the module decoder: its cache counts too
    rng = np.random.RandomState(9)
    p = rng.randint(0, VOCAB, (2, 4))
    key = jax.random.PRNGKey(0)
    before = len(dec._gen_jit)
    greedy = np.asarray(dec.generate(p, 5, temperature=0.0))
    for temp in (0.5, 2.0):
        out = np.asarray(dec.generate(p, 5, rng=key, temperature=temp))
        assert out.shape == greedy.shape
    assert len(dec._gen_jit) == before + 1  # one new shape, any temp
    # same key+temperature reproduces; temperature 0 re-matches greedy
    a = np.asarray(dec.generate(p, 5, rng=key, temperature=0.7))
    b = np.asarray(dec.generate(p, 5, rng=key, temperature=0.7))
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        greedy, np.asarray(dec.generate(p, 5, temperature=0.0)))
