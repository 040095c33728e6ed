"""Continuous-batching inference engine over a slot-paged KV cache.

Architecture (doc/serving.md has the full story):

* ONE persistent KV cache of ``S`` slots x ``max_len`` — ``Decoder``'s
  own cache layouts (plain float, int8-quantized scales, sliding-window
  rings) with the batch axis reinterpreted as a SLOT axis. A request
  occupies one slot from admission to retirement; a freed slot is
  recycled without touching the others (stale rows are hidden by the
  ``key_pos <= pos`` causal mask until overwritten; window rings get
  their position buffers reset at admission).

* FOUR compiled program families serve any request mix, ever (the
  fourth only with speculative decoding on; ``draft="model"`` adds
  the draft LM's proposal + prefill programs on top):

  - **bucketed prefill** (one program per power-of-2 length bucket):
    a prompt CHUNK padded to its bucket is pushed through the derived
    incremental graph at positions ``[start, start + C)`` of its
    assigned slot — slot index, start position, true chunk length,
    finality, temperature, rng key, eos id and token budget are all
    traced operands. The FINAL chunk samples the first output token
    in-program at the last real prompt position and scatter-updates
    the per-slot state vectors; non-final chunks (``prefill_chunk``
    pieces of a long prompt, interleaved with decode rounds —
    Sarathi-Serve, Agrawal et al. 2024) only write K/V and park the
    slot in a frozen state whose idempotent decode-round rewrite is
    harmless. Admission costs zero extra compiled programs.
  - **fused decode step** (exactly one program): one token for EVERY
    slot at its own position — per-slot position vector, per-slot
    temperature/rng sampling, vectorized EOS/length masking. Finished
    slots freeze (their write is idempotent) until reused.
  - **bucketed prefix copy** (one program per bucket, when the prefix
    cache is on): rows ``[0, B)`` of one cache slot land in another in
    a single compiled slice+scatter — pool→slot on a prefix hit
    (the matched prompt prefix's K/V replaces its prefill FLOPs,
    RadixAttention-style — Zheng et al. 2023), slot→pool when a
    freshly prefilled prompt is retained. Source/destination slot and
    direction are traced operands.
  - **speculative verify step** (exactly one program, ``draft`` on):
    the target model scores every slot's ``spec_k`` drafted tokens in
    one chunked dispatch and emits the accepted prefix plus one
    corrected token per slot — 1..``spec_k + 1`` tokens per weights
    read, byte-identical to plain decode by construction (drafts and
    their lengths are traced operands; doc/serving.md "Speculative
    decoding"; Leviathan et al. 2023, prompt-lookup drafting per the
    PLD/lookahead line).

* a host-side **prefix cache** (``serving/prefix.py``): a refcounted-
  LRU trie over token ids maps a new prompt to the longest prefix a
  RETAINED prompt shares with it; retained prompts own slots in a
  reserved on-device pool (same cache layout, extra slot axis rows)
  bounded by ``prefix_cache_mb``. Windowed-ring models bypass it —
  ring eviction invalidates absolute-position reuse (doc/serving.md).

* a host-side scheduler that admits queued requests into freed slots
  BETWEEN device steps (iteration-level / continuous batching — Orca,
  OSDI '22), retires finished sequences, and overlaps host work with
  device execution twice over: prompt h2d staging rides the unified
  depth-k ``io.StagedStream`` helper (PR 2's machinery), and output
  token vectors are drained ``drain_depth`` dispatches behind the
  device, so the step stream never blocks on either edge.

Determinism guarantees (pinned by tests/test_serving.py): greedy
(``temperature=0``) outputs are byte-identical to offline
``Decoder.generate`` per request, regardless of admission order, slot
assignment, co-resident requests, or bucket padding; sampled outputs
depend only on ``(seed, position)`` — not on scheduling.

Robustness (doc/serving.md "Serving under hostile traffic", all
host-side — the compiled program families above are the ONLY
device programs, frozen): per-request deadlines
(``deadline_ms``/``ttft_deadline_ms``) and :meth:`cancel` retire work
at round boundaries through the same dead-slot freeze + slot-recycle
machinery normal retirement uses; ``overload`` policies shed load with
a typed :class:`EngineOverloaded` instead of queueing unboundedly; a
round watchdog (``round_timeout_ms``) turns a wedged device dispatch
into a typed, recoverable :class:`EngineStuck`; per-request host
failures poison only their own request; :meth:`snapshot` /
:meth:`restore` rebuild the scheduler after a crash with
byte-identical continuations; :meth:`close` fails everything pending
with :class:`EngineClosed` and is idempotent.
"""
from __future__ import annotations

import collections
import itertools
import math
import os
import time
import warnings
import weakref

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .. import profiler
from .. import telemetry as tele
from ..io import StagedStream
from ..parallel.decode import Decoder
from .capture import CaptureStream
from .flight import FlightRecorder
from .handoff import HANDOFF_DTYPES, KVHandoff, unpack_rows
from .prefix import PrefixCache
from .spec import NgramDrafter

__all__ = ["InferenceEngine", "Request", "EngineOverloaded",
           "EngineClosed", "EngineStuck"]

# live engines in this process, for the observability plane only: the
# exposition server's /requests, /flight/<id> and /healthz walk this
# set (weak — an engine the caller dropped disappears with it)
_ENGINES = weakref.WeakSet()

# monotonic suffix for auto-assigned engine ids ("e<pid>.<n>"): the
# FleetRouter keys replicas by engine_id, and capture headers carry it
# as provenance, so ids must be unique within a process across
# engine rebuilds (a restore() successor gets a FRESH id; the donor's
# travels in ``migrated_from``)
_ENGINE_SEQ = itertools.count()

# serving-side fault injection (mxnet_tpu.testing.faults): an installed
# injector's hooks run at the engine's host-side seams — h2d/prefill
# admission work, post-dispatch (simulated crash), and the watchdog's
# readiness poll. None in production; never on a device path.
_SERVING_FAULTS = None


class EngineOverloaded(MXNetError):
    """Typed overload signal: raised by ``submit`` under the ``shed``
    policy when the queue is full (and attached as the ``error`` of
    requests evicted by ``shed_oldest``). Callers fail fast and retry
    against another replica instead of queueing into a missed SLO."""


class EngineClosed(MXNetError):
    """The engine was shut down: raised by ``submit``/``step`` after
    :meth:`InferenceEngine.close`, and attached as the ``error`` of
    requests that were still pending when close ran."""


class EngineStuck(MXNetError):
    """Round watchdog trip: a dispatched device round failed to
    materialize within ``round_timeout_ms``. The undrained round stays
    queued — a later ``step()`` retries it if the device recovers;
    otherwise ``snapshot()`` still works (host state only) and
    ``restore()`` resumes every request on a fresh engine."""

# hard bound on reserved prefix-pool slots: the byte budget is the
# real knob; this only stops a tiny model + big budget from minting a
# silly slot axis (256 entries is far past any workload's useful
# distinct-prefix count)
_MAX_POOL_SLOTS = 256

# per-request serving stats (doc/observability.md "serving"): all
# host-side perf_counter arithmetic on values the scheduler already
# tracks — nothing new crosses the device boundary
_TM_QUEUE_WAIT_MS = tele.histogram("serving.queue_wait_ms")
_TM_TTFT_MS = tele.histogram("serving.ttft_ms")
_TM_CADENCE_MS = tele.histogram("serving.token_cadence_ms")
_TM_TOKENS = tele.counter("serving.tokens")
_TM_COMPLETED = tele.counter("serving.completed")
_TM_RETIRED_EOS = tele.counter("serving.retired_eos")
_TM_RETIRED_LENGTH = tele.counter("serving.retired_length")
_TM_ROUNDS = tele.counter("serving.rounds")
# routed experts (MoEFFN top_k > 0 in the batched slot walk): experts
# that were given a token, summed over layers and decode steps, beside
# the layer-steps it was summed over. Counted on the device inside the
# decode program and brought back as one more column of the round's
# tokens: no new sync, no new transfer.
_TM_MOE_TOUCHED = tele.counter("serving.moe_experts_touched")
_TM_MOE_LAYER_STEPS = tele.counter("serving.moe_layer_steps")
# the same walk's token-expert pairs that fell on experts the nodes
# hold (a node may hold a share of its experts), counted on the device
# like the experts touched, beside all the pairs routed over the same
# layers and steps (slots x top_k each: the host's product), and the
# slots that held no request there, whose pairs the walk dropped (they
# route nothing and stream no expert): over slots x layer-steps the
# dead share, 1 - the occupancy
_TM_MOE_PAIRS_HELD = tele.counter("serving.moe_pairs_held")
_TM_MOE_PAIRS_ROUTED = tele.counter("serving.moe_pairs_routed")
_TM_MOE_ROWS_MASKED = tele.counter("serving.moe_rows_masked")
# recurrent state (GatedDeltaNet nodes): slots whose state a decode
# step advanced, summed on the device over those layers and steps (one
# more column), beside slots x layers x steps, the host's product
_TM_STATE_ADVANCED = tele.counter("serving.state_slots_advanced")
_TM_STATE_POOL = tele.counter("serving.state_slots_pool")
_TM_STATE_IN_PLACE = tele.counter("serving.state_steps_in_place")
# cache rows the decode rounds' bounded reads fetched (block-rounded,
# summed over slots, attention layers and steps) and the rows of the
# pool over the same layers and steps: their quotient is the share of
# the cache a decode step reads
_TM_ATTN_ROWS_READ = tele.counter("serving.attn_rows_read")
_TM_ATTN_ROWS_POOL = tele.counter("serving.attn_rows_pool")
# latent rows (LatentAttention nodes): the live slots' TRUE lengths,
# summed on the device over those layers and decode steps (one more
# column): the rows a step's latent reads have to fetch, before blocks
# round them up (the layer-steps are attn_rows_pool's)
_TM_LATENT_ROWS_LIVE = tele.counter("serving.latent_rows_live")
_TM_PREFILLS = tele.counter("serving.prefills")
_TM_ADMITTED = tele.histogram(
    "serving.admitted_per_round", buckets=(0, 1, 2, 4, 8, 16, 32, 64))
_TM_SLOTS_BUSY = tele.histogram(
    "serving.slots_busy_per_round",
    buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128))
_TM_OCCUPANCY = tele.gauge("serving.slot_occupancy")
# prefix cache + chunked prefill (all host-side: the lookup is a trie
# walk, the copy/chunk spans time dispatches — nothing crosses the
# device boundary beyond the programs themselves)
_TM_PREFIX_HITS = tele.counter("serving.prefix_hits")
_TM_PREFIX_MISSES = tele.counter("serving.prefix_misses")
_TM_PREFIX_HIT_TOKENS = tele.counter("serving.prefix_hit_tokens")
_TM_PREFIX_LOOKUP_MS = tele.histogram(
    "serving.prefix_lookup_ms",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0))
_TM_PREFIX_BYTES = tele.gauge("serving.prefix_cache_bytes")
_TM_PREFIX_EVICTIONS = tele.counter("serving.prefix_evictions")
_TM_PREFIX_INSERT_SKIPPED = tele.counter(
    "serving.prefix_insert_skipped")
_TM_CHUNKS = tele.histogram(
    "serving.prefill_chunks_per_request",
    buckets=(1, 2, 4, 8, 16, 32, 64))
# speculative decoding (doc/serving.md "Speculative decoding"): all
# host-side accounting on values the drain already sees — drafted vs
# accepted tokens, the per-slot accepted-length shape, drafter source
# mix, and rounds that fell back to the plain decode program
_TM_SPEC_ROUNDS = tele.counter("serving.spec_rounds")
_TM_SPEC_FALLBACK = tele.counter("serving.spec_fallback_rounds")
_TM_SPEC_DRAFTED = tele.counter("serving.spec_drafted_tokens")
_TM_SPEC_ACCEPTED = tele.counter("serving.spec_accepted_tokens")
_TM_SPEC_ACCEPT_LEN = tele.histogram(
    "serving.spec_accepted_per_step",
    buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16))
_TM_SPEC_NGRAM = tele.counter("serving.spec_drafts_ngram")
_TM_SPEC_MODEL = tele.counter("serving.spec_drafts_model")
# tensor-parallel serving (doc/serving.md "Tensor-parallel serving"):
# info gauges set at construction — the sharding degree (1 = unsharded)
# and each shard's slice of the serving KV cache in bytes (the
# multi-chip win condition: decode is memory-bound, so bytes/shard is
# what scales down with chips). With several engines in one process
# an info gauge reflects the engine built last (the
# one-engine-per-process SLO note applies).
_TM_TP = tele.gauge("serving.tp_degree")
_TM_TP_KV_BYTES = tele.gauge("serving.kv_bytes_per_shard")
# weight-only quantization (doc/serving.md "Quantized weights"): info
# gauges set at construction — the weight storage dtype (0 = float,
# 1 = int8) and the engine's total stored weight bytes (quantized
# entries count int8 values + scales; the draft model's weights, when
# present, are included — they ride the same programs). Engine-last-
# built semantics like serving.tp_degree.
_TM_WEIGHT_DTYPE = tele.gauge("serving.weight_dtype")
_TM_WEIGHT_BYTES = tele.gauge("serving.weight_bytes")
# fused quantized kernels (doc/serving.md "Fused quantized kernels"):
# info gauges set at construction — which matmul impl the quantized
# products trace (0 = dense fori loop, 1 = pallas) and the int4
# per-group scale width (0 = not int4 / auto). Engine-last-built
# semantics like serving.tp_degree.
_TM_MATMUL_IMPL = tele.gauge("serving.matmul_impl")
_TM_WEIGHT_GROUP = tele.gauge("serving.weight_group_size")
# disaggregated prefill/decode (doc/serving.md "Disaggregated
# prefill/decode"): info gauge for the engine's role (0 = unified,
# 1 = prefill, 2 = decode; engine-last-built semantics like
# serving.tp_degree) and the time a FINISHED prefill's package waited
# between export-ready and decode-side admission — the queueing cost
# the split adds in front of decode, observed by the router at
# delivery
_TM_ROLE = tele.gauge("serving.role")
_TM_HANDOFF_WAIT = tele.histogram("serving.handoff_wait_ms")
# compile_counts re-exported as telemetry: the in-engine log stays the
# tested contract; these make recompiles visible in ONE snapshot next
# to everything else
_TM_COMPILE_DECODE = tele.counter("serving.compiles_decode")
_TM_COMPILE_PREFILL = tele.counter("serving.compiles_prefill")
_TM_COMPILE_COPY = tele.counter("serving.compiles_copy")
_TM_COMPILE_VERIFY = tele.counter("serving.compiles_verify")
_TM_COMPILE_DRAFT = tele.counter("serving.compiles_draft")
_TM_COMPILE_HANDOFF = tele.counter("serving.compiles_handoff")
# robustness counters (doc/observability.md): every abnormal retirement
# path is visible in the same snapshot as the latencies it protects
_TM_SHED = tele.counter("serving.shed")
_TM_DEADLINE = tele.counter("serving.deadline_missed")
_TM_CANCELLED = tele.counter("serving.cancelled")
_TM_ERRORS = tele.counter("serving.request_errors")
_TM_WATCHDOG = tele.counter("serving.watchdog_trips")
_TM_RESTORES = tele.counter("serving.restores")
# SLO accounting (doc/observability.md "SLO accounting"): attainment
# counters tick at the same host-side points that feed the TTFT and
# cadence histograms; the burn gauges are multi-window derivatives of
# those histograms (tele.SloWindow), refreshed each round and on every
# exposition-server scrape. Declared with literal names so the metric
# catalog lint sees them.
_TM_SLO_TTFT_OK = tele.counter("serving.slo_ttft_attained")
_TM_SLO_TTFT_MISS = tele.counter("serving.slo_ttft_missed")
_TM_SLO_CAD_OK = tele.counter("serving.slo_cadence_attained")
_TM_SLO_CAD_MISS = tele.counter("serving.slo_cadence_missed")
_SLO_TTFT_WINDOWS = (
    (60.0, tele.gauge("serving.slo_ttft_burn_1m")),
    (300.0, tele.gauge("serving.slo_ttft_burn_5m")),
    (3600.0, tele.gauge("serving.slo_ttft_burn_1h")))
_SLO_CADENCE_WINDOWS = (
    (60.0, tele.gauge("serving.slo_cadence_burn_1m")),
    (300.0, tele.gauge("serving.slo_cadence_burn_5m")),
    (3600.0, tele.gauge("serving.slo_cadence_burn_1h")))
# round-phase attribution (doc/observability.md "Round-phase
# attribution"): where one step()'s wall time went. Every phase is a
# same-thread ``engine._phase(...)`` span (``serving.<name>``, in the
# Chrome capture and on the profiler's host plane alike); "sched" is
# the unattributed remainder (host scheduling — sweeps, queue
# bookkeeping, chunk math), so the phases SUM to the round wall time
# by construction. Sub-ms buckets: decode rounds are ms-scale.
_PHASE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                  25.0, 50.0, 100.0, 500.0, 5000.0)
_TM_PHASE = {
    "sched": tele.histogram("serving.round_phase_ms.sched",
                            buckets=_PHASE_BUCKETS),
    "prefix_lookup": tele.histogram(
        "serving.round_phase_ms.prefix_lookup",
        buckets=_PHASE_BUCKETS),
    "h2d": tele.histogram("serving.round_phase_ms.h2d",
                          buckets=_PHASE_BUCKETS),
    "prefill": tele.histogram("serving.round_phase_ms.prefill",
                              buckets=_PHASE_BUCKETS),
    "copy": tele.histogram("serving.round_phase_ms.copy",
                           buckets=_PHASE_BUCKETS),
    "dispatch": tele.histogram("serving.round_phase_ms.dispatch",
                               buckets=_PHASE_BUCKETS),
    "drain": tele.histogram("serving.round_phase_ms.drain",
                            buckets=_PHASE_BUCKETS),
}
_TM_ROUND_WALL = tele.histogram("serving.round_wall_ms",
                                buckets=_PHASE_BUCKETS)
# bounded per-engine ledger of recent rounds (GET /rounds); the
# histograms above are the fleet view, the ledger is the incident view
_ROUND_LEDGER = 256


# the spans whose seconds land under another name in the phase ledger
# (every other span is the phase of its own name)
_PHASE_OF = {"decode_round": "dispatch", "verify_round": "dispatch",
             "draft_round": "dispatch", "prefix_copy": "copy",
             "handoff_export": "copy", "handoff_import": "copy"}


class _Phase(tele.span):
    """One phase of a round: the telemetry span ``serving.<name>`` whose
    seconds are also added to the in-flight round's phase ledger (a
    no-op outside ``step()`` — e.g. a submit-path h2d), under
    ``_PHASE_OF[name]`` where that differs from the span's name. The
    ledger is charged from the phase's creation to the end of its exit,
    the span's own bookkeeping included, so that the round's remainder
    (``sched``) holds the scheduler's work and not the instrumentation's."""

    __slots__ = ("_engine", "_key", "_made")

    def __init__(self, engine, name, hist, args):
        self._made = time.perf_counter()
        super().__init__("serving." + name, cat="serving", hist=hist,
                         **args)
        self._engine = engine
        self._key = _PHASE_OF.get(name, name)

    def __exit__(self, *exc):
        super().__exit__(*exc)
        acc = self._engine._phase_acc
        if acc is not None:
            acc[self._key] = acc.get(self._key, 0.0) \
                + time.perf_counter() - self._made
        return False


class Request:
    """One submitted generation request (handle returned by
    :meth:`InferenceEngine.submit`).

    ``tokens`` fills in as output drains: generated ids only (no
    prompt echo), including ``eos_id`` when hit. ``done`` flips when
    the sequence retires; ``result()`` returns the tokens as int32
    numpy. Latency probes: ``t_submit``/``t_admit``/``t_first``/
    ``t_done`` (perf_counter seconds; admit = slot assigned + prefill
    dispatched; first = first token DRAINED, i.e. visible to the
    caller, not merely computed). ``retire_reason`` once done is
    ``"eos"`` / ``"length"`` (normal completion), ``"deadline"`` /
    ``"cancelled"`` (host-retired, ``result()`` returns the tokens
    generated so far), or ``"shed"`` / ``"error"`` / ``"closed"``
    (failed — ``result()`` raises the typed ``error``; partial tokens
    stay readable on ``.tokens``). ``prefix_hit_tokens`` counts prompt
    positions whose K/V came from the prefix cache instead of prefill
    FLOPs; ``prefill_chunks`` how many prefill dispatches admitted the
    prompt (1 unless ``prefill_chunk`` split it). The same breakdown
    feeds the ``serving.*`` telemetry histograms (queue wait / TTFT /
    per-token cadence / prefix + chunk stats — doc/observability.md).
    """

    def __init__(self, rid, prompt, max_tokens, eos_id, temperature,
                 seed, limit, deadline_ms=None, ttft_deadline_ms=None,
                 resume_tokens=()):
        self.id = rid
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.eos_id = eos_id
        self.temperature = temperature
        self.seed = seed
        self.limit = limit          # min(max_tokens, max_len - P)
        # tokens already emitted by a pre-crash engine (restore());
        # ``seq`` is what admission prefills — re-prefilling the
        # emitted suffix puts every position's draw key back where the
        # uninterrupted run had it (byte-identical continuations)
        self.tokens = list(int(t) for t in resume_tokens)
        self.resumed = len(self.tokens)
        self.seq = prompt if not self.resumed else np.concatenate(
            [prompt, np.asarray(self.tokens, np.int32)])
        self.done = False
        self.error = None
        self.t_submit = time.perf_counter()
        self.t_admit = None
        self.t_first = None
        self.t_done = None
        self.retire_reason = None
        self.prefix_hit_tokens = 0
        self.prefill_chunks = 0
        self.deadline_ms = deadline_ms
        self.ttft_deadline_ms = ttft_deadline_ms
        # fleet trace context: (trace_id, hop) when a FleetRouter
        # minted this request's identity, None for direct submits
        self.trace = None
        self._deadline = None if deadline_ms is None \
            else self.t_submit + deadline_ms / 1e3
        self._ttft_deadline = None if ttft_deadline_ms is None \
            else self.t_submit + ttft_deadline_ms / 1e3
        self._cancelled = False

    def _expired(self, now):
        """Which deadline (if any) has passed — checked at round
        boundaries and at admission pop (host clock only)."""
        if self._deadline is not None and now >= self._deadline:
            return True
        return self._ttft_deadline is not None and self.t_first is None \
            and now >= self._ttft_deadline

    def result(self):
        if not self.done:
            raise MXNetError("request %s is not finished" % self.id)
        if self.error is not None:
            raise self.error
        return np.asarray(self.tokens, np.int32)

    def __repr__(self):
        return ("Request(id=%r, prompt_len=%d, max_tokens=%d, done=%s, "
                "generated=%d)" % (self.id, len(self.prompt),
                                   self.max_tokens, self.done,
                                   len(self.tokens)))


class _PlacementError:
    """Marker riding a staged ``(req, dev)`` tuple when
    ``_place_prompt`` failed: admission retires the request with the
    carried error instead of serving it."""

    def __init__(self, error):
        self.error = error


class _PendingSource:
    """StagedStream source over the engine's pending deque (empty deque
    = StopIteration; the stream runs ``live_source`` mode, so submits
    arriving later are staged by the very next fill)."""

    def __init__(self, dq):
        self._dq = dq

    def next(self):
        if not self._dq:
            raise StopIteration
        return self._dq.popleft()

    def reset(self):
        pass


def _default_buckets(max_len):
    out, b = [], 16
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def _raw_key(seed):
    """threefry PRNGKey layout without dispatching a device op (the
    compile-count contract stays clean): [hi32, lo32] of the seed."""
    seed = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


class InferenceEngine:
    """Continuous-batching serving loop over a :class:`Decoder`.

    Parameters
    ----------
    decoder : Decoder
        The derived incremental program (any cache flavor: bf16/int8
        ``cache_dtype``, sliding-window models, GQA, rope). Build one
        with ``Decoder(symbol, params, max_len=...)`` or use
        :meth:`from_checkpoint` / ``FeedForward.as_serving_engine``.
        Which read the decode / verify / draft programs take follows
        the decoder's cache kind (the table above
        ``Decoder._cached_mha``; doc/serving.md "The decode read").
    slots : int
        ``S``, the resident-sequence capacity — the continuous batch
        size and the cache's slot-axis length. Throughput knob: decode
        cost per step is roughly flat until the chip saturates, so
        more slots = more tokens per step (tools/bench_serving.py
        sweeps it).
    prefill_buckets : tuple of int, optional
        Ascending prompt-padding lengths; a prompt takes the smallest
        bucket >= its length (default: powers of two from 16, capped
        at ``max_len``). One prefill program compiles per bucket
        actually used — the whole compile budget is
        ``len(buckets) + 1``.
    max_queue : int
        Backpressure bound on submitted-but-not-admitted requests;
        ``submit`` raises ``MXNetError`` beyond it.
    stage_depth : int
        Depth of the prompt h2d stager (``io.StagedStream``).
    drain_depth : int
        How many step outputs may remain un-drained while work is in
        flight — the d2h analogue of ``stage_depth``. Retirement is
        discovered at drain time, so a slot frees at most
        ``drain_depth`` rounds after its sequence finished (the device
        freezes finished slots in the meantime).
    steps_per_round : int
        Tokens decoded per dispatched round: the decode program is a
        ``lax.scan`` of this many fused all-slots steps, amortizing
        the per-dispatch host overhead k-fold (one jit call,
        one [k, S] output drain per k tokens). Admission/retirement
        granularity coarsens to k tokens — a slot freed mid-round sits
        frozen until the round ends, so k should stay well under the
        typical output length (k=1 is latency-optimal per-token
        scheduling; the chip-facing bench uses 8). Still ONE compiled
        decode program either way.
    prefix_cache_mb : float, optional
        Byte budget (MiB) for the prefix-reuse pool: prompts are
        retained as on-device K/V rows in a reserved slot pool, and a
        new request whose prompt shares a prefix with a retained one
        gets that prefix COPIED into its slot (one compiled copy per
        bucket) instead of re-prefilled — shared system prompts stop
        paying their FLOPs per request. Default: the
        ``MXNET_SERVING_PREFIX_CACHE_MB`` env var, else 64. ``0``
        disables. Pool slots = budget // per-slot cache bytes (capped
        at 256); eviction is refcounted LRU. Windowed-ring decoders
        bypass the cache automatically (ring eviction invalidates
        absolute-position reuse — doc/serving.md). Greedy outputs stay
        byte-identical with the cache on or off.
    prefill_chunk : int, optional
        Chunked-prefill bound: a prompt (suffix) longer than this many
        tokens is admitted as a SEQUENCE of chunk-sized prefill
        dispatches interleaved with decode rounds, under a per-round
        prefill budget of one chunk shared by all in-flight admissions
        — resident decode slots stall ~one chunk of prefill work per
        round, not one whole prompt (nor a burst of them): the p99
        token-cadence lever under long-prompt traffic. Also lifts the
        submit length cap from the largest bucket to ``max_len - 1``
        (pieces only need the chunk to fit a bucket). Default: the
        ``MXNET_SERVING_PREFILL_CHUNK`` env var, else 0 (= monolithic
        prefill). Uses the SAME bucketed prefill programs (chunk start
        is a traced operand); greedy outputs stay byte-identical
        across any chunk boundary.
    overload : {"block", "shed", "shed_oldest"}, optional
        What a full queue does to ``submit`` (default: the
        ``MXNET_SERVING_OVERLOAD`` env var, else ``"block"``).
        ``block`` keeps the PR 3 backpressure contract (generic
        ``MXNetError``; callers drive ``step`` to drain). ``shed``
        fails the NEW request fast with a typed
        :class:`EngineOverloaded` — the router-facing policy: a
        rejected request can retry elsewhere instead of aging into a
        missed SLO. ``shed_oldest`` evicts the oldest QUEUED (never
        admitted) request instead — freshest-work-wins under bursts.
        Under either shedding policy the engine also degrades
        gracefully while the queue is full: admitted work keeps
        priority (the chunking queue always ran first) and
        prefix-cache RETENTION pauses, so slot-to-pool copy dispatches
        stop competing with serving work under pressure.
    round_timeout_ms : float, optional
        Round watchdog (default: ``MXNET_SERVING_ROUND_TIMEOUT_MS``
        env var, else 0 = off): when draining a dispatched round, the
        engine polls device-buffer readiness host-side and raises a
        typed :class:`EngineStuck` after this long instead of blocking
        ``serve_forever`` forever on a wedged dispatch. The undrained
        round stays queued — a later ``step()`` retries (transient
        stall), or ``snapshot()``/``restore()`` move the requests to a
        fresh engine (real wedge). Mutable attribute; size it well
        above the worst legitimate round (compiles excepted — first
        rounds trace).
    slo_ttft_ms / slo_cadence_ms : float, optional
        Per-engine SLO targets (defaults: ``MXNET_SERVING_SLO_TTFT_MS``
        / ``MXNET_SERVING_SLO_CADENCE_MS`` env vars, else unset = no
        SLO accounting): a request whose time-to-first-token (resp.
        steady per-token cadence) beats the target ticks
        ``serving.slo_*_attained``, otherwise ``_missed``; multi-window
        burn-rate gauges (``serving.slo_*_burn_{1m,5m,1h}``) are
        derived from the existing latency histograms each round and on
        every ``/metrics`` scrape. Measurement only — nothing here
        changes scheduling (that is ROADMAP item 5's job). Mutable
        attributes. ``slo_target`` (default 0.99) is the attainment
        objective the burn rates are normalized against.
    spec_k : int, optional
        Draft length for speculative decoding (default: the
        ``MXNET_SERVING_SPEC_K`` env var, else 4; only meaningful with
        ``draft != "off"``). Each verify round the target model scores
        up to ``spec_k`` drafted tokens per slot in ONE chunked
        dispatch and emits the accepted prefix plus one corrected
        token — up to ``spec_k + 1`` tokens per weights read instead
        of 1. Raising it helps only while drafts keep getting
        accepted; rejected positions are wasted chunk width.
    draft : {"off", "ngram", "model"}, optional
        Drafting source (default: the ``MXNET_SERVING_DRAFT`` env var,
        else ``"off"``). ``"ngram"`` is the host-side prompt-lookup
        drafter (:class:`~mxnet_tpu.serving.NgramDrafter` — no second
        model: propose the continuation that followed the current
        suffix earlier in the request's own prompt + output).
        ``"model"`` drafts with a small draft LM (pass
        ``draft_decoder``) sharing the slot-paged layout — one greedy
        k-token proposal program plus its own per-bucket prefill.
        Greedy outputs are byte-identical to ``draft="off"`` either
        way (the target verifies every token in-program); sampled
        requests accept a draft token only when it matches the
        target's own ``fold_in(seed, position)`` draw, so the sampled
        identity is preserved too (acceptance just gets rarer at hot
        temperatures). Windowed-ring decoders refuse speculation
        loudly (a ``UserWarning``; the chunk write would wrap rejected
        drafts onto live ring rows — same bypass precedent as the
        prefix cache) and serve with ``draft="off"``.
    draft_decoder : Decoder, optional
        The draft model for ``draft="model"`` (e.g. the 124M config
        drafting for a 350M target, loaded from its own checkpoint —
        ``from_checkpoint(draft_prefix=..., draft_epoch=...)`` builds
        it for you). Must share ``max_len`` and be non-windowed; its
        vocabulary must cover the target's token ids.
    flight_recorder : int, optional
        How many RETIRED requests keep their full flight-recorder
        timeline (submit → staged → admitted → prefix hit/copy →
        prefill chunks → sampled decode progress → retire reason) for
        post-hoc reconstruction via ``engine.flight.timeline(id)`` or
        ``GET /flight/<id>``. Default: the
        ``MXNET_SERVING_FLIGHT_RECORDER`` env var, else 256; 0
        disables recording. Host-side, bounded (doc/observability.md
        "The flight recorder").
    capture_dir : str, optional
        Traffic capture (the serving time machine's record half —
        doc/observability.md): when set (default: the
        ``MXNET_SERVING_CAPTURE_DIR`` env var, else off), the engine
        appends a crash-safe JSONL record per accepted submit (arrival
        time, prompt, sampling identity, deadlines) and per retirement
        (emitted tokens, reason, TTFT/cadence) to its own
        ``mx_capture_<pid>_<n>.jsonl`` in this directory, size-bounded
        by ``MXNET_SERVING_CAPTURE_MB`` (default 64; ``capture_mb``
        overrides). ``tools/replay_serving.py`` replays a capture
        byte-identically on a fresh engine — any config change can be
        validated offline against yesterday's traffic
        (``--verify``). Flushed per record: a killed process leaves a
        readable log. ``snapshot()`` carries the knob, so capture
        continues across a crash cycle (fresh file, same directory).
    tp : int, optional
        Tensor-parallel degree (default: the ``MXNET_SERVING_TP`` env
        var, else 1 = unsharded): the slot-paged KV cache — int8
        scales and draft-model caches included — is sharded over a
        ``tp``-device mesh's ``model`` axis on the KV-HEAD dimension,
        and every compiled program family (decode, bucketed prefill,
        per-bucket copy, verify, draft, draft_prefill) runs as ONE
        shard_map program: each device computes its heads' attention
        against its cache shard and everything else replicated at
        tp=1's exact shapes, with one all-gather per attention node
        as the only collective. One engine serves a model whose KV
        footprint exceeds a chip, and decode's per-shard cache
        traffic drops ~1/tp (doc/serving.md "Tensor-parallel
        serving"). Greedy outputs are byte-identical to tp=1 across
        the whole feature gauntlet (logits land replicated, so
        host-side sampling identity is untouched); the compile-count
        contract is unchanged. Every attention node's kv heads must
        divide ``tp`` evenly (GQA groups stay whole per shard —
        refused loudly otherwise). The bounded read composes: each
        shard runs the Pallas kernel against its local cache shard
        (a per-shard kv-head grid), so the live-rows cut and the
        per-shard cut multiply. ``snapshot()``/``restore()``
        carry the degree.
    mesh : jax.sharding.Mesh, optional
        Serve over an existing mesh instead of building one: must
        carry a ``model`` axis (its size is the tp degree;
        ``parallel.model_parallel_mesh`` builds the canonical
        single-axis one). Mutually consistent with ``tp`` when both
        are given.
    weight_dtype : {"float", "int8"}, optional
        Weight storage for the engine's programs (default: the
        decoder's own ``weight_dtype``, itself defaulted from
        ``MXNET_SERVING_WEIGHT_DTYPE``, else ``"float"``). ``"int8"``
        quantizes the engine's OWN copy of every matmul weight —
        attention QKV/out projections, the MLP and unembedding
        FullyConnecteds, Embedding tables, MoE gate/expert stacks,
        and the draft model's weights when ``draft="model"`` — to
        int8 with per-output-channel f32 scales (LayerNorm and biases
        stay float), and every compiled program family dequantizes ON
        THE FLY inside a chunked scale-fused matmul (no float weight
        copy is ever materialized), so decode reads the weight stream
        at 1 byte/elem — the serving-batch bytes/token lever, and
        more resident slots per HBM byte. The decoder object stays
        float, so one weight set serves a quantized engine next to
        its fp oracle. Greedy outputs are argmax-stable vs. the fp
        engine on the tested configs (tolerance-bounded in general —
        the int8-KV contract); quantized engines stay byte-identical
        ACROSS their own gauntlet (tp degrees, admission orders,
        speculation, snapshot/restore). Composes with everything:
        tp>1 (scales replicate with their weights), int8 KV, the
        bounded read, prefix cache, chunked prefill, both speculation
        modes, capture/replay. ``snapshot()``/``restore()`` and the
        capture header carry the knob. doc/serving.md "Quantized
        weights".
    """

    def __init__(self, decoder, slots=8, prefill_buckets=None,
                 max_queue=256, stage_depth=2, drain_depth=2,
                 steps_per_round=1, prefix_cache_mb=None,
                 prefill_chunk=None, overload=None,
                 round_timeout_ms=None, slo_ttft_ms=None,
                 slo_cadence_ms=None, slo_target=0.99,
                 flight_recorder=None, spec_k=None, draft=None,
                 draft_decoder=None, capture_dir=None,
                 capture_mb=None, tp=None, mesh=None,
                 weight_dtype=None, weight_group=None, matmul_impl=None,
                 ep=None, engine_id=None, migrated_from=None,
                 role=None, handoff_dtype=None):
        if not isinstance(decoder, Decoder):
            raise MXNetError("InferenceEngine needs a Decoder, got %r"
                             % type(decoder).__name__)
        self._dec = decoder
        self._t0 = time.perf_counter()   # ledger/capture time origin
        # fleet identity: engine_id names this replica (FleetRouter
        # rotation key, capture-header provenance); migrated_from is
        # the donor's id when this engine was built by restore() from
        # another engine's snapshot — requests it finishes attribute
        # to the replica lineage that served them
        self.engine_id = str(engine_id) if engine_id is not None \
            else "e%d.%d" % (os.getpid(), next(_ENGINE_SEQ))
        self.migrated_from = None if migrated_from is None \
            else str(migrated_from)
        # drain state: set by FleetRouter.drain (or an operator)
        # before migration — admission stops routing here and
        # /healthz reports it, distinct from stuck/closed
        self.draining = False
        self.max_len = decoder.max_len
        self.slots = int(slots)
        if self.slots < 1:
            raise MXNetError("InferenceEngine: slots must be >= 1")
        if prefill_buckets is None:
            prefill_buckets = _default_buckets(self.max_len)
        buckets = tuple(int(b) for b in prefill_buckets)
        if not buckets or list(buckets) != sorted(set(buckets)) \
                or buckets[0] < 1 or buckets[-1] > self.max_len:
            raise MXNetError(
                "InferenceEngine: prefill_buckets must be strictly "
                "ascending lengths in [1, max_len], got %r" % (buckets,))
        self.prefill_buckets = buckets
        self.max_queue = int(max_queue)
        self._drain_depth = max(0, int(drain_depth))
        self.steps_per_round = int(steps_per_round)
        if self.steps_per_round < 1:
            raise MXNetError("InferenceEngine: steps_per_round must "
                             "be >= 1")
        if prefill_chunk is None:
            prefill_chunk = int(os.environ.get(
                "MXNET_SERVING_PREFILL_CHUNK", "0") or 0)
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise MXNetError("InferenceEngine: prefill_chunk must be "
                             ">= 0 (0 disables chunking)")
        if self.prefill_chunk > buckets[-1]:
            raise MXNetError(
                "InferenceEngine: prefill_chunk=%d exceeds the largest "
                "prefill bucket %d — every chunk piece must fit a "
                "bucket program" % (self.prefill_chunk, buckets[-1]))
        if overload is None:
            overload = os.environ.get("MXNET_SERVING_OVERLOAD") \
                or "block"
        if overload not in ("block", "shed", "shed_oldest"):
            raise MXNetError(
                "InferenceEngine: overload must be 'block', 'shed' or "
                "'shed_oldest', got %r (MXNET_SERVING_OVERLOAD sets "
                "the default)" % (overload,))
        self.overload = overload
        if round_timeout_ms is None:
            round_timeout_ms = float(os.environ.get(
                "MXNET_SERVING_ROUND_TIMEOUT_MS") or "0")
        self.round_timeout_ms = float(round_timeout_ms)
        if self.round_timeout_ms < 0:
            raise MXNetError("InferenceEngine: round_timeout_ms must "
                             "be >= 0 (0 disables the watchdog)")
        if slo_ttft_ms is None:
            slo_ttft_ms = os.environ.get("MXNET_SERVING_SLO_TTFT_MS")
            slo_ttft_ms = float(slo_ttft_ms) if slo_ttft_ms else None
        if slo_cadence_ms is None:
            slo_cadence_ms = os.environ.get(
                "MXNET_SERVING_SLO_CADENCE_MS")
            slo_cadence_ms = float(slo_cadence_ms) if slo_cadence_ms \
                else None
        for nm, v in (("slo_ttft_ms", slo_ttft_ms),
                      ("slo_cadence_ms", slo_cadence_ms)):
            if v is not None and not v > 0:
                raise MXNetError("InferenceEngine: %s must be > 0 "
                                 "(None disables SLO accounting), got "
                                 "%r" % (nm, v))
        if not 0.0 < float(slo_target) < 1.0:
            raise MXNetError("InferenceEngine: slo_target must be in "
                             "(0, 1), got %r" % (slo_target,))
        self.slo_ttft_ms = slo_ttft_ms
        self.slo_cadence_ms = slo_cadence_ms
        self.slo_target = float(slo_target)
        self._slo_windows = {}
        if flight_recorder is None:
            flight_recorder = int(os.environ.get(
                "MXNET_SERVING_FLIGHT_RECORDER", "") or 256)
        if int(flight_recorder) < 0:
            raise MXNetError("InferenceEngine: flight_recorder must "
                             "be >= 0 (0 disables the recorder)")
        self.flight = FlightRecorder(retain=int(flight_recorder))
        self.stage_depth = int(stage_depth)

        # tensor-parallel serving (doc/serving.md "Tensor-parallel
        # serving"): resolve the mesh/degree FIRST — the cache layout,
        # the replicated parameter placement and every compiled
        # program's shard_map wrapper depend on it
        if mesh is None and tp is None:
            tp = int(os.environ.get("MXNET_SERVING_TP", "") or 1)
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise MXNetError(
                    "InferenceEngine: mesh=... needs a 'model' axis "
                    "to shard the KV cache over (axes: %r) — "
                    "parallel.model_parallel_mesh builds one"
                    % (mesh.axis_names,))
            if tp is not None and int(tp) != int(mesh.shape["model"]):
                raise MXNetError(
                    "InferenceEngine: tp=%r disagrees with the mesh's "
                    "model axis size %d — pass one or the other"
                    % (tp, mesh.shape["model"]))
            tp = int(mesh.shape["model"])
        tp = int(tp)
        if tp < 1:
            raise MXNetError("InferenceEngine: tp must be >= 1 "
                             "(1 = unsharded; MXNET_SERVING_TP sets "
                             "the default), got %d" % tp)
        # expert-parallel MoE (doc/serving.md "Expert-parallel MoE"):
        # an "expert" mesh axis composed with tp — the per-expert
        # weight stacks (the largest tensors in a MoE config) shard
        # on their leading expert axis instead of replicating per
        # shard; moe_ffn_math gathers gate logits / psums the combine
        if ep is None:
            ep = int(os.environ.get("MXNET_SERVING_EP", "") or 1)
        ep = int(ep)
        if ep < 1:
            raise MXNetError("InferenceEngine: ep must be >= 1 "
                             "(1 = no expert sharding; "
                             "MXNET_SERVING_EP sets the default), "
                             "got %d" % ep)
        # the decoder has a state leaf (a CCAttention's rolling state
        # beside its rows, a GatedDeltaNet's recurrent state instead of
        # rows); every feature that cannot carry it refuses by the
        # op's name, here and below (ROADMAP D4: no silent fallback)
        rolling = decoder.has_state
        if rolling and tp > 1:
            decoder.refuse_rolling_state("tp=%d" % tp)
        # latent rows (a LatentAttention's one buffer, no head axis):
        # the same rule, by their own name
        latent = decoder.has_latent
        if latent and tp > 1:
            decoder.refuse_latent_rows("tp=%d" % tp)
        if ep > 1:
            decoder.refuse_given_router("ep=%d" % ep)
        moe_nodes = [n for n in decoder._topo
                     if not n.is_var and n.spec.name == "MoEFFN"]
        if ep > 1:
            if not moe_nodes:
                raise MXNetError(
                    "InferenceEngine: ep=%d needs a MoE decoder — no "
                    "MoEFFN node to shard experts over" % ep)
            for n in moe_nodes:
                nx = int(n.params["num_experts"])
                if nx % ep:
                    raise MXNetError(
                        "InferenceEngine: ep=%d must divide "
                        "num_experts=%d (node %r) — the expert stacks "
                        "shard their leading axis evenly"
                        % (ep, nx, n.name))
            if mesh is not None:
                if "expert" not in mesh.axis_names \
                        or int(mesh.shape["expert"]) != ep:
                    raise MXNetError(
                        "InferenceEngine: ep=%d disagrees with the "
                        "mesh's expert axis (axes: %r) — "
                        "parallel.build_mesh({'expert': ep, 'model': "
                        "tp}) builds a composed mesh"
                        % (ep, mesh.axis_names))
            else:
                from ..parallel.mesh import build_mesh
                mesh = build_mesh({"expert": ep, "model": tp})
        elif tp > 1 and mesh is None:
            from ..parallel.mesh import model_parallel_mesh
            mesh = model_parallel_mesh(tp)
        self.tp = tp
        self.ep = ep
        self._mesh = mesh if (tp > 1 or ep > 1) else None
        self._expert_names = set()
        if ep > 1:
            for n in moe_nodes:
                for inp, _ in n.inputs[1:]:
                    self._expert_names.add(inp.name)
        # weight-only quantization (doc/serving.md "Quantized
        # weights"): resolve BEFORE parameter placement — an int8
        # engine over a float decoder quantizes its OWN parameter
        # copy, so the decoder (and its offline oracle programs)
        # stays float and one weight set serves a quantized engine
        # next to its fp oracle (the identity tests do)
        if weight_dtype is None:
            weight_dtype = decoder.weight_dtype
        if weight_dtype not in ("float", "int8", "int4"):
            raise MXNetError(
                "InferenceEngine: weight_dtype must be 'float', "
                "'int8' or 'int4', got %r (MXNET_SERVING_WEIGHT_DTYPE "
                "sets the default)" % (weight_dtype,))
        if weight_dtype == "float" and decoder.weight_dtype != "float":
            raise MXNetError(
                "InferenceEngine: weight_dtype='float' over a Decoder "
                "built with weight_dtype='int8' — the float weights "
                "are gone; build the decoder float (the engine "
                "quantizes its own copy)")
        if decoder.weight_dtype != "float" \
                and weight_dtype != decoder.weight_dtype:
            raise MXNetError(
                "InferenceEngine: weight_dtype=%r over a Decoder "
                "already quantized to %r — re-flavoring quantized "
                "weights would re-round; build the decoder float (the "
                "engine quantizes its own copy)"
                % (weight_dtype, decoder.weight_dtype))
        if weight_dtype != "float":
            if rolling:
                decoder.refuse_rolling_state("weight_dtype=%r"
                                             % (weight_dtype,))
            if latent:
                decoder.refuse_latent_rows("weight_dtype=%r"
                                           % (weight_dtype,))
            decoder.refuse_given_router("weight_dtype=%r"
                                        % (weight_dtype,))
        self.weight_dtype = weight_dtype
        if weight_group is None:
            weight_group = decoder.weight_group
        self.weight_group = weight_group
        params, auxs = decoder._params, decoder._aux
        if weight_dtype != "float" and decoder.weight_dtype == "float":
            from .quant import quantize_params, quantized_weight_names
            params = quantize_params(
                params, quantized_weight_names(decoder._topo),
                bits=8 if weight_dtype == "int8" else 4,
                group=weight_group,
                row_quant=decoder._embedding_weight_names())
        if weight_dtype == "int4" and self.weight_group is None:
            # representative group for the gauges/geometry when the
            # engine quantized its own copy under the auto pick: read
            # it off a quantized matmul weight (the E-axis resolution
            # Decoder records when IT quantizes)
            from .quant import QuantizedTensor as _QT
            for v in params.values():
                if isinstance(v, _QT) and v.bits == 4:
                    self.weight_group = v.group
                    break
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from ..ops.attention import MultiHeadAttention as _MHA
            # GQA head partitioning must divide evenly or refuse
            # loudly — an uneven split would give shards different
            # compute shapes and break the replicated-prefix
            # byte-identity argument
            for n in decoder._mha:
                _MHA.check_head_shards(n.params, tp)
            self._kv_shard = NamedSharding(
                self._mesh, PartitionSpec(None, None, "model"))
            rep = NamedSharding(self._mesh, PartitionSpec())
            self._rep_shard = rep
            # the engine's OWN parameter placement (see the
            # weight_dtype note above for why the decoder object is
            # never touched); QuantizedTensor entries are pytrees, so
            # device_put replicates their int8 values and scales alike.
            # Under ep>1 the MoE expert stacks shard their LEADING
            # expert axis instead of replicating — the whole point of
            # the expert mesh axis (quantized stacks shard values and
            # scales alike: both carry the expert axis first)
            exp = NamedSharding(self._mesh, PartitionSpec("expert")) \
                if ep > 1 else rep
            self._params = {
                k: jax.device_put(v, exp if k in self._expert_names
                                  else rep)
                for k, v in params.items()}
            self._aux = [jax.device_put(v, rep) for v in auxs]
        else:
            self._kv_shard = None
            self._rep_shard = None
            self._params, self._aux = params, auxs
        _TM_TP.set(tp)

        # device-resident: the slot-paged cache + per-slot state vectors
        S = self.slots
        self._caches = decoder.init_cache(S, kv_sharding=self._kv_shard)
        self._state = (
            jnp.zeros((S,), jnp.int32),        # pos: next write position
            jnp.zeros((S,), jnp.int32),        # tok: last sampled token
            jnp.zeros((S,), bool),             # live
            jnp.zeros((S,), jnp.float32),      # temperature
            jnp.zeros((S, 2), jnp.uint32),     # rng key
            jnp.full((S,), -1, jnp.int32),     # eos id (-1: none)
            jnp.zeros((S,), jnp.int32),        # last allowed position
        )
        if self._mesh is not None:
            self._state = tuple(jax.device_put(s, self._rep_shard)
                                for s in self._state)

        # prefix-reuse pool: a SEPARATE cache tree of pool slots (same
        # per-slot layout) holding retained prompt K/V. Separate, not
        # extra rows on the serving tree, so the fused decode step
        # keeps vmapping over exactly S lanes — pool size must never
        # tax the per-token path.
        if prefix_cache_mb is None:
            prefix_cache_mb = os.environ.get(
                "MXNET_SERVING_PREFIX_CACHE_MB")
            if prefix_cache_mb is None or prefix_cache_mb == "":
                # the default pool; none where a rolling state would
                # have to be snapshotted with the rows (asked for by
                # value, it refuses below)
                prefix_cache_mb = 0 if rolling or latent else 64
        self.prefix_cache_mb = float(prefix_cache_mb)
        if rolling and self.prefix_cache_mb > 0:
            decoder.refuse_rolling_state(
                "prefix_cache_mb=%g (the prefix pool copies rows)"
                % self.prefix_cache_mb)
        if latent and self.prefix_cache_mb > 0:
            decoder.refuse_latent_rows(
                "prefix_cache_mb=%g (the prefix pool sizes and copies "
                "K/V rows)" % self.prefix_cache_mb)
        if self.prefix_cache_mb < 0:
            raise MXNetError("InferenceEngine: prefix_cache_mb must "
                             "be >= 0 (0 disables the prefix cache)")
        self._windowed = any(decoder._node_window(n)
                             for n in decoder._mha)
        # fused quantized kernels (doc/serving.md "Fused quantized
        # kernels"): which impl the quantized matmuls trace — threaded
        # into every Decoder._run_slots/_run dispatch. "pallas" runs the same
        # output-channel partition as "dense" through the Pallas
        # kernel and agrees with it to f32 rounding (token-level
        # identity is what the gauntlet pins, not bits —
        # tests/test_pallas_quant.py)
        if matmul_impl is None:
            matmul_impl = decoder._matmul_impl
        if matmul_impl not in ("dense", "pallas"):
            raise MXNetError(
                "InferenceEngine: matmul_impl must be 'dense' or "
                "'pallas', got %r (MXNET_SERVING_MATMUL_IMPL sets the "
                "default)" % (matmul_impl,))
        self.matmul_impl = matmul_impl
        # disaggregated prefill/decode (doc/serving.md "Disaggregated
        # prefill/decode"): role gates which program families ever
        # DISPATCH — a prefill engine runs admission + prefill only
        # and hands finished KV off; a decode engine admits handoffs
        # only and never traces a prefill program (a compile-memory
        # win the compile contract pins). Purely a scheduler gate: the
        # jit families are lazy, so nothing extra compiles either way.
        if role is None:
            role = os.environ.get("MXNET_SERVING_ROLE") or "unified"
        if role not in ("unified", "prefill", "decode"):
            raise MXNetError(
                "InferenceEngine: role must be 'unified', 'prefill' "
                "or 'decode', got %r (MXNET_SERVING_ROLE sets the "
                "default)" % (role,))
        if role != "unified" and rolling:
            decoder.refuse_rolling_state(
                "role=%r (the KV handoff ships rows)" % (role,))
        if role != "unified" and latent:
            decoder.refuse_latent_rows(
                "role=%r (the KV handoff ships K/V rows)" % (role,))
        if role != "unified" and self._windowed:
            raise MXNetError(
                "InferenceEngine: windowed-ring decoders do not "
                "compose with role=%r — ring rows live at wrapped "
                "positions, outside the [0, P) prefix contract the "
                "KV handoff rows ride (slot_prefix_rows); serve "
                "unified" % (role,))
        self.role = role
        if handoff_dtype is None:
            handoff_dtype = os.environ.get(
                "MXNET_SERVING_HANDOFF_DTYPE") or "native"
        if handoff_dtype not in HANDOFF_DTYPES:
            raise MXNetError(
                "InferenceEngine: handoff_dtype must be one of %s, "
                "got %r (MXNET_SERVING_HANDOFF_DTYPE sets the "
                "default)" % (", ".join(map(repr, HANDOFF_DTYPES)),
                              handoff_dtype))
        self.handoff_dtype = handoff_dtype
        _TM_ROLE.set({"unified": 0, "prefill": 1, "decode": 2}[role])
        slot_bytes = sum(x.nbytes for x in
                         jax.tree_util.tree_leaves(self._caches)) // S
        # per-shard KV residency (jax Array.nbytes is GLOBAL, so the
        # byte-budget semantics above are tp-invariant): the gauge the
        # tp sweep reads — what actually sits on each chip. Only
        # head-dim buffers (rank >= 3) shard; windowed rings'
        # position buffers replicate and reside in FULL on every
        # shard (Decoder.cache_specs is the layout source of truth)
        _TM_TP_KV_BYTES.set(sum(
            x.nbytes // self.tp if x.ndim >= 3 else x.nbytes
            for x in jax.tree_util.tree_leaves(self._caches)))
        pool_slots = 0
        if self.prefix_cache_mb > 0 and not self._windowed:
            pool_slots = min(
                int(self.prefix_cache_mb * 2**20) // max(1, slot_bytes),
                _MAX_POOL_SLOTS)
        if pool_slots > 0:
            self._pool = decoder.init_cache(pool_slots,
                                            kv_sharding=self._kv_shard)
            self._prefix = PrefixCache(pool_slots, slot_bytes)
        else:
            self._pool = None
            self._prefix = None

        # speculative decoding (doc/serving.md "Speculative decoding")
        if draft is None:
            draft = os.environ.get("MXNET_SERVING_DRAFT") or "off"
        if draft not in ("off", "ngram", "model"):
            raise MXNetError(
                "InferenceEngine: draft must be 'off', 'ngram' or "
                "'model', got %r (MXNET_SERVING_DRAFT sets the "
                "default)" % (draft,))
        if spec_k is None:
            spec_k = int(os.environ.get("MXNET_SERVING_SPEC_K", "")
                         or 4)
        self.spec_k = int(spec_k)
        if draft != "off" and rolling:
            decoder.refuse_rolling_state(
                "draft=%r (a rejected draft would have advanced the "
                "state)" % (draft,))
        if draft != "off":
            if self.spec_k < 1:
                raise MXNetError(
                    "InferenceEngine: spec_k must be >= 1 when draft "
                    "is on, got %d (MXNET_SERVING_SPEC_K sets the "
                    "default)" % self.spec_k)
            if self.spec_k > self.max_len - 3:
                raise MXNetError(
                    "InferenceEngine: spec_k=%d leaves no room in the "
                    "max_len=%d cache for a verify chunk (need "
                    "spec_k <= max_len - 3)"
                    % (self.spec_k, self.max_len))
            if self._windowed:
                # refuse LOUDLY, then serve unspeculated: the verify
                # chunk write would wrap rejected drafts onto live
                # ring rows (the prefix cache bypasses for the same
                # absolute-position reason)
                warnings.warn(
                    "InferenceEngine: windowed-ring decoders do not "
                    "compose with speculative decoding (the verify "
                    "chunk would wrap rejected drafts onto live ring "
                    "rows) — serving with draft='off'", UserWarning,
                    stacklevel=2)
                draft = "off"
        self.spec_draft = draft
        self._spec = draft != "off"
        self._drafters = {}           # request id -> NgramDrafter
        self._draft_dec = None
        if self.spec_draft == "model":
            if not isinstance(draft_decoder, Decoder):
                raise MXNetError(
                    "InferenceEngine: draft='model' needs a "
                    "draft_decoder (a Decoder over the small draft "
                    "LM), got %r" % type(draft_decoder).__name__)
            if draft_decoder.max_len != self.max_len:
                raise MXNetError(
                    "InferenceEngine: draft_decoder.max_len=%d must "
                    "equal the target's max_len=%d (the draft cache "
                    "mirrors the slot clocks)"
                    % (draft_decoder.max_len, self.max_len))
            if any(draft_decoder._node_window(n)
                   for n in draft_decoder._mha):
                raise MXNetError(
                    "InferenceEngine: windowed draft models are not "
                    "supported (the catch-up chunk would wrap junk "
                    "onto live ring rows)")
            self._draft_dec = draft_decoder
            if self.weight_dtype == "float" \
                    and draft_decoder.weight_dtype == "int8":
                raise MXNetError(
                    "InferenceEngine: weight_dtype='float' over a "
                    "draft_decoder built with weight_dtype='int8' — "
                    "build the draft decoder float (the engine "
                    "quantizes its own copy)")
            dparams = draft_decoder._params
            if self.weight_dtype == "int8" \
                    and draft_decoder.weight_dtype != "int8":
                # the draft model reads its weights every proposal
                # round — quantize it with the target (engine copy,
                # same reasoning as above)
                from .quant import (quantize_params,
                                    quantized_weight_names)
                dparams = quantize_params(
                    dparams,
                    quantized_weight_names(draft_decoder._topo))
            if self._mesh is not None:
                from ..ops.attention import MultiHeadAttention as _MHA
                for n in draft_decoder._mha:
                    _MHA.check_head_shards(
                        n.params, self.tp,
                        where="tensor-parallel draft serving")
                self._draft_params = {
                    k: jax.device_put(v, self._rep_shard)
                    for k, v in dparams.items()}
                self._draft_aux = [jax.device_put(v, self._rep_shard)
                                   for v in draft_decoder._aux]
            else:
                self._draft_params = dparams
                self._draft_aux = draft_decoder._aux
            self._draft_caches = draft_decoder.init_cache(
                S, kv_sharding=self._kv_shard)
            self._draft_pos = [0] * S     # next draft-cache position
            self._draft_pending = [[] for _ in range(S)]

        # weight-storage info gauges (doc/observability.md): dtype +
        # the engine's total stored weight bytes — what int8 weights
        # buy is exactly this number shrinking while the programs
        # read it once per step (replicated per shard under tp)
        _TM_WEIGHT_DTYPE.set(
            {"float": 0, "int8": 1, "int4": 2}[self.weight_dtype])
        from .quant import weight_nbytes
        wbytes = weight_nbytes(self._params)
        if self._draft_dec is not None:
            wbytes += weight_nbytes(self._draft_params)
        self.weight_bytes = wbytes
        _TM_WEIGHT_BYTES.set(wbytes)
        _TM_MATMUL_IMPL.set(
            {"dense": 0, "pallas": 1}[self.matmul_impl])
        _TM_WEIGHT_GROUP.set(int(self.weight_group or 0))

        # host-side scheduler state
        self._pending = collections.deque()
        self._stager = StagedStream(_PendingSource(self._pending),
                                    place=self._place_prompt,
                                    depth=stage_depth, live_source=True)
        self._free = collections.deque(range(S))  # FIFO slot recycling
        self._mirror = [None] * S   # drain-side view: slot -> Request
        self._drain = collections.deque()
        # requests admitted to a slot whose prompt is still being
        # chunk-prefilled, oldest first; plus one admission candidate
        # held over when a round's prefill budget ran out. Each round
        # runs at most ~prefill_chunk tokens of prefill work between
        # decode rounds (the chunked-prefill cadence bound)
        self._chunking = collections.deque()
        self._held = None
        self._round_budget = float("inf")
        self._next_id = 0
        self._auto_seed = 0
        # request lifecycle: every not-yet-done request, in submission
        # order (snapshot/restore replays this order); _watched is the
        # subset that can retire host-side (deadline or cancel) so the
        # per-round sweep never walks a deadline-less backlog
        self._active = {}            # id -> Request
        self._watched = set()        # ids with a deadline / cancel mark
        self._done_buf = []          # finished since the last step()
        self._closed = False
        # KV handoff state (role="prefill" exports, any non-prefill
        # role imports): _handoff_out holds packaged finished prefills
        # until the router resolves them; _handoff_slots are the cache
        # slots those packages pin (out of _free but carrying no live
        # request — idle/step accounting treats them as neither);
        # _imported is a bounded id ring for exactly-once admission
        # under retried deliveries
        self._handoff_out = collections.deque()
        self._handoff_slots = set()
        self._imported = collections.OrderedDict()
        self.stats = {"submitted": 0, "completed": 0, "prefills": 0,
                      "steps": 0, "tokens": 0, "prefix_hits": 0,
                      "prefix_hit_tokens": 0, "prefill_chunks": 0,
                      "prefix_copies": 0, "shed": 0, "deadline_missed": 0,
                      "cancelled": 0, "errors": 0, "watchdog_trips": 0,
                      "restores": 0, "spec_rounds": 0,
                      "spec_fallback_rounds": 0, "spec_drafted": 0,
                      "spec_accepted": 0, "handoffs_out": 0,
                      "handoffs_in": 0}

        # the compiled program families; the log records one tag
        # per TRACE (python side effects run at trace time only), so it
        # IS the compile count — tests pin the contract against it.
        # Under tp>1 every family body is wrapped in ONE shard_map
        # (_wrap_tp) before jit — same families, same counts, sharded
        # execution.
        self._compile_log = []
        self._tp_ax = ("model", self.tp) \
            if (self._mesh is not None and self.tp > 1) else None
        self._ep_ax = ("expert", self.ep) if self.ep > 1 else None
        # params in_spec: replicated, except the expert stacks under
        # ep>1 (leading-axis expert sharding — QuantizedTensor leaves
        # prefix-match the per-name spec)
        if self.ep > 1:
            from jax.sharding import PartitionSpec as _P
            self._param_spec = {
                k: (_P("expert") if k in self._expert_names else _P())
                for k in self._params}
        else:
            self._param_spec = "r"
        ps = self._param_spec
        on_chip = jax.default_backend() != "cpu"
        self._donate = (2, 3) if on_chip else ()
        self._copy_donate = (0, 1) if on_chip else ()
        cs = self._cache_spec(self._caches)
        # routed MoEFFN layers whose touched experts the decode program
        # counts (0: none; counted for the walk of a decoder with a
        # state leaf or latent rows, whose experts run in the routed
        # form), and the
        # token-expert pairs a step routes in them
        counted = [n for n in moe_nodes if n.params["top_k"] > 0] \
            if (rolling or latent) and not decoder._mha else []
        self._moe_counted = len(counted)
        self._moe_pairs_step = self.slots * sum(
            n.params["top_k"] for n in counted)
        # GatedDeltaNet layers, whose advanced slots it counts
        self._state_layers = len(decoder._gdn)
        # LatentAttention layers, whose live rows it sums
        self._latent_layers = len(decoder._mla) \
            if decoder._slots_batched else 0
        # rows of the K buffers of every attention layer (a
        # CCAttention's K rows too; a GatedDeltaNet layer has none),
        # which a decode step's bounded reads are counted against (0:
        # the walk is not batched — a ring — so no read is bounded and
        # nothing is counted)
        self._attn_pool_rows = sum(
            k.shape[0] * k.shape[1]
            for k in Decoder.row_buffers(self._caches)) \
            if decoder._slots_batched else 0
        self._step_fn = jax.jit(
            self._wrap_tp(self._make_step(),
                          (ps, "r", cs, "r"), (cs, "r", "r")),
            donate_argnums=self._donate)
        self._prefill_fns = {}
        self._copy_fns = {}
        self._handoff_fns = {}   # (bucket, write?) -> jitted row mover
        # speculative-decoding programs: ONE verify program (the whole
        # contract extension) plus, for draft="model", one draft
        # proposal program and a per-bucket draft prefill family
        self._verify_fn = None
        self._draft_fn = None
        self._draft_prefill_fns = {}
        if self._spec:
            self._verify_fn = jax.jit(
                self._wrap_tp(self._make_verify(),
                              (ps, "r", cs, "r", "r", "r"),
                              (cs, "r", "r")),
                donate_argnums=self._donate)
            if self.spec_draft == "model":
                dcs = self._cache_spec(self._draft_caches)
                self._draft_fn = jax.jit(
                    self._wrap_tp(self._make_draft(),
                                  ("r", "r", dcs, "r", "r", "r", "r"),
                                  (dcs, "r")),
                    donate_argnums=(2,) if on_chip else ())
        # observability plane: watchdog/liveness state read by
        # health() and the exposition server's /healthz, plus the
        # once-per-program introspection registration guard
        self._last_ok_t = time.perf_counter()
        self._watchdog_stuck_t = None
        self._prog_seen = set()
        # round-phase attribution: _phase_acc is the accumulator dict
        # while a step() is in flight (every _phase span adds its
        # seconds), _rounds the bounded ledger GET /rounds reads
        self._phase_acc = None
        self._rounds = collections.deque(maxlen=_ROUND_LEDGER)
        self._round_no = 0
        # traffic capture: opened LAST so the header carries the final
        # geometry (windowed-ring fallbacks included); a disabled
        # stream (knob unset) is a no-op on every path
        self.capture = CaptureStream.open(
            capture_dir, capture_mb,
            dict(self._geometry(), max_len=self.max_len,
                 engine_id=self.engine_id,
                 migrated_from=self.migrated_from), self._t0)
        # resolved (env default included) so snapshot() carries it
        self.capture_dir = os.path.dirname(self.capture.path) \
            if self.capture.enabled else None
        _ENGINES.add(self)

    # -- construction ---------------------------------------------------
    @classmethod
    def from_checkpoint(cls, prefix, epoch, max_len, slots=8,
                        prefill_buckets=None, max_queue=256,
                        stage_depth=2, drain_depth=2, steps_per_round=1,
                        prefix_cache_mb=None, prefill_chunk=None,
                        overload=None, round_timeout_ms=None,
                        slo_ttft_ms=None, slo_cadence_ms=None,
                        slo_target=0.99, flight_recorder=None,
                        spec_k=None, draft=None, draft_decoder=None,
                        draft_prefix=None, draft_epoch=None,
                        capture_dir=None, tp=None, mesh=None,
                        weight_dtype=None, **decoder_kwargs):
        """Checkpoint → serving engine in one call
        (``prefix-symbol.json`` + ``prefix-NNNN.params``, the reference
        format): builds the :class:`Decoder` via
        ``Decoder.from_checkpoint`` and wraps it. ``decoder_kwargs``
        reach the decoder (``compute_dtype``, ``cache_dtype``, ...).
        ``draft_prefix``/``draft_epoch`` load a SECOND (small)
        checkpoint as the speculative draft model — implies
        ``draft="model"`` unless overridden; the draft decoder
        inherits ``compute_dtype`` but none of the cache-flavor
        kwargs."""
        # weight_dtype goes to the DECODER (which owns the env-default
        # resolution) and the engine inherits it: an explicit "float"
        # must be able to override MXNET_SERVING_WEIGHT_DTYPE=int8 —
        # an env-quantized decoder cannot serve a float engine (the
        # float weights are gone)
        decoder_kwargs.setdefault("weight_dtype", weight_dtype)
        dec = Decoder.from_checkpoint(prefix, epoch, max_len,
                                      **decoder_kwargs)
        if draft_prefix is not None and draft_decoder is None:
            draft_decoder = Decoder.from_checkpoint(
                draft_prefix, 0 if draft_epoch is None else draft_epoch,
                max_len,
                compute_dtype=decoder_kwargs.get("compute_dtype"),
                weight_dtype=decoder_kwargs["weight_dtype"])
            if draft is None:
                draft = "model"
        return cls(dec, slots=slots, prefill_buckets=prefill_buckets,
                   max_queue=max_queue, stage_depth=stage_depth,
                   drain_depth=drain_depth,
                   steps_per_round=steps_per_round,
                   prefix_cache_mb=prefix_cache_mb,
                   prefill_chunk=prefill_chunk, overload=overload,
                   round_timeout_ms=round_timeout_ms,
                   slo_ttft_ms=slo_ttft_ms,
                   slo_cadence_ms=slo_cadence_ms, slo_target=slo_target,
                   flight_recorder=flight_recorder, spec_k=spec_k,
                   draft=draft, draft_decoder=draft_decoder,
                   capture_dir=capture_dir, tp=tp, mesh=mesh)

    # -- compiled programs ----------------------------------------------
    def _cache_spec(self, tree):
        """Per-leaf PartitionSpec tree for a cache pytree under tp
        (None at tp=1) — Decoder.cache_specs, so the program specs and
        the cache layout can never drift."""
        if self._mesh is None:
            return None
        return Decoder.cache_specs(tree)

    def _wrap_tp(self, fn, in_specs, out_specs):
        """Tensor-parallel program wrapper (no-op at tp=1): shard_map
        ``fn`` over the mesh's model axis. ``"r"`` entries mean
        replicated (every device sees the full operand at tp=1's
        exact shape — the byte-identity lever); cache-spec trees mark
        the kv-head-sharded cache arguments. Inside, each device runs
        a plain single-device program on its cache shard; the ONLY
        collectives are the one-per-attention-node all-gathers
        ``Decoder._cached_mha`` inserts, so the program count and the
        trace-time compile log are exactly the tp=1 ones.
        ``check_vma=False``: replication of the replicated outputs is
        by construction (identical inputs, identical per-device
        programs), not something the rep-checker can see through the
        collectives."""
        if self._mesh is None:
            return fn
        from jax import shard_map
        from jax.sharding import PartitionSpec

        rep = PartitionSpec()

        def is_r(s):
            return isinstance(s, str) and s == "r"

        in_specs = tuple(rep if is_r(s) else s for s in in_specs)
        if is_r(out_specs):
            out_specs = rep
        elif isinstance(out_specs, tuple) \
                and not isinstance(out_specs, PartitionSpec):
            out_specs = tuple(rep if is_r(s) else s for s in out_specs)
        return shard_map(fn, mesh=self._mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _make_step(self):
        dec = self._dec
        k_rounds = self.steps_per_round
        mm = self.matmul_impl
        tp_ax = self._tp_ax
        ep_ax = self._ep_ax

        counted = self._moe_counted
        state_counted = self._state_layers
        latent_counted = self._latent_layers
        rows_counted = self._attn_pool_rows

        def one_step(caches, state, params, aux):
            pos, tok, live, temp, keys, eos, last = state
            # write each slot's pending token at ITS position, read
            # logits for the next one. Two contracts, by the kind of
            # cache. ROWS: a frozen slot rewrites its last token in
            # place — idempotent — and, holding no request, has no row
            # the bounded read may fetch: a finished slot keeps its
            # last position, so a bound by ``pos`` alone would read
            # its stale rows for ever. A recurrent STATE: a step
            # advances it, so a slot that is not live (``lens`` 0)
            # leaves its state untouched (doc/serving.md "The decode
            # round")
            stats = {} if counted or rows_counted or state_counted \
                or latent_counted else None
            logits, caches = dec._run_slots(
                params, aux, caches, pos, tok[:, None], tp=tp_ax,
                mm_impl=mm, ep=ep_ax, stats=stats,
                lens=jnp.where(live, pos + 1, 0))
            logits = logits[:, 0]
            nxt_pos = pos + 1
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def with_sampling(_):
                t = jnp.where(temp > 0.0, temp, jnp.float32(1.0))

                def draw(k, q, row):
                    return jax.random.categorical(
                        jax.random.fold_in(k, q), row)

                sampled = jax.vmap(draw)(
                    keys, nxt_pos,
                    logits.astype(jnp.float32) / t[:, None]
                ).astype(jnp.int32)
                return jnp.where(temp > 0.0, sampled, greedy)

            # all-greedy rounds (the common case) must not pay the
            # per-slot fold_in + categorical they will never take —
            # same reasoning as Decoder._build_generate's lax.cond
            nxt = lax.cond(jnp.any(temp > 0.0), with_sampling,
                           lambda _: greedy, None)
            done_now = (nxt == eos) | (nxt_pos >= last)
            out = jnp.where(live, nxt, -1)     # -1: slot had no token
            # more columns beside the S tokens, in this order: the
            # experts this step touched, the pairs that fell on held
            # experts and the slots that routed nothing, over the
            # routed layers; the slots whose state it advanced, over
            # the GatedDeltaNet layers, and how many of those layers
            # advanced it in place; the cache rows its bounded
            # reads fetched, over the attention layers
            cols = (["experts_touched", "pairs_held", "rows_masked"]
                    if counted else []) \
                + (["state_advanced", "state_in_place"]
                   if state_counted else []) \
                + (["latent_rows_live"] if latent_counted else []) \
                + (["attn_rows_read"] if rows_counted else [])
            if cols:
                out = jnp.concatenate(
                    [out] + [jnp.asarray(stats[c], out.dtype).reshape(1)
                             for c in cols])
            live2 = live & ~done_now
            pos2 = jnp.where(live, nxt_pos, pos)
            tok2 = jnp.where(live, nxt, tok)
            return caches, (pos2, tok2, live2, temp, keys, eos, last), \
                out

        def step(params, aux, caches, state):
            # trace-time, see above; an introspection re-lower
            # (profiler.collect_program_stats on a lowering-cache
            # miss) must not count as a compile
            if not profiler.collecting():
                self._compile_log.append("decode")
                _TM_COMPILE_DECODE.inc()

            def body(carry, _):
                caches, st = carry
                caches, st, out = one_step(caches, st, params, aux)
                return (caches, st), out

            (caches, state), outs = lax.scan(body, (caches, state),
                                             None, length=k_rounds)
            return caches, state, outs          # outs [k, S + columns]

        return step

    def _make_verify(self):
        """The ONE compiled verify program (doc/serving.md
        "Speculative decoding"): per round, the target model scores
        every slot's ``spec_k`` drafted tokens in one chunked run
        (``Decoder.verify_step_slots`` — the multi-token cache append
        plus in-program accepted-prefix computation) and emits the
        accepted prefix + one corrected token per slot. Slots with
        ``dlen == 0`` ride along and emit exactly their plain-decode
        token; rounds with NO drafts at all dispatch the plain decode
        program instead (the fallback path, counted)."""
        dec = self._dec
        mm = self.matmul_impl
        tp_ax = self._tp_ax
        ep_ax = self._ep_ax

        def verify(params, aux, caches, state, drafts, dlen):
            if not profiler.collecting():
                self._compile_log.append("verify")
                _TM_COMPILE_VERIFY.inc()
            return dec.verify_step_slots(params, aux, caches, state,
                                         drafts, dlen, tp=tp_ax,
                                         mm_impl=mm, ep=ep_ax)

        return verify

    def _make_draft(self):
        """The draft proposal program (``draft="model"``): catch the
        draft cache up on the tokens the target emitted since last
        round, then greedily propose ``spec_k`` tokens per slot
        (``Decoder.draft_propose_slots``)."""
        ddec = self._draft_dec
        k = self.spec_k
        mm = self.matmul_impl
        tp_ax = self._tp_ax

        def draft(params, aux, caches, pos, catchup, clen, live):
            if not profiler.collecting():
                self._compile_log.append("draft")
                _TM_COMPILE_DRAFT.inc()
            return ddec.draft_propose_slots(params, aux, caches, pos,
                                            catchup, clen, k, tp=tp_ax,
                                            mm_impl=mm, live=live)

        return draft

    def _draft_prefill_fn(self, bucket):
        """Per-bucket draft-cache prefill (``draft="model"``): write
        the prompt's K/V into the DRAFT model's slot cache — no
        sampling, no state vectors, just the cache build the proposal
        program decodes from. The draft model prefills the WHOLE
        prompt even on a prefix-cache hit (the pool holds target K/V
        only; the draft model is small enough that re-prefilling
        beats maintaining a second pool)."""
        if bucket not in self._draft_prefill_fns:
            ddec = self._draft_dec
            mm = self.matmul_impl
            tp_ax = self._tp_ax

            def dprefill(params, aux, caches, slot, tokens, start,
                         true_len):
                if not profiler.collecting():
                    self._compile_log.append(("draft_prefill", bucket))
                    _TM_COMPILE_DRAFT.inc()
                sub = ddec.slot_slice(caches, slot)
                sub = ddec.clear_window_positions(
                    sub, only_if=start == jnp.int32(0))
                _, sub = ddec._run(params, aux, sub, start, tokens,
                                   valid_len=start + true_len,
                                   tp=tp_ax, mm_impl=mm)
                return ddec.slot_update(caches, slot, sub)

            dcs = self._cache_spec(self._draft_caches)
            self._draft_prefill_fns[bucket] = jax.jit(
                self._wrap_tp(dprefill,
                              ("r", "r", dcs, "r", "r", "r", "r"),
                              dcs),
                donate_argnums=(2,) if self._donate else ())
        return self._draft_prefill_fns[bucket]

    def _prefill_fn(self, bucket):
        if bucket not in self._prefill_fns:
            dec = self._dec
            mm = self.matmul_impl
            tp_ax = self._tp_ax
            ep_ax = self._ep_ax

            def prefill(params, aux, caches, state, slot, tokens,
                        start, true_len, final, temp, key, eos,
                        max_toks):
                # ONE program per bucket serves whole prompts AND every
                # chunk of a chunked prefill: start, the chunk's true
                # length and finality are traced operands. total = the
                # absolute prompt length covered so far.
                if not profiler.collecting():
                    self._compile_log.append(("prefill", bucket))
                    _TM_COMPILE_PREFILL.inc()
                pos, tok, live, temps, keys, eoss, lasts = state
                total = start + true_len
                sub = dec.slot_slice(caches, slot)
                # ring-position reset: a recycled slot must not leak
                # the previous occupant's window entries — but ONLY on
                # the first chunk; later chunks extend the same ring
                sub = dec.clear_window_positions(
                    sub, only_if=start == jnp.int32(0))
                # valid_len (absolute): pad rows must not enter window
                # rings (they would EVICT real in-window keys — linear
                # cache rows are masked-until-overwritten, ring slots
                # wrap)
                logits, sub = dec._run(params, aux, sub, start, tokens,
                                       valid_len=total, tp=tp_ax,
                                       mm_impl=mm, ep=ep_ax)
                caches = dec.slot_update(caches, slot, sub)
                v = logits.shape[2]
                zero = jnp.int32(0)
                lastlog = lax.dynamic_slice(
                    logits, (zero, true_len - 1, zero), (1, 1, v))[0, 0]
                greedy = jnp.argmax(lastlog, -1).astype(jnp.int32)
                t = jnp.where(temp > 0.0, temp, jnp.float32(1.0))
                sampled = jax.random.categorical(
                    jax.random.fold_in(key, total),
                    lastlog.astype(jnp.float32) / t).astype(jnp.int32)
                t0 = jnp.where(temp > 0.0, sampled, greedy)
                lastp = jnp.minimum(total + max_toks - 1,
                                    dec.max_len - 1).astype(jnp.int32)
                done0 = (t0 == eos) | (total >= lastp)
                # a NON-final chunk parks the slot dead at (pos=total,
                # tok=last chunk token): the decode rounds that
                # interleave until the next chunk rewrite exactly that
                # token's K/V at row `total` — a row the next chunk
                # overwrites before any masked read could see it, the
                # same idempotent-freeze contract finished slots use
                lastchunk = lax.dynamic_slice(
                    tokens, (zero, true_len - 1), (1, 1))[0, 0]
                state2 = (pos.at[slot].set(total),
                          tok.at[slot].set(
                              jnp.where(final, t0, lastchunk)),
                          live.at[slot].set(final & ~done0),
                          temps.at[slot].set(temp),
                          keys.at[slot].set(key),
                          eoss.at[slot].set(eos),
                          lasts.at[slot].set(lastp))
                return caches, state2, t0

            cs = self._cache_spec(self._caches)
            self._prefill_fns[bucket] = jax.jit(
                self._wrap_tp(prefill,
                              (self._param_spec, "r", cs) + ("r",) * 10,
                              (cs, "r", "r")),
                donate_argnums=self._donate)
        return self._prefill_fns[bucket]

    def _copy_fn(self, bucket):
        """Compiled slot-to-slot prefix copy, one program per bucket:
        rows ``[0, bucket)`` of a source slot land in a destination
        slot. Source/destination may each be a serving slot or a pool
        slot — the direction booleans are traced operands, so ONE
        program covers pool→slot (prefix hit) and slot→pool
        (retention). int8 flavors copy their row scales alongside
        automatically (the copy is a tree-map over every cache
        buffer)."""
        if bucket not in self._copy_fns:
            def copy(serv, pool, src, dst, src_pool, dst_pool):
                if not profiler.collecting():
                    self._compile_log.append(("copy", bucket))
                    _TM_COMPILE_COPY.inc()
                rows = lax.cond(
                    src_pool,
                    lambda _: Decoder.slot_prefix_rows(pool, src,
                                                       bucket),
                    lambda _: Decoder.slot_prefix_rows(serv, src,
                                                       bucket),
                    None)
                serv = lax.cond(
                    dst_pool, lambda s: s,
                    lambda s: Decoder.slot_write_prefix_rows(s, dst,
                                                             rows),
                    serv)
                pool = lax.cond(
                    dst_pool,
                    lambda p: Decoder.slot_write_prefix_rows(p, dst,
                                                             rows),
                    lambda p: p, pool)
                return serv, pool

            self._copy_fns[bucket] = jax.jit(
                self._wrap_tp(copy,
                              (self._cache_spec(self._caches),
                               self._cache_spec(self._pool),
                               "r", "r", "r", "r"),
                              (self._cache_spec(self._caches),
                               self._cache_spec(self._pool))),
                donate_argnums=self._copy_donate)
        return self._copy_fns[bucket]

    def _dispatch_copy(self, length, src, dst, src_pool, dst_pool):
        """Bucket ``length`` and dispatch the copy program (prefix-hit
        admission or retention insert)."""
        bucket = self._bucket_for(length)
        with self._phase("prefix_copy", bucket=bucket,
                         to_pool=bool(dst_pool)):
            self._caches, self._pool = self._copy_fn(bucket)(
                self._caches, self._pool, np.int32(src), np.int32(dst),
                np.bool_(src_pool), np.bool_(dst_pool))
        if ("copy", bucket) not in self._prog_seen:
            self._prog_seen.add(("copy", bucket))
            profiler.register_program(
                "serving_copy_b%d" % bucket, self._copy_fns[bucket],
                (self._caches, self._pool, np.int32(0), np.int32(0),
                 np.bool_(True), np.bool_(False)))
        self.stats["prefix_copies"] += 1

    # -- KV handoff (disaggregated prefill/decode) ----------------------
    def _handoff_fn(self, bucket, write=False):
        """Per-bucket handoff row movers, jitted lazily like the copy
        family: the EXPORT direction reads one slot's first ``bucket``
        KV rows out of the serving cache (``Decoder.slot_prefix_rows``
        — the same static-length/traced-slot contract the prefix pool
        copies ride), the IMPORT direction writes host rows into one
        slot (``slot_write_prefix_rows``, junk-row discipline
        unchanged: rows past the request's position are never read).
        Any one engine only ever fires ONE direction per bucket — a
        prefill engine exports, everyone else imports — so the
        ("handoff", bucket) compile tag stays once-per-bucket."""
        key = (bucket, bool(write))
        if key not in self._handoff_fns:
            cs = self._cache_spec(self._caches)
            if write:
                def run(serv, slot, rows, _b=bucket):
                    if not profiler.collecting():
                        self._compile_log.append(("handoff", _b))
                        _TM_COMPILE_HANDOFF.inc()
                    return Decoder.slot_write_prefix_rows(serv, slot,
                                                          rows)

                self._handoff_fns[key] = jax.jit(
                    self._wrap_tp(run, (cs, "r", cs), cs),
                    donate_argnums=(0,) if self._donate else ())
            else:
                def run(serv, slot, _b=bucket):
                    if not profiler.collecting():
                        self._compile_log.append(("handoff", _b))
                        _TM_COMPILE_HANDOFF.inc()
                    return Decoder.slot_prefix_rows(serv, slot, _b)

                # NO donation: the source cache must survive the read
                # (other slots keep decoding against it)
                self._handoff_fns[key] = jax.jit(
                    self._wrap_tp(run, (cs, "r"), cs))
        return self._handoff_fns[key]

    def _export_rows(self, slot, length):
        """Pull one slot's first ``length`` KV rows to host numpy
        (rounded up to the covering bucket — the decode side clips by
        position, so the pad rows are junk it never reads)."""
        bucket = self._bucket_for(length)
        with self._phase("handoff_export", bucket=bucket):
            rows = self._handoff_fn(bucket)(self._caches,
                                            np.int32(slot))
            rows = jax.tree_util.tree_map(np.asarray, rows)
        if ("handoff", bucket, "export") not in self._prog_seen:
            self._prog_seen.add(("handoff", bucket, "export"))
            profiler.register_program(
                "serving_handoff_b%d" % bucket,
                self._handoff_fns[(bucket, False)],
                (self._caches, np.int32(0)))
        return rows

    def _import_rows(self, slot, length, rows):
        """Write transferred rows into ``slot`` through the
        prefix-pool write path (dequantized to cache dtype first when
        the transfer was int8)."""
        bucket = self._bucket_for(length)
        rows = unpack_rows(rows, self._caches)
        with self._phase("handoff_import", bucket=bucket):
            self._caches = self._handoff_fn(bucket, write=True)(
                self._caches, np.int32(slot), rows)
        if ("handoff", bucket, "import") not in self._prog_seen:
            self._prog_seen.add(("handoff", bucket, "import"))
            profiler.register_program(
                "serving_handoff_wr_b%d" % bucket,
                self._handoff_fns[(bucket, True)],
                (self._caches, np.int32(0), rows))

    @property
    def compile_counts(self):
        """{'decode': n, 'verify': n, 'prefill': {bucket: n},
        'copy': {bucket: n}} — the compile-count contract: after any
        workload, decode == 1, verify <= 1 (0 with speculation off or
        never fired), each USED prefill bucket == 1 and each USED copy
        bucket == 1 (chunked prefill reuses the prefill buckets —
        chunk start is a traced operand, so chunking adds NO programs;
        one copy program covers both pool→slot and slot→pool; the ONE
        verify program serves every draft mix — drafts and their
        lengths are traced operands). Engines with ``draft="model"``
        additionally report ``'draft'`` (<= 1) and ``'draft_prefill'``
        ({bucket: 1}). Engines that ever touched the KV handoff path
        (role != "unified", or a unified engine that imported)
        additionally report ``'handoff'`` ({bucket: 1} — one row mover
        per bucket per engine; each engine only ever fires one
        DIRECTION, so export and import never share a tag).
        doc/serving.md."""
        out = {"decode": 0, "verify": 0, "prefill": {}, "copy": {}}
        if self.spec_draft == "model":
            out["draft"] = 0
            out["draft_prefill"] = {}
        if self.role != "unified" or self._handoff_fns:
            out["handoff"] = {}
        for tag in self._compile_log:
            if isinstance(tag, str):
                out[tag] += 1
            else:
                fam = out[tag[0]]
                fam[tag[1]] = fam.get(tag[1], 0) + 1
        return out

    # -- host scheduler -------------------------------------------------
    def _bucket_for(self, n):
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise MXNetError(
            "InferenceEngine: prompt length %d exceeds the largest "
            "prefill bucket %d" % (n, self.prefill_buckets[-1]))

    def _place_prompt(self, req):
        """Stager place fn: pad to the bucket and dispatch the h2d
        (async) — runs up to stage_depth requests ahead of admission.

        A prompt longer than ``prefill_chunk`` is guaranteed to admit
        as chunk pieces built at admission time (the split depends on
        the prefix match), so its full-prompt h2d would only be
        discarded — stage nothing; likewise a resumed sequence past
        the largest bucket (it admits in bucket-sized pieces). A
        prefix HIT on a short prompt also discards the staged array,
        but hits are unknowable this far ahead of admission; the waste
        there is one small int32 h2d (chunk/suffix arrays are a few KB
        — the prefill dispatch they feed dominates).

        A placement failure (a bad h2d) must poison only ITS request:
        the error rides the staged tuple to admission, where the
        request retires with reason ``"error"`` instead of unwinding
        ``step()`` from inside the stager fill."""
        # the stager is inline, so fills run inside _admit and the
        # time lands on the round in flight (dropped when none is)
        with self._phase("h2d"):
            return self._place_prompt_inner(req)

    def _put_tokens(self, padded):
        """Host prompt tokens -> the device array every prefill
        dispatch takes. Under tp it lands REPLICATED on the mesh (a
        bare device_put commits to device 0, which the sharded
        programs would reject). Staged whole prompts and host-built
        chunks both go through here: an array on the mesh and a host
        array are different argument TYPES to jit (the mesh is part
        of the aval), so mixing them traced — and compiled — a
        prefill bucket twice."""
        if self._mesh is not None:
            return jax.device_put(padded, self._rep_shard)
        return jax.device_put(padded)

    def _place_prompt_inner(self, req):
        try:
            p = len(req.seq)
            if (self.prefill_chunk and p > self.prefill_chunk) \
                    or p > self.prefill_buckets[-1]:
                self.flight.event(req.id, "staged", chunked=True)
                return req, None
            bucket = self._bucket_for(p)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :p] = req.seq
            dev = self._put_tokens(padded)
            self.flight.event(req.id, "staged", bucket=bucket)
            return req, dev
        except Exception as e:               # noqa: BLE001 — isolated
            self.flight.event(req.id, "staged", error=str(e))
            return req, _PlacementError(e)

    def queued(self):
        """Requests submitted but not yet admitted to a slot."""
        return len(self._pending) + self._stager.staged() \
            + (self._held is not None)

    @property
    def idle(self):
        # handoff-pinned slots count as free here: the engine has no
        # work left to STEP for them — delivery is the router's job,
        # and FleetRouter.idle separately refuses to go idle while any
        # replica still holds an unresolved package
        return not self._pending and self._stager.staged() == 0 \
            and self._held is None \
            and len(self._free) + len(self._handoff_slots) == self.slots \
            and not self._drain and not self._chunking

    def submit(self, prompt, max_tokens, eos_id=None, temperature=0.0,
               seed=None, request_id=None, deadline_ms=None,
               ttft_deadline_ms=None, _resume_tokens=(), _trace=None):
        """Queue one generation request; returns its :class:`Request`
        handle (fills in as the engine steps).

        prompt : 1-D int sequence, ``1 <= len <= max_len - 1`` (and
        within the largest bucket). ``max_tokens`` is truncated to the
        cache: at most ``max_len - len(prompt)`` tokens come back.
        ``eos_id``: generation stops after emitting it (included in
        the output). ``temperature=0``: greedy, byte-identical to
        ``Decoder.generate``; > 0 samples with ``seed`` (auto-drawn if
        omitted) — reproducible and schedule-independent.

        ``deadline_ms`` / ``ttft_deadline_ms`` (host wall clock from
        submit): past the deadline — overall, or first-token — the
        request retires at the next round boundary with
        ``retire_reason="deadline"`` and whatever tokens it generated;
        a still-QUEUED expired request is failed without ever
        occupying a slot. :meth:`cancel` retires the same way with
        ``"cancelled"``.

        A full queue follows the ``overload`` policy: ``block`` raises
        a generic ``MXNetError`` (backpressure — callers drive
        :meth:`step` to drain), ``shed`` raises a typed
        :class:`EngineOverloaded`, ``shed_oldest`` evicts the oldest
        queued request in favor of this one.
        """
        self._check_open()
        if self.draining and not _resume_tokens:
            # a draining replica takes no NEW work; resumed
            # (migrated/restored) submits still land so an operator
            # can fold work INTO an engine that is about to stop —
            # never the reverse
            raise MXNetError(
                "InferenceEngine: engine %s is draining — submit to "
                "another replica" % self.engine_id)
        if self.role == "decode":
            # decode specialists admit work through admit_handoff
            # ONLY: a fresh prompt — and equally a resumed/migrated
            # one, which re-prefills prompt+tokens on the admitting
            # engine — would trace the prefill family this role
            # exists to avoid (the FleetRouter's role-aware placement
            # never routes a submit here)
            raise MXNetError(
                "InferenceEngine: engine %s has role='decode' — "
                "prompts go to a prefill or unified replica (the "
                "FleetRouter's role-aware placement does this)"
                % self.engine_id)
        # validate shape/dtype HERE, where the caller can see the
        # problem — a bad prompt forwarded to the compiled programs
        # surfaces as an opaque shape/dtype error rounds later;
        # validation runs BEFORE the overload branch so an
        # inadmissible submit can never shed valid queued work
        try:
            prompt = np.asarray(prompt)
        except Exception as e:
            raise MXNetError(
                "InferenceEngine: prompt is not array-like (%s)" % e)
        if prompt.ndim != 1:
            raise MXNetError(
                "InferenceEngine: prompt must be a 1-D token sequence "
                "(one request per submit), got shape %r"
                % (prompt.shape,))
        if prompt.size < 1:
            raise MXNetError("InferenceEngine: empty prompt")
        if not np.issubdtype(prompt.dtype, np.integer):
            raise MXNetError(
                "InferenceEngine: prompt token ids must be integers, "
                "got dtype %s (floats would be silently truncated)"
                % prompt.dtype)
        prompt = prompt.astype(np.int32)
        if prompt.size + len(_resume_tokens) > self.max_len - 1:
            raise MXNetError(
                "InferenceEngine: prompt length %d leaves no room to "
                "generate (max_len=%d)" % (prompt.size, self.max_len))
        if not self.prefill_chunk and not _resume_tokens:
            # monolithic prefill must fit one bucket program; chunked
            # engines serve ANY prompt <= max_len - 1 in pieces (each
            # piece <= prefill_chunk <= the largest bucket), and a
            # RESUMED sequence admits in bucket-sized pieces even with
            # chunking off (restore() must never reject what the
            # crashed engine had accepted)
            self._bucket_for(prompt.size)
        max_tokens = int(max_tokens)
        if max_tokens < 1:
            raise MXNetError("InferenceEngine: max_tokens must be >= 1")
        # eos/temperature validation HERE too (same reasoning as the
        # prompt checks): a vector eos or NaN temperature forwarded as
        # a traced operand misbehaves downstream — a NaN softmax draw,
        # a shape error rounds later — with no pointer back to the
        # offending submit
        if eos_id is not None:
            try:
                e = np.asarray(eos_id)
            except Exception:
                e = None
            if e is None or e.ndim != 0 \
                    or not np.issubdtype(e.dtype, np.integer):
                raise MXNetError(
                    "InferenceEngine: eos_id must be a scalar integer "
                    "token id, got %r" % (eos_id,))
            eos_id = int(e)
            if eos_id < 0:
                raise MXNetError(
                    "InferenceEngine: eos_id must be >= 0, got %d "
                    "(negative ids collide with the engine's 'no eos' "
                    "sentinel)" % eos_id)
        try:
            temp = float(temperature)
        except (TypeError, ValueError):
            temp = float("nan")          # rejected just below
        if math.isnan(temp) or math.isinf(temp) or temp < 0:
            raise MXNetError(
                "InferenceEngine: temperature must be a finite float "
                ">= 0, got %r (0 = greedy)" % (temperature,))
        temperature = temp
        if self.queued() >= self.max_queue:
            if self.overload == "shed_oldest" and self._shed_oldest():
                pass                     # room made; admit the new one
            elif self.overload in ("shed", "shed_oldest"):
                _TM_SHED.inc()
                self.stats["shed"] += 1
                raise EngineOverloaded(
                    "InferenceEngine: overloaded — %d requests waiting "
                    "(max_queue=%d, overload=%r); retry against "
                    "another replica or back off"
                    % (self.queued(), self.max_queue, self.overload))
            else:
                raise MXNetError(
                    "InferenceEngine: request queue is full (%d "
                    "waiting; max_queue=%d) — step() the engine to "
                    "drain it" % (self.queued(), self.max_queue))
        if seed is None:
            seed = self._auto_seed
            self._auto_seed += 1
        rid = request_id
        if rid is None:
            rid = self._next_id
            self._next_id += 1
        limit = min(max_tokens, self.max_len - prompt.size)
        req = Request(rid, prompt, max_tokens, eos_id,
                      temperature, seed, limit,
                      deadline_ms=deadline_ms,
                      ttft_deadline_ms=ttft_deadline_ms,
                      resume_tokens=_resume_tokens)
        if _trace is not None:
            req.trace = (str(_trace[0]), int(_trace[1]))
        self._pending.append(req)
        self._active[rid] = req
        if req._deadline is not None or req._ttft_deadline is not None:
            self._watched.add(rid)
        self.stats["submitted"] += 1
        self.capture.submit(req)
        if self.flight.enabled:
            meta = {"prompt_len": int(prompt.size),
                    "max_tokens": max_tokens}
            if temperature:
                meta["temperature"] = temperature
            if req.resumed:
                meta["resumed"] = req.resumed
            if deadline_ms is not None:
                meta["deadline_ms"] = deadline_ms
            if ttft_deadline_ms is not None:
                meta["ttft_deadline_ms"] = ttft_deadline_ms
            if req.trace is not None:
                meta["trace"], meta["hop"] = req.trace
            self.flight.start(rid, **meta)
        return req

    def cancel(self, request_id):
        """Cancel a queued or in-flight request: it retires at the
        next round boundary with ``retire_reason="cancelled"`` and
        whatever tokens already drained (``result()`` returns them); a
        still-queued request never occupies a slot. Returns True if
        the request was live, False if unknown or already done."""
        req = self._active.get(request_id)
        if req is None or req.done:
            return False
        req._cancelled = True
        self._watched.add(request_id)
        return True

    # -- KV handoff scheduler seams -------------------------------------
    def _handoff_prefill(self, req, slot, t0, now):
        """Prefill-role drain tail: the first token lands on the
        request exactly as unified serving would land it (TTFT is
        SERVED here — the decode side inherits it), then the finished
        prefill is packaged for the router. The slot leaves the free
        list into ``_handoff_slots`` — its KV rows must survive until
        the package resolves — and the request retires locally with
        ``retire_reason="handoff"`` (the FleetRequest facade treats
        that as still-running)."""
        self._push_token(req, slot, t0, now)
        if req.done:
            return          # eos / one-token limit on t0: completed
                            # here, nothing left to hand off (the slot
                            # was released by _push_token)
        pkg = KVHandoff(self, req, slot)
        self._handoff_slots.add(slot)
        self._handoff_out.append(pkg)
        self.stats["handoffs_out"] += 1
        self.flight.event(req.id, "handoff_export", slot=slot,
                          prefill_len=pkg.prefill_len)
        self._finish(req, "handoff")

    def take_handoffs(self):
        """Drain the packaged finished prefills (router-facing). The
        caller OWNS delivery: every returned package must eventually
        be ``resolve()``d — delivered, deduped, or abandoned — or its
        slot stays pinned forever."""
        out = []
        while self._handoff_out:
            out.append(self._handoff_out.popleft())
        return out

    def _resolve_handoff(self, pkg):
        """Release a package's slot, exactly once (KVHandoff.resolve
        target). Double resolution is a transport-discipline bug —
        refuse loudly rather than corrupt the free list."""
        if pkg.resolved:
            raise MXNetError(
                "InferenceEngine: handoff package %r resolved twice — "
                "each package has exactly one terminal path" % (pkg,))
        pkg.resolved = True
        if pkg.slot in self._handoff_slots:
            self._handoff_slots.discard(pkg.slot)
            self._release_slot(pkg.slot)

    def set_role(self, role):
        """Widen a specialist to ``"unified"`` (failover promotion:
        the survivor of a dead prefill/decode pair serves both phases;
        any program family it is missing compiles lazily on first
        use). Narrowing a live engine is refused — slots may hold
        state the narrower role could never have produced."""
        if role == self.role:
            return
        if role != "unified":
            raise MXNetError(
                "InferenceEngine: role can only widen to 'unified' "
                "(engine %s is %r, asked for %r) — build a new engine "
                "to specialize" % (self.engine_id, self.role, role))
        self.role = "unified"
        _TM_ROLE.set(0)

    def admit_handoff(self, payload, deadline_ms=None,
                      ttft_deadline_ms=None):
        """Admit a handed-off finished prefill (router-facing): write
        the transferred KV rows into a free slot through the
        prefix-pool write path — or skip the write entirely when
        ``payload["rows"]`` is None because this engine's prefix pool
        already retains the full prefill — poke the slot's scheduler
        state to resume AFTER the prefill's first token, and continue
        decoding byte-identically to a unified engine.

        Exactly-once under retries: a package id already active or
        already imported returns the existing request without touching
        the cache (the router's retry ambiguity resolves here, the
        ``_channel_submit`` adoption discipline). Raises
        :class:`EngineOverloaded` when no slot is free — the router
        tries the next decode replica or waits."""
        self._check_open()
        if self.role == "prefill":
            raise MXNetError(
                "InferenceEngine: engine %s has role='prefill' — it "
                "exports handoffs, it cannot admit one"
                % self.engine_id)
        rid = payload["id"]
        existing = self._active.get(rid)
        if existing is not None:
            return existing
        existing = self._imported.get(rid)
        if existing is not None:
            return existing
        # Flush every dispatched-but-undrained round BEFORE touching a
        # slot: those rounds saw the slot device-dead (-1 sentinel) and
        # must not drain after the mirror names the imported request —
        # the same hazard the submit path avoids by deferring its
        # mirror write to prefill-drain time. Draining may also retire
        # finished requests and free slots, so it runs before the
        # overload check.
        while self._drain:
            self._drain_one()
        if not self._free:
            raise EngineOverloaded(
                "InferenceEngine: engine %s has no free slot for a "
                "handoff (slots=%d busy)" % (self.engine_id,
                                             self.slots))
        prompt = np.asarray(payload["prompt"], np.int32)
        tokens = [int(t) for t in payload["tokens"]]
        if not tokens:
            raise MXNetError(
                "InferenceEngine: handoff payload %r carries no first "
                "token — the prefill side emits it" % (rid,))
        req = Request(rid, prompt, int(payload["max_tokens"]),
                      payload["eos_id"], float(payload["temperature"]),
                      int(payload["seed"]),
                      min(int(payload["max_tokens"]),
                          self.max_len - prompt.size),
                      deadline_ms=deadline_ms,
                      ttft_deadline_ms=ttft_deadline_ms,
                      resume_tokens=tokens)
        # TTFT was served on the prefill engine; mark it attained so
        # cadence math never divides by a first-token gap this engine
        # did not serve
        req.t_first = req.t_submit
        trace = payload.get("trace")
        if trace is not None:
            # the wire crossing is one hop: the decode-side record
            # carries hop+1 relative to the exporting prefill engine
            req.trace = (str(trace[0]), int(trace[1]) + 1)
        P = int(payload["prefill_len"])
        if P != len(req.seq) - 1:
            raise MXNetError(
                "InferenceEngine: handoff payload %r is inconsistent — "
                "prefill_len=%d but prompt+tokens cover %d positions "
                "(+1 for the first emitted token)"
                % (rid, P, len(req.seq)))
        if P > self.prefill_buckets[-1] or payload["last"] >= self.max_len:
            raise MXNetError(
                "InferenceEngine: handoff %r does not fit this "
                "engine's geometry (prefill_len=%d, last=%d vs "
                "buckets %r, max_len=%d) — replicas in one fleet share "
                "geometry" % (rid, P, payload["last"],
                              self.prefill_buckets, self.max_len))
        slot = self._free.popleft()
        req.t_admit = time.perf_counter()
        rows = payload.get("rows")
        entry = None
        try:
            if rows is None:
                # transfer skipped on prefix affinity: the router saw
                # this engine's pool retaining the full prefill. The
                # pin brackets the copy dispatch (PR 7 discipline).
                if self._prefix is None:
                    raise MXNetError(
                        "InferenceEngine: rows-less handoff %r but "
                        "engine %s has no prefix pool"
                        % (rid, self.engine_id))
                depth, entry = self._prefix.lookup(req.seq[:P])
                if depth < P or entry is None:
                    raise MXNetError(
                        "InferenceEngine: rows-less handoff %r but "
                        "the pool covers only %d of %d prefill "
                        "positions — the router's affinity probe was "
                        "stale; retry with rows" % (rid, depth, P))
                self._prefix.acquire(entry)
                self._dispatch_copy(P, src=entry.slot, dst=slot,
                                    src_pool=True, dst_pool=False)
                self._prefix.release(entry)
                entry = None
                self.stats["prefix_hits"] += 1
                self.stats["prefix_hit_tokens"] += P
                _TM_PREFIX_HITS.inc()
                _TM_PREFIX_HIT_TOKENS.inc(P)
            else:
                self._import_rows(slot, P, rows)
            # scheduler-state poke: resume exactly where the unified
            # engine's prefill program would have left this slot
            # (pos=P, tok=t_last, live, the sampling identity, and the
            # same lastp clamp _prefill_fn computes)
            vals = (np.int32(P), np.int32(tokens[-1]), True,
                    np.float32(req.temperature), _raw_key(req.seed),
                    np.int32(-1 if req.eos_id is None else req.eos_id),
                    np.int32(payload["last"]))
            new_state = Decoder.slot_set_state(self._state, slot, vals)
            if self._mesh is not None:
                new_state = tuple(
                    jax.device_put(a, self._rep_shard)
                    for a in new_state)
            self._state = new_state
        except Exception:
            if entry is not None:
                self._prefix.release(entry)
            self._release_slot(slot)
            self._free.remove(slot)      # popleft put-back, FIFO head
            self._free.appendleft(slot)
            raise
        self._mirror[slot] = req
        self._active[rid] = req
        if req._deadline is not None or req._ttft_deadline is not None:
            self._watched.add(rid)
        if self.spec_draft == "ngram":
            self._drafters[rid] = NgramDrafter(req.seq)
        elif self.spec_draft == "model":
            self._draft_prefill_all(req, slot)
        # decode-side retention: park the prefill in THIS engine's
        # pool so the next same-prefix handoff ships no rows at all
        # (the router's affinity probe finds it via peek)
        if rows is not None and self._prefix is not None \
                and not self._pressure \
                and P <= self.prefill_buckets[-1] \
                and self._prefix.get(req.seq[:P]) is None:
            try:
                new = self._prefix.insert(req.seq[:P])
                if new is not None:
                    try:
                        self._dispatch_copy(P, src=slot, dst=new.slot,
                                            src_pool=False,
                                            dst_pool=True)
                    except Exception:
                        self._prefix.discard(new)
                        raise
                _TM_PREFIX_BYTES.set(self._prefix.bytes_used)
            except Exception:            # noqa: BLE001 — isolated
                _TM_PREFIX_INSERT_SKIPPED.inc()
        self.stats["handoffs_in"] += 1
        self.stats["submitted"] += 1
        self.capture.submit(req)
        if self.flight.enabled:
            meta = {"prompt_len": int(prompt.size),
                    "max_tokens": int(payload["max_tokens"]),
                    "handoff": True, "resumed": req.resumed}
            if req.trace is not None:
                meta["trace"], meta["hop"] = req.trace
            self.flight.start(rid, **meta)
            self.flight.event(rid, "handoff_import", slot=slot,
                              prefill_len=P,
                              rows=rows is not None)
        self._imported[rid] = req
        while len(self._imported) > 256:
            self._imported.popitem(last=False)
        return req

    # -- lifecycle: retirement, shedding, shutdown ----------------------
    def _check_open(self):
        if self._closed:
            raise EngineClosed(
                "InferenceEngine is closed — build a new engine (or "
                "restore() a snapshot)")

    def _release_slot(self, slot):
        """Host-side slot release — the same freeze contract device
        retirement uses: the device copy may still be live (it keeps
        decoding its dead request harmlessly until its own budget, or
        until the next occupant's prefill scatter overwrites its state
        and rows), and pending drain entries for it drop their tokens
        through the cleared mirror. Purely host bookkeeping: no device
        op, no new program."""
        self._mirror[slot] = None
        self._free.append(slot)

    def _finish(self, req, reason, error=None):
        """Common retirement tail for every host-side path; the
        request is handed back by the next ``step()`` return."""
        req.done = True
        req.t_done = time.perf_counter()
        req.retire_reason = reason
        req.error = error
        self._active.pop(req.id, None)
        self._watched.discard(req.id)
        self._drafters.pop(req.id, None)
        if self.flight.enabled:
            extra = {"tokens": len(req.tokens)}
            if error is not None:
                extra["error"] = str(error)
            self.flight.retire(req.id, reason, **extra)
        # a TTFT SLO cannot be attained by a request that died without
        # a first token: count the deadline retirement as a miss (the
        # burn gauges, derived from the TTFT histogram, only see
        # SERVED requests — doc/observability.md notes the split)
        if self.slo_ttft_ms is not None and req.t_first is None \
                and reason == "deadline":
            _TM_SLO_TTFT_MISS.inc()
        self.capture.retire(req)
        if reason == "deadline":
            _TM_DEADLINE.inc()
            self.stats["deadline_missed"] += 1
        elif reason == "cancelled":
            _TM_CANCELLED.inc()
            self.stats["cancelled"] += 1
        elif reason == "shed":
            _TM_SHED.inc()
            self.stats["shed"] += 1
        elif reason == "error":
            _TM_ERRORS.inc()
            self.stats["errors"] += 1
        self._done_buf.append(req)

    def _retire_active(self, req, reason, error=None):
        """Detach ``req`` from whichever scheduler structure holds it
        (queue, stager, held buffer, chunking queue, drain queue, or a
        decoding slot), releasing its slot and prefix-cache pin. The
        slot-recycle argument is `_release_slot`'s; prefix pins are
        released on EVERY path (a leaked pin would starve the pool)."""
        try:
            self._pending.remove(req)
        except ValueError:
            pass
        self._stager.prune(lambda item: item[0] is req)
        if self._held is not None and self._held[0] is req:
            self._held = None
        for st in list(self._chunking):
            if st["req"] is req:
                self._chunking.remove(st)
                if st["entry"] is not None:
                    self._prefix.release(st["entry"])
                    st["entry"] = None
                self._release_slot(st["slot"])
        for entry in self._drain:
            if entry[0] == "prefill" and entry[1] is req:
                # the staged first token is dropped at drain time (the
                # req is done); the slot frees NOW — FIFO draining
                # keeps any reuse ordered behind this entry
                self._release_slot(entry[2])
        for s in range(self.slots):
            if self._mirror[s] is req:
                self._release_slot(s)
        self._finish(req, reason, error)

    def _shed_oldest(self, why="under overload='shed_oldest' (newer "
                                "work displaced it)"):
        """Evict the oldest QUEUED (never admitted) request to make
        room (overload="shed_oldest") or to drop an unadmitted backlog
        (``why`` names the cause on the victim's error). Admitted work
        is never shed — its prefill is sunk cost. Age order: the held
        admission candidate (popped from the stager earliest), then
        staged items, then the pending deque. Returns True if one was
        shed."""
        victim = None
        if self._held is not None:
            victim = self._held[0]
        elif self._stager.staged():
            first = []

            def oldest(item):       # one-shot: prune is single-pass
                if first:
                    return False
                first.append(item)
                return True

            dropped = self._stager.prune(oldest)
            if dropped:
                victim = dropped[0][0]
        if victim is None and self._pending:
            victim = self._pending[0]
        if victim is None:
            return False
        self._retire_active(victim, "shed", EngineOverloaded(
            "InferenceEngine: request %r shed %s" % (victim.id, why)))
        return True

    def _sweep(self):
        """Round-boundary lifecycle sweep: retire cancelled and
        deadline-expired requests. Only ``_watched`` ids are visited,
        so deadline-less traffic pays nothing."""
        if not self._watched:
            return
        now = time.perf_counter()
        for rid in list(self._watched):
            req = self._active.get(rid)
            if req is None or req.done:
                self._watched.discard(rid)
                continue
            if req._cancelled:
                self._retire_active(req, "cancelled")
            elif req._expired(now):
                self._retire_active(req, "deadline")

    @property
    def _pressure(self):
        """Overloaded right now? Under a shedding policy this pauses
        prefix-cache retention (the slot→pool copy dispatch competes
        with serving work exactly when there is least room for it)."""
        return self.overload != "block" \
            and self.queued() >= self.max_queue

    def _admit(self):
        """Fill freed slots from the staged queue, between device
        steps (iteration-level scheduling). Admission = prefix-cache
        lookup (longest retained prefix → one compiled row copy into
        the slot) + the FIRST prefill piece of the uncovered suffix;
        further pieces run one budget's worth per round via the
        chunking queue. Under chunking, each admission's first piece
        draws from the round's prefill-token budget — a burst of
        arrivals admits only as much prefill work per round as the
        budget allows (the held request resumes first next round, so
        FIFO order is preserved). Returns how many requests were
        admitted."""
        admitted = 0
        now = time.perf_counter()
        while self._free:
            if self._held is not None:
                req, dev, self._held = \
                    self._held[0], self._held[1], None
            else:
                try:
                    req, dev = self._stager.next()
                except StopIteration:
                    break
            if req.done:
                continue            # retired while staged (shed/close)
            if req._cancelled or req._expired(now):
                # queue-waiting expiry: failed WITHOUT occupying a slot
                self._finish(req, "cancelled" if req._cancelled
                             else "deadline")
                continue
            if isinstance(dev, _PlacementError):
                self._finish(req, "error", MXNetError(
                    "InferenceEngine: request %r failed h2d staging "
                    "(%s)" % (req.id, dev.error)))
                continue
            p = len(req.seq)
            try:
                hit, entry, depth = 0, None, 0
                if self._prefix is not None:
                    with self._phase("prefix_lookup",
                                     hist=_TM_PREFIX_LOOKUP_MS):
                        depth, entry = self._prefix.lookup(req.seq)
                    # a FULL hit still re-prefills the last prompt
                    # token: the cache retains K/V only, and the first
                    # generated token needs the last position's logits
                    hit = min(depth, p - 1)
                    # a hit only pays when it REDUCES prefill work
                    # (fewer padded tokens across the piece split);
                    # otherwise the copy dispatch is pure overhead on
                    # top of the same bucket-quantized prefill — treat
                    # as miss
                    if hit > 0 and self._suffix_cost(p - hit) \
                            >= self._suffix_cost(p):
                        hit, entry = 0, None
            except Exception as e:       # noqa: BLE001 — trie fault
                # a corrupt trie poisons THIS request, not the engine:
                # no slot was taken, nothing was pinned
                self._finish(req, "error", MXNetError(
                    "InferenceEngine: prefix-cache lookup failed for "
                    "request %r (%s)" % (req.id, e)))
                continue
            first_piece = min(p - hit, self.prefill_chunk or p - hit)
            if first_piece > self._round_budget:
                # this round's prefill budget is spent: hold the
                # request (admitted next round, before newer arrivals)
                self._held = (req, dev)
                break
            slot = self._free.popleft()
            req.t_admit = time.perf_counter()
            _TM_QUEUE_WAIT_MS.observe(
                (req.t_admit - req.t_submit) * 1e3)
            self.flight.event(
                req.id, "admitted", slot=slot,
                queue_wait_ms=round(
                    (req.t_admit - req.t_submit) * 1e3, 3))
            st = {"req": req, "slot": slot, "dev": dev, "next": hit,
                  "entry": None,
                  # retain only prompts no entry already covers whole
                  # (a second copy buys nothing) that fit the copy
                  # bucket family (longer chunked prompts stay
                  # unretained — their prefixes can still hit via
                  # shorter entries); the overload-pressure pause is
                  # checked at the retention DISPATCH instead (the
                  # final chunk may land rounds after admission)
                  "insert": self._prefix is not None and depth < p
                  and p <= self.prefill_buckets[-1]}
            try:
                if self.spec_draft == "ngram":
                    # drafter context = prompt + emitted so far (the
                    # resumed suffix rides in req.seq); drained tokens
                    # append in _push_token
                    self._drafters[req.id] = NgramDrafter(req.seq)
                elif self.spec_draft == "model":
                    self._draft_prefill_all(req, slot)
                if self._prefix is not None:
                    if hit > 0:
                        self._prefix.acquire(entry)
                        st["entry"] = entry
                        req.prefix_hit_tokens = hit
                        self.stats["prefix_hits"] += 1
                        self.stats["prefix_hit_tokens"] += hit
                        _TM_PREFIX_HITS.inc()
                        _TM_PREFIX_HIT_TOKENS.inc(hit)
                        self.flight.event(req.id, "prefix_hit",
                                          tokens=hit)
                        self._dispatch_copy(hit, src=entry.slot,
                                            dst=slot, src_pool=True,
                                            dst_pool=False)
                    else:
                        _TM_PREFIX_MISSES.inc()
                        self.flight.event(req.id, "prefix_miss")
                if not self._advance_chunk(st):
                    self._chunking.append(st)
            except Exception as e:       # noqa: BLE001 — poisoned
                self._poison(st, e)
            admitted += 1
        return admitted

    def _poison(self, st, exc):
        """A per-request host-side failure (bad h2d, chunk math, copy
        dispatch) retires ONLY that request: its slot is released, its
        prefix pin dropped, the error carried on the request — the
        co-resident slots' requests never notice (acceptance-pinned in
        tests/test_serving_faults.py)."""
        if st["entry"] is not None:
            self._prefix.release(st["entry"])
            st["entry"] = None
        self._release_slot(st["slot"])
        req = st["req"]
        self._finish(req, "error", MXNetError(
            "InferenceEngine: request %r poisoned during admission/"
            "prefill (%s: %s) — retired alone, engine keeps serving"
            % (req.id, type(exc).__name__, exc)))

    def _draft_prefill_all(self, req, slot):
        """Build the DRAFT model's cache for a freshly admitted slot:
        the whole ``req.seq`` in bucket-capped pieces, dispatched at
        admission (the draft model is a fraction of the target's
        FLOPs, so it is not chunk-budgeted like target prefill; it
        also ignores prefix hits — the pool holds target K/V only).
        Resets the slot's draft clock and pending-token queue."""
        p = len(req.seq)
        start = 0
        top = self.prefill_buckets[-1]
        with self._phase("prefill", slot=slot, draft=True):
            while start < p:
                piece = min(p - start, top)
                bucket = self._bucket_for(piece)
                chunk = np.zeros((1, bucket), np.int32)
                chunk[0, :piece] = req.seq[start:start + piece]
                self._draft_caches = self._draft_prefill_fn(bucket)(
                    self._draft_params, self._draft_aux,
                    self._draft_caches, np.int32(slot), chunk,
                    np.int32(start), np.int32(piece))
                start += piece
        self._draft_pos[slot] = p
        self._draft_pending[slot] = []

    def _suffix_cost(self, n):
        """Prefill-work proxy for an ``n``-token suffix: total PADDED
        tokens across its piece split — what bucket quantization
        actually charges for (piece count alone would demote every hit
        whose suffix and full prompt both fit one chunk). Splits
        exactly like :meth:`_advance_chunk`: chunking off still caps
        pieces at the largest bucket (resumed sequences can exceed
        it)."""
        chunk = self.prefill_chunk or self.prefill_buckets[-1]
        total = 0
        while n > 0:
            piece = min(n, chunk)
            total += self._bucket_for(piece)
            n -= piece
        return total

    def _advance_chunk(self, st):
        """Dispatch the next prefill piece for an admitted request:
        the whole remaining suffix when chunking is off (or it fits),
        else one ``prefill_chunk``-sized piece (a RESUMED sequence
        longer than the largest bucket splits into bucket-sized pieces
        even with chunking off — same programs, same park-dead
        contract between pieces). The FINAL piece samples the first
        token in-program and (prefix cache on) retains the freshly
        built prompt K/V in the pool. Returns True once the final
        piece is dispatched. Exceptions poison only this request — the
        caller routes them to :meth:`_poison`."""
        req, slot = st["req"], st["slot"]
        flt = _SERVING_FAULTS
        if flt is not None:
            flt.serving_h2d(req)         # injected per-request fault
        params, aux = self._params, self._aux
        start = st["next"]
        p = len(req.seq)
        remaining = p - start
        piece = min(remaining,
                    self.prefill_chunk or self.prefill_buckets[-1])
        final = start + piece == p
        if start == 0 and piece == p and st["dev"] is not None:
            dev = st["dev"]            # staged whole-prompt h2d
            bucket = int(dev.shape[1])
        else:
            bucket = self._bucket_for(piece)
            chunk = np.zeros((1, bucket), np.int32)
            chunk[0, :piece] = req.seq[start:start + piece]
            dev = self._put_tokens(chunk)
        fn = self._prefill_fn(bucket)
        with self._phase("prefill", bucket=bucket, slot=slot,
                         start=start):
            self._caches, self._state, t0 = fn(
                params, aux, self._caches, self._state,
                np.int32(slot), dev, np.int32(start), np.int32(piece),
                np.bool_(final), np.float32(req.temperature),
                _raw_key(req.seed),
                np.int32(-1 if req.eos_id is None else req.eos_id),
                np.int32(req.limit - req.resumed))
        if ("prefill", bucket) not in self._prog_seen:
            self._prog_seen.add(("prefill", bucket))
            # post-dispatch arrays carry the same avals the dispatch
            # traced with (the pre-call ones may be donated) — the
            # registry converts to ShapeDtypeStructs immediately
            profiler.register_program(
                "serving_prefill_b%d" % bucket, fn,
                (params, aux, self._caches, self._state, np.int32(0),
                 dev, np.int32(0),
                 np.int32(1), np.bool_(True), np.float32(0),
                 _raw_key(0), np.int32(-1), np.int32(1)))
        self.flight.event(req.id, "prefill_chunk", start=start,
                          tokens=piece, bucket=bucket,
                          final=bool(final))
        req.prefill_chunks += 1
        st["next"] = start + piece
        self.stats["prefill_chunks"] += 1
        self._round_budget -= piece
        if not final:
            return False
        self._drain.append(("prefill", req, slot, t0))
        self.stats["prefills"] += 1
        _TM_PREFILLS.inc()
        _TM_CHUNKS.observe(req.prefill_chunks)
        if st["entry"] is not None:
            self._prefix.release(st["entry"])
            st["entry"] = None
        # a duplicate prompt admitted while this one was mid-chunk may
        # have finished first and retained the same tokens — its rows
        # are already byte-identical, so re-copying is a wasted
        # dispatch. Retention failures are NON-fatal: the request has
        # its token coming — drop the half-made entry (its rows never
        # materialized) and skip.
        try:
            # pressure is re-checked NOW, not at admission: the slot→
            # pool copy competes with serving exactly when the queue
            # is full at dispatch time (and transient pressure back at
            # admission shouldn't suppress a retention the engine has
            # room for by the final chunk)
            if st["insert"] and not self._pressure \
                    and self._prefix.get(req.seq) is None:
                ev0 = self._prefix.evictions
                new = self._prefix.insert(req.seq)
                _TM_PREFIX_EVICTIONS.inc(self._prefix.evictions - ev0)
                if new is None:
                    _TM_PREFIX_INSERT_SKIPPED.inc()
                else:
                    self.flight.event(req.id, "retained", tokens=p)
                    try:
                        # the slot's rows [0, P) ARE the prompt K/V
                        # right now — the retention copy is ordered
                        # before the slot's decode writes by the
                        # cache-tree data dependency
                        self._dispatch_copy(p, src=slot, dst=new.slot,
                                            src_pool=False,
                                            dst_pool=True)
                    except Exception:
                        self._prefix.discard(new)
                        raise
                _TM_PREFIX_BYTES.set(self._prefix.bytes_used)
        except Exception:                # noqa: BLE001 — isolated
            _TM_PREFIX_INSERT_SKIPPED.inc()
        return True

    def _busy(self):
        return (self.slots - len(self._free)
                - len(self._handoff_slots)) > 0 \
            or bool(self._pending) \
            or self._stager.staged() > 0 or self._held is not None

    def _push_token(self, req, slot, t, now):
        assert t >= 0, "drained a token from a device-dead slot"
        req.tokens.append(int(t))
        if self._spec:
            dr = self._drafters.get(req.id)
            if dr is not None:
                dr.append(t)        # n-gram context stays current
            if self._draft_dec is not None:
                # the draft cache catches up on this token before the
                # next proposal (_model_drafts)
                self._draft_pending[slot].append(int(t))
        if req.t_first is None:
            req.t_first = now
            ttft_ms = (now - req.t_submit) * 1e3
            _TM_TTFT_MS.observe(ttft_ms)
            if self.slo_ttft_ms is not None:
                (_TM_SLO_TTFT_OK if ttft_ms <= self.slo_ttft_ms
                 else _TM_SLO_TTFT_MISS).inc()
            self.flight.event(req.id, "first_token",
                              ttft_ms=round(ttft_ms, 3))
        else:
            self.flight.token(req.id, len(req.tokens))
        self.stats["tokens"] += 1
        _TM_TOKENS.inc()
        hit_eos = req.eos_id is not None and t == req.eos_id
        if hit_eos or len(req.tokens) >= req.limit:
            req.done = True
            req.t_done = now
            req.retire_reason = "eos" if hit_eos else "length"
            (_TM_RETIRED_EOS if hit_eos else _TM_RETIRED_LENGTH).inc()
            _TM_COMPLETED.inc()
            self._drafters.pop(req.id, None)
            # cadence = wall time per decode interval THIS engine ran:
            # a resumed request's pre-crash tokens arrived before
            # t_first and must not inflate the denominator
            if len(req.tokens) - req.resumed > 1:
                cadence_ms = ((req.t_done - req.t_first)
                              / (len(req.tokens) - req.resumed - 1)
                              * 1e3)
                _TM_CADENCE_MS.observe(cadence_ms)
                if self.slo_cadence_ms is not None:
                    (_TM_SLO_CAD_OK
                     if cadence_ms <= self.slo_cadence_ms
                     else _TM_SLO_CAD_MISS).inc()
            self._active.pop(req.id, None)
            self._watched.discard(req.id)
            self._release_slot(slot)
            self.stats["completed"] += 1
            self.capture.retire(req)
            self.flight.retire(req.id, req.retire_reason,
                               tokens=len(req.tokens))
            self._done_buf.append(req)

    def _guard_ready(self, arrays):
        """Round watchdog: with ``round_timeout_ms`` set, poll the
        drain head's device buffers host-side and raise a typed
        :class:`EngineStuck` instead of letting the d2h conversion
        block forever on a wedged dispatch. The undrained entry stays
        queued — a recovered device drains it on the next step."""
        if self.round_timeout_ms <= 0:
            return
        flt = _SERVING_FAULTS
        deadline = time.perf_counter() + self.round_timeout_ms / 1e3
        while True:
            stuck = flt is not None and flt.serving_round_stuck()
            if not stuck and Decoder.buffers_ready(arrays):
                return
            if time.perf_counter() >= deadline:
                _TM_WATCHDOG.inc()
                self.stats["watchdog_trips"] += 1
                self._watchdog_stuck_t = time.perf_counter()
                raise EngineStuck(
                    "InferenceEngine: dispatched round not ready after "
                    "round_timeout_ms=%g — device stuck or overloaded. "
                    "step() again to retry the drain, or snapshot()/"
                    "restore() onto a fresh engine"
                    % self.round_timeout_ms)
            time.sleep(0.001)

    def _phase(self, name, hist=None, **args):
        """``with self._phase("prefill", bucket=...):`` — the span
        ``serving.<name>`` (:class:`_Phase`), its seconds attributed to
        the in-flight round's ledger phase of that name."""
        return _Phase(self, name, hist, args)

    def _drain_one(self):
        with self._phase("drain"):
            self._drain_one_inner()

    def _drain_one_inner(self):
        entry = self._drain[0]       # peek: a watchdog trip must not
        self._guard_ready(entry[3] if entry[0] == "prefill"
                          else entry[1])  # lose the undrained round
        self._watchdog_stuck_t = None    # drained: device recovered
        self._drain.popleft()
        now = time.perf_counter()
        if entry[0] == "prefill":
            _, req, slot, t0 = entry
            if req.done:
                return               # host-retired while staged: the
                                     # slot was already released
            if self.role == "prefill":
                self._handoff_prefill(req, slot, int(np.asarray(t0)),
                                      now)
                return
            self._mirror[slot] = req
            self._push_token(req, slot, int(np.asarray(t0)), now)
        elif entry[0] == "verify":
            # [<=K+1, S] variable-width drain: row i is the i-th token
            # a slot emitted this verify round, -1 where its accepted
            # prefix ended (a slot that had no draft emits exactly
            # row 0 — its plain-decode token). Accepted drafts =
            # emitted - 1, observed per drafted slot.
            rows, dlen = np.asarray(entry[1]), entry[2]
            emitted = np.zeros((self.slots,), np.int64)
            for row in rows:
                for s in range(self.slots):
                    req = self._mirror[s]
                    t = int(row[s])
                    if req is None or t < 0:
                        continue
                    emitted[s] += 1
                    self._push_token(req, s, t, now)
            acc = 0
            for s in range(self.slots):
                if dlen[s] > 0 and emitted[s] > 0:
                    a = int(emitted[s]) - 1
                    acc += a
                    _TM_SPEC_ACCEPT_LEN.observe(a)
            if acc:
                self.stats["spec_accepted"] += acc
                _TM_SPEC_ACCEPTED.inc(acc)
        else:
            rounds = np.asarray(entry[1])   # [steps_per_round, S + columns]
            col = self.slots
            if self._moe_counted:
                # the device's counts, step by step: experts touched,
                # pairs on held experts, slots that routed nothing
                _TM_MOE_TOUCHED.inc(int(rounds[:, col].sum()))
                _TM_MOE_LAYER_STEPS.inc(
                    self._moe_counted * rounds.shape[0])
                _TM_MOE_PAIRS_HELD.inc(int(rounds[:, col + 1].sum()))
                _TM_MOE_PAIRS_ROUTED.inc(
                    self._moe_pairs_step * rounds.shape[0])
                _TM_MOE_ROWS_MASKED.inc(int(rounds[:, col + 2].sum()))
                col += 3
            if self._state_layers:
                _TM_STATE_ADVANCED.inc(int(rounds[:, col].sum()))
                _TM_STATE_POOL.inc(self.slots * self._state_layers
                                   * rounds.shape[0])
                _TM_STATE_IN_PLACE.inc(int(rounds[:, col + 1].sum()))
                col += 2
            if self._latent_layers:
                _TM_LATENT_ROWS_LIVE.inc(int(rounds[:, col].sum()))
            if self._attn_pool_rows:
                # the last column: cache rows the step's bounded reads
                # fetched, against the pool's rows over the same steps
                _TM_ATTN_ROWS_READ.inc(int(rounds[:, -1].sum()))
                _TM_ATTN_ROWS_POOL.inc(
                    self._attn_pool_rows * rounds.shape[0])
            for row in rounds:
                for s in range(self.slots):
                    req = self._mirror[s]
                    if req is not None:
                        self._push_token(req, s, int(row[s]), now)

    def _spec_round(self, busy):
        """Try to dispatch ONE verify round (doc/serving.md
        "Speculative decoding"): collect up to ``spec_k`` draft tokens
        per decodable slot from the configured drafter, and if at
        least one slot has a draft, run the verify program — one
        chunked target dispatch emitting each slot's accepted prefix
        plus one corrected token (``[<=K+1, S]`` drain). Returns False
        (→ the caller dispatches the plain decode round, counted as a
        fallback) when no slot drafted, or when ANY occupied slot sits
        too near the cache end for the fixed-width chunk write
        (``dynamic_update_slice`` clamps an out-of-range start, which
        would shift the write onto live rows — the last few tokens of
        a near-``max_len`` sequence always decode plainly)."""
        K = self.spec_k
        S = self.slots
        parts = []
        for s in range(S):
            req = self._mirror[s]
            if req is None:
                continue
            # the slot's device position (exact: spec drains eagerly)
            pos = len(req.seq) + len(req.tokens) - req.resumed - 1
            if pos + K + 2 > self.max_len:
                return False
            k_s = min(K, req.limit - len(req.tokens) - 1)
            if k_s > 0:
                parts.append((s, req, k_s))
        for st in self._chunking:
            # parked mid-prefill slots ride the chunk write too
            if st["next"] + K + 2 > self.max_len:
                return False
        for entry in self._drain:
            # a slot admitted THIS round (its prefill entry is still
            # queued, so it is not in the mirror yet) is device-live
            # at pos = len(seq) — it rides the chunk write like every
            # slot and needs the same room
            if entry[0] == "prefill" and not entry[1].done \
                    and len(entry[1].seq) + K + 2 > self.max_len:
                return False
        if not parts:
            return False
        drafts = np.zeros((S, K), np.int32)
        dlen = np.zeros((S,), np.int32)
        if self.spec_draft == "ngram":
            for s, req, k_s in parts:
                dr = self._drafters.get(req.id)
                prop = dr.propose(k_s) if dr is not None else []
                if prop:
                    drafts[s, :len(prop)] = prop
                    dlen[s] = len(prop)
            if not dlen.any():
                return False
            _TM_SPEC_NGRAM.inc(int(dlen.sum()))
        else:
            self._model_drafts(parts, drafts, dlen)
            if not dlen.any():
                return False
            _TM_SPEC_MODEL.inc(int(dlen.sum()))
        ndraft = int(dlen.sum())
        self.stats["spec_drafted"] += ndraft
        _TM_SPEC_DRAFTED.inc(ndraft)
        with self._phase("verify_round", slots_busy=busy,
                         drafted=ndraft):
            self._caches, self._state, out = self._verify_fn(
                self._params, self._aux, self._caches,
                self._state, drafts, dlen)
        if "verify" not in self._prog_seen:
            self._prog_seen.add("verify")
            profiler.register_program(
                "serving_verify", self._verify_fn,
                (self._params, self._aux, self._caches,
                 self._state, np.zeros((S, K), np.int32),
                 np.zeros((S,), np.int32)))
        self._drain.append(("verify", out, dlen))
        self.stats["steps"] += 1
        self.stats["spec_rounds"] += 1
        _TM_ROUNDS.inc()
        _TM_SPEC_ROUNDS.inc()
        _TM_SLOTS_BUSY.observe(busy)
        flt = _SERVING_FAULTS
        if flt is not None:
            flt.serving_crash()  # injected mid-round process death
        return True

    def _model_drafts(self, parts, drafts, dlen):
        """Draft-model proposals (``draft="model"``): catch the draft
        cache up on every token emitted since its last run (pending
        queues fed by ``_push_token``), then one greedy ``spec_k``-token
        proposal per slot — all in dispatches of the ONE draft
        program. Pending longer than the catch-up width (after
        fallback-round bursts) drains over several dispatches; only
        the last one's proposals are used. Slots with nothing pending
        ride along with an idempotent junk write above their head."""
        K = self.spec_k
        S = self.slots
        W = K + 1
        dd = self._draft_dec
        # each slot's proposal is taken from the dispatch in which its
        # catch-up COMPLETED: in a multi-dispatch drain (a fallback
        # burst longer than W), a slot that finished early would
        # otherwise ride later dispatches with a junk catch-up token
        # and have its valid proposal overwritten by noise
        final_props = np.zeros((S, K), np.int32)
        proposed = set()
        # a slot that holds no request rides along unread
        live = np.array([r is not None for r in self._mirror], bool)
        while True:
            pos = np.zeros((S,), np.int32)
            catchup = np.zeros((S, W), np.int32)
            clen = np.ones((S,), np.int32)
            again = False
            newly_done = []
            for s in range(S):
                pos[s] = min(self._draft_pos[s], self.max_len - W)
                pend = self._draft_pending[s]
                if pend:
                    n = min(len(pend), W)
                    catchup[s, :n] = pend[:n]
                    clen[s] = n
                    del pend[:n]
                    self._draft_pos[s] += n
                    if pend:
                        again = True
                    else:
                        newly_done.append(s)
            with self._phase("draft_round"):
                self._draft_caches, props = self._draft_fn(
                    self._draft_params, self._draft_aux,
                    self._draft_caches, pos, catchup, clen, live)
            if "draft" not in self._prog_seen:
                self._prog_seen.add("draft")
                profiler.register_program(
                    "serving_draft", self._draft_fn,
                    (self._draft_params, self._draft_aux,
                     self._draft_caches, pos, catchup, clen, live))
            if newly_done:
                props = np.asarray(props)                   # [S, K]
                for s in newly_done:
                    final_props[s] = props[s]
                    proposed.add(s)
            if not again:
                break
        for s, req, k_s in parts:
            if s in proposed:       # else: nothing pending fed the
                drafts[s, :k_s] = final_props[s, :k_s]  # draft — skip
                dlen[s] = k_s

    def step(self):
        """One scheduling round: retire cancelled/expired requests
        (round-boundary lifecycle sweep), advance every mid-prefill
        request by ONE chunk, admit staged requests into free slots
        (prefix copy + first prefill piece), dispatch ONE decode round
        (``steps_per_round`` fused all-slot steps) if any decodable
        slot is occupied, then drain output vectors that are
        ``drain_depth`` dispatches old (all of them once nothing is in
        flight). Returns the requests that finished since the last
        round — normal completions AND host retirements (check
        ``retire_reason``) — in completion order.

        Every non-idle round also lands a row in the bounded
        round-phase ledger (:meth:`round_table`, ``GET /rounds``) and
        feeds the ``serving.round_phase_ms.*`` histograms: the round's
        wall time decomposed into drain / prefix lookup / h2d staging /
        prefill / copy / decode-verify dispatch, with host scheduling
        as the exact remainder — the phases sum to the round wall time
        by construction (doc/observability.md "Round-phase
        attribution")."""
        self._check_open()
        self._phase_acc = {}
        try:
            with tele.span("serving.round", cat="serving") as rnd:
                busy, admitted, dispatched = self._round()
            self._record_round(rnd, busy, admitted, dispatched)
        finally:
            self._phase_acc = None
        done_now, self._done_buf = self._done_buf, []
        return done_now

    def _round(self):
        """The body of :meth:`step`; returns (busy slots, admissions,
        which program the round dispatched or None)."""
        dispatched = None
        if self._spec and self._drain:
            # speculation drains EAGERLY: drafting needs the
            # current context (the n-gram drafter and the
            # draft-model catch-up read drained tokens) and exact
            # per-slot positions; the tokens-per-dispatch the
            # verify step buys replaces the drain-lag pipelining
            # drain_depth bought (doc/serving.md)
            while self._drain:
                self._drain_one()
        self._sweep()
        # chunked prefill, Sarathi-style per-round budget: at most
        # ~prefill_chunk tokens of prefill work run between decode
        # rounds — ONE piece of the oldest parked request, then
        # admissions' first pieces until the budget is spent
        # (_admit holds the overflow request for next round).
        # Resident decoders therefore stall at most one budget's
        # worth of prefill per round, however many long prompts
        # are in flight.
        self._round_budget = self.prefill_chunk or float("inf")
        if self._chunking:
            st = self._chunking.popleft()
            try:
                if not self._advance_chunk(st):
                    self._chunking.append(st)
            except Exception as e:   # noqa: BLE001 — poisoned
                self._poison(st, e)
        admitted = self._admit()
        busy = self.slots - len(self._free)
        _TM_OCCUPANCY.set(busy)
        if admitted or busy:
            # zero-admission rounds COUNT while work is resident
            # (they are what admission starvation looks like — the
            # histogram's 0 bucket exists for them); only
            # fully-idle polls are not a scheduling round
            _TM_ADMITTED.observe(admitted)
        # slots still mid-prefill have nothing to decode: a round
        # with ONLY those resident would be pure wasted dispatch.
        # Handoff-pinned slots likewise (their requests left), and
        # a prefill-role engine NEVER dispatches the decode family
        # — that is the role's compile contract
        if busy - len(self._chunking) - len(self._handoff_slots) > 0 \
                and self.role != "prefill":
            if self._spec and self._spec_round(busy):
                dispatched = "verify"
            else:
                if self._spec:
                    # speculation armed but no slot had a usable
                    # draft (cold context, budget exhausted, or a
                    # slot too near the cache end for the chunk
                    # write): plain decode serves the round
                    _TM_SPEC_FALLBACK.inc()
                    self.stats["spec_fallback_rounds"] += 1
                with self._phase("decode_round", slots_busy=busy):
                    self._caches, self._state, out = self._step_fn(
                        self._params, self._aux,
                        self._caches, self._state)
                dispatched = "decode"
                if "decode" not in self._prog_seen:
                    self._prog_seen.add("decode")
                    profiler.register_program(
                        "serving_decode", self._step_fn,
                        (self._params, self._aux,
                         self._caches, self._state))
                self._drain.append(("step", out))
                self.stats["steps"] += 1
                _TM_ROUNDS.inc()
                _TM_SLOTS_BUSY.observe(busy)
                flt = _SERVING_FAULTS
                if flt is not None:
                    flt.serving_crash()   # injected process death
        # a prefill-role engine drains eagerly: no decode rounds
        # follow to push results out of the drain-lag window, and
        # every drained prefill is a handoff package the router is
        # waiting on
        while len(self._drain) > (
                self._drain_depth
                if self._busy() and self.role != "prefill" else 0):
            self._drain_one()
        self._last_ok_t = time.perf_counter()
        self._slo_tick(self._last_ok_t)
        return busy, admitted, dispatched

    def _record_round(self, rnd, busy, admitted, dispatched):
        """Land the finished round (``rnd``: its ``serving.round`` span)
        in the phase ledger + histograms. Pure-idle polls (nothing
        resident, admitted, or drained) are not scheduling rounds and
        are skipped; an aborted round (a watchdog trip unwinding
        step()) records nothing — its drain retries next round."""
        acc = self._phase_acc
        rt0, wall = rnd.t0, rnd.dt
        if not (admitted or busy or acc):
            return
        # host scheduling = the unattributed remainder (sweep, queue
        # bookkeeping, chunk math, drafter proposals). The attributed
        # phases are disjoint same-thread spans inside the round's
        # own, so the remainder is >= 0 up to float error —
        # clamped, and the phases sum to wall_ms exactly.
        acc["sched"] = max(0.0, wall - sum(acc.values()))
        phases_ms = {k: round(v * 1e3, 4) for k, v in acc.items()}
        for k, v in phases_ms.items():
            _TM_PHASE[k].observe(v)
        _TM_ROUND_WALL.observe(wall * 1e3)
        self._round_no += 1
        self._rounds.append({
            "round": self._round_no,
            "t_s": round(rt0 - self._t0, 4),
            "wall_ms": round(wall * 1e3, 4),
            "slots_busy": busy,
            "admitted": admitted,
            "dispatched": dispatched,
            "phases_ms": phases_ms,
        })

    def round_table(self, n=None):
        """The last ``n`` (default: all retained, bounded at 256)
        round-phase ledger rows, oldest first — what ``GET /rounds``
        serves. Plain dicts: round number, start time (s since engine
        construction), wall ms, occupancy, admissions, which program
        the round dispatched (``decode``/``verify``/None), and the
        per-phase ms decomposition (summing to ``wall_ms``)."""
        # exposition-server threads read while the engine thread
        # appends; deque APPEND is atomic but ITERATION over a
        # mutating deque raises RuntimeError — retry instead of
        # holding a lock on the per-round hot path (the window is one
        # append; a scrape must never silently drop the engine)
        for _ in range(8):
            try:
                rows = list(self._rounds)
                break
            except RuntimeError:
                continue
        else:
            rows = []
        if n is not None:
            n = max(0, int(n))
            rows = rows[-n:] if n else []
        return [dict(r, phases_ms=dict(r["phases_ms"])) for r in rows]

    # -- observability plane (doc/observability.md) ---------------------
    def _slo_tick(self, now=None):
        """Refresh the multi-window SLO burn gauges from the TTFT /
        cadence histograms (rate-limited inside ``tele.SloWindow`` —
        per-round calls cost a dict lookup). Called at the end of
        every ``step()`` and by the exposition server per scrape, so
        the gauges stay current even when the engine idles. The
        histograms are process-wide: with several engines in one
        process the burn gauges reflect the engine that ticked last
        (deploy one engine per process for per-replica SLOs)."""
        for kind, thr, hist, windows in (
                ("ttft", self.slo_ttft_ms, _TM_TTFT_MS,
                 _SLO_TTFT_WINDOWS),
                ("cadence", self.slo_cadence_ms, _TM_CADENCE_MS,
                 _SLO_CADENCE_WINDOWS)):
            if thr is None:
                continue
            w = self._slo_windows.get(kind)
            if w is None or w.threshold != float(thr):
                # (re)build on first use or a threshold change — the
                # window history restarts, which is the honest reading
                # of "the SLO target changed"
                w = tele.SloWindow(
                    hist, thr, target=self.slo_target,
                    windows=[(s, g) for s, g in windows])
                self._slo_windows[kind] = w
            w.tick(now)

    def health(self):
        """Liveness summary for ``/healthz`` (plain dict, host-side):
        ``stuck`` is the PR 7 watchdog state — True from a
        ``round_timeout_ms`` trip until a later drain succeeds (the
        recovered device clears it); ``closed`` after :meth:`close`.
        ``last_round_age_s`` is how long since a ``step()`` completed
        — a serving loop that stopped stepping shows up here even
        without a watchdog armed."""
        now = time.perf_counter()
        return {
            "closed": self._closed,
            "stuck": self._watchdog_stuck_t is not None,
            "draining": self.draining,
            "role": self.role,
            "watchdog_trips": self.stats["watchdog_trips"],
            "slots": self.slots,
            "slots_busy": self.slots - len(self._free),
            "queued": self.queued(),
            "handoffs_waiting": len(self._handoff_out),
            "last_round_age_s": round(now - self._last_ok_t, 3),
        }

    def request_table(self):
        """Live + recently-retired request rows for ``/requests``:
        every unfinished request (queued, staged, mid-prefill, or
        decoding) followed by the flight recorder's retired ring.
        Plain dicts, host bookkeeping only."""
        now = time.perf_counter()
        rows = []
        for req in list(self._active.values()):
            if req.done:
                continue
            state = "queued" if req.t_admit is None else "running"
            rows.append({
                "id": req.id, "state": state,
                "prompt_len": int(len(req.prompt)),
                "tokens": len(req.tokens),
                "age_s": round(now - req.t_submit, 3),
                "deadline_ms": req.deadline_ms,
                "prefix_hit_tokens": req.prefix_hit_tokens,
            })
        rows.extend(self.flight.rows())
        # multi-replica processes expose every engine's table on ONE
        # /requests endpoint — rows are indistinguishable without the
        # owning engine's identity and role
        for row in rows:
            row["engine_id"] = self.engine_id
            row["role"] = self.role
        return rows

    def serve_forever(self, requests=None):
        """Drive the loop to completion: pull submissions from
        ``requests`` (optional iterable — dict kwargs for
        :meth:`submit`, a ``(prompt, kwargs)`` pair, a bare prompt
        array, or ``None`` meaning "nothing has arrived yet", which
        lets a generator pace an online arrival process), stepping
        continuously; between pulls the engine keeps serving whatever
        is resident. Returns all finished requests in completion order
        (host retirements included — check ``retire_reason``). With
        ``requests=None`` it serves what was already submitted and
        returns when idle.

        Failure containment: if the ``requests`` iterable (or a submit
        it drives) raises mid-iteration, already-admitted work FINISHES
        first — queued-but-unadmitted requests finish too under
        ``overload="block"``, or are shed under a shedding policy —
        and only then does the original exception propagate, traceback
        intact. On KeyboardInterrupt the engine :meth:`close`\\ s
        (pending requests fail with :class:`EngineClosed`) before the
        interrupt propagates."""
        self._check_open()
        completed = []
        src = iter(requests) if requests is not None else None
        exhausted = src is None
        ingest_error = None
        try:
            while True:
                # ingest until backpressure or a pacing None — one item
                # per round would starve free slots while the source
                # has ready requests
                while not exhausted and self.queued() < self.max_queue:
                    try:
                        item = next(src)
                    except StopIteration:
                        exhausted = True
                        break
                    except Exception as e:   # noqa: BLE001
                        ingest_error = e
                        break
                    try:
                        if item is None:
                            break          # nothing ready yet: decode
                        if isinstance(item, dict):
                            self.submit(**item)
                        elif isinstance(item, tuple) \
                                and len(item) == 2 \
                                and isinstance(item[1], dict):
                            self.submit(item[0], **item[1])
                        else:
                            self.submit(item, max_tokens=self.max_len)
                    except Exception as e:   # noqa: BLE001
                        ingest_error = e
                        break
                if ingest_error is not None and not exhausted:
                    # stop ingesting; shed the unadmitted backlog when
                    # the policy allows, then drain what was admitted
                    exhausted = True
                    if self.overload != "block":
                        why = ("with the unadmitted backlog after the "
                               "request stream raised (overload=%r "
                               "drops instead of draining it)"
                               % self.overload)
                        while self._shed_oldest(why):
                            pass
                completed.extend(self.step())
                if exhausted and self.idle:
                    break
            if ingest_error is not None:
                raise ingest_error
            return completed
        except KeyboardInterrupt:
            self.close()
            raise

    # -- shutdown -------------------------------------------------------
    def close(self):
        """Shut the engine down: every pending request — queued,
        staged, mid-prefill, or decoding — fails with a typed
        :class:`EngineClosed` error (``retire_reason="closed"``,
        already-drained tokens stay readable on ``.tokens``), the
        prompt stager stops, and every slot and prefix-cache pin is
        released. Idempotent; ``submit``/``step``/``serve_forever``
        raise :class:`EngineClosed` afterwards. Also usable as a
        context manager (``with engine: ...`` closes on exit), and
        installed by ``serve_forever`` on KeyboardInterrupt."""
        if self._closed:
            return
        self._closed = True
        # a closed engine is not "stuck": the wedged round died with
        # it, and /healthz must not 503 a process that closed the
        # tripped engine and replaced it with a healthy one
        self._watchdog_stuck_t = None
        for req in list(self._active.values()):
            self._retire_active(req, "closed", EngineClosed(
                "InferenceEngine: engine closed while request %r was "
                "pending" % (req.id,)))
        self._pending.clear()
        self._chunking.clear()
        self._held = None
        self._drain.clear()
        # outbound handoff packages die with the engine: mark them
        # resolved so a router holding one cannot release the slot of
        # (or deliver rows from) a closed engine, and free the pinned
        # slots directly
        while self._handoff_out:
            self._handoff_out.popleft().resolved = True
        for slot in sorted(self._handoff_slots):
            self._release_slot(slot)
        self._handoff_slots.clear()
        self._stager.close()
        self.capture.close()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.close()
        return False

    # -- crash-safe restart ---------------------------------------------
    def snapshot(self):
        """Host scheduler state as a plain JSON-serializable dict:
        every unfinished request (queued AND in-flight) with its
        prompt, the tokens drained so far, its sampling identity
        (seed/temperature — draws are keyed ``fold_in(seed, position)``,
        so a resumed request reproduces them), and its remaining
        deadline budget, plus the engine geometry. NO device state:
        prompt K/V is a pure function of the token ids, so
        :meth:`restore` re-prefills ``prompt + emitted`` (through the
        prefix cache where it hits) and every greedy continuation is
        byte-identical to the uninterrupted run. Valid after a crashed
        ``step()`` or a watchdog trip — tokens dispatched but never
        drained are simply re-generated."""
        now = time.perf_counter()
        reqs = []
        for req in self._active.values():
            if req.done:
                continue
            reqs.append({
                "id": req.id,
                "prompt": np.asarray(req.prompt).tolist(),
                "tokens": list(req.tokens),
                "max_tokens": int(req.max_tokens),
                "eos_id": req.eos_id,
                "temperature": float(req.temperature),
                "seed": int(req.seed),
                "deadline_ms": None if req._deadline is None
                else (req._deadline - now) * 1e3,
                "ttft_deadline_ms": None
                if req._ttft_deadline is None or req.t_first is not None
                else (req._ttft_deadline - now) * 1e3,
            })
        # packaged-but-undelivered handoffs: locally retired, but the
        # work is NOT done — a restore (or the fleet failover path)
        # re-prefills prompt + the already-emitted first token and
        # serves the remainder unified, byte-identically
        for pkg in self._handoff_out:
            if pkg.resolved:
                continue
            reqs.append({
                "id": pkg.id,
                "prompt": pkg.prompt.tolist(),
                "tokens": list(pkg.tokens),
                "max_tokens": int(pkg.max_tokens),
                "eos_id": pkg.eos_id,
                "temperature": float(pkg.temperature),
                "seed": int(pkg.seed),
                "deadline_ms": None,
                "ttft_deadline_ms": None,
            })
        return {
            "version": 1,
            "auto_seed": self._auto_seed,
            # provenance, NOT geometry: restore() gives the successor
            # a fresh identity and records this id as migrated_from
            "engine_id": self.engine_id,
            "engine": self._geometry(),
            "requests": reqs,
        }

    def _geometry(self):
        """Engine geometry as plain JSON — every constructor knob a
        fresh engine needs to serve the same way. Shared by
        :meth:`snapshot` (restore() feeds it back) and the traffic
        capture's header (``tools/replay_serving.py`` rebuilds from
        it). ``capture_dir`` rides along for the crash cycle
        (None inside the capture header itself — it is written before
        the knob resolves, and replay must not re-capture by
        default)."""
        return {
            "slots": self.slots,
            "prefill_buckets": list(self.prefill_buckets),
            "max_queue": self.max_queue,
            "stage_depth": self.stage_depth,
            "drain_depth": self._drain_depth,
            "steps_per_round": self.steps_per_round,
            "prefix_cache_mb": self.prefix_cache_mb,
            "prefill_chunk": self.prefill_chunk,
            "overload": self.overload,
            "round_timeout_ms": self.round_timeout_ms,
            "slo_ttft_ms": self.slo_ttft_ms,
            "slo_cadence_ms": self.slo_cadence_ms,
            "slo_target": self.slo_target,
            "flight_recorder": self.flight.retain,
            "spec_k": self.spec_k,
            "draft": self.spec_draft,
            "tp": self.tp,
            "ep": self.ep,
            "weight_dtype": self.weight_dtype,
            "weight_group": self.weight_group,
            "matmul_impl": self.matmul_impl,
            "role": self.role,
            "handoff_dtype": self.handoff_dtype,
            "capture_dir": getattr(self, "capture_dir", None),
        }

    @classmethod
    def restore(cls, snap, decoder, **overrides):
        """Warm restart from :meth:`snapshot`: builds a fresh engine
        (same geometry unless ``overrides`` change it) on ``decoder``
        (the same weights) and resubmits every unfinished request,
        re-prefilling ``prompt + already-emitted`` so each one resumes
        exactly where it stopped — greedy continuations are
        byte-identical to an uninterrupted run, and sampled draws stay
        position-keyed. Emitted tokens reappear on the handles'
        ``.tokens``; resumed sequences longer than the largest bucket
        admit in bucket-sized pieces automatically. Remaining deadline
        budgets carry over (an already-expired one retires on the
        first round). Returns ``(engine, {request_id: Request})``.

        Speculation knobs (``spec_k``/``draft``) restore with the
        geometry; drafter context rebuilds from each request's
        ``prompt + emitted`` at admission, so accept rates warm back
        up immediately. A ``draft="model"`` snapshot needs the draft
        model back: pass ``draft_decoder=...`` in ``overrides`` (the
        snapshot is plain JSON and cannot carry weights)."""
        if not isinstance(snap, dict) or snap.get("version") != 1:
            raise MXNetError(
                "InferenceEngine.restore: not an engine snapshot "
                "(want the dict snapshot() returned)")
        cfg = dict(snap["engine"])
        # a snapshot written by an older tree names the decode read
        # it took; the read follows the cache kind now
        cfg.pop("attn_impl", None)
        cfg["prefill_buckets"] = tuple(cfg["prefill_buckets"])
        cfg.update(overrides)
        # migration provenance: the successor's capture header names
        # the donor engine, so a replayed crash/drain cycle attributes
        # each request to the replica lineage that finished it
        cfg.setdefault("migrated_from", snap.get("engine_id"))
        eng = cls(decoder, **cfg)
        handles = {}
        real_max_queue = eng.max_queue
        # resubmission must never shed: the crashed engine had already
        # accepted this work (its in-flight slots don't count as queue)
        eng.max_queue = max(real_max_queue, len(snap["requests"]))
        try:
            next_id = eng._next_id
            for r in snap["requests"]:
                req = eng.submit(
                    np.asarray(r["prompt"], np.int32),
                    max_tokens=r["max_tokens"], eos_id=r["eos_id"],
                    temperature=r["temperature"], seed=r["seed"],
                    request_id=r["id"],
                    deadline_ms=r.get("deadline_ms"),
                    ttft_deadline_ms=r.get("ttft_deadline_ms"),
                    _resume_tokens=r["tokens"])
                handles[req.id] = req
                if isinstance(req.id, int):
                    next_id = max(next_id, req.id + 1)
            eng._next_id = next_id   # fresh auto-ids never collide
            # likewise fresh auto-drawn seeds: resubmission passes
            # explicit seeds, so the new counter sits at 0 and the
            # next seed-less sampled submit would replay a resumed
            # request's draws
            eng._auto_seed = max(int(snap.get("auto_seed", 0)),
                                 *(int(r["seed"]) + 1
                                   for r in snap["requests"]), 0)
        finally:
            eng.max_queue = real_max_queue
        eng.stats["restores"] = 1
        _TM_RESTORES.inc()
        return eng, handles
