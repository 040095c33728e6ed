"""Tracing / profiling.

The reference has no dedicated profiler — its observability is the
Monitor callback, `Speedometer`, plan dumps and `MXNET_ENGINE_INFO` op
logs (SURVEY §5). On TPU the right tool is the XLA profiler: this module
wraps ``jax.profiler`` with a stable mxnet-style surface so traces can be
captured around any training region and opened in TensorBoard/Perfetto.

Usage::

    mx.profiler.start("/tmp/traces")     # or profiler_set_config + start
    ... training steps ...
    mx.profiler.stop()

    with mx.profiler.scope("epoch-3"):   # named sub-regions in the trace
        train_epoch()
"""
from __future__ import annotations

import weakref as _weakref

import jax
import numpy as _np

from . import telemetry

_state = {"dir": None, "running": False}


def profiler_set_config(output_dir: str):
    """Configure the trace output directory before :func:`start`."""
    _state["dir"] = output_dir


def start(output_dir: str | None = None):
    """Begin capturing a device+host trace."""
    if output_dir is not None:
        _state["dir"] = output_dir
    if _state["dir"] is None:
        raise ValueError("profiler: no output dir configured")
    jax.profiler.start_trace(_state["dir"])
    _state["running"] = True


def stop():
    """End the capture and flush the trace to the output dir."""
    if _state["running"]:
        jax.profiler.stop_trace()
        _state["running"] = False


# a named region in BOTH captures (the profiler's host plane and the
# ``MXNET_TRACE_DIR`` Chrome trace): it IS the telemetry span
scope = telemetry.span


def device_memory_profile() -> bytes:
    """Snapshot of current device memory (pprof format)."""
    return jax.profiler.device_memory_profile()


# ---------------------------------------------------------------------------
# compiled-program analysis (the reference's example/memcost tool reports
# the memory planner's totals; XLA's equivalents are memory_analysis and
# cost_analysis on the compiled executable)

def compiled_stats(compiled):
    """FLOPs/bytes/memory for a compiled jax function (the object
    returned by ``jax.jit(f).lower(...).compile()``) or for an Executor
    (uses its infer program). Returns a dict with whatever the backend
    reports: flops, bytes_accessed, argument/output/temp sizes."""
    if hasattr(compiled, "_compiled_infer"):  # Executor duck-type
        compiled = compiled._compiled_infer()  # cached; no recompile
    out = {}
    try:
        cost = compiled.cost_analysis()
        for k in ("flops", "bytes accessed"):
            if k in cost:
                out[k.replace(" ", "_")] = float(cost[k])
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# XLA program introspection registry (doc/observability.md "Program and
# device introspection"): the hot-path jit sites — the serving engine's
# three program families, the fused trainer step — REGISTER their
# jitted callable + argument avals here at first dispatch, and
# `collect_program_stats` turns registrations into `program.*` gauges
# on demand. Two-phase on purpose:
#
# * registration is nearly free: one tree_map to ShapeDtypeStructs
#   (nothing device-resident is retained — donated buffers must not be
#   pinned by an introspection registry);
# * collection reads `Lowered.cost_analysis()` through jax's lowering
#   cache — the avals match the dispatch that already traced, so this
#   re-traces nothing, compiles nothing, and never touches the device.
#   The TPU reports no cost for a lowering: there the gauges exist only
#   after `compile=True`, which AOT-compiles and reads `cost_analysis()`
#   AND `memory_analysis()` (temporaries included) from the compiled
#   executable (one extra backend compile per program, a cache read
#   where the persistent cache is on) — bench/tool territory, on
#   demand only, never the scrape path or set-up.
#
# Everything is best-effort: an analysis a backend doesn't report
# degrades to an absent gauge, never an error.

_programs = {}        # name -> (jitted_fn, aval_args)
_collected = {}       # name -> depth collected ("cost" | "memory")

# thread-local "a collection lower() is running" flag: when the
# lowering cache HITS (the normal case — collection uses the avals the
# dispatch traced with) nothing re-runs; if it ever MISSES (e.g.
# committed-array avals on a real chip), the re-trace replays
# trace-time side effects — the serving engine's compile-count log
# checks this flag so an introspection re-trace can never corrupt the
# pinned compile contract. Thread-local so a scrape-thread collection
# never masks a real compile on the dispatch thread.
import threading as _threading

_collecting = _threading.local()


def collecting():
    """True on the thread currently lowering for introspection."""
    return getattr(_collecting, "active", False)


def _aval(x):
    """Shape/dtype skeleton of one argument leaf. Arrays (jax, numpy,
    numpy scalars) become ShapeDtypeStructs; python scalars pass
    through unchanged — their weak type is part of the lowering cache
    key, and substituting a typed aval would force a re-trace."""
    if isinstance(x, jax.Array):
        # the sharding rides along: an array on a mesh and one off it
        # are different argument types to jit, and dropping it made
        # every collection of a sharded program re-trace
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=x.sharding)
    if isinstance(x, (_np.ndarray, _np.generic)):
        return jax.ShapeDtypeStruct(_np.shape(x), x.dtype)
    return x


def register_program(name, fn, args, eager=True):
    """Register a jitted program for introspection: ``fn`` is the
    ``jax.jit`` callable, ``args`` the positional arguments of a real
    dispatch (converted to avals immediately; safe to call with
    donated buffers). Re-registering a name (a recompile) clears its
    collected stats so the next collection refreshes the gauges.

    The callable is held by WEAK reference: a jit wrapper's closure
    reaches its owner (the serving engine's traced step appends to
    ``self._compile_log`` — so ``fn`` transitively pins the engine,
    its slot-paged KV cache and the decoder weights). A strong
    registry entry would keep a dropped engine's device memory alive
    forever and defeat the ``serving/engine._ENGINES`` WeakSet;
    dead registrations are pruned at the next collection instead.

    ``eager=True`` (the default) collects the COST gauges right here,
    through the lowering the dispatch just populated (a cache hit:
    ~ms, no re-trace) — so the gauges survive the owner being dropped
    (FeedForward.fit discards its trainer after fitting; serving
    engines churn through restore()). Worst case on a cache miss is
    one abstract re-trace at the registration site. ``eager=False``
    defers to the next ``collect_program_stats`` — only correct for
    owners that outlive the scrape."""
    try:
        avals = tuple(jax.tree_util.tree_map(_aval, a) for a in args)
        ref = _weakref.ref(fn)
    except Exception:
        return                      # introspection must never raise
    _programs[name] = (ref, avals)
    _collected.pop(name, None)
    if eager:
        try:
            _collect_one(name, fn, avals, compile=False)
        except Exception:
            pass


def _collect_one(name, fn, avals, compile):
    """Lower + analyze one program into its gauges; returns the stats
    dict (empty when the backend reports nothing). Without ``compile``
    the cost is the lowering's (the CPU backend reports one, the TPU
    none); with it, cost and memory both come from the COMPILED
    executable, which every backend fills."""
    stats = {}
    _collecting.active = True
    try:
        prog = fn.lower(*avals)
    finally:
        _collecting.active = False
    try:
        if compile:
            prog = prog.compile()
        cost = prog.cost_analysis()
        if isinstance(cost, (list, tuple)):   # one dict per partition
            cost = cost[0] if cost else {}
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in (cost or {}):
                stats[k.replace(" ", "_")] = float(cost[k])
    except Exception:
        pass
    if compile:
        try:
            ma = prog.memory_analysis()
            for k in ("argument_size_in_bytes",
                      "output_size_in_bytes", "temp_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    stats[k.replace("_size_in_bytes", "_bytes")] \
                        = int(v)
        except Exception:
            pass
    for k, v in stats.items():
        telemetry.gauge("program.%s.%s" % (name, k)).set(v)
    _collected[name] = "memory" if compile else "cost"
    return stats


def collect_program_stats(compile=False):
    """Materialize `program.<name>.*` gauges for every registered
    program; returns ``{name: {stat: value}}``. Cheap by default (see
    the registry note above); ``compile=True`` adds the compiled
    memory analysis. Already-collected programs are skipped until
    re-registered (or a deeper collection is requested)."""
    out = {}
    want = "memory" if compile else "cost"
    for name, (ref, avals) in list(_programs.items()):
        fn = ref()
        if fn is None:              # owner dropped: prune, don't pin
            _programs.pop(name, None)
            _collected.pop(name, None)
            continue
        if _collected.get(name) in (want, "memory"):
            continue
        try:
            stats = _collect_one(name, fn, avals, compile)
        except Exception:
            continue                # e.g. avals no longer lowerable
        if stats:
            out[name] = stats
    return out


def registered_programs():
    """Names currently registered for introspection."""
    return sorted(_programs)


# device-memory watermarks: the live-array census works on every
# backend (it is jax's own bookkeeping, no device op); allocator
# stats (bytes_in_use / peak / limit) exist only where the backend
# reports them (TPU/GPU) and degrade to absent gauges elsewhere
_dev_peak = {"live": 0.0}


def device_memory():
    """Best-effort device-memory occupancy, refreshed into `device.*`
    gauges and returned as a dict. Host-side only: a census of live
    ``jax.Array`` bytes (every backend) plus allocator stats where the
    backend exposes ``Device.memory_stats()`` (absent on CPU). The
    live-bytes watermark persists across calls, so a snapshot diff
    across a workload shows its HBM high-water mark."""
    out = {}
    try:
        live_bytes = 0
        live_count = 0
        for a in jax.live_arrays():
            try:
                if not a.is_deleted():
                    live_bytes += a.nbytes
                    live_count += 1
            except Exception:
                continue
        _dev_peak["live"] = max(_dev_peak["live"], float(live_bytes))
        telemetry.gauge("device.live_array_bytes").set(live_bytes)
        telemetry.gauge("device.live_arrays").set(live_count)
        telemetry.gauge("device.live_array_peak_bytes").set(
            _dev_peak["live"])
        out.update(live_array_bytes=live_bytes,
                   live_arrays=live_count,
                   live_array_peak_bytes=_dev_peak["live"])
    except Exception:
        pass
    try:
        in_use = peak = limit = 0
        have = False
        for d in jax.devices():
            ms = getattr(d, "memory_stats", None)
            ms = ms() if callable(ms) else None
            if not ms:
                continue
            have = True
            in_use += ms.get("bytes_in_use", 0)
            peak += ms.get("peak_bytes_in_use", 0)
            limit += ms.get("bytes_limit", 0)
        if have:
            telemetry.gauge("device.bytes_in_use").set(in_use)
            telemetry.gauge("device.peak_bytes_in_use").set(peak)
            telemetry.gauge("device.bytes_limit").set(limit)
            out.update(bytes_in_use=in_use, peak_bytes_in_use=peak,
                       bytes_limit=limit)
    except Exception:
        pass
    return out
