"""The program's own names in a profiler trace (``.xplane.pb``): device time
per named scope inside a compiled program, and the program's host spans
with their arguments.

``trace.py`` reads a trace through ``jax.profiler.ProfileData``, which
shows each event's own stats (its start and duration) and nothing else.
The JAX name stack of an operation (``jit(_step_impl)/mx.grads/jvp(
Convolution/stem_conv)/conv_general_dilated``) sits in the stat ``tf_op``
of the event's METADATA, beside ``program_id``, ``hlo_category``, ``flops``
and ``bytes_accessed``; so this file decodes the ``XSpace`` message itself,
with the few fields it needs declared below through ``google.protobuf``
(no TensorFlow import: 15 s, and not promised on the chip's machine).

What the names mean (PERF.md section 3): ``mxnet_tpu`` traces every Symbol
node under ``jax.named_scope("<OpType>/<node name>")``, the trainer's step
under ``mx.grads`` / ``mx.clip`` / ``mx.optimizer``, the decoder's attention
under ``.../cache`` and ``.../attend``; JAX wraps a scope in ``jvp(...)`` on
the forward pass of a differentiated function and in ``transpose(...)`` on
the backward pass. A fusion carries the path of ONE of its operations
(XLA keeps the root's or the heaviest instruction's metadata), so a fusion
that spans two scopes is attributed whole to one of them. The host spans
are ``mxnet_tpu.telemetry.span`` regions (``serving.round``,
``serving.decode_round`` with ``slots_busy=`` ..., ``train.step``), which
enter a ``jax.profiler.TraceAnnotation`` and so land on a line of the
``/host:CPU`` plane, on the device trace's clock.

A program without scopes (the parent of the PR that added them) gives
paths that match no predicate: every reader built on this file then
returns None, and its metric is left out of the line.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import sys
import time

if __name__ == "__main__":      # ``python3 benchmark/scopes.py <trace>``
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import harness as H
from benchmark import trace as T

HOST_PREFIXES = ("serving.", "train.", "io.")
# a Symbol node's scope anywhere in a path: ``Convolution/stem_conv``
NODE = re.compile(r"(^|[/(])_?[A-Z]\w*/\w")


@functools.lru_cache(maxsize=None)
def _xspace():
    """The ``XSpace`` message class, from the fields this file reads
    (tsl/profiler/protobuf/xplane.proto; the two maps are declared as
    what they are on the wire: repeated key/value entries)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, f64, string, msg = (F.TYPE_INT64, F.TYPE_UINT64,
                                  F.TYPE_DOUBLE, F.TYPE_STRING,
                                  F.TYPE_MESSAGE)
    fd = descriptor_pb2.FileDescriptorProto(
        name="benchmark_scopes_xplane.proto", package="benchxplane",
        syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, of in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=F.LABEL_REPEATED if of and of[0] == "*"
                            else F.LABEL_OPTIONAL)
            if of:
                f.type_name = ".benchxplane." + of.lstrip("*")

    message("XStat", ("metadata_id", 1, i64, ""),
            ("double_value", 2, f64, ""), ("uint64_value", 3, u64, ""),
            ("int64_value", 4, i64, ""), ("str_value", 5, string, ""),
            ("ref_value", 7, u64, ""))
    message("XEvent", ("metadata_id", 1, i64, ""),
            ("offset_ps", 2, i64, ""), ("duration_ps", 3, i64, ""),
            ("stats", 4, msg, "*XStat"))
    message("XLine", ("name", 2, string, ""), ("timestamp_ns", 3, i64, ""),
            ("events", 4, msg, "*XEvent"))
    message("XEventMetadata", ("id", 1, i64, ""), ("name", 2, string, ""),
            ("stats", 5, msg, "*XStat"))
    message("XStatMetadata", ("id", 1, i64, ""), ("name", 2, string, ""))
    message("EventMetadataEntry", ("key", 1, i64, ""),
            ("value", 2, msg, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, i64, ""),
            ("value", 2, msg, "XStatMetadata"))
    message("XPlane", ("name", 2, string, ""), ("lines", 3, msg, "*XLine"),
            ("event_metadata", 4, msg, "*EventMetadataEntry"),
            ("stat_metadata", 5, msg, "*StatMetadataEntry"))
    message("XSpace", ("planes", 1, msg, "*XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchxplane.XSpace"))


def _stats(stats, stat_names):
    """{stat name: value} of one event or one event's metadata. A
    ``ref_value`` points at a stat-metadata name (how strings are
    interned)."""
    out = {}
    for s in stats:
        if s.str_value:
            v = s.str_value
        elif s.ref_value:
            v = stat_names.get(s.ref_value, s.ref_value)
        elif s.double_value:
            v = s.double_value
        else:
            v = s.int64_value or s.uint64_value
        out[stat_names.get(s.metadata_id, s.metadata_id)] = v
    return out


class Scopes:
    """One parsed trace. ``programs``: program_id -> {"name", "calls",
    "seconds"}; ``ops``: program_id -> {operation name -> {"calls",
    "seconds", "path" (the ``tf_op`` name stack, "" where the compiler
    left none), "category", "text"}} (first chip only, as ``trace.py``
    ranks operations); ``host``: [(name, start_ns, end_ns, stats)] of the
    program's own spans, in order of start."""

    def __init__(self, programs, ops, host):
        self.programs, self.ops, self.host = programs, ops, host

    def program_ids(self, name):
        """The ids of the programs called ``name`` (``jit_prefill`` is one
        name and a program per bucket)."""
        return [pid for pid, row in self.programs.items()
                if row["name"] == name]

    def calls(self, name):
        return sum(self.programs[p]["calls"] for p in self.program_ids(name))

    def scope_seconds(self, name, predicate):
        """Device seconds, over the traced window, of the operations of
        the programs called ``name`` whose path ``predicate`` accepts.
        Containers (a ``while`` holds its body's operations, which the
        line lists too) are left out, as ``trace.reduce`` ranks them."""
        return sum(row["seconds"] for row in self.leaves(name)
                   if predicate(row["path"]))

    def leaves(self, name):
        """The operations of the programs called ``name`` that hold no
        other: what ``scope_seconds`` sums over."""
        return [row for pid in self.program_ids(name)
                for row in self.ops.get(pid, {}).values()
                if not T.CONTAINER.search(row["text"])]

    def spans(self, name):
        """The host spans called ``name``: [(start_ns, end_ns, stats)]."""
        return [(s, e, st) for n, s, e, st in self.host if n == name]


def read(path):
    """Parse ``path`` (an ``.xplane.pb``) into a :class:`Scopes`."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    programs, ops, host = {}, {}, []
    first = None
    for plane in space.planes:
        m = T.DEVICE_PLANE.match(plane.name)
        if not m and plane.name != T.HOST_PLANE:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        if m:
            chip = int(m.group(1))
            if first is not None and chip != first:
                continue            # one chip's operations, as trace.py
            first = chip
            _device(plane, meta, stat_names, programs, ops)
        else:
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    if name.startswith(HOST_PREFIXES):
                        s = base + ev.offset_ps
                        host.append((name, s // 1000,
                                     (s + ev.duration_ps) // 1000,
                                     _stats(ev.stats, stat_names)))
    host.sort(key=lambda h: h[1])
    return Scopes(programs, ops, host)


def _device(plane, meta, stat_names, programs, ops):
    described = {}      # event-metadata id -> (program_id, row template)
    for line in plane.lines:
        if line.name == T.MODULE_LINE:
            for ev in line.events:
                text = meta[ev.metadata_id].name
                m = re.search(r"\((\d+)\)$", text)
                pid = m.group(1) if m else text
                row = programs.setdefault(
                    pid, {"name": T.program_name(text), "calls": 0,
                          "seconds": 0.0})
                row["calls"] += 1
                row["seconds"] += ev.duration_ps / 1e12
        elif line.name == T.OP_LINE:
            for ev in line.events:
                got = described.get(ev.metadata_id)
                if got is None:
                    md = meta[ev.metadata_id]
                    st = _stats(md.stats, stat_names)
                    got = (str(st.get("program_id", "")),
                           T.op_name(md.name),
                           {"calls": 0, "seconds": 0.0,
                            "path": str(st.get("tf_op", "")).rstrip(":"),
                            "category": str(st.get("hlo_category", "")),
                            "text": md.name})
                    described[ev.metadata_id] = got
                pid, name, template = got
                row = ops.setdefault(pid, {}).setdefault(name, template)
                row["calls"] += 1
                row["seconds"] += ev.duration_ps / 1e12


def newest_trace():
    """The ``.xplane.pb`` the run's driver wrote: the newest under the
    directory ``run.py`` gives every driver. ``harness.ROOT`` is read when
    called, since the tests move it."""
    found = glob.glob(os.path.join(H.ROOT, ".cache", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def of_run(ctx):
    """The :class:`Scopes` of this run's trace, parsed once and kept for
    every reader of the run; None where there is no trace. ``ctx`` (the
    readers' context) carries no path, so it is found again."""
    if not ctx.get("trace"):
        return None
    path = newest_trace()
    if path is None:
        return None
    st = os.stat(path)
    return _read_once(path, st.st_mtime, st.st_size)


@functools.lru_cache(maxsize=1)
def _read_once(path, mtime, size):
    t0 = time.perf_counter()
    sc = read(path)
    print("scopes: read %d bytes of trace in %.2f s"
          % (size, time.perf_counter() - t0), flush=True)
    return sc


# -- what the readers share --------------------------------------------------

def per_call_ms(ctx, which, predicate):
    """Device milliseconds per call of the program the traffic file names
    ``programs.<which>``, over the operations whose path ``predicate``
    accepts; None where the trace holds no such operation."""
    sc = of_run(ctx)
    name = ctx["traffic"].get("programs", {}).get(which)
    if sc is None or not name:
        return None
    calls = sc.calls(name)
    secs = sc.scope_seconds(name, predicate)
    if not calls or secs <= 0:
        return None
    return secs / calls * 1e3


def under(scope):
    """A predicate: the path holds the scope ``scope`` as a whole segment
    (bare, or inside ``jvp(...)`` / ``transpose(...)`` / ``vmap(...)``).
    ``scope`` may end in ``/`` to mean any node of an operator type."""
    pat = re.compile(r"(^|[/(])" + re.escape(scope)
                     + (r"" if scope.endswith("/") else r"($|[/)])"))
    return lambda path: bool(pat.search(path))


def print_split(ctx, which, parts, top=8):
    """One ``scopes:`` line for reading by hand: the device time per call
    of ``programs.<which>``, split over ``parts`` ({label: predicate},
    first match wins), what no part claims, and the largest operations of
    that remainder by name."""
    sc = of_run(ctx)
    name = ctx["traffic"].get("programs", {}).get(which)
    calls = sc.calls(name) if sc is not None and name else 0
    if not calls:
        return
    got = dict.fromkeys(parts, 0.0)
    rest = {}
    for row in sc.leaves(name):
        for label, pred in parts.items():
            if pred(row["path"]):
                got[label] += row["seconds"]
                break
        else:
            key = T.op_name(row["text"])
            rest[key] = rest.get(key, 0.0) + row["seconds"]
    total = sum(got.values()) + sum(rest.values())
    ranked = sorted(rest.items(), key=lambda kv: -kv[1])[:top]
    kinds = {}          # ``copy.249`` and ``copy.254`` are one kind
    for key, secs in rest.items():
        kind = re.sub(r"[.\d]+$", "", key)
        kinds[kind] = kinds.get(kind, 0.0) + secs
    print("scopes: %s calls=%d leaf_ops_ms=%.4f module_ms=%.4f %s "
          "unscoped_ms=%.4f scoped_share=%.2f%% unscoped_kinds=[%s] "
          "unscoped_top=[%s]"
          % (name, calls, total / calls * 1e3,
             sum(sc.programs[p]["seconds"]
                 for p in sc.program_ids(name)) / calls * 1e3,
             " ".join("%s_ms=%.4f" % (k, v / calls * 1e3)
                      for k, v in got.items()),
             sum(rest.values()) / calls * 1e3,
             100.0 * sum(got.values()) / max(total, 1e-30),
             ", ".join("%s %.4f" % (k, v / calls * 1e3) for k, v in
                       sorted(kinds.items(), key=lambda kv: -kv[1])[:top]),
             ", ".join("%s %.4f" % (k, v / calls * 1e3)
                       for k, v in ranked)), flush=True)


def describe(path, top=15):
    """For reading a trace by hand: per program its device time, the
    share under each top-level scope, and the unscoped operations."""
    sc = read(path)
    for pid, prog in sorted(sc.programs.items(),
                            key=lambda kv: -kv[1]["seconds"]):
        rows = [r for r in sc.ops.get(pid, {}).values()
                if not T.CONTAINER.search(r["text"])]
        total = sum(r["seconds"] for r in rows)
        print("PROGRAM %s id=%s calls=%d module_ms=%.3f leaf_ops_ms=%.3f"
              % (prog["name"], pid, prog["calls"], prog["seconds"] * 1e3,
                 total * 1e3))
        groups = {}
        for r in rows:
            key = "/".join(re.sub(r"^jit\([^)]*\)/", "",
                                  r["path"]).split("/")[:2]) or "(no path)"
            g = groups.setdefault(key, [0.0, 0])
            g[0] += r["seconds"]
            g[1] += 1
        for key, (secs, n) in sorted(groups.items(),
                                     key=lambda kv: -kv[1][0])[:top]:
            print("    %8.3f ms %5.1f%% ops=%-5d %s"
                  % (secs * 1e3, 100 * secs / max(total, 1e-12), n, key))
    names = {}
    for n, s, e, st in sc.host:
        row = names.setdefault(n, [0, 0, set()])
        row[0] += 1
        row[1] += e - s
        row[2].update(st)
    for n, (c, ns, keys) in sorted(names.items()):
        print("HOST %-28s calls=%-6d ms=%.3f stats=%s"
              % (n, c, ns / 1e6, sorted(keys)))


if __name__ == "__main__":
    arg = sys.argv[1]
    found = sorted(glob.glob(os.path.join(
        arg, "plugins", "profile", "*", "*.xplane.pb"))) \
        if os.path.isdir(arg) else [arg]
    describe(found[-1])
