"""From a profiler trace (``.xplane.pb``) to device-busy intervals,
per-program and per-operation device time, and the idle gaps named by what
the host was doing.

Only ``jax.profiler.ProfileData`` reads the file. The arithmetic below it
works on plain tuples, so ``benchmark/tests`` checks it on hand-made
intervals and on the small recorded trace kept in ``tests/data``.

What a TPU trace looks like (read by hand, PERF.md section 6): one plane
per chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per
executed program (``jit_<fn>(<fingerprint>)``) and whose line ``XLA Ops``
has one event per operation inside it; host threads are lines of the plane
``/host:CPU``, and a ``jax.profiler.TraceAnnotation`` is an event on the
calling thread's line.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
CONTAINER = re.compile(r" (while|conditional|call)\(")


# -- taking a trace --------------------------------------------------------------

@contextlib.contextmanager
def null_annotation(name):
    yield


def annotation(name):
    """A span on the host's line of the profiler's own trace, so that an
    idle gap of the device can be named by what the host was doing."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Capture:
    """``with Capture(dir) as cap:`` traces the block; ``cap.path`` is the
    ``.xplane.pb`` it left. The directory is emptied first: a trace is a
    run's own, and the disk keeps every block ever written."""

    def __init__(self, directory):
        self.dir = directory
        self.path = None

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        self.path = found[-1] if found else None


# -- reading a trace ---------------------------------------------------------------

def read_events(path):
    """{"devices": {chip: {"modules": [...], "ops": [...]}}, "host":
    [...]}; every event is (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    key = "modules"
                elif line.name == OP_LINE:
                    key = "ops"
                else:
                    continue
                dev[key] += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
            out["devices"][int(m.group(1))] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)]
    return out


# -- arithmetic on intervals -----------------------------------------------------------

def union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_and_gaps(intervals, window):
    """(busy ns inside ``window``, the idle gaps [(start, end)]) for one
    chip's events."""
    w0, w1 = window
    merged = union((max(s, w0), min(e, w1)) for s, e in intervals)
    busy = sum(e - s for s, e in merged)
    gaps, at = [], w0
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = e
    if w1 > at:
        gaps.append((at, w1))
    return busy, gaps


def program_name(event_name):
    """``jit_step(1234567)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name):
    """An operation's name without its trailing instance number, so that
    ``fusion.12`` and ``fusion.340`` stay apart but the program's own
    kernel names (``%flash_fwd.3``) reduce to the kernel."""
    return event_name.lstrip("%").split(" = ")[0]


def by_name(events, name_of, window):
    """name -> calls, seconds inside ``window`` and the event's full text
    (an operation's is its HLO line, which says what kind it is)."""
    w0, w1 = window
    out = {}
    for name, s, e in events:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        row = out.setdefault(name_of(name), [0, 0, name])
        row[0] += 1
        row[1] += e - s
    return {k: {"calls": c, "seconds": ns / 1e9, "text": text}
            for k, (c, ns, text) in out.items()}


def name_gaps(gaps, host, top=10):
    """The longest gaps, each named by the host annotation that covers
    most of it ("unattributed" where none does): [[name, seconds]]."""
    rows = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "unattributed", 0
        for name, hs, he in host:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        rows.append([best, (e - s) / 1e9])
    return rows


def reduce(path, top=10):
    """What the per-layer readers and the result line take from a trace.
    The window is the span of the device's own events (first start to
    last end over the chips), so the profiler's start-up and the time
    after the last program are not counted as idle."""
    ev = read_events(path)
    devs = ev["devices"]
    if not devs:
        return None
    every = [x for d in devs.values() for x in d["modules"] + d["ops"]]
    if not every:
        return None
    window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    busy_ns, gaps = [], []
    programs, ops = {}, {}
    for chip in sorted(devs):
        d = devs[chip]
        # operations where the line has them, whole programs otherwise
        basis = d["ops"] or d["modules"]
        b, g = busy_and_gaps([(s, e) for _, s, e in basis], window)
        busy_ns.append(b)
        if chip == min(devs):
            gaps = g
            programs = by_name(d["modules"], program_name, window)
            ops = by_name(d["ops"], op_name, window)
    # an operation that only holds others (a scan's loop) would count its
    # children's time twice in the ranking
    ranked = sorted(((k, v) for k, v in ops.items()
                     if not CONTAINER.search(v["text"])),
                    key=lambda kv: -kv[1]["seconds"])
    return {"window_s": (window[1] - window[0]) / 1e9,
            "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "chips": len(devs), "programs": programs, "ops": ops,
            "breakdown": {
                "device_ops": [[k, v["seconds"]] for k, v in ranked[:top]],
                "idle_gaps": name_gaps(gaps, ev["host"], top)}}


def describe(path, top=12):
    """What a trace holds, for reading one by hand: planes, lines, event
    counts and the events that took most time on each line."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE %r lines=%d" % (plane.name, len(lines)))
        for line in lines:
            total = {}
            n = 0
            for e in line.events:
                n += 1
                row = total.setdefault(e.name, [0, 0.0])
                row[0] += 1
                row[1] += e.duration_ns
            print("  LINE %r events=%d names=%d" % (line.name, n,
                                                     len(total)))
            ranked = sorted(total.items(), key=lambda kv: -kv[1][1])
            for name, (c, ns) in ranked[:top]:
                print("      %-70s calls=%-6d ms=%.3f"
                      % (name[:70], c, ns / 1e6))
            for name, (c, ns) in ranked:
                if "custom-call" in name or "custom_call" in name:
                    print("      CUSTOM calls=%d ms=%.3f %s"
                          % (c, ns / 1e6, name[:600]))


if __name__ == "__main__":
    import sys
    found = sorted(glob.glob(os.path.join(
        sys.argv[1], "plugins", "profile", "*", "*.xplane.pb"))) \
        if os.path.isdir(sys.argv[1]) else [sys.argv[1]]
    describe(found[-1])
    out = reduce(found[-1])
    if out:
        print({k: out[k] for k in ("window_s", "busy_s", "chips",
                                   "breakdown")})
        print({k: (v["calls"], v["seconds"])
               for k, v in out["programs"].items()})
