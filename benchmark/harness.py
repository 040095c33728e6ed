"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the table of peaks, weights from the seed, the compile
counter, the checks that decide ``correct`` and the result line.

Nothing here belongs to one configuration, one traffic mix or one metric:
those sit in ``configs/``, ``traffic/``, ``limits/``, ``families/`` and
``metrics/`` and are found by name, so a later PR adds files and one entry
to ``BENCHMARK.json`` and edits nothing that is here.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
import time

import numpy as np

CODE = os.path.dirname(os.path.abspath(__file__))   # drivers, families, ...
HERE = CODE     # the data: traffic/, limits/, peaks.json (tests point it
ROOT = os.path.dirname(CODE)    # elsewhere); ROOT holds BENCHMARK.json

# The platform a measuring run insists on. benchmark/tests patch this from
# inside a test; the program has no option or variable that relaxes it.
PLATFORM = "tpu"


class BenchError(Exception):
    """A cell, file or name the benchmark cannot resolve."""


def load_json(*parts):
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError("benchmark: no file %s" % os.path.relpath(path, ROOT))


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` loaded by path (a metric's name may
    hold dots, which an import statement cannot spell)."""
    path = os.path.join(CODE, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError("benchmark: unknown %s %r (no %s)"
                         % (kind.rstrip("s"), name,
                            os.path.relpath(path, ROOT)))
    modname = "benchmark.%s.%s" % (kind, name.replace(".", "_"))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name):
    """Everything one cell is made of, found by the names in
    ``BENCHMARK.json``."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError("benchmark: unknown workload %r (known: %s)"
                         % (name, ", ".join(sorted(cells))))
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in confs:
        raise BenchError("benchmark: workload %r names config %r, which "
                         "BENCHMARK.json does not have"
                         % (name, cell["config"]))
    entry = confs[cell["config"]]
    cfg = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", name + ".json")

    def listed(kind):
        return [m for m in bench[kind]
                if "workloads" not in m or name in m["workloads"]]

    return {"name": name, "cell": cell, "cfg": cfg, "traffic": traffic,
            "limits": limits, "family": load_module("families",
                                                    cfg["family"]),
            "end_to_end": listed("end_to_end"),
            "per_layer": listed("per_layer"),
            "run_seconds": bench["run_seconds"]}


# -- the device ------------------------------------------------------------

def peaks_for(kind):
    """Peak FLOP/s and bytes/s of ``device_kind`` from ``peaks.json``. A
    device that is not in the table is an error, not a default."""
    table = load_json(HERE, "peaks.json")["devices"]
    for key, row in table.items():
        if kind.lower().startswith(key.lower()):
            return row
    raise BenchError("benchmark: no peaks on record for device_kind %r "
                     "(known: %s)" % (kind, ", ".join(sorted(table))))


def require_device(chips):
    """The accelerator this run measures, or SystemExit: no CPU fallback,
    no result line."""
    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise SystemExit("benchmark: needs a %s, JAX found %s (%s)"
                         % (PLATFORM, devs[0].platform, devs[0].device_kind))
    if len(devs) < chips:
        raise SystemExit("benchmark: the cell asks for %d chip(s), JAX "
                         "found %d" % (chips, len(devs)))
    return devs[:chips], peaks_for(devs[0].device_kind)


def enable_compile_cache():
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_
    DIR`` says, else one fixed path inside the checkout (the path is part
    of the cache's key). Every program is cached, however quick."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compiles and persistent-cache hits, from jax.monitoring
    (as ``chip_smoke.Smoke`` counts them)."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def memory_now(devices):
    """(peak_bytes_in_use, bytes_in_use) of the fullest chip, as the
    allocator reports them. On this runtime the allocator's peak leaves
    out a running program's temporaries (PERF.md section 7), so a driver
    adds the timed program's ``memory_analysis`` temporaries itself."""
    peak = live = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        live = max(live, int(st.get("bytes_in_use", 0)))
    return peak, live


# -- inputs and weights from the seed ---------------------------------------

def key_for(seed, stream=0):
    """A PRNG key from any whole-number seed (the driver's pass 2**31),
    one independent stream per use."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    return jax.random.fold_in(key, stream)


def _leaf(key, shape, how, dtype):
    import jax
    import jax.numpy as jnp
    kind = how[0]
    if kind == "normal":
        x = how[1] * jax.random.normal(key, shape, jnp.float32)
    elif kind == "const":
        x = jnp.full(shape, how[1], jnp.float32)
    elif kind == "around":          # how[1] + how[2] * N(0, 1)
        x = how[1] + how[2] * jax.random.normal(key, shape, jnp.float32)
    else:
        raise BenchError("benchmark: unknown weight recipe %r" % (how,))
    return x.astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(items, dtype):
    """One compiled program per list of (shape, recipe) and type: the
    layers of a model share it, whatever their leaves are called."""
    import jax
    return jax.jit(lambda keys: [_leaf(k, shape, how, dtype)
                                 for k, (shape, how) in zip(keys, items)])


def make_weights(specs, seed, dtype, names=None):
    """Weights on the device from ``seed`` in ONE jitted call, in the type
    they are used in. ``specs``: name -> (shape, recipe), the family's.
    Each leaf draws from its own key (its index in the sorted names), so
    the reference can make any subset (``names``) alone and get the same
    values, layer by layer."""
    import jax
    import jax.numpy as jnp
    order = {n: i for i, n in enumerate(sorted(specs))}
    want = sorted(specs) if names is None else list(names)
    base = key_for(seed, stream=1)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.asarray([order[n] for n in want], jnp.uint32))
    items = tuple((tuple(specs[n][0]), tuple(specs[n][1])) for n in want)
    vals = _maker(items, jnp.dtype(dtype))(list(keys))
    return dict(zip(want, vals))


# -- the control's precision -------------------------------------------------

def _fp8(x, dtype, top):
    """``x`` through an 8-bit float type and back, one scale a tensor."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0).astype(x.dtype)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


def fake_quant(x, precision):
    """A matmul's or convolution's operand in the control's precision:
    rounded to it and back, the gradient passed straight through.
    ``fp8`` is the step below bfloat16 that would tempt a later PR: e4m3
    forward, one scale a tensor (Micikevicius et al., arXiv:2209.05433)."""
    import jax
    import jax.numpy as jnp
    if precision is None:
        return x
    if precision != "fp8":
        raise BenchError("benchmark: unknown control precision %r"
                         % (precision,))
    q = _fp8(x, jnp.float8_e4m3fn, 448.0)
    return x + jax.lax.stop_gradient(q - x)


def grad_quant(y, precision):
    """A matmul's or convolution's result: the value passes unchanged, the
    gradient that comes back to it is rounded to the control's backward
    type (``fp8``: e5m2, as fp8 training keeps its gradients)."""
    import jax
    import jax.numpy as jnp
    if precision is None:
        return y

    @jax.custom_vjp
    def through(v):
        return v

    through.defvjp(lambda v: (v, None),
                   lambda _, g: (_fp8(g, jnp.float8_e5m2, 57344.0),))
    return through(y)


# -- correct ------------------------------------------------------------------

class Checks:
    """Every number compared, each beside its limit. ``correct`` is true
    only when every number is finite and within its limit."""

    def __init__(self, limits):
        self.limits = limits["limits"]
        self.rows = []

    def add(self, name, value, limit=None):
        if limit is None:
            if name not in self.limits:
                raise BenchError("benchmark: no limit on record for %r"
                                 % name)
            limit = self.limits[name]
        value = float(value)
        ok = bool(np.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": ok})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def as_dict(self):
        return {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}

    def print(self, file=None):
        file = file or sys.stderr
        for r in self.rows:
            print("check %s value=%.6g limit=%.6g %s"
                  % (r["name"], r["value"], r["limit"],
                     "ok" if r["ok"] else "FAILED"), file=file)
        print("correct=%s" % self.correct, file=file, flush=True)


def worst_leaf_gap(prog, ref, skip=()):
    """The contract's measure for a training cell. ``prog``/``ref``: name
    -> norm of one leaf. The gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger; the worst leaf counts. Returns (gap, leaf)."""
    names = [n for n in sorted(ref) if n not in skip]
    med = float(np.median([ref[n] for n in names]))
    worst, which = 0.0, None
    for n in names:
        gap = abs(float(prog[n]) - float(ref[n])) / max(float(ref[n]), med)
        if not np.isfinite(gap):
            return float("inf"), n
        if gap >= worst:
            worst, which = gap, n
    return worst, which


def median_leaf_gap(prog, ref, skip=()):
    """The same gap for the median leaf: steady where the worst leaf
    swings."""
    names = [n for n in sorted(ref) if n not in skip]
    med = float(np.median([ref[n] for n in names]))
    return float(np.median([abs(float(prog[n]) - float(ref[n]))
                            / max(float(ref[n]), med) for n in names]))


# -- the result line -----------------------------------------------------------

def result_line(checks, attempted, failed, metrics, device, breakdown=None):
    """The contract's last line of standard output."""
    out = {"correct": checks.correct, "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks.as_dict()
    return json.dumps(out)


def p90(values):
    """The 90th percentile, an observed value (no interpolation)."""
    return float(np.percentile(np.asarray(values, np.float64), 90,
                               method="higher"))


class Stopwatch:
    """Seconds since the process started measuring set-up: ``run.py``
    creates it before the first heavy import."""

    def __init__(self, t0=None):
        self.t0 = time.perf_counter() if t0 is None else t0

    def __call__(self):
        return time.perf_counter() - self.t0
