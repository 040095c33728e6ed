"""The live observability plane (ISSUE 9): HTTP exposition server,
flight recorder, SLO burn-rate accounting, XLA program/device
introspection, and the metric-catalog lint.

Everything here is host-side and compile-frugal: the ONLY compiled
program in this file is one element-wise jit in the introspection test
(~tens of ms on CPU) — no engines, no trainers. The engine-integrated
paths (flight timeline of a fault-injected run, /healthz fed by the
watchdog) are covered in tests/test_serving_faults.py on its
module-scoped engines. The registry is process-global and shared with
other test files, so assertions are delta-based or keyed to t10.*
names no other file uses.
"""
import json
import math
import os
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tele
from mxnet_tpu import telemetry_http
from mxnet_tpu.serving.flight import FlightRecorder


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers.get("Content-Type"), \
            resp.read().decode()


@pytest.fixture()
def server():
    """Ephemeral-port exposition server, stopped even on failure (the
    module singleton would otherwise leak across tests)."""
    srv = tele.serve(port=0)
    try:
        yield srv
    finally:
        tele.stop_server()


# -- satellite: histogram honesty --------------------------------------

def test_percentile_on_empty_histogram_is_nan():
    h = tele.histogram("t10.empty_hist")
    assert math.isnan(h.percentile(0.5))
    assert math.isnan(h.percentile(0.99))
    h.observe(3.0)
    assert not math.isnan(h.percentile(0.5))


def test_count_le_uses_bucket_resolution():
    h = tele.histogram("t10.le_hist", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 5000.0):
        h.observe(v)
    assert h.count_le(1.0) == 1          # exact on a bucket bound
    assert h.count_le(10.0) == 2
    assert h.count_le(5.0) == 2          # quantized UP to le=10
    assert h.count_le(100.0) == 3
    assert h.count_le(1e9) == 4          # past the last bound: total


def test_prometheus_exposes_exact_min_max():
    h = tele.histogram("t10.mm_hist")
    h.observe(0.07)
    h.observe(123.4)
    text = tele.to_prometheus()
    assert "# TYPE mxnet_t10_mm_hist_min gauge" in text
    lines = dict(l.rsplit(" ", 1) for l in text.splitlines()
                 if l.startswith("mxnet_t10_mm_hist"))
    # the histogram buckets report le=0.1/le=250 for these values; the
    # _min/_max gauges carry the EXACT extrema
    assert float(lines["mxnet_t10_mm_hist_min"]) == 0.07
    assert float(lines["mxnet_t10_mm_hist_max"]) == 123.4
    # empty histograms emit no extrema lines
    tele.histogram("t10.mm_empty")
    assert "mxnet_t10_mm_empty_min" not in tele.to_prometheus()


# -- SLO burn-rate math ------------------------------------------------

def test_slo_window_burn_rates_multi_window():
    """Burn = windowed miss fraction / error budget, from the
    cumulative histogram alone: misses age OUT of a short window while
    they still burn the long one."""
    h = tele.histogram("t10.slo_hist", buckets=(10.0, 100.0))
    g1 = tele.gauge("t10.slo_burn_short")
    g2 = tele.gauge("t10.slo_burn_long")
    w = tele.SloWindow(h, threshold=10.0, target=0.9,
                       windows=((60.0, g1), (3600.0, g2)),
                       min_interval_s=0.0)
    w.tick(now=1000.0)                     # baseline: empty
    for _ in range(8):
        h.observe(1.0)                     # attained (<= 10ms)
    for _ in range(2):
        h.observe(50.0)                    # missed
    w.tick(now=1010.0)
    # 2/10 missed, budget 0.1 -> burn 2.0 in both windows
    assert g1.value == pytest.approx(2.0)
    assert g2.value == pytest.approx(2.0)
    # 100s later: only attained traffic in the last 60s
    for _ in range(10):
        h.observe(1.0)
    w.tick(now=1110.0)
    assert g1.value == pytest.approx(0.0)          # short window clean
    assert g2.value == pytest.approx(1.0)          # 2/20 missed / 0.1
    # no traffic at all in the short window -> burn 0, not NaN
    w.tick(now=1200.0)
    assert g1.value == 0.0


def test_slo_window_rate_limits_sampling():
    h = tele.histogram("t10.slo_rl_hist")
    g = tele.gauge("t10.slo_rl_burn")
    w = tele.SloWindow(h, threshold=10.0, target=0.99,
                       windows=((60.0, g),), min_interval_s=1.0)
    for i in range(100):
        w.tick(now=500.0 + i * 0.01)       # 1s of 10ms-spaced ticks
    assert len(w._samples) == 1            # all but the first skipped


# -- flight recorder ---------------------------------------------------

def test_flight_recorder_ring_bounds_and_eviction():
    fr = FlightRecorder(retain=3)
    for rid in range(5):
        fr.start(rid, prompt_len=4)
        fr.event(rid, "admitted", slot=0)
        fr.retire(rid, "eos", tokens=2)
    live, retired = fr.ids()
    assert live == [] and retired == [2, 3, 4]     # oldest evicted
    assert fr.timeline(0) is None and fr.timeline(1) is None
    tl = fr.timeline(4)
    assert not tl["live"]
    assert [e["event"] for e in tl["events"]] == \
        ["submit", "admitted", "retire"]
    assert tl["meta"]["retire_reason"] == "eos"
    assert [r["id"] for r in fr.rows()] == [2, 3, 4]


def test_flight_recorder_event_cap_and_terminal_retire():
    fr = FlightRecorder(retain=2, max_events=8)
    fr.start("r", prompt_len=1)
    for i in range(20):
        fr.event("r", "prefill_chunk", start=i)
    fr.retire("r", "error", error="boom")
    tl = fr.timeline("r")
    assert tl["dropped_events"] == 20 - 7      # cap hit, drops counted
    assert tl["events"][-1]["event"] == "retire"   # terminal always lands
    assert tl["events"][-1]["reason"] == "error"


def test_flight_recorder_token_sampling_and_disable():
    fr = FlightRecorder(retain=4, token_sample=16)
    fr.start(1, prompt_len=1)
    for n in range(2, 40):
        fr.token(1, n)
    tl = fr.timeline(1)
    decode = [e for e in tl["events"] if e["event"] == "decode"]
    assert [e["tokens"] for e in decode] == [16, 32]
    # multi-token drains (speculative verify) make the running count
    # JUMP — sampling fires on boundary CROSSINGS, not exact
    # multiples, and the event carries the true count (PR 10)
    fr.start(2, prompt_len=1)
    for n in (5, 15, 21, 30, 37):       # skips 16 and 32 exactly
        fr.token(2, n)
    decode = [e["tokens"] for e in fr.timeline(2)["events"]
              if e["event"] == "decode"]
    assert decode == [21, 37]
    # retain=0 disables recording entirely
    off = FlightRecorder(retain=0)
    off.start(1, prompt_len=1)
    off.retire(1, "eos")
    assert off.timeline(1) is None and not off.enabled


# -- HTTP exposition server --------------------------------------------

_PROM_LINE = re.compile(
    r"^(?:# (?:TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
    r"(?:counter|gauge|histogram)|HELP .*)"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^{}]*\})? [0-9eE+.natif-]+)$")


def test_http_metrics_is_valid_prometheus_exposition(server):
    tele.counter("t10.http_events").inc(3)
    tele.histogram("t10.http_lat_ms").observe(2.0)
    status, ctype, text = _get(server.url + "/metrics")
    assert status == 200 and ctype.startswith("text/plain")
    declared = set()
    for line in text.rstrip("\n").splitlines():
        assert _PROM_LINE.match(line), "bad exposition line: %r" % line
        if line.startswith("# TYPE "):
            declared.add(line.split()[2])
        elif not line.startswith("#"):
            name = re.split(r"[ {]", line, 1)[0]
            # every sample belongs to a family declared ABOVE it
            # (histogram samples carry _bucket/_sum/_count suffixes)
            fam = re.sub(r"_(bucket|sum|count)$", "", name)
            assert name in declared or fam in declared, name
    assert "mxnet_t10_http_events_total 3" in text \
        or re.search(r"mxnet_t10_http_events_total \d+", text)
    # the scrape carries the PR 9 gauge families: SLO counters are
    # registered at import, device gauges by the scrape's own refresh
    assert "mxnet_serving_slo_ttft_attained_total" in text
    assert "mxnet_serving_slo_ttft_burn_5m" in text
    assert "mxnet_device_live_array_bytes" in text
    # cumulative bucket shape survives the wire
    lines = dict(l.rsplit(" ", 1) for l in text.splitlines()
                 if l.startswith("mxnet_t10_http_lat_ms"))
    assert lines['mxnet_t10_http_lat_ms_bucket{le="+Inf"}'] == \
        lines["mxnet_t10_http_lat_ms_count"]


def test_http_snapshot_round_trips_and_matches_registry(server):
    tele.gauge("t10.http_gauge").set(7.5)
    status, ctype, body = _get(server.url + "/snapshot")
    assert status == 200 and ctype == "application/json"
    snap = json.loads(body)                      # strict JSON parses
    assert snap["t10"]["http_gauge"] == 7.5
    assert json.loads(json.dumps(snap)) == snap  # round-trips


def test_http_unknown_paths_and_write_methods_rejected(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(server.url + "/not-an-endpoint")
    assert e.value.code == 404
    req = urllib.request.Request(server.url + "/metrics", data=b"x",
                                 method="POST")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=10)
    assert e.value.code == 405                   # strictly read-only
    status, _, body = _get(server.url + "/")
    assert status == 200 and "/flight/<request_id>" in body


def test_http_healthz_ok_and_server_restart_and_stop(server):
    status, _, body = _get(server.url + "/healthz")
    doc = json.loads(body)
    assert status == 200 and doc["status"] == "ok"
    old_port = server.port
    srv2 = tele.serve(port=0)                    # restart: singleton
    assert telemetry_http._server is srv2
    status, _, _ = _get(srv2.url + "/healthz")
    assert status == 200
    tele.stop_server()
    assert not srv2.running
    # the old server was stopped by the restart; its port is closed
    with pytest.raises(Exception):
        _get("http://127.0.0.1:%d/healthz" % old_port, timeout=2)


def test_http_server_stops_cleanly_atexit_registered():
    """serve() registers stop_server atexit, so an armed server never
    outlives the interpreter holding its port."""
    import atexit
    # the hook is registered at module import; atexit keeps it in its
    # private callback table — unregister succeeds only if present
    atexit.unregister(telemetry_http.stop_server)
    atexit.register(telemetry_http.stop_server)  # re-arm for real exits


def test_http_requests_flight_healthz_with_stub_engine():
    """/requests aggregates engine.request_table(), /flight searches
    the recorders, and /healthz turns a stuck watchdog into 503 — all
    duck-typed, so a stub keeps this zero-compile (the real engine
    path is pinned in test_serving_faults.py)."""
    from mxnet_tpu.serving import engine as engine_mod

    class _StubEngine:
        def __init__(self):
            self.flight = FlightRecorder(retain=4)
            self.stuck = False

        def request_table(self):
            # the engine contract since ISSUE 19: every row names its
            # owning engine and role (a multi-replica process exposes
            # every engine's table on ONE /requests endpoint)
            rows = [{"id": "stub-1", "state": "running",
                     "prompt_len": 3, "tokens": 1, "age_s": 0.5}] \
                + self.flight.rows()
            for row in rows:
                row["engine_id"] = "stub-e0"
                row["role"] = "unified"
            return rows

        def health(self):
            return {"closed": False, "stuck": self.stuck,
                    "watchdog_trips": int(self.stuck)}

    stub = _StubEngine()
    stub.flight.start("stub-1", prompt_len=3)
    stub.flight.event("stub-1", "admitted", slot=0)
    stub.flight.retire("stub-1", "deadline", tokens=1)
    engine_mod._ENGINES.add(stub)
    srv = tele.serve(port=0)
    try:
        _, _, body = _get(srv.url + "/requests")
        rows = json.loads(body)["requests"]
        assert {"id": "stub-1", "state": "running", "prompt_len": 3,
                "tokens": 1, "age_s": 0.5, "engine_id": "stub-e0",
                "role": "unified"} in rows
        assert any(r.get("state") == "retired" for r in rows)
        # ISSUE 19 S1 pin: every row carries the owning engine + role
        stub_rows = [r for r in rows if r["id"] == "stub-1"]
        assert len(stub_rows) >= 2        # the running + retired rows
        assert all(r["engine_id"] == "stub-e0" and r["role"] == "unified"
                   for r in stub_rows)
        _, _, body = _get(srv.url + "/flight/stub-1")
        tl = json.loads(body)
        assert [e["event"] for e in tl["events"]] == \
            ["submit", "admitted", "retire"]
        assert tl["meta"]["retire_reason"] == "deadline"
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/flight/never-submitted")
        assert e.value.code == 404
        stub.stuck = True                       # watchdog trip state
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/healthz")
        assert e.value.code == 503
        assert json.loads(e.value.read())["status"] == "stuck"
    finally:
        engine_mod._ENGINES.discard(stub)
        tele.stop_server()


def test_http_scrape_concurrent_with_writers(server):
    """Scrapes race metric writers without error — the server thread
    only ever reads under the registry's own locks."""
    stop = threading.Event()
    c = tele.counter("t10.race_count")
    h = tele.histogram("t10.race_hist")

    def writer():
        while not stop.is_set():
            c.inc()
            h.observe(1.0)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        for _ in range(10):
            status, _, _ = _get(server.url + "/metrics")
            assert status == 200
            status, _, _ = _get(server.url + "/snapshot")
            assert status == 200
    finally:
        stop.set()
        t.join(timeout=5)


# -- XLA program / device introspection --------------------------------

def test_program_registry_cost_memory_and_device_gauges():
    """register_program + collect_program_stats turn a dispatched jit
    program into program.* gauges WITHOUT re-tracing it (trace count
    pinned); device_memory always reports the live-array census and
    degrades allocator stats to absent gauges on CPU."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import profiler

    traces = []

    def f(x, s):
        traces.append(1)
        return x * 2.0 + s

    jf = jax.jit(f)
    x = jnp.ones((16, 4), jnp.float32)
    jf(x, np.float32(1)).block_until_ready()
    assert len(traces) == 1
    # eager=False exercises the scrape-time (lazy) collection path the
    # trainer uses; engine registrations collect eagerly at dispatch
    profiler.register_program("t10_prog", jf, (x, np.float32(1)),
                              eager=False)
    stats = profiler.collect_program_stats()
    assert len(traces) == 1                  # cached lowering: no re-trace
    assert stats["t10_prog"]["flops"] > 0
    snap = tele.snapshot()["program"]["t10_prog"]
    assert snap["flops"] > 0 and snap["bytes_accessed"] > 0
    # second collection is a cached no-op
    assert profiler.collect_program_stats() == {}
    # deep collection adds the compiled memory analysis
    deep = profiler.collect_program_stats(compile=True)
    assert deep["t10_prog"]["argument_bytes"] > 0
    assert "temp_bytes" in deep["t10_prog"]

    dev = profiler.device_memory()
    assert dev["live_array_bytes"] > 0
    assert dev["live_array_peak_bytes"] >= dev["live_array_bytes"]
    dsnap = tele.snapshot()["device"]
    assert dsnap["live_arrays"] >= 1
    if jax.default_backend() == "cpu":       # allocator stats absent
        assert "bytes_in_use" not in dsnap   # -> absent gauges, no error


def test_program_registry_holds_weakrefs_and_prunes_dead():
    """Review finding: the registry must not pin a dropped owner (a
    jit closure reaches the engine and its device-resident KV cache)
    — dead registrations are pruned at the next collection."""
    import gc
    import weakref
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import profiler

    class _Owner:                       # stands in for an engine
        def __init__(self):
            # the closure captures self, exactly like the engine's
            # traced step capturing its compile log — a strong
            # registry entry would pin the owner through it
            self.log = []

            def f(x):
                self.log                # trace-time touch of owner
                return x * 3.0

            self.fn = jax.jit(f)

    owner = _Owner()
    wr = weakref.ref(owner)
    x = jnp.ones((4,), jnp.float32)
    owner.fn(x).block_until_ready()
    profiler.register_program("t10_weak", owner.fn, (x,))
    assert "t10_weak" in profiler.registered_programs()
    del owner
    gc.collect()
    assert wr() is None                 # registry did not pin it
    profiler.collect_program_stats()
    assert "t10_weak" not in profiler.registered_programs()


def test_healthz_ignores_closed_stuck_engines():
    """Review finding: a watchdog-tripped engine that was closed and
    replaced must not 503 /healthz forever — only a LIVE stuck engine
    does."""
    from mxnet_tpu.serving import engine as engine_mod

    class _ClosedStuck:
        flight = FlightRecorder(retain=0)

        def request_table(self):
            return []

        def health(self):
            return {"closed": True, "stuck": True, "watchdog_trips": 1}

    stub = _ClosedStuck()
    engine_mod._ENGINES.add(stub)
    srv = tele.serve(port=0)
    try:
        status, _, body = _get(srv.url + "/healthz")
        assert status == 200
        assert json.loads(body)["status"] == "ok"
    finally:
        engine_mod._ENGINES.discard(stub)
        tele.stop_server()


def test_healthz_reports_draining_without_503():
    """Fleet satellite (ISSUE 16): a draining replica is deliberately
    refusing NEW admissions while it migrates its in-flight work — it
    is healthy, not stuck.  /healthz must stay 200 and surface the
    ``draining`` field verbatim so fleet dashboards can tell "rolling
    restart in progress" from "replica wedged" (the real engine's
    health()['draining'] flip is pinned in test_fleet.py)."""
    from mxnet_tpu.serving import engine as engine_mod

    class _Draining:
        flight = FlightRecorder(retain=0)

        def request_table(self):
            return []

        def health(self):
            return {"closed": False, "stuck": False, "watchdog_trips": 0,
                    "draining": True}

    stub = _Draining()
    engine_mod._ENGINES.add(stub)
    srv = tele.serve(port=0)
    try:
        status, _, body = _get(srv.url + "/healthz")
        assert status == 200
        doc = json.loads(body)
        assert doc["status"] == "ok"
        ours = [e for e in doc["engines"] if e.get("draining")]
        assert ours and ours[0]["draining"] is True
    finally:
        engine_mod._ENGINES.discard(stub)
        tele.stop_server()


def test_collect_lowering_miss_does_not_replay_side_effects():
    """If collection's lower() ever MISSES the lowering cache (e.g.
    committed-array avals on a real chip), the re-trace replays
    trace-time side effects — the profiler.collecting() flag lets
    compile-count logs (the serving engine's pinned contract) exempt
    introspection re-traces."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import profiler

    effects = []

    def f(x):
        if not profiler.collecting():
            effects.append(1)           # the engine's compile-log shape
        return x + 1.0

    jf = jax.jit(f)
    jf(jnp.ones((4,), jnp.float32)).block_until_ready()
    assert effects == [1]
    # different avals: the lowering cache misses, collection re-traces
    profiler.register_program("t10_miss", jf,
                              (jnp.ones((8,), jnp.float32),),
                              eager=False)
    stats = profiler.collect_program_stats()
    assert "t10_miss" in stats
    assert effects == [1]               # guarded side effect suppressed


# -- metric-catalog lint -----------------------------------------------

def test_metric_catalog_lint_is_clean():
    """Every registered dotted metric literal under mxnet_tpu/ has a
    doc/observability.md catalog row and vice versa — the catalog can
    never silently rot again."""
    from tools import lint_metrics
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    undocumented, stale = lint_metrics.lint(root)
    assert not undocumented, (
        "metrics registered in code but missing from the "
        "doc/observability.md catalog: %s" % undocumented)
    assert not stale, (
        "metrics documented in doc/observability.md but no longer "
        "registered in code: %s" % stale)


def test_metric_catalog_lint_detects_drift(tmp_path):
    """The lint actually fails on drift (guards the guard): an
    undocumented registration and a stale catalog row both trip."""
    from tools import lint_metrics
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'from . import telemetry as tele\n'
        'C = tele.counter("sub.real_metric")\n'
        'U = tele.gauge("sub.undocumented_metric")\n'
        '# tele.counter("sub.commented_out") must NOT count\n')
    doc = tmp_path / "doc"
    doc.mkdir()
    (doc / "observability.md").write_text(
        "# Catalog\n\n"
        "| Metric | Kind | Meaning |\n"
        "|---|---|---|\n"
        "| `sub.real_metric` | counter | Real. |\n"
        "| `sub.gone_metric` | gauge | Stale. |\n"
        "| `program.<name>.flops` | gauge | Pattern row. |\n")
    undocumented, stale = lint_metrics.lint(str(tmp_path))
    assert list(undocumented) == ["sub.undocumented_metric"]
    assert stale == ["sub.gone_metric"]


def test_env_knob_lint_is_clean():
    """Every MXNET_* env var the package reads has a doc/env_var.md
    row and every documented knob is still read somewhere — the knob
    catalog can't rot either (ISSUE 13 satellite; the check found
    MXNET_CONV_NHWC / MXNET_TPU_INIT_TIMEOUT
    undocumented on arrival)."""
    from tools import lint_metrics
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    undocumented, stale = lint_metrics.lint_env(root)
    assert not undocumented, (
        "env knobs read under mxnet_tpu/ but missing from "
        "doc/env_var.md: %s" % undocumented)
    assert not stale, (
        "env knobs documented in doc/env_var.md but no longer read "
        "anywhere: %s" % stale)


def test_env_knob_lint_detects_drift(tmp_path):
    """Self-test with injected drift: an undocumented environ read
    (get AND subscript forms) and a stale doc row both trip; a knob
    mentioned only in a docstring/comment does NOT count as read; a
    knob read outside mxnet_tpu/ (tools/, tests/) satisfies the stale
    check but is not required to be documented."""
    from tools import lint_metrics
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        '"""Docstring naming MXNET_DOC_ONLY must not count."""\n'
        'import os\n'
        'A = os.environ.get("MXNET_REAL_KNOB", "1")\n'
        'B = os.environ["MXNET_SUBSCRIPT_KNOB"]\n'
        'C = os.getenv("MXNET_GETENV_KNOB")\n'
        '# os.environ.get("MXNET_COMMENTED") must not count\n'
        'err = "set MXNET_MENTIONED to change this"\n')
    tools_dir = tmp_path / "tools"
    tools_dir.mkdir()
    (tools_dir / "t.py").write_text(
        'import os\nX = os.environ.get("MXNET_TOOL_KNOB")\n')
    doc = tmp_path / "doc"
    doc.mkdir()
    (doc / "env_var.md").write_text(
        "# Env\n\n"
        "| Variable | Default | Effect |\n"
        "|---|---|---|\n"
        "| `MXNET_REAL_KNOB` | `1` | Real. |\n"
        "| `MXNET_GONE_KNOB` | unset | Stale. |\n"
        "| `MXNET_TOOL_KNOB` | unset | Read under tools/ only. |\n\n"
        "| Reference variable | Where |\n"
        "|---|---|\n"
        "| `MXNET_SUBSUMED` | excluded table — must not count |\n")
    undocumented, stale = lint_metrics.lint_env(str(tmp_path))
    assert sorted(undocumented) == ["MXNET_GETENV_KNOB",
                                    "MXNET_SUBSCRIPT_KNOB"]
    assert stale == ["MXNET_GONE_KNOB"]


# -- ?prefix= subtree filter + /rounds (ISSUE 13) ----------------------

def test_http_prefix_filter_metrics_and_snapshot(server):
    """/metrics?prefix= and /snapshot?prefix= serve only the named
    dotted subtree — and the filtered exposition still obeys the line
    grammar (TYPE before samples, cumulative buckets)."""
    tele.counter("t13.pref_events").inc(2)
    tele.histogram("t13.pref_ms").observe(1.0)
    tele.gauge("other13.unrelated").set(5)
    status, _, text = _get(server.url + "/metrics?prefix=t13.")
    assert status == 200
    declared = set()
    for line in text.rstrip("\n").splitlines():
        assert _PROM_LINE.match(line), line
        if line.startswith("# TYPE "):
            declared.add(line.split()[2])
        elif not line.startswith("#"):
            name = re.split(r"[ {]", line, 1)[0]
            assert name.startswith("mxnet_t13_"), \
                "unfiltered family leaked: %r" % name
    assert "mxnet_t13_pref_events_total" in declared
    assert "mxnet_other13_unrelated" not in text
    status, _, body = _get(server.url + "/snapshot?prefix=t13.")
    snap = json.loads(body)
    assert set(snap) == {"t13"}
    assert snap["t13"]["pref_events"] == 2
    # unfiltered scrape still carries everything
    _, _, body = _get(server.url + "/snapshot")
    assert "other13" in json.loads(body)


def test_http_rounds_endpoint_reads_ledgers():
    """/rounds aggregates engine.round_table(n) across the registry
    (read-only; ?n= bounds rows per engine; engines without a ledger
    are skipped, not errors)."""
    from mxnet_tpu.serving import engine as engine_mod

    class _LedgerStub:
        flight = FlightRecorder(retain=0)

        def __init__(self):
            self.rows = [
                {"round": i, "t_s": i * 0.1, "wall_ms": 1.5,
                 "slots_busy": 1, "admitted": 0,
                 "dispatched": "decode",
                 "phases_ms": {"sched": 0.5, "dispatch": 1.0}}
                for i in range(5)]

        def round_table(self, n=None):
            return self.rows[-n:] if n else list(self.rows)

    class _NoLedger:                    # pre-ledger engine shape
        flight = FlightRecorder(retain=0)

    stub = _LedgerStub()
    engine_mod._ENGINES.add(stub)
    engine_mod._ENGINES.add(_NoLedger())
    srv = tele.serve(port=0)
    try:
        def stub_blocks(doc):
            # other live engines may share the registry (it is
            # process-wide) — key on the stub's distinctive wall_ms
            return [b for b in doc["engines"]
                    if b["rounds"]
                    and b["rounds"][-1].get("wall_ms") == 1.5]

        _, _, body = _get(srv.url + "/rounds")
        (eng,) = stub_blocks(json.loads(body))  # no-ledger stub skipped
        assert len(eng["rounds"]) == 5
        assert eng["rounds"][-1]["phases_ms"]["dispatch"] == 1.0
        _, _, body = _get(srv.url + "/rounds?n=2")
        assert len(stub_blocks(json.loads(body))[0]["rounds"]) == 2
        _, _, body = _get(srv.url + "/rounds?n=bogus")  # degrade
        assert len(stub_blocks(json.loads(body))[0]["rounds"]) == 5
        _, _, body = _get(srv.url + "/")
        assert "/rounds" in body
        req = urllib.request.Request(srv.url + "/rounds", data=b"x",
                                     method="POST")
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 405       # strictly read-only
    finally:
        engine_mod._ENGINES.discard(stub)
        tele.stop_server()


def test_http_healthz_multi_engine_itemizes_stuck_and_healthy():
    """ISSUE 13 satellite: one STUCK engine next to one healthy one
    must 503 the process (the router signal) while the payload
    itemizes BOTH engines, so an operator sees which replica-internal
    engine tripped (PR 9 only pinned the single-engine case)."""
    from mxnet_tpu.serving import engine as engine_mod

    class _Stub:
        flight = FlightRecorder(retain=0)

        def __init__(self, name, stuck):
            self.name, self.stuck = name, stuck

        def request_table(self):
            return []

        def health(self):
            return {"closed": False, "stuck": self.stuck,
                    "watchdog_trips": int(self.stuck),
                    "slots": 2, "name": self.name}

    healthy = _Stub("healthy", stuck=False)
    wedged = _Stub("wedged", stuck=True)
    engine_mod._ENGINES.add(healthy)
    engine_mod._ENGINES.add(wedged)
    srv = tele.serve(port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/healthz")
        assert e.value.code == 503
        doc = json.loads(e.value.read())
        assert doc["status"] == "stuck"
        by_name = {h["name"]: h for h in doc["engines"]
                   if "name" in h}
        assert set(by_name) == {"healthy", "wedged"}
        assert by_name["wedged"]["stuck"] is True
        assert by_name["healthy"]["stuck"] is False
        # the healthy engine alone flips the process back to 200
        engine_mod._ENGINES.discard(wedged)
        status, _, body = _get(srv.url + "/healthz")
        assert status == 200
        assert json.loads(body)["status"] == "ok"
    finally:
        engine_mod._ENGINES.discard(healthy)
        engine_mod._ENGINES.discard(wedged)
        tele.stop_server()


# -- dump_telemetry --url / --watch ------------------------------------

def test_dump_telemetry_url_and_watch_read_live_server(capsys):
    from tools import dump_telemetry
    tele.counter("t10.dump_live").inc(4)
    srv = tele.serve(port=0)
    try:
        dump_telemetry.main(["--url", srv.url])
        out = capsys.readouterr().out
        assert "dump_live" in out and "4" in out
        # a copied Prometheus scrape URL reads the JSON twin instead
        # of crashing on text exposition (review finding)
        dump_telemetry.main(["--url", srv.url + "/metrics"])
        assert "dump_live" in capsys.readouterr().out
        # --watch re-reads the source on an interval (test hook caps
        # the loop; non-tty output separates refreshes with a marker)
        dump_telemetry.main(["--url", srv.url, "--watch", "0.01",
                             "--watch-count", "2", "--serving"])
        out = capsys.readouterr().out
        assert out.count("--- refresh") == 2
    finally:
        tele.stop_server()
    # exactly one source required
    with pytest.raises(SystemExit):
        dump_telemetry.main([])


# -- the fleet tracing plane (ISSUE 19) --------------------------------

def _stub_journey(rid="f9"):
    """A stitched journey built without a fleet: router events plus an
    engine-side FlightRecorder absorbed at hop boundaries — the exact
    shape FleetRouter produces, minus the engines."""
    from mxnet_tpu.serving.fleet import FleetFlightRecorder

    ffr = FleetFlightRecorder(retain=4)
    ffr.start(rid, prompt_len=3, max_tokens=4)
    ffr.hop(rid, "eng-a")
    ffr.hop(rid, "eng-a")                 # consecutive dup collapses
    ffr.event(rid, "placed", replica="eng-a", reason="least_loaded",
              hop=1)
    efr = FlightRecorder(retain=4)
    efr.start(rid, prompt_len=3, trace=rid, hop=1)
    efr.event(rid, "admitted", slot=0)
    ffr.absorb(rid, "eng-a", efr.records(rid))   # mid-life absorption
    efr.event(rid, "first_token", ttft_ms=1.0)
    efr.retire(rid, "length", tokens=4)
    ffr.absorb(rid, "eng-a", efr.records(rid))   # hop-end absorption
    ffr.absorb(rid, "eng-a", efr.records(rid))   # idempotent
    ffr.retire(rid, "length", tokens=4, migrations=0,
               slo={"router_queue": 0.1, "prefill": 0.9,
                    "handoff_wait": 0.0, "decode_admission": 0.0,
                    "decode": 2.0, "e2e_ms": 3.0, "ttft_ms": 1.0})
    return ffr


def test_fleet_flight_recorder_stitching_and_bounds():
    """FleetFlightRecorder unit pins: absorption is idempotent per
    engine record (a live timeline() query mid-hop plus the hop-end
    sweep double-absorbs the same record — events must not
    duplicate), absorbed events land on ONE ascending clock tagged
    with their scope, consecutive duplicate hops collapse, the
    per-journey event cap drops-and-counts with the terminal retire
    always landing, and the ring evicts oldest-first."""
    from mxnet_tpu.serving.fleet import FleetFlightRecorder

    ffr = _stub_journey()
    tl = ffr.timeline("f9")
    assert tl is not None and not tl["live"]
    assert tl["hops"] == ["eng-a"]
    names = [(e["scope"], e["event"]) for e in tl["events"]]
    # each engine event exactly once despite the triple absorb
    assert names.count(("eng-a", "admitted")) == 1
    assert names.count(("eng-a", "first_token")) == 1
    assert names.count(("eng-a", "retire")) == 1
    assert names[0] == ("router", "submit")
    assert names[-1] == ("router", "retire")
    ts = [e["t_ms"] for e in tl["events"]]
    assert ts == sorted(ts) and ts[0] == 0.0
    # the absorbed submit kept the trace context it was recorded with
    sub = [e for e in tl["events"]
           if e["scope"] == "eng-a" and e["event"] == "submit"][0]
    assert sub["trace"] == "f9" and sub["hop"] == 1
    assert tl["meta"]["slo"]["e2e_ms"] == 3.0
    # chrome export: one named track per scope, SLO components as
    # back-to-back spans on the router track
    ch = ffr.chrome_trace("f9")
    tracks = {e["args"]["name"] for e in ch["traceEvents"]
              if e.get("ph") == "M"}
    assert tracks == {"router", "eng-a"}
    spans = [e for e in ch["traceEvents"] if e.get("ph") == "X"]
    assert [s["name"] for s in spans] == [
        "router_queue", "prefill", "handoff_wait",
        "decode_admission", "decode"]
    assert ch["otherData"]["trace_id"] == "f9"

    # event cap: drops counted, terminal retire still lands
    capped = FleetFlightRecorder(retain=2, max_events=8)
    capped.start("c", prompt_len=1)
    for i in range(12):
        capped.event("c", "placed", attempt=i)
    capped.retire("c", "done")
    tl = capped.timeline("c")
    assert tl["dropped_events"] == 5       # 1 submit + 7 of 12 + retire
    assert tl["events"][-1]["event"] == "retire"
    # ring eviction, oldest first
    for rid in ("r1", "r2"):
        capped.start(rid, prompt_len=1)
        capped.retire(rid, "done")
    assert capped.timeline("c") is None
    assert capped.timeline("r1") is not None
    live, retired = capped.ids()
    assert live == [] and retired == ["r1", "r2"]
    # disabled recorder: every call a no-op
    off = FleetFlightRecorder(retain=0)
    off.start("x", prompt_len=1)
    off.retire("x", "done")
    assert off.timeline("x") is None and off.rows() == []


def test_http_fleet_endpoints_with_stub_router():
    """/fleet aggregates fleet_table() over the live-router registry
    and /fleet/flight/<id> searches each router's stitched ring
    (?chrome=1 for the Perfetto export) — duck-typed like the engine
    endpoints, so a stub keeps this zero-compile (the real fleet path
    is pinned in test_serving_disagg.py)."""
    from mxnet_tpu.serving import fleet as fleet_mod

    class _StubRouter:
        _closed = False

        def __init__(self):
            self.flight = _stub_journey()
            self.ticks = 0

        def _slo_tick(self, now=None):
            self.ticks += 1

        def fleet_table(self):
            live, retired = self.flight.ids()
            return {"replicas": [{"id": "eng-a", "role": "unified",
                                  "alive": True}],
                    "stats": {"handoffs": 0},
                    "flight": {"live": live, "retired": retired},
                    "slo": {"ttft_ms": None, "cadence_ms": None}}

    router = _StubRouter()
    fleet_mod._ROUTERS.add(router)
    srv = tele.serve(port=0)
    try:
        _, _, body = _get(srv.url + "/fleet")
        fleets = json.loads(body)["fleets"]
        ours = [f for f in fleets
                if f["replicas"][0]["id"] == "eng-a"]
        assert len(ours) == 1
        assert ours[0]["flight"]["retired"] == ["f9"]
        assert router.ticks >= 1          # the scrape's SLO refresh
        _, _, body = _get(srv.url + "/fleet/flight/f9")
        tl = json.loads(body)
        assert tl["id"] == "f9" and tl["hops"] == ["eng-a"]
        assert tl["meta"]["slo"]["ttft_ms"] == 1.0
        scopes = {e["scope"] for e in tl["events"]}
        assert scopes == {"router", "eng-a"}
        _, _, body = _get(srv.url + "/fleet/flight/f9?chrome=1")
        ch = json.loads(body)
        assert ch["otherData"]["trace_id"] == "f9"
        assert any(e.get("cat") == "fleet.slo"
                   for e in ch["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/fleet/flight/never-traced")
        assert e.value.code == 404
        assert "stitched" in json.loads(e.value.read())["error"]
        # a closed router drops out of the aggregation
        router._closed = True
        _, _, body = _get(srv.url + "/fleet")
        assert not [f for f in json.loads(body)["fleets"]
                    if f.get("replicas", [{}])[0].get("id") == "eng-a"]
    finally:
        fleet_mod._ROUTERS.discard(router)
        tele.stop_server()


def test_dump_telemetry_fleet_trace_printer(capsys):
    """``--fleet --trace <id> --url ...`` prints one stitched journey
    from /fleet/flight/<id> — hops header, per-event scope table, the
    SLO decomposition — and composes with ``--watch`` for a live
    view."""
    from tools import dump_telemetry
    from mxnet_tpu.serving import fleet as fleet_mod

    class _StubRouter:
        _closed = False
        flight = None

        def _slo_tick(self, now=None):
            pass

        def fleet_table(self):
            return {"replicas": [], "stats": {}, "flight": {}, "slo": {}}

    router = _StubRouter()
    router.flight = _stub_journey()
    fleet_mod._ROUTERS.add(router)
    srv = tele.serve(port=0)
    try:
        dump_telemetry.main(["--url", srv.url, "--fleet",
                             "--trace", "f9"])
        out = capsys.readouterr().out
        assert "trace f9" in out and "retired(length)" in out
        assert "hops: eng-a" in out
        assert "first_token" in out and "eng-a" in out
        assert "slo decomposition" in out
        assert "router_queue" in out and "e2e_ms" in out
        # --watch composes: the journey re-prints per refresh
        dump_telemetry.main(["--url", srv.url, "--fleet", "--trace",
                             "f9", "--watch", "0.01",
                             "--watch-count", "2"])
        out = capsys.readouterr().out
        assert out.count("--- refresh") == 2
        assert out.count("trace f9") == 2
    finally:
        fleet_mod._ROUTERS.discard(router)
        tele.stop_server()
