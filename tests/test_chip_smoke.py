"""CPU rehearsal of chip_smoke.py and the compile-cache helper.

chip_smoke.py refuses anything but a TPU and has no option or
environment variable that relaxes that. The rehearsal relaxes it from
INSIDE the test: it patches the module's ``PLATFORM`` to the CPU and
its ``SIZES`` to toy shapes (depth and widths cut — this finds wrong
paths, arguments and control flow, not chip faults), then runs the
same phase functions the chip runs.
"""
import importlib.util
import json
import os

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

TOY = {
    "resnet_layers": 18, "resnet_classes": 10, "image": 32,
    "train_batch": 4, "train_steps": 4, "clock_steps": 2,
    "vocab": 61, "layers": 1, "embed": 32, "heads": 4,
    "seq": 16, "lm_batch": 4, "lm_steps": 3,
    "max_len": 32, "slots": 4, "buckets": (8, 16),
    "steps_per_round": 2,
    "requests": ((3, 6), (7, 5), (12, 6), (3, 6), (9, 4)),
    "arm_requests": ((3, 5), (7, 4), (3, 5)), "score_len": 32,
    "mesh_steps": 2, "tp_slots": 4,
}


_CACHE_CONFIG = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture()
def keep_cache_off(monkeypatch, tmp_path):
    """The tests never turn the persistent cache on: the helper is
    pointed at a throw-away directory through the variable (so it sets
    none in code), and whatever it changes in jax's config is put
    back."""
    import jax
    saved = {k: getattr(jax.config, k) for k in _CACHE_CONFIG}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jaxcache"))
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.fixture()
def smoke(monkeypatch, keep_cache_off):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "PLATFORM", "cpu")
    monkeypatch.setattr(mod, "SIZES", dict(TOY))
    # the fused fit path is the default on an all-tpu ctx only when
    # the backend has that many devices — true on the 8-device CPU mesh
    # too (mx.tpu() resolves there), so nothing else needs steering
    return mod


def _last_json(out):
    return json.loads(out.strip().splitlines()[-1])


def test_smoke_refuses_cpu(capsys, keep_cache_off):
    """Unpatched, the script exits non-zero on this CPU-only machine,
    never prints ``ok`` and never reaches the compile cache."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_raw", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(SystemExit) as e:
        mod.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
    import jax
    assert jax.config.jax_compilation_cache_dir is None


def test_smoke_one_chip_phases_toy(smoke, capsys):
    assert smoke.main([]) == 0
    out = capsys.readouterr().out
    for phase in ("device", "train", "clock", "lm_train", "serve"):
        assert "[%s] ok" % phase in out
    last = _last_json(out)
    assert last["ok"] is True and last["device"]["platform"] == "cpu"
    assert set(last) == {"ok", "device"}


def test_smoke_four_chip_phases_toy(smoke, capsys):
    assert smoke.main(["--chips", "4"]) == 0
    out = capsys.readouterr().out
    for phase in ("device", "mesh_train", "tp_serve"):
        assert "[%s] ok" % phase in out
    assert "[train]" not in out and "[serve]" not in out
    assert _last_json(out)["ok"] is True


def test_smoke_failed_check_is_fatal(smoke, capsys, monkeypatch):
    """A failed check raises out of main: no ``ok`` line."""
    def broken(*a):
        smoke.check(False, "injected")
    monkeypatch.setattr(smoke, "phase_train", broken)
    with pytest.raises(AssertionError, match="injected"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


# -- the compile-cache helper -------------------------------------------

def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from mxnet_tpu import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    from mxnet_tpu import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".cache", "jax")
    assert compile_cache.cache_dir() == want
    assert compile_cache.cache_dir() == want      # never varies


def test_compile_cache_enable_sets_no_other_dir(keep_cache_off,
                                                tmp_path):
    """With the variable set, enable() leaves the directory to JAX."""
    import jax
    from mxnet_tpu import compile_cache
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path / "jaxcache")
    assert jax.config.jax_compilation_cache_dir == before


# -- bench.py: the TPU or nothing, and no exit 0 after a caught arm -----

class _FakeTpu:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_bench_refuses_cpu_and_unknown_chip():
    import bench
    with pytest.raises(SystemExit, match="needs a TPU"):
        bench._require_tpu()
    fake = _FakeTpu()
    fake.device_kind = "TPU v9 imaginary"
    with pytest.raises(RuntimeError, match="no peak FLOP/s on record"):
        bench._peak_flops(fake)
    assert bench._peak_flops(_FakeTpu()) == 197e12


@pytest.mark.parametrize("quant_ab", ["raises", "survives_its_probe"])
def test_bench_caught_arm_fails_the_run(quant_ab, monkeypatch, capsys,
                                        tmp_path, keep_cache_off):
    """An arm that raises is reported with its traceback and its keys
    stay null, the arms after it still run, the headline still prints
    — and the exit is non-zero, naming the arms. Every guarded arm is
    made to raise (a stub that returned would have to know each arm's
    result shape); in the second case the quantized serving A/B
    returns while its lowering probe raises."""
    import bench
    called = []

    def arm(name, result):
        def stub(*a, **kw):
            called.append(name)
            if isinstance(result, Exception):
                raise result
            return result
        return stub

    guarded = [n for n in dir(bench) if n.startswith("bench_")]
    always = {"bench_gemm_calibration": None,
              "bench_resnet50": (1000.0, 900.0, 0.3),
              "bench_inception_bn": 500.0}
    for name in guarded:
        monkeypatch.setattr(bench, name, arm(
            name, always.get(name, RuntimeError("injected: " + name))))
    if quant_ab == "survives_its_probe":
        monkeypatch.setattr(bench, "bench_serving_quant", arm(
            "bench_serving_quant", {"int8": {"tokens_per_sec": 7.0}}))
    monkeypatch.setattr(bench, "_require_tpu", lambda: _FakeTpu())
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert "arm(s) raised" in str(e.value)
    assert "serving_quant_bytes" in str(e.value)
    assert sorted(set(called)) == sorted(guarded)       # none skipped
    out, err = capsys.readouterr()
    assert "injected: bench_serving_quant_bytes" in err  # the traceback
    head = _last_json(out)
    assert head["metric"] == "resnet50_imagenet_train_throughput"
    assert head["value"] == 1000.0
    assert head["extra"]["serving_int4_bytes_ratio"] is None
    extra = json.load(open(tmp_path / "BENCH_extra.json"))
    if quant_ab == "survives_its_probe":
        assert head["extra"]["serving_quant_tokens_per_sec"] == 7.0
        assert extra["serving_weight_quant"]["serving_batch_probe"] is None
    else:
        assert extra["serving_weight_quant"] is None
        assert str(e.value).count(",") >= 15            # every guarded arm
