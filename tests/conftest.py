"""Test harness: run on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; per the reference's test strategy
(SURVEY.md §4: multi-process localhost testing for dist kvstore), all
sharding/collective paths are tested on
``--xla_force_host_platform_device_count=8``.

``JAX_PLATFORMS=cpu`` and the device-count flag are set here, before any
backend initializes (both are read lazily at first backend init), and
``jax_platforms`` is pinned through the config as well, so a jax that was
imported earlier cannot pick another platform. Set
``MXNET_TPU_TEST_ON_TPU=1`` to leave the platform alone — on the chip
machine that puts THIS pytest process on the chip, and then no test may
start a child that wants it (doc/distributed_training.md).
"""
import os

if os.environ.get("MXNET_TPU_TEST_ON_TPU") != "1":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")


# pytest markers ("slow", "faults") are registered once, in
# pyproject.toml [tool.pytest.ini_options] — not duplicated here.


def _needs_native(path, _cache={}):
    """Does this test module touch the native libraries?  Detected from
    the module SOURCE (``.so`` / ``get_lib`` / ``im2rec`` references), so
    a future native-dependent test file is picked up automatically —
    no hand-maintained file list to drift."""
    if path not in _cache:
        try:
            with open(path, "r", errors="ignore") as f:
                src = f.read()
        except OSError:
            src = ""
        _cache[path] = any(tok in src for tok in
                           (".so", "get_lib", "im2rec", "dist_worker"))
    return _cache[path]


def pytest_collection_modifyitems(config, items):
    """Build the native libs only when a selected test actually needs
    them, so pure-Python selections (``pytest tests/test_symbol.py``)
    pay nothing (advisor round 3)."""
    if os.environ.get("MXNET_TPU_SKIP_NATIVE_BUILD") == "1":
        return
    if any(_needs_native(str(it.fspath)) for it in items):
        _ensure_native_built()


def _ensure_native_built():
    """Build the native IO/C-API libraries so their tests never silently
    skip on a fresh clone (the reference's Makefile likewise builds
    libmxnet.so before anything runs).  Best-effort: if the toolchain is
    missing the affected tests still skip with their own message.
    """
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lib = os.path.join(root, "mxnet_tpu", "lib", "libmxnet_tpu.so")
    if os.path.exists(lib):
        return
    try:
        subprocess.run(["make", "-C", os.path.join(root, "cpp")],
                       check=True, capture_output=True, timeout=600)
    except Exception as exc:  # pragma: no cover - toolchain missing
        import warnings

        warnings.warn("native build failed; native IO tests will skip: %s"
                      % (exc,))
