"""Multi-host runtime initialization.

TPU-native replacement for the reference's process bootstrap: ps-lite's
scheduler/server/worker roles wired through ``DMLC_ROLE``/``DMLC_PS_ROOT_*``
env vars (src/kvstore/kvstore_dist.h, python/mxnet/kvstore_server.py,
tools/launch.py trackers). There are no server processes here — every
process is a worker holding a slice of one global device mesh, and
cross-host traffic is XLA collectives over ICI/DCN. What remains of the
bootstrap is JAX distributed initialization: coordinator address + process
count + process id, carried in ``MXNET_TPU_*`` env vars (set by
``tools/launch.py``) or auto-detected on real TPU pods.
"""
from __future__ import annotations

import os

import jax

__all__ = ["initialize", "is_initialized", "rank", "num_workers",
           "local_devices", "barrier", "shutdown"]

_initialized = False


def initialize(coordinator=None, num_processes=None, process_id=None,
               local_device_count=None):
    """Initialize the multi-process runtime.

    With no args: reads ``MXNET_TPU_COORDINATOR`` / ``MXNET_TPU_NUM_WORKERS``
    / ``MXNET_TPU_RANK`` (set by tools/launch.py), else tries TPU-pod
    auto-detection, else becomes a single-process run (no-op).

    ``local_device_count`` forces N virtual CPU devices per process
    (testing multi-host on localhost, SURVEY.md §4's "real processes on one
    machine" strategy).
    """
    global _initialized
    if _initialized:
        return
    coordinator = coordinator or os.environ.get("MXNET_TPU_COORDINATOR")
    if num_processes is None and "MXNET_TPU_NUM_WORKERS" in os.environ:
        num_processes = int(os.environ["MXNET_TPU_NUM_WORKERS"])
    if process_id is None and "MXNET_TPU_RANK" in os.environ:
        process_id = int(os.environ["MXNET_TPU_RANK"])
    if local_device_count is None and "MXNET_TPU_LOCAL_DEVICES" in os.environ:
        local_device_count = int(os.environ["MXNET_TPU_LOCAL_DEVICES"])

    if local_device_count is not None:
        # must run before backend init
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices",
                          int(local_device_count))
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass
    # generous join deadline: on oversubscribed hosts (the 1-core CI
    # box runs 4 jax processes) a peer's XLA compile can stall it for
    # minutes before it reaches the rendezvous; the default 5-minute
    # window was the main source of coordination-service flakes
    init_timeout = int(os.environ.get("MXNET_TPU_INIT_TIMEOUT", 600))
    if coordinator is None and num_processes is None:
        # single process (or TPU pod with full auto-detection)
        try:
            jax.distributed.initialize(
                initialization_timeout=init_timeout)
        except Exception:
            pass  # not in a managed multi-host environment
    else:
        jax.distributed.initialize(coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id,
                                   initialization_timeout=init_timeout)
    _initialized = True


def is_initialized():
    return _initialized


def rank():
    """This process's index (reference: kvstore rank / DMLC worker id)."""
    return jax.process_index()


def num_workers():
    return jax.process_count()


def local_devices():
    return jax.local_devices()


def barrier(name="mxnet_tpu_barrier"):
    """Global process barrier (reference Postoffice::Barrier)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


def shutdown():
    global _initialized
    if _initialized and jax.process_count() > 1:
        jax.distributed.shutdown()
    _initialized = False
