"""Device context.

Parity with ``/root/reference/python/mxnet/context.py`` (Context stack,
``mx.cpu()``/``mx.gpu()``) and ``include/mxnet/base.h:90-175`` (dev type
codes), extended with a first-class TPU device type per the north star.

On this runtime every context resolves to a JAX device: ``tpu(i)`` (and
``gpu(i)``, kept as a compatibility alias for accelerator #i) map to the
default JAX backend's devices; ``cpu()`` maps to the host platform. Data
placement is done with ``jax.device_put`` instead of cudaMemcpy.
"""
from __future__ import annotations

from .base import MXNetError

__all__ = ["Context", "current_context", "cpu", "gpu", "tpu", "cpu_pinned"]


class Context:
    """A device context (device type + device id).

    Reference: ``include/mxnet/base.h:90-175`` — kCPU=1, kGPU=2, kCPUPinned=3;
    this build adds kTPU=4 (``Context::kMaxDevType`` in the reference is 4, so
    the on-disk code stays in range).
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    default_ctx = None  # set below

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        elif isinstance(device_type, str):
            if device_type not in Context.devstr2type:
                raise MXNetError("unknown device type %s" % device_type)
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        else:
            self.device_typeid = int(device_type)
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = Context.default_ctx
        Context.default_ctx = self
        return self

    def __exit__(self, ptype, value, trace):
        Context.default_ctx = self._old_ctx

    # --- JAX resolution -------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax.Device.

        tpu/gpu → i-th device of the default backend, WHATEVER its
        platform: with no accelerator present ``mx.tpu()`` names a host
        device, which is what lets the tests run the same programs on
        the CPU mesh. It also means a context never proves where a
        program ran — the entry points that measure (``chip_smoke.py``,
        ``bench.py``) check ``jax.devices()[0].platform`` themselves.
        cpu/cpu_pinned → i-th host-platform device.
        """
        import jax

        # local_devices, not devices: in multi-process runs the global list
        # leads with other processes' (non-addressable) devices; a Context
        # always names a device THIS process can allocate on (the
        # reference's Context is likewise process-local, base.h:90-175)
        if self.device_type in ("tpu", "gpu"):
            devs = jax.local_devices()
        else:
            try:
                devs = [d for d in jax.local_devices()
                        if d.platform == "cpu"]
                if not devs:
                    raise RuntimeError
            except RuntimeError:
                devs = jax.local_devices()
        if self.device_id < len(devs):
            return devs[self.device_id]
        # an id past the last device is an error, not device 0: a
        # program written for four chips that finds one must not pile
        # everything onto the first without a word
        raise MXNetError(
            "%s: this process has %d %s device(s)"
            % (self, len(devs), devs[0].platform))


Context.default_ctx = Context("cpu", 0)


def cpu(device_id=0):
    """Return a CPU context (reference: ``context.py:79``)."""
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    """Pinned-memory CPU context; on TPU hosts identical to cpu()."""
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Accelerator context — compatibility alias mapping onto TPU chips."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context: the i-th chip of the default JAX backend."""
    return Context("tpu", device_id)


def current_context():
    """Return the current context (reference: ``context.py:103``)."""
    return Context.default_ctx
