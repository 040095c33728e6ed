"""Runtime user kernels: the TPU analogue of MXRtc.

Parity: ``python/mxnet/rtc.py`` + ``src/common/mxrtc.cc`` — the reference
lets users JIT-compile raw CUDA source at runtime (NVRTC) and launch it on
NDArrays with engine-tracked dependencies. On TPU the user-supplied kernel
is a **Pallas** kernel function; this module wraps it so it (a) runs
eagerly on NDArrays like ``Rtc.push``, and (b) composes into symbolic
graphs as an operator.

Example::

    def scale_kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    op = mx.rtc.PallasOp("scale2", scale_kernel,
                         out_shapes=lambda shapes: [shapes[0]])
    y = op.push([x_nd])[0]                  # imperative, like Rtc.push
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray

__all__ = ["Rtc", "PallasOp"]


class PallasOp:
    """A user Pallas kernel callable on NDArrays.

    Parameters
    ----------
    name : str
    kernel : pallas kernel ``f(*in_refs, *out_refs)``
    out_shapes : list of shapes, or callable(in_shapes) -> list of shapes
    out_dtypes : optional list of dtypes (defaults to input[0] dtype)
    grid, in_specs, out_specs : forwarded to ``pl.pallas_call`` (optional;
        default = whole-array blocks in VMEM)
    interpret : force interpreter (defaults to "not on TPU")
    """

    def __init__(self, name, kernel, out_shapes, out_dtypes=None, grid=None,
                 in_specs=None, out_specs=None, interpret=None):
        self.name = name
        self.kernel = kernel
        self.out_shapes = out_shapes
        self.out_dtypes = out_dtypes
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.interpret = interpret

    def _shapes_for(self, in_shapes):
        if callable(self.out_shapes):
            return [tuple(s) for s in self.out_shapes(list(in_shapes))]
        return [tuple(s) for s in self.out_shapes]

    def apply(self, *xs):
        """Traceable application on jax arrays (usable inside jit)."""
        from .ops.pallas_kernels import _pallas_call, _use_interpret
        interpret = self.interpret
        if interpret is None:
            interpret = _use_interpret()
        out_shapes = self._shapes_for([x.shape for x in xs])
        dtypes = self.out_dtypes or [xs[0].dtype] * len(out_shapes)
        out_shape = [jax.ShapeDtypeStruct(s, d)
                     for s, d in zip(out_shapes, dtypes)]
        if len(out_shape) == 1:
            out_shape = out_shape[0]
        kwargs = {}
        if self.grid is not None:
            kwargs["grid"] = self.grid
        if self.in_specs is not None:
            kwargs["in_specs"] = self.in_specs
        if self.out_specs is not None:
            kwargs["out_specs"] = self.out_specs
        # traced with x64 off like the library's own kernels: a user
        # kernel's python ints would otherwise reach Mosaic as int64
        return _pallas_call(self.kernel, *xs, out_shape=out_shape,
                            interpret=interpret, **kwargs)

    def push(self, ins, out=None):
        """Eager launch on NDArrays (reference ``Rtc.push(ins, outs, ...)``:
        grid/block come from the kernel's specs here, not launch args).
        Returns list of output NDArrays (written into ``out`` if given)."""
        for x in ins:
            if not isinstance(x, NDArray):
                raise MXNetError("push expects NDArrays")
        outs = self.apply(*[x._val for x in ins])
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        if out is not None:
            for dst, val in zip(out, outs):
                dst._set(val.astype(dst.dtype))
            return out
        return [NDArray._from_jax(jnp.asarray(o), ins[0].context)
                for o in outs]

    __call__ = push


class Rtc(PallasOp):
    """Reference-named alias (python/mxnet/rtc.py Rtc): runtime-compiled
    user kernels. The NVRTC-era signature took (name, inputs, outputs,
    kernel_source); here the kernel is a Pallas function."""
