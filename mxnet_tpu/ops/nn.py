"""Neural-network operators.

Parity targets in ``/root/reference/src/operator/``: fully_connected-inl.h,
convolution-inl.h, deconvolution-inl.h, activation-inl.h, batch_norm-inl.h,
pooling-inl.h, dropout-inl.h, lrn-inl.h, leaky_relu-inl.h, embedding-inl.h,
upsampling-inl.h, softmax_activation-inl.h.

TPU-first notes
---------------
* Convolutions lower to ``lax.conv_general_dilated`` — one XLA HLO that the
  TPU backend tiles directly onto the MXU. The reference's im2col+GEMM
  staging, workspace chunking (convolution-inl.h:107-128) and cuDNN variants
  all collapse into this single op; ``num_group`` maps to
  ``feature_group_count``.
* Layout is NCHW at the API surface (reference layout). XLA:TPU internally
  re-lays out to its preferred packing, so no manual NHWC plumbing is needed.
* BatchNorm carries its moving stats as *aux state* threaded functionally
  through the executor (the reference mutates aux NDArrays in place,
  batch_norm-inl.h:93-125).
* Dropout uses the executor-provided PRNG key; the mask is never stored —
  autodiff re-links it between forward and backward residuals.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpSpec, Param, register, shape_assign, same_shape_infer

_DIMNUMS = ("NCHW", "OIHW", "NCHW")


def _BN_STATS_MODE():
    """Training BatchNorm statistics algorithm via MXNET_BN_STATS:
    "auto" (default) = one fused read, flax-parity E[x^2]-mean^2 with
    clamp — fastest, precision contract assumes roughly-normalized
    inputs; "centered" = exact two-pass; "welford" = exact one-read
    variadic reduce (see _bn_train_fwd and doc/performance.md).
    Unknown values raise so a typo cannot silently select the inexact
    default."""
    import os
    mode = os.environ.get("MXNET_BN_STATS", "auto")
    if mode not in ("auto", "centered", "welford", "onepass_unsafe"):
        raise MXNetError(
            "MXNET_BN_STATS=%r: expected auto|centered|welford" % mode)
    return mode


def _use_nhwc():
    """Run convs/pools internally in NHWC (API stays NCHW).

    Measured on the v5e chip: a SINGLE-op jit pays ~38x for NCHW (host
    interface pins the layout; the MXU wants channels minor), while
    inside a whole-model program XLA's layout assignment mostly fixes it
    — explicit NHWC still measures ~3% faster end-to-end on ResNet-50
    (2,354 vs 2,289 img/s) and guarantees the good layout for imperative
    /small-jit use. ``MXNET_CONV_NHWC=0/1`` overrides; default on TPU.
    """
    import os
    flag = os.environ.get("MXNET_CONV_NHWC")
    if flag == "0":
        return False
    if flag == "1":
        return True
    return jax.default_backend() == "tpu"


def _conv_out(h, k, s, p, d):
    eff = d * (k - 1) + 1
    return (h + 2 * p - eff) // s + 1


@register
class FullyConnected(OpSpec):
    """out = data · weightᵀ + bias (``fully_connected-inl.h:53-81``).

    Data with >2 dims is flattened to (N, -1) like the reference; with
    ``flatten=False`` the dot applies position-wise over the trailing
    axis ([..., K] -> [..., num_hidden]), the layout transformer FFNs
    need. The dot is the canonical MXU op; bias-add fuses into it.

    ``precision="highest"`` asks the backend for full-precision
    products (on the TPU a float32 product otherwise rounds its
    operands to bfloat16): for the few small layers whose output
    decides something discrete, such as a router's.
    """

    name = "FullyConnected"
    params = {"num_hidden": Param("int"), "no_bias": Param("bool", False),
              "flatten": Param("bool", True),
              "precision": Param("str", "")}

    def arguments(self, p):
        return ["data", "weight"] if p["no_bias"] else ["data", "weight", "bias"]

    def infer_shape(self, p, in_shapes):
        nh = p["num_hidden"]
        d = in_shapes[0]
        w = in_shapes[1] if len(in_shapes) > 1 else None
        ins = list(in_shapes)
        if d is not None:
            k = d[-1] if not p["flatten"] else int(np.prod(d[1:]))
            ins[1] = shape_assign(w, (nh, k), "FullyConnected weight")
        elif w is not None and None not in w and 0 not in w:
            pass  # cannot reconstruct data shape from weight alone
        if not p["no_bias"]:
            ins[2] = shape_assign(ins[2], (nh,), "FullyConnected bias")
        if d is None:
            out = None
        elif p["flatten"]:
            out = (d[0], nh)
        else:
            out = tuple(d[:-1]) + (nh,)
        return ins, [out], []

    def forward(self, p, ins, aux, is_train, rng):
        prec = p.get("precision") or None
        if p["flatten"]:
            x = ins[0].reshape(ins[0].shape[0], -1)
            out = jnp.dot(x, ins[1].T, precision=prec)
        else:
            out = jnp.einsum("...k,nk->...n", ins[0], ins[1],
                             precision=prec)
        if not p["no_bias"]:
            out = out + ins[2]
        return [out], []


@register
class Convolution(OpSpec):
    """2-D convolution, NCHW (``convolution-inl.h``)."""

    name = "Convolution"
    params = {
        "kernel": Param("shape"),
        "num_filter": Param("int"),
        "stride": Param("shape", (1, 1)),
        "dilate": Param("shape", (1, 1)),
        "pad": Param("shape", (0, 0)),
        "num_group": Param("int", 1),
        "workspace": Param("int", 512),  # accepted for parity; XLA plans memory
        "no_bias": Param("bool", False),
    }

    def arguments(self, p):
        return ["data", "weight"] if p["no_bias"] else ["data", "weight", "bias"]

    def infer_shape(self, p, in_shapes):
        ins = list(in_shapes)
        d = ins[0]
        kh, kw = p["kernel"]
        nf = p["num_filter"]
        if nf % p["num_group"]:
            raise MXNetError("Convolution: num_filter %d not divisible by "
                             "num_group %d" % (nf, p["num_group"]))
        if d is not None:
            if len(d) != 4:
                raise MXNetError("Convolution: data must be 4D NCHW")
            if d[1] % p["num_group"]:
                raise MXNetError("Convolution: channels %d not divisible by "
                                 "num_group %d" % (d[1], p["num_group"]))
            ins[1] = shape_assign(ins[1], (nf, d[1] // p["num_group"], kh, kw),
                                  "Convolution weight")
        if not p["no_bias"]:
            ins[2] = shape_assign(ins[2], (nf,), "Convolution bias")
        if d is None:
            return ins, [None], []
        oh = _conv_out(d[2], kh, p["stride"][0], p["pad"][0], p["dilate"][0])
        ow = _conv_out(d[3], kw, p["stride"][1], p["pad"][1], p["dilate"][1])
        if oh <= 0 or ow <= 0:
            raise MXNetError("Convolution: kernel size exceeds input")
        return ins, [(d[0], nf, oh, ow)], []

    def forward(self, p, ins, aux, is_train, rng):
        ph, pw = p["pad"]
        if _use_nhwc():
            x = jnp.transpose(ins[0], (0, 2, 3, 1))
            w = jnp.transpose(ins[1], (2, 3, 1, 0))  # OIHW -> HWIO
            out = lax.conv_general_dilated(
                x, w,
                window_strides=p["stride"],
                padding=((ph, ph), (pw, pw)),
                rhs_dilation=p["dilate"],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=p["num_group"],
            )
            if not p["no_bias"]:
                out = out + ins[2]
            return [jnp.transpose(out, (0, 3, 1, 2))], []
        out = lax.conv_general_dilated(
            ins[0], ins[1],
            window_strides=p["stride"],
            padding=((ph, ph), (pw, pw)),
            rhs_dilation=p["dilate"],
            dimension_numbers=_DIMNUMS,
            feature_group_count=p["num_group"],
        )
        if not p["no_bias"]:
            out = out + ins[2][None, :, None, None]
        return [out], []


@register
class Deconvolution(OpSpec):
    """Transposed convolution (``deconvolution-inl.h``): the gradient of
    Convolution wrt its input, as a forward op. out = s·(H-1) + k - 2p."""

    name = "Deconvolution"
    params = {
        "kernel": Param("shape"),
        "num_filter": Param("int"),
        "stride": Param("shape", (1, 1)),
        "pad": Param("shape", (0, 0)),
        "num_group": Param("int", 1),
        "workspace": Param("int", 512),
        "no_bias": Param("bool", True),
    }

    def arguments(self, p):
        return ["data", "weight"] if p["no_bias"] else ["data", "weight", "bias"]

    def infer_shape(self, p, in_shapes):
        ins = list(in_shapes)
        d = ins[0]
        kh, kw = p["kernel"]
        if d is not None:
            ins[1] = shape_assign(
                ins[1], (d[1], p["num_filter"] // p["num_group"], kh, kw),
                "Deconvolution weight")
        if not p["no_bias"]:
            ins[2] = shape_assign(ins[2], (p["num_filter"],), "Deconv bias")
        if d is None:
            return ins, [None], []
        oh = p["stride"][0] * (d[2] - 1) + kh - 2 * p["pad"][0]
        ow = p["stride"][1] * (d[3] - 1) + kw - 2 * p["pad"][1]
        return ins, [(d[0], p["num_filter"], oh, ow)], []

    def forward(self, p, ins, aux, is_train, rng):
        kh, kw = p["kernel"]
        sh, sw = p["stride"]
        ph, pw = p["pad"]
        g = p["num_group"]
        # Transposed conv = conv with lhs (input) dilation by the stride and
        # a flipped kernel. Weight is (C_in, nf/g, kh, kw); grouped XLA conv
        # wants rhs I = C_in/g with the g groups laid out along O, so
        # regroup: (g, C_in/g, nf/g, kh, kw) → (C_in/g, g*nf/g, kh, kw).
        w = jnp.flip(ins[1], axis=(-2, -1))
        if g > 1:
            cin, nf_per_g = w.shape[0], w.shape[1]
            w = w.reshape(g, cin // g, nf_per_g, kh, kw) \
                 .transpose(1, 0, 2, 3, 4) \
                 .reshape(cin // g, g * nf_per_g, kh, kw)
        pad2 = ((kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw))
        if _use_nhwc():
            x = jnp.transpose(ins[0], (0, 2, 3, 1))
            w = jnp.transpose(w, (2, 3, 0, 1))  # IOHW -> HWIO
            out = lax.conv_general_dilated(
                x, w, window_strides=(1, 1), padding=pad2,
                lhs_dilation=(sh, sw),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=g,
            )
            if not p["no_bias"]:
                out = out + ins[2]
            return [jnp.transpose(out, (0, 3, 1, 2))], []
        out = lax.conv_general_dilated(
            ins[0], w,
            window_strides=(1, 1),
            padding=pad2,
            lhs_dilation=(sh, sw),
            dimension_numbers=("NCHW", "IOHW", "NCHW"),
            feature_group_count=g,
        )
        if not p["no_bias"]:
            out = out + ins[2][None, :, None, None]
        return [out], []


@register
class Activation(OpSpec):
    """relu/sigmoid/tanh/softrelu (``activation-inl.h`` + mshadow_op.h),
    and the transformer era's silu and (exact, erf) gelu."""

    name = "Activation"
    params = {"act_type": Param("str")}
    _FNS = {
        "relu": lambda x: jnp.maximum(x, 0),
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    }

    def infer_shape(self, p, in_shapes):
        return same_shape_infer(p, in_shapes)

    def forward(self, p, ins, aux, is_train, rng):
        try:
            fn = self._FNS[p["act_type"]]
        except KeyError:
            raise MXNetError("Activation: unknown act_type " + p["act_type"])
        return [fn(ins[0])], []


@register
class LeakyReLU(OpSpec):
    """leaky/prelu/rrelu/elu (``leaky_relu-inl.h``). prelu learns a
    per-channel gamma; rrelu samples slope in [lower, upper) at train time
    and uses the midpoint for inference."""

    name = "LeakyReLU"
    params = {"act_type": Param("str", "leaky"),
              "slope": Param("float", 0.25),
              "lower_bound": Param("float", 0.125),
              "upper_bound": Param("float", 0.334)}

    def arguments(self, p):
        return ["data", "gamma"] if p["act_type"] == "prelu" else ["data"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        ins = list(in_shapes)
        if p["act_type"] == "prelu" and d is not None:
            ins[1] = shape_assign(ins[1], (d[1],), "LeakyReLU gamma")
        return ins, [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x = ins[0]
        t = p["act_type"]
        if t == "leaky":
            return [jnp.where(x > 0, x, p["slope"] * x)], []
        if t == "elu":
            return [jnp.where(x > 0, x, p["slope"] * (jnp.exp(x) - 1))], []
        if t == "prelu":
            g = ins[1].reshape((1, -1) + (1,) * (x.ndim - 2))
            return [jnp.where(x > 0, x, g * x)], []
        if t == "rrelu":
            if is_train:
                slope = jax.random.uniform(
                    rng, x.shape, dtype=x.dtype,
                    minval=p["lower_bound"], maxval=p["upper_bound"])
            else:
                slope = (p["lower_bound"] + p["upper_bound"]) / 2.0
            return [jnp.where(x > 0, x, slope * x)], []
        raise MXNetError("LeakyReLU: unknown act_type " + t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_train(x, gamma, beta, eps):
    return _bn_train_fwd(x, gamma, beta, eps)[0]


def _bn_train_fwd(x, gamma, beta, eps):
    """Training batch-norm with a hand-derived backward.

    Why not plain autodiff: BN is pure HBM traffic (the step profile on
    the v5e puts BatchNorm at ~1/3 of the ResNet-50 train step —
    doc/performance.md), and differentiating through the two-reduction
    stats graph makes XLA materialize extra activation-sized
    intermediates. This form does the minimum the selected stats mode
    needs (see _BN_STATS_MODE: fused one-pass flax-parity default,
    exact "centered"/"welford" escapes) + one folded scale/shift pass;
    backward = one fused reduction pass
    (sum(dy), sum(dy*xhat)) + one elementwise pass, all in the compute
    dtype, recomputing xhat from (x, mean, inv) so no extra activation
    residual is kept beyond x itself (which the surrounding conv's
    backward already holds).
    """
    axes = (0,) + tuple(range(2, x.ndim))
    shape = (1, -1) + (1,) * (x.ndim - 2)
    n = x.size // x.shape[1]
    # accumulate at >= f32 (bf16 in stays bf16 TRAFFIC, f64 parity runs
    # keep full precision)
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)
    mode = _BN_STATS_MODE()
    if mode == "centered":
        # TWO full reads: mean, then E[(x-mean)^2] — exact
        mean = jnp.mean(xf, axis=axes)
        var = jnp.mean(jnp.square(xf - mean.reshape(shape)), axis=axes)
    elif mode == "welford":
        # exact ONE-read variance via a variadic reduce with the
        # parallel Welford combiner (Chan et al. pairwise merge);
        # measured +10 ms vs "auto" on the ResNet-50 step (the custom
        # computation misses XLA's fast reduction emitter) but keeps
        # full precision at one read where "centered" takes two
        def _comb(a, b):
            mu1, m1, n1 = a
            mu2, m2, n2 = b
            nt = n1 + n2
            w = jnp.where(nt > 0, n2 / jnp.maximum(nt, 1.0), 0.0)
            d = mu2 - mu1
            return (mu1 + d * w, m1 + m2 + d * d * n1 * w, nt)
        zero = jnp.zeros((), xf.dtype)
        mean, m2, cnt = lax.reduce(
            (xf, jnp.zeros_like(xf), jnp.ones_like(xf)),
            (zero, zero, zero), _comb, axes)
        var = m2 / cnt
    else:
        # "auto" (default): ONE full read. sum(x) and sum(x^2) are
        # sibling reductions over the same input, which XLA fuses into
        # a single pass (measured -6.4 ms on the 106.4 ms ResNet-50
        # b256 train step vs the two-pass form; full A/B table in
        # doc/performance.md). The combine E[x^2]-mean^2 loses
        # ~mean^2/var relative precision to cancellation, which is
        # catastrophic for channels with |mean|/sigma >~ 2000 (mean
        # ~3e4, std 1 -> var computes EXACTLY 0) — this is the SAME
        # algorithm and contract as flax/haiku BatchNorm on TPU
        # (flax.linen.normalization computes mean and mean-of-squares
        # exactly like this), and it is benign for conv outputs, whose
        # channel means sit within a few sigma of 0. Guarded variants
        # were all measured SLOWER THAN THE SAVING on this backend
        # (lax.cond +25 ms — XLA select-izes it; any subsampled or
        # shifted second read +15..+44 ms — a third consumer of the
        # activation materializes an f32 copy; Welford variadic reduce
        # +10 ms — misses the fast reduction emitter): the honest
        # options are fast-with-contract or exact-two-pass, selected by
        # MXNET_BN_STATS ("centered" = exact two-pass, "welford" =
        # exact one-read variadic reduce).
        mean = jnp.mean(xf, axis=axes)
        var = jnp.maximum(
            jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean), 0.0)
    inv = lax.rsqrt(var + eps)
    # fold per-channel scalars so the big pass is one multiply-add
    scale = (gamma.astype(acc) * inv).astype(x.dtype)
    shift = (beta.astype(acc)
             - mean * gamma.astype(acc) * inv).astype(x.dtype)
    out = x * scale.reshape(shape) + shift.reshape(shape)
    return ((out, mean.astype(x.dtype), var.astype(x.dtype)),
            (x, gamma, beta, mean, inv, n))


def _bn_train_bwd(eps, res, gs):
    x, gamma, beta, mean, inv, n = res
    g_out, g_mean, g_var = gs
    axes = (0,) + tuple(range(2, x.ndim))
    shape = (1, -1) + (1,) * (x.ndim - 2)
    acc = jnp.promote_types(x.dtype, jnp.float32)
    gy = g_out.astype(acc)
    xc = x.astype(acc) - mean.astype(acc).reshape(shape)
    xhat = xc * inv.reshape(shape)
    # fused sibling reductions over (gy, xhat)
    sum_gy = jnp.sum(gy, axis=axes)
    sum_gy_xhat = jnp.sum(gy * xhat, axis=axes)
    dgamma = sum_gy_xhat
    dbeta = sum_gy
    gf = gamma.astype(acc)
    dx = (gf * inv).reshape(shape) * (
        gy - (sum_gy / n).reshape(shape)
        - xhat * (sum_gy_xhat / n).reshape(shape))
    # exact contributions from the (rarely differentiated) mean/var
    # outputs — per-channel scalars, folded into the same pass
    dx = dx + (g_mean.astype(acc) / n).reshape(shape)
    dx = dx + xc * (2.0 * g_var.astype(acc) / n).reshape(shape)
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype))


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


@register
class BatchNorm(OpSpec):
    """Batch normalization (``batch_norm-inl.h``).

    Train: normalize by batch stats; update aux moving_mean/var with
    ``momentum`` (reference default 0.9, eps 1e-3). Eval: normalize by the
    moving stats. ``fix_gamma`` freezes the scale at 1 (and zeroes its
    gradient, which stop_gradient reproduces).
    """

    name = "BatchNorm"
    params = {"eps": Param("float", 1e-3),
              "momentum": Param("float", 0.9),
              "fix_gamma": Param("bool", True)}

    def arguments(self, p):
        return ["data", "gamma", "beta"]

    def aux_states(self, p):
        return ["moving_mean", "moving_var"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        ins = list(in_shapes)
        if d is None:
            return ins, [None], [None, None]
        c = (d[1],)
        ins[1] = shape_assign(ins[1], c, "BatchNorm gamma")
        ins[2] = shape_assign(ins[2], c, "BatchNorm beta")
        return ins, [d], [c, c]

    def forward(self, p, ins, aux, is_train, rng):
        x, gamma, beta = ins
        mmean, mvar = aux
        shape = (1, -1) + (1,) * (x.ndim - 2)
        if p["fix_gamma"]:
            gamma = jnp.ones_like(gamma)
        if is_train:
            out, mean, var = _bn_train(x, gamma, beta, float(p["eps"]))
            m = p["momentum"]
            new_mmean = m * mmean + (1 - m) * mean
            new_mvar = m * mvar + (1 - m) * var
            return [out], [new_mmean, new_mvar]
        inv = lax.rsqrt(mvar + p["eps"])
        out = (x - mmean.reshape(shape)) * inv.reshape(shape)
        out = out * gamma.reshape(shape) + beta.reshape(shape)
        return [out], [mmean, mvar]


@register
class Pooling(OpSpec):
    """max/avg/sum pooling (``pooling-inl.h``). Output size uses ceil
    division capped so the last window starts inside the padded input
    (pooling-inl.h:177-183); avg divides by the full kernel size like
    mshadow's pool<Reducer>."""

    name = "Pooling"
    params = {"kernel": Param("shape"),
              "pool_type": Param("str", "max"),
              "stride": Param("shape", (1, 1)),
              "pad": Param("shape", (0, 0)),
              # pool over the whole spatial extent regardless of kernel
              # (later-MXNet extension; lets ImageNet heads stay
              # shape-agnostic under ceil-mode stage arithmetic)
              "global_pool": Param("bool", False)}

    @staticmethod
    def _osize(h, k, s, p):
        o = (h + 2 * p - k + s - 1) // s + 1
        # cap: last window must start within input+padding
        if (o - 1) * s >= h + p:
            o -= 1
        return o

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return [None], [None], []
        if p["global_pool"]:
            return [d], [(d[0], d[1], 1, 1)], []
        kh, kw = p["kernel"]
        if kh > d[2] + 2 * p["pad"][0] or kw > d[3] + 2 * p["pad"][1]:
            raise MXNetError("Pooling: kernel size exceeds input")
        oh = self._osize(d[2], kh, p["stride"][0], p["pad"][0])
        ow = self._osize(d[3], kw, p["stride"][1], p["pad"][1])
        return [d], [(d[0], d[1], oh, ow)], []

    def forward(self, p, ins, aux, is_train, rng):
        x = ins[0]
        if p["global_pool"]:
            kh, kw = x.shape[2], x.shape[3]
            sh, sw, ph, pw = 1, 1, 0, 0
        else:
            kh, kw = p["kernel"]
            sh, sw = p["stride"]
            ph, pw = p["pad"]
        oh = self._osize(x.shape[2], kh, sh, ph)
        ow = self._osize(x.shape[3], kw, sw, pw)
        # right/bottom padding extended so ceil-mode windows fit
        eh = max((oh - 1) * sh + kh - x.shape[2] - ph, ph)
        ew = max((ow - 1) * sw + kw - x.shape[3] - pw, pw)
        nhwc = _use_nhwc()
        if nhwc:  # channels-minor windows (see _use_nhwc)
            x = jnp.transpose(x, (0, 2, 3, 1))
            dims = (1, kh, kw, 1)
            strides = (1, sh, sw, 1)
            pads = ((0, 0), (ph, eh), (pw, ew), (0, 0))
        else:
            dims = (1, 1, kh, kw)
            strides = (1, 1, sh, sw)
            pads = ((0, 0), (0, 0), (ph, eh), (pw, ew))
        # NB: init values must be concrete (np) scalars — a traced jnp scalar
        # stops JAX pattern-matching the monoid, losing the autodiff rule.
        if p["pool_type"] == "max":
            # NB a closed-form mshadow-style backward (dx = sum over
            # offsets of (x==out_up)*g_up) was built and REJECTED in
            # round 4: the python-loop form blew HBM (9 simultaneous
            # x-sized slices, 17.8G) and the lax.scan form ran the
            # ResNet-50 step 2.3x SLOWER (lane-misaligned dynamic
            # slices + broken fusion). XLA's SelectAndScatter autodiff
            # rule stays (doc/performance.md round-4 notes).
            init = -np.inf if jnp.issubdtype(x.dtype, jnp.floating) \
                else np.iinfo(np.dtype(x.dtype)).min
            out = lax.reduce_window(x, np.array(init, x.dtype), lax.max,
                                    dims, strides, pads)
        elif p["pool_type"] in ("avg", "sum"):
            out = lax.reduce_window(x, np.array(0, x.dtype), lax.add,
                                    dims, strides, pads)
            if p["pool_type"] == "avg":
                out = out / (kh * kw)
        else:
            raise MXNetError("Pooling: unknown pool_type " + p["pool_type"])
        if nhwc:
            out = jnp.transpose(out, (0, 3, 1, 2))
        return [out], []


@register
class Dropout(OpSpec):
    """Inverted dropout (``dropout-inl.h``): train-time mask scaled by
    1/(1-p); identity at inference. The reference keeps the mask as a
    hidden second output — here it lives in the vjp residuals instead."""

    name = "Dropout"
    params = {"p": Param("float", 0.5)}

    def infer_shape(self, p, in_shapes):
        return same_shape_infer(p, in_shapes)

    def forward(self, p, ins, aux, is_train, rng):
        x = ins[0]
        rate = p["p"]
        if not is_train or rate <= 0.0:
            return [x], []
        keep = 1.0 - rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return [jnp.where(mask, x / keep, 0).astype(x.dtype)], []


@register
class LRN(OpSpec):
    """Local response normalization across channels (``lrn-inl.h``),
    AlexNet-style: out = x / (knorm + alpha/n * sum(x²))^beta."""

    name = "LRN"
    params = {"alpha": Param("float", 1e-4),
              "beta": Param("float", 0.75),
              "knorm": Param("float", 2.0),
              "nsize": Param("int")}

    def infer_shape(self, p, in_shapes):
        return same_shape_infer(p, in_shapes)

    def forward(self, p, ins, aux, is_train, rng):
        x = ins[0]
        n = p["nsize"]
        sq = jnp.square(x)
        # windowed sum over channel axis, window n centered, same size out
        pad = ((0, 0), (n // 2, n - 1 - n // 2), (0, 0), (0, 0))
        ssum = lax.reduce_window(sq, np.array(0, x.dtype), lax.add,
                                 (1, n, 1, 1), (1, 1, 1, 1), pad)
        scale = p["knorm"] + (p["alpha"] / n) * ssum
        return [x * jnp.power(scale, -p["beta"])], []


@register
class Embedding(OpSpec):
    """Index lookup table (``embedding-inl.h``): data (N,) of indices →
    (N, output_dim). One-hot matmul form keeps it on the MXU and makes the
    scatter-add gradient an MXU op too."""

    name = "Embedding"
    params = {"input_dim": Param("int"), "output_dim": Param("int")}

    def arguments(self, p):
        return ["data", "weight"]

    def integer_arguments(self, p):
        return ("data",)  # token ids — bf16 casts would corrupt >256

    def infer_shape(self, p, in_shapes):
        ins = list(in_shapes)
        ins[1] = shape_assign(ins[1], (p["input_dim"], p["output_dim"]),
                              "Embedding weight")
        d = ins[0]
        if d is None:
            return ins, [None], []
        return ins, [tuple(d) + (p["output_dim"],)], []

    def forward(self, p, ins, aux, is_train, rng):
        idx = lax.stop_gradient(ins[0]).astype(jnp.int32)
        return [jnp.take(ins[1], idx, axis=0)], []


@register
class UpSampling(OpSpec):
    """Nearest or bilinear upsampling (``upsampling-inl.h``). nearest takes
    N inputs (concat after scaling); bilinear is a fixed/learned deconv."""

    name = "UpSampling"
    params = {"scale": Param("int"),
              "num_args": Param("int", 1),
              "sample_type": Param("str", "nearest"),
              "num_filter": Param("int", 0),
              "multi_input_mode": Param("str", "concat"),
              "workspace": Param("int", 512)}

    def arguments(self, p):
        if p["sample_type"] == "bilinear":
            return ["data", "weight"]
        return ["arg%d" % i for i in range(p["num_args"])] \
            if p["num_args"] > 1 else ["data"]

    def infer_shape(self, p, in_shapes):
        s = p["scale"]
        ins = list(in_shapes)
        d = ins[0]
        if p["sample_type"] == "bilinear":
            k = 2 * s - s % 2
            if d is not None:
                ins[1] = shape_assign(ins[1], (d[1], 1, k, k), "UpSampling weight")
        if d is None:
            return ins, [None], []
        c = d[1]
        if p["sample_type"] == "nearest" and p["num_args"] > 1 \
                and p["multi_input_mode"] == "concat":
            if any(sh is None for sh in in_shapes):
                return ins, [None], []
            c = sum(sh[1] for sh in in_shapes)
        return ins, [(d[0], c, d[2] * s, d[3] * s)], []

    def forward(self, p, ins, aux, is_train, rng):
        s = p["scale"]
        if p["sample_type"] == "bilinear":
            x, w = ins
            k = 2 * s - s % 2
            pad = (s + 1) // 2 - 1 + (k - 1) // 2  # deconv pad for scale
            # depthwise transposed conv: weight (C,1,k,k) is already OIHW
            # for feature_group_count=C (I = C/C = 1)
            out = lax.conv_general_dilated(
                x, jnp.flip(w, axis=(-2, -1)),
                window_strides=(1, 1),
                padding=((k - 1 - pad,) * 2, (k - 1 - pad,) * 2),
                lhs_dilation=(s, s),
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=x.shape[1],
            )
            return [out], []
        # each input is upsampled to the first input's target size
        # (reference upsampling-inl.h: per-input scale = out_H / in_H)
        th, tw = ins[0].shape[2] * s, ins[0].shape[3] * s
        outs = []
        for x in ins:
            fh, fw = th // x.shape[2], tw // x.shape[3]
            outs.append(jnp.repeat(jnp.repeat(x, fh, axis=2), fw, axis=3))
        if len(outs) == 1:
            return outs, []
        if p["multi_input_mode"] == "sum":
            return [sum(outs[1:], outs[0])], []
        return [jnp.concatenate(outs, axis=1)], []


@register
class SoftmaxActivation(OpSpec):
    """Softmax as a differentiable layer (``softmax_activation-inl.h``);
    mode=instance (over trailing dim of 2D) or channel (over axis 1)."""

    name = "SoftmaxActivation"
    params = {"mode": Param("str", "instance")}

    def infer_shape(self, p, in_shapes):
        return same_shape_infer(p, in_shapes)

    def forward(self, p, ins, aux, is_train, rng):
        axis = 1 if p["mode"] == "channel" else -1
        return [jax.nn.softmax(ins[0], axis=axis)], []
