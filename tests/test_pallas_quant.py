"""Quantized-matmul Pallas kernels (PR 17), interpreter mode on CPU —
the same code runs compiled on TPU (backend-consistency oracle, as in
test_pallas.py).

The contract: ``pk.quant_matmul`` agrees with
``serving.quant.scale_fused_matmul``'s host-level ``fori_loop`` to f32
rounding (``_TOL`` below), and is BITWISE invariant to its own block
size — the grid walks output-channel blocks only and contracts the
full E axis per step, a partition of independent dots. It is not
bitwise against the fori form: on int8 both sides run the same
contraction, but XLA chooses a dot's accumulation order by the
fusion it sits in (a jitted ``fori_loop`` body vs. the kernel's own
jaxpr), which on jax 0.9.0 moves ~70% of the elements by an ulp or
two (max abs 1.9e-6 at these shapes); on int4 the kernel sums an
even-element and an odd-element product — the chip's compiler cannot
afford the lane re-interleave — where the fori form runs one dot.
What ``matmul_impl="pallas"`` is held to end to end is the serving
engine's token-level gauntlet (tests/test_serving_quant.py); this
file pins the kernel side, zero engine compiles.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.serving.quant import (pack_int4,
                                     quantize_tensor, resolve_chunk,
                                     scale_fused_matmul, unpack_int4)


def _qt(rng, f, e, bits=8, group=None):
    w = rng.randn(f, e).astype(np.float32)
    return quantize_tensor(jnp.asarray(w), bits=bits, group=group)


# The fori reference is compared UNDER JIT, like every serving program
# that runs it: eager XLA materializes the int8->f32 cast before the
# dot while jit folds the convert into the dot (a different gemv
# accumulation at M=1) — one more instance of the fusion-context
# rounding the tolerance below allows for.
_fori = jax.jit(scale_fused_matmul)


# -- quant_matmul vs the fori fallback: f32 rounding --------------------
# A few ulp of O(10) sums over E <= 32 f32 products of O(1) inputs:
# 1e-5 is ~5x the largest difference jax 0.9.0 shows here (1.9e-6) and
# ~1e4 x below one quantization step of these weights.
_TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,e,f", [
    (3, 16, 512),    # two 256-row blocks
    (1, 32, 8),      # single block, single row
    (5, 24, 72),     # F has no lane-legal divisor -> whole
    (2, 16, 256),    # exactly one max-size block; the fori walks 2x128
    (4, 8, 1152),    # block 128, 9 grid steps
])
def test_quant_matmul_int8_vs_fori(m, e, f):
    rng = np.random.RandomState(0)
    qt = _qt(rng, f, e)
    x = jnp.asarray(rng.randn(m, e).astype(np.float32))
    got = pk.quant_matmul(x, qt.q, qt.scale, bits=8)
    want = _fori(x, qt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


def test_quant_matmul_block_partition_invariance():
    """Any legal block_f gives the bitwise-same product: blocking
    partitions output channels, it never splits the contraction."""
    rng = np.random.RandomState(1)
    qt = _qt(rng, 512, 16)
    x = jnp.asarray(rng.randn(3, 16).astype(np.float32))
    outs = [np.asarray(pk.quant_matmul(x, qt.q, qt.scale, bits=8,
                                       block_f=bf))
            for bf in (512, 256, 128)]
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


@pytest.mark.parametrize("e,group", [
    (16, 16),    # one group spanning the whole axis
    (16, 2),     # minimal group width
    (24, 8),     # several groups, E not a power of two
])
def test_quant_matmul_int4_vs_fori(e, group):
    rng = np.random.RandomState(2)
    f = 256                                # two 128-row blocks
    qt = _qt(rng, f, e, bits=4, group=group)
    assert qt.bits == 4 and qt.group == group
    assert qt.q.shape == (f, e // 2) and qt.q.dtype == jnp.uint8
    assert qt.scale.shape == (f, e // group)
    x = jnp.asarray(rng.randn(3, e).astype(np.float32))
    got = pk.quant_matmul(x, qt.q, qt.scale, bits=4, group=group,
                          block_f=128)
    want = _fori(x, qt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


def test_int4_pack_unpack_bitwise():
    """pack/unpack round-trips every 4-bit value, and the kernel's
    in-VMEM unpackers are the bitwise mirror of the host one: the
    two nibble planes are the even and the odd elements."""
    vals = np.tile(np.arange(-8, 8, dtype=np.int8), 4).reshape(4, 16)
    packed = pack_int4(jnp.asarray(vals))
    assert packed.shape == (4, 8) and packed.dtype == jnp.uint8
    back = unpack_int4(packed)
    np.testing.assert_array_equal(np.asarray(back), vals)
    lo, hi = pk._unpack4_halves(packed)
    np.testing.assert_array_equal(np.asarray(lo), vals[:, 0::2])
    np.testing.assert_array_equal(np.asarray(hi), vals[:, 1::2])


def test_quant_matmul_all_zero_rows():
    """All-zero output rows quantize to scale 1 / values 0 and come
    out exactly zero — no NaNs from the amax/127 guard."""
    rng = np.random.RandomState(3)
    w = rng.randn(16, 8).astype(np.float32)
    w[3] = 0.0
    w[10] = 0.0
    x = jnp.asarray(rng.randn(2, 8).astype(np.float32))
    for bits, group in ((8, None), (4, 4)):
        qt = quantize_tensor(jnp.asarray(w), bits=bits, group=group)
        out = np.asarray(pk.quant_matmul(x, qt.q, qt.scale, bits=bits,
                                         group=group))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[:, 3], 0.0)
        np.testing.assert_array_equal(out[:, 10], 0.0)


def test_quant_matmul_validation():
    rng = np.random.RandomState(4)
    qt = _qt(rng, 12, 8)
    x = jnp.asarray(rng.randn(2, 8).astype(np.float32))
    with pytest.raises(ValueError, match="block_f"):
        pk.quant_matmul(x, qt.q, qt.scale, bits=8, block_f=5)
    # a divisor the chip's lane axis cannot hold is refused here too,
    # not quietly replaced: the interpreter runs the chip's partition
    q256 = _qt(rng, 256, 8)
    with pytest.raises(ValueError, match="multiple of 128"):
        pk.quant_matmul(x, q256.q, q256.scale, bits=8, block_f=64)
    q4 = _qt(rng, 12, 8, bits=4, group=4)
    with pytest.raises(ValueError, match="group"):
        pk.quant_matmul(x, q4.q, q4.scale, bits=4, group=3)
    with pytest.raises(ValueError, match="group"):
        pk.quant_matmul(x, q4.q, q4.scale, bits=4)


def test_quant_chunk_env_knob():
    """MXNET_QUANT_CHUNK: an explicit lane-legal divisor is honored by
    BOTH impls (they stage identically), >= F means dequantize-whole;
    a non-divisor, a divisor that is no multiple of 128 or a
    non-integer is refused loudly instead of silently falling back to
    the auto table."""
    rng = np.random.RandomState(5)
    qt = _qt(rng, 512, 16)
    x = jnp.asarray(rng.randn(3, 16).astype(np.float32))
    base = np.asarray(_fori(x, qt))
    old = os.environ.get("MXNET_QUANT_CHUNK")
    try:
        os.environ["MXNET_QUANT_CHUNK"] = "128"
        assert resolve_chunk(512) == 128
        # fresh jit wrapper: the module-level _fori would replay its
        # cached trace and never re-read the env knob
        np.testing.assert_array_equal(
            np.asarray(jax.jit(scale_fused_matmul)(x, qt)), base)
        np.testing.assert_allclose(
            np.asarray(pk.quant_matmul(x, qt.q, qt.scale, bits=8,
                                       block_f=resolve_chunk(512))),
            base, **_TOL)
        os.environ["MXNET_QUANT_CHUNK"] = "1024"
        assert resolve_chunk(512) is None     # whole-weight dequant
        os.environ["MXNET_QUANT_CHUNK"] = "0"
        assert resolve_chunk(512) == 256      # auto table
        assert resolve_chunk(3072) == 256 and resolve_chunk(1024) == 128
        assert resolve_chunk(48) is None      # no lane-legal divisor
        for bad in ("7", "64", "lots"):       # 64 divides, no lane block
            os.environ["MXNET_QUANT_CHUNK"] = bad
            with pytest.raises(MXNetError, match="MXNET_QUANT_CHUNK"):
                resolve_chunk(512)
    finally:
        if old is None:
            del os.environ["MXNET_QUANT_CHUNK"]
        else:
            os.environ["MXNET_QUANT_CHUNK"] = old
