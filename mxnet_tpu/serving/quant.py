"""Weight-only int8 quantization for the serving engine
(doc/serving.md "Quantized weights").

Decode is memory-bound, and at serving batch sizes the WEIGHT stream —
not the KV stream — dominates bytes per token: every matmul reads its
full weight matrix once per fused step however many slots share it.
Storing those weights int8 with per-output-channel f32 scales cuts the
stream to 1 byte/elem (the int8-KV lesson of doc/serving.md "The
decode read", applied to the other half of the traffic).

Scheme — the same symmetric amax/127 discipline the int8 KV cache uses
(``parallel/decode.py`` ``_quantize_rows``), one scale per OUTPUT
channel:

* every quantizable weight in the LM contracts over its LAST axis
  (``qkv_weight``/``out_weight`` ``[F, E]``, FullyConnected
  ``[out, in]``, Embedding ``[vocab, E]`` rows, MoE expert stacks
  ``[X, H, E]`` / ``[X, E, H]``), so "per output channel" is uniformly
  "per all-but-last-axis row": ``scale = amax(|w|, axis=-1) / 127``,
  ``q = round(w / scale)``. One outlier row cannot poison its
  neighbours, and the scale tensor is D-fold smaller than the weight.
* LayerNorm gains, biases, and positional-embedding tables stay float
  — they are tiny, and their consumers run the generic op forwards.

Dequantization happens ON THE FLY inside the traced programs, never as
a materialized float copy of the weight (the PR 11 int8-KV lesson: the
dense int8 cache path used to dequantize the whole buffer every step).
:func:`scale_fused_matmul` applies the per-output-channel scale AFTER
the dot — ``(x @ q^T) * scale`` equals ``x @ (q * scale)^T`` exactly —
and walks the weight in output-channel CHUNKS inside one
``lax.fori_loop``, so the float staging is one chunk, not one weight:
the compiled program reads the stored int8 stream plus a bounded
scratch, which is also what keeps the XLA cost model's
``bytes_accessed`` for the decode program at the quantized width
(doc/serving.md "Measuring it"). Chunking over output channels is a
partition of independent dot products — NOT a reassociation — so the
chunked product is bitwise identical to the unchunked one, which is
what makes tp>1 quantized engines byte-identical to tp=1 quantized.

Wiring: ``Decoder(weight_dtype="int8")`` quantizes at construction
(offline generate/beam run quantized too);
``InferenceEngine(weight_dtype="int8")`` quantizes the ENGINE's own
parameter copy, leaving the decoder float so one set of weights can
serve a quantized engine next to its fp oracle (the identity tests
do). ``MXNET_SERVING_WEIGHT_DTYPE`` sets the default for both.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError

__all__ = ["QuantizedTensor", "quantize_tensor", "dequantize",
           "quantized_weight_names", "quantize_params",
           "scale_fused_matmul", "pack_int4", "unpack_int4",
           "resolve_chunk", "resolve_group"]

# op name -> input indices that are quantizable matmul weights (the
# consumers Decoder._run / _cached_mha intercept); every OTHER consumer
# position vetoes quantization of its variable, so a name is quantized
# only when every consumer dequantizes it on the fly
_QUANT_ARGS = {
    "FullyConnected": (1,),
    "Embedding": (1,),
    "MultiHeadAttention": (1, 3),          # qkv_weight, out_weight
    "MoEFFN": (1, 2, 4),                   # gate, expert_w1, expert_w2
}


class QuantizedTensor:
    """A quantized weight with f32 scales, in one of two layouts.

    ``bits=8`` (the PR 15 scheme): ``q`` is int8 in the original
    weight's shape, ``scale`` is f32 of shape ``q.shape[:-1]`` (one per
    all-but-last-axis row — the output channel under the LM's uniform
    ``[out..., contract]`` weight layouts).

    ``bits=4`` (per-group, ISSUE 17): ``q`` is uint8 holding TWO
    4-bit values per byte packed along the contraction (last) axis —
    shape ``[..., E//2]`` for a float weight ``[..., E]`` — and
    ``scale`` is f32 of shape ``[..., E//group]``: one scale per
    ``group`` consecutive contraction elements of each output row.
    Group scales sit on the CONTRACTION axis, so (unlike the per-row
    int8 scale) they cannot be folded into the product after the dot —
    consumers dequantize the weight block (unpack + scale) before
    contracting, which is exactly what the Pallas ``quant_matmul``
    kernel does per VMEM tile.

    ``dtype``: the dequantization target (the dtype the float weight
    had — ``compute_dtype`` under a casting decoder).

    Registered as a jax pytree, so parameter dicts containing
    quantized entries flow through ``jit`` / ``device_put`` /
    ``shard_map`` untouched; the consuming ops dispatch on
    ``isinstance`` at trace time.
    """

    __slots__ = ("q", "scale", "dtype", "bits", "group")

    def __init__(self, q, scale, dtype, bits=8, group=None):
        self.q = q
        self.scale = scale
        self.dtype = dtype
        self.bits = bits
        self.group = group

    @property
    def shape(self):
        if self.bits == 4:
            return self.q.shape[:-1] + (2 * self.q.shape[-1],)
        return self.q.shape

    @property
    def nbytes(self):
        return self.q.nbytes + self.scale.nbytes

    def __repr__(self):
        return ("QuantizedTensor(shape=%r, dtype=%r, bits=%d%s)"
                % (tuple(self.shape), self.dtype, self.bits,
                   "" if self.group is None
                   else ", group=%d" % self.group))


jax.tree_util.register_pytree_node(
    QuantizedTensor,
    lambda t: ((t.q, t.scale), (t.dtype, t.bits, t.group)),
    lambda aux, ch: QuantizedTensor(ch[0], ch[1], *aux))


def pack_int4(q):
    """Pack an int array of 4-bit values (range [-8, 7]) pairwise
    along the last axis into uint8: byte ``i`` holds element ``2i`` in
    its low nibble and ``2i+1`` in its high nibble. The last axis must
    be even. Exact inverse of :func:`unpack_int4` (bitwise)."""
    q = jnp.asarray(q)
    lo = (q[..., 0::2] & 0xF).astype(jnp.uint8)
    hi = (q[..., 1::2] & 0xF).astype(jnp.uint8)
    return lo | (hi << 4)


def unpack_int4(u, dtype=jnp.int8):
    """Unpack :func:`pack_int4` bytes back to signed 4-bit values
    ``[..., 2*E2]`` (sign-extended two's complement nibbles)."""
    u = jnp.asarray(u)
    lo = (u & 0xF).astype(jnp.int32)
    hi = ((u >> 4) & 0xF).astype(jnp.int32)
    both = jnp.stack([lo, hi], axis=-1).reshape(u.shape[:-1]
                                                + (2 * u.shape[-1],))
    return (both - 16 * (both >= 8)).astype(dtype)


def resolve_group(n, group=None):
    """The per-group scale width for a contraction axis of size ``n``
    under int4 quantization. ``group=None`` reads ``MXNET_QUANT_GROUP``
    (unset = auto). Auto picks the largest of (128, 64, 32, 16, 8, 4,
    2) dividing ``n``; an explicit group must be an even divisor of
    ``n`` or the whole axis is refused loudly — silent shrinking would
    quietly change the recorded bytes ratio."""
    if group is None:
        env = os.environ.get("MXNET_QUANT_GROUP", "").strip()
        group = int(env) if env else None
    if group is None:
        for g in (128, 64, 32, 16, 8, 4, 2):
            if n % g == 0:
                return g
        raise MXNetError(
            "int4 quantization needs an even contraction axis to pack "
            "nibble pairs, got axis size %d" % n)
    group = int(group)
    if group <= 0 or group % 2 or n % group:
        raise MXNetError(
            "MXNET_QUANT_GROUP=%d must be a positive even divisor of "
            "the contraction axis (%d here); pick a divisor or unset "
            "it for the auto choice" % (group, n))
    return group


def quantize_tensor(w, dtype=None, bits=8, group=None):
    """Quantize one float weight to :class:`QuantizedTensor`.

    ``bits=8``: symmetric per-output-channel ``amax/127`` (all-zero
    rows get scale 1 so dequantization is exact zero). ``bits=4``:
    symmetric per-group ``amax/7`` over ``group`` consecutive
    contraction elements (see :func:`resolve_group`), values packed
    two per byte. ``dtype`` is the dequant target (default: ``w``'s
    own dtype)."""
    w = jnp.asarray(w)
    if w.ndim < 2:
        raise MXNetError(
            "quantize_tensor: per-output-channel quantization needs a "
            "rank >= 2 weight, got shape %r" % (tuple(w.shape),))
    if dtype is None:
        dtype = str(w.dtype)
    dtype = str(jnp.dtype(dtype))
    wf = w.astype(jnp.float32)
    if bits == 8:
        s = jnp.max(jnp.abs(wf), axis=-1) / 127.0
        s = jnp.where(s > 0, s, 1.0).astype(jnp.float32)
        q = jnp.round(wf / s[..., None]).astype(jnp.int8)
        return QuantizedTensor(q, s, dtype)
    if bits != 4:
        raise MXNetError("quantize_tensor: bits must be 8 or 4, got %r"
                         % (bits,))
    e = w.shape[-1]
    g = resolve_group(e, group)
    wg = wf.reshape(wf.shape[:-1] + (e // g, g))
    s = jnp.max(jnp.abs(wg), axis=-1) / 7.0
    s = jnp.where(s > 0, s, 1.0).astype(jnp.float32)
    q4 = jnp.round(wg / s[..., None]).astype(jnp.int32)
    q4 = q4.reshape(wf.shape)
    return QuantizedTensor(pack_int4(q4), s, dtype, bits=4, group=g)


def _group_scales(qt, scale_slice=None):
    """Expand a per-group scale block to per-element width along the
    contraction axis (``[..., E//g] -> [..., E]``)."""
    s = qt.scale if scale_slice is None else scale_slice
    return jnp.repeat(s, qt.group, axis=-1)


def dequantize(qt):
    """The float weight a :class:`QuantizedTensor` stands for —
    testing/debugging only: the serving programs never materialize
    this (see :func:`scale_fused_matmul`)."""
    if qt.bits == 4:
        v = unpack_int4(qt.q, dtype=jnp.float32)
        return (v * _group_scales(qt)).astype(qt.dtype)
    return (qt.q.astype(jnp.float32)
            * qt.scale[..., None]).astype(qt.dtype)


def quantized_weight_names(topo):
    """Which parameter names of a Decoder's topological node walk are
    safely quantizable: variables consumed ONLY at the matmul-weight
    positions of the intercepted ops (attention QKV/out projections,
    FullyConnected weights — the MLP and the unembedding head —
    Embedding tables, MoE gate/expert stacks). A name any other
    consumer touches (data, biases, LayerNorm gains, positional
    tables, or an op the quantized forwards do not cover) is left
    float."""
    want, veto = set(), set()
    for n in topo:
        if n.is_var:
            continue
        idxs = _QUANT_ARGS.get(n.spec.name, ())
        for j, (inp, _) in enumerate(n.inputs):
            if not inp.is_var:
                continue
            (want if j in idxs else veto).add(inp.name)
    return want - veto


def quantize_params(params, names, bits=8, group=None, row_quant=()):
    """Quantize ``names`` of a parameter dict (each entry keeps its
    own dtype as the dequant target); everything else passes through
    by reference. ``bits``/``group`` select the scheme; names in
    ``row_quant`` (Embedding tables, whose consumer gathers whole
    rows host-side) stay per-row int8 even under ``bits=4`` — packed
    nibbles cannot be row-gathered cheaply and the tables are a small
    slice of the stream."""
    def one(k, v):
        if k not in names:
            return v
        b = 8 if k in row_quant else bits
        return quantize_tensor(v, dtype=str(jnp.asarray(v).dtype),
                               bits=b, group=group)
    return {k: one(k, v) for k, v in params.items()}


# Chunk heights are lane-legal: the Pallas kernel's output block carries
# the chunk on its lane axis, where the TPU lowering takes a multiple of
# 128 or the whole axis and nothing else. The fori walk keeps to the
# same heights, so both impls run ONE partition on both backends (what
# the interpreter tests is what the chip runs).
_LANE = 128


def _block_rows(f):
    """Default output-channel chunk height for the fused-dequant loop:
    the larger of (256, 128) dividing ``f`` into at least 8 chunks —
    the float staging (convert + dot read of ONE chunk) must be a
    small fraction of the int8 stream for the loop to pay, in the
    cost model and in scratch bytes alike — falling back to >= 2
    chunks for small weights, else None (weights with no such divisor
    dequantize whole: same math)."""
    for least in (8, 2):
        for r in (2 * _LANE, _LANE):
            if f % r == 0 and f // r >= least:
                return r
    return None


def resolve_chunk(f):
    """Output-channel chunk for a weight with ``f`` output rows.
    ``MXNET_QUANT_CHUNK`` overrides the :func:`_block_rows` divisor
    table explicitly; a value that does not divide ``f`` or is not a
    multiple of 128 (see ``_LANE``) is refused with a loud
    ``MXNetError`` instead of silently falling back (the silent pick
    made the staging footprint — and the cost model's read of it —
    depend on a hidden table). ``0``/unset = the auto pick. A chunk
    >= ``f`` means "dequantize whole" (returned as None, like the
    auto path's small-weight fallback)."""
    env = os.environ.get("MXNET_QUANT_CHUNK", "").strip()
    if not env or env == "0":
        return _block_rows(f)
    try:
        r = int(env)
    except ValueError:
        raise MXNetError(
            "MXNET_QUANT_CHUNK=%r is not an integer chunk size" % env)
    if r < 0 or (r < f and (f % r or r % _LANE)):
        raise MXNetError(
            "MXNET_QUANT_CHUNK=%d must be a multiple of %d that divides "
            "the weight's output-channel count (%d here): the chunk "
            "walk partitions output rows exactly, in blocks the TPU's "
            "lane axis can hold; pick such a divisor, a value >= the "
            "count (dequantize whole) or 0 for the auto choice"
            % (r, _LANE, f))
    return None if r >= f else r


def _dequant_rows(qt, wc, sc, dtype):
    """Dequantize one output-row chunk ``wc`` (with its scale slice
    ``sc``) to ``dtype``. int8: values scaled per row AFTER this via
    the caller (returns the raw cast); int4: unpack + per-group scale
    on the contraction axis (must happen before the dot)."""
    if qt.bits == 4:
        v = unpack_int4(wc, dtype=jnp.float32)
        return (v * jnp.repeat(sc, qt.group, axis=-1)).astype(dtype)
    return wc.astype(dtype)


def scale_fused_matmul(x, qt):
    """``x [..., E] @ qt [F, E]^T`` with on-the-fly dequantization:
    returns ``[..., F]`` in ``x``'s dtype.

    int8: the per-output-channel scale multiplies the OUTPUT
    (``(x @ q^T) * s == x @ (q*s)^T`` exactly), so the int8 weight
    feeds the dot directly and no float copy of the weight ever
    exists. int4: per-group scales sit on the contraction axis, so
    each chunk is unpacked and scaled BEFORE its dot — still only one
    chunk of float staging. Either way the weight is walked in
    output-channel chunks inside one ``lax.fori_loop``
    (:func:`resolve_chunk` — ``MXNET_QUANT_CHUNK``): chunking
    partitions independent output channels — bitwise identical to the
    unchunked product, at any chunk count."""
    q, s = qt.q, qt.scale
    f = q.shape[0]

    def piece(wc, sc):
        if qt.bits == 4:
            w = _dequant_rows(qt, wc, sc, x.dtype)
            return jnp.einsum("...e,fe->...f", x, w)
        oc = jnp.einsum("...e,fe->...f", x, wc.astype(x.dtype))
        return oc * sc.astype(x.dtype)

    r = resolve_chunk(f)
    if r is None:
        return piece(q, s)
    out0 = jnp.zeros(x.shape[:-1] + (f,), x.dtype)
    ax = out0.ndim - 1

    def body(i, out):
        wc = lax.dynamic_slice_in_dim(q, i * r, r, axis=0)
        sc = lax.dynamic_slice_in_dim(s, i * r, r, axis=0)
        return lax.dynamic_update_slice_in_dim(out, piece(wc, sc),
                                               i * r, axis=ax)

    return lax.fori_loop(0, f // r, body, out0)


def embedding_rows(qt, idx):
    """Quantized Embedding lookup: gather int8 rows and their scales,
    dequantize only the GATHERED rows — the table itself is read at
    1 byte/elem (per-row scales are per-output-channel here: the
    vocab row IS the output channel). Embedding tables are always
    per-row int8 (``quantize_params(row_quant=...)``): a packed-nibble
    row gather would read-modify every byte for half its bits."""
    rows = jnp.take(qt.q, idx, axis=0).astype(jnp.float32)
    sc = jnp.take(qt.scale, idx, axis=0)
    return (rows * sc[..., None]).astype(qt.dtype)


def expert_slice(qt, i):
    """Static expert ``i`` of a stacked MoE :class:`QuantizedTensor`
    (``[X, out, contract]`` values + matching scales) as its own 2-D
    quantized weight — what the per-expert Pallas matmul dispatches
    on."""
    return QuantizedTensor(qt.q[i], qt.scale[i], qt.dtype,
                           bits=qt.bits, group=qt.group)


def _expert_matmul(h, qt):
    """``h [B, T, X, H] x w2 [X, E, H] -> [B, T, X, E]`` (the MoE
    down-projection, contraction per expert) with on-the-fly dequant:
    a ``fori_loop`` over experts, each expert's slice staged at expert
    size. Bitwise identical to the unchunked einsum on the
    dequantized stack (experts are independent output blocks)."""
    q, s = qt.q, qt.scale
    nx = q.shape[0]
    out0 = jnp.zeros(h.shape[:2] + (nx, q.shape[1]), h.dtype)

    def body(i, out):
        qc = lax.dynamic_slice_in_dim(q, i, 1, axis=0)
        sc = lax.dynamic_slice_in_dim(s, i, 1, axis=0)
        hc = lax.dynamic_slice_in_dim(h, i, 1, axis=2)
        if qt.bits == 4:
            w = _dequant_rows(qt, qc, sc, h.dtype)
            oc = jnp.einsum("btxh,xeh->btxe", hc, w)
        else:
            oc = jnp.einsum("btxh,xeh->btxe", hc, qc.astype(h.dtype)) \
                * sc.astype(h.dtype)[None, None]
        return lax.dynamic_update_slice_in_dim(out, oc, i, axis=2)

    return lax.fori_loop(0, nx, body, out0)


def moe_ffn_forward(p, ins, mm=None, ep=None):
    """MoEFFN forward with any mix of quantized/float weights: the
    routing + combine math is ``ops.attention.moe_ffn_math`` — the
    SAME implementation the fp op runs — with the matmul of each
    quantized weight swapped for its scale-fused form.

    ``mm`` (optional) replaces :func:`scale_fused_matmul` for the 2-D
    quantized products — the ``matmul_impl="pallas"`` hook: the MoE
    expert stack rides the SAME kernel as the dense projections
    through these pluggable matmuls. ``ep=(axis_name, degree)`` runs
    the math expert-parallel: the stacks arrive sharded on the expert
    axis and ``moe_ffn_math`` gathers gate logits / psums the combine
    (doc/serving.md "Expert-parallel MoE")."""
    from ..ops.attention import moe_ffn_math
    qmm = mm if mm is not None else scale_fused_matmul

    def gate_mm(x, w):
        if isinstance(w, QuantizedTensor):
            return qmm(x, w)
        return jnp.einsum("bte,xe->btx", x, w)

    def up_mm(x, w):
        if not isinstance(w, QuantizedTensor):
            return jnp.einsum("bte,xhe->btxh", x, w)
        # [X, H, E] contracts E with output channels (x, h): the 2-D
        # helper over the flattened [X*H, E] view is the same einsum,
        # bitwise
        xq, hq = w.q.shape[:2]
        flat = QuantizedTensor(
            w.q.reshape((xq * hq,) + w.q.shape[2:]),
            w.scale.reshape((xq * hq,) + w.scale.shape[2:]),
            w.dtype, bits=w.bits, group=w.group)
        return qmm(x, flat).reshape(x.shape[:-1] + (xq, hq))

    def down_mm(h, w):
        if not isinstance(w, QuantizedTensor):
            return jnp.einsum("btxh,xeh->btxe", h, w)
        if mm is None:
            return _expert_matmul(h, w)
        # kernel path: one quant_matmul per expert (trace-time unroll
        # — the expert count is static and, under ep, already local)
        nx = w.q.shape[0]
        cols = [mm(h[:, :, i], expert_slice(w, i)) for i in range(nx)]
        return jnp.stack(cols, axis=2)

    return moe_ffn_math(p, ins, gate_mm=gate_mm, up_mm=up_mm,
                        down_mm=down_mm, ep=ep)


def weight_nbytes(params):
    """Total stored bytes of a parameter dict (quantized entries count
    int8 values + scales) — the ``serving.weight_bytes`` gauge."""
    return int(sum(leaf.nbytes
                   for leaf in jax.tree_util.tree_leaves(params)))
