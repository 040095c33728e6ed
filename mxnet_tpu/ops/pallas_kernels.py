"""Hand-written Pallas TPU kernels for the hot ops.

The reference's analogue layer is the cuDNN-backed operator variants
(``src/operator/cudnn_*``, selected at CreateOp when available) and NVRTC
runtime kernels (``src/common/mxrtc.cc``). Here the default path is XLA
fusion; these kernels cover what XLA does not fuse well:

* ``flash_attention`` — streaming-softmax attention tiled for VMEM: one
  pass over K/V blocks per query block, f32 accumulators, MXU matmuls.
  O(T) memory instead of O(T²), forward AND backward: the forward also
  emits the per-row logsumexp, and the ``jax.custom_vjp`` backward is a
  pair of Pallas kernels (dQ tiled over query blocks, dK/dV over key
  blocks) that stream-recompute the probability blocks from (q, k, lse)
  instead of materializing the T×T matrix — training memory through the
  attention op is linear in sequence length.
* ``fused_linear`` — matmul + bias + activation epilogue in one kernel
  (the reference fuses this per-op in mshadow: fully_connected-inl.h).

Kernels run on TPU; on CPU (tests) they run under the Pallas interpreter,
keeping the backend-consistency oracle (SURVEY.md §4.3) meaningful.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "fused_linear", "striped_pair_attention",
           "matmul_stats", "paged_attention", "default_paged_block_k",
           "paged_rows_fetched",
           "quant_matmul", "grouped_matmul"]


def _use_interpret():
    """Kernels compile for the TPU and run under the Pallas interpreter
    anywhere else. Keyed on the backend, which is only safe because the
    entry points that measure (``chip_smoke.py``, ``bench.py``) refuse
    a non-TPU backend outright and the smoke asserts this is False: an
    interpreted kernel can never stand in for a compiled one there."""
    return jax.default_backend() != "tpu"


def _pallas_call(kernel, *operands, **kw):
    """``pl.pallas_call(kernel, **kw)(*operands)`` traced with x64 OFF.

    The package turns jax x64 on for the whole process (f64 NDArray
    parity, ``mxnet_tpu/__init__.py``), under which every weak-typed
    Python int in a kernel body or an index map — a literal ``0``, a
    loop bound, ``16 * (x >= 8)`` — traces to int64, and Mosaic
    refuses 64-bit types outright. The kernel and index-map jaxprs are
    traced at this call, so switching x64 off around it keeps 64-bit
    types out of every kernel at one place; operands keep their own
    dtypes."""
    with jax.enable_x64(False):
        return pl.pallas_call(kernel, **kw)(*operands)


def _round_up(x, m):
    return (x + m - 1) // m * m


def default_attn_blocks(head_dim):
    """(block_q, block_k) default for the flash/ring kernels: 512
    tiles measured -33% on the 124M-LM step for head_dim <= 128
    (doc/performance.md round 4); large head dims overflow VMEM at 512.
    MXNET_FLASH_BLOCK_Q/K override.

    Known single-chip ceiling: the BACKWARD kernels keep full-sequence
    q/do/lse/dcap rows in VMEM (the [T, 1] residuals tile to 128
    lanes), which at T=8192 exceeds scoped VMEM at >=256 blocks. Full
    (non-windowed) attention trains longer sequences via sp/ring
    sharding (SequenceParallelTrainer) where each shard's local T
    stays below the limit; the ring impls do not support window>0, so
    windowed training is bounded by this ceiling."""
    import os
    d = 512 if head_dim <= 128 else 128
    return (int(os.environ.get("MXNET_FLASH_BLOCK_Q", d)),
            int(os.environ.get("MXNET_FLASH_BLOCK_K", d)))


# ---------------------------------------------------------------------------
# flash attention

def _window_lo(qi, block_q, block_k, window):
    """First key block any row of query block ``qi`` can see under a
    sliding window: max(0, (qi*block_q - (window-1)) // block_k), in
    the kernels' int32 arithmetic (shared by the fwd and dQ kernels so
    their skip bounds cannot drift apart)."""
    return jnp.maximum(jnp.int32(0),
                       lax.div(qi * jnp.int32(block_q)
                               - jnp.int32(window - 1),
                               jnp.int32(block_k)))


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q,
                     block_k, seq_k, causal, scale, window=0):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # [block_q, D]
    bq, d = q.shape
    # plain python int: pl.cdiv yields a numpy int64 scalar, which would
    # type the fori_loop counter as i64 — Mosaic cannot lower i64 and its
    # int64->int32 conversion helper recurses infinitely
    nkb = int(pl.cdiv(seq_k, block_k))
    if causal:
        # only blocks up to the diagonal contribute (explicit int32 math:
        # x64 weak-typing + Mosaic lowering disagree on int promotion)
        hi = (qi + 1) * jnp.int32(block_q)
        nkb = jnp.minimum(jnp.int32(nkb),
                          lax.div(hi + jnp.int32(block_k - 1),
                                  jnp.int32(block_k)))
    lo = jnp.int32(0)
    if window:
        # sliding window: whole k blocks before the earliest visible
        # key are skipped (this is where the T/window saving comes from)
        lo = _window_lo(qi, block_q, block_k, window)

    neg_big = jnp.float32(-1e30)  # avoid -inf arithmetic in Mosaic

    def body(j, carry):
        o, l, m = carry  # o:[bq,d]  l,m:[bq,1]  (keep 2-D for the VPU)
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        kpos = j * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = kpos < seq_k  # K padding
        if causal:
            mask = mask & (qpos >= kpos)
        if window:
            mask = mask & (qpos - kpos < jnp.int32(window))
        s = jnp.where(mask, s, neg_big)
        new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - new_m), 0.0)
        corr = jnp.exp(m - new_m)
        new_l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        new_o = o * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return new_o, new_l, new_m

    o0 = jnp.zeros((bq, d), jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    m0 = jnp.full((bq, 1), neg_big, jnp.float32)
    # int32 bounds: the package enables jax x64 (f64 NDArray parity), so
    # python-int bounds would make an i64 counter Mosaic cannot lower
    o, l, m = lax.fori_loop(lo, jnp.int32(nkb), body,
                            (o0, l0, m0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    # per-row logsumexp — the backward's residual: p = exp(s - lse)
    # recovers the normalized probabilities blockwise. Kept [T, 1]-shaped
    # (last dim 1): Mosaic requires block last-two-dims (8k, 128k) or
    # equal to the array dims, which (1, block_q) rows would violate.
    lse_ref[0] = m + jnp.log(l)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, true_tk,
               window=0):
    """q,k,v: [BH, T, D] (T padded to block multiples); true_tk = unpadded
    key length (padded keys are masked out). Returns (o, lse)."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    grid = (bh, tq // block_q)
    return _pallas_call(
        functools.partial(_attn_fwd_kernel, block_q=block_q,
                          block_k=block_k, seq_k=true_tk, causal=causal,
                          scale=scale, window=window),
        q, k, v,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))],
        interpret=interpret)


def _attn_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dq_ref,
                    *, block_q, block_k, seq_k, causal, scale, window=0):
    """dQ for one query block: stream over key blocks, recomputing the
    probability block from (q, k, lse) — nothing T×T is ever resident."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)          # [bq, D]
    do = do_ref[0].astype(jnp.float32)        # [bq, D]
    lse = lse_ref[0]                          # [bq, 1]
    dcap = dcap_ref[0]                        # [bq, 1]  rowsum(dO*O)
    bq, d = q.shape
    nkb = int(pl.cdiv(seq_k, block_k))
    if causal:
        hi = (qi + 1) * jnp.int32(block_q)
        nkb = jnp.minimum(jnp.int32(nkb),
                          lax.div(hi + jnp.int32(block_k - 1),
                                  jnp.int32(block_k)))
    lo = jnp.int32(0)
    if window:
        lo = _window_lo(qi, block_q, block_k, window)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        kpos = j * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask = mask & (qpos >= kpos)
        if window:
            mask = mask & (qpos - kpos < jnp.int32(window))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dcap) * scale
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((bq, d), jnp.float32)
    dq = lax.fori_loop(lo, jnp.int32(nkb), body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _attn_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                     dk_ref, dv_ref, *, block_q, block_k, seq_q, seq_k,
                     causal, scale, window=0):
    """dK/dV for one key block: stream over query blocks."""
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)          # [bk, D]
    v = v_ref[0].astype(jnp.float32)          # [bk, D]
    bk, d = k.shape
    nqb = jnp.int32(int(pl.cdiv(seq_q, block_q)))
    if causal:
        # first query block intersecting the diagonal for this key block
        lo = lax.div(ki * jnp.int32(block_k), jnp.int32(block_q))
    else:
        lo = jnp.int32(0)
    if window:
        # sliding window: the LAST query that can see any key of this
        # block is (ki*block_k + bk - 1) + window - 1; later q blocks
        # are skipped entirely
        nqb = jnp.minimum(
            nqb, lax.div(ki * jnp.int32(block_k)
                         + jnp.int32(block_k + window - 2),
                         jnp.int32(block_q)) + jnp.int32(1))

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]    # [bq, 1]
        dcap = dcap_ref[0, pl.ds(i * block_q, block_q), :]  # [bq, 1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
        kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
        mask = (kpos < seq_k) & (qpos < seq_q)
        if causal:
            mask = mask & (qpos >= kpos)
        if window:
            mask = mask & (qpos - kpos < jnp.int32(window))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dcap) * scale
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = lax.fori_loop(lo, nqb, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal, scale, block_q, block_k,
               interpret, true_tq, true_tk, window=0):
    """Blockwise flash backward: dQ kernel over query blocks, dK/dV
    kernel over key blocks. Memory is O(T·block), not O(T²)."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    # D_i = sum_d dO_i * O_i  (the softmax-jacobian row term); padded
    # query rows have dO == 0 so their D is 0. [BH, T, 1] like lse.
    dcap = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True)
    kw = dict(block_q=block_q, block_k=block_k, causal=causal, scale=scale,
              window=window)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    kfull = pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0))
    qfull = pl.BlockSpec((1, tq, d), lambda b, i: (b, 0, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0))
    rowq = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    rowfull = pl.BlockSpec((1, tq, 1), lambda b, i: (b, 0, 0))
    dq = _pallas_call(
        functools.partial(_attn_dq_kernel, seq_k=true_tk, **kw),
        q, k, v, g, lse, dcap,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, tq // block_q),
        in_specs=[qspec, kfull, kfull, qspec, rowq, rowq],
        out_specs=qspec,
        interpret=interpret)
    dk, dv = _pallas_call(
        functools.partial(_attn_dkv_kernel, seq_q=true_tq, seq_k=true_tk,
                          **kw),
        q, k, v, g, lse, dcap,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=(bh, tk // block_k),
        in_specs=[qfull, kspec, kspec, qfull, rowfull, rowfull],
        out_specs=[kspec, kspec],
        interpret=interpret)
    return dq, dk, dv


def _reference_attention(q, k, v, causal, scale, true_tk):
    """Blockwise-exact attention in plain JAX — supplies the VJP and the
    numerical oracle. [BH, T, D] layout, f32 accumulation."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    tq, tk = q.shape[1], k.shape[1]
    kpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    mask = kpos < true_tk
    if causal:
        mask = mask & (lax.broadcasted_iota(jnp.int32, (tq, tk), 0) >= kpos)
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)  # -inf masked entries -> 0
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_core(q, k, v, causal, scale, block_q, block_k, interpret,
                true_tq, true_tk, window=0):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      true_tk, window)[0]


def _flash_core_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                    true_tq, true_tk, window=0):
    o, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret, true_tk, window)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(causal, scale, block_q, block_k, interpret, true_tq,
                    true_tk, window, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, causal, scale, block_q, block_k,
                      interpret, true_tq, true_tk, window)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# The mesh a GSPMD-partitioned caller is tracing under (the trainers set
# it around their graph walk). Mosaic kernels cannot be partitioned
# automatically — on a multi-chip mesh the compiler refuses the step
# with "Mosaic kernels cannot be automatically partitioned. Please wrap
# the call in a shard_map" (the CPU interpreter inlines the kernel, so
# only the chip's compiler ever says so) — and a legacy ``with mesh:``
# is not visible to traced code through any public API. A context
# variable: a trainer and an engine may trace on different threads.
_KERNEL_MESH = contextvars.ContextVar("kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Trace the enclosed code with ``mesh`` as the one kernels
    partition themselves over (see ``flash_attention``)."""
    token = _KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def flash_attention(q, k, v, *, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, window=0):
    """Fused attention. q,k,v: [B, T, H, D]; returns [B, T, H, D].

    Under a multi-device :func:`kernel_mesh` the kernel runs per shard
    inside a ``shard_map``: batch over the ``dp`` axis and heads over
    the ``tp`` axis where the mesh has them and they divide (attention
    is independent per batch row and per head, so this is a partition,
    not a reassociation); any other axis sees the operands replicated.
    """
    local = functools.partial(
        _flash_attention_local, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window)
    mesh = _KERNEL_MESH.get()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        def axis(name, n):
            return name if name in mesh.shape \
                and n % mesh.shape[name] == 0 else None

        spec = P(axis("dp", q.shape[0]), None, axis("tp", q.shape[2]),
                 None)
        local = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                              out_specs=spec, check_vma=False)
    return local(q, k, v)


def _flash_attention_local(q, k, v, *, causal, scale, block_q, block_k,
                           interpret, window):
    """One device's flash attention (see :func:`flash_attention`).

    ``window``>0 (requires ``causal``) computes sliding-window
    attention: keys more than ``window-1`` positions behind their query
    are masked AND whole out-of-window key/query blocks are skipped in
    the forward and both backward kernels, so attention compute scales
    with T·window instead of T².

    Pads T to block multiples internally (padded keys masked out, padded
    queries dropped). Use inside jit; differentiable.

    Block sizes default from ``default_attn_blocks`` (512 for
    head_dim <= 128: bigger tiles amortize the streaming loop, measured
    -33% on the 124M-LM train step vs the round-3 128-blocks,
    doc/performance.md; large head_dims overflow VMEM at 512).
    """
    dq, dk = default_attn_blocks(q.shape[-1])
    if block_q is None:
        block_q = dq
    if block_k is None:
        block_k = dk
    if interpret is None:
        interpret = _use_interpret()
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    block_q = min(block_q, _round_up(tq, 8))
    block_k = min(block_k, _round_up(tk, 8))

    def to_bh(x, t):
        x = x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        tp = _round_up(t, max(block_q, block_k))
        if tp != t:
            x = jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))
        return x

    if window < 0:
        # a negative window would mask EVERY key (qpos-kpos >= 0 always)
        # and silently return zeros through the l >= 1e-30 clamp
        raise ValueError("flash_attention: window must be >= 0, got %d"
                         % window)
    if window and not causal:
        raise ValueError("flash_attention: window>0 requires causal")
    qb, kb, vb = to_bh(q, tq), to_bh(k, tk), to_bh(v, tk)
    out = _flash_core(qb, kb, vb, causal, scale, block_q, block_k, interpret,
                      tq, tk, int(window))
    out = out[:, :tq]
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# striped pair attention — the half-block kernel for striped ring
# attention (parallel/ring.py striped_ring_attention).
#
# Under the STRIPED sequence layout, ring device ``my`` holds tokens at
# global positions {a*n + my}; at each hop it attends its queries against
# the K/V block of ring position ``src`` (tokens {b*n + src}). The causal
# mask is then a*n + q_off >= b*n + k_off — a near-triangle for EVERY
# (my, src) pair, so per-hop FLOPs are balanced across the ring (striped
# attention), unlike the contiguous layout where device 0 masks almost
# everything and device n-1 almost nothing. These kernels skip key
# blocks entirely above the position diagonal (the dynamic fori bound),
# so each hop really costs ~half a block, and emit/consume the per-row
# logsumexp so partial results merge exactly via streaming softmax.
# (q_off, k_off) arrive as an SMEM scalar operand — they are traced ring
# indices, different on every device and hop.


def _spair_fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      block_q, block_k, seq_k, n_stride, scale):
    qi = pl.program_id(1)
    q_off = offs_ref[0]
    k_off = offs_ref[1]
    q = q_ref[0].astype(jnp.float32)  # [bq, D]
    bq, d = q.shape
    ns = jnp.int32(n_stride)
    nkb_static = int(pl.cdiv(seq_k, block_k))
    # last key block with any valid pair: max qpos >= min kpos
    numer = ((qi + 1) * jnp.int32(block_q) - 1) * ns + q_off - k_off
    nkb = jnp.minimum(jnp.int32(nkb_static),
                      lax.div(numer, jnp.int32(block_k) * ns) + 1)
    nkb = jnp.maximum(nkb, jnp.int32(0))
    neg_big = jnp.float32(-1e30)

    def body(j, carry):
        o, l, m = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qrow = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                   (bq, block_k), 0)
        kcol = j * block_k + lax.broadcasted_iota(jnp.int32,
                                                  (bq, block_k), 1)
        mask = (kcol < seq_k) & (qrow * ns + q_off >= kcol * ns + k_off)
        s = jnp.where(mask, s, neg_big)
        new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - new_m), 0.0)
        corr = jnp.exp(m - new_m)
        new_l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        new_o = o * corr + jnp.dot(p, v,
                                   preferred_element_type=jnp.float32)
        return new_o, new_l, new_m

    o0 = jnp.zeros((bq, d), jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    m0 = jnp.full((bq, 1), neg_big, jnp.float32)
    o, l, m = lax.fori_loop(jnp.int32(0), nkb, body, (o0, l0, m0))
    # rows with no valid keys (l == 0): o = 0, lse = -big so the merge
    # weights them to zero
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), neg_big)
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0] = lse


def _spair_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     dcap_ref, dq_ref, *, block_q, block_k, seq_k,
                     n_stride, scale):
    qi = pl.program_id(1)
    q_off = offs_ref[0]
    k_off = offs_ref[1]
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    dcap = dcap_ref[0]
    bq, d = q.shape
    ns = jnp.int32(n_stride)
    nkb_static = int(pl.cdiv(seq_k, block_k))
    numer = ((qi + 1) * jnp.int32(block_q) - 1) * ns + q_off - k_off
    nkb = jnp.minimum(jnp.int32(nkb_static),
                      lax.div(numer, jnp.int32(block_k) * ns) + 1)
    nkb = jnp.maximum(nkb, jnp.int32(0))

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qrow = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                   (bq, block_k), 0)
        kcol = j * block_k + lax.broadcasted_iota(jnp.int32,
                                                  (bq, block_k), 1)
        mask = (kcol < seq_k) & (qrow * ns + q_off >= kcol * ns + k_off)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dcap) * scale
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((bq, d), jnp.float32)
    dq = lax.fori_loop(jnp.int32(0), nkb, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _spair_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      dcap_ref, dk_ref, dv_ref, *, block_q, block_k,
                      seq_q, seq_k, n_stride, scale):
    ki = pl.program_id(1)
    q_off = offs_ref[0]
    k_off = offs_ref[1]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    ns = jnp.int32(n_stride)
    nqb = jnp.int32(int(pl.cdiv(seq_q, block_q)))
    # first query block with any valid pair: max kpos <= max qpos in blk
    # a valid iff a*ns + q_off >= ki*block_k*ns + k_off
    amin = ki * jnp.int32(block_k) + \
        jnp.where(k_off > q_off, jnp.int32(1), jnp.int32(0))
    lo = jnp.maximum(lax.div(amin, jnp.int32(block_q)), jnp.int32(0))

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]
        dcap = dcap_ref[0, pl.ds(i * block_q, block_q), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qrow = i * block_q + lax.broadcasted_iota(jnp.int32,
                                                  (block_q, bk), 0)
        kcol = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                   (block_q, bk), 1)
        mask = (kcol < seq_k) & (qrow < seq_q) & \
            (qrow * ns + q_off >= kcol * ns + k_off)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dcap) * scale
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = lax.fori_loop(lo, nqb, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _spair_specs(tq, tk, block_q, d):
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    kfull = pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0))
    rowq = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    return smem, qspec, kfull, rowq


def _spair_fwd(q, k, v, offs, n_stride, scale, block_q, block_k,
               interpret, true_tk):
    bh, tq, d = q.shape
    tk = k.shape[1]
    smem, qspec, kfull, rowq = _spair_specs(tq, tk, block_q, d)
    return _pallas_call(
        functools.partial(_spair_fwd_kernel, block_q=block_q,
                          block_k=block_k, seq_k=true_tk,
                          n_stride=n_stride, scale=scale),
        offs, q, k, v,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32)],
        grid=(bh, tq // block_q),
        in_specs=[smem, qspec, kfull, kfull],
        out_specs=[qspec, rowq],
        interpret=interpret)


def _spair_bwd_impl(q, k, v, o, lse, offs, g_o, g_lse, n_stride, scale,
                    block_q, block_k, interpret, true_tq, true_tk):
    bh, tq, d = q.shape
    tk = k.shape[1]
    # softmax-jacobian row term, with the lse cotangent folded in:
    # ds = p*(dp - D) + g_lse*p  ==  p*(dp - (D - g_lse))
    dcap = jnp.sum(g_o.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True) - g_lse.astype(jnp.float32)
    smem, qspec, kfull, rowq = _spair_specs(tq, tk, block_q, d)
    qfull = pl.BlockSpec((1, tq, d), lambda b, i: (b, 0, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0))
    rowfull = pl.BlockSpec((1, tq, 1), lambda b, i: (b, 0, 0))
    kw = dict(block_q=block_q, block_k=block_k, n_stride=n_stride,
              scale=scale)
    dq = _pallas_call(
        functools.partial(_spair_dq_kernel, seq_k=true_tk, **kw),
        offs, q, k, v, g_o, lse, dcap,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, tq // block_q),
        in_specs=[smem, qspec, kfull, kfull, qspec, rowq, rowq],
        out_specs=qspec,
        interpret=interpret)
    dk, dv = _pallas_call(
        functools.partial(_spair_dkv_kernel, seq_q=true_tq,
                          seq_k=true_tk, **kw),
        offs, q, k, v, g_o, lse, dcap,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=(bh, tk // block_k),
        in_specs=[smem, qfull, kspec, kspec, qfull, rowfull, rowfull],
        out_specs=[kspec, kspec],
        interpret=interpret)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _spair_core(q, k, v, offs, n_stride, scale, block_q, block_k,
                interpret, true_tk):
    return _spair_fwd(q, k, v, offs, n_stride, scale, block_q, block_k,
                      interpret, true_tk)


def _spair_core_fwd(q, k, v, offs, n_stride, scale, block_q, block_k,
                    interpret, true_tk):
    o, lse = _spair_fwd(q, k, v, offs, n_stride, scale, block_q, block_k,
                        interpret, true_tk)
    return (o, lse), (q, k, v, o, lse, offs)


def _spair_core_bwd(n_stride, scale, block_q, block_k, interpret, true_tk,
                    res, gs):
    q, k, v, o, lse, offs = res
    g_o, g_lse = gs
    tq = q.shape[1]
    dq, dk, dv = _spair_bwd_impl(q, k, v, o, lse, offs, g_o, g_lse,
                                 n_stride, scale, block_q, block_k,
                                 interpret, tq, true_tk)
    d_offs = np.zeros(offs.shape, jax.dtypes.float0)
    return dq, dk, dv, d_offs


_spair_core.defvjp(_spair_core_fwd, _spair_core_bwd)


def striped_pair_attention(q, k, v, q_off, k_off, *, n_stride, scale=None,
                           block_q=128, block_k=128, interpret=None):
    """One striped ring hop: flash attention of the local query block
    against one arriving K/V block under the striped causal mask
    ``(a*n + q_off) >= (b*n + k_off)``.

    q, k, v: [BH, C, D] (C = T/n local length; C must divide into the
    block sizes after internal clamping). ``q_off``/``k_off``: traced
    int32 ring positions. Returns ``(o, lse)`` — o normalized over the
    VALID keys of this block, lse the per-row logsumexp (-1e30 where no
    key is valid) — merge partials with ``jnp.logaddexp`` streaming
    softmax. Differentiable (custom_vjp; the lse cotangent folds into
    the flash backward's dcap term).
    """
    if interpret is None:
        interpret = _use_interpret()
    bh, tq, d = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    block_q = min(block_q, _round_up(tq, 8))
    block_k = min(block_k, _round_up(tk, 8))

    def padt(x, t, blk):
        tp = _round_up(t, blk)
        if tp != t:
            x = jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))
        return x

    qp = padt(q, tq, block_q)
    kp, vp = padt(k, tk, block_k), padt(v, tk, block_k)
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])
    o, lse = _spair_core(qp, kp, vp, offs, int(n_stride), float(scale),
                         block_q, block_k, interpret, tk)
    return o[:, :tq], lse[:, :tq]


# ---------------------------------------------------------------------------
# fused GEMM epilogue (matmul + per-column scale/bias + activation)

_ACTS = {
    "linear": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "gelu": jax.nn.gelu,
}

# derivative of the activation expressed from its OUTPUT (residual-free
# backward); gelu is excluded (needs the preactivation) and handled by
# composing the linear kernel with XLA's gelu
_ACT_GRADS = {
    "linear": lambda g, out: g,
    "relu": lambda g, out: g * (out > 0),
    "sigmoid": lambda g, out: g * out * (1 - out),
    "tanh": lambda g, out: g * (1 - out * out),
}


def _gemm_epi_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, act,
                     nk):
    """One (M,N) tile of act(scale * (x@w) + bias): K is the innermost
    grid dim, accumulated in a VMEM f32 scratch; the epilogue runs on the
    accumulator while it is still in VMEM — one HBM round-trip for the
    output instead of one per fused op."""
    kidx = pl.program_id(2)

    @pl.when(kidx == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kidx == jnp.int32(nk - 1))
    def _epilogue():
        acc = acc_ref[...]
        acc = acc * s_ref[...].astype(jnp.float32) \
            + b_ref[...].astype(jnp.float32)
        o_ref[...] = _ACTS[act](acc).astype(o_ref.dtype)


def _matmul_epilogue(x, w, scale, bias, act, block_m, block_n, block_k,
                     interpret):
    """act(scale * (x @ w) + bias); x [M,K], w [K,N], scale/bias [N] or
    None. K-blocked Pallas GEMM with the epilogue fused on the MXU
    accumulator."""
    m, kdim = x.shape
    n = w.shape[1]
    bm = min(block_m, _round_up(m, 8))
    bn = min(block_n, _round_up(n, 128))
    bk = min(block_k, _round_up(kdim, 128))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(kdim, bk)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - kdim))) \
        if (mp, kp) != (m, kdim) else x
    wp = jnp.pad(w, ((0, kp - kdim), (0, np_ - n))) \
        if (kp, np_) != (kdim, n) else w
    if scale is None:
        scale = jnp.ones((n,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    sp = jnp.pad(scale, (0, np_ - n)).reshape(1, np_)
    bp = jnp.pad(bias, (0, np_ - n)).reshape(1, np_)
    nk = kp // bk
    out = _pallas_call(
        functools.partial(_gemm_epi_kernel, act=act, nk=nk),
        xp, wp, sp, bp,
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_linear_core(x, w, b, act, block_m, block_n, block_k, interpret):
    return _matmul_epilogue(x, w, None, b, act, block_m, block_n, block_k,
                            interpret)


def _fused_linear_fwd(x, w, b, act, block_m, block_n, block_k, interpret):
    out = _matmul_epilogue(x, w, None, b, act, block_m, block_n, block_k,
                           interpret)
    return out, (x, w, out)


def _fused_linear_bwd(act, block_m, block_n, block_k, interpret, res, g):
    x, w, out = res
    dpre = _ACT_GRADS[act](g.astype(jnp.float32), out.astype(jnp.float32))
    dpre = dpre.astype(x.dtype)
    # the backward matmuls are plain MXU dots — XLA schedules them
    dx = jnp.dot(dpre, w.T)
    dw = jnp.dot(x.T, dpre)
    db = jnp.sum(dpre, axis=0)
    return dx, dw, db


_fused_linear_core.defvjp(_fused_linear_fwd, _fused_linear_bwd)


def fused_linear(x, w, b, act="linear", *, block_m=256, block_n=256,
                 block_k=512, interpret=None):
    """act(x @ w + b) in one kernel. x: [M, K], w: [K, N], b: [N].

    Differentiable (``jax.custom_vjp``; the activation derivative is
    reconstructed from the output, so no extra residuals are kept).
    The reference fuses this per-op inside mshadow expressions
    (``fully_connected-inl.h:53-81`` + activation); on TPU the epilogue
    runs on the MXU accumulator while it is still in VMEM.
    """
    if interpret is None:
        interpret = _use_interpret()
    if act not in _ACTS:
        raise ValueError("unknown activation %r" % act)
    if act == "gelu":
        # gelu'(x) needs the preactivation: compose the fused linear
        # kernel with XLA's gelu (still one GEMM + one fused elementwise)
        pre = _fused_linear_core(x, w, b, "linear", block_m, block_n,
                                 block_k, interpret)
        return jax.nn.gelu(pre)
    return _fused_linear_core(x, w, b, act, block_m, block_n, block_k,
                              interpret)


def fused_conv_bn_act(x, w, scale, bias, stride=(1, 1), pad=(0, 0),
                      dilate=(1, 1), act="relu", *, block_m=256,
                      block_n=256, block_k=512, interpret=None):
    """``act(scale_c * conv(x, w) + bias_c)`` — the cuDNN-analogue fused
    inference kernel (reference selects ``cudnn_convolution-inl.h`` /
    ``cudnn_batch_norm-inl.h`` at CreateOp; here conv, the folded
    BatchNorm affine, and the activation run as ONE Pallas GEMM).

    x [N,C,H,W], w [O,C,kh,kw], scale/bias [O] (fold BatchNorm moving
    stats and any conv bias into them). im2col is XLA's
    ``conv_general_dilated_patches``; the GEMM + epilogue is Pallas.
    """
    if interpret is None:
        interpret = _use_interpret()
    n, c, h, wdim = x.shape
    nf, _, kh, kw = w.shape
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), tuple(stride),
        ((int(pad[0]),) * 2, (int(pad[1]),) * 2),
        rhs_dilation=tuple(dilate),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    nb, ckk, oh, ow = patches.shape
    xm = patches.transpose(0, 2, 3, 1).reshape(nb * oh * ow, ckk)
    wm = w.reshape(nf, ckk).T
    out = _matmul_epilogue(xm, wm, scale, bias, act, block_m, block_n,
                           block_k, interpret)
    return out.reshape(nb, oh, ow, nf).transpose(0, 3, 1, 2)


# ---------------------------------------------------------------------------
# training conv(1x1)+BN-stats epilogue: GEMM that also emits per-column
# sum and sum-of-squares of its OWN output, accumulated while the MXU
# tile is still in VMEM — the batch-stats read the training BatchNorm
# would otherwise do against HBM disappears. Reference analogue: the
# cuDNN-selected conv + batch_norm pair (cudnn_convolution-inl.h,
# batch_norm-inl.h:95-125), fused the TPU way.

def _gemm_stats_kernel(x_ref, w_ref, o_ref, s1_ref, s2_ref, acc_ref, *,
                       nk):
    """One (M,N) tile of x@w; on the last K step also reduce the f32
    accumulator tile to per-column sum / sum-of-squares partials
    (grid_m x N), BEFORE the output is rounded to its storage dtype —
    the stats see the exact f32 GEMM results."""
    kidx = pl.program_id(2)

    @pl.when(kidx == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kidx == jnp.int32(nk - 1))
    def _epilogue():
        acc = acc_ref[...]
        o_ref[...] = acc.astype(o_ref.dtype)
        # Mosaic wants >=8 sublanes per output block: broadcast the
        # per-column partials over the 8 rows (the host-side combine
        # divides the final sum by 8 — exact in binary fp)
        s1 = jnp.sum(acc, axis=0, keepdims=True)
        s2 = jnp.sum(acc * acc, axis=0, keepdims=True)
        s1_ref[...] = jnp.broadcast_to(s1, s1_ref.shape)
        s2_ref[...] = jnp.broadcast_to(s2, s2_ref.shape)


def _matmul_stats_impl(x, w, block_m, block_n, block_k, interpret):
    m, kdim = x.shape
    n = w.shape[1]
    bm = min(block_m, _round_up(m, 8))
    bn = min(block_n, _round_up(n, 128))
    bk = min(block_k, _round_up(kdim, 128))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(kdim, bk)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - kdim))) \
        if (mp, kp) != (m, kdim) else x
    wp = jnp.pad(w, ((0, kp - kdim), (0, np_ - n))) \
        if (kp, np_) != (kdim, n) else w
    nk = kp // bk
    gm = mp // bm
    out, s1p, s2p = _pallas_call(
        functools.partial(_gemm_stats_kernel, nk=nk),
        xp, wp,
        out_shape=(jax.ShapeDtypeStruct((mp, np_), x.dtype),
                   jax.ShapeDtypeStruct((gm * 8, np_), jnp.float32),
                   jax.ShapeDtypeStruct((gm * 8, np_), jnp.float32)),
        grid=(gm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
                   pl.BlockSpec((8, bn), lambda i, j, k: (i, j)),
                   pl.BlockSpec((8, bn), lambda i, j, k: (i, j))),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret)
    # tiny (8*grid_m, N) partial reduction — each tile's partial is
    # replicated over 8 sublanes (Mosaic min block), hence the /8,
    # which is exact in binary fp; padded M rows are zeros in x, so
    # they contribute exactly 0 to both partials
    return (out[:m, :n], s1p.sum(axis=0)[:n] / 8.0,
            s2p.sum(axis=0)[:n] / 8.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _matmul_stats_core(x, w, block_m, block_n, block_k, interpret):
    return _matmul_stats_impl(x, w, block_m, block_n, block_k, interpret)


def _matmul_stats_fwd(x, w, block_m, block_n, block_k, interpret):
    outs = _matmul_stats_impl(x, w, block_m, block_n, block_k, interpret)
    return outs, (x, w, outs[0])


def _matmul_stats_bwd(block_m, block_n, block_k, interpret, res, gs):
    x, w, y = res
    gy, gs1, gs2 = gs
    # s1 = sum_rows(y), s2 = sum_rows(y^2): their cotangents fold into
    # the output cotangent as broadcasts, keeping ONE pair of backward
    # MXU dots for the whole fused op
    g = (gy.astype(jnp.float32)
         + gs1[None, :].astype(jnp.float32)
         + 2.0 * y.astype(jnp.float32) * gs2[None, :].astype(jnp.float32))
    g = g.astype(x.dtype)
    dx = jnp.dot(g, w.T)
    dw = jnp.dot(x.T, g)
    return dx, dw


_matmul_stats_core.defvjp(_matmul_stats_fwd, _matmul_stats_bwd)


def matmul_stats(x, w, *, block_m=256, block_n=256, block_k=512,
                 interpret=None):
    """(x @ w, per-column sum, per-column sum-of-squares) in ONE kernel.

    x: [M, K], w: [K, N]. The stats are exact f32 sums of the GEMM
    output read from the VMEM accumulator — the consumer (training
    conv+BatchNorm fusion, ops/fusion.py) derives batch mean/var
    without re-reading the activation from HBM. Differentiable:
    d(s1)/d(s2) cotangents fold into the output cotangent, so the
    backward is the usual two MXU dots."""
    if interpret is None:
        interpret = _use_interpret()
    return _matmul_stats_core(x, w, block_m, block_n, block_k, interpret)


# ---------------------------------------------------------------------------
# paged attention — the serving engine's decode/verify/draft read.
#
# The slot cache is stored [S, max_len, Hkv*D] (kv-major lanes;
# parallel/decode.py states the layout) with every slot at its own
# position, and most of it holds nothing a request needs: rows past a
# slot's position, and every row of a slot whose request has finished.
# This kernel fetches only the blocks of rows that live requests hold.
# Grid (slot visit, kv-block) under a PrefetchScalarGridSpec: the
# per-slot LENGTHS (rows the read may fetch; 0 for a slot that holds no
# request) are scalar-prefetched, slots are visited live ones first,
# and the cache index maps send every grid step past a slot's last live
# block — and every step of a dead slot — to the block just visited (a
# revisited block index, whose HBM->VMEM copy Mosaic elides; the body
# is pl.when-gated off): the bound cuts the DMA itself, and a dead slot
# costs no copy at all. Inside a block the arithmetic is the dense
# read's (Decoder._lane_attn): all kv heads at once off the lane-dense
# rows, against the query spread block-diagonally over the lanes.
# Online-softmax scratch merges blocks exactly (a reassociation, not an
# approximation), int8 caches apply their side scales to the scores and
# the weights in the kernel, and C > 1 serves the chunked-query
# flavors: the speculative verify step's [S, K+1] chunk and the draft
# model's catch-up window (doc/serving.md "The decode read").
#
# NOT ring-safe: a windowed ring stores rows at wrapped positions, so
# "rows [0, pos+C)" is not the live set — a ring keeps its own walk
# (Decoder._window_attn).

# a K or V block in flight holds at most this many bytes (two of each
# are in flight: 4 MB of the chip's VMEM at the cap). On the chip, at
# bf16 rows of 2048 lanes with 5 of 16 slots live, blocks of 128 / 256 /
# 512 rows read 0.069 / 0.064 / 0.066 ms a layer and step (PERF.md
# section 6, PR 29): smaller blocks round a slot's length up by less,
# larger ones take fewer grid steps. At rows of 256 lanes (512 B) with
# 12 of 32 slots live the grid steps lead: blocks of 256 / 512 / 1024 /
# 2048 rows read 0.065 / 0.046 / 0.043 / 0.042 ms (PERF.md section 6,
# PR 31), so narrow rows take blocks of up to 1024 rows
_PAGED_BLOCK_BYTES = 1 << 20


def default_paged_block_k(max_len, row_bytes=None):
    """KV rows per block for ``paged_attention``, from the shapes
    alone: the largest of (1024, 512, 256, 128, 64, 32, 16, 8) that
    divides ``max_len`` (whole blocks keep the in-kernel slices static)
    and, where the stored row's ``row_bytes`` are given, keeps a block
    within 1 MB — 256 rows of 4 KB, 1024 rows of 512 B: a slot of a
    few hundred live rows is then one to four grid steps; else
    ``max_len`` itself: a cache too short/odd to block degenerates to
    one block, still bounded by the position mask."""
    fits = [b for b in (1024, 512, 256, 128, 64, 32, 16, 8)
            if max_len % b == 0]
    for b in fits:
        if row_bytes is None or b * row_bytes <= _PAGED_BLOCK_BYTES:
            return b
    return fits[-1] if fits else max_len


def paged_rows_fetched(lens, max_len, block_k):
    """Rows of ONE [S, max_len, ...] buffer that ``paged_attention``
    fetches for the lengths ``lens`` [S]: each slot's length rounded
    up to whole blocks (the serving engine's
    ``serving.attn_rows_read``). With no live slot at all the kernel
    still stages one block, which this leaves out."""
    lens = jnp.clip(jnp.asarray(lens, jnp.int32), 0, max_len)
    return jnp.sum((lens + (block_k - 1)) // block_k * block_k)


def _paged_visit(lens, max_len, block_k):
    """What the paged reads scalar-prefetch, from the slots' lengths
    ``lens`` [S]: the order of the visit (slots with rows first; a
    stable sort keeps their order), each visit's count of live blocks,
    and for a visit with none the (slot, block) the last live visit ended
    on, where every one of its steps parks."""
    i32 = jnp.int32
    nkb_slot = (jnp.clip(lens, 0, max_len) + (block_k - 1)) // block_k
    order = jnp.argsort(nkb_slot == 0, stable=True).astype(i32)
    nkb = nkb_slot[order]
    last = order[jnp.maximum(jnp.sum(nkb_slot > 0) - 1, 0)]
    kslot = jnp.where(nkb > 0, order, last).astype(i32)
    kblk = jnp.broadcast_to(jnp.maximum(nkb_slot[last] - 1, 0),
                            lens.shape).astype(i32)
    return order, nkb, kslot, kblk


def _paged_attn_kernel(order_ref, nkb_ref, kslot_ref, kblk_ref, pos_ref,
                       q_ref, k_ref, v_ref, hm_ref, *rest, block_k,
                       chunk, group, n_blocks, scale, quant, kv_heads,
                       kv_pad):
    """One (slot visit, kv-block) grid cell of the paged read.

    Step ``i`` visits slot ``order[i]`` (live slots first), whose
    ``nkb[i]`` leading blocks hold rows a request needs; the cache
    BlockSpecs' index maps (see ``paged_attention``) keep every other
    step on the block fetched last, and this body is ``pl.when``-gated
    off for them. The cache block is the ``[block_k, Hkv*D]``
    lane-dense view of ALL kv heads' rows, consumed whole: the query
    rows (r = (c, g): chunk position, member of the GQA group) are
    spread into ``Qbd [R * Hkv', Hkv*D]`` — row (r, h) holds query row
    r's head-h values on head h's lanes, zeros elsewhere (``hm``, the
    0/1 lane-to-head matrix; ``Hkv'`` is Hkv padded to whole sublane
    tiles, the padding rows all zero) — so ``Qbd @ K_block^T`` gives
    every head's scores in one product with the operands in the
    compute dtype and float32 sums, the softmax runs in float32 with
    ``block_k`` on the lanes, and ``p @ V_block`` gives every head's
    weights against every lane, of which the diagonal blocks (head h's
    weights against head h's lanes) are picked once, on the last step.
    Online-softmax state (acc/l/m) lives in VMEM scratch carried
    across the kv-block sweep. int8 caches: the row scales
    ``[block_k, Hkv]`` are turned and spread over the rows (r, h) by a
    product with a 0/1 matrix at full precision (exact) and applied to
    the scores and to ``p``, as ``Decoder._lane_attn`` does. int32
    arithmetic throughout (see ``_pallas_call``)."""
    if quant:
        ks_ref, vs_ref, sel_ref, o_ref, qbd_ref, acc_ref, l_ref, m_ref \
            = rest
    else:
        o_ref, qbd_ref, acc_ref, l_ref, m_ref = rest
    i = pl.program_id(0)
    j = pl.program_id(1)
    rows = chunk * group
    n = rows * kv_pad
    neg_big = jnp.float32(-1e30)
    f32 = jnp.float32
    cdt = qbd_ref.dtype
    # f32 operands (the byte-identity contract) multiply exactly
    prec = lax.Precision.HIGHEST if cdt == jnp.float32 else None

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        m_ref[...] = jnp.full(m_ref.shape, neg_big, f32)
        for r in range(rows):
            qbd_ref[r * kv_pad:(r + 1) * kv_pad, :] = \
                q_ref[0, r] * hm_ref[...]

    @pl.when(j < nkb_ref[i])
    def _block():
        p0 = pos_ref[order_ref[i]]
        # query absolute positions: row (r, h) sits at chunk offset
        # r // group
        row = lax.broadcasted_iota(jnp.int32, (n, block_k), 0)
        qpos = jnp.full((n, block_k), p0, jnp.int32)
        for c in range(1, chunk):
            qpos = jnp.where(row >= c * group * kv_pad, p0 + c, qpos)
        kpos = j * block_k + lax.broadcasted_iota(
            jnp.int32, (n, block_k), 1)
        mask = kpos <= qpos              # causal; also masks the tail
        kb = k_ref[0].astype(cdt)        # int8 values are exact
        vb = v_ref[0].astype(cdt)
        sc = lax.dot_general(qbd_ref[...], kb, (((1,), (1,)), ((), ())),
                             precision=prec,
                             preferred_element_type=f32)     # [n, bk]
        if quant:
            hi = lax.Precision.HIGHEST
            sc = sc * lax.dot_general(
                sel_ref[...], ks_ref[0], (((1,), (1,)), ((), ())),
                precision=hi, preferred_element_type=f32)
        sc = jnp.where(mask, sc * scale, neg_big)
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(sc - new_m), 0.0)
        corr = jnp.exp(m - new_m)
        l_ref[...] = l_ref[...] * corr \
            + jnp.sum(pexp, axis=1, keepdims=True)
        if quant:
            pexp = pexp * lax.dot_general(
                sel_ref[...], vs_ref[0], (((1,), (1,)), ((), ())),
                precision=hi, preferred_element_type=f32)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            pexp.astype(cdt), vb, precision=prec,
            preferred_element_type=f32)                      # [n, W]
        m_ref[...] = new_m

    # a live slot's row `pos` was written before the read, so its
    # denominator is never the clamp; a dead slot (no block at all)
    # emits zeros
    @pl.when(j == jnp.int32(n_blocks - 1))
    def _emit():
        x = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        hm = hm_ref[...].astype(f32)
        out_row = lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 0)
        o = jnp.zeros(o_ref.shape[1:], f32)
        for r in range(rows):
            o_r = jnp.sum(x[r * kv_pad:(r + 1) * kv_pad] * hm, axis=0,
                          keepdims=True)                     # [1, W]
            o = jnp.where(out_row == r, o_r, o)
        o_ref[0] = o.astype(o_ref.dtype)


def paged_attention(q, k, v, pos, *, kv_heads, lens=None, k_scale=None,
                    v_scale=None, scale=None, block_k=None,
                    interpret=None):
    """Slot-paged decode attention reading only the rows live requests
    hold.

    q: [S, C, H, D] — each slot's C-token query chunk (C=1 plain
    decode; C=K+1 the speculative verify chunk; C=W the draft
    catch-up). k, v: the cache buffers AS STORED, [S, L, Hkv*D] with
    the ``kv_heads`` heads' D values side by side, kv-major
    (``parallel/decode.py`` states the layout; float, or int8 with
    ``k_scale``/``v_scale`` [S, L, Hkv] f32 row scales, applied
    inside the kernel). The kernel takes them as they are: a
    (block_k, Hkv*D) block is whole on the lane axis. Inside a
    tensor-parallel shard the buffers are the shard's own lanes and
    ``kv_heads`` its local head count. pos: [S] int32, the chunk's
    start position per slot: the chunk rows at [pos, pos+C) must
    already be WRITTEN (the decoder writes before reading, same as the
    dense path), and each query row attends keys [0, pos + its chunk
    offset]. lens: [S] int32 (default ``pos + C``), the rows of each
    slot the read may FETCH, rounded up to whole blocks: the serving
    engine hands ``pos + C`` for a slot that holds a request and 0 for
    one that does not, whose output is then zeros (finite; its
    position is stale and its rows belong to nobody). Returns
    [S, C, H, D] in q's dtype, f32 accumulation.

    The kv-block walk is a grid dimension under a
    ``PrefetchScalarGridSpec``. What is scalar-prefetched is made here
    from ``lens``: the order of the visit (slots with rows first),
    each visit's count of live blocks, and for a visit with none the
    (slot, block) fetched last before it — so the cache index maps
    keep every step past a slot's live prefix, and every step of a
    dead slot, on a REVISITED block index whose HBM->VMEM copy Mosaic
    elides; the kernel body is ``pl.when``-gated off there. Dead rows
    are therefore never FETCHED, not merely never computed on.
    Grouped-query attention is native: a block of every kv head's K/V
    rows streams once past the whole query group.
    On TPU the kernel runs compiled; on CPU (tests) it runs under the
    Pallas interpreter — same testing discipline as the flash kernel
    above. NOTE the interpreter executes all ``n_blocks`` grid steps
    (the revisit elision is a Mosaic behavior), so a CPU wall clock
    and XLA cost analysis both under-sell the bound."""
    if interpret is None:
        interpret = _use_interpret()
    s_, c, h, d = q.shape
    l_ = k.shape[1]
    kv = int(kv_heads)
    w = kv * d
    if k.ndim != 3 or k.shape[2] != w or v.shape != k.shape:
        raise ValueError(
            "paged_attention: k and v must be the stored cache buffers "
            "[S, L, kv_heads*D] = [%d, L, %d], got %s and %s"
            % (s_, w, k.shape, v.shape))
    g = h // kv
    rows = c * g
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    if block_k is None:
        block_k = default_paged_block_k(
            l_, w * jnp.dtype(k.dtype).itemsize)
    if l_ % block_k:
        raise ValueError(
            "paged_attention: block_k=%d must divide the cache length "
            "%d (whole blocks keep the grid static)" % (block_k, l_))
    quant = (k_scale is not None) or (v_scale is not None)
    if quant and (k_scale is None or v_scale is None):
        raise ValueError("paged_attention: k_scale and v_scale must be "
                         "passed together")
    nb = l_ // block_k
    i32 = jnp.int32
    pos = jnp.asarray(pos, i32)
    lens = pos + c if lens is None else jnp.asarray(lens, i32)
    order, nkb, kslot, kblk = _paged_visit(lens, l_, block_k)
    # query rows r = (c, g), lanes (kv, d) as the cache stores them:
    # the decoder's GQA fold (Decoder._lane_attn)
    qr = q.reshape(s_, c, kv, g, d).transpose(0, 1, 3, 2, 4) \
        .reshape(s_, rows, 1, w)
    # Hkv padded to whole sublane tiles of the compute dtype, so every
    # row r's [Hkv', W] piece of Qbd is tile-aligned
    kvp = _round_up(kv, 8 * 4 // jnp.dtype(q.dtype).itemsize)
    hm = (np.arange(w)[None, :] // d == np.arange(kvp)[:, None])
    n = rows * kvp

    def qmap(i, j, order, nkb, kslot, kblk, pos):
        return (order[i], 0, 0, 0)

    def omap(i, j, order, nkb, kslot, kblk, pos):
        return (order[i], 0, 0)

    def kmap(i, j, order, nkb, kslot, kblk, pos):
        live = nkb[i] > 0
        return (kslot[i],
                jnp.where(live, jnp.minimum(j, nkb[i] - 1), kblk[i]), 0)

    def whole(i, j, order, nkb, kslot, kblk, pos):
        return (0, 0)

    # the cache rides as it is stored, [S, L, Hkv*D]: a (block_k,
    # Hkv*D) block is whole on the lane axis, which the TPU lowering
    # accepts at any head count
    in_specs = [
        pl.BlockSpec((1, rows, 1, w), qmap),
        pl.BlockSpec((1, block_k, w), kmap),
        pl.BlockSpec((1, block_k, w), kmap),
        pl.BlockSpec((kvp, w), whole),
    ]
    operands = [qr, k, v, jnp.asarray(hm, q.dtype)]
    if quant:
        # [S, L, KV] row scales: a (block_k, KV) block, one lane per
        # kv head; ``sel`` [n, KV] maps row (r, h) to its head's lane
        operands.append(k_scale.astype(jnp.float32))
        operands.append(v_scale.astype(jnp.float32))
        sel = np.arange(n)[:, None] % kvp == np.arange(kv)[None, :]
        operands.append(jnp.asarray(sel, jnp.float32))
        sspec = pl.BlockSpec((1, block_k, kv), kmap)
        in_specs.extend([sspec, sspec, pl.BlockSpec((n, kv), whole)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s_, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, w), omap),
        scratch_shapes=[
            pltpu.VMEM((n, w), q.dtype),           # Qbd
            pltpu.VMEM((n, w), jnp.float32),       # acc
            pltpu.VMEM((n, 1), jnp.float32),       # l
            pltpu.VMEM((n, 1), jnp.float32),       # m
        ],
    )
    # two K and two V blocks in flight, their casts (int8), Qbd / acc
    # and the [n, block_k] softmax temporaries
    block_bytes = block_k * w * jnp.dtype(k.dtype).itemsize
    need = 4 * block_bytes + 2 * block_k * w * jnp.dtype(q.dtype).itemsize \
        + 8 * n * (w + block_k) * 4
    out = _pallas_call(
        functools.partial(_paged_attn_kernel, block_k=block_k, chunk=c,
                          group=g, n_blocks=nb, scale=float(scale),
                          quant=quant, kv_heads=kv, kv_pad=kvp),
        order, nkb, kslot, kblk, pos, *operands,
        out_shape=jax.ShapeDtypeStruct((s_, rows, w), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(max(2 * need, 32 << 20),
                                     100 << 20))),
        interpret=interpret)
    return out.reshape(s_, c, g, kv, d).transpose(0, 1, 3, 2, 4) \
        .reshape(s_, c, h, d)


# -- the latent read: one block of rows for scores AND values ----------
#
# Multi-head latent attention's decode step (ops/attention.py, the absorbed
# form) reads a cache of LATENT rows ``[c ; k_r]`` [S, L, W] with no head
# axis: every head's query ``[q~_h ; q_r,h]`` (W numbers) scores against
# the whole row, and the values are the row's first ``v_width`` numbers
# (``c``). The walk is ``paged_attention``'s (same scalar prefetch, same
# visit order, dead blocks never fetched); what differs is inside a
# block: ONE [block_k, W] fetch serves both products, the H heads (times
# the chunk's C positions) are the query rows against it, there is no
# block-diagonal spread, and W need not be whole lane tiles (576 = 512 +
# 64: the two parts are contracted apart, each over whole or half tiles;
# the decoder stores the row padded to whole tiles, which is what the
# chip keeps of a 576-wide row anyway).

def _latent_attn_kernel(order_ref, nkb_ref, kslot_ref, kblk_ref, pos_ref,
                        q_ref, c_ref, o_ref, acc_ref, l_ref, m_ref, *,
                        block_k, chunk, heads, n_blocks, scale, v_width,
                        width):
    i = pl.program_id(0)
    j = pl.program_id(1)
    n = chunk * heads
    neg_big = jnp.float32(-1e30)
    f32 = jnp.float32
    cdt = q_ref.dtype
    prec = lax.Precision.HIGHEST if cdt == jnp.float32 else None
    nt = (((1,), (1,)), ((), ()))

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        m_ref[...] = jnp.full(m_ref.shape, neg_big, f32)

    @pl.when(j < nkb_ref[i])
    def _block():
        p0 = pos_ref[order_ref[i]]
        row = lax.broadcasted_iota(jnp.int32, (n, block_k), 0)
        qpos = jnp.full((n, block_k), p0, jnp.int32)
        for c in range(1, chunk):
            qpos = jnp.where(row >= c * heads, p0 + c, qpos)
        kpos = j * block_k + lax.broadcasted_iota(
            jnp.int32, (n, block_k), 1)
        mask = kpos <= qpos
        q = q_ref[0]                                   # [n, W]
        lat = c_ref[0, :, :v_width].astype(cdt)        # [bk, R]
        sc = lax.dot_general(q[:, :v_width], lat, nt, precision=prec,
                             preferred_element_type=f32)
        if width > v_width:
            sc = sc + lax.dot_general(
                q[:, v_width:], c_ref[0, :, v_width:width].astype(cdt), nt,
                precision=prec, preferred_element_type=f32)
        sc = jnp.where(mask, sc * scale, neg_big)
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(sc - new_m), 0.0)
        corr = jnp.exp(m - new_m)
        l_ref[...] = l_ref[...] * corr \
            + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            pexp.astype(cdt), lat, precision=prec,
            preferred_element_type=f32)                # [n, R]
        m_ref[...] = new_m

    @pl.when(j == jnp.int32(n_blocks - 1))
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)) \
            .astype(o_ref.dtype)


def latent_paged_attention(q, rows, pos, *, v_width, scale, lens=None,
                           block_k=None, interpret=None):
    """Slot-paged decode attention over a cache of latent rows, each
    block fetched once for scores and values.

    q: [S, C, H, W], each slot's C-token chunk of absorbed queries;
    rows: the cache as stored, [S, L, W'] with ``W' >= W`` (no head
    axis: key and value at once; the decoder stores a row padded to
    whole lane tiles, and lanes past ``W`` are fetched with their tile
    and not contracted); the values are ``rows[..., :v_width]``. pos, lens: as
    ``paged_attention`` takes them (the chunk's rows at [pos, pos + C)
    already written; ``lens`` 0 for a slot that holds no request, whose
    output is zeros). Returns [S, C, H, v_width] in q's dtype, float32
    accumulation. ``scale`` multiplies the scores."""
    if interpret is None:
        interpret = _use_interpret()
    s_, c, h, w = q.shape
    if rows.ndim != 3 or rows.shape[0] != s_ or rows.shape[2] < w:
        raise ValueError(
            "latent_paged_attention: rows must be the stored cache "
            "[S, L, W' >= W] = [%d, L, >= %d], got %s"
            % (s_, w, rows.shape))
    l_, wr = rows.shape[1], rows.shape[2]
    r = int(v_width)
    if not 0 < r <= w:
        raise ValueError("latent_paged_attention: v_width=%d is not in "
                         "(0, %d]" % (r, w))
    if block_k is None:
        block_k = default_paged_block_k(
            l_, wr * jnp.dtype(rows.dtype).itemsize)
    if l_ % block_k:
        raise ValueError(
            "latent_paged_attention: block_k=%d must divide the cache "
            "length %d" % (block_k, l_))
    nb = l_ // block_k
    n = c * h
    i32 = jnp.int32
    pos = jnp.asarray(pos, i32)
    lens = pos + c if lens is None else jnp.asarray(lens, i32)
    order, nkb, kslot, kblk = _paged_visit(lens, l_, block_k)

    def qmap(i, j, order, nkb, kslot, kblk, pos):
        return (order[i], 0, 0)

    def cmap(i, j, order, nkb, kslot, kblk, pos):
        live = nkb[i] > 0
        return (kslot[i],
                jnp.where(live, jnp.minimum(j, nkb[i] - 1), kblk[i]), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s_, nb),
        in_specs=[pl.BlockSpec((1, n, w), qmap),
                  pl.BlockSpec((1, block_k, wr), cmap)],
        out_specs=pl.BlockSpec((1, n, r), qmap),
        scratch_shapes=[
            pltpu.VMEM((n, r), jnp.float32),       # acc
            pltpu.VMEM((n, 1), jnp.float32),       # l
            pltpu.VMEM((n, 1), jnp.float32),       # m
        ],
    )
    lanes = _round_up(wr, 128)
    need = 3 * block_k * lanes * jnp.dtype(rows.dtype).itemsize \
        + 8 * n * (lanes + block_k) * 4
    out = _pallas_call(
        functools.partial(_latent_attn_kernel, block_k=block_k, chunk=c,
                          heads=h, n_blocks=nb, scale=float(scale),
                          v_width=r, width=w),
        order, nkb, kslot, kblk, pos, q.reshape(s_, n, w), rows,
        out_shape=jax.ShapeDtypeStruct((s_, n, r), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(max(2 * need, 32 << 20),
                                     100 << 20))),
        interpret=interpret)
    return out.reshape(s_, c, h, r)


# -- the expanded latent read of a prefill piece: one block a call --------
#
# A prefill piece of C queries at one position reads the latent rows so
# far a block at a time (``Decoder._latent_attn_blocks``): each block is
# up-projected to per-head keys ``k_n`` and values and to the rotary part
# ``k_r`` the heads share, then scored against the piece. This kernel is
# one block's scores, causal mask, online-softmax update and values
# product, the scores in fast memory; the running max, sum and
# accumulator are float32 operands aliased to its results, carried by the
# caller from block to block. Queries lie on the lanes: a key sub-tile's
# scores are [keys, queries], so a query's max and sum are a ROW (a
# reduction over sublanes), stored lane-dense as [B, H, 1, C], and the
# accumulator is [B, H, Dv, C] against values that come transposed,
# [B, H Dv, L]. A head's keys and values are a column block of lanes of
# the up-projection's output as it is; nothing is relaid per block.

def _latent_prefill_kernel(at_ref, qn_ref, qr_ref, kn_ref, kr_ref, vt_ref,
                           m_ref, l_ref, acc_ref, mo_ref, lo_ref, ao_ref, *,
                           block_q, block_k, rows, scale):
    i = pl.program_id(2)
    f32 = jnp.float32
    neg_big = jnp.float32(-1e30)
    prec = lax.Precision.HIGHEST if qn_ref.dtype == jnp.float32 else None
    nt = (((1,), (1,)), ((), ()))
    q0 = at_ref[0] + i * jnp.int32(block_q)    # the tile's first query
    mo_ref[...] = m_ref[...]
    lo_ref[...] = l_ref[...]
    ao_ref[...] = acc_ref[...]

    def update(sl, k0, masked):
        s = lax.dot_general(kn_ref[0, sl, :], qn_ref[0, 0], nt,
                            precision=prec, preferred_element_type=f32)
        s = (s + lax.dot_general(kr_ref[0, sl, :], qr_ref[0, 0], nt,
                                 precision=prec,
                                 preferred_element_type=f32)) * scale
        if masked:
            ok = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                <= q0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(ok, s, neg_big)
        m = mo_ref[0, 0]                                    # [1, tq]
        new_m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        pe = jnp.exp(s - new_m)
        if masked:
            pe = jnp.where(ok, pe, 0.0)
        corr = jnp.exp(m - new_m)
        lo_ref[0, 0] = lo_ref[0, 0] * corr \
            + jnp.sum(pe, axis=0, keepdims=True)
        ao_ref[0, 0] = ao_ref[0, 0] * corr + jnp.dot(
            vt_ref[0, :, sl], pe.astype(vt_ref.dtype), precision=prec,
            preferred_element_type=f32)                     # [Dv, tq]
        mo_ref[0, 0] = new_m

    for t in range(rows // block_k):
        k0 = at_ref[1] + jnp.int32(t * block_k)
        sl = slice(t * block_k, (t + 1) * block_k)
        # every key at or before the tile's first query: nothing masked
        whole = k0 + jnp.int32(block_k - 1) <= q0
        # a key sub-tile wholly after the tile's last query adds nothing
        some = k0 < q0 + jnp.int32(block_q)
        pl.when(whole)(functools.partial(update, sl, k0, False))
        pl.when(some & jnp.logical_not(whole))(
            functools.partial(update, sl, k0, True))


def latent_prefill_tiles(c, interpret=None):
    """(padded length, query tile) of a prefill piece of ``c`` queries
    for ``latent_prefill_attention``: on the chip the queries lie on
    the lanes, so the piece is padded to whole tiles of 128 and read
    512 (or 256, 128) at a time; the interpreter takes ``c`` whole."""
    if interpret is None:
        interpret = _use_interpret()
    if interpret:
        return c, c
    cp = _round_up(c, 128)
    return cp, next(t for t in (512, 256, 128) if cp % t == 0)


def latent_prefill_attention(qn, qr, kn, kr, vt, m, l, acc, pos, first, *,
                             scale, block_q=None, block_k=None,
                             interpret=None):
    """One block of latent rows, expanded, against a prefill piece: the
    online-softmax update of the piece's running (m, l, acc).

    qn: [B, H, C, Dn] and qr: [B, H, C, Dr], the piece's queries head-
    major (C padded as ``latent_prefill_tiles`` says); kn: [B, L, H Dn]
    and vt: [B, H Dv, L], the block's per-head keys and values (head h
    owns lanes [h Dn, (h+1) Dn) of ``kn`` and rows [h Dv, (h+1) Dv) of
    ``vt``); kr: [B, L, Dr], the rotary keys all heads share. m, l:
    [B, H, 1, C] float32, acc: [B, H, Dv, C] float32, the running max,
    sum and unnormalized values (aliased to the results). ``pos``: the
    piece's first position; ``first``: the block's first row; a key
    counts for a query at or after it. Scores are float32 from the
    operands' dtype times ``scale``, probabilities are cast to the
    values' dtype for their product. Returns (m, l, acc).

    Grid (batch row, head, query tile); a key sub-tile of ``block_k``
    rows wholly after a tile's last query is skipped. On the chip Dn and
    Dv must be whole lane tiles and C whole query tiles; the
    interpreter takes any shape."""
    if interpret is None:
        interpret = _use_interpret()
    c, rows = qn.shape[2], kn.shape[1]
    if block_q is None:
        block_q = latent_prefill_tiles(c, interpret)[1]
    if block_k is None:
        block_k = next((t for t in (512, 256, 128) if rows % t == 0), rows)
    if c % block_q or rows % block_k:
        raise ValueError(
            "latent_prefill_attention: block_q=%d must divide the %d "
            "queries and block_k=%d the %d rows"
            % (block_q, c, block_k, rows))
    at = jnp.stack([jnp.asarray(pos, jnp.int32),
                    jnp.asarray(first, jnp.int32)])
    return _latent_prefill_call(at, qn, qr, kn, kr, vt, m, l, acc,
                                scale=float(scale), block_q=block_q,
                                block_k=block_k, interpret=interpret)


# one trace and one lowering of the kernel for every latent layer of a
# program: the layers' calls are the same shapes
@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "interpret"))
def _latent_prefill_call(at, qn, qr, kn, kr, vt, m, l, acc, *, scale,
                         block_q, block_k, interpret):
    b, h, c, dn = qn.shape
    dr = qr.shape[-1]
    rows = kn.shape[1]
    dv = vt.shape[1] // h

    def tile(b_, h_, i, at):
        return (b_, h_, 0, i)

    stat = pl.BlockSpec((1, 1, 1, block_q), tile)
    accs = pl.BlockSpec((1, 1, dv, block_q), tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, c // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dn),
                         lambda b_, h_, i, at: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, dr),
                         lambda b_, h_, i, at: (b_, h_, i, 0)),
            pl.BlockSpec((1, rows, dn), lambda b_, h_, i, at: (b_, 0, h_)),
            pl.BlockSpec((1, rows, dr), lambda b_, h_, i, at: (b_, 0, 0)),
            pl.BlockSpec((1, dv, rows), lambda b_, h_, i, at: (b_, h_, 0)),
            stat, stat, accs],
        out_specs=[stat, stat, accs],
    )
    f32 = jnp.float32
    lanes = _round_up(block_q, 128)
    itm = jnp.dtype(kn.dtype).itemsize
    need = 2 * rows * (_round_up(dn, 128) + 128 + _round_up(dv, 8)) * itm \
        + 6 * block_k * lanes * 4 + 8 * (dv + 16) * lanes * 4
    return _pallas_call(
        functools.partial(_latent_prefill_kernel, block_q=block_q,
                          block_k=block_k, rows=rows, scale=scale),
        at, qn, qr, kn, kr, vt, m, l, acc,
        out_shape=[jax.ShapeDtypeStruct(m.shape, f32),
                   jax.ShapeDtypeStruct(l.shape, f32),
                   jax.ShapeDtypeStruct(acc.shape, f32)],
        grid_spec=grid_spec,
        input_output_aliases={6: 0, 7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=int(min(max(2 * need, 32 << 20), 100 << 20))),
        interpret=interpret)


# -- the recurrent state's decode step: live slots only, in place ---------
#
# A Gated DeltaNet layer keeps per slot a float32 matrix state [Hv, Dk, Dv]
# (2 MB at 32 heads of 128 x 128) and a decode step advances it by one
# position (``ops.attention.gdn_step``, the definition). The walk is the
# bounded reads' (``_paged_visit``: live slots first, every step of a dead
# slot parked on the block just visited, its body gated off), with blocks
# of heads where they have blocks of rows; what differs is that the block
# is an ALIASED operand, fetched once, advanced in fast memory and written
# back where it lay. A dead slot's state is neither read nor written. A
# Mamba-2 layer's state [H, P, N] (2 MB at 64 heads of 64 x 128) takes the
# same walk with the SSD update in the body (``ssd_state_step``).

# a block of heads' state in flight holds at most this many bytes (two in
# and two out are in flight: 8 MB at the cap)
_STATE_BLOCK_BYTES = 2 << 20


def _gdn_state_kernel(order_ref, nkb_ref, kslot_ref, kblk_ref, fresh_ref,
                      kq_ref, vbg_ref, s_ref, o_ref, so_ref, *, heads):
    """One (slot visit, block of heads) grid cell. ``kq_ref`` [1, 2, hb,
    Dk] holds the block's k and q rows, ``vbg_ref`` [1, 3, hb, Dv] its v
    rows and ``beta`` and ``g`` spread over Dv; ``s_ref`` / ``so_ref``
    [1, hb, Dk, Dv] are the same block of the state, before and after.
    Per head, with Dk on the sublanes and Dv on the lanes: k and q are
    wanted as COLUMNS (one transpose of the block's rows gives them all),
    everything else as rows. Float32 on the vector unit throughout: the
    sums over Dk are elementwise products added up, exact products."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    f32 = jnp.float32
    live = j < nkb_ref[i]

    @pl.when(live)
    def _advance():
        dk = kq_ref.shape[-1]
        zero = fresh_ref[order_ref[i]] != 0
        cols = kq_ref[0].reshape(2 * heads, dk).T          # [Dk, 2 hb]
        vbg = vbg_ref[0]
        decay = jnp.exp(vbg[2])                             # [hb, Dv]
        for h in range(heads):
            kc = cols[:, h:h + 1]                           # [Dk, 1]
            qc = cols[:, heads + h:heads + h + 1]
            sd = jnp.where(zero, 0.0, s_ref[0, h] * decay[h:h + 1])
            ks = jnp.sum(sd * kc, axis=0, keepdims=True)    # [1, Dv]
            qs = jnp.sum(sd * qc, axis=0, keepdims=True)
            d = vbg[1, h:h + 1] * (vbg[0, h:h + 1] - ks)
            o_ref[0, h:h + 1] = qs + d * jnp.sum(kc * qc, axis=0,
                                                 keepdims=True)
            so_ref[0, h] = sd + kc * d

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

        # live slots come first, so no slot is live at all: every step
        # parks on the first block, which is written back once, at the
        # end, and must hold what it held
        @pl.when(i == 0)
        def _keep():
            so_ref[...] = s_ref[...]


def default_state_block_h(heads, dk, dv):
    """Heads of a slot's state per block of ``gdn_state_step``: the
    most that divide ``heads``, are whole sublane tiles (or all of
    them) and keep a float32 block within 2 MB."""
    fits = [h for h in range(heads, 0, -1)
            if heads % h == 0 and (h % 8 == 0 or h == heads)]
    for h in fits:
        if h * dk * dv * 4 <= _STATE_BLOCK_BYTES:
            return h
    return fits[-1]


def gdn_state_step(state, q, k, v, beta, g, lens, fresh, *, block_h=None,
                   interpret=None):
    """One decode position of the gated delta rule for the slots that
    hold a request, the state advanced where it lies.

    state: [S, Hv, Dk, Dv] float32, ALIASED to the first result (donate
    it, or the caller's copy is XLA's). q, k: [S, Hv, Dk]; v: [S, Hv,
    Dv]; beta, g: [S, Hv]; all float32. lens: [S] int32, 0 for a slot
    that holds no request: its state is neither read nor written and its
    output is zeros. fresh: [S] bool, a live slot that starts from the
    ZERO state whatever it held (a dead slot's flag is not read). Returns (the state, o [S, Hv, Dv]),
    ``ops.attention.gdn_step``'s for the live slots up to the order of
    its sums.

    Grid (slot visit, block of heads) under a ``PrefetchScalarGridSpec``
    with ``paged_attention``'s visit order: a live slot's blocks are
    fetched once each and written back to the same place; every step
    of a dead slot stays on the block visited last (no copy in, none
    out). With no live slot at all that block is copied through. On
    the chip Dk and Dv must be whole lane tiles (128); the interpreter
    takes any shape."""
    if interpret is None:
        interpret = _use_interpret()
    s_, hv, dk, dv = state.shape
    f32, i32 = jnp.float32, jnp.int32
    if state.dtype != f32:
        raise ValueError("gdn_state_step: the state is float32, got %s"
                         % state.dtype)
    hb = default_state_block_h(hv, dk, dv) if block_h is None \
        else int(block_h)
    if hv % hb:
        raise ValueError("gdn_state_step: block_h=%d must divide the %d "
                         "heads" % (hb, hv))
    nb = hv // hb
    live = jnp.asarray(lens, i32) > 0
    order, nkb, kslot, kblk = _paged_visit(live.astype(i32) * nb, nb, 1)
    kq = jnp.stack([k, q], axis=1).astype(f32)             # [S, 2, Hv, Dk]
    wide = (s_, hv, dv)
    vbg = jnp.stack([v.astype(f32),
                     jnp.broadcast_to(beta.astype(f32)[..., None], wide),
                     jnp.broadcast_to(g.astype(f32)[..., None], wide)],
                    axis=1)                                # [S, 3, Hv, Dv]

    def blk(i, j, order, nkb, kslot, kblk, fresh):
        return jnp.where(nkb[i] > 0, j, kblk[i])

    def smap(i, j, *pre):
        return (pre[2][i], blk(i, j, *pre), 0, 0)

    def rmap(i, j, *pre):
        return (pre[2][i], 0, blk(i, j, *pre), 0)

    def omap(i, j, *pre):
        return (pre[0][i], j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s_, nb),
        in_specs=[pl.BlockSpec((1, 2, hb, dk), rmap),
                  pl.BlockSpec((1, 3, hb, dv), rmap),
                  pl.BlockSpec((1, hb, dk, dv), smap)],
        out_specs=[pl.BlockSpec((1, hb, dv), omap),
                   pl.BlockSpec((1, hb, dk, dv), smap)],
    )
    block_bytes = hb * dk * dv * 4
    o, new = _pallas_call(
        functools.partial(_gdn_state_kernel, heads=hb),
        order, nkb, kslot, kblk, fresh.astype(i32), kq, vbg, state,
        out_shape=[jax.ShapeDtypeStruct((s_, hv, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        grid_spec=grid_spec,
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(max(8 * block_bytes, 32 << 20),
                                     100 << 20))),
        interpret=interpret)
    return new, o


def _ssd_state_kernel(order_ref, nkb_ref, kslot_ref, kblk_ref, fresh_ref,
                      x_ref, dec_ref, bc_ref, s_ref, o_ref, so_ref, *,
                      heads, pack):
    """One (slot visit, block of heads) grid cell of the SSD update.
    ``x_ref`` [1, hb, P] holds the block's ``dt x`` rows, ``dec_ref``
    [1, hb, N] each head's decay ``exp(dt a)`` spread over N, ``bc_ref``
    [1, 2, N] the slot's B and C rows (one group, every head's; with
    groups [1, 1, 2, N], the rows of the group the block lies in);
    ``s_ref`` / ``so_ref`` [1, hb, P, N] are the same block of the
    state, before and after, P on the sublanes and N on the lanes;
    ``o_ref`` [1, hb / pack, pack P] is the block's ``y``, ``pack``
    heads to a row. Per head ``x`` is wanted as a COLUMN (one transpose
    of the block's rows gives them all) and B as a row. The read
    against C is made for ``pack`` heads at once: their products with C
    [pack P, N] are turned, so that the sum over N runs down the
    sublanes (vector adds) and lands lane-dense in one row of ``y``; a
    sum over the lanes would end every head in cross-lane reductions. A
    slot that starts from zeros takes a branch of its own, which reads
    none of its stale state. Float32 on the vector unit throughout:
    exact products, the sum over N added up."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    live = j < nkb_ref[i]
    fresh = fresh_ref[order_ref[i]] != 0

    def advance(zero):
        cols = x_ref[0].T                                   # [P, hb]
        dec = dec_ref[0]                                    # [hb, N]
        if len(bc_ref.shape) == 3:
            brow = bc_ref[0, 0:1]                           # [1, N]
            crow = bc_ref[0, 1:2]
        else:
            brow = bc_ref[0, 0, 0:1]
            crow = bc_ref[0, 0, 1:2]
        for r in range(heads // pack):
            read = []
            for h in range(r * pack, (r + 1) * pack):
                sd = cols[:, h:h + 1] * brow
                if not zero:
                    sd = s_ref[0, h] * dec[h:h + 1] + sd
                so_ref[0, h] = sd
                read.append(sd * crow)
            o_ref[0, r:r + 1] = jnp.sum(
                jnp.concatenate(read, axis=0).T, axis=0, keepdims=True)

    @pl.when(live & fresh)
    def _start():
        advance(True)

    @pl.when(live & jnp.logical_not(fresh))
    def _advance():
        advance(False)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

        # as in ``_gdn_state_kernel``: with no slot live every step parks
        # on the first block, which must be written back as it was
        @pl.when(i == 0)
        def _keep():
            so_ref[...] = s_ref[...]


def ssd_state_step(state, x, b, c, dt, a, lens, fresh, *, block_h=None,
                   interpret=None):
    """One decode position of the Mamba-2 (SSD) recurrence for the slots
    that hold a request, the state advanced where it lies.

    state: [S, H, P, N] float32, ALIASED to the first result (donate it,
    or the caller's copy is XLA's). x: [S, H, P]; b, c: [S, N] (one
    group, every head's) or [S, G, N] (group g's serve heads
    [g H/G, (g+1) H/G), and a block of heads lies within one group); dt:
    [S, H]; a: [H] (negative); all float32.
    lens: [S] int32, 0 for a slot that holds no request: its state is
    neither read nor written and its output is zeros. fresh: [S] bool, a
    live slot that starts from the ZERO state whatever it held. Returns
    (the state, y [S, H, P]), ``ops.attention.ssd_step``'s for the live
    slots up to the order of its sums (the ``D`` skip is the caller's).

    ``gdn_state_step``'s walk: grid (slot visit, block of heads) under a
    ``PrefetchScalarGridSpec`` with ``paged_attention``'s visit order
    (``_paged_visit``), a live slot's blocks fetched once each and
    written back to the same place, every step of a dead slot parked on
    the block visited last. The kernel writes ``y`` ``pack`` heads to a
    row of ``pack P`` lanes (the most heads of the block whose rows fit
    128 lanes: two at P = 64), turned back to [S, H, P] here. On the
    chip N must be whole lane tiles (128) and P whole sublane tiles; the
    interpreter takes any shape."""
    if interpret is None:
        interpret = _use_interpret()
    s_, h, pdim, n = state.shape
    f32, i32 = jnp.float32, jnp.int32
    if state.dtype != f32:
        raise ValueError("ssd_state_step: the state is float32, got %s"
                         % state.dtype)
    groups = 1 if b.ndim == 2 else b.shape[1]
    per = h // groups                          # heads of a group
    hb = default_state_block_h(per, pdim, n) if block_h is None \
        else int(block_h)
    if h % groups or per % hb:
        raise ValueError("ssd_state_step: block_h=%d must divide the %d "
                         "heads of a group" % (hb, per))
    nb = h // hb
    pack = max(k for k in range(1, hb + 1)
               if hb % k == 0 and (k == 1 or k * pdim <= 128))
    live = jnp.asarray(lens, i32) > 0
    order, nkb, kslot, kblk = _paged_visit(live.astype(i32) * nb, nb, 1)
    dt = dt.astype(f32)
    xdt = dt[..., None] * x.astype(f32)                    # [S, H, P]
    dec = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None],
                           (s_, h, n))                     # [S, H, N]
    bc = jnp.stack([b, c], axis=b.ndim - 1).astype(f32)    # [S, (G,) 2, N]
    new, y = _ssd_state_call(order, nkb, kslot, kblk, fresh.astype(i32),
                             xdt, dec, bc, state, hb=hb, pack=pack,
                             interpret=interpret)
    return new, y.reshape(s_, h, pdim)


# one trace and one lowering of the kernel for every Mamba-2 layer of a
# program: the layers' calls are the same shapes
@functools.partial(jax.jit, static_argnames=("hb", "pack", "interpret"))
def _ssd_state_call(order, nkb, kslot, kblk, fresh, xdt, dec, bc, state, *,
                    hb, pack, interpret):
    s_, h, pdim, n = state.shape
    nb = h // hb
    f32 = jnp.float32

    def blk(i, j, order, nkb, kslot, kblk, fresh):
        return jnp.where(nkb[i] > 0, j, kblk[i])

    def smap(i, j, *pre):
        return (pre[2][i], blk(i, j, *pre), 0, 0)

    def rmap(i, j, *pre):
        return (pre[2][i], blk(i, j, *pre), 0)

    def bmap(i, j, *pre):
        return (pre[2][i], 0, 0)

    def gmap(i, j, *pre):                # the group the block lies in
        return (pre[2][i], blk(i, j, *pre) // (nb // bc.shape[1]), 0, 0)

    def omap(i, j, *pre):
        return (pre[0][i], j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s_, nb),
        in_specs=[pl.BlockSpec((1, hb, pdim), rmap),
                  pl.BlockSpec((1, hb, n), rmap),
                  pl.BlockSpec((1, 2, n), bmap) if bc.ndim == 3
                  else pl.BlockSpec((1, 1, 2, n), gmap),
                  pl.BlockSpec((1, hb, pdim, n), smap)],
        out_specs=[pl.BlockSpec((1, hb // pack, pack * pdim), omap),
                   pl.BlockSpec((1, hb, pdim, n), smap)],
    )
    block_bytes = hb * pdim * n * 4
    y, new = _pallas_call(
        functools.partial(_ssd_state_kernel, heads=hb, pack=pack),
        order, nkb, kslot, kblk, fresh, xdt, dec, bc, state,
        out_shape=[jax.ShapeDtypeStruct((s_, h // pack, pack * pdim), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        grid_spec=grid_spec,
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(max(8 * block_bytes, 32 << 20),
                                     100 << 20))),
        interpret=interpret)
    return new, y


# -- fused quantized matmuls (ISSUE 17) -------------------------------

def _unpack4_halves(u):
    """Unpack a [rows, E/2] uint8 nibble-packed block to two f32
    [rows, E/2] planes: ``lo`` = the EVEN elements (low nibbles),
    ``hi`` = the ODD elements (high nibbles), sign-extended two's
    complement — the in-VMEM mirror of serving.quant.unpack_int4.
    Widened to int32 before the shift: Mosaic has no 8-bit vector
    shifts (``failed to legalize 'arith.shrui'`` on i8)."""
    u = u.astype(jnp.int32)

    def signed(v):
        return jnp.where(v >= 8, v - 16, v).astype(jnp.float32)

    return signed(u & 0xF), signed((u >> 4) & 0xF)


_NT = (((1,), (1,)), ((), ()))      # x [M, E] . w [F, E]^T


def _quant_mm8_kernel(x_ref, w_ref, s_ref, o_ref):
    acc = lax.dot_general(x_ref[...], w_ref[...].astype(jnp.float32),
                          _NT, preferred_element_type=jnp.float32)
    o_ref[...] = (acc * jnp.transpose(s_ref[...])).astype(o_ref.dtype)


def _quant_mm4_kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref, *, group):
    # x arrives de-interleaved (even / odd contraction elements), so
    # the packed tile is contracted as two nibble planes and never
    # re-interleaved on the lane axis; each plane's group is group/2
    # packed columns wide
    lo, hi = _unpack4_halves(w_ref[...])
    s = jnp.repeat(s_ref[...], group // 2, axis=-1)
    acc = lax.dot_general(xe_ref[...], lo * s, _NT,
                          preferred_element_type=jnp.float32) \
        + lax.dot_general(xo_ref[...], hi * s, _NT,
                          preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def quant_matmul(x, q, scale, *, bits=8, group=None, block_f=None,
                 out_dtype=None, interpret=None):
    """``x [M, E] @ dequant(q) [F, E]^T -> [M, F]``: the Pallas
    scale-fused matmul for quantized serving weights.

    The grid walks OUTPUT-CHANNEL blocks only — each step streams one
    ``[block_f, E]`` quantized tile into VMEM, dequantizes it there
    (int8: cast, scale folded into the product after the dot; int4:
    unpack the two nibble planes + per-group contraction scales
    before the dot) and contracts the full E axis. Blocking over
    output channels is a PARTITION of independent dots, never a
    reassociation, so the result does not depend on ``block_f``.
    Against ``serving.quant.scale_fused_matmul``'s ``fori_loop`` it
    agrees to f32 rounding, not bitwise: int8 runs the same
    contraction but XLA picks a dot's accumulation order by fusion
    context, and int4 sums an even-element and an odd-element
    product where the fori form runs one dot over the interleaved
    axis (tests/test_pallas_quant.py states the tolerance; the
    engine's token-level gauntlet is what ``matmul_impl="pallas"``
    is held to). The compiled program reads the stored
    int8/packed-int4 stream plus one tile of float staging.

    ``q``: int8 ``[F, E]`` (``bits=8``, ``scale`` f32 ``[F]``) or
    nibble-packed uint8 ``[F, E//2]`` (``bits=4``, ``scale`` f32
    ``[F, E//group]``, ``group`` even). ``block_f`` must divide F and
    be a multiple of 128, or be F itself — the output block's lane
    axis on the chip; the interpreter is held to the same rule, so
    the tests run the partition the chip runs (callers pass the
    ``MXNET_QUANT_CHUNK``-resolved chunk so both impls stage
    identically). Default: the larger of (256, 128) dividing F, else
    the whole weight as one block — past a few MB that no longer fits
    VMEM and the compiler says so. On CPU the kernel runs under the
    Pallas interpreter (tests)."""
    if interpret is None:
        interpret = _use_interpret()
    m, e = x.shape
    f = q.shape[0]
    ew = q.shape[1]
    if bits == 4:
        if group is None or group % 2 or (2 * ew) % group:
            raise ValueError(
                "quant_matmul: bits=4 needs the per-group scale width "
                "(an even divisor of E=%d), got group=%r"
                % (2 * ew, group))
        s2 = scale
    else:
        s2 = scale.reshape(f, 1)
    if block_f is None:
        block_f = next((r for r in (256, 128) if f % r == 0), f)
    block_f = min(block_f, f)
    if f % block_f or (block_f != f and block_f % 128):
        raise ValueError(
            "quant_matmul: block_f=%d must divide the output-channel "
            "count %d (the grid partitions whole blocks) and be a "
            "multiple of 128 or the whole count (the block's lane "
            "axis on the chip)" % (block_f, f))
    if bits == 4:
        kernel = functools.partial(_quant_mm4_kernel, group=group)
        xs = [x[:, 0::2], x[:, 1::2]]
    else:
        kernel = _quant_mm8_kernel
        xs = [x]
    sw = s2.shape[1]
    bf = block_f
    # x and the output's row axis ride whole (a block equal to the
    # array's own dimension is legal at any row count)
    return _pallas_call(
        kernel, *xs, q, s2,
        out_shape=jax.ShapeDtypeStruct(
            (m, f), jnp.dtype(out_dtype) if out_dtype else x.dtype),
        grid=(int(f // bf),),
        in_specs=[pl.BlockSpec((m, ew), lambda i: (0, 0))
                  for _ in xs] + [
            pl.BlockSpec((bf, ew), lambda i: (i, 0)),
            pl.BlockSpec((bf, sw), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((m, bf), lambda i: (0, i)),
        interpret=interpret)


# -- grouped matmul: the routed experts' product ------------------------

def _grouped_mm_kernel(block_e_ref, used_ref, x_ref, w_ref, o_ref):
    """One (row block, output tile) of ``grouped_matmul``: the block's
    rows against its own expert's tile, float32 sums. A block past the
    used ones holds no row: it writes zeros and multiplies nothing."""
    i = pl.program_id(1)

    @pl.when(i < used_ref[0])
    def _():
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _grouped_mm_xla(x, w, block_e, used, rows):
    """``grouped_matmul`` in plain XLA: each block against a gathered
    copy of its expert's matrix. The operations are the kernel's; the
    gather is ``blocks`` copies of a matrix, which is why the chip runs
    the kernel."""
    nb = x.shape[0] // rows
    xb = x.reshape(nb, rows, x.shape[1])
    y = jnp.einsum("bmk,bnk->bmn", xb, w[block_e],
                   preferred_element_type=jnp.float32)
    live = (jnp.arange(nb, dtype=jnp.int32) < used)[:, None, None]
    return jnp.where(live, y, 0).astype(x.dtype).reshape(
        nb * rows, w.shape[1])


def _grouped_mm_pallas(x, w, block_e, used, rows, block_n, interpret):
    m, kdim = x.shape
    nx, n, _ = w.shape
    nb = m // rows
    if block_n is None:
        # a [block_n, K] tile of the expert's matrix, twice (the
        # pipeline's two buffers), inside a quarter of the 16 MB of
        # fast memory a kernel may use
        block_n = next((c for c in (1024, 512, 256, 128)
                        if n % c == 0
                        and 2 * c * kdim * w.dtype.itemsize <= 4 << 20),
                       n)
    if n % block_n:
        raise ValueError("grouped_matmul: block_n=%d must divide the "
                         "output width %d" % (block_n, n))
    # output tiles outermost, row blocks innermost: the blocks of one
    # expert follow each other, so its tile stays where it is and an
    # expert's matrix is copied from HBM ONCE however many blocks it
    # has; a block past the used ones re-reads the last used block and
    # its expert's tile, which is no new copy either
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // block_n, nb),
        in_specs=[
            pl.BlockSpec((rows, kdim), lambda j, i, be, u: (
                jnp.minimum(i, jnp.maximum(u[0] - 1, 0)), 0)),
            pl.BlockSpec((1, block_n, kdim),
                         lambda j, i, be, u: (be[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block_n), lambda j, i, be, u: (i, j)),
    )
    return _pallas_call(
        _grouped_mm_kernel, block_e.astype(jnp.int32),
        jnp.reshape(used, (1,)).astype(jnp.int32), x, w,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=grid_spec, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped_mm_chip(x, w, block_e, used, rows, block_n):
    return _grouped_mm_pallas(x, w, block_e, used, rows, block_n, False)


def _grouped_mm_chip_fwd(x, w, block_e, used, rows, block_n):
    return (_grouped_mm_pallas(x, w, block_e, used, rows, block_n, False),
            (x, w, block_e, used))


def _grouped_mm_chip_bwd(rows, block_n, res, g):
    # the kernel has no backward of its own: the plain form's is exact
    x, w, block_e, used = res
    _, vjp = jax.vjp(lambda a, b: _grouped_mm_xla(a, b, block_e, used,
                                                  rows), x, w)
    return vjp(g) + (None, None)


_grouped_mm_chip.defvjp(_grouped_mm_chip_fwd, _grouped_mm_chip_bwd)


def grouped_matmul(x, w, block_e, used, rows, *, block_n=None,
                   interpret=None):
    """Rows in blocks, each block against ITS expert's matrix: the
    product of the routed experts (``ops.attention._routed_experts``).

    ``x`` [blocks * rows, K]: the token rows sorted by expert, every
    expert's group padded to whole blocks of ``rows``. ``w`` [X, N, K]:
    the experts' matrices, ``out x in`` like every weight here.
    ``block_e`` [blocks] int32: each block's expert. ``used`` (int32
    scalar): how many leading blocks hold rows; the others come back
    zero. Returns [blocks * rows, N] in ``x``'s dtype, float32 sums.

    On the TPU this is a Pallas kernel over (block, output tile) with
    ``block_e`` prefetched as scalars: the expert's tile is addressed
    through it, so only matrices of experts that have a block are ever
    copied from HBM, each once, and the operations are ``blocks * rows
    * N * K`` whatever X is. Elsewhere (the CPU tests) the same
    blocks run as one plain batched product against gathered matrices,
    so XLA's cost analysis counts what the kernel computes;
    ``interpret=True`` runs the kernel itself under the interpreter.
    Differentiable: the kernel's backward is the plain form's."""
    if x.shape[0] % rows:
        raise ValueError("grouped_matmul: %d rows are not whole blocks "
                         "of %d" % (x.shape[0], rows))
    if interpret is None and _use_interpret():
        return _grouped_mm_xla(x, w, block_e, used, rows)
    if interpret:
        return _grouped_mm_pallas(x, w, block_e, used, rows, block_n,
                                  True)
    return _grouped_mm_chip(x, w, block_e, used, rows, block_n)
