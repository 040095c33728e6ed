"""Long-context attention: blockwise (flash) and ring sequence parallelism.

The reference predates attention; its only long-sequence machinery is
explicit RNN unrolling + bucketing (SURVEY.md §5 "Long-context"). For the
TPU framework long context is first-class: sequences are sharded over the
``sp`` mesh axis and attention runs as a ring — each device holds a query
block, and key/value blocks rotate around the ring via
``lax.ppermute`` (one ICI hop per step) while a numerically-stable
streaming-softmax accumulator (the flash-attention recurrence) folds each
block in. Compute on block t overlaps the transfer of block t+1, so ICI
latency hides behind the MXU matmuls.

All math accumulates in float32 regardless of input dtype (bf16 in,
f32 softmax state) — the standard TPU recipe.

Causal load balance: in a contiguous-layout causal ring, early-position
devices fully mask most arriving blocks, and because every ring hop is
a lockstep collective, per-iteration wall time is set by the slowest
device — the one doing a FULL unmasked block. ``ring_attention`` keeps
that honest contiguous layout (it is the exact-layout drop-in).
``striped_ring_attention`` is the balanced form (striped attention):
tokens are dealt round-robin (device i holds positions {a*n + i}), so
at EVERY hop each device faces a near-triangle mask of the same size —
per-hop FLOPs are ~half a block everywhere instead of one device doing
a full block. The half-block Pallas kernel
(``ops/pallas_kernels.striped_pair_attention``) skips key blocks above
the striped diagonal, so the saving is realized in compute, not just in
the mask; partial (o, lse) results merge via streaming-softmax
logaddexp, and the kernel's custom vjp keeps it trainable.

Per-device FLOP balance (causal, ring size n, local length C, per-hop
block C×C): contiguous ring — device d computes sum over hops of the
unmasked fraction, i.e. between ~n/2 blocks-equivalent for the last
device and ~1/2 for the first, with the LOCKSTEP cost n * max ≈ n full
blocks; striped ring — every device computes ~(n+1)/2 half-ish blocks
and the lockstep cost is ~n/2 full-block-equivalents: a ~2x end-to-end
causal speedup at equal ring size (the striped-attention result).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map

from .shard import P

__all__ = ["blockwise_attention", "ring_attention", "ring_self_attention",
           "striped_ring_attention"]


def _block_update(q, k, v, o, l, m, mask, scale):
    """Fold one K/V block into the streaming-softmax state.

    q: [B,Tq,H,D]  k,v: [B,Tk,H,D]  o: [B,Tq,H,D] f32
    l,m: [B,H,Tq] f32.  mask: [Tq,Tk] bool or None (True = attend).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
    new_m = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows: exp(-inf - -inf) -> use safe max
    safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
    p = jnp.exp(s - safe_m[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None, :, :], p, 0.0)
    correction = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m) - safe_m)
    correction = jnp.where(jnp.isneginf(m), 0.0, correction)
    new_l = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    new_o = o * correction.transpose(0, 2, 1)[..., None] + pv
    return new_o, new_l, new_m


def _finalize(o, l):
    l = jnp.maximum(l, 1e-30)
    return o / l.transpose(0, 2, 1)[..., None]


def blockwise_attention(q, k, v, *, causal=False, block_size=512,
                        scale=None, window=0):
    """Memory-efficient attention on one device: K/V consumed in blocks by
    ``lax.scan`` over the flash recurrence, so peak memory is O(T·block)
    instead of O(T²). Shapes: [B,T,H,D] each; returns [B,T,H,D] in q.dtype.
    ``window``>0 additionally masks keys more than ``window-1`` positions
    behind their query (sliding-window attention; requires ``causal``).
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if window < 0:
        raise ValueError("blockwise_attention: window must be >= 0, "
                         "got %d" % window)
    if window and not causal:
        raise ValueError("blockwise_attention: window>0 requires causal")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    nblk = -(-Tk // block_size)
    pad = nblk * block_size - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, block_size, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block_size, H, D).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(Tq)

    o0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    m0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)

    def body(carry, blk):
        o, l, m, i = carry
        kblk, vblk = blk
        kpos = i * block_size + jnp.arange(block_size)
        mask = kpos[None, :] < Tk  # padding mask
        if causal:
            mask = mask & (qpos[:, None] >= kpos[None, :])
            if window:
                mask = mask & (qpos[:, None] - kpos[None, :] < window)
        else:
            mask = jnp.broadcast_to(mask, (Tq, block_size))
        o, l, m = _block_update(q, kblk, vblk, o, l, m, mask, scale)
        return (o, l, m, i + 1), None

    (o, l, m, _), _ = lax.scan(body, (o0, l0, m0, 0), (kb, vb))
    return _finalize(o, l).astype(q.dtype)


def _ring_attention_local(q, k, v, *, axis_name, causal, scale):
    """Per-shard body (runs inside shard_map over ``axis_name``).

    q,k,v: LOCAL sequence shards [B, T/n, H, D]. K/V rotate the ring;
    streaming softmax folds each arriving block in.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qpos = my * Tq + jnp.arange(Tq)

    o0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    m0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)

    def body(i, carry):
        o, l, m, kcur, vcur = carry
        src = (my - i) % n  # ring position whose K/V block we now hold
        kpos = src * Tk + jnp.arange(Tk)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
        else:
            mask = None
        o, l, m = _block_update(q, kcur, vcur, o, l, m, mask, scale)
        # rotate K/V one hop (overlapped with the next block's compute by
        # XLA's async collective-permute)
        knext = lax.ppermute(kcur, axis_name, perm)
        vnext = lax.ppermute(vcur, axis_name, perm)
        return o, l, m, knext, vnext

    o, l, m, _, _ = lax.fori_loop(0, n, body, (o0, l0, m0, k, v))
    return _finalize(o, l).astype(q.dtype)


def ring_attention(q, k, v, mesh, *, axis_name="sp", causal=False,
                   scale=None, batch_axis=None):
    """Ring attention over the ``axis_name`` mesh axis.

    q,k,v: GLOBAL [B,T,H,D] arrays (T sharded over ``axis_name`` by the
    returned computation). Peak per-device memory is O(T/n · T/n) per block
    pair; total sequence length scales linearly with ring size.
    """
    spec = P(batch_axis, axis_name, None, None)
    fn = functools.partial(_ring_attention_local, axis_name=axis_name,
                           causal=causal, scale=scale)
    mapped = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return mapped(q, k, v)


def _striped_ring_local(q, k, v, *, axis_name, scale, block_q, block_k):
    """Per-shard striped ring body. q,k,v: LOCAL striped shards
    [B, C, H, D] — local row ``a`` is global position ``a*n + my``.
    Each hop runs the half-block Pallas pair kernel and merges the
    (o, lse) partial with streaming softmax."""
    from ..ops.pallas_kernels import striped_pair_attention

    n = lax.psum(1, axis_name)
    if hasattr(n, "aval"):
        raise ValueError("striped ring must run inside shard_map")
    my = lax.axis_index(axis_name)
    B, C, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, C, D)

    qb = to_bh(q)
    o0 = jnp.zeros((B * H, C, D), jnp.float32)
    lse0 = jnp.full((B * H, C, 1), -1e30, jnp.float32)

    def body(i, carry):
        o, lse, kcur, vcur = carry
        src = (my - i) % n  # ring position of this K/V block
        o_i, lse_i = striped_pair_attention(
            qb, to_bh(kcur), to_bh(vcur), my, src, n_stride=n,
            scale=scale, block_q=block_q, block_k=block_k)
        new_lse = jnp.logaddexp(lse, lse_i)
        o = o * jnp.exp(lse - new_lse) + \
            o_i.astype(jnp.float32) * jnp.exp(lse_i - new_lse)
        knext = lax.ppermute(kcur, axis_name, perm)
        vnext = lax.ppermute(vcur, axis_name, perm)
        return o, new_lse, knext, vnext

    o, lse, _, _ = lax.fori_loop(0, n, body, (o0, lse0, k, v))
    out = o.reshape(B, H, C, D).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def striped_ring_attention(q, k, v, mesh, *, axis_name="sp", scale=None,
                           batch_axis=None, block_q=None, block_k=None):
    """Causal ring attention with the STRIPED token layout (striped
    attention): balanced per-hop FLOPs via the half-block Pallas pair
    kernel — see the module docstring for the balance math.

    q,k,v: GLOBAL [B,T,H,D] in NATURAL token order. The wrapper deals
    tokens round-robin onto the ring (one all-to-all-style reshuffle in,
    one out), runs the balanced ring, and returns output in natural
    order. Causal only — striping exists to balance the causal mask.
    """
    n = mesh.shape[axis_name]
    B, T, H, D = q.shape
    if T % n:
        raise ValueError("striped ring: T=%d not divisible by ring "
                         "size %d" % (T, n))
    C = T // n
    # same block heuristic as flash_attention (shared helper); the
    # pair kernel clamps to the local chunk length
    from ..ops.pallas_kernels import default_attn_blocks
    dq, dk = default_attn_blocks(D)
    if block_q is None:
        block_q = dq
    if block_k is None:
        block_k = dk

    def stripe(x):
        # natural [B, T] -> striped [B, T']: chunk j holds {a*n + j}
        return x.reshape(B, C, n, H, D).transpose(0, 2, 1, 3, 4) \
                .reshape(B, T, H, D)

    def unstripe(x):
        return x.reshape(B, n, C, H, D).transpose(0, 2, 1, 3, 4) \
                .reshape(B, T, H, D)

    spec = P(batch_axis, axis_name, None, None)
    fn = functools.partial(_striped_ring_local, axis_name=axis_name,
                           scale=scale, block_q=block_q, block_k=block_k)
    mapped = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return unstripe(mapped(stripe(q), stripe(k), stripe(v)))


def ring_self_attention(x, wq, wk, wv, wo, mesh, *, num_heads,
                        axis_name="sp", causal=True, batch_axis="dp"):
    """Full self-attention block with ring-parallel sequence dim.

    x: [B,T,E] (T sharded on ``axis_name``); wq/wk/wv/wo: [E,E].
    QKV/output projections are position-wise, so they need no
    communication under sequence sharding; only the ring rotates K/V.
    """
    B, T, E = x.shape
    D = E // num_heads
    q = (x @ wq).reshape(B, T, num_heads, D)
    k = (x @ wk).reshape(B, T, num_heads, D)
    v = (x @ wv).reshape(B, T, num_heads, D)
    o = ring_attention(q, k, v, mesh, axis_name=axis_name, causal=causal,
                       batch_axis=batch_axis)
    return o.reshape(B, T, E) @ wo
