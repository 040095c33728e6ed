"""The stored KV-cache layout ([B, rows, Hkv*D], parallel/decode.py) and
the read that consumes it as it is: a short query chunk (decode, the
speculative verify chunk) straight off the stored rows, against a plain
float32 ``jax.numpy`` attention that knows nothing of the layout."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder
from mxnet_tpu.parallel.decode import (fold_heads, head_segments,
                                       unfold_heads)

VOCAB, D, L, B = 17, 8, 24, 2


def _decoder(h, kv, rng, **kw):
    sym = get_transformer_lm(VOCAB, num_layers=1, embed_dim=h * D,
                             num_heads=h, impl="dense", num_kv_heads=kv)
    shapes = {"data": (B, L), "softmax_label": (B, L)}
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: jnp.asarray(rng.uniform(-0.3, 0.3, s).astype(np.float32))
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    return Decoder(sym, params, max_len=L, **kw)


def _reference(q, k, v, pos):
    """float32 attention per head: q [B,C,H,D], k/v [B,L,Hkv,D]; query
    row i sits at ``pos + i`` and sees keys at or before it."""
    with jax.default_matmul_precision("highest"):
        q, k, v = (jnp.asarray(x, jnp.float32) for x in (q, k, v))
        g = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        qpos = pos + jnp.arange(q.shape[1])[:, None]
        s = jnp.where(jnp.arange(k.shape[1])[None, :] <= qpos, s, -jnp.inf)
        return np.asarray(jnp.einsum("bhqk,bkhd->bqhd",
                                     jax.nn.softmax(s, axis=-1), v))


def test_fold_unfold_state_the_layout():
    """Head h's D values sit at lanes [h*D, (h+1)*D): fold and unfold
    are inverses, and the segment matrix maps a lane to its head."""
    x = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.float32).reshape(2, 3, 4, 5)
    rows = fold_heads(x)
    assert rows.shape == (2, 3, 20)
    np.testing.assert_array_equal(rows[..., 5:10], x[:, :, 1])
    np.testing.assert_array_equal(unfold_heads(rows, 4), x)
    seg = np.asarray(head_segments(4, 5, jnp.float32))
    assert seg.shape == (20, 4)
    np.testing.assert_array_equal(seg.sum(0), 5)
    np.testing.assert_array_equal(seg[5:10, 1], 1)
    np.testing.assert_array_equal(np.asarray(rows @ seg),
                                  np.asarray(x.sum(-1)))


@pytest.mark.parametrize("where", ["first", "mid_block", "last"])
@pytest.mark.parametrize("cache", ["float", "int8"])
@pytest.mark.parametrize("c", [1, 5])
@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2)])
def test_lane_read_matches_float32_attention(h, kv, c, cache, where):
    """Write a chunk into a filled cache, read it back through the
    lane-dense read: MHA and GQA, decode and verify width, float and
    int8 cache, at position 0, inside a block and at ``max_len - c``."""
    rng = np.random.RandomState(11)
    dec = _decoder(h, kv, rng,
                   **({"cache_dtype": "int8"} if cache == "int8" else {}))
    pos = {"first": 0, "mid_block": 13, "last": L - c}[where]
    q = rng.randn(B, c, h, D).astype(np.float32)
    k = rng.randn(B, L, kv, D).astype(np.float32)
    v = rng.randn(B, L, kv, D).astype(np.float32)
    # rows [0, pos) as an earlier prefill left them, then this chunk
    # (the rows after it hold junk the mask hides)
    entry = dec._write_cache(dec.init_cache(B)[0], jnp.asarray(k),
                             jnp.asarray(v), 0)
    new_k, new_v = k[:, pos:pos + c] * 1.5, v[:, pos:pos + c] - 0.25
    entry = dec._write_cache(entry, jnp.asarray(new_k),
                             jnp.asarray(new_v), jnp.int32(pos))
    assert entry[0].shape == (B, L, kv * D)
    got = np.asarray(dec._lane_attn(jnp.asarray(q), entry, jnp.int32(pos),
                                    kv))
    k[:, pos:pos + c], v[:, pos:pos + c] = new_k, new_v
    if cache == "int8":
        # the reference reads the SAME quantized rows, dequantized
        # first: the read applies the scales to scores and weights,
        # which is the same arithmetic
        assert entry[0].dtype == jnp.int8 and entry[1].shape == (B, L, kv)
        k = np.asarray(unfold_heads(entry[0], kv) * entry[1][..., None])
        v = np.asarray(unfold_heads(entry[2], kv) * entry[3][..., None])
    np.testing.assert_allclose(got, _reference(q, k, v, pos), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("h,kv", [(4, 4), (4, 1)])
def test_short_and_long_chunks_agree(h, kv):
    """One algorithm, two regimes: the read of a chunk just under the
    switch (straight off the stored rows) and just over it (per head
    off the unfolded rows) give the same attention, through the whole
    cached node, at a traced position."""
    from mxnet_tpu.parallel import decode as D_
    rng = np.random.RandomState(5)
    dec = _decoder(h, kv, rng)
    n = D_._SHORT_CHUNK
    toks = jnp.asarray(rng.randint(0, VOCAB, (B, n + 1)), jnp.int32)
    run = jax.jit(lambda c, p, t: dec._run(dec._params, dec._aux, c, p, t))
    long_logits, _ = run(dec.init_cache(B), jnp.int32(3), toks)
    short_logits, caches = run(dec.init_cache(B), jnp.int32(3), toks[:, :n])
    last, _ = run(caches, jnp.int32(3 + n), toks[:, n:])
    np.testing.assert_allclose(
        np.concatenate([np.asarray(short_logits), np.asarray(last)], 1),
        np.asarray(long_logits), rtol=1e-5, atol=1e-5)


def test_lane_read_rounds_no_earlier_than_the_per_head_read():
    """bfloat16 operands: the lane-dense read keeps products and sums
    in float32 (its scores never pass through bfloat16), so against a
    float32 reference on the same bfloat16 values it departs no more
    than the per-head einsums do."""
    rng = np.random.RandomState(2)
    h = kv = 4
    dec = _decoder(h, kv, rng, compute_dtype="bfloat16")
    pos = L - 1
    q = jnp.asarray(rng.randn(B, 1, h, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, L, kv, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, L, kv, D), jnp.bfloat16)
    entry = dec._write_cache(dec.init_cache(B)[0], k, v, 0)
    assert entry[0].dtype == jnp.bfloat16
    want = _reference(q, k, v, pos)
    lane = np.asarray(dec._lane_attn(q, entry, jnp.int32(pos), kv),
                      np.float32)
    head = np.asarray(dec._head_attn(
        q, *dec._read_cache(entry, q.dtype, kv), jnp.int32(pos)),
        np.float32)
    assert np.abs(lane - want).max() <= np.abs(head - want).max() + 1e-6
    np.testing.assert_allclose(lane, want, atol=2e-2)
