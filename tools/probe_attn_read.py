"""Stand-alone probe of the decode step's attention read, on the chip.

Times ONE layer's read of a step at serve-chat's shapes (bf16 K/V
[16, 1024, 2048], 32 heads of 64, a few live slots of 600-1,000 rows,
the other slots dead at stale positions) in the two forms the tree
has, and checks the bounded one against the dense one on the same rows
(the forms that lost, bounded by pos alone, an XLA while over live
slots and the old kernel, are answered in PERF.md section 6, PR 29):

  dense        Decoder._lane_attn over the whole pool (every row read
               and masked)
  bounded      ops.pallas_kernels.paged_attention with lens = live ?
               pos + 1 : 0, at each block size given

    python tools/probe_attn_read.py [--live 5] [--blocks 256,512]
        [--layers 4] [--shape 16,1024,32,64] [--kv 32] [--fill 0.6,1.0]
    python tools/probe_attn_read.py --shape 32,2048,8,128 --kv 2
        --live 12 --blocks 256,512,1024,2048

The second is CCAttention's read in ``zaya1-8b.serve-reason``: 8 query
heads over 2 kv heads of 128 (``--kv``: grouped-query heads; default
H), rows of 512 B where serve-chat's hold 4 KB.

Prints one JSON line per form: ms a layer and step, the rows read,
and the largest gap to the dense read's output.
"""
import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np                                        # noqa: E402
import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402
from jax import lax                                       # noqa: E402

import mxnet_tpu  # noqa: F401,E402  (x64 on, as the program runs)
from mxnet_tpu.ops import pallas_kernels as pk            # noqa: E402
from mxnet_tpu.parallel.decode import Decoder             # noqa: E402

STEPS = 8


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", type=int, default=5)
    ap.add_argument("--blocks", default="256,512")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shape", default="16,1024,32,64",
                    help="S,L,H,D (smaller for a rehearsal off the chip)")
    ap.add_argument("--fill", default="0.6,1.0",
                    help="a slot's position is drawn between these "
                         "shares of L")
    ap.add_argument("--kv", type=int, default=None,
                    help="kv heads the H query heads share (default H)")
    args = ap.parse_args()
    S, L, H, D = (int(x) for x in args.shape.split(","))
    KV = args.kv or H
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}))
    rng = np.random.RandomState(args.seed)
    dt = jnp.dtype(args.dtype)
    w = KV * D
    # every slot has held a request: stale positions everywhere, a few
    # slots live
    lo, hi = (float(x) for x in args.fill.split(","))
    pos = rng.randint(int(L * lo), int(L * hi) - 24, (S,)).astype(np.int32)
    live = np.zeros((S,), bool)
    live[rng.permutation(S)[:args.live]] = True
    lens = np.where(live, pos + 1, 0).astype(np.int32)
    key = jax.random.PRNGKey(args.seed)
    layers = []
    for i in range(args.layers):
        kk, kv_, key = jax.random.split(key, 3)
        layers.append((jax.random.normal(kk, (S, L, w), dt),
                       jax.random.normal(kv_, (S, L, w), dt)))
    q0 = jax.random.normal(key, (S, 1, H, D), dt)
    posj, lensj = jnp.asarray(pos), jnp.asarray(lens)
    plain = types.SimpleNamespace(_cache_int8=False)

    def dense(q, k, v):
        return Decoder._lane_attn(plain, q, (k, v), posj, KV)

    def bounded(bk):
        def f(q, k, v):
            return pk.paged_attention(q, k, v, posj, kv_heads=KV,
                                      lens=lensj, block_k=bk)
        return f

    forms = [("dense", dense, S * L)]
    for bk in [int(b) for b in args.blocks.split(",")]:
        forms.append(("bounded_%d" % bk, bounded(bk),
                      int(pk.paged_rows_fetched(lens, L, bk))))

    def chain(f):
        # STEPS steps of every layer, each read fed the one before it
        def run(q, layers):
            def step(q, _):
                for k, v in layers:
                    q = (q + f(q, k, v)).astype(q.dtype) * 0.5
                return q, None
            return lax.scan(step, q, None, length=STEPS)[0]
        return jax.jit(run)

    want = np.asarray(jax.jit(dense)(q0, *layers[0]), np.float32)
    for name, f, rows in forms:
        got = np.asarray(jax.jit(f)(q0, *layers[0]), np.float32)
        gap = float(np.max(np.abs(got - want)[live]))
        finite = bool(np.isfinite(got).all())
        run = chain(f)
        run(q0, layers).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = run(q0, layers)
        out.block_until_ready()
        ms = (time.perf_counter() - t0) * 1e3 \
            / (args.iters * STEPS * args.layers)
        print(json.dumps({
            "form": name, "ms_per_layer_step": round(ms, 4),
            "rows_read": rows, "rows_pool": S * L,
            "gb_per_s": round(rows * w * dt.itemsize * 2 / ms / 1e6, 1),
            "max_gap_live": gap, "finite": finite,
            "live": int(live.sum())}), flush=True)


if __name__ == "__main__":
    main()
