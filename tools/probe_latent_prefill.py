"""Stand-alone probe of a prefill piece's expanded latent read, on the
chip.

Times ONE LatentAttention layer's read of a prefill piece at
serve-longctx's shapes (1,024 queries, 32 heads of 128 + 64, values of
128, a latent row of 512 + 64 stored in 640 lanes, 9,216 rows read a
block of 1,024 at a time up to the piece's end) in the two forms the
tree has, at a few positions, and checks the kernel against the einsums
on the chip:

  einsum    Decoder._latent_attn_blocks' own body: per block the
            up-projection, float32 scores, mask, online softmax and the
            values product as XLA's einsums
  kernel    the same loop with a block's scores, softmax and values in
            ops.pallas_kernels.latent_prefill_attention (``--blocks``:
            query tile x key sub-tile, ``auto`` for the defaults)

    python tools/probe_latent_prefill.py [--pos 0,2048,7168]
        [--blocks auto,512x256] [--iters 10]
    python tools/probe_latent_prefill.py --shape 2,40,4,8,8,8,16,64
        --block 16 --blocks auto   (off the chip: the interpreter)

Prints one JSON line per form and position: ms a layer and piece on the
host's clock (the dispatch of a call included), the blocks read, and the
largest gap to the einsums over the output's spread; with ``--profile
DIR`` also the device's time a layer and piece from a trace of three
calls, and its three longest operations.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np                                        # noqa: E402
import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402

import mxnet_tpu  # noqa: F401,E402  (x64 on, as the program runs)
from benchmark import trace as T                          # noqa: E402
from mxnet_tpu.ops import pallas_kernels as pk            # noqa: E402
from mxnet_tpu.parallel.decode import Decoder             # noqa: E402


class _Einsum(Decoder):
    """The read as the einsums do it, whatever the shapes."""
    latent_read_in_kernel = staticmethod(lambda p, c: False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1,1024,32,128,64,128,512,9216",
                    help="B,C,H,Dn,Dr,Dv,R,L")
    ap.add_argument("--block", type=int, default=1024,
                    help="rows a block (Decoder._LATENT_BLOCK)")
    ap.add_argument("--pos", default="0,2048,7168")
    ap.add_argument("--blocks", default="auto,512x256,256x512,1024x256")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", default=None,
                    help="a directory for the traces of the device's time")
    a = ap.parse_args()
    b, c, h, dn, dr, dv, r, total = (int(x) for x in a.shape.split(","))
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    rng = np.random.RandomState(a.seed)
    p = {"num_heads": h, "kv_lora_rank": r, "nope_dim": dn, "rope_dim": dr,
         "v_dim": dv}
    lanes = -(-(r + dr) // 128) * 128
    q = jnp.asarray(rng.randn(b, c, h, dn + dr), dt)
    rows = jnp.asarray(np.pad(rng.randn(b, total, r + dr),
                              [(0, 0), (0, 0), (0, lanes - r - dr)]), dt)
    wukv = jnp.asarray(rng.randn(h * (dn + dv), r) * r ** -0.5, dt)
    scale = float((dn + dr) ** -0.5)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind,
                      "shape": [b, c, h, dn, dr, dv, r, total],
                      "block": a.block}), flush=True)
    Decoder._LATENT_BLOCK = a.block

    def form(cls, blocks):
        kern = pk.latent_prefill_attention
        if blocks != "auto":
            bq, bk = (int(x) for x in blocks.split("x"))
            kern = functools.partial(kern, block_q=bq, block_k=bk)

        def read(q, rows, wukv, pos):
            saved = pk.latent_prefill_attention
            pk.latent_prefill_attention = kern
            try:
                return cls._latent_attn_blocks(cls.__new__(cls), p, q, rows,
                                               wukv, pos, scale)
            finally:
                pk.latent_prefill_attention = saved
        return jax.jit(read)

    forms = [("einsum", form(_Einsum, "auto"))] + [
        ("kernel/%s" % bl, form(Decoder, bl)) for bl in a.blocks.split(",")]
    for pos in (int(x) for x in a.pos.split(",")):
        pos32 = jnp.int32(pos)
        want = None
        for name, fn in forms:
            out = {"form": name, "pos": pos,
                   "blocks": min(-(-(pos + c) // a.block), total // a.block)}
            try:
                o = fn(q, rows, wukv, pos32)
                jax.block_until_ready(o)
            except Exception as e:              # a refused tiling
                out["error"] = str(e).splitlines()[0][:200]
                print(json.dumps(out), flush=True)
                continue
            o = np.asarray(o.astype(jnp.float32))
            if want is None:
                want = o
            out["gap_over_std"] = float(np.abs(o - want).max() / want.std())
            t0 = time.perf_counter()
            for _ in range(a.iters):
                o = fn(q, rows, wukv, pos32)
            jax.block_until_ready(o)
            out["ms_per_layer_piece"] = round(
                (time.perf_counter() - t0) * 1e3 / a.iters, 4)
            if a.profile:
                out.update(device_time(
                    fn, (q, rows, wukv, pos32),
                    os.path.join(a.profile, "%s_%d" % (
                        name.replace("/", "_"), pos))))
            print(json.dumps(out), flush=True)


def device_time(fn, args, where, calls=3):
    """The device's ms a call of ``fn`` over ``calls`` traced calls, and
    its three longest operations (ms a call)."""
    with T.Capture(where) as prof:
        for _ in range(calls):
            o = fn(*args)
        jax.block_until_ready(o)
    ev = T.read_events(prof.path)["devices"]
    if not ev:                          # off the chip: no device's line
        return {}
    dev = ev[min(ev)]
    ops = {}
    for name, s, e in dev["ops"]:
        ops[T.op_name(name)] = ops.get(T.op_name(name), 0) + (e - s)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:3]
    return {"device_ms_per_layer_piece": round(
        sum(e - s for _, s, e in dev["modules"]) / calls / 1e6, 4),
        "top_ops_ms": [[k[:40], round(v / calls / 1e6, 4)] for k, v in top]}


if __name__ == "__main__":
    main()
