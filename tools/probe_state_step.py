"""Stand-alone probe of the decode step's recurrent-state update, on the
chip.

Times ONE Gated DeltaNet layer's state step at serve-longdoc's shapes (a
float32 leaf [64, 32, 128, 128], a few live slots, the rest dead) in the
two forms the tree has, and checks the kernel against the plain one on
the chip, the step with no live slot included:

  plain     ops.attention.gdn_step over the whole leaf between the two
            ``where``s of the state kind (zero start, dead slots keep
            theirs): what a chunk without a slot walk's ``lens`` runs
  in_place  ops.pallas_kernels.gdn_state_step at each block of heads

    python tools/probe_state_step.py [--live 5] [--blocks 8,16,32]
        [--shape 64,32,128,128] [--steps 48] [--iters 10]
    python tools/probe_state_step.py --shape 4,8,8,8 --blocks 8   (off
        the chip: the interpreter)

Prints one JSON line per form: ms a layer and step, the largest gaps to
the plain form, and whether every dead slot's state came back bit for
bit.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np                                        # noqa: E402
import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402
from jax import lax                                       # noqa: E402

import mxnet_tpu  # noqa: F401,E402  (x64 on, as the program runs)
from mxnet_tpu.ops import pallas_kernels as pk            # noqa: E402
from mxnet_tpu.ops.attention import gdn_step              # noqa: E402


def plain(state, q, k, v, beta, g, lens, fresh):
    live = lens > 0
    start = jnp.where((fresh & live)[:, None, None, None], 0, state)
    new, o = gdn_step(start, q, k, v, beta, g)
    return jnp.where(live[:, None, None, None], new, state), o


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", type=int, default=5)
    ap.add_argument("--blocks", default="8,16,32")
    ap.add_argument("--shape", default="64,32,128,128", help="S,Hv,Dk,Dv")
    ap.add_argument("--steps", type=int, default=48,
                    help="steps in one timed program (a round's 6 x 8)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    s, h, dk, dv = (int(x) for x in a.shape.split(","))
    f32 = jnp.float32
    r = np.random.RandomState(a.seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(r.randn(*shape) * scale, f32)

    state = draw(s, h, dk, dv)
    q, k = draw(s, h, dk, scale=dk ** -0.5), draw(s, h, dk, scale=dk ** -0.5)
    v = draw(s, h, dv)
    beta = jnp.asarray(r.rand(s, h), f32)
    g = -jnp.asarray(r.rand(s, h), f32)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind,
                      "shape": [s, h, dk, dv], "live": a.live}), flush=True)

    forms = [("plain", plain)] + [
        ("in_place/%s" % b,
         lambda *z, b=int(b): pk.gdn_state_step(*z, block_h=b))
        for b in a.blocks.split(",") if h % int(b) == 0]

    # what each form returns, on the chip, for four sets of slots
    at = r.permutation(s)
    cases = {"some": at[:a.live], "none": at[:0], "all": at,
             "fresh": at[:a.live]}
    for name, fn in forms:
        worst = {"form": name}
        for case, slots in cases.items():
            lens = np.zeros(s, np.int32)
            lens[slots] = 1 + r.randint(0, 9000, len(slots))
            fresh = np.zeros(s, bool)
            if case == "fresh":
                fresh[slots[::2]] = True
                fresh[at[-1]] = True            # a dead slot's flag
            want_s, want_o = jax.jit(plain)(state, q, k, v, beta, g,
                                            lens, fresh)
            got_s, got_o = jax.jit(fn)(state, q, k, v, beta, g,
                                       jnp.asarray(lens), jnp.asarray(fresh))
            dead = lens == 0
            gs, ws = np.asarray(got_s), np.asarray(want_s)
            worst[case] = {
                "state_gap": float(np.abs(gs - ws).max()),
                "o_gap": float(np.abs(np.asarray(got_o)
                                      - np.asarray(want_o))[~dead].max())
                if (~dead).any() else None,
                "dead_bit_for_bit": bool(
                    (gs[dead] == np.asarray(state)[dead]).all()),
                "dead_o_zero": bool((np.asarray(got_o)[dead] == 0).all())
                if name != "plain" else None}
        print(json.dumps(worst), flush=True)

    # ms a layer and step: ``steps`` steps in one program, the state
    # carried and donated as the engine's round carries it
    lens = np.zeros(s, np.int32)
    lens[at[:a.live]] = 1 + r.randint(0, 9000, a.live)
    lens, fresh = jnp.asarray(lens), jnp.zeros(s, bool)
    for name, fn in forms:
        def many(state, fn=fn):
            def body(_, c):
                st, acc = c
                st, o = fn(st, q, k, v, beta, g, lens, fresh)
                return st, acc + o
            return lax.fori_loop(0, a.steps, body,
                                 (state, jnp.zeros((s, h, dv), f32)))
        run = jax.jit(many, donate_argnums=(0,))
        st = state + 0
        st, acc = run(st)
        jax.block_until_ready(acc)
        t0 = time.perf_counter()
        for _ in range(a.iters):
            st, acc = run(st)
        jax.block_until_ready(acc)
        ms = (time.perf_counter() - t0) * 1e3 / (a.iters * a.steps)
        print(json.dumps({"form": name, "ms_per_layer_step": round(ms, 5),
                          "ms_per_round_of_48": round(ms * 48, 3)}),
              flush=True)


if __name__ == "__main__":
    main()
