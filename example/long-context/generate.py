"""Train a tiny LM and generate from it with the KV-cache decoder.

The decode program is DERIVED from the same Symbol graph the trainer
compiled (``parallel.Decoder`` — no second model definition): K/V of
each new token land in static [B, max_len, H, D] cache buffers and the
whole greedy loop runs as one compiled ``lax.scan`` program.

The toy task is a deterministic cycle (token t+1 = (token t + 1) mod V),
so a trained model's greedy continuation should keep counting — the
script reports that pattern accuracy.

No reference counterpart: the reference samples from its explicitly
unrolled char-LSTM (example/rnn/lstm.py); attention-era decoding is a
TPU-build extension. Run anywhere:

    JAX_PLATFORMS=cpu python generate.py
"""
import argparse
import logging

import numpy as np

import mxnet_tpu as mx  # noqa: F401
from mxnet_tpu import parallel as par
from mxnet_tpu.models import get_transformer_lm
from mxnet_tpu.parallel import Decoder


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=12)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--batches", type=int, default=60)
    ap.add_argument("--gen-steps", type=int, default=16)
    ap.add_argument("--num-kv-heads", type=int, default=0,
                    help="grouped-query attention: K/V heads "
                         "(0 = num_heads); the decode cache shrinks "
                         "by the group factor")
    ap.add_argument("--cache-dtype", default=None,
                    help="e.g. int8 — half-size quantized K/V cache")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window attention: the decode cache "
                         "becomes a window-slot ring buffer")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    V, T = args.vocab, args.seq_len
    # loss_layout="ce": the fused SoftmaxCELoss head emits per-token
    # LOSSES, so the training log below is a real NLL (the reference
    # layout would emit probabilities); the Decoder strips either head
    sym = get_transformer_lm(V, num_layers=2, embed_dim=32, num_heads=2,
                             impl="dense", loss_layout="ce",
                             num_kv_heads=args.num_kv_heads,
                             window=args.window)
    trainer = par.ParallelTrainer(
        sym, {"data": (16, T), "softmax_label": (16, T)},
        optimizer="adam", mesh=par.data_parallel_mesh(1),
        optimizer_params={"learning_rate": 3e-3})
    trainer.init_params()

    rng = np.random.RandomState(0)
    for i in range(args.batches):
        start = rng.randint(0, V, (16, 1))
        toks = (start + np.arange(T + 1)[None, :]) % V
        out = trainer.step({"data": toks[:, :-1].astype(np.float32),
                            "softmax_label": toks[:, 1:].astype(np.float32)})
        if i % 20 == 0:
            logging.info("batch %d nll/token %.4f (uniform %.4f)", i,
                         float(np.asarray(out[0]).mean()), np.log(V))

    dec = Decoder(sym, trainer.params, max_len=T,
                  cache_dtype=args.cache_dtype)
    prompt = (rng.randint(0, V, (4, 1)) + np.arange(8)[None, :]) % V
    out = np.asarray(dec.generate(prompt, num_steps=args.gen_steps))
    want = (prompt[:, -1:] + 1 + np.arange(args.gen_steps)[None, :]) % V
    acc = float((out[:, prompt.shape[1]:] == want).mean())
    logging.info("generated: %s", out[0].tolist())
    logging.info("pattern accuracy %.3f", acc)
    print("pattern accuracy %.3f" % acc)


if __name__ == "__main__":
    main()
