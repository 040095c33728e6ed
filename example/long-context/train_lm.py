"""Long-context language-model training with ring-attention sequence
parallelism.

The marquee TPU-scale path: the sequence axis is sharded over the ``sp``
mesh axis, each device holds T/n positions, and only K/V blocks rotate
the ring (``MultiHeadAttention(impl="ring")`` inside
``SequenceParallelTrainer``). Activation memory per device scales as
T/n, so maximum context length grows linearly with the ring size —
the blockwise/ring-attention recipe.

No reference counterpart (2015); run it on the virtual CPU mesh with

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python train_lm.py --dp 2 --sp 4

or on a real TPU slice with the same flags-free command.
"""
import argparse
import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.models import get_transformer_lm


def markov_batches(vocab, batch, seq_len, n_batches, seed=0):
    rng = np.random.RandomState(seed)
    trans = rng.dirichlet(np.full(vocab, 0.05), size=vocab)
    for _ in range(n_batches):
        toks = np.zeros((batch, seq_len + 1), np.float32)
        cur = rng.randint(0, vocab, batch)
        toks[:, 0] = cur
        for t in range(seq_len):
            cur = np.array([rng.choice(vocab, p=trans[c]) for c in cur])
            toks[:, t + 1] = cur
        yield {"data": toks[:, :-1], "softmax_label": toks[:, 1:]}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--dp', type=int, default=2)
    parser.add_argument('--sp', type=int, default=4)
    parser.add_argument('--attn', default='ring',
                        choices=['ring', 'ring_striped'],
                        help='ring = contiguous layout; ring_striped = '
                             'balanced half-block causal ring '
                             '(striped attention, ~2x causal at equal '
                             'ring size — parallel/ring.py)')
    parser.add_argument('--seq-len', type=int, default=512)
    parser.add_argument('--batch-size', type=int, default=4)
    parser.add_argument('--vocab', type=int, default=64)
    parser.add_argument('--embed', type=int, default=64)
    parser.add_argument('--layers', type=int, default=2)
    parser.add_argument('--heads', type=int, default=4)
    parser.add_argument('--steps', type=int, default=30)
    parser.add_argument('--lr', type=float, default=0.3)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    sym = get_transformer_lm(args.vocab, num_layers=args.layers,
                             embed_dim=args.embed, num_heads=args.heads,
                             impl=args.attn)
    mesh = par.build_mesh({"dp": args.dp, "sp": args.sp})
    trainer = par.SequenceParallelTrainer(
        sym, {"data": (args.batch_size, args.seq_len),
              "softmax_label": (args.batch_size, args.seq_len)},
        mesh, optimizer="sgd",
        optimizer_params={"learning_rate": args.lr, "momentum": 0.9})
    trainer.init_params()

    losses = []
    for i, batch in enumerate(markov_batches(
            args.vocab, args.batch_size, args.seq_len, args.steps)):
        nll = trainer.step(batch)
        losses.append(nll)
        if i % 5 == 0:
            logging.info("step %d  nll/token %.4f  (uniform %.4f)",
                         i, nll, np.log(args.vocab))
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    logging.info("final nll/token %.4f < initial %.4f — learning across "
                 "the ring", losses[-1], losses[0])


if __name__ == '__main__':
    main()
