"""Whole-model A/B timing harness (doc/performance.md methodology).

Usage: python tools/perf_ab.py resnet50 [batch] — prints median ms/step
over three two-chain differences. Run each experimental arm in its OWN
process (env vars are read at trace time; XLA compile caches are
per-process).
"""
import sys
import time

import numpy as np


def main():
    model = sys.argv[1] if len(sys.argv) > 1 else "resnet50"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    steps = int(sys.argv[3]) if len(sys.argv) > 3 else 15

    sys.path.insert(0, ".")
    import bench

    if model == "resnet50":
        from mxnet_tpu.models import get_resnet
        sym = get_resnet(num_classes=1000, num_layers=50)
        shapes = {"data": (batch, 3, 224, 224), "softmax_label": (batch,)}
        n_classes, int_data = 1000, False
    elif model == "transformer_lm":
        import os
        from mxnet_tpu.models import get_transformer_lm
        heads = int(os.environ.get("AB_HEADS", 12))
        impl = os.environ.get("AB_IMPL", "flash")
        layout = os.environ.get("AB_LOSS_LAYOUT", "reference")
        seq = int(os.environ.get("AB_SEQ", 1024))
        vocab = int(os.environ.get("AB_VOCAB", 32000))
        layers = int(os.environ.get("AB_LAYERS", 12))
        embed = int(os.environ.get("AB_EMBED", 768))
        sym = get_transformer_lm(vocab, num_layers=layers,
                                 embed_dim=embed, num_heads=heads,
                                 impl=impl, loss_layout=layout)
        shapes = {"data": (batch, seq), "softmax_label": (batch, seq)}
        n_classes, int_data = vocab, True
    else:
        raise SystemExit("unknown model " + model)

    trainer, _, devb = bench._make_trainer_and_batches(
        sym, shapes, n_classes, "bfloat16",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        int_data=int_data)

    def chain(n):
        tic = time.perf_counter()
        outs = None
        for _ in range(n):
            outs = trainer.step(devb)
        np.asarray(outs[0][(0,) * outs[0].ndim])
        return time.perf_counter() - tic

    chain(3)  # warmup/compile
    diffs = []
    for _ in range(3):
        t1 = chain(steps)
        t2 = chain(2 * steps)
        d = t2 - t1
        if d > 0.02 * t1:
            diffs.append(d / steps)
    if not diffs:
        print("RESULT ms_per_step=NaN (no positive chain difference)")
        return
    ms = 1e3 * sorted(diffs)[len(diffs) // 2]
    spread = (max(diffs) - min(diffs)) / min(diffs) * 100
    print("RESULT ms_per_step=%.2f img_per_s=%.1f spread_pct=%.1f n=%d"
          % (ms, batch / (ms / 1e3), spread, len(diffs)))


if __name__ == "__main__":
    main()
