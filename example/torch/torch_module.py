"""Embed a real torch.nn module inside a symbolic graph
(reference example/torch/torch_module.py — there, torch layers via the
lua-torch plugin; here, modern pytorch modules through
``mxnet_tpu.torch.TorchModuleOp``: forward AND backward run in torch on
host, gradients flow back into the XLA graph through ``pure_callback``).

Torch runs on the HOST through ``pure_callback``; run on CPU:

    JAX_PLATFORMS=cpu \
    PYTHONPATH=../..:$PYTHONPATH python torch_module.py
"""
import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.torch import TorchModuleOp


def main():
    import torch

    logging.basicConfig(level=logging.INFO)
    rng = np.random.RandomState(0)
    n, d, k = 400, 16, 4
    X = rng.randn(n, d).astype(np.float32)
    y = np.argmax(X @ rng.randn(d, k), axis=1).astype(np.float32)

    # network: framework FC -> TORCH linear+tanh -> framework softmax
    data = mx.symbol.Variable("data")
    fc1 = mx.symbol.FullyConnected(data=data, name="fc1", num_hidden=32)
    act = mx.symbol.Activation(data=fc1, act_type="relu")
    tmod = TorchModuleOp(torch.nn.Sequential(torch.nn.Linear(32, 16),
                                             torch.nn.Tanh()))
    mid = tmod.get_symbol(act, name="torch_mid")
    fc2 = mx.symbol.FullyConnected(data=mid, name="fc2", num_hidden=k)
    net = mx.symbol.SoftmaxOutput(data=fc2, name="softmax")

    model = mx.model.FeedForward(net, ctx=mx.cpu(), num_epoch=12,
                                 learning_rate=0.2, momentum=0.9,
                                 numpy_batch_size=50)
    model.fit(X, y, eval_metric="acc")
    acc = model.score(mx.io.NDArrayIter(X, y, batch_size=50))
    print("final accuracy %.3f" % acc)
    assert acc > 0.9, "torch-module hybrid failed to converge"


if __name__ == "__main__":
    main()
