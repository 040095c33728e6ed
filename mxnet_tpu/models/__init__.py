"""Model zoo: symbol constructors for the reference's example model families.

Parity map (reference ``example/``):

* ``example/image-classification/train_mnist.py`` nets  -> :mod:`.classifiers`
* ``symbol_alexnet.py``                                 -> :mod:`.alexnet`
* ``symbol_vgg.py``                                     -> :mod:`.vgg`
* ``symbol_resnet-28-small.py`` (+ modern ImageNet
  ResNets, the BASELINE.json north-star model)          -> :mod:`.resnet`
* ``symbol_inception-bn-28-small.py``, ``symbol_inception-bn.py``,
  ``symbol_inception-bn-full.py``, ``symbol_inception-v3.py``,
  ``symbol_googlenet.py``                               -> :mod:`.inception`
* ``example/rnn/lstm.py`` (unroll + bucketing)          -> :mod:`.lstm`
* ``example/fcn-xs/symbol_fcnxs.py``                    -> :mod:`.fcn`
* no reference counterpart (decoder-only LMs):
  ``get_transformer_lm``, ``get_zaya_lm``,
  ``get_qwen3_next_lm``, ``get_xing4_lm``,
  ``get_granite_hybrid_lm``, ``get_nemotron_h_lm``      -> :mod:`.transformer`

Every constructor returns a :class:`mxnet_tpu.symbol.Symbol` whose single
head is a ``SoftmaxOutput`` (classification) so it drops straight into
``FeedForward``/``fit``. ``get_symbol(name, **kw)`` mirrors the reference's
``train_model.py --network`` dispatch.
"""
from . import classifiers, alexnet, vgg, resnet, inception, lstm, fcn
from .classifiers import get_mlp, get_lenet
from .alexnet import get_alexnet
from .vgg import get_vgg
from .resnet import (get_resnet, get_resnet_cifar,
                     convert_stem_weight_s2d,
                     space_to_depth_batch)
from .inception import (get_inception_bn_small, get_inception_bn,
                        get_inception_v3, get_googlenet)
from .lstm import lstm_unroll, LSTMState, LSTMParam
from .fcn import get_fcn_symbol
from . import transformer
from .transformer import (get_transformer_lm, transformer_block,
                          moe_transformer_block, get_zaya_lm, zaya_block,
                          get_qwen3_next_lm, qwen3_next_block,
                          get_xing4_lm, xing4_block,
                          get_granite_hybrid_lm, granite_hybrid_block,
                          get_nemotron_h_lm, nemotron_h_block)

_REGISTRY = {
    "mlp": get_mlp,
    "lenet": get_lenet,
    "alexnet": get_alexnet,
    "vgg": get_vgg,
    "resnet": get_resnet,
    "resnet-28-small": get_resnet_cifar,
    "inception-bn-28-small": get_inception_bn_small,
    "inception-bn": get_inception_bn,
    "inception-v3": get_inception_v3,
    "googlenet": get_googlenet,
    "fcn-xs": get_fcn_symbol,
}


def get_symbol(name, **kwargs):
    """Construct a model symbol by name (``train_model.py --network``)."""
    if name not in _REGISTRY:
        raise ValueError("unknown network %r (have: %s)"
                         % (name, ", ".join(sorted(_REGISTRY))))
    return _REGISTRY[name](**kwargs)
