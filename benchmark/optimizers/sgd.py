"""SGD with momentum and weight decay folded into the gradient, as
published (Sutskever et al. 2013; the program's ``optimizer="sgd"``):
``mom = momentum * mom - lr * (g + wd * w); w += mom``. The reference's
own arithmetic in plain jax.numpy, and how the first gradient is read back
from the program's state (one momentum buffer a leaf, zero at the start).
``opt`` holds the traffic file's hyper-parameters; its numbers may be
traced values."""


def init(w):
    import jax.numpy as jnp
    return jnp.zeros_like(w)


def update(opt, w, g, state, t):
    g = g + opt.get("wd", 0.0) * w
    mom = opt.get("momentum", 0.0) * state - opt["learning_rate"] * g
    return w + mom, mom


def first_gradient(opt, state, w0):
    """After one step from zero ``mom1 = -lr * (g + wd * w0)``."""
    return -state / opt["learning_rate"] - opt.get("wd", 0.0) * w0
