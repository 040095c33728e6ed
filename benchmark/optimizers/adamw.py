"""AdamW (Loshchilov & Hutter, arXiv:1711.05101; the program's
``optimizer="adamw"``): Adam's bias-corrected step, the weight decay
applied to the weight and not to the gradient. The reference's own
arithmetic in plain jax.numpy, and how the first gradient is read back
from the program's state ((mean, variance) a leaf, zero at the start).
``opt`` holds the traffic file's hyper-parameters; its numbers may be
traced values."""


def init(w):
    import jax.numpy as jnp
    return jnp.zeros_like(w), jnp.zeros_like(w)


def update(opt, w, g, state, t):
    import jax.numpy as jnp
    lr, wd = opt["learning_rate"], opt.get("wd", 0.0)
    b1, b2 = opt.get("beta1", 0.9), opt.get("beta2", 0.999)
    m, v = state
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    step = lr_t * m / (jnp.sqrt(v) + opt.get("epsilon", 1e-8))
    return w - step - lr * wd * w, (m, v)


def first_gradient(opt, state, w0):
    """After one step from zero ``mean1 = (1 - beta1) * g``."""
    return state[0] / (1.0 - opt.get("beta1", 0.9))
