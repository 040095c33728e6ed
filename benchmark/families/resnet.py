"""The ResNet family (He et al., arXiv:1512.03385): how the program is
asked for it, its plain float32 reference, and its operations from shapes.

The reference follows the paper's v1 post-activation unit as the program's
``mx.models.get_resnet`` lays it out (stride on the 3x3 of a bottleneck,
projection shortcut where the shape changes, 3x3/2 max-pool over a 112x112
stem output padded one row and column at the far edge). It imports nothing
of the program: only the parameter NAMES are the program's, since the
benchmark hands it the weights.
"""
from __future__ import annotations

import math

UNITS = {18: ([2, 2, 2, 2], False), 34: ([3, 4, 6, 3], False),
         50: ([3, 4, 6, 3], True), 101: ([3, 4, 23, 3], True),
         152: ([3, 8, 36, 3], True)}


def _layout(cfg):
    """[(name, kind, in_ch, out_ch, kernel, stride)] of every conv, in
    forward order, with the unit structure the forward pass walks."""
    units, bottleneck = UNITS[cfg["num_layers"]]
    if list(cfg["units"]) != units or bool(cfg["bottleneck"]) != bottleneck:
        raise ValueError("resnet: units/bottleneck in the config do not "
                         "match num_layers=%d" % cfg["num_layers"])
    filters = cfg["filters"]
    convs = [("stem", 3, 64, 7, 2)]
    plan = []
    ch = 64
    for si, (n, f) in enumerate(zip(units, filters), start=1):
        for ui in range(n):
            stride = 2 if si > 1 and ui == 0 else 1
            name = "stage%d_unit%d" % (si, ui + 1)
            match = ui > 0
            if bottleneck:
                mid = f // 4
                body = [(name + "_a", ch, mid, 1, 1),
                        (name + "_b", mid, mid, 3, stride),
                        (name + "_c", mid, f, 1, 1)]
            else:
                body = [(name + "_a", ch, f, 3, stride),
                        (name + "_b", f, f, 3, 1)]
            sc = None if match else (name + "_sc", ch, f, 1, stride)
            convs += body + ([sc] if sc else [])
            plan.append((body, sc))
            ch = f
    return convs, plan, ch


def param_specs(cfg):
    """name -> (shape, recipe) for every trained leaf, named as the
    program's symbol names its arguments."""
    convs, _, ch = _layout(cfg)
    specs = {}
    for name, cin, cout, k, _ in convs:
        std = math.sqrt(2.0 / (cin * k * k))
        specs[name + "_conv_weight"] = ((cout, cin, k, k), ("normal", std))
        # the last BatchNorm of a unit starts small (Goyal et al.,
        # arXiv:1706.02677, start it at 0; here 0.2, so that its branch
        # still carries gradient): with every scale at 1 the gradient of
        # a BatchNorm net at its start grows with depth until float32
        # itself departs from float64 by per cent at the stem
        last = name.endswith("_c" if cfg["bottleneck"] else "_b") \
            and name != "stem"
        specs[name + "_bn_gamma"] = ((cout,), ("around", 0.2, 0.02) if last
                                     else ("around", 1.0, 0.1))
        specs[name + "_bn_beta"] = ((cout,), ("normal", 0.1))
    # wide enough that the logits are of order 1: with a head at 0.01 the
    # loss is ln(classes) whatever the features are, and no rounding shows
    specs["fc1_weight"] = ((cfg["num_classes"], ch), ("normal", 0.1))
    specs["fc1_bias"] = ((cfg["num_classes"],), ("normal", 0.01))
    return specs


def aux_specs(cfg):
    convs, _, _ = _layout(cfg)
    specs = {}
    for name, _, cout, _, _ in convs:
        specs[name + "_bn_moving_mean"] = ((cout,), ("const", 0.0))
        specs[name + "_bn_moving_var"] = ((cout,), ("const", 1.0))
    return specs


def input_shapes(cfg, traffic):
    b = traffic["batch"]
    return {"data": (b,) + tuple(cfg["image"]), "softmax_label": (b,)}


def build_symbol(mx, cfg, traffic):
    import mxnet_tpu.models  # noqa: F401 (mx.models)
    return mx.models.get_resnet(num_classes=cfg["num_classes"],
                                num_layers=cfg["num_layers"])


def make_batch(key, cfg, traffic):
    """One batch on the device from ``key``: every row differs. Images
    are noise at three scales (blocks of 32, of 8 and single pixels), so
    that they differ from each other at every depth of the net: under
    plain white noise every image looks alike past a few layers, the
    batch statistics of a deep BatchNorm shrink to rounding, and float32
    itself no longer agrees with float64."""
    import jax
    import jax.numpy as jnp
    k32, k8, k1, kl = jax.random.split(key, 4)
    b, c, h, w = input_shapes(cfg, traffic)["data"]

    def blocks(k, n):
        z = jax.random.normal(k, (b, c, -(-h // n), -(-w // n)), jnp.float32)
        return jnp.repeat(jnp.repeat(z, n, axis=2), n, axis=3)[:, :, :h, :w]

    data = blocks(k32, 32) + 0.5 * blocks(k8, 8) + 0.25 * blocks(k1, 1)
    label = jax.random.randint(kl, (b,), 0, cfg["num_classes"]) \
        .astype(jnp.float32)
    return {"data": data, "softmax_label": label}


def row_losses(outs, batch):
    """Each row's cross-entropy from the step's output (the softmax
    probabilities [B, classes]), on the device; the loss is their mean."""
    import jax.numpy as jnp
    label = batch["softmax_label"].astype(jnp.int32)
    p = jnp.take_along_axis(outs[0].astype(jnp.float32), label[:, None], 1)
    return -jnp.log(jnp.maximum(p[:, 0], 1e-30))


def loss_rows(cfg, traffic):
    """Rows the loss is a mean over: what ``rescale_grad`` divides by."""
    return traffic["batch"]


# -- the plain reference ------------------------------------------------------

def reference_loss(params, batch, cfg, precision=None, remat=True):
    """(mean cross-entropy, each row's) of the training-mode forward pass
    in float32 (``jax.default_matmul_precision('highest')`` is the caller's).
    ``precision`` rounds both operands of every convolution and of the
    classifier to the control's type. Units are rematerialised so the
    float32 backward of 256 images fits beside nothing else."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from benchmark.harness import fake_quant, grad_quant

    eps = cfg["bn_eps"]

    def conv_bn(x, name, stride, k, relu):
        w = params[name + "_conv_weight"]
        pad = (k - 1) // 2
        y = lax.conv_general_dilated(
            fake_quant(x, precision), fake_quant(w, precision),
            (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        y = grad_quant(y, precision)
        mean = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(y - mean), axis=(0, 2, 3), keepdims=True)
        g = params[name + "_bn_gamma"].reshape(1, -1, 1, 1)
        b = params[name + "_bn_beta"].reshape(1, -1, 1, 1)
        y = (y - mean) * lax.rsqrt(var + eps) * g + b
        return jnp.maximum(y, 0.0) if relu else y

    def unit(x, body, sc):
        y = x
        for i, (name, _, _, k, stride) in enumerate(body):
            y = conv_bn(y, name, stride, k, relu=i < len(body) - 1)
        if sc is not None:
            x = conv_bn(x, sc[0], sc[4], sc[3], relu=False)
        return jnp.maximum(y + x, 0.0)

    _, plan, _ = _layout(cfg)
    x = batch["data"].astype(params["fc1_bias"].dtype)
    x = conv_bn(x, "stem", 2, 7, relu=True)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (0, 1), (0, 1)])
    for body, sc in plan:
        f = (lambda v, _b=body, _s=sc: unit(v, _b, _s))
        x = jax.checkpoint(f)(x) if remat else f(x)
    x = jnp.mean(x, axis=(2, 3))
    logits = grad_quant(fake_quant(x, precision) @ fake_quant(
        params["fc1_weight"], precision).T, precision) + params["fc1_bias"]
    label = batch["softmax_label"].astype(jnp.int32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, label[:, None], 1)[:, 0]
    return jnp.mean(lse - picked), lse - picked


# -- operations from shapes -----------------------------------------------------

def conv_macs(cin, cout, k, out_hw):
    """Multiply-accumulates of one convolution for one image."""
    return cin * cout * k * k * out_hw * out_hw


def forward_macs(cfg):
    """(all forward MACs, the stem's) for one image."""
    convs, plan, ch = _layout(cfg)
    _, cin, cout, k, stride = convs[0]
    hw = -(-cfg["image"][1] // stride)
    stem = total = conv_macs(cin, cout, k, hw)
    hw = -(-hw // 2)                                    # the max-pool
    for body, sc in plan:
        cur = hw
        for _, cin, cout, k, stride in body:
            cur = -(-cur // stride)
            total += conv_macs(cin, cout, k, cur)
        if sc is not None:
            _, cin, cout, k, stride = sc
            total += conv_macs(cin, cout, k, -(-hw // stride))
        hw = cur
    total += ch * cfg["num_classes"]
    return total, stem


def train_flops_per_step(cfg, traffic):
    """Operations one training step needs: 2 per MAC; the backward pass
    is twice the forward (gradient to the input and to the weight of
    every layer), less the stem's input gradient, which nobody needs."""
    total, stem = forward_macs(cfg)
    return 2.0 * (3 * total - stem) * traffic["batch"]
