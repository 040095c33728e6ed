"""The OPT family (Zhang et al., arXiv:2205.01068): a pre-LayerNorm
decoder with learned positions, biased projections and a ReLU
feed-forward, which is the block the program's ``get_transformer_lm``
builds. How the program is asked for it, its plain float32 reference
(forward for serving, loss for training), and operations and bytes from
shapes.

Departures from the published model, stated in the configuration files:
the output head is not tied to the embedding, and the position table has
``max_position_embeddings`` rows with no offset of 2.
"""
from __future__ import annotations

import math


def _dims(cfg):
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (cfg["vocab_size"], cfg["num_hidden_layers"], e, h, e // h,
            cfg["ffn_dim"], cfg["max_position_embeddings"])


def layer_names(i):
    p = "layer%d_" % i
    return [p + s for s in (
        "ln1_gamma", "ln1_beta", "qkv_weight", "qkv_bias", "proj_weight",
        "proj_bias", "ln2_gamma", "ln2_beta", "ffn1_weight", "ffn1_bias",
        "ffn2_weight", "ffn2_bias")]


def param_specs(cfg):
    """name -> (shape, recipe), named as the program's symbol names its
    arguments. Matrices N(0, init_std); the two projections that write
    the residual stream are scaled down by sqrt(2 * layers) as GPT-2 and
    OPT's own initialisation do; LayerNorm scales near 1; biases small
    but not zero, so that a dropped bias shows."""
    v, n, e, _, _, f, t = _dims(cfg)
    std = cfg["init_std"]
    out_std = std / math.sqrt(2.0 * n)
    specs = {"embed_weight": ((v, e), ("normal", std)),
             "pos_embed": ((t, e), ("normal", std)),
             "lnf_gamma": ((e,), ("around", 1.0, 0.05)),
             "lnf_beta": ((e,), ("normal", 0.02)),
             "lm_head_weight": ((v, e), ("normal", std)),
             "lm_head_bias": ((v,), ("normal", 0.02))}
    for i in range(n):
        p = "layer%d_" % i
        specs[p + "ln1_gamma"] = ((e,), ("around", 1.0, 0.05))
        specs[p + "ln1_beta"] = ((e,), ("normal", 0.02))
        specs[p + "qkv_weight"] = ((3 * e, e), ("normal", std))
        specs[p + "qkv_bias"] = ((3 * e,), ("normal", 0.02))
        specs[p + "proj_weight"] = ((e, e), ("normal", out_std))
        specs[p + "proj_bias"] = ((e,), ("normal", 0.02))
        specs[p + "ln2_gamma"] = ((e,), ("around", 1.0, 0.05))
        specs[p + "ln2_beta"] = ((e,), ("normal", 0.02))
        specs[p + "ffn1_weight"] = ((f, e), ("normal", std))
        specs[p + "ffn1_bias"] = ((f,), ("normal", 0.02))
        specs[p + "ffn2_weight"] = ((e, f), ("normal", out_std))
        specs[p + "ffn2_bias"] = ((e,), ("normal", 0.02))
    return specs


def aux_specs(cfg):
    return {}


def norm_parts(cfg):
    """Leaves that are several matrices fused along their first axis: the
    query, key and value projections. The key's bias has no gradient under
    softmax, and would hide in the norm of the fused leaf."""
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        out["layer%d_qkv_weight" % i] = 3
        out["layer%d_qkv_bias" % i] = 3
    return out


def input_shapes(cfg, traffic):
    shape = (traffic["batch"], traffic["seq_len"])
    return {"data": shape, "softmax_label": shape}


def build_symbol(mx, cfg, traffic):
    import mxnet_tpu.models  # noqa: F401 (mx.models)
    return mx.models.get_transformer_lm(
        cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        embed_dim=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], ffn_hidden=cfg["ffn_dim"],
        impl=traffic.get("attention", "flash"),
        dropout=traffic.get("dropout", 0.0),
        loss_layout=traffic.get("loss_layout", "reference"),
        pos_encoding="learned")


def make_batch(key, cfg, traffic):
    """One batch of token rows on the device from ``key``: ids uniform
    over the vocabulary, the label the next token."""
    import jax
    import jax.numpy as jnp
    b, t = input_shapes(cfg, traffic)["data"]
    toks = jax.random.randint(key, (b, t + 1), 0, cfg["vocab_size"])
    return {"data": toks[:, :-1].astype(jnp.float32),
            "softmax_label": toks[:, 1:].astype(jnp.float32)}


def row_losses(outs, batch):
    """Each token's cross-entropy from the step's output: under
    ``loss_layout="ce"`` that is the output itself, [B*T]; the loss is
    their mean."""
    import jax.numpy as jnp
    return outs[0].astype(jnp.float32).reshape(-1)


def loss_rows(cfg, traffic):
    return traffic["batch"] * traffic["seq_len"]


# -- the plain reference ---------------------------------------------------------

def _ln(x, g, b, eps=1e-5):
    import jax.numpy as jnp
    from jax import lax
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _linear(x, w, b, precision):
    from benchmark.harness import fake_quant, grad_quant
    return grad_quant(fake_quant(x, precision) @ fake_quant(w, precision).T,
                      precision) + b


def reference_layer(x, p, cfg, precision=None, head_block=8):
    """One decoder layer on [B, T, E] in the dtype of ``x``; ``p`` maps
    the twelve short names (``ln1_gamma`` ... ``ffn2_bias``) to leaves.
    Attention runs over ``head_block`` heads at a time so that the
    [T, T] scores of a long row fit."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant, grad_quant
    _, _, e, h, d, _, _ = _dims(cfg)
    b, t, _ = x.shape
    hn = _ln(x, p["ln1_gamma"], p["ln1_beta"])
    qkv = _linear(hn, p["qkv_weight"], p["qkv_bias"], precision)
    q, k, v = (qkv[..., i * e:(i + 1) * e].reshape(b, t, h, d)
               for i in range(3))
    mask = jnp.tril(jnp.ones((t, t), bool))

    def heads(args):
        qh, kh, vh = args                       # [B, T, hb, D]
        s = grad_quant(jnp.einsum(
            "bqhd,bkhd->bhqk", fake_quant(qh, precision),
            fake_quant(kh, precision)), precision) / math.sqrt(d)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return grad_quant(jnp.einsum(
            "bhqk,bkhd->bqhd", fake_quant(pr, precision),
            fake_quant(vh, precision)), precision)

    hb = min(head_block, h)
    split = lambda z: jnp.moveaxis(z.reshape(b, t, h // hb, hb, d), 2, 0)
    # rebuilt block by block in the backward pass, or a scan keeps every
    # block's probabilities
    o = jax.lax.map(jax.checkpoint(heads), (split(q), split(k), split(v)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, t, e)
    x = x + _linear(o, p["proj_weight"], p["proj_bias"], precision)
    hn = _ln(x, p["ln2_gamma"], p["ln2_beta"])
    f = jnp.maximum(_linear(hn, p["ffn1_weight"], p["ffn1_bias"],
                            precision), 0.0)
    return x + _linear(f, p["ffn2_weight"], p["ffn2_bias"], precision)


def reference_embed(tokens, embed, pos):
    return embed[tokens] + pos[None, :tokens.shape[1]]


def reference_head(x, p, precision=None):
    return _linear(_ln(x, p["lnf_gamma"], p["lnf_beta"]),
                   p["lm_head_weight"], p["lm_head_bias"], precision)


def reference_logits(tokens, make_leaves, cfg, precision=None):
    """The reference's logits over ``tokens`` ([K, L] int32), layer by
    layer: ``make_leaves(names)`` hands over the named leaves in float32,
    so only one layer's weights are on the device at a time. The caller
    sets full matmul precision; ``precision`` makes it the control."""
    import jax
    w = make_leaves(["embed_weight", "pos_embed"])
    x = jax.jit(reference_embed)(tokens, w["embed_weight"], w["pos_embed"])
    layer = jax.jit(lambda v, p: reference_layer(v, p, cfg, precision))
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, _short(make_leaves(layer_names(i)), i))
    w = make_leaves(["lnf_gamma", "lnf_beta", "lm_head_weight",
                     "lm_head_bias"])
    return jax.jit(lambda v, p: reference_head(v, p, precision))(x, w)


def _short(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


def reference_loss(params, batch, cfg, precision=None, remat=True,
                   head_block_rows=2048):
    """(mean next-token cross-entropy, each token's) of the whole forward
    pass in the parameters' dtype (float32, the caller sets full matmul precision).
    Layers are rematerialised: the float32 scores of 2048-token rows do
    not fit five layers deep otherwise."""
    import jax
    import jax.numpy as jnp
    tokens = batch["data"].astype(jnp.int32)
    label = batch["softmax_label"].astype(jnp.int32)
    x = reference_embed(tokens, params["embed_weight"], params["pos_embed"])
    for i in range(cfg["num_hidden_layers"]):
        f = (lambda v, p: reference_layer(v, p, cfg, precision))
        f = jax.checkpoint(f) if remat else f
        x = f(x, _short(params, i))
    # the head in blocks of rows, rebuilt in the backward pass: the
    # float32 logits of 8,192 rows are 1.6 GB, and their gradient as much
    e = x.shape[-1]
    rows = x.reshape(-1, e)
    label = label.reshape(-1)
    block = min(head_block_rows, rows.shape[0])
    head = {k: params[k] for k in ("lnf_gamma", "lnf_beta", "lm_head_weight",
                                   "lm_head_bias")}

    def block_loss(args):
        xb, lb = args
        logits = reference_head(xb, head, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]

    f = jax.checkpoint(block_loss) if remat else block_loss
    each = jax.lax.map(f, (rows.reshape(-1, block, e),
                           label.reshape(-1, block))).reshape(-1)
    return jnp.mean(each), each


# -- operations and bytes from shapes ------------------------------------------------

def layer_macs_per_token(cfg):
    """Weight multiply-accumulates of one layer for one token: the fused
    qkv projection, the output projection and the two feed-forward
    matrices."""
    _, _, e, _, _, f, _ = _dims(cfg)
    return 4 * e * e + 2 * e * f


def attention_macs(cfg, q_rows, k_rows):
    """QK^T and PV of one layer for ``q_rows`` queries each seeing
    ``k_rows`` keys: 2 * q * k * E."""
    return 2 * q_rows * k_rows * cfg["hidden_size"]


def train_flops_per_step(cfg, traffic):
    """Forward and backward operations one step needs: 2 per MAC, the
    backward twice the forward, causal attention counted once (a query
    sees (T + 1) / 2 keys on average), nothing recomputed."""
    v, n, e, _, _, _, _ = _dims(cfg)
    b, t = traffic["batch"], traffic["seq_len"]
    per_row = t * (n * layer_macs_per_token(cfg) + e * v) \
        + n * attention_macs(cfg, t, (t + 1) / 2.0)
    return 2.0 * 3.0 * b * per_row


def flash_train_cost(cfg, traffic):
    """(operations, bytes) the flash-attention kernels of one step need:
    forward 2 matmuls over the causal half, backward 5 (it rebuilds the
    scores: that is the algorithm, not a recomputation it could avoid);
    bytes are q, k, v, o once forward and q, k, v, o, do, dq, dk, dv once
    backward, in bfloat16."""
    _, n, e, _, _, _, _ = _dims(cfg)
    b, t = traffic["batch"], traffic["seq_len"]
    one = 2.0 * b * t * (t + 1) / 2.0 * e      # one matmul, causal half
    flops = n * 7.0 * one
    nbytes = n * 12.0 * b * t * e * 2
    return flops, nbytes


def decode_weight_bytes(cfg, itemsize=2):
    """Bytes of weights one decode step has to read: every layer's
    matrices, vectors and the head, once (the embedding is a gather of
    a few rows)."""
    v, n, e, _, _, f, _ = _dims(cfg)
    per_layer = layer_macs_per_token(cfg) + 9 * e + f
    return itemsize * (n * per_layer + e * v + v + 2 * e)


def decode_cache_bytes_per_row(cfg, itemsize=2):
    """Bytes of K and V that one live cache row holds over all layers."""
    return itemsize * 2 * cfg["num_hidden_layers"] * cfg["hidden_size"]


def decode_flops(cfg, live_tokens, live_rows):
    """Operations of decoding ``live_tokens`` tokens that between them
    attend to ``live_rows`` cache rows."""
    v, n, e, _, _, _, _ = _dims(cfg)
    return 2.0 * (live_tokens * (n * layer_macs_per_token(cfg) + e * v)
                  + n * 2 * live_rows * e)
