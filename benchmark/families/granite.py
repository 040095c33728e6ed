"""The Granite 4.0-H family (IBM; granite-4.0-h-micro's ``config.json``,
``model_type`` ``granitemoehybrid`` with no experts): Mamba-2 layers
(Dao & Gu, arXiv:2405.21060) beside grouped-query softmax-attention
layers without positions, the kinds placed by ``layer_types``; every layer
also a SwiGLU MLP; RMSNorm, no biases, tied head, four multipliers. How the
program is asked for it (``get_granite_hybrid_lm``), the weights' recipe,
the plain float32 reference of the equations below, and operations and
bytes from shapes.

The equations (``E`` hidden, ``m`` ``residual_multiplier``, every RMSNorm
``x / rms(x) * gamma`` with eps ``rms_norm_eps``):

* ``x_0 = embedding_multiplier * Embed(ids)``; every layer
  ``x <- x + m mixer(RMSNorm(x))``, then
  ``x <- x + m W_down (silu(W_gate h) * W_up h)`` with ``h = RMSNorm(x)``;
  ``logits = (RMSNorm(x) . Embed^T) / logits_scaling``.
* Mamba-2 (``H`` heads of ``P``, state ``N``, one group, kernel 4,
  ``D_in = H P``, ``F = D_in + 2N``): ``[z; xBC; dt] = W_in h``;
  ``xBC_t = silu(sum_{j=0..3} w[:, j] u_{t-3+j} + bias)`` (depthwise,
  causal, zeros before position 0), split into ``x`` [H, P], ``B`` [N],
  ``C`` [N]; per head ``dt = softplus(dt + dt_bias)``, ``a = -exp(A_log)``;
  per head, ``S_{-1} = 0``: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``; ``out = W_out [rmsnorm(y * silu(z)) w_norm]``,
  the gate first and the norm over the whole ``D_in``.
* attention (``Hq`` query / ``Hkv`` kv heads of ``D``, no rotary):
  ``[q; k; v] = W_qkv h``; causal ``softmax(q . k * attention_multiplier)
  v``, kv head j serving query heads ``[j Hq/Hkv, (j+1) Hq/Hkv)``;
  ``W_o``.

Departures from the published description, each the program's too:
* the checkpoint's ``input_linear`` [2 F, E] is stored as its two halves
  (``mlp_gate_weight`` then ``mlp_up_weight``) and q_proj, k_proj, v_proj
  as one ``[q; k; v]`` matrix (``attn_qkv_weight``): storage choices;
* the reference runs the recurrence position by position (the published
  kernels run it in chunks of ``mamba_chunk_size``, as the program's
  prefill does: the same sums in another order).

The reference imports nothing of ``mxnet_tpu``; only the parameter names
are the program's.
"""
from __future__ import annotations

import math


def _dims(cfg):
    return {"v": cfg["vocab_size"], "n": cfg["num_hidden_layers"],
            "e": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"],
            "d": cfg["hidden_size"] // cfg["num_attention_heads"],
            "f": cfg["shared_intermediate_size"],
            "sh": cfg["mamba_n_heads"], "sp": cfg["mamba_d_head"],
            "sn": cfg["mamba_d_state"], "g": cfg["mamba_n_groups"],
            "taps": cfg["mamba_d_conv"],
            "din": cfg["mamba_n_heads"] * cfg["mamba_d_head"]}


def is_attention(cfg, i):
    return cfg["layer_types"][i] == "attention"


MAMBA_LEAVES = ("mamba_in_weight", "mamba_conv_weight", "mamba_conv_bias",
                "mamba_a_log", "mamba_d_skip", "mamba_dt_bias",
                "mamba_norm_weight", "mamba_out_weight")
ATTN_LEAVES = ("attn_qkv_weight", "attn_out_weight")
MLP_LEAVES = ("mlp_norm_gamma", "mlp_gate_weight", "mlp_up_weight",
              "mlp_down_weight")


def layer_names(cfg, i):
    mixer = ATTN_LEAVES if is_attention(cfg, i) else MAMBA_LEAVES
    return ["layer%d_%s" % (i, s)
            for s in ("mixer_norm_gamma",) + mixer + MLP_LEAVES]


# the recipe (``param_specs`` says why)
QK_GAIN = 4.0           # of the fused q/k/v matrix: scores of spread 2
ATTN_OUT, MAMBA_OUT, MLP_OUT = 1.0, 1.0, 1.5
A_LOG = (math.log(4.0), 0.5)
DT_BIAS = (-4.6, 0.8)


def param_specs(cfg):
    """name -> (shape, recipe), named as ``get_granite_hybrid_lm`` names
    its arguments. Matrices N(0, 1/sqrt(fan_in)), so a normalized input
    gives outputs of unit scale; the matrix that writes the stream in each
    sublayer is scaled by ``stream_gain`` times a gain of its kind, so
    that each sublayer writes about unit scale before the program's own
    ``residual_multiplier`` and every kind writes a like share (Mamba-2's
    output is normalized and gated: 1; attention averages values that
    the q/k gain made 4 times larger: 1; silu(gate) * up shrinks: 1.5).
    The fused q/k/v matrix has ``QK_GAIN``: the published scores are
    scaled by 1/D, not 1/sqrt(D), and unit q and k would attend to every
    key alike; at 4 the scores have a spread of 2 and a position attends
    to dozens of keys.

    The head is the embedding (tied), so an embedding that is a large
    share of the final stream makes every token predict itself: the
    embedding is small (``embed_std``; it is multiplied by
    ``embedding_multiplier`` and every layer reads it normalized), and
    the eighty sublayers write the stream.

    The recurrence's vectors keep ``dt`` and the decay in range, as the
    family initialises them: ``exp(A_log)`` about 4 (1.5 to 11 at two
    sigma; the family draws A in [1, 16]), ``softplus(dt + dt_bias)``
    about 0.01 (0.001-0.1, the family's log-uniform range, with the
    projection's unit spread on top), so a state forgets over some tens
    of positions. The harness's recipes are normal, constant and
    normal-around: ``A_log`` is normal in the log where the family draws
    it log-uniform; ``D`` is 1 +- 0.05 (the family's ones)."""
    c = _dims(cfg)
    e, din, f = c["e"], c["din"], c["f"]
    cw = din + 2 * c["g"] * c["sn"]
    gain = cfg["stream_gain"]

    def mat(shape, fan_in, g=1.0):
        return (tuple(shape), ("normal", g / math.sqrt(fan_in)))

    near1 = ("around", 1.0, 0.05)
    specs = {"embed_weight": ((c["v"], e), ("normal", cfg["embed_std"])),
             "final_norm_gamma": ((e,), near1)}
    for i in range(c["n"]):
        p = "layer%d_" % i
        specs[p + "mixer_norm_gamma"] = ((e,), near1)
        if is_attention(cfg, i):
            qkv = (c["h"] + 2 * c["hkv"]) * c["d"]
            specs[p + "attn_qkv_weight"] = mat((qkv, e), e, QK_GAIN)
            specs[p + "attn_out_weight"] = mat((e, c["h"] * c["d"]),
                                               c["h"] * c["d"],
                                               ATTN_OUT * gain)
        else:
            specs[p + "mamba_in_weight"] = mat((din + cw + c["sh"], e), e)
            specs[p + "mamba_conv_weight"] = ((cw, c["taps"]),
                                              ("normal", 0.5))
            specs[p + "mamba_conv_bias"] = ((cw,), ("normal", 0.1))
            specs[p + "mamba_a_log"] = ((c["sh"],), ("around",) + A_LOG)
            specs[p + "mamba_d_skip"] = ((c["sh"],), near1)
            specs[p + "mamba_dt_bias"] = ((c["sh"],),
                                          ("around",) + DT_BIAS)
            specs[p + "mamba_norm_weight"] = ((din,), near1)
            specs[p + "mamba_out_weight"] = mat((e, din), din,
                                                MAMBA_OUT * gain)
        specs[p + "mlp_norm_gamma"] = ((e,), near1)
        specs[p + "mlp_gate_weight"] = mat((f, e), e)
        specs[p + "mlp_up_weight"] = mat((f, e), e)
        specs[p + "mlp_down_weight"] = mat((e, f), f, MLP_OUT * gain)
    return specs


def aux_specs(cfg):
    return {}


def build_symbol(mx, cfg, traffic):
    import mxnet_tpu.models  # noqa: F401 (mx.models)
    c = _dims(cfg)
    if c["g"] != 1:
        raise ValueError("granite: mamba_n_groups=%d; the program and the "
                         "reference have one group of B and C" % c["g"])
    return mx.models.get_granite_hybrid_lm(
        c["v"], cfg["layer_types"], embed_dim=c["e"], num_heads=c["h"],
        num_kv_heads=c["hkv"], mlp_hidden=c["f"], mamba_heads=c["sh"],
        mamba_head_dim=c["sp"], mamba_state=c["sn"], conv_kernel=c["taps"], chunk_size=cfg["mamba_chunk_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        eps=cfg["rms_norm_eps"], impl=traffic.get("attention", "flash"),
        loss_layout=traffic.get("loss_layout", "reference"))


# -- the plain reference ---------------------------------------------------------

def _rms(x, g, eps):
    import jax.numpy as jnp
    from jax import lax
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * g


def _mm(x, w, precision):
    """``x [..., k] . w [n, k]^T`` with both operands in the control's
    precision."""
    from benchmark.harness import fake_quant
    return fake_quant(x, precision) @ fake_quant(w, precision).T


def reference_mamba(h, p, cfg, precision=None):
    """The Mamba-2 mixer's ``out`` on normalized ``h`` [B, T, E]: the
    recurrence position by position."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant
    c = _dims(cfg)
    hh, pp, nn, din, taps = c["sh"], c["sp"], c["sn"], c["din"], c["taps"]
    fw = din + 2 * nn
    b, t, _ = h.shape
    zxd = _mm(h, p["mamba_in_weight"], precision)
    z, u, dt = zxd[..., :din], zxd[..., din:din + fw], zxd[..., din + fw:]
    # causal depthwise convolution with a bias:
    # xBC_t = silu(sum_j w[:, j] u_{t-(taps-1)+j} + bias)
    up = jnp.concatenate([jnp.zeros((b, taps - 1, fw), u.dtype),
                          fake_quant(u, precision)], axis=1)
    w = fake_quant(p["mamba_conv_weight"], precision)
    xbc = jax.nn.silu(sum(w[:, j] * up[:, j:j + t] for j in range(taps))
                      + p["mamba_conv_bias"])
    x = xbc[..., :din].reshape(b, t, hh, pp)
    bb, cc = xbc[..., din:din + nn], xbc[..., din + nn:]
    dt = jax.nn.softplus(dt + p["mamba_dt_bias"])              # [B, T, H]
    a = -jnp.exp(p["mamba_a_log"])

    def step(s, xs):
        x_t, b_t, c_t, dt_t = xs                 # [B,H,P] [B,N] [B,N] [B,H]
        s = s * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((b, hh, pp, nn), jnp.float32),
                        tuple(jnp.moveaxis(v, 1, 0)
                              for v in (x, bb, cc, dt)))
    y = jnp.moveaxis(y, 0, 1) + p["mamba_d_skip"][:, None] * x
    g = _rms(y.reshape(b, t, din) * jax.nn.silu(z),
             p["mamba_norm_weight"], cfg["rms_norm_eps"])
    return _mm(g, p["mamba_out_weight"], precision)


def reference_attention(h, p, cfg, precision=None, block=512):
    """The attention mixer's ``out`` on normalized ``h`` [B, T, E]: no
    positions, scores times ``attention_multiplier``, ``block`` queries
    at a time so that long sequences fit."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant
    c = _dims(cfg)
    hq, hkv, d, e = c["h"], c["hkv"], c["d"], c["e"]
    g = hq // hkv
    b, t, _ = h.shape
    qkv = _mm(h, p["attn_qkv_weight"], precision)
    q = qkv[..., :hq * d].reshape(b, t, hq, d)
    k = qkv[..., hq * d:hq * d + hkv * d].reshape(b, t, hkv, d)
    v = qkv[..., hq * d + hkv * d:].reshape(b, t, hkv, d)
    kq, vq = fake_quant(k, precision), fake_quant(v, precision)
    kpos = jnp.arange(t)
    scale = float(cfg["attention_multiplier"])

    def attend(args):
        qb, start = args                              # [B, n, Hq, D]
        n = qb.shape[1]
        s = jnp.einsum("bqjgd,bkjd->bjgqk",
                       fake_quant(qb.reshape(b, n, hkv, g, d), precision),
                       kq) * scale
        ok = kpos[None, :] <= (start + jnp.arange(n))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("bjgqk,bkjd->bqjgd", fake_quant(pr, precision),
                          vq).reshape(b, n, hq, d)

    if t > block and t % block == 0:
        nb = t // block
        o = jax.lax.map(attend, (
            jnp.moveaxis(q.reshape(b, nb, block, hq, d), 1, 0),
            jnp.arange(nb) * block))
        o = jnp.moveaxis(o, 0, 1).reshape(b, t, hq, d)
    else:
        o = attend((q, 0))
    return _mm(o.reshape(b, t, e), p["attn_out_weight"], precision)


def reference_mlp(h, p, precision=None):
    import jax
    return _mm(jax.nn.silu(_mm(h, p["mlp_gate_weight"], precision))
               * _mm(h, p["mlp_up_weight"], precision),
               p["mlp_down_weight"], precision)


def reference_layer(x, p, cfg, attention, precision=None):
    """One layer on [B, T, E]; ``p`` maps the short names of the leaves
    to the leaves."""
    eps, m = cfg["rms_norm_eps"], float(cfg["residual_multiplier"])
    mixer = reference_attention if attention else reference_mamba
    x = x + m * mixer(_rms(x, p["mixer_norm_gamma"], eps), p, cfg,
                      precision)
    return x + m * reference_mlp(_rms(x, p["mlp_norm_gamma"], eps), p,
                                 precision)


def reference_logits(tokens, make_leaves, cfg, precision=None):
    """The reference's logits over ``tokens`` ([K, L] int32), layer by
    layer: ``make_leaves(names)`` hands over the named leaves in float32,
    so one layer's weights are on the device at a time (the tied matrix
    is made twice, for the embedding and for the head). The caller sets
    full matmul precision; ``precision`` makes it the control, at every
    matmul's and convolution's operands."""
    import jax
    x = jax.jit(lambda t, w: w[t] * float(cfg["embedding_multiplier"]))(
        tokens, make_leaves(["embed_weight"])["embed_weight"])
    layer = {a: jax.jit(lambda v, p, a=a: reference_layer(v, p, cfg, a,
                                                          precision))
             for a in (False, True)}
    for i in range(cfg["num_hidden_layers"]):
        p = _short(make_leaves(layer_names(cfg, i)), i)
        x = layer[is_attention(cfg, i)](x, p)
        del p
    w = make_leaves(["final_norm_gamma", "embed_weight"])
    return jax.jit(lambda v, g, e: _mm(
        _rms(v, g, cfg["rms_norm_eps"]), e, precision)
        / float(cfg["logits_scaling"]))(
            x, w["final_norm_gamma"], w["embed_weight"])


def _short(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


# -- operations and bytes from shapes ------------------------------------------------

def layer_kinds(cfg):
    """(Mamba-2 layers, attention layers) of the layers run here."""
    attn = sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - attn, attn


def mamba_params(cfg):
    """Weights of one Mamba-2 mixer: the input projection, the
    convolution with its bias, the three vectors of a head, the norm,
    the output projection."""
    c = _dims(cfg)
    fw = c["din"] + 2 * c["g"] * c["sn"]
    return c["e"] * (c["din"] + fw + c["sh"]) + fw * (c["taps"] + 1) \
        + 3 * c["sh"] + c["din"] + c["din"] * c["e"]


def attn_params(cfg):
    """Weights of one attention mixer: q, k and v, the output
    projection."""
    c = _dims(cfg)
    return c["e"] * (c["h"] + 2 * c["hkv"]) * c["d"] \
        + c["h"] * c["d"] * c["e"]


def mlp_params(cfg):
    """Weights of one layer's MLP: gate, up and down."""
    c = _dims(cfg)
    return 3 * c["e"] * c["f"]


def ssm_state_macs_per_token(cfg):
    """The recurrence for one token: the rank-one write ``dt x B^T`` and
    the read ``S C`` of ``H`` states of ``P x N`` (the decay is a
    scaling)."""
    c = _dims(cfg)
    return 2 * c["sh"] * c["sp"] * c["sn"]


def decode_flops(cfg, live_tokens, live_rows):
    """Operations of decoding ``live_tokens`` tokens that between them
    attend to ``live_rows`` cache rows: every layer's mixer and MLP for
    each token, the tied head over the vocabulary, and in the attention
    layers scores and values over the ``Hq D`` query lanes."""
    c = _dims(cfg)
    ssm, attn = layer_kinds(cfg)
    per_token = ssm * (mamba_params(cfg) + ssm_state_macs_per_token(cfg)) \
        + attn * attn_params(cfg) + c["n"] * mlp_params(cfg) \
        + c["e"] * c["v"]
    return 2.0 * (live_tokens * per_token
                  + attn * 2 * live_rows * c["h"] * c["d"])


def decode_cache_bytes_per_row(cfg, itemsize=2):
    """Bytes of K and V that one live cache row holds over the attention
    layers (the Mamba-2 layers hold no rows): ``Hkv D`` lanes each."""
    c = _dims(cfg)
    return itemsize * 2 * layer_kinds(cfg)[1] * c["hkv"] * c["d"]


def ssm_state_bytes(cfg, itemsize=2):
    """Bytes of recurrent state one sequence holds in ONE Mamba-2 layer
    whatever its length: the float32 state [H, P, N] and the
    convolution's ``kernel - 1`` rows of ``xBC`` (in the cache's
    type)."""
    c = _dims(cfg)
    fw = c["din"] + 2 * c["g"] * c["sn"]
    return 4 * c["sh"] * c["sp"] * c["sn"] + itemsize * (c["taps"] - 1) * fw


def state_bytes_per_slot(cfg, itemsize=2):
    """Bytes of recurrent state one sequence holds over every Mamba-2
    layer."""
    return layer_kinds(cfg)[0] * ssm_state_bytes(cfg, itemsize)


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight as served: the tied matrix once."""
    return itemsize * sum(math.prod(shape)
                          for shape, _ in param_specs(cfg).values())
