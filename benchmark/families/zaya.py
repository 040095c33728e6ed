"""The ZAYA family (Zyphra; ZAYA1-8B's ``config.json``): every layer a
compressed-convolutional-attention (CCA, arXiv:2510.04476) sublayer and a
routed-experts sublayer with an MLP router, both merged into the stream
with learned scales; RMSNorm, no biases, tied embedding and head, rotary
on part of each head. How the program is asked for it
(``get_zaya_lm``), the weights' recipe, the plain float32 reference of
the equations below, and operations and bytes from shapes.

The equations (``E`` hidden, ``Hq``/``Hkv`` heads of ``D``, ``X`` experts
of width ``F``, router width ``R``; ``Q = Hq D``, ``K = Hkv D``):

* merge (both sublayers): ``x <- (x + b_r) s_r + (y + b_y) s_y``.
* CCA: ``h = RMSNorm(x)``; ``u_t = [W_q h_t ; W_k h_t]``;
  ``v_t = [W_v1 h_t ; W_v2 h_{t-1}]``;
  ``c1_t = a0 u_t + a1 u_{t-1} + d1`` (depthwise);
  ``c2_t[g] = B0_g c1_t[g] + B1_g c1_{t-1}[g] + d2_g`` per head, the
  sequences padded with zeros on the left;
  ``m_t[i] = (q~_t[i] + k~_t[i // G]) / 2``; ``q = c2[:Q] + m``;
  ``k[j] = c2[Q:][j] + mean_i m[i]``; per head ``q <- sqrt(D) q/|q|``,
  ``k <- sqrt(D) exp(tau_j) k/|k|``; rotary on the first
  ``partial_rotary_factor D`` dims; causal softmax(q k / sqrt(D)) v,
  grouped; ``y = W_o o``.
* experts: ``h = RMSNorm(x)``; ``r = W_d h``;
  ``r <- r + gamma r_prev_layer``;
  ``z = W_3 gelu(W_2 gelu(W_1 RMSNorm(r)))``; ``p = softmax(z)``;
  ``e* = argmax(p + beta)``;
  ``y = p[e*] W_down,e*(silu(W_gate,e* h) * W_up,e* h)``.
* ends: ``logits = embed . RMSNorm(x)``.

The reference imports nothing of ``mxnet_tpu``; only the parameter names
are the program's.
"""
from __future__ import annotations

import math


def _dims(cfg):
    return {"v": cfg["vocab_size"], "n": cfg["num_hidden_layers"],
            "e": cfg["hidden_size"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "x": cfg["num_experts"], "f": cfg["moe_intermediate_size"],
            "r": cfg["router_hidden_size"],
            "k": cfg["num_experts_per_tok"]}


LAYER_LEAVES = (
    "attn_norm_gamma", "cca_qk_weight", "cca_v_weight", "cca_conv0_weight",
    "cca_conv0_bias", "cca_conv1_weight", "cca_conv1_bias", "cca_temp",
    "cca_out_weight", "attn_res_bias", "attn_res_scale", "attn_out_bias",
    "attn_out_scale", "moe_norm_gamma", "router_down_weight",
    "router_mix", "router_norm_gamma", "router_fc1_weight",
    "router_fc2_weight", "router_fc3_weight", "router_balance",
    "expert_w1", "expert_w2", "moe_res_bias", "moe_res_scale",
    "moe_out_bias", "moe_out_scale")


def layer_names(i):
    return ["layer%d_%s" % (i, s) for s in LAYER_LEAVES
            if not (i == 0 and s == "router_mix")]


# the routing recipe (``param_specs`` says why): gains of the router's
# three matrices, the gain of the layers' own ``W_d`` above layer 0, the
# scale of every shift and bias, and the mean of CCA's temperature
ROUTER_GAINS = (0.1, 0.1, 800.0)
ROUTER_DOWN_REST = 0.1
BIAS_STD = 0.005
CCA_TEMP = 1.0      # mean of tau: exp(1) sharpens the scores (``param_specs``)


def param_specs(cfg):
    """name -> (shape, recipe), named as ``get_zaya_lm`` names its
    arguments. Matrices N(0, 1/sqrt(fan_in)), so a normalized input
    gives outputs of unit scale; the two matrices that write the stream
    (CCA's output projection, the experts' down projection) are scaled
    by ``stream_gain / sqrt(2 layers)``, times 3 and 4 (attention
    averages its values down, the gate's probability and silu shrink
    the experts'), so that the sublayers' sum, not the embedding,
    carries the stream and both kinds write about as much of it: with
    a tied head an embedding that is a large share of the final stream
    makes every token predict itself (``assumed.embed_std``). Norm
    scales and merge scales near 1, merge shifts and conv biases small
    but not zero, so that a dropped one shows; the experts' branch
    scale ``s_y`` is ``moe_branch_scale`` (0.25), not 1: top-1 routing
    is discontinuous, at 1 a flipped choice replaces a sixth of the
    stream, the routers downstream re-roll, and the logits of that
    token decorrelate from the reference's for the program and its
    fp8 control alike (``assumed.moe_branch_scale`` has the chip's
    readings).

    The router's recipe makes every seed route alike, because a decode
    step streams the experts it touches and nothing else of them: how
    many a step touches IS its work (``assumed.routing``). A trained
    router is balanced, its ``beta`` exists for that, and chooses by
    the token. Random matrices do neither by themselves: ``gelu`` of a
    unit input has a mean, which the last matrix turns into one
    constant per expert as large as the part that varies (one expert
    then takes a third of all tokens, another none, differently in
    every layer and seed), and a stream that attention has averaged
    over hundreds of rows changes little from token to token (a slot
    then keeps its expert for its whole life). So: the router's two
    inner matrices are small (``ROUTER_GAINS``, its MLP starts in
    ``gelu``'s linear part, mean a tenth of spread) and the last one
    large (logits of std about 2); layer 0's ``r``, read where the
    stream is still the token's own and exact (its embedding and what
    one attention made of it), is carried up the
    stack by ``gamma`` near 1 while the layers above add a tenth of
    their own (``ROUTER_DOWN_REST``); merge shifts and conv biases are
    small beside what layer 0 writes (``BIAS_STD``); and ``tau`` is
    ``CCA_TEMP`` +- 0.2, so that a position's own key, which shares the
    q-k mean with its query, takes a large share of its attention and
    the stream follows the token (at ``tau`` 0 the final stream of
    one position and the next have a cosine of 0.98, and greedy
    decoding emits one token for ever; at 2 the stream is the token's
    own but the scores are so sharp that bf16 decorrelates the logits:
    ``logit_gap`` 5 for program and control alike). The embedding is
    small (``embed_std``): what it adds to its own token's logit is
    what makes greedy decoding repeat its input."""
    c = _dims(cfg)
    e, d, f, r, x = c["e"], c["d"], c["f"], c["r"], c["x"]
    q, kv = c["hq"] * d, c["hkv"] * d
    w, g = q + kv, c["hq"] + c["hkv"]
    out = cfg["stream_gain"] / math.sqrt(2.0 * c["n"])

    def mat(shape, fan_in, gain=1.0):
        return (tuple(shape), ("normal", gain / math.sqrt(fan_in)))

    near1 = ("around", 1.0, 0.05)
    small = ("normal", BIAS_STD)
    g1, g2, g3 = ROUTER_GAINS
    specs = {"embed_weight": ((c["v"], e), ("normal", cfg["embed_std"])),
             "final_norm_gamma": ((e,), near1)}
    for i in range(c["n"]):
        p = "layer%d_" % i
        specs[p + "attn_norm_gamma"] = ((e,), near1)
        specs[p + "cca_qk_weight"] = mat((w, e), e)
        specs[p + "cca_v_weight"] = mat((kv, e), e)
        specs[p + "cca_conv0_weight"] = ((w, 2), ("around", 0.6, 0.3))
        specs[p + "cca_conv0_bias"] = ((w,), small)
        specs[p + "cca_conv1_weight"] = mat((g, 2, d, d), d)
        specs[p + "cca_conv1_bias"] = ((w,), small)
        specs[p + "cca_temp"] = ((c["hkv"],), ("around", CCA_TEMP, 0.2))
        specs[p + "cca_out_weight"] = mat((e, q), q, 3.0 * out)
        specs[p + "moe_norm_gamma"] = ((e,), near1)
        specs[p + "router_down_weight"] = mat(
            (r, e), e, ROUTER_DOWN_REST if i else 1.0)
        if i:
            specs[p + "router_mix"] = ((r,), near1)
        specs[p + "router_norm_gamma"] = ((r,), near1)
        specs[p + "router_fc1_weight"] = mat((r, r), r, g1)
        specs[p + "router_fc2_weight"] = mat((r, r), r, g2)
        specs[p + "router_fc3_weight"] = mat((x, r), r, g3)
        specs[p + "router_balance"] = ((x,), ("normal", 0.01))
        specs[p + "expert_w1"] = mat((x, 2 * f, e), e)
        specs[p + "expert_w2"] = mat((x, e, f), f, 4.0 * out)
        for s in ("attn", "moe"):
            specs[p + s + "_res_bias"] = ((e,), small)
            specs[p + s + "_res_scale"] = ((e,), near1)
            specs[p + s + "_out_bias"] = ((e,), small)
        specs[p + "attn_out_scale"] = ((e,), near1)
        b = cfg["moe_branch_scale"]
        specs[p + "moe_out_scale"] = ((e,), ("around", b, 0.05 * b))
    return specs


def aux_specs(cfg):
    return {}


def build_symbol(mx, cfg, traffic):
    import mxnet_tpu.models  # noqa: F401 (mx.models)
    c = _dims(cfg)
    rope = cfg["rope_parameters"]["hybrid"]
    return mx.models.get_zaya_lm(
        c["v"], num_layers=c["n"], embed_dim=c["e"], num_heads=c["hq"],
        num_kv_heads=c["hkv"], head_dim=c["d"], num_experts=c["x"],
        expert_hidden=c["f"], router_hidden=c["r"], top_k=c["k"],
        rotary_dim=int(c["d"] * rope["partial_rotary_factor"]),
        rope_base=float(rope["rope_theta"]), eps=cfg["rms_norm_eps"],
        impl=traffic.get("attention", "flash"),
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


def _merge(x, y, p, s):
    return (x + p[s + "_res_bias"]) * p[s + "_res_scale"] \
        + (y + p[s + "_out_bias"]) * p[s + "_out_scale"]


def _shift(z):
    """The sequence one position later, a zero in front: z_{t-1}."""
    import jax.numpy as jnp
    return jnp.concatenate([jnp.zeros_like(z[:, :1]), z[:, :-1]], axis=1)


def _rotary(z, theta, rot):
    """Half-split rotary on the first ``rot`` dims of [B, T, H, D]."""
    import jax.numpy as jnp
    t = z.shape[1]
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(ang)[None, :, None]
    sin = jnp.sin(ang)[None, :, None]
    a, b, rest = z[..., :half], z[..., half:rot], z[..., rot:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def reference_cca(h, p, cfg, precision=None):
    """The CCA sublayer's ``y`` on normalized ``h`` [B, T, E]."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant
    c = _dims(cfg)
    hq, hkv, d = c["hq"], c["hkv"], c["d"]
    q_w, g = hq * d, hq // hkv
    b, t, _ = h.shape
    u = _mm(h, p["cca_qk_weight"], precision)              # [B,T,Q+K]
    vv = _mm(h, p["cca_v_weight"], precision)              # [B,T,K]
    half = vv.shape[-1] // 2
    v = jnp.concatenate([vv[..., :half], _shift(vv[..., half:])], -1)
    a = p["cca_conv0_weight"]
    c1 = a[:, 0] * u + a[:, 1] * _shift(u) + p["cca_conv0_bias"]
    c1h = c1.reshape(b, t, hq + hkv, d)
    bw = p["cca_conv1_weight"]                             # [G,2,D,D]

    def heads(z, w):
        return jnp.einsum("btgi,goi->btgo", fake_quant(z, precision),
                          fake_quant(w, precision))
    c2 = heads(c1h, bw[:, 0]) + heads(_shift(c1h), bw[:, 1]) \
        + p["cca_conv1_bias"].reshape(hq + hkv, d)
    qt = u[..., :q_w].reshape(b, t, hkv, g, d)
    kt = u[..., q_w:].reshape(b, t, hkv, 1, d)
    m = (qt + kt) / 2.0
    q = c2[:, :, :hq] + m.reshape(b, t, hq, d)
    k = c2[:, :, hq:] + jnp.mean(m, axis=3)

    def unit(z):
        n = jnp.sqrt(jnp.sum(jnp.square(z), -1, keepdims=True))
        return math.sqrt(d) * z / jnp.maximum(n, 1e-12)
    q = unit(q)
    k = unit(k) * jnp.exp(p["cca_temp"])[:, None]
    rope = cfg["rope_parameters"]["hybrid"]
    rot = int(d * rope["partial_rotary_factor"])
    q = _rotary(q, float(rope["rope_theta"]), rot)
    k = _rotary(k, float(rope["rope_theta"]), rot)
    qg = q.reshape(b, t, hkv, g, d)
    s = jnp.einsum("bqjgd,bkjd->bjgqk", fake_quant(qg, precision),
                   fake_quant(k, precision)) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bjgqk,bkjd->bqjgd", fake_quant(pr, precision),
                   fake_quant(v.reshape(b, t, hkv, d), precision))
    return _mm(o.reshape(b, t, q_w), p["cca_out_weight"], precision)


def reference_router(h, r_prev, p, cfg):
    """(p [B, T, X], this layer's r) — float32, never the control's
    precision: what it decides is discrete."""
    import jax
    r = h @ p["router_down_weight"].T
    if r_prev is not None:
        r = r + p["router_mix"] * r_prev
    z = _rms(r, p["router_norm_gamma"], cfg["rms_norm_eps"])
    z = jax.nn.gelu(z @ p["router_fc1_weight"].T, approximate=False)
    z = jax.nn.gelu(z @ p["router_fc2_weight"].T, approximate=False)
    return jax.nn.softmax(z @ p["router_fc3_weight"].T, axis=-1), r


def reference_experts(h, probs, p, cfg, precision=None):
    """The routed experts' ``y``: each token through its top experts by
    ``probs + beta``, weighted by ``probs``. Expert by expert under a
    mask: every expert computes every token here, which is what makes
    it plain."""
    import jax
    import jax.numpy as jnp
    c = _dims(cfg)
    f = c["f"]
    _, idx = jax.lax.top_k(probs + p["router_balance"], c["k"])
    gate = jnp.sum(jax.nn.one_hot(idx, c["x"], dtype=probs.dtype), -2) \
        * probs                                            # [B,T,X]

    def one(y, args):
        w1, w2, ge = args
        up = _mm(h, w1, precision)
        act = jax.nn.silu(up[..., :f]) * up[..., f:]
        return y + ge[..., None] * _mm(act, w2, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["expert_w1"], p["expert_w2"],
                         jnp.moveaxis(gate, -1, 0)))
    return y


def reference_layer(x, r_prev, p, cfg, precision=None):
    """One layer on [B, T, E]; ``p`` maps the short names of
    ``LAYER_LEAVES`` to leaves. Returns (x, r)."""
    eps = cfg["rms_norm_eps"]
    y = reference_cca(_rms(x, p["attn_norm_gamma"], eps), p, cfg,
                      precision)
    x = _merge(x, y, p, "attn")
    h = _rms(x, p["moe_norm_gamma"], eps)
    probs, r = reference_router(h, r_prev, p, cfg)
    y = reference_experts(h, probs, p, cfg, precision)
    return _merge(x, y, p, "moe"), r


def reference_logits(tokens, make_leaves, cfg, precision=None):
    """The reference's logits over ``tokens`` ([K, L] int32), layer by
    layer: ``make_leaves(names)`` hands over the named leaves in float32,
    so one layer's weights are on the device at a time (the tied matrix
    is made twice, for the embedding and for the head). The caller sets
    full matmul precision; ``precision`` makes it the control, at every
    matmul operand but the router's."""
    import jax
    x = jax.jit(lambda t, w: w[t])(
        tokens, make_leaves(["embed_weight"])["embed_weight"])
    first = jax.jit(lambda v, p: reference_layer(v, None, p, cfg,
                                                 precision))
    rest = jax.jit(lambda v, r, p: reference_layer(v, r, p, cfg,
                                                   precision))
    r = None
    for i in range(cfg["num_hidden_layers"]):
        p = _short(make_leaves(layer_names(i)), i)
        x, r = first(x, p) if i == 0 else rest(x, r, p)
        del p
    w = make_leaves(["final_norm_gamma", "embed_weight"])
    return jax.jit(lambda v, g, e: _mm(
        _rms(v, g, cfg["rms_norm_eps"]), e, precision))(
            x, w["final_norm_gamma"], w["embed_weight"])


def _short(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


# -- operations and bytes from shapes ------------------------------------------------

def layer_macs_per_token(cfg):
    """Weight multiply-accumulates of one layer for one token: CCA's
    three projections and its per-head convolution, the router's four
    matrices, and the three matrices of each of the token's
    ``num_experts_per_tok`` experts (one here: the experts it is not
    routed to cost it nothing)."""
    c = _dims(cfg)
    e, d, r = c["e"], c["d"], c["r"]
    q, kv = c["hq"] * d, c["hkv"] * d
    cca = e * (q + kv) + e * kv + q * e + (c["hq"] + c["hkv"]) * 2 * d * d
    router = e * r + 2 * r * r + r * c["x"]
    return cca + router + c["k"] * 3 * e * c["f"]


def decode_flops(cfg, live_tokens, live_rows):
    """Operations of decoding ``live_tokens`` tokens that between them
    attend to ``live_rows`` cache rows (scores and values over the
    ``Hq D`` query lanes)."""
    c = _dims(cfg)
    q = c["hq"] * c["d"]
    return 2.0 * (live_tokens * (c["n"] * layer_macs_per_token(cfg)
                                 + c["e"] * c["v"])
                  + c["n"] * 2 * live_rows * q)


def decode_cache_bytes_per_row(cfg, itemsize=2):
    """Bytes of K and V that one live cache row holds over all layers:
    ``Hkv D`` lanes each."""
    c = _dims(cfg)
    return itemsize * 2 * c["n"] * c["hkv"] * c["d"]


def expert_bytes(cfg, itemsize=2):
    """Bytes of one expert's three matrices."""
    c = _dims(cfg)
    return itemsize * 3 * c["e"] * c["f"]


def moe_decode_bytes(cfg, experts_touched, itemsize=2):
    """Bytes of expert weights one decode step has to read: in every
    layer the matrices of the experts that were given a token
    (``experts_touched``: their mean number per layer and step, from the
    program's counter), once."""
    return cfg["num_hidden_layers"] * experts_touched \
        * expert_bytes(cfg, itemsize)


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight as served: the tied matrix once."""
    return itemsize * sum(math.prod(shape)
                          for shape, _ in param_specs(cfg).values())
