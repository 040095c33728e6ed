"""The Nemotron-H family (NVIDIA; NVIDIA-Nemotron-3-Super-120B-A12B's
``config.json``, ``model_type`` ``nemotron_h``): layers that are ONE
sublayer each, placed by ``hybrid_override_pattern``: Mamba-2 mixers (Dao &
Gu, arXiv:2405.21060) with ``n_groups`` groups of B and C, grouped-query
softmax attention without positions, and LatentMoE layers (relu² experts
in a latent space, chosen top-k by sigmoid scores under a correction bias,
renormalized and scaled, plus one relu² shared expert); RMSNorm, no
biases, untied head. How the program is asked for it (``get_nemotron_h_lm``),
the weights' recipe, the plain float32 reference of the equations below,
and operations and bytes from shapes.

The equations (``E`` hidden, every RMSNorm ``x / rms(x) * gamma`` with eps
``norm_eps``):

* ``x_0 = Embed(ids)``; every layer ``x <- x + f(RMSNorm(x))``, ``f`` by
  the layer's letter; ``logits = W_head RMSNorm(x)``.
* ``M``, Mamba-2 (``H`` heads of ``P``, state ``N``, ``G`` groups, kernel
  4, ``D_in = H P``, ``F = D_in + 2 G N``): ``[z; xBC; dt] = W_in h``;
  ``xBC_t = silu(sum_{j=0..3} w[:, j] u_{t-3+j} + bias)`` (depthwise,
  causal, zeros before position 0), split into ``x`` [H, P], ``B``
  [G, N], ``C`` [G, N]; per head ``dt = softplus(dt + dt_bias)``,
  ``a = -exp(A_log)``; head ``h`` of group ``g = h // (H / G)``, ``S_{-1}
  = 0``: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_{g,t}^T``,
  ``y_t = S_t C_{g,t} + D x_t``; ``out = W_out [gnorm(y * silu(z)) w]``,
  the gate first and the norm over each group's ``D_in / G`` channels.
* ``*``, attention (``Hq`` query / ``Hkv`` kv heads of ``D``, no rotary,
  no bias): ``[q; k; v] = W_qkv h``; causal ``softmax(q . k / sqrt(D)) v``,
  kv head j serving query heads ``[j Hq/Hkv, (j+1) Hq/Hkv)``; ``W_o``.
* ``E``, LatentMoE: ``s = sigmoid(W_r h)`` over all the published experts,
  float32; the ``num_experts_per_tok`` largest of ``s + b`` chosen (ties
  by index); weights ``w_i = s_i / (sum_chosen s + 1e-20) *
  routed_scaling_factor``; ``u = W_down h`` (``moe_latent_size``);
  ``r = sum over the chosen experts THAT ARE HELD HERE of w_i W2_i
  relu(W1_i u)^2``; ``out = W_up r + Ws2 relu(Ws1 h)^2``.

Departures from the published description, each the program's too
(``assumed`` in the configuration file says where each comes from):

* ``n_routed_experts`` of the configuration file counts the experts HELD
  here (the first of ``published.n_routed_experts``): the router has every
  published row and routes over all of them, what the absent experts would
  have added is left out, here and in the program alike; the vocabulary is
  the chip's slice; ``hybrid_override_pattern`` is the layers run here;
* the multi-token-prediction module serves no token on the plain decode
  path and is left out (``num_nextn_predict_layers`` 0);
* q_proj, k_proj and v_proj are stored as one ``[q; k; v]`` matrix; the
  reference runs the recurrence position by position (the published
  kernels and the program's prefill run it in chunks of ``chunk_size``:
  the same sums in another order).

The reference imports nothing of ``mxnet_tpu``; only the parameter names
are the program's.
"""
from __future__ import annotations

import math


def _dims(cfg):
    return {"v": cfg["vocab_size"], "n": cfg["num_hidden_layers"],
            "pattern": cfg["hybrid_override_pattern"],
            "e": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "sh": cfg["mamba_num_heads"], "sp": cfg["mamba_head_dim"],
            "sn": cfg["ssm_state_size"], "g": cfg["n_groups"],
            "taps": cfg["conv_kernel"], "chunk": cfg["chunk_size"],
            "din": cfg["mamba_num_heads"] * cfg["mamba_head_dim"],
            "held": cfg["n_routed_experts"],
            "x": cfg["published"]["n_routed_experts"],
            "first": cfg.get("expert_first", 0),
            "f": cfg["moe_intermediate_size"],
            "s": cfg["moe_shared_expert_intermediate_size"]
            * cfg["n_shared_experts"],
            "lat": cfg["moe_latent_size"],
            "k": cfg["num_experts_per_tok"],
            "scale": float(cfg["routed_scaling_factor"]),
            "eps": cfg["norm_eps"]}


def kind(cfg, i):
    """Layer ``i``'s letter: ``M``, ``*`` or ``E``."""
    return cfg["hybrid_override_pattern"][i]


MAMBA_LEAVES = ("mamba_in_weight", "mamba_conv_weight", "mamba_conv_bias",
                "mamba_a_log", "mamba_d_skip", "mamba_dt_bias",
                "mamba_norm_weight", "mamba_out_weight")
ATTN_LEAVES = ("attn_qkv_weight", "attn_out_weight")
MOE_LEAVES = ("router_weight", "router_bias", "latent_down_weight",
              "latent_up_weight", "expert_w1", "expert_w2", "shared_w1",
              "shared_w2")
LEAVES = {"M": MAMBA_LEAVES, "*": ATTN_LEAVES, "E": MOE_LEAVES}


def layer_names(cfg, i):
    return ["layer%d_%s" % (i, s)
            for s in ("norm_gamma",) + LEAVES[kind(cfg, i)]]


# the recipe (``param_specs`` says why): what each kind of sublayer's last
# matrix is scaled by, on top of ``stream_gain / sqrt(layers)``
MAMBA_OUT, ATTN_OUT, ROUTED_OUT, SHARED_OUT = 1.0, 4.0, 1.0, 1.0
A_LOG = (math.log(4.0), 0.5)
DT_BIAS = (-4.6, 0.8)
BIAS_STD = 0.02


def param_specs(cfg):
    """name -> (shape, recipe), named as ``get_nemotron_h_lm`` names its
    arguments. Matrices N(0, 1/sqrt(fan_in)), so a normalized input gives
    outputs of unit scale; the matrix that writes the stream in each
    sublayer is scaled by ``stream_gain / sqrt(layers)`` times a gain of
    its kind, so that the sublayers together write a stream of about the
    embedding's size: Mamba-2's output is normalized and gated (1);
    attention averages its values over its context (4: unit q and k
    scaled by 1/sqrt(D) give scores of spread 1); relu² of a unit normal
    has an rms of 1.22, so an expert writes about that. The latent
    up-projection and the shared expert take one gain (1), so that the
    uncut layer's routed part (22 weights of about 0.23, 1.22 x 1.07 =
    1.30) writes about what its shared expert writes (1.22); the gain
    also sets what a flipped 22nd choice does to a logit, and
    ``logit_gap`` is decided by such flips (PERF.md section 6).

    Routing has to come out alike under every seed, because a decode step
    streams the experts it touches and nothing else of them (PERF.md
    section 6): the embedding is of unit scale (``embed_std`` 1;
    the head is untied, so a large embedding does not make a token predict
    itself) and stays a large part of what every router reads; the ids
    are uniform, so each token's 22 experts are a fresh draw. Router
    logits of unit spread (``router_gain`` 1): the 22 largest sigmoid
    scores of 512 lie in 0.85-0.95, so the chosen weigh 0.21-0.25 each
    after the scale. The correction bias is N(0, 0.02): the scores near
    the 22nd lie some 0.003 apart, so it moves a choice by a few places
    and never a weight.

    The recurrence's vectors keep ``dt`` and the decay in range, as the
    family initialises them: ``exp(A_log)`` about 4 (1.5 to 11 at two
    sigma; the family draws A in [1, 16]), ``softplus(dt + dt_bias)``
    about 0.01 (0.001-0.1, the published ``time_step_min`` and
    ``time_step_max``, with the projection's unit spread on top), so a
    state forgets over some tens of positions. The harness's recipes are
    normal, constant and normal-around: ``A_log`` is normal in the log
    where the family draws it log-uniform; ``D`` is 1 +- 0.05 (the
    family's ones)."""
    c = _dims(cfg)
    e, din = c["e"], c["din"]
    cw = din + 2 * c["g"] * c["sn"]
    out = cfg["stream_gain"] / math.sqrt(c["n"])

    def mat(shape, fan_in, gain=1.0):
        return (tuple(shape), ("normal", gain / math.sqrt(fan_in)))

    near1 = ("around", 1.0, 0.05)
    specs = {"embed_weight": ((c["v"], e), ("normal", cfg["embed_std"])),
             "lm_head_weight": mat((c["v"], e), e),
             "final_norm_gamma": ((e,), near1)}
    for i in range(c["n"]):
        p = "layer%d_" % i
        specs[p + "norm_gamma"] = ((e,), near1)
        if kind(cfg, i) == "M":
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
                                                MAMBA_OUT * out)
        elif kind(cfg, i) == "*":
            hd = c["h"] * c["d"]
            specs[p + "attn_qkv_weight"] = mat(
                (hd + 2 * c["hkv"] * c["d"], e), e)
            specs[p + "attn_out_weight"] = mat((e, hd), hd, ATTN_OUT * out)
        else:
            specs[p + "router_weight"] = mat((c["x"], e), e,
                                             cfg["router_gain"])
            specs[p + "router_bias"] = ((c["x"],), ("normal", BIAS_STD))
            specs[p + "latent_down_weight"] = mat((c["lat"], e), e)
            specs[p + "latent_up_weight"] = mat((e, c["lat"]), c["lat"],
                                                ROUTED_OUT * out)
            specs[p + "expert_w1"] = mat((c["held"], c["f"], c["lat"]),
                                         c["lat"])
            specs[p + "expert_w2"] = mat((c["held"], c["lat"], c["f"]),
                                         c["f"])
            specs[p + "shared_w1"] = mat((c["s"], e), e)
            specs[p + "shared_w2"] = mat((e, c["s"]), c["s"],
                                         SHARED_OUT * out)
    return specs


def aux_specs(cfg):
    return {}


def build_symbol(mx, cfg, traffic):
    import mxnet_tpu.models  # noqa: F401 (mx.models)
    c = _dims(cfg)
    return mx.models.get_nemotron_h_lm(
        c["v"], c["pattern"], embed_dim=c["e"], num_heads=c["h"],
        num_kv_heads=c["hkv"], mamba_heads=c["sh"], mamba_head_dim=c["sp"],
        mamba_state=c["sn"], mamba_groups=c["g"], num_experts=c["x"],
        expert_hidden=c["f"], top_k=c["k"], shared_hidden=c["s"],
        latent=c["lat"], route_scale=c["scale"], experts_held=c["held"],
        expert_first=c["first"], conv_kernel=c["taps"],
        chunk_size=c["chunk"], eps=c["eps"],
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


def reference_mamba(h, p, cfg, precision=None):
    """The Mamba-2 mixer's ``out`` on normalized ``h`` [B, T, E]: the
    recurrence position by position, each head reading its group's B
    and C."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant
    c = _dims(cfg)
    hh, pp, nn, din, taps, g = (c["sh"], c["sp"], c["sn"], c["din"],
                                c["taps"], c["g"])
    fw = din + 2 * g * nn
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
    # B and C of each head: its group's
    bb = jnp.repeat(xbc[..., din:din + g * nn].reshape(b, t, g, nn),
                    hh // g, axis=2)
    cc = jnp.repeat(xbc[..., din + g * nn:].reshape(b, t, g, nn),
                    hh // g, axis=2)
    dt = jax.nn.softplus(dt + p["mamba_dt_bias"])              # [B, T, H]
    a = -jnp.exp(p["mamba_a_log"])

    def step(s, xs):
        x_t, b_t, c_t, dt_t = xs           # [B,H,P] [B,H,N] [B,H,N] [B,H]
        s = s * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((b, hh, pp, nn), jnp.float32),
                        tuple(jnp.moveaxis(v, 1, 0)
                              for v in (x, bb, cc, dt)))
    y = jnp.moveaxis(y, 0, 1) + p["mamba_d_skip"][:, None] * x
    gated = (y.reshape(b, t, din) * jax.nn.silu(z)).reshape(
        b, t, g, din // g)
    gn = _rms(gated, 1.0, c["eps"]).reshape(b, t, din) \
        * p["mamba_norm_weight"]
    return _mm(gn, p["mamba_out_weight"], precision)


def reference_attention(h, p, cfg, precision=None, block=512):
    """The attention mixer's ``out`` on normalized ``h`` [B, T, E]: no
    positions, scores at 1/sqrt(D), ``block`` queries at a time so that
    long sequences fit."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant
    c = _dims(cfg)
    hq, hkv, d = c["h"], c["hkv"], c["d"]
    g = hq // hkv
    b, t, _ = h.shape
    qkv = _mm(h, p["attn_qkv_weight"], precision)
    q = qkv[..., :hq * d].reshape(b, t, hq, d)
    k = qkv[..., hq * d:hq * d + hkv * d].reshape(b, t, hkv, d)
    v = qkv[..., hq * d + hkv * d:].reshape(b, t, hkv, d)
    kq, vq = fake_quant(k, precision), fake_quant(v, precision)
    kpos = jnp.arange(t)
    scale = 1.0 / math.sqrt(d)

    def attend(args):
        qb, start = args                              # [B, n, Hq, D]
        n = qb.shape[1]
        s = jnp.einsum("bqjgd,bkjd->bjgqk",
                       fake_quant(qb.reshape(b, n, hkv, g, d), precision),
                       kq) * scale
        ok = kpos[None, :] <= (start + jnp.arange(n))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("bjgqk,bkjd->bqjgd", fake_quant(pr, precision),
                          vq).reshape(b, n, hq * d)

    if t > block and t % block == 0:
        nb = t // block
        o = jax.lax.map(attend, (
            jnp.moveaxis(q.reshape(b, nb, block, hq, d), 1, 0),
            jnp.arange(nb) * block))
        o = jnp.moveaxis(o, 0, 1).reshape(b, t, hq * d)
    else:
        o = attend((q, 0))
    return _mm(o, p["attn_out_weight"], precision)


def reference_route(h, p, cfg):
    """The router on normalized ``h``, float32, never the control's
    precision (what it decides is discrete): (the chosen experts' ids
    [B, T, k], their weights [B, T, k] after renormalization and the
    scale)."""
    import jax
    import jax.numpy as jnp
    c = _dims(cfg)
    s = jax.nn.sigmoid(h @ p["router_weight"].T)
    _, idx = jax.lax.top_k(s + p["router_bias"], c["k"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    return idx, top / (jnp.sum(top, -1, keepdims=True) + 1e-20) * c["scale"]


def reference_moe(h, p, cfg, precision=None):
    """The LatentMoE's ``out`` on normalized ``h``: the router over all
    the published experts, the held ones among the chosen expert by
    expert in the latent space under a mask (every held expert computes
    every token here, which is what makes it plain), projected back
    once, and the shared expert on ``h`` itself."""
    import jax
    import jax.numpy as jnp
    c = _dims(cfg)
    idx, top = reference_route(h, p, cfg)
    gate = jnp.sum(jax.nn.one_hot(idx, c["x"], dtype=top.dtype)
                   * top[..., None], axis=-2)                 # [B, T, X]
    gate = gate[..., c["first"]:c["first"] + c["held"]]
    u = _mm(h, p["latent_down_weight"], precision)

    def one(r, args):
        w1, w2, ge = args
        act = jnp.square(jax.nn.relu(_mm(u, w1, precision)))
        return r + ge[..., None] * _mm(act, w2, precision), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p["expert_w1"], p["expert_w2"],
                         jnp.moveaxis(gate, -1, 0)))
    shared = jnp.square(jax.nn.relu(_mm(h, p["shared_w1"], precision)))
    return _mm(r, p["latent_up_weight"], precision) \
        + _mm(shared, p["shared_w2"], precision)


_MIXERS = {"M": reference_mamba, "*": reference_attention,
           "E": reference_moe}


def reference_layer(x, p, cfg, letter, precision=None):
    """One layer on [B, T, E]; ``p`` maps the short names of the leaves
    to the leaves."""
    return x + _MIXERS[letter](_rms(x, p["norm_gamma"], cfg["norm_eps"]),
                               p, cfg, precision)


def reference_logits(tokens, make_leaves, cfg, precision=None):
    """The reference's logits over ``tokens`` ([K, L] int32), layer by
    layer: ``make_leaves(names)`` hands over the named leaves in float32,
    so one layer's weights are on the device at a time. The caller sets
    full matmul precision; ``precision`` makes it the control, at every
    matmul's and convolution's operands but the router's."""
    import jax
    x = jax.jit(lambda t, w: w[t])(
        tokens, make_leaves(["embed_weight"])["embed_weight"])
    layer = {k: jax.jit(lambda v, p, k=k: reference_layer(v, p, cfg, k,
                                                          precision))
             for k in LEAVES}
    for i in range(cfg["num_hidden_layers"]):
        p = _short(make_leaves(layer_names(cfg, i)), i)
        x = layer[kind(cfg, i)](x, p)
        del p
    w = make_leaves(["final_norm_gamma", "lm_head_weight"])
    return jax.jit(lambda v, g, hd: _mm(_rms(v, g, cfg["norm_eps"]), hd,
                                        precision))(
        x, w["final_norm_gamma"], w["lm_head_weight"])


def _short(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


# -- operations and bytes from shapes ------------------------------------------------

def layer_kinds(cfg):
    """(Mamba-2 layers, attention layers, LatentMoE layers) of the layers
    run here."""
    pat = cfg["hybrid_override_pattern"]
    return pat.count("M"), pat.count("*"), pat.count("E")


def mamba_params(cfg):
    """Weights of one Mamba-2 mixer: the input projection, the
    convolution with its bias, the three vectors of a head, the grouped
    norm, the output projection."""
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


def expert_params(cfg):
    """Weights of one routed expert: up and down in the latent space."""
    c = _dims(cfg)
    return 2 * c["lat"] * c["f"]


def moe_params(cfg):
    """Weights of one LatentMoE as held here: the router over every
    published expert and its correction bias, the two latent projections,
    the shared expert, the held experts."""
    c = _dims(cfg)
    return c["x"] * (c["e"] + 1) + 2 * c["e"] * c["lat"] \
        + 2 * c["e"] * c["s"] + c["held"] * expert_params(cfg)


def ssm_state_macs_per_token(cfg):
    """The recurrence for one token: the rank-one write ``dt x B^T`` and
    the read ``S C`` of ``H`` states of ``P x N`` (the decay is a
    scaling)."""
    c = _dims(cfg)
    return 2 * c["sh"] * c["sp"] * c["sn"]


def moe_macs_per_token(cfg):
    """Multiply-accumulates of one token in one LatentMoE: the router over
    all published experts, the two latent projections (once a token), the
    shared expert, and the token's held share of its
    ``num_experts_per_tok`` experts (``held / published`` of them: the
    others run on the absent chips)."""
    c = _dims(cfg)
    share = c["k"] * c["held"] / float(c["x"])
    return c["e"] * c["x"] + 2 * c["e"] * c["lat"] + 2 * c["e"] * c["s"] \
        + share * expert_params(cfg)


def decode_flops(cfg, live_tokens, live_rows):
    """Operations of decoding ``live_tokens`` tokens that between them
    attend to ``live_rows`` cache rows: every layer's sublayer for each
    token, the head over the vocabulary's slice, and in the attention
    layers scores and values over the ``Hq D`` query lanes."""
    c = _dims(cfg)
    ssm, attn, moe = layer_kinds(cfg)
    per_token = ssm * (mamba_params(cfg) + ssm_state_macs_per_token(cfg)) \
        + attn * attn_params(cfg) + moe * moe_macs_per_token(cfg) \
        + c["e"] * c["v"]
    return 2.0 * (live_tokens * per_token
                  + attn * 2 * live_rows * c["h"] * c["d"])


def decode_cache_bytes_per_row(cfg, itemsize=2):
    """Bytes of K and V that one live cache row holds over the attention
    layers (the Mamba-2 layers hold no rows): ``Hkv D`` lanes each."""
    c = _dims(cfg)
    return itemsize * 2 * layer_kinds(cfg)[1] * c["hkv"] * c["d"]


def expert_bytes(cfg, itemsize=2):
    """Bytes of one routed expert's two latent matrices."""
    return itemsize * expert_params(cfg)


def moe_decode_bytes(cfg, experts_touched, itemsize=2):
    """Bytes of routed-expert weights one decode step has to read: in
    every LatentMoE layer the matrices of the held experts that were given
    a token (``experts_touched``: their mean number per layer and step,
    from the program's counter), once."""
    return layer_kinds(cfg)[2] * experts_touched \
        * expert_bytes(cfg, itemsize)


def ssm_state_bytes(cfg, itemsize=2):
    """Bytes of recurrent state one sequence holds in ONE Mamba-2 layer
    whatever its length: the float32 state [H, P, N] and the
    convolution's ``kernel - 1`` rows of ``xBC`` over the grouped ``F``
    (in the cache's type)."""
    c = _dims(cfg)
    fw = c["din"] + 2 * c["g"] * c["sn"]
    return 4 * c["sh"] * c["sp"] * c["sn"] + itemsize * (c["taps"] - 1) * fw


def state_bytes_per_slot(cfg, itemsize=2):
    """Bytes of recurrent state one sequence holds over every Mamba-2
    layer."""
    return layer_kinds(cfg)[0] * ssm_state_bytes(cfg, itemsize)


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight as served."""
    return itemsize * sum(math.prod(shape)
                          for shape, _ in param_specs(cfg).values())
