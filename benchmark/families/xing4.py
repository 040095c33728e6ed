"""The Xing4.0 family (XingChen-AGI; Xing4.0-29B-A4B's ``config.json``):
multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434 section 2.1)
under YaRN-scaled rotary positions, a residual stream of ``hc_mult`` lanes
mixed by manifold-constrained hyper-connections (arXiv:2512.24880, over Zhu
et al.'s hyper-connections, arXiv:2409.19606), leading dense SiLU layers,
then routed SiLU experts chosen top-k by sigmoid scores under a balancing
bias (``noaux_tc``, one group), renormalized and scaled, plus one ungated
shared expert; RMSNorm, no biases, untied head. How the program is asked for
it (``get_xing4_lm``), the weights' recipe, the plain float32 reference of
the equations below, and operations and bytes from shapes.

The equations (``E`` hidden, ``n`` lanes, ``H`` heads, ``L`` layers of which
the first ``first_k_dense_replace`` are dense):

* stream: ``X_0 = [e; e; ...; e]`` (the token's embedding in each of ``n``
  lanes, ``[n, E]``); every layer is two sublayers (attention; then the
  dense FFN or the experts), each inside its own hyper-connection; after
  the last layer ``x = sum over lanes of X``, ``logits = W_head RMSNorm(x)``.
  RMSNorm is ``x / rms(x) * gamma``, eps ``rms_norm_eps``.
* hyper-connection around a sublayer ``f`` (per token):
  ``z = flatten(X) / rms(flatten(X))`` (``[nE]``, eps ``rms_norm_eps``, no
  scale); ``[a_pre (n); a_post (n); a_res (n n)] = Phi z``, ``Phi``
  ``[2n + n n, nE]``; ``H_pre = sigmoid(alpha_0 a_pre + b_pre)``;
  ``H_post = 2 sigmoid(alpha_1 a_post + b_post)``;
  ``M_0 = exp(clip(alpha_2 mat(a_res) + B_res, -30, 30))``, then
  ``hc_sinkhorn_iters`` rounds of ``M <- M / (rowsum(M) + hc_eps)``;
  ``M <- M / (colsum(M) + hc_eps)``; ``H_res = M``;
  ``u = H_pre X`` (``[E]``); ``y = f(RMSNorm_f(u))``;
  ``X <- H_res X + outer(H_post, y)``. All of it in float32.
* latent attention: ``c_q = RMSNorm(W_dq h)`` (``q_lora_rank``); per head
  ``[q_n (Dn); q_r (Dr)] = W_uq c_q``; ``[c (R); k_r (Dr)] = W_dkv h``,
  ``c <- RMSNorm(c)``; ``q_r`` and ``k_r`` rotated by position (half-split
  pairs; YaRN frequencies: ``theta_i = base^(-i / (Dr/2))`` where pair ``i``
  turns more than ``beta_fast`` times over ``original_max_position_
  embeddings``, ``theta_i / factor`` where it turns fewer than
  ``beta_slow`` times, a linear blend between the pair indices those turn
  counts give; cos and sin unscaled since ``mscale = mscale_all_dim``);
  ``k_r`` is one vector for all heads; per head ``[k_n (Dn); v (Dv)] =
  W_ukv c``; ``score = (q_n . k_n + q_r . k_r) (Dn + Dr)^-0.5 m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``; causal softmax;
  ``out = W_o concat_h(sum p v)``. The EXPANDED form only: the program's
  decode step computes the absorbed form (``W_uk`` folded into the query,
  ``W_uv`` out of the mix of latents), the same function by other
  roundings, and is held to this one.
* experts: ``s = sigmoid(W_r h)`` over all the published experts, float32;
  the ``num_experts_per_tok`` largest of ``s + b`` chosen (ties by index);
  weights ``s_chosen / (sum s_chosen + 1e-20) * routed_scaling_factor``;
  expert ``W_down (silu(W_gate x) * W_up x)``; ``out = sum over the chosen
  experts THAT ARE HELD HERE + shared(h)``, the shared expert ungated.
  Dense layers: the same SiLU-gated form at width ``intermediate_size``.

Departures from the published description, each the program's too
(``assumed`` in the configuration file says where each comes from):
* ``n_routed_experts`` of the configuration file counts the experts HELD
  here (the first of ``published.n_routed_experts``): the router has every
  published row and routes over all of them, what the absent experts would
  have added is left out, here and in the program alike; the vocabulary is
  the chip's slice; ``first_k_dense_replace`` counts the dense layers RUN
  here (one of the published two);
* the multi-token-prediction module serves no token on the plain decode
  path and is left out (``num_nextn_predict_layers`` 0);
* the lanes enter as copies and leave as a sum (arXiv:2409.19606), one
  hyper-connection per sublayer, ``hc_eps`` in the Sinkhorn divisions, the
  clamp on the exponent: the row's ``config`` names the sizes, not where
  they sit;
* ``B_res`` is stored as its departure from ``hc_res_diag`` times the
  identity (``B_res = hc_res_diag I + stored``): a storage choice, the
  same function, so that a recipe of whole-leaf laws starts the mix near
  the identity;
* rotary pairs in the half-split order (a permutation of the rows of
  ``W_uq`` / ``W_dkv``); the q and kv norms are RMSNorm with a scale;
  ``W_gate`` and ``W_up`` of an expert are one leaf ``[2F, E]`` (the gate's
  rows first).

The reference imports nothing of ``mxnet_tpu``; only the parameter names
are the program's.
"""
from __future__ import annotations

import math


def _dims(cfg):
    rs = cfg["rope_scaling"]
    return {"v": cfg["vocab_size"], "n": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "e": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "rq": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "ffn": cfg["intermediate_size"],
            "held": cfg["n_routed_experts"],
            "x": cfg["published"]["n_routed_experts"],
            "first": cfg.get("expert_first", 0),
            "f": cfg["moe_intermediate_size"],
            "s": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "k": cfg["num_experts_per_tok"],
            "scale": float(cfg["routed_scaling_factor"]),
            "lanes": cfg["hc_mult"], "iters": cfg["hc_sinkhorn_iters"],
            "hc_eps": cfg["hc_eps"], "clamp": cfg["mhc_h_res_clamp_max"],
            "res_diag": cfg.get("hc_res_diag", 0.0),
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "yarn": (float(rs["factor"]),
                     int(rs["original_max_position_embeddings"]),
                     float(rs["beta_fast"]), float(rs["beta_slow"])),
            "mscale_all_dim": float(rs["mscale_all_dim"])}


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


HC_LEAVES = ("hc_phi", "hc_alpha", "hc_bias", "norm_gamma")
ATTN_LEAVES = ("attn_q_down_weight", "attn_q_norm", "attn_q_up_weight",
               "attn_kv_down_weight", "attn_kv_norm", "attn_kv_up_weight",
               "attn_out_weight")
DENSE_LEAVES = ("ffn_gate_weight", "ffn_up_weight", "ffn_down_weight")
MOE_LEAVES = ("router_weight", "router_balance", "expert_w1", "expert_w2",
              "shared_w1", "shared_w2")


def layer_names(cfg, i):
    ffn = DENSE_LEAVES if is_dense(cfg, i) else MOE_LEAVES
    return ["layer%d_%s" % (i, s)
            for s in tuple("attn_" + t for t in HC_LEAVES) + ATTN_LEAVES
            + tuple("ffn_" + t for t in HC_LEAVES) + ffn]


# the recipe (``param_specs`` says why): what each kind of sublayer's last
# matrix is scaled by, on top of ``stream_gain / sqrt(2 layers)``
ATTN_OUT, DENSE_OUT, ROUTED_OUT, SHARED_OUT = 4.0, 1.5, 1.5, 1.5
BALANCE_STD = 0.02


def param_specs(cfg):
    """name -> (shape, recipe), named as ``get_xing4_lm`` names its
    arguments. Matrices N(0, 1/sqrt(fan_in)), so a normalized input gives
    outputs of unit scale; the matrix that writes the stream in each
    sublayer is scaled by ``stream_gain / sqrt(2 layers)`` times a gain of
    its kind, so that the sublayers together write a stream of about the
    embedding's size: attention averages its values over its context (4;
    with unit q and k norms the scores have a spread of
    ``sqrt(Dn + Dr) (Dn + Dr)^-0.5 m^2`` = 2.0, so a position attends to
    dozens of keys, not thousands); the dense FFN and every expert, routed
    or shared, are ungated and take 1.5 alike: the routed experts' gain
    sets what a flipped fourth choice does to a logit (the fourth and
    fifth of 64 sigmoid scores lie 0.017 apart, a flip swaps an expert
    that weighs 0.5, and ``logit_gap`` is decided by such flips: PERF.md
    section 6, PR 36), and with one gain for every expert the uncut
    layer's four choices write about what its shared expert writes.

    Routing has to come out alike under every seed, because a decode step
    streams the experts it touches and nothing else of them (PERF.md
    section 6, PR 28): the embedding is of unit scale (``embed_std`` 1; the
    head is untied) and stays a large part of what every layer's router
    reads, the ids are uniform, so each token's four experts are a fresh
    draw. Router logits of unit spread (``router_gain`` 1): the four
    largest sigmoid scores of 64 lie in 0.8-0.93, so the four chosen
    weigh 0.22-0.28 each before the scale (a sigmoid's largest scores lie
    close together by nature: the 0.15-0.4 the issue names is where they
    may lie, not a spread to be forced by a router bias the model has
    not). The balancing bias is N(0, 0.02): the 4th and 5th scores differ
    by about as much, so it decides some choices and never a weight.

    The hyper-connection is kept honest: ``Phi`` N(0, 1/sqrt(nE)) on a
    normalized stream gives ``a`` of unit spread, the three ``alpha``
    0.5 +- 0.05, so ``alpha_2 a_res`` has a spread of about 0.5 and
    ``H_res`` differs from token to token; ``B_res`` is ``hc_res_diag``
    (2) on the diagonal (stored apart, see the module's departures) and
    N(0, 0.3) everywhere, so a lane keeps about two thirds of itself
    (0.69 +- 0.08 over tokens) and ``H_res`` is neither the identity nor
    uniform; ``b_pre`` and ``b_post`` the same N(0, 0.3): ``H_pre`` about
    0.5 +- 0.15, ``H_post`` about 1 +- 0.3. The issue asked for a spread
    of about 1; at 1 the twenty Sinkhorn rounds leave more than one token
    in a hundred with a row sum over 3e-3 from 1 (20,000 draws in numpy:
    worst 1.5e-2), at 0.5 the worst of 20,000 is 9e-4, and a mix that is
    doubly stochastic is the mechanism's point."""
    c = _dims(cfg)
    e, n, h = c["e"], c["n"], c["h"]
    ne = c["lanes"] * e
    nk = 2 * c["lanes"] + c["lanes"] ** 2
    out = cfg["stream_gain"] / math.sqrt(2.0 * n)

    def mat(shape, fan_in, gain=1.0):
        return (tuple(shape), ("normal", gain / math.sqrt(fan_in)))

    near1 = ("around", 1.0, 0.05)
    specs = {"embed_weight": ((c["v"], e), ("normal", cfg["embed_std"])),
             "lm_head_weight": mat((c["v"], e), e),
             "final_norm_gamma": ((e,), near1)}
    for i in range(n):
        p = "layer%d_" % i
        for sub in ("attn_", "ffn_"):
            specs[p + sub + "hc_phi"] = mat((nk, ne), ne)
            specs[p + sub + "hc_alpha"] = ((3,), ("around", 0.5, 0.05))
            specs[p + sub + "hc_bias"] = ((nk,), ("normal", 0.3))
            specs[p + sub + "norm_gamma"] = ((e,), near1)
        specs[p + "attn_q_down_weight"] = mat((c["rq"], e), e)
        specs[p + "attn_q_norm"] = ((c["rq"],), near1)
        specs[p + "attn_q_up_weight"] = mat(
            (h * (c["dn"] + c["dr"]), c["rq"]), c["rq"])
        specs[p + "attn_kv_down_weight"] = mat((c["r"] + c["dr"], e), e)
        specs[p + "attn_kv_norm"] = ((c["r"],), near1)
        specs[p + "attn_kv_up_weight"] = mat(
            (h * (c["dn"] + c["dv"]), c["r"]), c["r"])
        specs[p + "attn_out_weight"] = mat((e, h * c["dv"]), h * c["dv"],
                                           ATTN_OUT * out)
        if is_dense(cfg, i):
            specs[p + "ffn_gate_weight"] = mat((c["ffn"], e), e)
            specs[p + "ffn_up_weight"] = mat((c["ffn"], e), e)
            specs[p + "ffn_down_weight"] = mat((e, c["ffn"]), c["ffn"],
                                               DENSE_OUT * out)
        else:
            specs[p + "router_weight"] = mat((c["x"], e), e,
                                             cfg["router_gain"])
            specs[p + "router_balance"] = ((c["x"],),
                                           ("normal", BALANCE_STD))
            specs[p + "expert_w1"] = mat((c["held"], 2 * c["f"], e), e)
            specs[p + "expert_w2"] = mat((c["held"], e, c["f"]), c["f"],
                                         ROUTED_OUT * out)
            specs[p + "shared_w1"] = mat((2 * c["s"], e), e)
            specs[p + "shared_w2"] = mat((e, c["s"]), c["s"],
                                         SHARED_OUT * out)
    return specs


def aux_specs(cfg):
    return {}


def build_symbol(mx, cfg, traffic):
    import mxnet_tpu.models  # noqa: F401 (mx.models)
    c = _dims(cfg)
    return mx.models.get_xing4_lm(
        c["v"], num_layers=c["n"], embed_dim=c["e"], num_heads=c["h"],
        q_lora_rank=c["rq"], kv_lora_rank=c["r"], nope_dim=c["dn"],
        rope_dim=c["dr"], v_dim=c["dv"], ffn_hidden=c["ffn"],
        num_experts=c["x"], expert_hidden=c["f"], top_k=c["k"],
        shared_hidden=c["s"], dense_layers=c["dense"],
        route_scale=c["scale"], experts_held=c["held"],
        expert_first=c["first"], lanes=c["lanes"], hc_iters=c["iters"],
        hc_eps=c["hc_eps"], hc_clamp=float(c["clamp"]),
        hc_res_diag=c["res_diag"], rope_base=c["theta"], yarn=c["yarn"],
        mscale_all_dim=c["mscale_all_dim"], eps=c["eps"],
        impl=traffic.get("attention", "flash"),
        loss_layout=traffic.get("loss_layout", "reference"))


# -- the plain reference ---------------------------------------------------------

def _rms(x, g, eps):
    import jax.numpy as jnp
    from jax import lax
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y if g is None else y * g


def _mm(x, w, precision):
    """``x [..., k] . w [n, k]^T`` with both operands in the control's
    precision."""
    from benchmark.harness import fake_quant
    return fake_quant(x, precision) @ fake_quant(w, precision).T


def yarn_inv_freq(cfg):
    """The ``Dr / 2`` rotary frequencies under YaRN, float64 numpy, from
    the formula in the module's docstring."""
    import numpy as np
    c = _dims(cfg)
    factor, orig, fast, slow = c["yarn"]
    dim = c["dr"]
    half = dim // 2
    theta = c["theta"] ** (-np.arange(half, dtype=np.float64) / half)

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(c["theta"]))

    low = max(math.floor(pair_of(fast)), 0)
    high = min(math.ceil(pair_of(slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 0.001), 0, 1)
    return theta / factor * ramp + theta * (1.0 - ramp)


def _rotary(z, cfg):
    """Half-split rotary on the trailing ``Dr`` dims of [B, T, ..., Dr] at
    positions 0..T-1."""
    import jax.numpy as jnp
    t = z.shape[1]
    half = z.shape[-1] // 2
    freq = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    shape = (1, t) + (1,) * (z.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = z[..., :half], z[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def softmax_scale(cfg):
    c = _dims(cfg)
    m = 0.1 * c["mscale_all_dim"] * math.log(c["yarn"][0]) + 1.0
    return (c["dn"] + c["dr"]) ** -0.5 * m * m


def reference_attention(h, p, cfg, precision=None, block=256):
    """The latent attention's ``out`` on normalized ``h`` [B, T, E], in the
    EXPANDED form: every position's keys and values up-projected per head,
    ``block`` queries at a time so that long sequences fit."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant
    c = _dims(cfg)
    hq, dn, dr, dv, r = c["h"], c["dn"], c["dr"], c["dv"], c["r"]
    b, t, _ = h.shape
    cq = _rms(_mm(h, p["attn_q_down_weight"], precision), p["attn_q_norm"],
              c["eps"])
    q = _mm(cq, p["attn_q_up_weight"], precision).reshape(b, t, hq, dn + dr)
    qn, qr = q[..., :dn], _rotary(q[..., dn:], cfg)
    ckv = _mm(h, p["attn_kv_down_weight"], precision)
    lat = _rms(ckv[..., :r], p["attn_kv_norm"], c["eps"])
    kr = _rotary(ckv[..., r:], cfg)                        # [B, T, Dr]
    kv = _mm(lat, p["attn_kv_up_weight"], precision).reshape(
        b, t, hq, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    knq, krq, vq = (fake_quant(z, precision) for z in (kn, kr, v))
    kpos = jnp.arange(t)
    scale = softmax_scale(cfg)

    def attend(args):
        qnb, qrb, start = args                             # [B, n, H, .]
        n = qnb.shape[1]
        s = (jnp.einsum("bqhd,bkhd->bhqk", fake_quant(qnb, precision), knq)
             + jnp.einsum("bqhd,bkd->bhqk", fake_quant(qrb, precision),
                          krq)) * scale
        ok = kpos[None, :] <= (start + jnp.arange(n))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", fake_quant(pr, precision), vq)

    if t > block and t % block == 0:
        nb = t // block

        def blocks(z):
            return jnp.moveaxis(z.reshape((b, nb, block) + z.shape[2:]),
                                1, 0)
        o = jax.lax.map(attend, (blocks(qn), blocks(qr),
                                 jnp.arange(nb) * block))
        o = jnp.moveaxis(o, 0, 1).reshape(b, t, hq, dv)
    else:
        o = attend((qn, qr, 0))
    return _mm(o.reshape(b, t, hq * dv), p["attn_out_weight"], precision)


def reference_dense(h, p, cfg, precision=None):
    import jax
    act = jax.nn.silu(_mm(h, p["ffn_gate_weight"], precision)) \
        * _mm(h, p["ffn_up_weight"], precision)
    return _mm(act, p["ffn_down_weight"], precision)


def reference_route(h, p, cfg):
    """The router on normalized ``h``, float32, never the control's
    precision (what it decides is discrete): (the chosen experts' ids
    [B, T, k], their weights [B, T, k] after renormalization and the
    scale)."""
    import jax
    import jax.numpy as jnp
    c = _dims(cfg)
    s = jax.nn.sigmoid(h @ p["router_weight"].T)
    _, idx = jax.lax.top_k(s + p["router_balance"], c["k"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    return idx, top / (jnp.sum(top, -1, keepdims=True) + 1e-20) * c["scale"]


def reference_moe(h, p, cfg, precision=None):
    """The experts' ``out`` on normalized ``h``: the router over all the
    published experts, the held ones among the chosen expert by expert
    under a mask (every held expert computes every token here, which is
    what makes it plain), and the shared expert, ungated."""
    import jax
    import jax.numpy as jnp
    c = _dims(cfg)
    f, s = c["f"], c["s"]
    idx, top = reference_route(h, p, cfg)
    gate = jnp.sum(jax.nn.one_hot(idx, c["x"], dtype=top.dtype)
                   * top[..., None], axis=-2)                 # [B, T, X]
    gate = gate[..., c["first"]:c["first"] + c["held"]]

    def one(y, args):
        w1, w2, ge = args
        up = _mm(h, w1, precision)
        act = jax.nn.silu(up[..., :f]) * up[..., f:]
        return y + ge[..., None] * _mm(act, w2, precision), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["expert_w1"], p["expert_w2"],
                         jnp.moveaxis(gate, -1, 0)))
    up = _mm(h, p["shared_w1"], precision)
    return y + _mm(jax.nn.silu(up[..., :s]) * up[..., s:], p["shared_w2"],
                   precision)


def reference_hc(x, p, cfg, sub, precision=None):
    """The hyper-connection's coefficients for the stream ``x``
    [B, T, n, E] around sublayer ``sub`` ("attn" / "ffn"): (H_pre
    [B, T, n], H_post [B, T, n], H_res [B, T, n, n])."""
    import jax
    import jax.numpy as jnp
    c = _dims(cfg)
    n = c["lanes"]
    b, t = x.shape[:2]
    z = _rms(x.reshape(b, t, -1), None, c["eps"])
    a = _mm(z, p[sub + "_hc_phi"], precision)
    alpha, bias = p[sub + "_hc_alpha"], p[sub + "_hc_bias"]
    pre = jax.nn.sigmoid(alpha[0] * a[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[..., n:2 * n] + bias[n:2 * n])
    bres = bias[2 * n:].reshape(n, n) \
        + c["res_diag"] * jnp.eye(n, dtype=jnp.float32)
    m = jnp.exp(jnp.clip(alpha[2] * a[..., 2 * n:].reshape(b, t, n, n)
                         + bres, -c["clamp"], c["clamp"]))
    for _ in range(c["iters"]):
        m = m / (jnp.sum(m, -1, keepdims=True) + c["hc_eps"])
        m = m / (jnp.sum(m, -2, keepdims=True) + c["hc_eps"])
    return pre, post, m


def reference_layer(x, p, cfg, dense, precision=None):
    """One layer on the stream [B, T, n, E]; ``p`` maps the short names
    of the leaves to the leaves."""
    import jax.numpy as jnp
    eps = cfg["rms_norm_eps"]
    n = cfg["hc_mult"]
    ffn = reference_dense if dense else reference_moe
    for sub, f in (("attn", reference_attention), ("ffn", ffn)):
        pre, post, res = reference_hc(x, p, cfg, sub, precision)
        # the two mixes lane by lane, as sums of whole vectors: exact in
        # float32, no product to round
        u = sum(pre[..., j, None] * x[:, :, j] for j in range(n))
        y = f(_rms(u, p[sub + "_norm_gamma"], eps), p, cfg, precision)
        x = jnp.stack(
            [sum(res[..., i, j, None] * x[:, :, j] for j in range(n))
             + post[..., i, None] * y for i in range(n)], axis=2)
    return x


def reference_logits(tokens, make_leaves, cfg, precision=None):
    """The reference's logits over ``tokens`` ([K, L] int32), layer by
    layer: ``make_leaves(names)`` hands over the named leaves in float32,
    so one layer's weights are on the device at a time. The caller sets
    full matmul precision; ``precision`` makes it the control, at every
    matmul's operands but the router's."""
    import jax
    import jax.numpy as jnp
    n = cfg["hc_mult"]
    x = jax.jit(lambda t, w: jnp.repeat(w[t][:, :, None], n, axis=2))(
        tokens, make_leaves(["embed_weight"])["embed_weight"])
    layer = {d: jax.jit(lambda v, p, d=d: reference_layer(v, p, cfg, d,
                                                          precision))
             for d in (False, True)}
    for i in range(cfg["num_hidden_layers"]):
        p = _short(make_leaves(layer_names(cfg, i)), i)
        x = layer[is_dense(cfg, i)](x, p)
        del p
    w = make_leaves(["final_norm_gamma", "lm_head_weight"])
    return jax.jit(lambda v, g, e: _mm(
        _rms(jnp.sum(v, axis=2), g, cfg["rms_norm_eps"]), e, precision))(
            x, w["final_norm_gamma"], w["lm_head_weight"])


def _short(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


# -- operations and bytes from shapes ------------------------------------------------

def layer_kinds(cfg):
    """(dense layers, routed layers) of the layers run here."""
    return cfg["first_k_dense_replace"], \
        cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def attn_params(cfg):
    """Weights of one latent attention: both down-projections, both
    up-projections, the two norms, the output projection."""
    c = _dims(cfg)
    return c["e"] * c["rq"] + c["rq"] * c["h"] * (c["dn"] + c["dr"]) \
        + c["e"] * (c["r"] + c["dr"]) \
        + c["r"] * c["h"] * (c["dn"] + c["dv"]) + c["h"] * c["dv"] * c["e"] \
        + c["rq"] + c["r"]


def hc_params(cfg):
    """Weights of one hyper-connection: ``Phi``, the three ``alpha``, the
    biases, and the sublayer's own norm."""
    c = _dims(cfg)
    nk = 2 * c["lanes"] + c["lanes"] ** 2
    return nk * c["lanes"] * c["e"] + 3 + nk + c["e"]


def moe_macs_per_token(cfg):
    """Multiply-accumulates of one token in one routed layer's experts:
    the router over all published experts, the shared expert, and the
    token's held share of its ``num_experts_per_tok`` experts (``held /
    published`` of them: the others run on the absent chips)."""
    c = _dims(cfg)
    share = c["k"] * c["held"] / float(c["x"])
    return c["e"] * c["x"] + 3 * c["e"] * c["s"] \
        + share * 3 * c["e"] * c["f"]


def decode_flops(cfg, live_tokens, live_rows):
    """Operations of decoding ``live_tokens`` tokens that between them
    attend to ``live_rows`` cache rows: every layer's attention
    projections, hyper-connections and FFN or experts for each token, the
    head over the vocabulary's slice, and scores and values in the
    ABSORBED form the step runs (per head ``R + Dr`` and ``R``
    multiply-accumulates a row)."""
    c = _dims(cfg)
    dense, routed = layer_kinds(cfg)
    per_token = c["n"] * (attn_params(cfg) + 2 * hc_params(cfg)) \
        + dense * 3 * c["e"] * c["ffn"] + routed * moe_macs_per_token(cfg) \
        + c["e"] * c["v"]
    return 2.0 * (live_tokens * per_token
                  + c["n"] * live_rows * c["h"] * (2 * c["r"] + c["dr"]))


def decode_cache_bytes_per_row(cfg, itemsize=2):
    """Bytes one live cache row holds over all layers: ``R + Dr`` numbers
    a layer, key and value at once."""
    c = _dims(cfg)
    return itemsize * c["n"] * (c["r"] + c["dr"])


def expert_bytes(cfg, itemsize=2):
    """Bytes of one routed expert's three matrices."""
    c = _dims(cfg)
    return itemsize * 3 * c["e"] * c["f"]


def moe_decode_bytes(cfg, experts_touched, itemsize=2):
    """Bytes of routed-expert weights one decode step has to read: in
    every routed layer the matrices of the held experts that were given a
    token (``experts_touched``: their mean number per layer and step, from
    the program's counter), once."""
    return layer_kinds(cfg)[1] * experts_touched \
        * expert_bytes(cfg, itemsize)


def mla_decode_bytes(cfg, live_rows, live_slots, itemsize=2):
    """Bytes the latent attention layers have to move in one decode step:
    each layer's weights once, each live row (``live_rows``: the live
    slots' true lengths, their mean per layer and step, from the
    program's counter) ONCE (a row is key and value at once), and for
    each live slot its new row written. The work, whatever implements
    it."""
    c = _dims(cfg)
    row = itemsize * (c["r"] + c["dr"])
    return c["n"] * (itemsize * attn_params(cfg)
                     + (live_rows + live_slots) * row)


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight as served."""
    return itemsize * sum(math.prod(shape)
                          for shape, _ in param_specs(cfg).values())
