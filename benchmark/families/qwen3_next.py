"""The Qwen3-Next family (Qwen; Qwen3-Next-80B-A3B-Instruct's
``config.json``): three Gated DeltaNet (linear-attention; Yang et al.,
arXiv:2412.06464) layers to one gated softmax-attention layer, every layer
followed by routed SiLU experts (top-k of a linear router's softmax,
renormalized) plus one shared expert behind a sigmoid gate; RMSNorm, no
biases, untied head, rotary on part of each attention head. How the program
is asked for it (``get_qwen3_next_lm``), the weights' recipe, the plain
float32 reference of the equations below, and operations and bytes from
shapes.

The equations (``E`` hidden; ``L`` layers, layer ``i`` an attention layer
where ``(i + 1) % full_attention_interval == 0``):

* layer: ``x <- x + mixer(RMSNorm(x))``; ``x <- x + moe(RMSNorm(x))``;
  ends: ``logits = W_head RMSNorm(x)``. RMSNorm is ``x / rms(x) * gamma``,
  eps ``rms_norm_eps``.
* Gated DeltaNet (``Hk`` key heads of ``Dk``, ``Hv`` value heads of ``Dv``,
  kernel 4): ``[q; k; v; z] = W_qkvz h``, ``[b; a] = W_ba h``;
  ``c_t = silu(sum_{j=0..3} w[:, j] u_{t-3+j})`` over ``u = [q; k; v]``
  (depthwise, causal, zeros before position 0), split back into q, k, v;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` per
  value head; q and k repeated to ``Hv`` heads (key head j serves value
  heads ``[j Hv/Hk, (j+1) Hv/Hk)``), each ``x / sqrt(sum x^2 + 1e-6)``, q
  times ``Dk ** -0.5``; per head, ``S_{-1} = 0``:
  ``S' = exp(g_t) S_{t-1}``; ``d_t = beta_t (v_t - S'^T k_t)``;
  ``S_t = S' + k_t d_t^T``; ``o_t = S_t^T q_t``;
  ``y = W_out [rmsnorm(o_t) w_norm silu(z_t)]``, the norm over ``Dv``.
* gated attention (``H`` query / ``Hkv`` kv heads of ``D``):
  ``[q_h; gate_h] = W_q h`` per head, ``k = W_k h``, ``v = W_v h``; RMSNorm
  over ``D`` on q and k per head; rotary on the first
  ``partial_rotary_factor D`` dims at ``rope_theta``; causal
  softmax(q . k / sqrt(D)) v, grouped; ``y = W_o [attn sigmoid(gate)]``.
* experts: ``p = softmax(W_r h)`` over all ``X`` published experts; the
  ``top_k`` largest, their weights renormalized to sum 1; expert
  ``W_down (silu(W_gate x) * W_up x)``; ``out = sum over the chosen experts
  THAT ARE HELD HERE + sigmoid(w_sg . h) shared(h)``.

Departures from the published description, each the program's too:
* the checkpoint's zero-centred norm weights ``w`` (``x / rms(x) (1 + w)``)
  are stored as ``gamma = 1 + w``: a storage choice, the same function;
  DeltaNet's ``w_norm`` is plain as published;
* ``W_qkvz`` and ``W_ba`` hold their rows in the flat order ``[q; k; v; z]``
  and ``[b; a]``; the checkpoint interleaves them per key head: a
  permutation of rows;
* ``num_experts`` of the configuration file counts the experts HELD here
  (the first ``num_experts`` of ``published.num_experts``): the router has
  every published row, what the absent experts would have added is left
  out, here and in the program alike; the vocabulary is the chip's slice;
* no multi-token-prediction module (not in ``config``, off the plain decode
  path).

The reference imports nothing of ``mxnet_tpu``; only the parameter names
are the program's.
"""
from __future__ import annotations

import math


def _dims(cfg):
    return {"v": cfg["vocab_size"], "n": cfg["num_hidden_layers"],
            "e": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "hk": cfg["linear_num_key_heads"],
            "hv": cfg["linear_num_value_heads"],
            "dk": cfg["linear_key_head_dim"],
            "dv": cfg["linear_value_head_dim"],
            "taps": cfg["linear_conv_kernel_dim"],
            "held": cfg["num_experts"],
            "x": cfg["published"]["num_experts"],
            "first": cfg.get("expert_first", 0),
            "f": cfg["moe_intermediate_size"],
            "s": cfg["shared_expert_intermediate_size"],
            "k": cfg["num_experts_per_tok"],
            "every": cfg["full_attention_interval"]}


def is_attention(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


GDN_LEAVES = ("gdn_qkvz_weight", "gdn_ba_weight", "gdn_conv_weight",
              "gdn_a_log", "gdn_dt_bias", "gdn_norm_weight",
              "gdn_out_weight")
ATTN_LEAVES = ("attn_q_weight", "attn_k_weight", "attn_v_weight",
               "attn_q_norm", "attn_k_norm", "attn_out_weight")
MOE_LEAVES = ("moe_norm_gamma", "moe_gate_weight", "expert_w1", "expert_w2",
              "shared_w1", "shared_w2", "shared_gate")


def layer_names(cfg, i):
    mixer = ATTN_LEAVES if is_attention(cfg, i) else GDN_LEAVES
    return ["layer%d_%s" % (i, s)
            for s in ("mixer_norm_gamma",) + mixer + MOE_LEAVES]


# the recipe (``param_specs`` says why): what each kind of sublayer's last
# matrix is scaled by, on top of ``stream_gain / sqrt(2 layers)``
GDN_OUT, ATTN_OUT, ROUTED_OUT, SHARED_OUT = 1.5, 4.0, 6.0, 3.0
QK_NORM = 1.4           # mean of the q and k norms' scales
A_LOG = (math.log(4.0), 0.5)
DT_BIAS = (-4.6, 0.8)


def param_specs(cfg):
    """name -> (shape, recipe), named as ``get_qwen3_next_lm`` names its
    arguments. Matrices N(0, 1/sqrt(fan_in)), so a normalized input gives
    outputs of unit scale; the matrix that writes the stream in each
    sublayer is scaled by ``stream_gain / sqrt(2 layers)`` times a gain
    of its kind, so that the sixteen sublayers together write a stream of
    about the embedding's size and every kind writes a like share of it
    (read on the CPU at these widths, float32, 256 positions; ``assumed.
    weights`` has the numbers): DeltaNet's output is normalized per head
    and gated by ``silu(z)`` (1.5); attention averages its values over
    its context (4, and its q and k norms' scales are ``QK_NORM``, so that
    the scores have a spread of 2 and a position attends to dozens of
    keys, not thousands); the held experts' part is a quarter of ten
    renormalized choices (6); the shared expert sits behind a sigmoid
    (3).

    The recurrence's two vectors keep ``g`` away from 0 and from -inf,
    as the family initialises them: ``exp(A_log)`` about 4 (1.5 to 11 at
    two sigma), ``softplus(a + dt_bias)`` about 0.01 (0.001-0.1), so a
    state forgets over some tens of positions. The harness's recipes are
    normal, constant and normal-around: ``A_log`` is normal in the log
    where the family draws it log-uniform.

    Routing has to come out alike under every seed, because a decode step
    streams the experts it touches and nothing else of them (PERF.md
    section 6, PR 28). A linear router over a normalized stream is
    balanced if the stream differs from token to token: the embedding is
    of unit scale (``embed_std`` 1; the head is untied, so a large
    embedding does not make a token predict itself) and stays a large
    part of what every layer's router reads; the ids are uniform, so each
    token's ten experts are a fresh draw."""
    c = _dims(cfg)
    e, n = c["e"], c["n"]
    kw, vw = c["hk"] * c["dk"], c["hv"] * c["dv"]
    fw = 2 * kw + vw
    out = cfg["stream_gain"] / math.sqrt(2.0 * n)

    def mat(shape, fan_in, gain=1.0):
        return (tuple(shape), ("normal", gain / math.sqrt(fan_in)))

    near1 = ("around", 1.0, 0.05)
    specs = {"embed_weight": ((c["v"], e), ("normal", cfg["embed_std"])),
             "lm_head_weight": mat((c["v"], e), e),
             "final_norm_gamma": ((e,), near1)}
    for i in range(n):
        p = "layer%d_" % i
        specs[p + "mixer_norm_gamma"] = ((e,), near1)
        if is_attention(cfg, i):
            hd = c["h"] * c["d"]
            specs[p + "attn_q_weight"] = mat((2 * hd, e), e)
            specs[p + "attn_k_weight"] = mat((c["hkv"] * c["d"], e), e)
            specs[p + "attn_v_weight"] = mat((c["hkv"] * c["d"], e), e)
            specs[p + "attn_q_norm"] = ((c["d"],),
                                        ("around", QK_NORM, 0.05))
            specs[p + "attn_k_norm"] = ((c["d"],),
                                        ("around", QK_NORM, 0.05))
            specs[p + "attn_out_weight"] = mat((e, hd), hd, ATTN_OUT * out)
        else:
            specs[p + "gdn_qkvz_weight"] = mat((fw + vw, e), e)
            specs[p + "gdn_ba_weight"] = mat((2 * c["hv"], e), e)
            specs[p + "gdn_conv_weight"] = ((fw, c["taps"]),
                                            ("normal", 0.5))
            specs[p + "gdn_a_log"] = ((c["hv"],), ("around",) + A_LOG)
            specs[p + "gdn_dt_bias"] = ((c["hv"],), ("around",) + DT_BIAS)
            specs[p + "gdn_norm_weight"] = ((c["dv"],), near1)
            specs[p + "gdn_out_weight"] = mat((e, vw), vw, GDN_OUT * out)
        specs[p + "moe_norm_gamma"] = ((e,), near1)
        specs[p + "moe_gate_weight"] = mat((c["x"], e), e,
                                           cfg["router_gain"])
        specs[p + "expert_w1"] = mat((c["held"], 2 * c["f"], e), e)
        specs[p + "expert_w2"] = mat((c["held"], e, c["f"]), c["f"],
                                     ROUTED_OUT * out)
        specs[p + "shared_w1"] = mat((2 * c["s"], e), e)
        specs[p + "shared_w2"] = mat((e, c["s"]), c["s"], SHARED_OUT * out)
        specs[p + "shared_gate"] = mat((1, e), e)
    return specs


def aux_specs(cfg):
    return {}


def build_symbol(mx, cfg, traffic):
    import mxnet_tpu.models  # noqa: F401 (mx.models)
    c = _dims(cfg)
    return mx.models.get_qwen3_next_lm(
        c["v"], num_layers=c["n"], embed_dim=c["e"], num_heads=c["h"],
        num_kv_heads=c["hkv"], head_dim=c["d"], linear_k_heads=c["hk"],
        linear_v_heads=c["hv"], linear_k_dim=c["dk"], linear_v_dim=c["dv"],
        num_experts=c["x"], expert_hidden=c["f"], top_k=c["k"],
        shared_hidden=c["s"], full_attention_interval=c["every"],
        conv_kernel=c["taps"], experts_held=c["held"],
        expert_first=c["first"],
        rotary_dim=int(c["d"] * cfg["partial_rotary_factor"]),
        rope_base=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
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


def reference_gdn(h, p, cfg, precision=None):
    """The Gated DeltaNet mixer's ``y`` on normalized ``h`` [B, T, E]: the
    recurrence position by position."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant
    c = _dims(cfg)
    hk, hv, dk, dv, taps = c["hk"], c["hv"], c["dk"], c["dv"], c["taps"]
    kw, vw = hk * dk, hv * dv
    fw = 2 * kw + vw
    b, t, _ = h.shape
    qkvz = _mm(h, p["gdn_qkvz_weight"], precision)
    ba = _mm(h, p["gdn_ba_weight"], precision)
    u, z = qkvz[..., :fw], qkvz[..., fw:]
    # causal depthwise convolution: c_t = sum_j w[:, j] u_{t-(taps-1)+j}
    up = jnp.concatenate([jnp.zeros((b, taps - 1, fw), u.dtype),
                          fake_quant(u, precision)], axis=1)
    w = fake_quant(p["gdn_conv_weight"], precision)
    conv = jax.nn.silu(sum(w[:, j] * up[:, j:j + t] for j in range(taps)))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                 + 1e-6)
    rep = hv // hk
    q = jnp.repeat(unit(conv[..., :kw].reshape(b, t, hk, dk)), rep, 2) \
        * dk ** -0.5
    k = jnp.repeat(unit(conv[..., kw:2 * kw].reshape(b, t, hk, dk)), rep, 2)
    v = conv[..., 2 * kw:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["gdn_a_log"]) * jax.nn.softplus(ba[..., hv:]
                                                   + p["gdn_dt_bias"])

    def step(s, xs):
        q_t, k_t, v_t, b_t, g_t = xs                  # [B, H, .]
        s = s * jnp.exp(g_t)[..., None, None]
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32),
                        tuple(jnp.moveaxis(x, 1, 0)
                              for x in (q, k, v, beta, g)))
    o = jnp.moveaxis(o, 0, 1)                         # [B, T, Hv, Dv]
    o = _rms(o, p["gdn_norm_weight"], cfg["rms_norm_eps"]) \
        * jax.nn.silu(z.reshape(b, t, hv, dv))
    return _mm(o.reshape(b, t, vw), p["gdn_out_weight"], precision)


def reference_attention(h, p, cfg, precision=None, block=512):
    """The gated attention mixer's ``y`` on normalized ``h`` [B, T, E]:
    per head, ``block`` queries at a time so that long sequences fit."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import fake_quant
    c = _dims(cfg)
    hq, hkv, d = c["h"], c["hkv"], c["d"]
    g = hq // hkv
    b, t, _ = h.shape
    eps = cfg["rms_norm_eps"]
    qg = _mm(h, p["attn_q_weight"], precision).reshape(b, t, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _mm(h, p["attn_k_weight"], precision).reshape(b, t, hkv, d)
    v = _mm(h, p["attn_v_weight"], precision).reshape(b, t, hkv, d)
    rot = int(d * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_theta"])
    q = _rotary(_rms(q, p["attn_q_norm"], eps), theta, rot)
    k = _rotary(_rms(k, p["attn_k_norm"], eps), theta, rot)
    kq, vq = fake_quant(k, precision), fake_quant(v, precision)
    kpos = jnp.arange(t)

    def attend(args):
        qb, start = args                              # [B, n, Hq, D]
        n = qb.shape[1]
        s = jnp.einsum("bqjgd,bkjd->bjgqk",
                       fake_quant(qb.reshape(b, n, hkv, g, d), precision),
                       kq) / math.sqrt(d)
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
    o = o * jax.nn.sigmoid(gate)
    return _mm(o.reshape(b, t, hq * d), p["attn_out_weight"], precision)


def reference_moe(h, p, cfg, precision=None):
    """The experts' ``out`` on normalized ``h``: the router over all the
    published experts in float32 (never the control's precision: what it
    decides is discrete), the ten largest renormalized, the held ones
    among them expert by expert under a mask (every held expert computes
    every token here, which is what makes it plain), and the shared
    expert."""
    import jax
    import jax.numpy as jnp
    c = _dims(cfg)
    f, s = c["f"], c["s"]
    probs = jax.nn.softmax(h @ p["moe_gate_weight"].T, axis=-1)
    top, idx = jax.lax.top_k(probs, c["k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, c["x"], dtype=probs.dtype)
                   * top[..., None], axis=-2)             # [B, T, X]
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
    shared = _mm(jax.nn.silu(up[..., :s]) * up[..., s:], p["shared_w2"],
                 precision)
    return y + jax.nn.sigmoid(h @ p["shared_gate"].T) * shared


def reference_layer(x, p, cfg, attention, precision=None):
    """One layer on [B, T, E]; ``p`` maps the short names of the leaves
    to the leaves."""
    eps = cfg["rms_norm_eps"]
    mixer = reference_attention if attention else reference_gdn
    x = x + mixer(_rms(x, p["mixer_norm_gamma"], eps), p, cfg, precision)
    return x + reference_moe(_rms(x, p["moe_norm_gamma"], eps), p, cfg,
                             precision)


def reference_logits(tokens, make_leaves, cfg, precision=None):
    """The reference's logits over ``tokens`` ([K, L] int32), layer by
    layer: ``make_leaves(names)`` hands over the named leaves in float32,
    so one layer's weights are on the device at a time. The caller sets
    full matmul precision; ``precision`` makes it the control, at every
    matmul's and convolution's operands but the router's."""
    import jax
    x = jax.jit(lambda t, w: w[t])(
        tokens, make_leaves(["embed_weight"])["embed_weight"])
    layer = {a: jax.jit(lambda v, p, a=a: reference_layer(v, p, cfg, a,
                                                          precision))
             for a in (False, True)}
    for i in range(cfg["num_hidden_layers"]):
        p = _short(make_leaves(layer_names(cfg, i)), i)
        x = layer[is_attention(cfg, i)](x, p)
        del p
    w = make_leaves(["final_norm_gamma", "lm_head_weight"])
    return jax.jit(lambda v, g, e: _mm(
        _rms(v, g, cfg["rms_norm_eps"]), e, precision))(
            x, w["final_norm_gamma"], w["lm_head_weight"])


def _short(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items()
            if k.startswith(pre)}


# -- operations and bytes from shapes ------------------------------------------------

def layer_kinds(cfg):
    """(DeltaNet layers, attention layers) of the layers run here."""
    attn = sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - attn, attn


def gdn_params(cfg):
    """Weights of one DeltaNet mixer: its two projections, the
    convolution, the two vectors, the norm, the output projection."""
    c = _dims(cfg)
    kw, vw = c["hk"] * c["dk"], c["hv"] * c["dv"]
    fw = 2 * kw + vw
    return c["e"] * (fw + vw) + c["e"] * 2 * c["hv"] + fw * c["taps"] \
        + 2 * c["hv"] + c["dv"] + vw * c["e"]


def attn_params(cfg):
    """Weights of one attention mixer: q with its gate, k, v, the two
    norms, the output projection."""
    c = _dims(cfg)
    hd = c["h"] * c["d"]
    return c["e"] * (2 * hd + 2 * c["hkv"] * c["d"]) + 2 * c["d"] \
        + hd * c["e"]


def moe_macs_per_token(cfg):
    """Multiply-accumulates of one token in one layer's experts: the
    router over all published experts, the shared expert and its gate,
    and the token's held share of its ``num_experts_per_tok`` experts
    (``held / published`` of them: the others run on the absent chips)."""
    c = _dims(cfg)
    share = c["k"] * c["held"] / float(c["x"])
    return c["e"] * c["x"] + 3 * c["e"] * c["s"] + c["e"] \
        + share * 3 * c["e"] * c["f"]


def gdn_state_macs_per_token(cfg):
    """The recurrence for one token: ``S'^T k``, ``S'^T q``, the decay and
    the rank-one update of ``Hv`` states of ``Dk x Dv``."""
    c = _dims(cfg)
    return 4 * c["hv"] * c["dk"] * c["dv"]


def decode_flops(cfg, live_tokens, live_rows):
    """Operations of decoding ``live_tokens`` tokens that between them
    attend to ``live_rows`` cache rows: every layer's mixer and experts
    for each token, the head over the vocabulary's slice, and in the
    attention layers scores and values over the ``H D`` query lanes."""
    c = _dims(cfg)
    gdn, attn = layer_kinds(cfg)
    per_token = gdn * (gdn_params(cfg) + gdn_state_macs_per_token(cfg)) \
        + attn * attn_params(cfg) + c["n"] * moe_macs_per_token(cfg) \
        + c["e"] * c["v"]
    return 2.0 * (live_tokens * per_token
                  + attn * 2 * live_rows * c["h"] * c["d"])


def decode_cache_bytes_per_row(cfg, itemsize=2):
    """Bytes of K and V that one live cache row holds over the attention
    layers (the DeltaNet layers hold no rows): ``Hkv D`` lanes each."""
    c = _dims(cfg)
    return itemsize * 2 * layer_kinds(cfg)[1] * c["hkv"] * c["d"]


def state_bytes_per_slot(cfg, itemsize=2):
    """Bytes of recurrent state one sequence holds whatever its length:
    in every DeltaNet layer the float32 matrix state and the convolution's
    ``kernel - 1`` rows of inputs (in the cache's type)."""
    c = _dims(cfg)
    fw = 2 * c["hk"] * c["dk"] + c["hv"] * c["dv"]
    return layer_kinds(cfg)[0] * (4 * c["hv"] * c["dk"] * c["dv"]
                                  + itemsize * (c["taps"] - 1) * fw)


def expert_bytes(cfg, itemsize=2):
    """Bytes of one routed expert's three matrices."""
    c = _dims(cfg)
    return itemsize * 3 * c["e"] * c["f"]


def moe_decode_bytes(cfg, experts_touched, itemsize=2):
    """Bytes of routed-expert weights one decode step has to read: in
    every layer the matrices of the held experts that were given a token
    (``experts_touched``: their mean number per layer and step, from the
    program's counter), once."""
    return cfg["num_hidden_layers"] * experts_touched \
        * expert_bytes(cfg, itemsize)


def gdn_decode_bytes(cfg, slots_advanced, itemsize=2):
    """Bytes the DeltaNet layers have to move in one decode step: each
    layer's weights once, and for each slot whose state the step advanced
    (``slots_advanced``: their mean number per layer and step, from the
    program's counter) the state read once and written once. The work,
    whatever implements it."""
    gdn, _ = layer_kinds(cfg)
    return gdn * itemsize * gdn_params(cfg) \
        + slots_advanced * 2 * state_bytes_per_slot(cfg, itemsize)


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight as served."""
    return itemsize * sum(math.prod(shape)
                          for shape, _ in param_specs(cfg).values())
