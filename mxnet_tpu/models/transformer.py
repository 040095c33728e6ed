"""Transformer language model (decoder-only) — the long-context flagship.

No reference counterpart (the reference's sequence model is the unrolled
LSTM, example/rnn/lstm.py); this is the model family that exercises the
TPU framework's long-context machinery: flash attention (Pallas), ring
sequence parallelism (parallel/ring.py) and the dp/tp sharding rules.
Built entirely from registered Symbol ops so it trains through
FeedForward or ParallelTrainer like every other zoo model.
"""
from __future__ import annotations

import os

from .. import symbol as sym


def _ln(data, name):
    """LayerNorm site. ``MXNET_DIAG_IDENTITY_LN=1`` replaces every
    LayerNorm in the model with identity — a DIAGNOSTIC knob for the
    perf-attribution A/B (doc/performance.md: bounding the
    LN/elementwise share of the step) — never a training mode (the
    un-normalized model diverges)."""
    if os.environ.get("MXNET_DIAG_IDENTITY_LN", "0") == "1":
        return data
    return sym.LayerNorm(data=data,
                         gamma=sym.Variable(name + "_gamma"),
                         beta=sym.Variable(name + "_beta"),
                         name=name)

__all__ = ["transformer_block", "moe_transformer_block",
           "get_transformer_lm", "zaya_block", "get_zaya_lm",
           "granite_hybrid_block", "get_granite_hybrid_lm", "tp_rules",
           "ep_rules"]


def _attn_sublayer(data, num_heads, name, causal, impl, dropout,
                   rope=False, num_kv_heads=0, window=0):
    """x + MHA(LN(x)) then LN — the shared attention half of a block."""
    ln1 = _ln(data, name + "_ln1")
    attn = sym.MultiHeadAttention(
        data=ln1,
        qkv_weight=sym.Variable(name + "_qkv_weight"),
        qkv_bias=sym.Variable(name + "_qkv_bias"),
        out_weight=sym.Variable(name + "_proj_weight"),
        out_bias=sym.Variable(name + "_proj_bias"),
        num_heads=num_heads, num_kv_heads=num_kv_heads, causal=causal,
        impl=impl, dropout=dropout, rope=rope, window=window,
        name=name + "_attn")
    x = data + attn
    ln2 = _ln(x, name + "_ln2")
    return x, ln2


def transformer_block(data, num_heads, hidden, embed_dim, name,
                      causal=True, impl="flash", dropout=0.0,
                      rope=False, num_kv_heads=0, window=0):
    """Pre-LN block: x + MHA(LN(x)); x + FFN(LN(x)). data: [B,T,E]."""
    x, ln2 = _attn_sublayer(data, num_heads, name, causal, impl, dropout,
                            rope=rope, num_kv_heads=num_kv_heads,
                            window=window)
    f1 = sym.FullyConnected(data=ln2, num_hidden=hidden,
                            name=name + "_ffn1", flatten=False)
    act = sym.Activation(data=f1, act_type="relu", name=name + "_ffn_relu")
    f2 = sym.FullyConnected(data=act, num_hidden=embed_dim,
                            name=name + "_ffn2", flatten=False)
    return x + f2


def moe_transformer_block(data, num_heads, hidden, embed_dim, num_experts,
                          name, causal=True, impl="flash", dropout=0.0,
                          moe_top_k=0, rope=False, num_kv_heads=0,
                          window=0):
    """Transformer block whose FFN is a mixture of experts (MoEFFN):
    shard the expert dim over ``ep`` (ep_rules) for expert parallelism.
    ``moe_top_k>0`` enables static-shaped top-k hard routing."""
    x, ln2 = _attn_sublayer(data, num_heads, name, causal, impl, dropout,
                            rope=rope, num_kv_heads=num_kv_heads,
                            window=window)
    moe = sym.MoEFFN(
        data=ln2,
        gate_weight=sym.Variable(name + "_gate_weight"),
        expert_w1=sym.Variable(name + "_expert_w1"),
        expert_b1=sym.Variable(name + "_expert_b1"),
        expert_w2=sym.Variable(name + "_expert_w2"),
        expert_b2=sym.Variable(name + "_expert_b2"),
        num_experts=num_experts, hidden=hidden, top_k=moe_top_k,
        name=name + "_moe")
    return x + moe


def get_transformer_lm(vocab_size, num_layers=2, embed_dim=128, num_heads=4,
                       ffn_hidden=None, seq_len=None, impl="flash",
                       dropout=0.0, num_experts=0, pipeline_stages=None,
                       moe_top_k=0, loss_layout="reference",
                       pos_encoding="learned", num_kv_heads=0,
                       window=0):
    """Decoder-only LM: Embedding -> N blocks -> tied-free FC -> softmax
    over vocab per position (multi_output SoftmaxOutput, the reference's
    per-position softmax mode, softmax_output-inl.h multi_output).

    ``pipeline_stages=S`` tags every node with ``ctx_group='stage<K>'``
    (the reference's model-parallel graph-cut attribute,
    graph_executor.cc:341-458): embedding with the first block group,
    final LN + head + loss with the last; blocks spread evenly. The
    tagged symbol drives ``parallel.PipelineTrainer``.

    ``loss_layout``: "reference" (default) swaps the [B,T,V] logits to
    [B,V,T] and uses the reference's multi_output per-position softmax
    (output [B,V,T]). "flat" reshapes to [B*T,V] and applies the plain
    softmax along the LAST (lane-aligned) axis — identical loss and
    gradients without transposing the vocab-sized logits tensor
    (output [B*T,V]). "ce" ends in the fused ``SoftmaxCELoss`` head:
    the output is the per-token LOSS [B*T] (f32) and the vocab-sized
    probability tensor is never materialized — identical parameter
    updates (the loss gradient is SoftmaxOutput's), but consumers that
    need probabilities (accuracy metrics, predict) should use the other
    layouts.

    ``pos_encoding``: "learned" (default) adds the trained absolute
    pos_embed table; "rope" rotates q/k inside every attention instead
    (rotary/RoFormer — relative positions, no table, so decoding is not
    bounded by a trained length).

    ``num_kv_heads`` (0 = ``num_heads``): grouped-query attention —
    K/V projected to fewer heads, shrinking the decoder's K/V cache by
    the group factor (see MultiHeadAttention).

    ``window`` (0 = unlimited): sliding-window attention in every
    block; the decode cache becomes an O(window) ring buffer (pair
    with ``pos_encoding="rope"`` for unbounded-length generation).
    """
    from ..attribute import AttrScope

    if pos_encoding not in ("learned", "rope"):
        raise ValueError("pos_encoding must be 'learned' or 'rope', "
                         "got %r" % (pos_encoding,))
    if loss_layout not in ("reference", "flat", "ce"):
        raise ValueError("loss_layout must be 'reference', 'flat' or "
                         "'ce', got %r" % (loss_layout,))
    if ffn_hidden is None:
        ffn_hidden = 4 * embed_dim

    def scope(i=None, last=False):
        if not pipeline_stages:
            return AttrScope()
        if last:
            s = pipeline_stages - 1
        else:
            s = 0 if i is None else i * pipeline_stages // num_layers
        return AttrScope(ctx_group="stage%d" % s)

    with scope(0):
        data = sym.Variable("data")  # [B, T] int tokens
        net = sym.Embedding(data=data, input_dim=vocab_size,
                            output_dim=embed_dim, name="embed")
        rope = pos_encoding == "rope"
        if not rope:
            # learned additive positional embedding, rows sharded with
            # their positions under sequence parallelism
            net = sym.PositionalEmbedding(data=net,
                                          pos=sym.Variable("pos_embed"),
                                          name="pos_add")
    for i in range(num_layers):
        with scope(i):
            if num_experts:
                net = moe_transformer_block(net, num_heads, ffn_hidden,
                                            embed_dim, num_experts,
                                            "layer%d" % i, impl=impl,
                                            dropout=dropout,
                                            moe_top_k=moe_top_k,
                                            rope=rope,
                                            num_kv_heads=num_kv_heads,
                                            window=window)
            else:
                net = transformer_block(net, num_heads, ffn_hidden,
                                        embed_dim, "layer%d" % i,
                                        impl=impl, dropout=dropout,
                                        rope=rope,
                                        num_kv_heads=num_kv_heads,
                                        window=window)
    with scope(last=True):
        ln_f = _ln(net, "lnf")
        logits = sym.FullyConnected(data=ln_f, num_hidden=vocab_size,
                                    name="lm_head", flatten=False)
        return _lm_loss(logits, vocab_size, loss_layout)


def _lm_loss(logits, vocab_size, loss_layout):
    """The loss head over [B, T, V] logits in one of the three layouts
    ``get_transformer_lm`` documents."""
    if loss_layout not in ("reference", "flat", "ce"):
        raise ValueError("loss_layout must be 'reference', 'flat' or "
                         "'ce', got %r" % (loss_layout,))
    if loss_layout in ("flat", "ce"):
        flat = sym.Reshape(data=logits, shape=(-1, vocab_size),
                           name="logits_flat")
        flat_label = sym.Reshape(
            data=sym.Variable("softmax_label"), shape=(-1,),
            name="label_flat")
        if loss_layout == "ce":
            return sym.SoftmaxCELoss(data=flat, label=flat_label,
                                     name="softmax")
        return sym.SoftmaxOutput(data=flat, label=flat_label,
                                 name="softmax")
    # per-position softmax: label [B, T]
    logits_t = sym.SwapAxis(data=logits, dim1=1, dim2=2,
                            name="logits_t")
    return sym.SoftmaxOutput(data=logits_t, name="softmax",
                             multi_output=True)


def _rms(data, name, eps):
    return sym.RMSNorm(data=data, gamma=sym.Variable(name + "_gamma"),
                       eps=eps, name=name)


def _merge(data, branch, name):
    """``(x + b_r) * s_r + (y + b_y) * s_y``: the scaled residual
    merge of both ZAYA sublayers."""
    return sym.ResidualMerge(
        data=data, branch=branch,
        data_bias=sym.Variable(name + "_res_bias"),
        data_scale=sym.Variable(name + "_res_scale"),
        branch_bias=sym.Variable(name + "_out_bias"),
        branch_scale=sym.Variable(name + "_out_scale"),
        affine="full", name=name + "_merge")


def zaya_block(data, router_prev, name, num_heads, num_kv_heads, head_dim,
               num_experts, expert_hidden, router_hidden, top_k=1,
               rotary_dim=0, rope_base=10000.0, eps=1e-5, impl="flash"):
    """One ZAYA layer (Zyphra): a CCA sublayer, then a routed-experts
    sublayer whose router is an MLP over a narrow projection of the
    stream, mixed with the previous layer's (``router_prev``, None for
    the first layer). Both sublayers end in the scaled residual merge.
    Returns (the stream, this layer's router state for the next).

    The router is ordinary symbols and runs in float32 at full
    product precision whatever the model computes in: what it decides
    is discrete."""
    attn = sym.CCAttention(
        data=_rms(data, name + "_attn_norm", eps),
        qk_weight=sym.Variable(name + "_cca_qk_weight"),
        v_weight=sym.Variable(name + "_cca_v_weight"),
        conv0_weight=sym.Variable(name + "_cca_conv0_weight"),
        conv0_bias=sym.Variable(name + "_cca_conv0_bias"),
        conv1_weight=sym.Variable(name + "_cca_conv1_weight"),
        conv1_bias=sym.Variable(name + "_cca_conv1_bias"),
        temp=sym.Variable(name + "_cca_temp"),
        out_weight=sym.Variable(name + "_cca_out_weight"),
        num_heads=num_heads, num_kv_heads=num_kv_heads,
        head_dim=head_dim, rotary_dim=rotary_dim, rope_base=rope_base,
        impl=impl, name=name + "_cca")
    x = _merge(data, attn, name + "_attn")
    h = _rms(x, name + "_moe_norm", eps)

    def fc(z, width, tag):
        return sym.FullyConnected(
            data=z, num_hidden=width, no_bias=True, flatten=False,
            precision="highest", name="%s_router_%s" % (name, tag))

    def gelu(z, tag):
        return sym.Activation(data=z, act_type="gelu",
                              name="%s_router_%s" % (name, tag))

    r = fc(sym.Cast(data=h, dtype="float32", name=name + "_router_in"),
           router_hidden, "down")
    if router_prev is not None:
        r = sym.ResidualMerge(
            data=r, branch=router_prev,
            branch_scale=sym.Variable(name + "_router_mix"),
            affine="scale", name=name + "_router_eda")
    z = fc(gelu(fc(gelu(fc(_rms(r, name + "_router_norm", eps),
                            router_hidden, "fc1"), "act1"),
                   router_hidden, "fc2"), "act2"),
           num_experts, "fc3")
    probs = sym.SoftmaxActivation(data=z, name=name + "_router_probs")
    moe = sym.MoEFFN(
        data=h, probs=probs,
        select_bias=sym.Variable(name + "_router_balance"),
        expert_w1=sym.Variable(name + "_expert_w1"),
        expert_w2=sym.Variable(name + "_expert_w2"),
        num_experts=num_experts, hidden=expert_hidden, top_k=top_k,
        router="given", gated=True, name=name + "_moe")
    return _merge(x, moe, name + "_moe"), r


def get_zaya_lm(vocab_size, num_layers, embed_dim, num_heads, num_kv_heads,
                head_dim, num_experts, expert_hidden, router_hidden,
                top_k=1, rotary_dim=0, rope_base=10000.0, eps=1e-5,
                impl="flash", loss_layout="reference"):
    """ZAYA1-shaped decoder-only LM (Zyphra): ``num_layers`` of
    ``zaya_block`` between an embedding and the SAME matrix as the
    output head (tied), RMSNorm throughout, no biases, no positional
    table (rotary inside the attention). Built from registered Symbol
    ops like every zoo model: it binds, trains through ``FeedForward``
    / ``ParallelTrainer`` and is served by ``Decoder`` /
    ``InferenceEngine``. ``loss_layout`` as in
    ``get_transformer_lm``."""
    embed = sym.Variable("embed_weight")
    net = sym.Embedding(data=sym.Variable("data"), weight=embed,
                        input_dim=vocab_size, output_dim=embed_dim,
                        name="embed")
    router = None
    for i in range(num_layers):
        net, router = zaya_block(
            net, router, "layer%d" % i, num_heads, num_kv_heads, head_dim,
            num_experts, expert_hidden, router_hidden, top_k=top_k,
            rotary_dim=rotary_dim, rope_base=rope_base, eps=eps,
            impl=impl)
    logits = sym.FullyConnected(
        data=_rms(net, "final_norm", eps), weight=embed,
        num_hidden=vocab_size, no_bias=True, flatten=False,
        name="lm_head")
    return _lm_loss(logits, vocab_size, loss_layout)


def qwen3_next_block(data, name, attention, num_heads, num_kv_heads,
                     head_dim, linear_k_heads, linear_v_heads,
                     linear_k_dim, linear_v_dim, conv_kernel, num_experts,
                     expert_hidden, top_k, shared_hidden, experts_held=0,
                     expert_first=0, rotary_dim=0, rope_base=1e7,
                     eps=1e-6, impl="flash"):
    """One Qwen3-Next layer: ``x + mixer(RMSNorm(x))`` then
    ``x + moe(RMSNorm(x))``, the mixer a ``GatedAttention``
    (``attention``) or a ``GatedDeltaNet``, the experts routed top-k by
    a linear gate over all ``num_experts`` with one shared expert
    (``MoEFFN``; ``experts_held`` / ``expert_first``: the share of the
    routed experts that lives here, 0 = all). RMSNorm's scale is
    stored as ``gamma = 1 + w`` for the published zero-centred weight
    ``w``: a storage choice, the same function."""
    h = _rms(data, name + "_mixer_norm", eps)

    def var(tag):
        return sym.Variable("%s_%s" % (name, tag))

    if attention:
        mix = sym.GatedAttention(
            data=h, q_weight=var("attn_q_weight"),
            k_weight=var("attn_k_weight"), v_weight=var("attn_v_weight"),
            q_norm=var("attn_q_norm"), k_norm=var("attn_k_norm"),
            out_weight=var("attn_out_weight"), num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim,
            rotary_dim=rotary_dim, rope_base=rope_base, eps=eps,
            impl=impl, name=name + "_attn")
    else:
        mix = sym.GatedDeltaNet(
            data=h, qkvz_weight=var("gdn_qkvz_weight"),
            ba_weight=var("gdn_ba_weight"),
            conv_weight=var("gdn_conv_weight"), a_log=var("gdn_a_log"),
            dt_bias=var("gdn_dt_bias"),
            norm_weight=var("gdn_norm_weight"),
            out_weight=var("gdn_out_weight"),
            num_k_heads=linear_k_heads, num_v_heads=linear_v_heads,
            head_k_dim=linear_k_dim, head_v_dim=linear_v_dim,
            conv_kernel=conv_kernel, eps=eps, name=name + "_gdn")
    x = data + mix
    moe = sym.MoEFFN(
        data=_rms(x, name + "_moe_norm", eps),
        gate_weight=var("moe_gate_weight"), expert_w1=var("expert_w1"),
        expert_w2=var("expert_w2"), shared_w1=var("shared_w1"),
        shared_w2=var("shared_w2"), shared_gate=var("shared_gate"),
        num_experts=num_experts, hidden=expert_hidden, top_k=top_k,
        gated=True, experts_held=experts_held, expert_first=expert_first,
        shared_hidden=shared_hidden, name=name + "_moe")
    return x + moe


def get_qwen3_next_lm(vocab_size, num_layers, embed_dim, num_heads,
                      num_kv_heads, head_dim, linear_k_heads,
                      linear_v_heads, linear_k_dim, linear_v_dim,
                      num_experts, expert_hidden, top_k, shared_hidden,
                      full_attention_interval=4, conv_kernel=4,
                      experts_held=0, expert_first=0, rotary_dim=0,
                      rope_base=1e7, eps=1e-6, impl="flash",
                      loss_layout="reference"):
    """Qwen3-Next-shaped decoder-only LM: ``num_layers`` of
    ``qwen3_next_block``, layer ``i`` a ``GatedAttention`` layer where
    ``(i + 1) % full_attention_interval == 0`` and a ``GatedDeltaNet``
    layer otherwise, between an embedding and an untied head behind a
    final RMSNorm; no biases, no positional table (rotary inside the
    attention layers). Built from registered Symbol ops like every zoo
    model: it binds, and is served by ``Decoder`` / ``InferenceEngine``,
    which keep K/V rows for the attention layers and a recurrent state
    for the others. ``loss_layout`` as in ``get_transformer_lm``."""
    net = sym.Embedding(data=sym.Variable("data"),
                        weight=sym.Variable("embed_weight"),
                        input_dim=vocab_size, output_dim=embed_dim,
                        name="embed")
    for i in range(num_layers):
        net = qwen3_next_block(
            net, "layer%d" % i, (i + 1) % full_attention_interval == 0,
            num_heads, num_kv_heads, head_dim, linear_k_heads,
            linear_v_heads, linear_k_dim, linear_v_dim, conv_kernel,
            num_experts, expert_hidden, top_k, shared_hidden,
            experts_held=experts_held, expert_first=expert_first,
            rotary_dim=rotary_dim, rope_base=rope_base, eps=eps,
            impl=impl)
    logits = sym.FullyConnected(
        data=_rms(net, "final_norm", eps), num_hidden=vocab_size,
        no_bias=True, flatten=False, name="lm_head")
    return _lm_loss(logits, vocab_size, loss_layout)


def granite_hybrid_block(data, name, attention, embed_dim, num_heads,
                         num_kv_heads, mlp_hidden, mamba_heads, mamba_head_dim,
                         mamba_state, conv_kernel=4,
                         chunk_size=256, residual_multiplier=1.0,
                         attention_multiplier=0.0, eps=1e-5, impl="flash"):
    """One Granite 4.0-H layer: ``x + m mixer(RMSNorm(x))`` then
    ``x + m mlp(RMSNorm(x))`` with ``m = residual_multiplier``, the mixer
    a bias-free grouped-query ``MultiHeadAttention`` without positions
    whose scores are scaled by ``attention_multiplier`` (``attention``)
    or a ``Mamba2`` mixer, the MLP ``W_down (silu(W_gate h) * W_up h)``.
    The checkpoint's fused ``input_linear`` [2 F, E] is stored as its two
    halves, gate then up; its q/k/v projections as one [q ; k ; v]
    matrix: storage choices, the same function."""
    h = _rms(data, name + "_mixer_norm", eps)

    def var(tag):
        return sym.Variable("%s_%s" % (name, tag))

    if attention:
        mix = sym.MultiHeadAttention(
            data=h, qkv_weight=var("attn_qkv_weight"),
            out_weight=var("attn_out_weight"), num_heads=num_heads,
            num_kv_heads=num_kv_heads, causal=True, impl=impl,
            scale=attention_multiplier, no_bias=True, name=name + "_attn")
    else:
        mix = sym.Mamba2(
            data=h, in_weight=var("mamba_in_weight"),
            conv_weight=var("mamba_conv_weight"),
            conv_bias=var("mamba_conv_bias"), a_log=var("mamba_a_log"),
            d_skip=var("mamba_d_skip"), dt_bias=var("mamba_dt_bias"),
            norm_weight=var("mamba_norm_weight"),
            out_weight=var("mamba_out_weight"), num_heads=mamba_heads,
            head_dim=mamba_head_dim, state_size=mamba_state,
            conv_kernel=conv_kernel,
            chunk_size=chunk_size, eps=eps, name=name + "_mamba")
    x = data + mix * residual_multiplier
    h = _rms(x, name + "_mlp_norm", eps)

    def fc(z, width, tag):
        return sym.FullyConnected(data=z, num_hidden=width, no_bias=True,
                                  flatten=False, name="%s_mlp_%s"
                                  % (name, tag))

    act = sym.Activation(data=fc(h, mlp_hidden, "gate"), act_type="silu",
                         name=name + "_mlp_act") * fc(h, mlp_hidden, "up")
    return x + fc(act, embed_dim, "down") * residual_multiplier


def get_granite_hybrid_lm(vocab_size, layer_types, embed_dim, num_heads,
                          num_kv_heads, mlp_hidden, mamba_heads,
                          mamba_head_dim, mamba_state, conv_kernel=4,
                          chunk_size=256,
                          embedding_multiplier=1.0, residual_multiplier=1.0,
                          attention_multiplier=0.0, logits_scaling=1.0,
                          eps=1e-5, impl="flash", loss_layout="reference"):
    """Granite-4.0-H-shaped decoder-only LM (IBM's ``granitemoehybrid``
    without experts): one ``granite_hybrid_block`` per entry of
    ``layer_types`` (``"attention"`` or ``"mamba"``), between an
    embedding times ``embedding_multiplier`` and the SAME matrix as the
    output head (tied) behind a final RMSNorm, its logits divided by
    ``logits_scaling``; no biases, no positions (the Mamba-2 layers
    order the sequence), one group of B and C in every Mamba-2 layer.
    Built from registered Symbol ops like every zoo model: it binds, and
    is served by ``Decoder`` / ``InferenceEngine``, which keep K/V rows
    for the attention layers and a recurrent state for the others.
    ``loss_layout`` as in ``get_transformer_lm``."""
    kinds = set(layer_types) - {"attention", "mamba"}
    if kinds:
        raise ValueError("layer_types: 'attention' or 'mamba', got %s"
                         % sorted(kinds))
    embed = sym.Variable("embed_weight")
    net = sym.Embedding(data=sym.Variable("data"), weight=embed,
                        input_dim=vocab_size, output_dim=embed_dim,
                        name="embed") * embedding_multiplier
    for i, kind in enumerate(layer_types):
        net = granite_hybrid_block(
            net, "layer%d" % i, kind == "attention", embed_dim, num_heads,
            num_kv_heads, mlp_hidden, mamba_heads, mamba_head_dim,
            mamba_state, conv_kernel=conv_kernel,
            chunk_size=chunk_size, residual_multiplier=residual_multiplier,
            attention_multiplier=attention_multiplier, eps=eps, impl=impl)
    logits = sym.FullyConnected(
        data=_rms(net, "final_norm", eps), weight=embed,
        num_hidden=vocab_size, no_bias=True, flatten=False,
        name="lm_head") / logits_scaling
    return _lm_loss(logits, vocab_size, loss_layout)


def nemotron_h_block(data, name, kind, embed_dim, num_heads, num_kv_heads,
                     mamba_heads, mamba_head_dim, mamba_state, mamba_groups,
                     num_experts, expert_hidden, top_k, shared_hidden, latent,
                     route_scale=1.0, experts_held=0, expert_first=0,
                     conv_kernel=4, chunk_size=256, eps=1e-5, impl="flash"):
    """One Nemotron-H layer, ONE sublayer behind its own RMSNorm:
    ``x + f(RMSNorm(x))``, ``f`` by ``kind``, a letter of the published
    ``hybrid_override_pattern``: ``"M"`` a ``Mamba2`` mixer with
    ``mamba_groups`` groups of B and C; ``"*"`` bias-free grouped-query
    ``MultiHeadAttention`` without positions, scores at 1/sqrt(D); ``"E"``
    a LatentMoE: sigmoid scores of a float32 router over all
    ``num_experts``, the ``top_k`` largest of the scores plus a
    correction bias chosen, renormalized over the chosen and times
    ``route_scale``, the held experts (``experts_held`` /
    ``expert_first``: the share that lives here, 0 = all) relu² in a
    ``latent``-wide space, plus an ungated relu² shared expert of width
    ``shared_hidden`` on the full stream (``MoEFFN``)."""
    h = _rms(data, name + "_norm", eps)

    def var(tag):
        return sym.Variable("%s_%s" % (name, tag))

    if kind == "M":
        f = sym.Mamba2(
            data=h, in_weight=var("mamba_in_weight"),
            conv_weight=var("mamba_conv_weight"),
            conv_bias=var("mamba_conv_bias"), a_log=var("mamba_a_log"),
            d_skip=var("mamba_d_skip"), dt_bias=var("mamba_dt_bias"),
            norm_weight=var("mamba_norm_weight"),
            out_weight=var("mamba_out_weight"), num_heads=mamba_heads,
            head_dim=mamba_head_dim, state_size=mamba_state,
            n_groups=mamba_groups, conv_kernel=conv_kernel,
            chunk_size=chunk_size, eps=eps, name=name + "_mamba")
    elif kind == "*":
        f = sym.MultiHeadAttention(
            data=h, qkv_weight=var("attn_qkv_weight"),
            out_weight=var("attn_out_weight"), num_heads=num_heads,
            num_kv_heads=num_kv_heads, causal=True, impl=impl,
            no_bias=True, name=name + "_attn")
    else:
        # the router in float32 at full product precision whatever the
        # model computes in: what it decides is discrete
        scores = sym.Activation(
            data=sym.FullyConnected(
                data=sym.Cast(data=h, dtype="float32",
                              name=name + "_router_in"),
                num_hidden=num_experts, no_bias=True, flatten=False,
                precision="highest", name=name + "_router"),
            act_type="sigmoid", name=name + "_router_scores")
        f = sym.MoEFFN(
            data=h, probs=scores, select_bias=var("router_bias"),
            latent_down=var("latent_down_weight"),
            latent_up=var("latent_up_weight"),
            expert_w1=var("expert_w1"), expert_w2=var("expert_w2"),
            shared_w1=var("shared_w1"), shared_w2=var("shared_w2"),
            num_experts=num_experts, hidden=expert_hidden, top_k=top_k,
            router="given", relu2=True, latent=latent, renormalize=True,
            route_scale=route_scale, experts_held=experts_held,
            expert_first=expert_first, shared_hidden=shared_hidden,
            shared_gated=False, name=name + "_moe")
    return data + f


def get_nemotron_h_lm(vocab_size, pattern, embed_dim, num_heads,
                      num_kv_heads, mamba_heads, mamba_head_dim, mamba_state,
                      mamba_groups, num_experts, expert_hidden, top_k,
                      shared_hidden, latent, route_scale=1.0, experts_held=0,
                      expert_first=0, conv_kernel=4, chunk_size=256,
                      eps=1e-5, impl="flash", loss_layout="reference"):
    """Nemotron-H-shaped decoder-only LM (NVIDIA's ``nemotron_h``): one
    ``nemotron_h_block`` per letter of ``pattern`` (the published
    ``hybrid_override_pattern``: ``M`` Mamba-2, ``*`` attention, ``E``
    LatentMoE), between an untied, unscaled embedding and an untied head
    behind a final RMSNorm; no biases, no positions (the Mamba-2 layers
    order the sequence). Built from registered Symbol ops like every zoo
    model: it binds, and is served by ``Decoder`` / ``InferenceEngine``,
    which keep K/V rows for the attention layers and a recurrent state
    for the Mamba-2 ones. ``loss_layout`` as in ``get_transformer_lm``."""
    kinds = set(pattern) - set("M*E")
    if kinds:
        raise ValueError("pattern: 'M', '*' or 'E' a layer, got %s"
                         % sorted(kinds))
    net = sym.Embedding(data=sym.Variable("data"),
                        weight=sym.Variable("embed_weight"),
                        input_dim=vocab_size, output_dim=embed_dim,
                        name="embed")
    for i, kind in enumerate(pattern):
        net = nemotron_h_block(
            net, "layer%d" % i, kind, embed_dim, num_heads, num_kv_heads,
            mamba_heads, mamba_head_dim, mamba_state, mamba_groups,
            num_experts, expert_hidden, top_k, shared_hidden, latent,
            route_scale=route_scale, experts_held=experts_held,
            expert_first=expert_first, conv_kernel=conv_kernel,
            chunk_size=chunk_size, eps=eps, impl=impl)
    logits = sym.FullyConnected(
        data=_rms(net, "final_norm", eps), num_hidden=vocab_size,
        no_bias=True, flatten=False, name="lm_head")
    return _lm_loss(logits, vocab_size, loss_layout)


def _hyper(data, name, branch_fn, lanes, iters, hc_eps, clamp, res_diag,
           eps):
    """One sublayer inside its hyper-connection: ``u = H_pre X``,
    ``y = branch_fn(RMSNorm(u))`` under the sublayer's own scale,
    ``X <- H_res X + outer(H_post, y)`` (``ops.attention``, the
    hyper-connection section)."""
    pre = sym.HyperConnectionPre(
        data=data, phi=sym.Variable(name + "_hc_phi"),
        alpha=sym.Variable(name + "_hc_alpha"),
        bias=sym.Variable(name + "_hc_bias"), lanes=lanes, iters=iters,
        eps=hc_eps, clamp=clamp, res_diag=res_diag, norm_eps=eps,
        name=name + "_hc_pre")
    y = branch_fn(_rms(pre[0], name + "_norm", eps))
    return sym.HyperConnectionPost(data=data, branch=y, mix=pre[1],
                                   lanes=lanes, name=name + "_hc_post")


def xing4_block(data, name, dense, embed_dim, num_heads, q_lora_rank,
                kv_lora_rank, nope_dim, rope_dim, v_dim, ffn_hidden,
                num_experts, expert_hidden, top_k, shared_hidden,
                route_scale=1.0, experts_held=0, expert_first=0, lanes=4,
                hc_iters=20, hc_eps=1e-6, hc_clamp=30.0, hc_res_diag=0.0,
                rope_base=10000.0, yarn=None, mscale_all_dim=0.0, eps=1e-6,
                impl="flash"):
    """One Xing4.0-shaped layer on a stream of ``lanes`` lanes
    [B, T, lanes E]: latent attention, then a SiLU-gated FFN of width
    ``ffn_hidden`` (``dense``) or routed experts (top-k of
    ``num_experts`` by sigmoid scores under a balancing bias,
    renormalized over the chosen, times ``route_scale``, plus an
    ungated shared expert; ``experts_held`` / ``expert_first``: the
    share of the routed experts that lives here, 0 = all), each
    sublayer inside its own hyper-connection (``_hyper``;
    ``hc_res_diag``: ``HyperConnectionPre``'s ``res_diag``). ``yarn``:
    ``(factor, original_max, beta_fast, beta_slow)`` or None."""
    def var(tag):
        return sym.Variable("%s_%s" % (name, tag))

    def fc(z, width, tag, **kw):
        return sym.FullyConnected(data=z, num_hidden=width, no_bias=True,
                                  flatten=False, name="%s_%s" % (name, tag),
                                  **kw)

    def attention(h):
        scaling = {} if yarn is None else dict(
            yarn_factor=yarn[0], yarn_original_max=yarn[1],
            yarn_beta_fast=yarn[2], yarn_beta_slow=yarn[3],
            mscale_all_dim=mscale_all_dim)
        return sym.LatentAttention(
            data=h, q_down_weight=var("attn_q_down_weight"),
            q_norm=var("attn_q_norm"), q_up_weight=var("attn_q_up_weight"),
            kv_down_weight=var("attn_kv_down_weight"),
            kv_norm=var("attn_kv_norm"),
            kv_up_weight=var("attn_kv_up_weight"),
            out_weight=var("attn_out_weight"), num_heads=num_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
            rope_base=rope_base, eps=eps, impl=impl, name=name + "_attn",
            **scaling)

    def dense_ffn(h):
        gate = sym.Activation(data=fc(h, ffn_hidden, "ffn_gate"),
                              act_type="silu", name=name + "_ffn_act")
        return fc(gate * fc(h, ffn_hidden, "ffn_up"), embed_dim,
                  "ffn_down")

    def experts(h):
        # the router in float32 at full product precision whatever the
        # model computes in: what it decides is discrete
        scores = sym.Activation(
            data=fc(sym.Cast(data=h, dtype="float32",
                             name=name + "_router_in"),
                    num_experts, "router", precision="highest"),
            act_type="sigmoid", name=name + "_router_scores")
        return sym.MoEFFN(
            data=h, probs=scores, select_bias=var("router_balance"),
            expert_w1=var("expert_w1"), expert_w2=var("expert_w2"),
            shared_w1=var("shared_w1"), shared_w2=var("shared_w2"),
            num_experts=num_experts, hidden=expert_hidden, top_k=top_k,
            router="given", gated=True, renormalize=True,
            route_scale=route_scale, experts_held=experts_held,
            expert_first=expert_first, shared_hidden=shared_hidden,
            shared_gated=False, name=name + "_moe")

    hc = (lanes, hc_iters, hc_eps, hc_clamp, hc_res_diag, eps)
    x = _hyper(data, name + "_attn", attention, *hc)
    return _hyper(x, name + "_ffn", dense_ffn if dense else experts, *hc)


def get_xing4_lm(vocab_size, num_layers, embed_dim, num_heads, q_lora_rank,
                 kv_lora_rank, nope_dim, rope_dim, v_dim, ffn_hidden,
                 num_experts, expert_hidden, top_k, shared_hidden,
                 dense_layers=1, route_scale=1.0, experts_held=0,
                 expert_first=0, lanes=4, hc_iters=20, hc_eps=1e-6,
                 hc_clamp=30.0, hc_res_diag=0.0, rope_base=10000.0,
                 yarn=None, mscale_all_dim=0.0, eps=1e-6, impl="flash",
                 loss_layout="reference"):
    """Xing4.0-shaped decoder-only LM: a residual stream of ``lanes``
    lanes (the embedding copied into each, ``StreamLanes``) through
    ``num_layers`` of ``xing4_block`` (the first ``dense_layers`` with a
    dense FFN, the others with routed experts), the lanes summed before
    the final RMSNorm and an untied head; no biases, no positional table
    (rotary inside the latent attention). Built from registered Symbol
    ops like every zoo model: it binds, and is served by ``Decoder`` /
    ``InferenceEngine``, which keep ONE buffer of latent rows per layer
    (``parallel/decode.py``, the latent-rows kind). ``loss_layout`` as
    in ``get_transformer_lm``."""
    net = sym.Embedding(data=sym.Variable("data"),
                        weight=sym.Variable("embed_weight"),
                        input_dim=vocab_size, output_dim=embed_dim,
                        name="embed")
    net = sym.StreamLanes(data=net, lanes=lanes, mode="copy",
                          name="stream_in")
    for i in range(num_layers):
        net = xing4_block(
            net, "layer%d" % i, i < dense_layers, embed_dim, num_heads,
            q_lora_rank, kv_lora_rank, nope_dim, rope_dim, v_dim,
            ffn_hidden, num_experts, expert_hidden, top_k, shared_hidden,
            route_scale=route_scale, experts_held=experts_held,
            expert_first=expert_first, lanes=lanes, hc_iters=hc_iters,
            hc_eps=hc_eps, hc_clamp=hc_clamp, hc_res_diag=hc_res_diag,
            rope_base=rope_base, yarn=yarn, mscale_all_dim=mscale_all_dim, eps=eps, impl=impl)
    net = sym.StreamLanes(data=net, lanes=lanes, mode="sum",
                          name="stream_out")
    logits = sym.FullyConnected(
        data=_rms(net, "final_norm", eps), num_hidden=vocab_size,
        no_bias=True, flatten=False, name="lm_head")
    return _lm_loss(logits, vocab_size, loss_layout)


def tp_rules():
    """Tensor-parallel sharding rules for transformer params (Megatron
    layout: QKV/FFN1 column-parallel, proj/FFN2 row-parallel) — pass to
    ShardingRules(param_rules=...)."""
    from ..parallel.shard import P
    return [
        (r"_qkv_weight$", P("tp", None)),
        (r"_qkv_bias$", P("tp")),
        (r"_ffn1_weight$", P("tp", None)),
        (r"_ffn1_bias$", P("tp")),
        (r"_proj_weight$", P(None, "tp")),
        (r"_ffn2_weight$", P(None, "tp")),
        (r"embed_weight$", P("tp", None)),
        (r"lm_head_weight$", P("tp", None)),
    ]


def ep_rules():
    """Expert-parallel sharding rules: the leading num_experts dim of
    every MoEFFN parameter shards over ``ep``; XLA inserts the psum over
    ``ep`` for the gate-weighted combine."""
    from ..parallel.shard import P
    return [
        (r"_expert_w1$", P("ep", None, None)),
        (r"_expert_b1$", P("ep", None)),
        (r"_expert_w2$", P("ep", None, None)),
        (r"_expert_b2$", P("ep", None)),
    ]
