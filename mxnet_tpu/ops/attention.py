"""Attention and normalization operators (TPU-era extensions).

The reference predates attention (its sequence story is explicit LSTM
unrolling, example/rnn/lstm.py); these ops extend the same declarative
operator pattern (``registry.OpSpec``) so transformers compose through
the ordinary Symbol API. The compute path is the Pallas flash-attention
kernel (ops/pallas_kernels.py) on TPU — interpreter elsewhere — with the
blockwise recurrence supplying gradients.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from .registry import OpSpec, Param, register, shape_assign


@register
class LayerNorm(OpSpec):
    """Layer normalization over the trailing axis: gamma/beta learnable.
    (No reference counterpart — BatchNorm is its 2015 relative; kept in
    the same Param/arguments/infer_shape mold as batch_norm-inl.h.)"""

    name = "LayerNorm"
    params = {"eps": Param("float", 1e-5)}

    def arguments(self, p):
        return ["data", "gamma", "beta"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        c = (d[-1],)
        return [d, shape_assign(in_shapes[1], c, "LayerNorm gamma"),
                shape_assign(in_shapes[2], c, "LayerNorm beta")], [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x, gamma, beta = ins
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + p["eps"])
        return [y * gamma + beta], []


@register
class RMSNorm(OpSpec):
    """Root-mean-square normalization over the trailing axis:
    ``x / sqrt(mean(x^2) + eps) * gamma`` (Zhang & Sennrich 2019): no
    mean subtraction, no shift. The statistics are taken in float32
    whatever the input's dtype; the output keeps the input's dtype."""

    name = "RMSNorm"
    params = {"eps": Param("float", 1e-5)}

    def arguments(self, p):
        return ["data", "gamma"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        return [d, shape_assign(in_shapes[1], (d[-1],),
                                "RMSNorm gamma")], [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x, gamma = ins
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + p["eps"]) * gamma.astype(jnp.float32)
        return [y.astype(x.dtype)], []


@register
class ResidualMerge(OpSpec):
    """A residual connection with learned per-channel scales:
    ``affine="scale"``: ``data + branch * branch_scale`` (2 + 1
    inputs); ``affine="full"``:
    ``(data + data_bias) * data_scale + (branch + branch_bias) *
    branch_scale`` (2 + 4 inputs). Every vector is [C], the trailing
    axis of both tensors. Position-wise."""

    name = "ResidualMerge"
    params = {"affine": Param("str", "full")}

    def arguments(self, p):
        if p["affine"] == "scale":
            return ["data", "branch", "branch_scale"]
        if p["affine"] != "full":
            raise MXNetError("ResidualMerge: affine must be 'scale' or "
                             "'full', got %r" % (p["affine"],))
        return ["data", "branch", "data_bias", "data_scale",
                "branch_bias", "branch_scale"]

    def infer_shape(self, p, in_shapes):
        d = shape_assign(in_shapes[0], in_shapes[1], "ResidualMerge")
        if d is None:
            return list(in_shapes), [None], []
        vec = [shape_assign(s, (d[-1],), "ResidualMerge vector")
               for s in in_shapes[2:]]
        return [d, d] + vec, [d], []

    def forward(self, p, ins, aux, is_train, rng):
        # float32 inside whatever the stream's dtype: four roundings a
        # sublayer would otherwise add to the residual stream
        dt = jnp.result_type(ins[0], ins[1])
        x, y, *vec = (z.astype(jnp.float32) for z in ins)
        if p["affine"] == "scale":
            return [(x + y * vec[0]).astype(dt)], []
        xb, xs, yb, ys = vec
        return [((x + xb) * xs + (y + yb) * ys).astype(dt)], []


@register
class PositionalEmbedding(OpSpec):
    """out = data + pos[None, :, :] — learned additive positional
    embedding. data: [B, T, E]; pos: [T, E] (a parameter). Under
    sequence parallelism pos rows shard with their positions
    (``P('sp', None)``). No reference counterpart (transformer-era op).
    """

    name = "PositionalEmbedding"
    params = {}

    def arguments(self, p):
        return ["data", "pos"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        ins = list(in_shapes)
        if d is not None:
            if len(d) != 3:
                raise MXNetError("PositionalEmbedding: data must be "
                                 "[B, T, E]")
            ins[1] = shape_assign(in_shapes[1], (d[1], d[2]),
                                  "PositionalEmbedding pos")
        return ins, [d], []

    def forward(self, p, ins, aux, is_train, rng):
        return [ins[0] + ins[1][None, :, :]], []


@register
class MoEFFN(OpSpec):
    """Mixture-of-experts position-wise FFN (soft or top-k routing).

    data: [B, T, E]; out[b,t] = sum_x gate[b,t,x] * FFN_x(data[b,t]).
    Two switches choose the block:

    ``router``, and the three routers' arithmetic side by side (``s``
    the scores over all ``num_experts``, ``C`` the ``top_k`` chosen):

    ====================  ======================  =====================
    router                chosen by               weight of a chosen x
    ====================  ======================  =====================
    ``"linear"``          ``s = data . gate^T``   ``softmax over C of
    (default; OPT-MoE,    (``gate_weight``        s``: renormalized by
    Qwen3-Next)           [X, E], float32 sums)   construction
    ``"given"``           ``probs + select_bias`` ``probs[x]``, NOT
    (ZAYA: an MLP         (``probs`` [B, T, X]    renormalized
    router's softmax)     from the graph,
                          ``select_bias`` [X])
    ``"given"``,          ``probs + select_bias`` ``probs[x] / (sum
    ``renormalize=True``  (the bias decides the   over C of probs +
    ``route_scale=c``     choice and never the    1e-20) * c``
    (sigmoid scores       weight: ``noaux_tc``)
    under a balancing
    bias)
    ====================  ======================  =====================

    ``"linear"`` owns its gate; ``"given"`` takes the routing from the
    graph (a router built from ordinary symbols, in float32). Ties are
    broken by index (``lax.top_k``). ``renormalize`` and
    ``route_scale`` belong to ``"given"``; their defaults (False, 1.0)
    trace no operation, so a graph that does not set them compiles to
    the program it compiled to before they existed.

    ``gated``: False (default) is the biased ReLU pair, ``expert_w1``
    [X, H, E], ``expert_b1`` [X, H], ``expert_w2`` [X, E, H],
    ``expert_b2`` [X, E]. True is the SiLU-gated pair without biases:
    ``expert_w1`` [X, 2H, E] (the gate's rows, then the up
    projection's), ``expert_w2`` [X, E, H]:
    ``w2 (silu(w1[:H] x) * (w1[H:] x))``. ``relu2=True`` (with
    ``gated`` False) is the ungated relu² pair without biases,
    ``expert_w1`` [X, H, E], ``expert_w2`` [X, E, H]:
    ``w2 relu(w1 x)^2``; the shared expert, if any, is then relu² too.

    A LATENT space (``latent`` = its width L > 0, Nemotron-H's
    LatentMoE): the routed experts read ``u = latent_down x``
    (``latent_down`` [L, E]) and their weighted sum ``r`` [L] is
    projected back, ``out = latent_up r`` (``latent_up`` [E, L]), once a
    token and not once a pair; ``expert_w1`` [X, ., L], ``expert_w2``
    [X, L, H]. The router and the shared expert read ``x`` itself. The
    two projections trace under the scope ``latent``. Routed form only.

    Routing: ``top_k=0`` (default) is soft routing: every expert
    weighs in and computes every token, fully differentiable.
    ``top_k=k`` is hard routing: the k best experts per token carry
    weight (ties broken by index, like ``lax.top_k``), and ONLY THEY
    COMPUTE: the token-expert pairs are sorted by expert and run as a
    grouped matrix product over the experts that have tokens
    (``moe_ffn_math``, ``pallas_kernels.grouped_matmul``), so the
    operations follow k, not X, and a decode step reads the weights
    of the experts it touched and no others. Gradients flow through
    the kept gates' values and through the chosen experts.

    Expert parallelism: shard the leading X dim of the expert params
    over an ``ep`` mesh axis (``models.transformer.ep_rules()``). A
    shard holds its experts only, so under ``ep`` (and under the
    serving engine's quantized weights, whose products are scale-fused
    per expert) the experts run in the dense form: every local expert
    computes every token and the gates, zero outside the top k, pick
    the result; the combine ends in one ``psum`` over ``ep``.

    A SHARE of the experts (``experts_held`` > 0): this node holds
    the experts ``[expert_first, expert_first + experts_held)`` of
    ``num_experts`` and no others, as one chip of an expert-parallel
    deployment does. The router keeps all ``num_experts`` rows and
    routes over all of them; the expert stacks have ``experts_held``
    leading rows. The node computes the token-expert pairs that fall
    on its own experts, weighs them with gates normalized over ALL of
    a token's ``top_k`` choices, and leaves out what the absent experts
    would have added: the shares of all holders add up to the whole
    layer. Routed form only (``top_k`` > 0, plain products, no ``ep``).

    A SHARED expert (``shared_hidden`` = its width > 0): one more
    SiLU-gated expert that every token takes, behind a sigmoid gate:
    ``out += sigmoid(shared_gate . x) * shared_w2 (silu(shared_w1[:H]
    x) * (shared_w1[H:] x))``, ``shared_w1`` [2H, E], ``shared_w2``
    [E, H], ``shared_gate`` [1, E]; with ``shared_gated=False`` there
    is no gate and no ``shared_gate`` argument. Every holder of a share
    computes it alike, so it counts once in their sum.
    """

    name = "MoEFFN"
    params = {"num_experts": Param("int"), "hidden": Param("int"),
              "top_k": Param("int", 0),
              "router": Param("str", "linear"),
              "gated": Param("bool", False),
              "experts_held": Param("int", 0),
              "expert_first": Param("int", 0),
              "shared_hidden": Param("int", 0),
              "renormalize": Param("bool", False),
              "route_scale": Param("float", 1.0),
              "shared_gated": Param("bool", True),
              "latent": Param("int", 0),
              "relu2": Param("bool", False)}

    def arguments(self, p):
        route = ["probs", "select_bias"] if self.given(p) \
            else ["gate_weight"]
        latent = ["latent_down", "latent_up"] if p.get("latent") else []
        experts = ["expert_w1", "expert_w2"] \
            if p["gated"] or p.get("relu2") else \
            ["expert_w1", "expert_b1", "expert_w2", "expert_b2"]
        shared = []
        if p.get("shared_hidden"):
            shared = ["shared_w1", "shared_w2"] \
                + (["shared_gate"] if p.get("shared_gated", True) else [])
        return ["data"] + route + latent + experts + shared

    @staticmethod
    def held(p):
        """(first, count) of the experts this node holds: all of them
        unless ``experts_held`` says otherwise."""
        nx = int(p["num_experts"])
        n = int(p.get("experts_held") or 0) or nx
        first = int(p.get("expert_first") or 0)
        if first < 0 or n < 1 or first + n > nx:
            raise MXNetError(
                "MoEFFN: experts [%d, %d) are not among num_experts=%d"
                % (first, first + n, nx))
        return first, n

    @staticmethod
    def given(p):
        r = p.get("router", "linear")
        if r not in ("linear", "given"):
            raise MXNetError("MoEFFN: router must be 'linear' or "
                             "'given', got %r" % (r,))
        return r == "given"

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        ins = list(in_shapes)
        if d is not None:
            if len(d) != 3:
                raise MXNetError("MoEFFN: data must be [B, T, E]")
            e = d[2]
            x, h = p["num_experts"], p["hidden"]
            names = self.arguments(p)
            _, n = self.held(p)
            sh = p.get("shared_hidden") or 0
            lw = p.get("latent") or 0
            ew = lw or e                # what the routed experts read
            want = {"gate_weight": (x, e), "probs": (d[0], d[1], x),
                    "select_bias": (x,), "latent_down": (lw, e),
                    "latent_up": (e, lw),
                    "expert_w1": (n, 2 * h if p["gated"] else h, ew),
                    "expert_b1": (n, h), "expert_w2": (n, ew, h),
                    "expert_b2": (n, e),
                    "shared_w1": (sh if p.get("relu2") else 2 * sh, e),
                    "shared_w2": (e, sh), "shared_gate": (1, e)}
            for i, n in enumerate(names[1:], 1):
                ins[i] = shape_assign(ins[i], want[n], "MoEFFN " + n)
        return ins, [d], []

    def forward(self, p, ins, aux, is_train, rng):
        return [moe_ffn_math(p, ins)], []


def moe_ffn_math(p, ins, gate_mm=None, up_mm=None, down_mm=None,
                 ep=None, stats=None, live=None):
    """The ONE MoE routing + combine implementation, parameterized
    over its three matmuls (``None`` = the plain products). The
    serving engine's weight-quantized path (``serving/quant.py``)
    passes scale-fused forms for whichever weights are quantized —
    sharing this function is what keeps quantized MoE routing from
    silently diverging from the fp op it is tested against.

    Two forms of the experts' products, one routing. With ``top_k >
    0`` and plain products on one shard the experts are ROUTED
    (``_routed_experts``): only the chosen experts' rows are computed.
    Soft routing, ``ep`` and the quantized products run them DENSE:
    every (local) expert computes every token and the gates, zero
    outside the top k, pick the result.

    ``ep=(axis_name, degree)`` runs the SAME math expert-parallel
    inside a ``shard_map``: every expert-stacked input (gate rows,
    w1/b1/w2/b2) arrives sharded on its leading expert axis, so this
    shard computes its local experts only. Routing needs the FULL
    gate row — local logits are all-gathered over the expert axis
    before top-k/softmax (tiny: one f32 per expert per token) — and
    the weighted combine ends in one ``psum``: each token's output is
    a sum over experts, partitioned across shards. The psum
    reassociates the float sum, so ep>1 is token-stable rather than
    bitwise vs ep=1 (the PR 14 all-gather precedent is the same
    contract family).

    ``stats`` (a dict, optional): the routed form leaves
    ``stats["experts_touched"]`` there, the number of (held) experts
    that were given a token, and ``stats["pairs_held"]``, the
    token-expert pairs that fell on held experts (traced int32
    scalars).

    ``live`` (bool [B], optional; every token of a batch row alike): a
    row that is not live routes nothing in the routed form. Its pairs
    are marked absent before they are sorted into groups, so they take
    no row and touch no expert, and its output is the shared expert's
    part alone (zeros without one): finite, and for a caller that
    discards it (the serving engine's slot that holds no request). The
    gates, the choice and the shared expert are computed as ever, and a
    live row's values do not change. ``stats["rows_masked"]`` is then
    the number of rows that were not live. The dense forms ignore
    ``live``: every expert computes every token there whatever the
    gates say, so masking saves nothing. With ``live=None`` the traced
    program is the one without the argument."""
    given, gated = MoEFFN.given(p), bool(p.get("gated", False))
    relu2, lw = bool(p.get("relu2", False)), int(p.get("latent") or 0)
    if relu2 and gated:
        raise MXNetError("MoEFFN: relu2 experts are ungated; gated=True "
                         "is the SiLU-gated pair")
    # the experts' activation: the biased ReLU pair, SiLU-gated, relu²
    act = "silu" if gated else "relu2" if relu2 else "relu"
    it = iter(ins)
    x = next(it)
    if given:
        probs, select_bias = next(it), next(it)
        if gate_mm is not None or (ep is not None and ep[1] > 1):
            raise MXNetError(
                "MoEFFN: router='given' does not run expert-parallel "
                "or with quantized weights yet (ROADMAP R-M2)")
    else:
        gate_w = next(it)
    if lw:
        latent_down, latent_up = next(it), next(it)
    w1 = next(it)
    b1 = None if act != "relu" else next(it)
    w2 = next(it)
    b2 = None if act != "relu" else next(it)
    shared = None
    if p.get("shared_hidden"):
        shared = (next(it), next(it),
                  next(it) if p.get("shared_gated", True) else None)
    renorm = bool(p.get("renormalize", False))
    route_scale = float(p.get("route_scale", 1.0))
    if (renorm or route_scale != 1.0) and not given:
        raise MXNetError(
            "MoEFFN: renormalize / route_scale belong to router='given' "
            "(the linear router renormalizes over its kept logits by "
            "its softmax)")
    k = int(p["top_k"])
    nx = int(p["num_experts"])
    first, held = MoEFFN.held(p)
    if k >= nx:
        raise MXNetError(
            "MoEFFN: top_k=%d must be < num_experts=%d (use "
            "top_k=0 for dense routing)" % (k, nx))
    sharded = ep is not None and ep[1] > 1
    with jax.named_scope("route"):
        if given:
            score = probs + select_bias
        else:
            # float32 sums kept as they are: rounded to bfloat16 the
            # scores of 512 experts tie at the k-th place
            score = gate_mm(x, gate_w) if gate_mm is not None \
                else jnp.einsum("bte,xe->btx", x, gate_w,
                                preferred_element_type=jnp.float32)
            nloc = gate_w.shape[0] if hasattr(gate_w, "shape") else nx
            if sharded:
                # full gate row for routing; this shard's slice of
                # the renormalized gates comes back out below
                score = jax.lax.all_gather(score, ep[0], axis=-1,
                                           tiled=True)
        idx = None
        if k > 0:
            # exactly k experts from top_k's INDICES (not a >=
            # threshold, which would keep every expert tied with the
            # k-th — e.g. all of them at zero-init), ties broken by
            # index like lax.top_k itself
            _, idx = jax.lax.top_k(score, k)
            mask = jnp.sum(jax.nn.one_hot(idx, nx, dtype=score.dtype),
                           axis=-2) > 0
        if given:
            gates = probs if k == 0 else jnp.where(mask, probs, 0)
            if renorm:
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                                 + 1e-20)
            if route_scale != 1.0:
                gates = gates * route_scale
        else:
            if k > 0:
                # mask BEFORE the softmax, so kept gates renormalize
                # among themselves and dropped gates get exactly zero
                score = jnp.where(
                    mask, score,
                    jnp.float32(-1e30).astype(score.dtype))
            gates = jax.nn.softmax(score, axis=-1)
            if sharded:
                # this shard's slice of the (globally renormalized)
                # gates
                i = jax.lax.axis_index(ep[0])
                gates = jax.lax.dynamic_slice_in_dim(
                    gates, i * nloc, nloc, axis=-1)
    plain = gate_mm is None and up_mm is None and down_mm is None
    if k > 0 and plain and not sharded:
        b, t, _ = x.shape
        picked = jnp.take_along_axis(gates, idx, axis=-1)     # [b,t,k]
        # the held experts count from 0; a pair on an absent expert
        # gets the index ``held``, which no group has
        local = idx - first
        local = jnp.where((local >= 0) & (local < held), local, held)
        if live is not None:
            local = jnp.where(live[:, None, None], local, held)
            if stats is not None:
                stats["rows_masked"] = jnp.sum(~live).astype(jnp.int32)
        u = x
        if lw:
            with jax.named_scope("latent"):
                u = jnp.einsum("bte,le->btl", x, latent_down)
        out = _routed_sum(u.reshape(b * t, -1), local.reshape(b * t, k),
                          picked.reshape(b * t, k), held, w1, b1, w2, b2,
                          act, stats).reshape(b, t, -1)
        if lw:
            with jax.named_scope("latent"):
                out = jnp.einsum("btl,el->bte", out.astype(x.dtype),
                                 latent_up,
                                 preferred_element_type=jnp.float32)
        if shared is not None:
            out = out + _shared_expert(x, *shared, relu2=relu2)
        return out.astype(x.dtype)
    if held != nx or shared is not None or lw or relu2:
        raise MXNetError(
            "MoEFFN: a share of the experts (experts_held=%d of %d), a "
            "shared expert, a latent space and relu2 experts have the "
            "routed form only: top_k > 0, plain products, no ep "
            "(ROADMAP R-M2)" % (held, nx))
    with jax.named_scope("experts"):
        up = up_mm(x, w1) if up_mm is not None \
            else jnp.einsum("bte,xhe->btxh", x, w1)
        if gated:
            hid = up.shape[-1] // 2
            h = jax.nn.silu(up[..., :hid]) * up[..., hid:]
        else:
            h = jax.nn.relu(up + b1[None, None])
        y = down_mm(h, w2) if down_mm is not None \
            else jnp.einsum("btxh,xeh->btxe", h, w2)
        if not gated:
            y = y + b2[None, None]
        out = jnp.einsum("btxe,btx->bte", y, gates.astype(y.dtype))
    if sharded:
        out = jax.lax.psum(out, ep[0])
    return out


def routed_block_rows(pairs, num_experts):
    """Rows of one block of the routed experts' grouped product: half
    an average group (``pairs / num_experts`` token-expert pairs), as a
    power of two between the bf16 tile's 16 rows and the MXU's 128.
    Every expert with a token pads its group to whole blocks, so the
    rows computed stay under ``pairs + num_experts * block``: one and
    a half times the pairs once groups outgrow a tile, where the dense
    form computes ``num_experts`` times the tokens. (The kernel copies
    an expert's matrix once however many blocks it has, so small
    blocks cost no traffic.)"""
    per = int(pairs) // (2 * int(num_experts))
    rows = 16
    while rows * 2 <= per and rows < 128:
        rows *= 2
    return rows


# a pass of the routed experts lays out at most this many token-expert
# pairs. The padded rows of a pass are a buffer the chip's compiler keeps
# in fast memory: at 20,480 pairs (a prefill piece of 2,048 tokens with
# ten choices) it is 28,672 rows of 4 KB, 112 MiB of the 128, and the
# eight-layer prefill program did not come back in twelve minutes (the
# layer alone runs, 5.9 ms); at 10,240 pairs the program ran (PERF.md
# section 6, PR 34)
_ROUTED_PASS_PAIRS = 8192
# ... and its first product's float32 rows [rows, N] (N the up width) take
# at most this many bytes: with 22 choices of experts 2,688 wide a pass of
# 256 tokens is 7,680 rows, 79 MiB of fast memory (PERF.md section 6);
# Xing4.0's pieces of 1,024 tokens lay out 48 MiB
_ROUTED_PASS_BYTES = 56 << 20


def _routed_layout(pairs, num_experts):
    """(rows of a block, blocks) of a pass of ``pairs`` token-expert
    pairs: ``pairs // rows`` full blocks at most, and one partly filled
    one per expert."""
    rows = routed_block_rows(pairs, num_experts)
    return rows, pairs // rows + min(num_experts, pairs)


def _routed_sum(x, idx, gates, nx, w1, b1, w2, b2, act, stats=None):
    """``x`` [N, E] through each token's experts ``idx`` [N, k] (``nx``
    marks a pair whose expert is not here), weighted by ``gates`` [N, k]
    and summed over the k: float32 [N, E]. A long chunk goes in passes
    of a power-of-two number of tokens, at most ``_ROUTED_PASS_PAIRS``
    pairs and ``_ROUTED_PASS_BYTES`` of first products each, one after
    the other (``lax.map``); every pass streams the experts it touches
    again, so a decode step and a short chunk are one pass. A chunk that is not whole passes is filled up with tokens
    whose pairs are all absent: no pass is ever larger than the limit."""
    n, k = idx.shape
    f32 = jnp.float32

    def one(x, idx, gates):
        seen = {}
        y = _routed_experts(x, idx, nx, w1, b1, w2, b2, act, seen)
        out = jnp.sum(y.astype(f32) * gates[..., None].astype(f32), axis=1)
        return out, seen["experts_touched"], seen["pairs_held"]

    def up_bytes(pairs):
        rows, nb = _routed_layout(pairs, nx)
        return nb * rows * w1.shape[1] * 4

    per = 1
    while per * 2 * k <= _ROUTED_PASS_PAIRS \
            and up_bytes(per * 2 * k) <= _ROUTED_PASS_BYTES:
        per *= 2
    if n <= per:
        out, touched, pairs = one(x, idx, gates)
    else:
        fill = -n % per

        def passes(z, value):
            z = jnp.pad(z, [(0, fill)] + [(0, 0)] * (z.ndim - 1),
                        constant_values=value)
            return z.reshape(((n + fill) // per, per) + z.shape[1:])

        out, touched, pairs = jax.lax.map(
            lambda a: one(*a),
            (passes(x, 0), passes(idx, nx), passes(gates, 0)))
        out = out.reshape(n + fill, -1)[:n]
        touched, pairs = jnp.sum(touched), jnp.sum(pairs)
    if stats is not None:
        stats["experts_touched"] = touched
        stats["pairs_held"] = pairs
    return out


def _shared_expert(x, w1, w2, gate, relu2=False):
    """The shared expert's part, float32 [B, T, E]: the SiLU-gated
    pair (``relu2``: ``relu(w1 x)^2``, ungated) on every token, times
    ``sigmoid(gate . x)`` (``gate`` None: ungated)."""
    f32 = jnp.float32
    with jax.named_scope("shared"):
        up = jnp.einsum("bte,he->bth", x, w1)
        if relu2:
            h = jnp.square(jax.nn.relu(up))
        else:
            hid = up.shape[-1] // 2
            h = jax.nn.silu(up[..., :hid]) * up[..., hid:]
        y = jnp.einsum("bth,eh->bte", h, w2, preferred_element_type=f32)
        if gate is None:
            return y
        g = jnp.einsum("bte,oe->bto", x, gate, preferred_element_type=f32)
        return y * jax.nn.sigmoid(g)


def _routed_experts(x, idx, nx, w1, b1, w2, b2, act, stats=None):
    """``x`` [N, E] through each token's own experts ``idx`` [N, k]:
    returns [N, k, E]; ``act`` is ``moe_ffn_math``'s (``"relu"``, the
    biased pair; ``"silu"``, gated; ``"relu2"``, ungated without biases).
    The N*k token-expert pairs are sorted by expert and laid out in
    blocks of ``routed_block_rows`` rows, each block one expert's (a
    group is padded to whole blocks); the two products then run block by
    block against that block's expert
    (``pallas_kernels.grouped_matmul``), and the rows go back to their
    pairs. The number of blocks is static: ``pairs // rows`` full ones
    at most, and one partly filled one per expert. An index of ``nx``
    (one past the last) marks a pair whose expert is not here, where
    the ``nx`` experts are a share of the routed ones: it joins no
    group, takes no row, and comes back as zeros."""
    from .pallas_kernels import grouped_matmul
    n, k = idx.shape
    pairs = n * k
    rows, nb = _routed_layout(pairs, nx)
    i32 = jnp.int32
    with jax.named_scope("route"):
        expert = idx.reshape(pairs).astype(i32)
        sizes = jnp.zeros((nx + 1,), i32).at[expert].add(1)[:nx]
        if stats is not None:
            stats["experts_touched"] = jnp.sum(sizes > 0).astype(i32)
            stats["pairs_held"] = jnp.sum(sizes).astype(i32)
        padded = (sizes + rows - 1) // rows * rows
        ends = jnp.cumsum(padded)                 # padded group ends
        order = jnp.argsort(expert, stable=True).astype(i32)
        sorted_e = expert[order]                  # absent pairs last
        here = jnp.minimum(sorted_e, nx - 1)
        first = jnp.cumsum(sizes) - sizes         # group starts, packed
        rank = jnp.arange(pairs, dtype=i32) - first[here]
        row = (ends - padded)[here] + rank        # pair -> padded row
        row = jnp.where(sorted_e < nx, row, nb * rows)   # absent: outside
        dest = jnp.zeros((pairs,), i32).at[order].set(row)
        used = (ends[-1] // rows).astype(i32)     # blocks that hold rows
        # block -> its expert; the blocks past the used ones repeat the
        # last used block's expert, so the kernel fetches nothing new
        at = jnp.minimum(jnp.arange(nb, dtype=i32),
                         jnp.maximum(used - 1, 0)) * rows
        block_e = jnp.minimum(
            jnp.searchsorted(ends, at, side="right",
                             method="compare_all").astype(i32), nx - 1)
        xp = jnp.zeros((nb * rows, x.shape[1]), x.dtype).at[dest].set(
            jnp.repeat(x, k, axis=0) if k > 1 else x, mode="drop")
    with jax.named_scope("experts"):
        def per_row(v):             # an expert's vector for its blocks
            return jnp.repeat(v[block_e], rows, axis=0)
        up = grouped_matmul(xp, w1, block_e, used, rows)
        if act == "silu":
            hid = up.shape[-1] // 2
            h = jax.nn.silu(up[:, :hid]) * up[:, hid:]
        elif act == "relu2":
            h = jnp.square(jax.nn.relu(up))
        else:
            h = jax.nn.relu(up + per_row(b1))
        y = grouped_matmul(h.astype(x.dtype), w2, block_e, used, rows)
        if act == "relu":
            y = y + per_row(b2)
    with jax.named_scope("route"):
        return y.at[dest].get(mode="fill", fill_value=0).reshape(n, k, -1)


def yarn_frequencies(half, base, factor, original_max, beta_fast=32.0,
                     beta_slow=1.0):
    """The ``half`` rotary frequencies under YaRN's scaling (Peng et
    al., arXiv:2309.00071, as DeepSeek-V2 runs it), float32 numpy: pair
    ``i`` keeps ``theta_i = base^(-i/half)`` where it turns more than
    ``beta_fast`` times over ``original_max`` positions, takes
    ``theta_i / factor`` where it turns fewer than ``beta_slow`` times,
    and a linear blend between the two pair indices those turn counts
    give (floor of the one, ceiling of the other, within
    ``[0, 2 half - 1]``)."""
    dim = 2 * int(half)
    theta = float(base) ** (-np.arange(half, dtype=np.float64) / half)

    def pair_of(turns):
        return dim * np.log(original_max / (turns * 2.0 * np.pi)) \
            / (2.0 * np.log(float(base)))

    low = max(int(np.floor(pair_of(beta_fast))), 0)
    high = min(int(np.ceil(pair_of(beta_slow))), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (theta / float(factor) * ramp
            + theta * (1.0 - ramp)).astype(np.float32)


def rope_rotate(x, positions, base=10000.0, rotary_dim=None, yarn=None):
    """Rotary position embedding (RoFormer / GPT-NeoX half-split form):
    rotate the two halves of each head dim by position-dependent angles,
    so q·k depends only on RELATIVE distance. x: [B, T, H, D] (D even);
    positions: [T] absolute positions of these tokens, or [B, T] when
    each batch row sits at its own clock (the decoder's slot-paged
    batched walk — every row gets its own angles). ``rotary_dim``
    (even, default D): only the first ``rotary_dim`` dims of each head
    turn, as two halves of their own; the rest pass through (partial
    rotary). ``yarn`` (optional ``(factor, original_max, beta_fast,
    beta_slow)``): the frequencies are ``yarn_frequencies``'; cos and
    sin stay unscaled."""
    d = x.shape[-1]
    r = d if rotary_dim is None else int(rotary_dim)
    if r < d:
        return jnp.concatenate(
            [rope_rotate(x[..., :r], positions, base, yarn=yarn),
             x[..., r:]], -1)
    half = d // 2
    if yarn is None:
        freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freq = jnp.asarray(yarn_frequencies(half, base, *yarn))
    ang = positions[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if ang.ndim == 2:          # positions [T]: broadcast over batch
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:                      # positions [B, T]: per-row angles
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


@register
class CCAttention(OpSpec):
    """Compressed convolutional attention (CCA; Zyphra,
    arXiv:2510.04476): causal grouped-query attention held entirely in
    a compressed space, whose queries and keys are mixed over the last
    three positions by two small causal convolutions and whose values
    are half the previous token's.

    data [B, T, E] (already normalized). With ``Q = num_heads * D``,
    ``K = num_kv_heads * D``, ``W = Q + K``:

    - ``qk_weight`` [W, E]: ``u_t = [q~_t ; k~_t]``;
      ``v_weight`` [K, E]: the first half of its rows gives this
      token's half of the values, the second half the half that the
      NEXT token uses (``v_t = [v1 h_t ; v2 h_{t-1}]``, ``h_{-1} = 0``:
      the first ``num_kv_heads / 2`` kv heads hold this token's values,
      the others the previous token's);
    - ``conv0_weight`` [W, 2], ``conv0_bias`` [W]: depthwise,
      ``c1_t = w[:, 0] * u_t + w[:, 1] * u_{t-1} + b``;
    - ``conv1_weight`` [G, 2, D, D] (``G = num_heads + num_kv_heads``),
      ``conv1_bias`` [W]: per head,
      ``c2_t[g] = w[g, 0] c1_t[g] + w[g, 1] c1_{t-1}[g] + b[g]``, the
      sequence of ``c1`` padded with zeros on the left;
    - q-k mean: ``m_t[i] = (q~_t[i] + k~_t[kv(i)]) / 2``,
      ``q_t = c2_t[:Q] + m_t``, ``k_t[j] = c2_t[Q:][j] + mean of m_t
      over j's query heads``;
    - per head ``q <- sqrt(D) q / |q|``,
      ``k <- sqrt(D) exp(temp_j) k / |k|`` (``temp`` [num_kv_heads]);
      rotary on the first ``rotary_dim`` dims at ``rope_base``;
    - causal softmax(q . k / sqrt(D)) over v, grouped;
      ``out_weight`` [E, Q].

    The full-sequence forward (training, the executor) attends with
    the flash kernel (``impl``); the decoder's cached form
    (``parallel/decode.py``) keeps K and V rows and a short ring of
    the last positions' ``u`` and ``v2`` per sequence, and computes the
    same mixing through ``cca_qkv``."""

    name = "CCAttention"
    params = {"num_heads": Param("int"), "num_kv_heads": Param("int"),
              "head_dim": Param("int"),
              "rotary_dim": Param("int", 0),
              "rope_base": Param("float", 10000.0),
              "impl": Param("str", "flash")}

    def arguments(self, p):
        return ["data", "qk_weight", "v_weight", "conv0_weight",
                "conv0_bias", "conv1_weight", "conv1_bias", "temp",
                "out_weight"]

    @staticmethod
    def widths(p):
        """(Q, K, D): the query and key/value widths and the head's."""
        h, kv, d = p["num_heads"], p["num_kv_heads"], p["head_dim"]
        if kv < 2 or kv % 2 or h % kv:
            raise MXNetError(
                "CCAttention: num_kv_heads=%d must be even (half hold "
                "the previous token's values) and divide num_heads=%d"
                % (kv, h))
        return h * d, kv * d, d

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        if len(d) != 3:
            raise MXNetError("CCAttention: data must be [B, T, E]")
        q, k, hd = self.widths(p)
        e, g = d[2], p["num_heads"] + p["num_kv_heads"]
        want = [d, (q + k, e), (k, e), (q + k, 2), (q + k,),
                (g, 2, hd, hd), (q + k,), (p["num_kv_heads"],), (e, q)]
        names = self.arguments(p)
        return [shape_assign(s, w, "CCAttention " + n)
                for s, w, n in zip(in_shapes, want, names)], [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x, wqk, wv, c0w, c0b, c1w, c1b, temp, wo = ins
        b, t, _ = x.shape
        qw, kw, d = self.widths(p)
        h, kv = p["num_heads"], p["num_kv_heads"]
        with jax.named_scope("proj"):
            u = jnp.einsum("bte,fe->btf", x, wqk)
            vv = jnp.einsum("bte,fe->btf", x, wv)
        with jax.named_scope("conv"):
            q, k, v = cca_qkv(p, u, vv, (c0w, c0b, c1w, c1b, temp),
                              jnp.arange(t, dtype=jnp.int32)[None])
        with jax.named_scope("attend"):
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)
            if p["impl"] == "flash":
                from .pallas_kernels import flash_attention
                o = flash_attention(q, k, v, causal=True)
            elif p["impl"] == "dense":
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / float(np.sqrt(d))
                mask = jnp.tril(jnp.ones((t, t), bool))
                s = jnp.where(mask[None, None], s, -jnp.inf)
                o = jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(s, axis=-1), v)
            else:
                raise MXNetError("CCAttention: unknown impl %r (flash "
                                 "or dense)" % (p["impl"],))
        with jax.named_scope("proj"):
            return [jnp.einsum("btq,eq->bte", o.reshape(b, t, qw), wo)], []


def cca_qkv(p, u, vv, mix, positions, prev=None):
    """CCA's queries, keys and values of a chunk from its raw
    projections: the two convolutions, the q-k mean, the value shift,
    the norms and the rotary turn. One function for the full forward
    (``prev=None``: the chunk starts the sequence) and the decoder's
    cached walk.

    ``u`` [B, C, W], ``vv`` [B, C, K]: the chunk's ``qk`` and ``v``
    projections. ``mix``: (conv0_weight, conv0_bias, conv1_weight,
    conv1_bias, temp). ``positions`` [1 or B, C] int32, absolute and
    consecutive. ``prev``: (u of the position before the chunk, u of
    the one before that, the ``v2`` half of the position before), each
    [B, 1, .]; whatever lies before position 0 is taken as zero here,
    by position, so a caller hands over what its ring holds and never
    has to clear it. Returns q [B, C, H, D], k and v [B, C, Hkv, D] in
    ``u``'s dtype; the mixing itself runs in float32."""
    c0w, c0b, c1w, c1b, temp = mix
    qw, kw, d = CCAttention.widths(p)
    h, kv = p["num_heads"], p["num_kv_heads"]
    b, c, w = u.shape
    f32 = jnp.float32
    positions = jnp.asarray(positions, jnp.int32)
    first = positions[:, :1]                               # [1|B, 1]
    uf, vf = u.astype(f32), vv.astype(f32)
    if prev is None:
        u1 = u2 = jnp.zeros((b, 1, w), f32)
        v2p = jnp.zeros((b, 1, kw // 2), f32)
    else:
        u1, u2, v2p = (z.astype(f32) for z in prev)
        u1 = jnp.where((first >= 1)[..., None], u1, 0)
        u2 = jnp.where((first >= 2)[..., None], u2, 0)
        v2p = jnp.where((first >= 1)[..., None], v2p, 0)
    # positions first-2 .. first+C-1, then the first conv over
    # first-1 .. first+C-1 (what lies before position 0 is padding)
    ue = jnp.concatenate([u2, u1, uf], axis=1)
    c0w, c0b = c0w.astype(f32), c0b.astype(f32)
    c1 = c0w[:, 0] * ue[:, 1:] + c0w[:, 1] * ue[:, :-1] + c0b
    pe = jnp.concatenate([first - 1, positions], axis=1)
    c1 = jnp.where((pe >= 0)[..., None], c1, 0)
    g = h + kv
    c1h = c1.reshape(b, c + 1, g, d)
    c1w = c1w.astype(f32)
    c2 = (jnp.einsum("bcgi,goi->bcgo", c1h[:, 1:], c1w[:, 0])
          + jnp.einsum("bcgi,goi->bcgo", c1h[:, :-1], c1w[:, 1])
          + c1b.astype(f32).reshape(g, d))
    qt = uf[..., :qw].reshape(b, c, kv, h // kv, d)
    kt = uf[..., qw:].reshape(b, c, kv, 1, d)
    m = (qt + kt) * 0.5                                    # [b,c,kv,G,d]
    q = c2[:, :, :h] + m.reshape(b, c, h, d)
    k = c2[:, :, h:] + jnp.mean(m, axis=3)

    def unit(z):
        n = jnp.sqrt(jnp.sum(jnp.square(z), axis=-1, keepdims=True))
        return z * (float(np.sqrt(d)) / jnp.maximum(n, 1e-12))

    q = unit(q)
    k = unit(k) * jnp.exp(temp.astype(f32))[:, None]
    rot = p.get("rotary_dim") or d
    q = rope_rotate(q, positions, p["rope_base"], rot)
    k = rope_rotate(k, positions, p["rope_base"], rot)
    half = kw // 2
    v = jnp.concatenate(
        [vf[..., :half],
         jnp.concatenate([v2p, vf[:, :-1, half:]], axis=1)], axis=-1)
    dt = u.dtype
    return (q.astype(dt), k.astype(dt),
            v.reshape(b, c, kv, d).astype(dt))


@register
class MultiHeadAttention(OpSpec):
    """Multi-head self-attention with fused QKV projection.

    data: [B, T, E]; weights: qkv_weight [F, E], qkv_bias [F] with
    ``F = E + 2*num_kv_heads*head_dim`` (= 3E without grouped-query
    attention), out_weight [E, E], out_bias [E] (weights laid out
    ``num_hidden x input`` like FullyConnected,
    fully_connected-inl.h:148-171).

    ``impl``: flash (Pallas kernel), blockwise (lax.scan recurrence), or
    dense. Long sequences shard over the ``sp`` mesh axis via
    ``parallel.ring_attention`` at the trainer level; inside a single
    program this op is the per-shard compute.

    ``rope=True`` applies rotary position embeddings to q/k before the
    attention kernel (``rope_rotate``) — rotation attaches to each
    token's absolute position, so it composes with every impl
    (under shard_map the shard's global offset comes from
    ``lax.axis_index``; striping re-deals already-rotated tokens).

    ``num_kv_heads`` (default 0 = ``num_heads``) enables grouped-query
    attention: K/V are projected to only this many heads and each K/V
    head serves ``num_heads/num_kv_heads`` query heads. The fused
    projection shrinks to ``[E + 2*num_kv_heads*head_dim, E]``, and —
    the point on TPU — the decoder's K/V cache shrinks by the group
    factor, cutting the per-token HBM reads that dominate deep-fill
    decode (doc/performance.md "KV-cache decode"). Inside the training
    step K/V are broadcast back to ``num_heads`` (XLA fuses the
    broadcast into the attention GEMMs), so every impl composes.

    ``window`` (default 0 = unlimited) enables sliding-window
    attention: position q attends only to keys in
    ``(q - window, q]`` — ``window`` positions including itself.
    Causal-only. The flash Pallas kernel SKIPS out-of-window key/query
    blocks in the forward and both backward kernels (attention compute
    scales with T·window instead of T²); dense and blockwise mask; the
    sp ring impls reject it. The decoder's cache for a windowed
    attention is a RING BUFFER of ``window`` slots, so decode memory
    and per-token cache reads are O(window) no matter how long the
    generation runs (with rope there is no positional table to outgrow
    either).

    ``scale`` (default 0 = ``1/sqrt(D)``) multiplies the scores before
    the softmax, in every impl and in the decoder's reads (Granite 4.0-H
    scales by ``attention_multiplier`` = 1/D). ``no_bias=True`` drops
    both biases: the op then takes ``data, qkv_weight, out_weight`` and
    stores no zero bias leaves.
    """

    name = "MultiHeadAttention"
    params = {"num_heads": Param("int"),
              "num_kv_heads": Param("int", 0),
              "causal": Param("bool", True),
              "impl": Param("str", "flash"),
              "dropout": Param("float", 0.0),
              "rope": Param("bool", False),
              "rope_base": Param("float", 10000.0),
              "window": Param("int", 0),
              "axis_name": Param("str", "sp"),
              "scale": Param("float", 0.0),
              "no_bias": Param("bool", False)}

    @staticmethod
    def kv_heads(p):
        kv = p.get("num_kv_heads", 0) or p["num_heads"]
        if kv < 1 or p["num_heads"] % kv:
            raise MXNetError(
                "MultiHeadAttention: num_kv_heads=%d must be a positive "
                "divisor of num_heads=%d" % (kv, p["num_heads"]))
        return kv

    @staticmethod
    def check_head_shards(p, tp, where="tensor-parallel serving"):
        """Refuse LOUDLY when the head layout does not partition
        evenly over ``tp`` shards. Tensor-parallel serving splits the
        KV cache (and the per-head attention compute) on the KV-HEAD
        dimension, keeping each grouped-query head with its query
        group — an uneven split would silently give shards different
        work shapes (and GQA groups straddling a shard boundary),
        so the divisibility is a hard contract, not a rounding."""
        kv = MultiHeadAttention.kv_heads(p)
        if kv % tp:
            raise MXNetError(
                "MultiHeadAttention: %s needs the %d kv head(s) to "
                "divide evenly over tp=%d shards (GQA query groups "
                "must stay whole on their kv head's shard) — use a "
                "tp that divides num_kv_heads" % (where, kv, tp))

    @staticmethod
    def softmax_scale(p, d):
        """What the scores of heads of ``d`` are multiplied by: the
        ``scale`` parameter, or ``1/sqrt(d)`` where it is 0. A plain
        float: ``np.sqrt`` returns a STRONG f64 scalar under x64, which
        would silently promote the whole graph."""
        return float(p.get("scale", 0.0) or 1.0 / np.sqrt(d))

    @staticmethod
    def split_inputs(p, ins):
        """(data, qkv_weight, qkv_bias, out_weight, out_bias) of the op's
        inputs, the biases None under ``no_bias``."""
        if p.get("no_bias"):
            x, wqkv, wo = ins
            return x, wqkv, None, wo, None
        return tuple(ins)

    def arguments(self, p):
        if p.get("no_bias"):
            return ["data", "qkv_weight", "out_weight"]
        return ["data", "qkv_weight", "qkv_bias", "out_weight", "out_bias"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        if len(d) != 3:
            raise MXNetError("MultiHeadAttention: data must be [B, T, E]")
        e = d[2]
        if e % p["num_heads"] != 0:
            raise MXNetError("MultiHeadAttention: %d heads do not divide "
                             "embed dim %d" % (p["num_heads"], e))
        if p["rope"] and (e // p["num_heads"]) % 2:
            raise MXNetError("MultiHeadAttention: rope needs an even "
                             "head dim, got %d" % (e // p["num_heads"]))
        kv = self.kv_heads(p)
        if p.get("window", 0):
            if p["window"] < 1:
                raise MXNetError("MultiHeadAttention: window must be "
                                 ">= 1 (0 disables), got %d"
                                 % p["window"])
            if not p["causal"]:
                raise MXNetError("MultiHeadAttention: window>0 is "
                                 "defined for causal attention only")
        f = e + 2 * kv * (e // p["num_heads"])  # q rows + kv k/v rows
        if p.get("no_bias"):
            return [d, shape_assign(in_shapes[1], (f, e), "qkv_weight"),
                    shape_assign(in_shapes[2], (e, e), "out_weight")], \
                [d], []
        ins = [d,
               shape_assign(in_shapes[1], (f, e), "qkv_weight"),
               shape_assign(in_shapes[2], (f,), "qkv_bias"),
               shape_assign(in_shapes[3], (e, e), "out_weight"),
               shape_assign(in_shapes[4], (e,), "out_bias")]
        return ins, [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x, wqkv, bqkv, wo, bo = self.split_inputs(p, ins)
        b, t, e = x.shape
        h = p["num_heads"]
        d = e // h
        kv = self.kv_heads(p)
        scale = self.softmax_scale(p, d) if p.get("scale") else None
        qkv = jnp.einsum("bte,fe->btf", x, wqkv)
        if bqkv is not None:
            qkv = qkv + bqkv
        q = qkv[..., :e].reshape(b, t, h, d)
        k = qkv[..., e:e + kv * d].reshape(b, t, kv, d)
        v = qkv[..., e + kv * d:].reshape(b, t, kv, d)
        if kv != h:
            # GQA: broadcast each K/V head to its query group. On the
            # einsum paths (dense/blockwise) XLA folds the repeat into
            # the attention GEMM operands; the Pallas flash kernel
            # takes concrete buffers, so there the expanded K/V ARE
            # materialized — GQA's training win is the smaller
            # projection, its big win the kv-head decode cache
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)
        if p["rope"]:
            if d % 2:
                raise MXNetError("MultiHeadAttention: rope needs an even "
                                 "head dim, got %d" % d)
            try:  # sequence-parallel shard: global offset of this shard
                off = jax.lax.axis_index(p["axis_name"]) * t
            except NameError:
                off = 0
            posv = off + jnp.arange(t)
            q = rope_rotate(q, posv, p["rope_base"])
            k = rope_rotate(k, posv, p["rope_base"])
        impl = p["impl"]
        window = p.get("window", 0)
        if window:
            # mirror infer_shape's validation: forward can run without
            # shape inference (direct bind), and a negative window on
            # the dense path would mask EVERY key — NaN softmax rows
            if window < 1:
                raise MXNetError("MultiHeadAttention: window must be "
                                 ">= 1 (0 disables), got %d" % window)
            if not p["causal"]:
                raise MXNetError("MultiHeadAttention: window>0 is "
                                 "defined for causal attention only")
            if impl in ("ring", "ring_striped"):
                raise MXNetError(
                    "MultiHeadAttention: window>0 is not supported by "
                    "the sp ring impls — short windows don't need "
                    "sequence sharding; use impl='flash'/'blockwise'/"
                    "'dense'")
        if impl == "flash":
            from .pallas_kernels import flash_attention
            o = flash_attention(q, k, v, causal=p["causal"],
                                window=window, scale=scale)
        elif impl == "blockwise":
            from ..parallel.ring import blockwise_attention
            o = blockwise_attention(q, k, v, causal=p["causal"],
                                    window=window, scale=scale)
        elif impl == "dense":
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
            # float(): np.sqrt returns a STRONG f64 scalar under x64,
            # which would silently promote the whole graph (and f64 is
            # emulated, ~10x slower, on TPU)
            s = s / float(np.sqrt(d)) if scale is None else s * scale
            if p["causal"]:
                qpos_m = jnp.arange(t)[:, None]
                kpos_m = jnp.arange(t)[None, :]
                mask = kpos_m <= qpos_m
                if window:
                    mask &= qpos_m - kpos_m < window
                s = jnp.where(mask[None, None], s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        elif impl == "ring":
            # sequence/context parallelism: this shard holds [B, T/n, E];
            # K/V blocks rotate the ring over mesh axis `axis_name`.
            # Only valid inside shard_map (SequenceParallelTrainer) —
            # positions are derived from lax.axis_index.
            from ..parallel.ring import _ring_attention_local
            try:
                o = _ring_attention_local(q, k, v,
                                          axis_name=p["axis_name"],
                                          causal=p["causal"], scale=scale)
            except NameError as e:
                raise MXNetError(
                    "MultiHeadAttention impl='ring' needs mesh axis %r "
                    "bound by shard_map — train this symbol with "
                    "SequenceParallelTrainer, or use impl='flash'/"
                    "'dense' for single-program execution (%s)"
                    % (p["axis_name"], e)) from e
        elif impl == "ring_striped":
            # balanced causal ring (striped attention): re-deal this
            # shard's CONTIGUOUS tokens round-robin across the ring with
            # one all_to_all, run the half-block Pallas ring, deal back.
            # Drop-in for impl='ring' inside SequenceParallelTrainer;
            # ~2x causal speedup at equal ring size (parallel/ring.py
            # module docstring has the balance math).
            from ..parallel.ring import _striped_ring_local
            if not p["causal"]:
                raise MXNetError("impl='ring_striped' is causal-only — "
                                 "striping exists to balance the causal "
                                 "mask; use impl='ring' for full "
                                 "attention")
            axis = p["axis_name"]
            try:
                n = jax.lax.psum(1, axis)
            except NameError as e:
                raise MXNetError(
                    "MultiHeadAttention impl='ring_striped' needs mesh "
                    "axis %r bound by shard_map — train this symbol "
                    "with SequenceParallelTrainer (%s)"
                    % (axis, e)) from e
            c = q.shape[1]
            if c % n:
                raise MXNetError(
                    "impl='ring_striped': local length %d not divisible "
                    "by ring size %d" % (c, n))

            def deal(z):  # contiguous shard -> striped shard
                B_, C_, H_, D_ = z.shape
                z = z.reshape(B_, C_ // n, n, H_, D_) \
                     .transpose(0, 2, 1, 3, 4)
                z = jax.lax.all_to_all(z, axis, 1, 1)
                return z.reshape(B_, C_, H_, D_)

            def undeal(z):  # striped shard -> contiguous shard
                B_, C_, H_, D_ = z.shape
                z = z.reshape(B_, n, C_ // n, H_, D_)
                z = jax.lax.all_to_all(z, axis, 1, 1)
                return z.transpose(0, 2, 1, 3, 4) \
                        .reshape(B_, C_, H_, D_)

            o = undeal(_striped_ring_local(deal(q), deal(k), deal(v),
                                           axis_name=axis, scale=scale,
                                           block_q=128, block_k=128))
        else:
            raise MXNetError("MultiHeadAttention: unknown impl %r" % impl)
        o = o.reshape(b, t, e)
        out = jnp.einsum("bte,fe->btf", o, wo)
        if bo is not None:
            out = out + bo
        if is_train and p["dropout"] > 0.0:
            keep = 1.0 - p["dropout"]
            mask = jax.random.bernoulli(rng, keep, out.shape)
            out = jnp.where(mask, out / keep, 0.0)
        return [out], []


# -- Gated DeltaNet: linear attention with a matrix state ---------------------
# (Yang et al., arXiv:2412.06464, as Qwen3-Next runs it.) Per value head
# a float32 state S [Dk, Dv]; with g_t <= 0 the log of the decay and
# beta_t the write strength:
#     S' = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S'^T k_t);
#     S_t = S' + k_t d_t^T;   o_t = S_t^T q_t.
# ``gdn_step`` is that recurrence for one position (under ``lax.scan``
# the definition the chunked form is held to, and the decode step of a
# walk without ``lens``); ``gdn_chunked`` is the same recurrence over a
# sequence in chunks; a slot walk's decode step takes
# ``pallas_kernels.gdn_state_step``, ``gdn_step`` for the live slots alone
# (``gdn_steps_in_place``).

_HI = jax.lax.Precision.HIGHEST     # the state is float32 and stays it


def gdn_step(state, q, k, v, beta, g):
    """One position of the recurrence for every (batch row, head):
    ``state`` [B, H, Dk, Dv] float32, ``q``/``k`` [B, H, Dk], ``v``
    [B, H, Dv], ``beta``/``g`` [B, H], all float32. Returns (the new
    state, o [B, H, Dv]). Two passes over the state: one reads it for
    ``S'^T k`` and ``S'^T q`` together (``o = S'^T q + d (k . q)``, so
    the output does not wait for the new state), one reads it again and
    writes ``S' + k d^T``."""
    sd = state * jnp.exp(g)[..., None, None]
    ks = jnp.sum(sd * k[..., None], axis=-2)
    qs = jnp.sum(sd * q[..., None], axis=-2)
    d = beta[..., None] * (v - ks)
    o = qs + d * jnp.sum(k * q, axis=-1, keepdims=True)
    return sd + k[..., None] * d[..., None, :], o


def gdn_sequential(state, q, k, v, beta, g):
    """The recurrence position by position (``lax.scan`` of
    ``gdn_step``): ``q``/``k`` [B, T, H, Dk], ``v`` [B, T, H, Dv],
    ``beta``/``g`` [B, T, H]. The definition; a prefill takes
    ``gdn_chunked``. Returns (state, o [B, T, H, Dv])."""
    state, o = jax.lax.scan(
        lambda s, xs: gdn_step(s, *xs), state,
        tuple(jnp.moveaxis(z, 1, 0) for z in (q, k, v, beta, g)))
    return state, jnp.moveaxis(o, 0, 1)


def gdn_chunked(state, q, k, v, beta, g, chunk=64):
    """The same recurrence in chunks of ``chunk`` positions (the
    chunkwise form of the gated delta rule). Inside a chunk, with
    ``gamma_i = sum_{j<=i} g_j``: ``A_ij = -beta_i (k_i . k_j)
    exp(gamma_i - gamma_j)`` for ``j < i``; ``W`` and ``U`` solve
    ``(I - A) [W U] = [beta k exp(gamma), beta v]`` (forward
    substitution, the matrix is unit lower triangular); then for the
    state ``S`` the chunk starts from, ``V' = U - W S``,
    ``o = (q exp(gamma)) S + tril(q k^T exp(gamma_i - gamma_j)) V'``,
    ``S <- exp(gamma_C) S + (k exp(gamma_C - gamma))^T V'``. Everything
    that does not need ``S`` is computed for all chunks at once; the
    chunks then follow each other in a ``lax.scan`` of three small
    products. A sequence that is not whole chunks is padded with
    positions of ``beta = 0, g = 0``, which leave the state as it is.
    Shapes as ``gdn_sequential``; float32 at full product precision."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(int(chunk), t)
    n = -(-t // c)
    pad = n * c - t

    def chunks(z):                      # [B, T, H, ...] -> [B, H, N, C, ...]
        if pad:
            z = jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
        z = z.reshape((b, n, c) + z.shape[2:])
        return jnp.moveaxis(z, 3, 1)

    q, k, v, beta, g = (chunks(z) for z in (q, k, v, beta, g))
    gam = jnp.cumsum(g, axis=-1)                            # [B,H,N,C]
    low = jnp.tril(jnp.ones((c, c), bool))
    # exp only where j <= i: above the diagonal the difference is
    # positive and may overflow
    decay = jnp.exp(jnp.where(low, gam[..., :, None] - gam[..., None, :],
                              -jnp.inf))
    kb = k * beta[..., None]
    kk = jnp.einsum("bhnik,bhnjk->bhnij", kb, k, precision=_HI)
    lower = jnp.where(jnp.tril(low, -1), kk * decay, 0.0) \
        + jnp.eye(c, dtype=kk.dtype)                        # I - A
    rhs = jnp.concatenate([kb * jnp.exp(gam)[..., None],
                           v * beta[..., None]], axis=-1)
    wu = jax.lax.linalg.triangular_solve(
        lower, rhs, left_side=True, lower=True, unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    qk = jnp.einsum("bhnik,bhnjk->bhnij", q, k, precision=_HI) * decay
    qg = q * jnp.exp(gam)[..., None]
    last = gam[..., -1:]                                    # [B,H,N,1]
    kg = k * jnp.exp(last - gam)[..., None]

    def body(s, xs):
        w_, u_, qk_, qg_, kg_, last_ = xs
        vn = u_ - jnp.einsum("bhik,bhkv->bhiv", w_, s, precision=_HI)
        o = jnp.einsum("bhik,bhkv->bhiv", qg_, s, precision=_HI) \
            + jnp.einsum("bhij,bhjv->bhiv", qk_, vn, precision=_HI)
        s = s * jnp.exp(last_)[..., None] \
            + jnp.einsum("bhik,bhiv->bhkv", kg_, vn, precision=_HI)
        return s, o

    state, o = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(z, 2, 0)
                           for z in (w, u, qk, qg, kg, last)))
    o = jnp.moveaxis(o, 0, 2)                               # [B,H,N,C,Dv]
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * c, h, dv)
    return state, o[:, :t]


@register
class GatedDeltaNet(OpSpec):
    """A Gated DeltaNet mixer (linear attention; Yang et al.,
    arXiv:2412.06464) as Qwen3-Next's ``linear_attention`` layers run
    it. data [B, T, E] (already normalized). With ``Hk`` key heads of
    ``Dk``, ``Hv`` value heads of ``Dv`` (``Hk`` divides ``Hv``: key
    head j serves value heads ``[j Hv/Hk, (j+1) Hv/Hk)``),
    ``K = Hk Dk``, ``V = Hv Dv``, ``F = 2K + V``:

    - ``qkvz_weight`` [F + V, E]: ``[q ; k ; v ; z] = W x`` (the
      checkpoint interleaves these rows per key head; this flat order
      is a permutation of it); ``ba_weight`` [2 Hv, E]: ``[b ; a]``;
    - ``conv_weight`` [F, kernel]: depthwise and causal over
      ``u = [q ; k ; v]``, no bias, zeros before position 0,
      ``c_t = silu(sum_j w[:, j] u_{t-(kernel-1)+j})``;
    - ``beta = sigmoid(b)``, ``g = -exp(a_log) softplus(a + dt_bias)``
      per value head (``a_log``, ``dt_bias`` [Hv]), float32;
    - q and k repeated to ``Hv`` heads, each ``x / sqrt(sum x^2 +
      1e-6)``, q times ``Dk ** -0.5``;
    - the recurrence at the top of this section, state float32;
    - ``y = out_weight [rmsnorm(o) norm_weight silu(z)]``, the norm
      over ``Dv`` per head, ``norm_weight`` [Dv], ``out_weight``
      [E, V].

    The full-sequence forward starts from the zero state and runs the
    chunked form; the decoder's cached form (``parallel/decode.py``)
    keeps NO rows: per sequence the state [Hv, Dk, Dv] and the last
    ``kernel - 1`` positions' ``u``."""

    name = "GatedDeltaNet"
    params = {"num_k_heads": Param("int"), "num_v_heads": Param("int"),
              "head_k_dim": Param("int"), "head_v_dim": Param("int"),
              "conv_kernel": Param("int", 4),
              "eps": Param("float", 1e-6)}

    def arguments(self, p):
        return ["data", "qkvz_weight", "ba_weight", "conv_weight",
                "a_log", "dt_bias", "norm_weight", "out_weight"]

    @staticmethod
    def widths(p):
        """(K, V, F): the key, value and convolved widths."""
        hk, hv = p["num_k_heads"], p["num_v_heads"]
        if hk < 1 or hv % hk:
            raise MXNetError(
                "GatedDeltaNet: num_k_heads=%d must divide num_v_heads="
                "%d" % (hk, hv))
        k, v = hk * p["head_k_dim"], hv * p["head_v_dim"]
        return k, v, 2 * k + v

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        if len(d) != 3:
            raise MXNetError("GatedDeltaNet: data must be [B, T, E]")
        _, v, f = self.widths(p)
        e, hv = d[2], p["num_v_heads"]
        want = [d, (f + v, e), (2 * hv, e), (f, p["conv_kernel"]), (hv,),
                (hv,), (p["head_v_dim"],), (e, v)]
        return [shape_assign(s, w, "GatedDeltaNet " + n)
                for s, w, n in zip(in_shapes, want,
                                   self.arguments(p))], [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x = ins[0]
        b = x.shape[0]
        _, _, f = self.widths(p)
        state = jnp.zeros((b, p["num_v_heads"], p["head_k_dim"],
                           p["head_v_dim"]), jnp.float32)
        prev = jnp.zeros((b, p["conv_kernel"] - 1, f), x.dtype)
        y, _, _ = gdn_mix(p, x, ins[1:], state, prev)
        return [y], []


def gdn_steps_in_place(p, c, lens):
    """Whether ``gdn_mix`` advances the state of a chunk of ``c``
    positions through ``pallas_kernels.gdn_state_step`` (the live rows'
    states alone, where they lie), by what the code can see: ONE
    position of a slot walk (``lens`` given), and on the chip heads of
    whole lane tiles (the interpreter takes any). Everything else (the
    full forward, a prefill piece, offline ``generate``) runs
    ``gdn_step`` / ``gdn_chunked``, the definition."""
    from . import pallas_kernels as pk
    if c != 1 or lens is None:
        return False
    return pk._use_interpret() or (p["head_k_dim"] % 128 == 0
                                   and p["head_v_dim"] % 128 == 0)


def gdn_mix(p, x, weights, state, prev, real=None, lens=None, fresh=None):
    """The mixer on a chunk ``x`` [B, C, E] that continues a sequence:
    ``state`` [B, Hv, Dk, Dv] float32 and ``prev`` [B, kernel-1, F],
    the ``u`` of the positions before the chunk (zeros at the start of
    a sequence). One function for the full forward and the decoder's
    cached walk. ``real`` ([B, 1] int32, optional): only the chunk's
    first ``real`` positions are tokens, the rest padding, which must
    leave the state and ``prev`` of the last real one (``beta = 0, g =
    0`` there). ``lens`` ([B] int32, optional: a slot walk's): a row
    with ``lens`` 0 is not live and keeps its state and ``prev``
    untouched. ``fresh`` ([B] bool, optional): a live row that starts
    from the ZERO state whatever ``state`` holds for it. Where
    ``gdn_steps_in_place`` says so, nothing outside the kernel touches
    the whole ``state``. Returns (y [B, C, E], the new state, the new
    prev)."""
    wqkvz, wba, wconv, a_log, dt_bias, wnorm, wout = weights
    kw, vw, f = GatedDeltaNet.widths(p)
    hk, hv = p["num_k_heads"], p["num_v_heads"]
    dk, dv = p["head_k_dim"], p["head_v_dim"]
    b, c, _ = x.shape
    f32 = jnp.float32
    with jax.named_scope("proj"):
        qkvz = jnp.einsum("bte,fe->btf", x, wqkvz)
        ba = jnp.einsum("bte,fe->btf", x, wba,
                        preferred_element_type=f32)
        u, z = qkvz[..., :f], qkvz[..., f:]
    with jax.named_scope("conv"):
        win = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
        wf = wconv.astype(f32)
        taps = wconv.shape[1]
        cv = jax.nn.silu(sum(wf[:, j] * win[:, j:j + c].astype(f32)
                             for j in range(taps)))
        # the window the NEXT chunk starts from: the last kernel-1
        # real positions (of ``prev`` too, where the chunk holds fewer)
        if real is None:
            nxt = win[:, c:]
        else:
            at = real + jnp.arange(taps - 1, dtype=jnp.int32)
            nxt = jnp.take_along_axis(win, at[..., None], axis=1)

        def unit(t):
            return t * jax.lax.rsqrt(
                jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)

        rep = hv // hk
        q = jnp.repeat(unit(cv[..., :kw].reshape(b, c, hk, dk)), rep,
                       axis=2) * (float(dk) ** -0.5)
        k = jnp.repeat(unit(cv[..., kw:2 * kw].reshape(b, c, hk, dk)),
                       rep, axis=2)
        v = cv[..., 2 * kw:].reshape(b, c, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log.astype(f32)) \
            * jax.nn.softplus(ba[..., hv:] + dt_bias.astype(f32))
        if real is not None:
            pad = (jnp.arange(c, dtype=jnp.int32) >= real)[..., None]
            beta = jnp.where(pad, 0.0, beta)
            g = jnp.where(pad, 0.0, g)
    live = None if lens is None else jnp.asarray(lens, jnp.int32) > 0
    with jax.named_scope("state"):
        if gdn_steps_in_place(p, c, lens):
            from .pallas_kernels import gdn_state_step
            new, o = gdn_state_step(
                state, q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0], lens,
                jnp.zeros((b,), bool) if fresh is None else fresh)
            o = o[:, None]
        else:
            start = state if fresh is None else \
                jnp.where(fresh[:, None, None, None], 0, state)
            if c == 1:
                new, o = gdn_step(start, q[:, 0], k[:, 0], v[:, 0],
                                  beta[:, 0], g[:, 0])
                o = o[:, None]
            else:
                new, o = gdn_chunked(start, q, k, v, beta, g)
            if live is not None:
                new = jnp.where(live[:, None, None, None], new, state)
        if live is not None:
            nxt = jnp.where(live[:, None, None], nxt,
                            prev.astype(nxt.dtype))
    with jax.named_scope("norm"):
        ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(ms + p["eps"]) * wnorm.astype(f32) \
            * jax.nn.silu(z.astype(f32).reshape(b, c, hv, dv))
    with jax.named_scope("proj"):
        y = jnp.einsum("btv,ev->bte", o.reshape(b, c, vw).astype(x.dtype),
                       wout)
    return y, new, nxt.astype(prev.dtype)


# -- Mamba-2: a state-space mixer in its SSD form -----------------------------
# (Dao & Gu, arXiv:2405.21060, as Granite 4.0-H runs it.) Per head a float32
# state S [P, N]; with ``dt_t > 0`` the head's step and ``a = -exp(A_log)``:
#     S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t
# (the ``D`` skip is added outside). B and C come in ``G`` groups: group ``g``
# serves heads ``[g H/G, (g+1) H/G)``; with one group (Granite's) they are
# [.., N] and every head's, with G they are [.., G, N] (Nemotron-H's eight).
# ``ssd_step`` is that recurrence for one position (under ``lax.scan``
# the definition the chunked form is held to, and the decode step of a walk
# without ``lens``); ``ssd_chunked`` is the state-space duality's chunked
# form over a sequence; a slot walk's decode step takes
# ``pallas_kernels.ssd_state_step``, ``ssd_step`` for the live slots alone
# (``ssd_steps_in_place``).


def _per_head(v, heads):
    """B or C of one position, [B, N] (one group) or [B, G, N], laid
    against a state [B, H, P, N]."""
    if v.ndim == 2:
        return v[:, None, None, :]
    return jnp.repeat(v, heads // v.shape[1], axis=1)[:, :, None, :]


def ssd_step(state, x, b, c, dt, a):
    """One position of the recurrence for every (batch row, head):
    ``state`` [B, H, P, N] float32, ``x`` [B, H, P], ``b``/``c`` [B, N]
    (one group) or [B, G, N], ``dt`` [B, H], ``a`` [H] (negative), all
    float32. Returns (the new state, y [B, H, P]): one pass over the
    state, which is decayed, takes the rank-one write and is read
    against ``c``."""
    h = state.shape[1]
    s = state * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * _per_head(b, h)
    return s, jnp.sum(s * _per_head(c, h), axis=-1)


def ssd_sequential(state, x, b, c, dt, a):
    """The recurrence position by position (``lax.scan`` of
    ``ssd_step``): ``x`` [B, T, H, P], ``b``/``c`` [B, T, N] or
    [B, T, G, N], ``dt`` [B, T, H]. The definition; a prefill takes
    ``ssd_chunked``. Returns (state, y [B, T, H, P])."""
    state, y = jax.lax.scan(
        lambda s, xs: ssd_step(s, *xs, a), state,
        tuple(jnp.moveaxis(z, 1, 0) for z in (x, b, c, dt)))
    return state, jnp.moveaxis(y, 0, 1)


def ssd_chunked(state, x, b, c, dt, a, chunk=256):
    """The same recurrence in chunks of ``chunk`` positions (the SSD
    form). Inside a chunk, with ``l_i = sum_{j<=i} dt_j a`` per head:
    ``y = tril(C B^T exp(l_i - l_j)) (dt x) + exp(l_i) C S`` for the
    state ``S`` the chunk starts from, and ``S <- exp(l_C) S + sum_j
    exp(l_C - l_j) dt_j x_j B_j^T``. Everything that does not need
    ``S`` is computed for all chunks at once; the chunks then follow
    each other in a ``lax.scan`` of two small products. A sequence that
    is not whole chunks is padded with positions of ``dt = 0``, which
    leave the state as it is. Shapes as ``ssd_sequential``; with G
    groups the heads are taken as [G, H/G] and ``C B^T`` is made once a
    group. Float32 at full product precision."""
    bsz, t, h, pdim = x.shape
    n = b.shape[-1]
    cs = min(int(chunk), t)
    nc = -(-t // cs)
    pad = nc * cs - t
    grouped = b.ndim == 4
    # einsum labels: a head is ``h`` of one group, or ``gh``
    g, hd = ("g", "gh") if grouped else ("", "h")
    if grouped:
        ng = b.shape[2]
        x = x.reshape(bsz, t, ng, h // ng, pdim)
        dt = dt.reshape(bsz, t, ng, h // ng)
        a = a.reshape(ng, h // ng)
        state = state.reshape(bsz, ng, h // ng, pdim, n)

    def chunks(z):                      # [B, T, ...] -> [B, Nc, C, ...]
        if pad:
            z = jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
        return z.reshape((bsz, nc, cs) + z.shape[2:])

    x, b, c, dt = (chunks(z) for z in (x, b, c, dt))
    cum = jnp.cumsum(dt * a, axis=2)                        # [B,Nc,C,H]
    cum = jnp.moveaxis(cum, 2, -1)                          # [B,Nc,H,C]
    low = jnp.tril(jnp.ones((cs, cs), bool))
    # exp only where j <= i: above the diagonal the difference is
    # positive and may overflow
    decay = jnp.exp(jnp.where(low, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                    # [B,Nc,H,C,C]
    xdt = x * dt[..., None]                                 # [B,Nc,C,H,P]
    cb = jnp.einsum("bzi%ss,bzj%ss->bz%sij" % (g, g, g), c, b,
                    precision=_HI)                          # [B,Nc,C,C]
    cb = cb[:, :, :, None] if grouped else cb[:, :, None]
    y = jnp.einsum("bz%sij,bzj%sp->bzi%sp" % (hd, hd, hd), cb * decay, xdt,
                   precision=_HI)
    last = cum[..., -1:]                                    # [B,Nc,H,1]
    own = jnp.einsum("bzj%ss,bz%sj,bzj%sp->bz%sps" % (g, hd, hd, hd), b,
                     jnp.exp(last - cum), xdt,
                     precision=_HI)                         # [B,Nc,H,P,N]

    def body(s, xs):
        c_, cum_, last_, own_ = xs
        off = jnp.einsum("bi%ss,b%sps->bi%sp" % (g, hd, hd), c_, s,
                         precision=_HI) \
            * jnp.moveaxis(jnp.exp(cum_), -1, 1)[..., None]
        s = s * jnp.exp(last_)[..., None] + own_
        return s, off

    state, off = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(z, 1, 0)
                           for z in (c, cum, last, own)))
    y = y + jnp.moveaxis(off, 0, 1)                         # [B,Nc,C,H,P]
    if grouped:
        state = state.reshape(bsz, h, pdim, n)
    return state, y.reshape(bsz, nc * cs, h, pdim)[:, :t]


@register
class Mamba2(OpSpec):
    """A Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as Granite 4.0-H's
    ``mamba`` layers and Nemotron-H's ``M`` layers run it. data [B, T, E]
    (already normalized). With ``H`` heads of ``P`` (``head_dim``), a
    state of ``N`` (``state_size``) and ``G`` groups of B and C
    (``n_groups``; group ``g`` serves heads ``[g H/G, (g+1) H/G)``),
    ``D_in = H P`` and ``F = D_in + 2 G N``:

    - ``in_weight`` [D_in + F + H, E]: ``[z ; xBC ; dt] = W x``, no bias;
    - ``conv_weight`` [F, kernel], ``conv_bias`` [F]: depthwise and
      causal over ``xBC``, zeros before position 0,
      ``xBC_t = silu(sum_j w[:, j] u_{t-(kernel-1)+j} + bias)``, then
      split into ``x`` [H, P], ``B`` [G, N], ``C`` [G, N];
    - ``dt = softplus(dt + dt_bias)``, ``a = -exp(a_log)`` per head
      (``a_log``, ``dt_bias``, ``d_skip`` [H]), float32;
    - the recurrence at the top of this section, state float32, then
      ``y = S C + d_skip x`` per head;
    - ``out = out_weight [rmsnorm(y silu(z)) norm_weight]``: the gate
      comes first and the norm runs over each group's ``D_in / G``
      channels (the whole ``D_in`` with one group), ``norm_weight``
      [D_in], ``out_weight`` [E, D_in].

    The full-sequence forward starts from the zero state and runs the
    chunked form in chunks of ``chunk_size``; the decoder's cached form
    (``parallel/decode.py``) keeps NO rows: per sequence the state
    [H, P, N] and the last ``kernel - 1`` positions' ``xBC``."""

    name = "Mamba2"
    params = {"num_heads": Param("int"), "head_dim": Param("int"),
              "state_size": Param("int"), "conv_kernel": Param("int", 4),
              "chunk_size": Param("int", 256),
              "eps": Param("float", 1e-5),
              "n_groups": Param("int", 1)}

    def arguments(self, p):
        return ["data", "in_weight", "conv_weight", "conv_bias", "a_log",
                "d_skip", "dt_bias", "norm_weight", "out_weight"]

    @staticmethod
    def widths(p):
        """(D_in, F): the inner and the convolved widths (``G`` groups
        of B and C)."""
        d_in = p["num_heads"] * p["head_dim"]
        ng = p.get("n_groups", 1)
        if ng < 1 or p["num_heads"] % ng:
            raise MXNetError("Mamba2: n_groups=%d must divide the %d heads"
                             % (ng, p["num_heads"]))
        return d_in, d_in + 2 * ng * p["state_size"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        if len(d) != 3:
            raise MXNetError("Mamba2: data must be [B, T, E]")
        d_in, f = self.widths(p)
        e, h = d[2], p["num_heads"]
        want = [d, (d_in + f + h, e), (f, p["conv_kernel"]), (f,), (h,),
                (h,), (h,), (d_in,), (e, d_in)]
        return [shape_assign(s, w, "Mamba2 " + n)
                for s, w, n in zip(in_shapes, want,
                                   self.arguments(p))], [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x = ins[0]
        b = x.shape[0]
        _, f = self.widths(p)
        state = jnp.zeros((b, p["num_heads"], p["head_dim"],
                           p["state_size"]), jnp.float32)
        prev = jnp.zeros((b, p["conv_kernel"] - 1, f), x.dtype)
        y, _, _ = mamba_mix(p, x, ins[1:], state, prev)
        return [y], []


def ssd_steps_in_place(p, c, lens):
    """Whether ``mamba_mix`` advances the state of a chunk of ``c``
    positions through ``pallas_kernels.ssd_state_step`` (the live rows'
    states alone, where they lie), by what the code can see: ONE
    position of a slot walk (``lens`` given), and on the chip a state of
    whole lane tiles and heads of whole sublane tiles (the interpreter
    takes any). Everything else (the full forward, a prefill piece,
    offline ``generate``) runs ``ssd_step`` / ``ssd_chunked``, the
    definition."""
    from . import pallas_kernels as pk
    if c != 1 or lens is None:
        return False
    return pk._use_interpret() or (p["state_size"] % 128 == 0
                                   and p["head_dim"] % 8 == 0)


def mamba_mix(p, x, weights, state, prev, real=None, lens=None, fresh=None):
    """The mixer on a chunk ``x`` [B, C, E] that continues a sequence:
    ``state`` [B, H, P, N] float32 and ``prev`` [B, kernel-1, F], the
    ``xBC`` of the positions before the chunk (zeros at the start of a
    sequence). One function for the full forward and the decoder's
    cached walk, with ``gdn_mix``'s contracts: ``real`` ([B, 1] int32,
    optional): only the chunk's first ``real`` positions are tokens, the
    rest padding, which leaves the state and ``prev`` of the last real
    one (``dt = 0`` there); ``lens`` ([B] int32, optional: a slot
    walk's): a row with ``lens`` 0 is not live and keeps its state and
    ``prev`` untouched; ``fresh`` ([B] bool, optional): a live row that
    starts from the ZERO state whatever ``state`` holds for it. Where
    ``ssd_steps_in_place`` says so, nothing outside the kernel touches
    the whole ``state``. Returns (out [B, C, E], the new state, the new
    prev)."""
    win_, wconv, bconv, a_log, d_skip, dt_bias, wnorm, wout = weights
    d_in, f = Mamba2.widths(p)
    h, pdim, n = p["num_heads"], p["head_dim"], p["state_size"]
    ng = p.get("n_groups", 1)
    b, c, _ = x.shape
    f32 = jnp.float32
    with jax.named_scope("proj"):
        zxd = jnp.einsum("bte,fe->btf", x, win_)
        z, u, dt = zxd[..., :d_in], zxd[..., d_in:d_in + f], \
            zxd[..., d_in + f:]
    with jax.named_scope("conv"):
        win = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
        wf = wconv.astype(f32)
        taps = wconv.shape[1]
        cv = jax.nn.silu(sum(wf[:, j] * win[:, j:j + c].astype(f32)
                             for j in range(taps)) + bconv.astype(f32))
        # the window the NEXT chunk starts from: the last kernel-1
        # real positions (of ``prev`` too, where the chunk holds fewer)
        if real is None:
            nxt = win[:, c:]
        else:
            at = real + jnp.arange(taps - 1, dtype=jnp.int32)
            nxt = jnp.take_along_axis(win, at[..., None], axis=1)
        xs = cv[..., :d_in].reshape(b, c, h, pdim)
        bb, cc = cv[..., d_in:d_in + ng * n], cv[..., d_in + ng * n:]
        if ng > 1:                                  # [B, C, G, N]
            bb, cc = bb.reshape(b, c, ng, n), cc.reshape(b, c, ng, n)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        if real is not None:
            pad = (jnp.arange(c, dtype=jnp.int32) >= real)[..., None]
            dt = jnp.where(pad, 0.0, dt)
        a = -jnp.exp(a_log.astype(f32))
    live = None if lens is None else jnp.asarray(lens, jnp.int32) > 0
    with jax.named_scope("state"):
        if ssd_steps_in_place(p, c, lens):
            from .pallas_kernels import ssd_state_step
            new, y = ssd_state_step(
                state, xs[:, 0], bb[:, 0], cc[:, 0], dt[:, 0], a, lens,
                jnp.zeros((b,), bool) if fresh is None else fresh)
            y = y[:, None]
        else:
            start = state if fresh is None else \
                jnp.where(fresh[:, None, None, None], 0, state)
            if c == 1:
                new, y = ssd_step(start, xs[:, 0], bb[:, 0], cc[:, 0],
                                  dt[:, 0], a)
                y = y[:, None]
            else:
                new, y = ssd_chunked(start, xs, bb, cc, dt, a,
                                     p.get("chunk_size", 256))
            if live is not None:
                new = jnp.where(live[:, None, None, None], new, state)
        if live is not None:
            nxt = jnp.where(live[:, None, None], nxt,
                            prev.astype(nxt.dtype))
    with jax.named_scope("norm"):
        y = (y + d_skip.astype(f32)[:, None] * xs).reshape(b, c, d_in) \
            * jax.nn.silu(z.astype(f32))
        if ng > 1:                      # the norm runs over each group
            y = y.reshape(b, c, ng, d_in // ng)
        ms = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
        y = y * jax.lax.rsqrt(ms + p["eps"])
        if ng > 1:
            y = y.reshape(b, c, d_in)
        y = y * wnorm.astype(f32)
    with jax.named_scope("proj"):
        out = jnp.einsum("btv,ev->bte", y.astype(x.dtype), wout)
    return out, new, nxt.astype(prev.dtype)


# -- gated softmax attention -------------------------------------------------

@register
class GatedAttention(OpSpec):
    """Causal grouped-query softmax attention with a head size of its
    own, per-head q/k norms, partial rotary and a sigmoid gate on its
    output, no biases (Qwen3-Next's ``full_attention`` layers). data
    [B, T, E] (already normalized), ``H`` query heads, ``Hkv`` kv heads
    of ``D``:

    - ``q_weight`` [H 2D, E]: per head ``[q_h ; gate_h]``;
      ``k_weight``, ``v_weight`` [Hkv D, E];
    - ``q_norm``, ``k_norm`` [D]: RMSNorm over ``D`` on q and k per
      head, ``x / rms(x) * gamma`` (a checkpoint's zero-centred weight
      ``w`` is stored here as ``gamma = 1 + w``);
    - rotary on the first ``rotary_dim`` dims at ``rope_base``;
    - causal softmax(q . k / sqrt(D)) over v, grouped;
      ``y = out_weight [attn * sigmoid(gate)]``, ``out_weight``
      [E, H D].

    The full forward attends with the flash kernel or densely
    (``impl``); the decoder's cached form keeps K and V rows like
    MultiHeadAttention's and reads them the same ways."""

    name = "GatedAttention"
    params = {"num_heads": Param("int"), "num_kv_heads": Param("int"),
              "head_dim": Param("int"), "rotary_dim": Param("int", 0),
              "rope_base": Param("float", 10000.0),
              "eps": Param("float", 1e-6),
              "impl": Param("str", "flash")}

    def arguments(self, p):
        return ["data", "q_weight", "k_weight", "v_weight", "q_norm",
                "k_norm", "out_weight"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        if len(d) != 3:
            raise MXNetError("GatedAttention: data must be [B, T, E]")
        h, kv, hd = p["num_heads"], p["num_kv_heads"], p["head_dim"]
        if kv < 1 or h % kv:
            raise MXNetError(
                "GatedAttention: num_kv_heads=%d must divide num_heads="
                "%d" % (kv, h))
        e = d[2]
        want = [d, (2 * h * hd, e), (kv * hd, e), (kv * hd, e), (hd,),
                (hd,), (e, h * hd)]
        return [shape_assign(s, w, "GatedAttention " + n)
                for s, w, n in zip(in_shapes, want,
                                   self.arguments(p))], [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x = ins[0]
        b, t, _ = x.shape
        h, kv, d = p["num_heads"], p["num_kv_heads"], p["head_dim"]
        q, k, v, gate = gattn_qkv(p, x, ins[1:6],
                                  jnp.arange(t, dtype=jnp.int32)[None])
        with jax.named_scope("attend"):
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)
            if p["impl"] == "flash":
                from .pallas_kernels import flash_attention
                o = flash_attention(q, k, v, causal=True)
            elif p["impl"] == "dense":
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / float(np.sqrt(d))
                mask = jnp.tril(jnp.ones((t, t), bool))
                s = jnp.where(mask[None, None], s, -jnp.inf)
                o = jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(s, axis=-1), v)
            else:
                raise MXNetError("GatedAttention: unknown impl %r (flash "
                                 "or dense)" % (p["impl"],))
        return [gattn_out(o, gate, ins[6])], []


def gattn_qkv(p, x, weights, positions):
    """GatedAttention's queries, keys, values and gate of a chunk ``x``
    [B, C, E] at ``positions`` ([1 or B, C] int32, absolute): the
    projections, the per-head norms and the rotary turn. One function
    for the full forward and the decoder's cached walk. Returns q
    [B, C, H, D], k and v [B, C, Hkv, D], gate [B, C, H, D] in ``x``'s
    dtype."""
    wq, wk, wv, qn, kn = weights
    h, kv, d = p["num_heads"], p["num_kv_heads"], p["head_dim"]
    b, c, _ = x.shape
    f32 = jnp.float32
    with jax.named_scope("proj"):
        qg = jnp.einsum("bte,fe->btf", x, wq).reshape(b, c, h, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = jnp.einsum("bte,fe->btf", x, wk).reshape(b, c, kv, d)
        v = jnp.einsum("bte,fe->btf", x, wv).reshape(b, c, kv, d)

        def norm(z, gamma):
            zf = z.astype(f32)
            ms = jnp.mean(jnp.square(zf), axis=-1, keepdims=True)
            return zf * jax.lax.rsqrt(ms + p["eps"]) * gamma.astype(f32)

        rot = p.get("rotary_dim") or d
        positions = jnp.asarray(positions, jnp.int32)
        q = rope_rotate(norm(q, qn), positions, p["rope_base"], rot)
        k = rope_rotate(norm(k, kn), positions, p["rope_base"], rot)
    return q.astype(x.dtype), k.astype(x.dtype), v, gate


def gattn_out(o, gate, wo):
    """``out_weight [o * sigmoid(gate)]``: ``o`` and ``gate``
    [B, C, H, D]."""
    with jax.named_scope("proj"):
        b, c, h, d = gate.shape
        o = o.reshape(b, c, h, d).astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        return jnp.einsum("btq,eq->bte",
                          o.reshape(b, c, h * d).astype(gate.dtype), wo)


# -- multi-head latent attention ----------------------------------------------
# (DeepSeek-V2, arXiv:2405.04434 section 2.1.) A token's keys and values
# are re-made from ONE stored row ``[c ; k_r]``: ``c`` the normalized
# latent (``kv_lora_rank`` numbers), ``k_r`` the rotated positional key
# that every head shares (``rope_dim``). Per head ``[k_n ; v] = W_ukv c``.
# Two algebraically equal forms of the read:
#   expanded  score_h = q_n,h . k_n,h + q_r,h . k_r;  o_h = sum p v_h
#   absorbed  q~_h = W_uk,h^T q_n,h;  score_h = q~_h . c + q_r,h . k_r;
#             o_h = W_uv,h (sum p c)
# The absorbed form reads the stored row as it is, all heads against one
# row like a many-query, one-kv-head attention with keys of
# ``kv_lora_rank + rope_dim`` and values of ``kv_lora_rank``: the decode
# step's. The expanded form costs fewer operations per (query, key) pair:
# the prefill's and the full forward's.

def _rms_scaled(z, gamma, eps):
    zf = z.astype(jnp.float32)
    ms = jnp.mean(jnp.square(zf), axis=-1, keepdims=True)
    return zf * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)


@register
class LatentAttention(OpSpec):
    """Causal multi-head latent attention (MLA), no biases. data
    [B, T, E] (already normalized); ``H`` heads whose query/key width
    ``Dn + Dr`` (``nope_dim`` + ``rope_dim``) is not their value width
    ``Dv``; ``Rq = q_lora_rank``, ``R = kv_lora_rank``:

    - ``q_down_weight`` [Rq, E], ``q_norm`` [Rq]:
      ``c_q = RMSNorm(W_dq x)``; ``q_up_weight`` [H (Dn + Dr), Rq]: per
      head ``[q_n ; q_r] = W_uq c_q``;
    - ``kv_down_weight`` [R + Dr, E], ``kv_norm`` [R]:
      ``[c ; k_r] = W_dkv x``, ``c <- RMSNorm(c)``;
    - ``q_r`` and ``k_r`` rotated by position in the half-split order
      at ``rope_base`` (``yarn_factor`` > 0: YaRN's frequencies over
      ``yarn_original_max`` positions, cos and sin unscaled); ``k_r``
      is one vector for all heads;
    - ``kv_up_weight`` [H (Dn + Dv), R]: per head ``[k_n ; v] = W_ukv c``;
    - ``score = (q_n . k_n + q_r . k_r) (Dn + Dr)^-0.5 m^2``,
      ``m = 0.1 mscale_all_dim ln(yarn_factor) + 1`` (1 without YaRN);
      causal softmax; ``out_weight`` [E, H Dv].

    The full forward runs the EXPANDED form (``impl``: ``flash`` pads
    the values to the query's width for the flash kernel, which takes
    one width; ``dense``). The decoder's cached form
    (``parallel/decode.py``, the latent-rows kind) stores ``[c ; k_r]``
    after norm and rotation, ``R + Dr`` numbers a token with no head
    axis, reads it expanded in a prefill piece and ABSORBED in a decode
    step."""

    name = "LatentAttention"
    params = {"num_heads": Param("int"), "q_lora_rank": Param("int"),
              "kv_lora_rank": Param("int"), "nope_dim": Param("int"),
              "rope_dim": Param("int"), "v_dim": Param("int"),
              "rope_base": Param("float", 10000.0),
              "yarn_factor": Param("float", 0.0),
              "yarn_original_max": Param("int", 4096),
              "yarn_beta_fast": Param("float", 32.0),
              "yarn_beta_slow": Param("float", 1.0),
              "mscale_all_dim": Param("float", 0.0),
              "eps": Param("float", 1e-6),
              "impl": Param("str", "flash")}

    def arguments(self, p):
        return ["data", "q_down_weight", "q_norm", "q_up_weight",
                "kv_down_weight", "kv_norm", "kv_up_weight", "out_weight"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        if len(d) != 3:
            raise MXNetError("LatentAttention: data must be [B, T, E]")
        if p["rope_dim"] % 2:
            raise MXNetError("LatentAttention: rope_dim=%d must be even"
                             % p["rope_dim"])
        e, h = d[2], p["num_heads"]
        rq, r = p["q_lora_rank"], p["kv_lora_rank"]
        dn, dr, dv = p["nope_dim"], p["rope_dim"], p["v_dim"]
        want = [d, (rq, e), (rq,), (h * (dn + dr), rq), (r + dr, e), (r,),
                (h * (dn + dv), r), (e, h * dv)]
        return [shape_assign(s, w, "LatentAttention " + n)
                for s, w, n in zip(in_shapes, want,
                                   self.arguments(p))], [d], []

    def forward(self, p, ins, aux, is_train, rng):
        x = ins[0]
        b, t, _ = x.shape
        q, rows = mla_down(p, x, ins[1:6],
                           jnp.arange(t, dtype=jnp.int32)[None])
        with jax.named_scope("expand"):
            kn, v, kr = mla_expand(p, rows, ins[6])
        with jax.named_scope("attend"):
            h, dr = p["num_heads"], p["rope_dim"]
            k = jnp.concatenate(
                [kn, jnp.broadcast_to(kr[:, :, None], (b, t, h, dr))], -1)
            scale = mla_softmax_scale(p)
            if p["impl"] == "flash":
                from .pallas_kernels import flash_attention
                pad = q.shape[-1] - v.shape[-1]
                vp = jnp.pad(v, [(0, 0)] * 3 + [(0, pad)])
                o = flash_attention(q, k, vp, causal=True,
                                    scale=scale)[..., :v.shape[-1]]
            elif p["impl"] == "dense":
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                               preferred_element_type=jnp.float32) * scale
                mask = jnp.tril(jnp.ones((t, t), bool))
                s = jnp.where(mask[None, None], s, -jnp.inf)
                o = jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(s, axis=-1).astype(v.dtype),
                               v)
            else:
                raise MXNetError("LatentAttention: unknown impl %r (flash "
                                 "or dense)" % (p["impl"],))
        return [mla_out(o, ins[7])], []


def mla_softmax_scale(p):
    """``(Dn + Dr)^-0.5 m^2``: YaRN's ``m = 0.1 mscale_all_dim
    ln(factor) + 1`` enters the softmax scale squared (q and k each
    carry one) where ``mscale_all_dim`` is set."""
    m = 1.0
    if p.get("yarn_factor", 0.0) > 1.0 and p.get("mscale_all_dim", 0.0):
        m = 0.1 * float(p["mscale_all_dim"]) \
            * float(np.log(p["yarn_factor"])) + 1.0
    return float((p["nope_dim"] + p["rope_dim"]) ** -0.5) * m * m


def _mla_yarn(p):
    if p.get("yarn_factor", 0.0) > 1.0:
        return (p["yarn_factor"], p["yarn_original_max"],
                p["yarn_beta_fast"], p["yarn_beta_slow"])
    return None


def mla_down(p, x, weights, positions):
    """LatentAttention's queries and latent rows of a chunk ``x``
    [B, C, E] at ``positions`` ([1 or B, C] int32, absolute): both
    down-projections, their norms, the queries' up-projection and the
    rotary turn. One function for the full forward and the decoder's
    cached walk. Returns q [B, C, H, Dn + Dr] (``[q_n ; q_r]``, ``q_r``
    rotated) and the rows to store, ``[c ; k_r]`` [B, C, R + Dr], in
    ``x``'s dtype."""
    wdq, qn, wuq, wdkv, kvn = weights
    h, r = p["num_heads"], p["kv_lora_rank"]
    dn, dr = p["nope_dim"], p["rope_dim"]
    b, c, _ = x.shape
    dt = x.dtype
    positions = jnp.asarray(positions, jnp.int32)
    yarn = _mla_yarn(p)
    with jax.named_scope("down"):
        cq = _rms_scaled(jnp.einsum("bte,fe->btf", x, wdq), qn,
                         p["eps"]).astype(dt)
        q = jnp.einsum("btf,gf->btg", cq, wuq).reshape(b, c, h, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn],
             rope_rotate(q[..., dn:].astype(jnp.float32), positions,
                         p["rope_base"], yarn=yarn).astype(dt)], -1)
        ckv = jnp.einsum("bte,fe->btf", x, wdkv)
        lat = _rms_scaled(ckv[..., :r], kvn, p["eps"])
        kr = rope_rotate(ckv[..., None, r:].astype(jnp.float32), positions,
                         p["rope_base"], yarn=yarn)[:, :, 0]
        rows = jnp.concatenate([lat, kr], -1).astype(dt)
    return q, rows


def mla_expand(p, rows, wukv):
    """Stored rows ``[c ; k_r]`` [B, L, R + Dr or wider] (lanes past
    ``R + Dr`` are a store's padding) up-projected to per-head keys and
    values: (k_n [B, L, H, Dn], v [B, L, H, Dv], k_r [B, L, Dr]) in the
    weight's dtype."""
    h, r = p["num_heads"], p["kv_lora_rank"]
    dn, dv, dr = p["nope_dim"], p["v_dim"], p["rope_dim"]
    b, l, _ = rows.shape
    kv = jnp.einsum("blr,fr->blf", rows[..., :r].astype(wukv.dtype),
                    wukv).reshape(b, l, h, dn + dv)
    return kv[..., :dn], kv[..., dn:], \
        rows[..., r:r + dr].astype(wukv.dtype)


def mla_absorb_q(p, q, wukv):
    """``[q_n ; q_r]`` [B, C, H, Dn + Dr] with ``W_uk`` folded in:
    ``[W_uk,h^T q_n,h ; q_r,h]`` [B, C, H, R + Dr], which scores
    against a stored row as it is."""
    h, r = p["num_heads"], p["kv_lora_rank"]
    dn, dv = p["nope_dim"], p["v_dim"]
    wuk = wukv.reshape(h, dn + dv, r)[:, :dn]
    qa = jnp.einsum("bchd,hdr->bchr", q[..., :dn], wuk)
    return jnp.concatenate([qa.astype(q.dtype), q[..., dn:]], -1)


def mla_absorb_out(p, o, wukv):
    """The mix of latents ``sum p c`` [B, C, H, R] through each head's
    ``W_uv``: [B, C, H, Dv]."""
    h, r = p["num_heads"], p["kv_lora_rank"]
    dn, dv = p["nope_dim"], p["v_dim"]
    wuv = wukv.reshape(h, dn + dv, r)[:, dn:]
    return jnp.einsum("bchr,hdr->bchd", o.astype(wukv.dtype), wuv)


def mla_out(o, wo):
    """``out_weight`` on the heads' outputs [B, C, H, Dv]."""
    with jax.named_scope("out"):
        b, c, h, dv = o.shape
        return jnp.einsum("btq,eq->bte",
                          o.reshape(b, c, h * dv).astype(wo.dtype), wo)


# -- hyper-connections: a residual stream of several lanes --------------------
# (Zhu et al., arXiv:2409.19606; the manifold-constrained form,
# arXiv:2512.24880.) The stream holds ``n`` lanes of E numbers a token,
# flat [B, T, n E] (lane i owns [i E, (i+1) E)). Around a sublayer ``f``:
#     z = X / rms(X) over all n E numbers (no scale);
#     [a_pre (n) ; a_post (n) ; a_res (n n)] = Phi z;
#     H_pre = sigmoid(alpha_0 a_pre + b_pre);
#     H_post = 2 sigmoid(alpha_1 a_post + b_post);
#     M = exp(clip(alpha_2 mat(a_res) + B_res, -clamp, clamp)), then
#     ``iters`` rounds of M <- M / (rowsum + eps); M <- M / (colsum + eps)
#     (Sinkhorn: H_res = M is doubly stochastic to the rounds' convergence);
#     u = H_pre X (E numbers: the sublayer's input, before its own norm);
#     X <- H_res X + outer(H_post, f(...)).
# ``HyperConnectionPre`` gives ``u`` and the float32 coefficients
# ``[H_post ; H_res]`` of a token; ``HyperConnectionPost`` takes the
# sublayer's output and writes the stream. All of it in float32 whatever
# the stream's type; the stream is stored in its own.

@register
class StreamLanes(OpSpec):
    """The ends of a several-lane residual stream. ``mode="copy"``:
    [B, T, E] -> [B, T, n E], the token's vector in each of ``lanes``
    lanes; ``mode="sum"``: [B, T, n E] -> [B, T, E], the lanes added up
    (in float32). Position-wise."""

    name = "StreamLanes"
    params = {"lanes": Param("int"), "mode": Param("str")}

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if p["mode"] not in ("copy", "sum"):
            raise MXNetError("StreamLanes: mode must be 'copy' or 'sum', "
                             "got %r" % (p["mode"],))
        if d is None:
            return list(in_shapes), [None], []
        n = p["lanes"]
        if p["mode"] == "copy":
            return [d], [tuple(d[:-1]) + (d[-1] * n,)], []
        if d[-1] % n:
            raise MXNetError("StreamLanes: %d lanes do not divide the "
                             "stream's width %d" % (n, d[-1]))
        return [d], [tuple(d[:-1]) + (d[-1] // n,)], []

    def forward(self, p, ins, aux, is_train, rng):
        x, n = ins[0], p["lanes"]
        if p["mode"] == "copy":
            return [jnp.tile(x, (1,) * (x.ndim - 1) + (n,))], []
        xl = x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))
        return [jnp.sum(xl.astype(jnp.float32), axis=-2).astype(x.dtype)], []


def hc_coefficients(p, x, phi, alpha, bias):
    """The hyper-connection's coefficients of every token of the stream
    ``x`` [B, T, n E], float32: (H_pre [B, T, n], H_post [B, T, n],
    H_res [B, T, n, n]). The Sinkhorn rounds run with the tokens on the
    minor axis ([n, n, tokens]: sums over rows and columns are adds of
    whole vectors)."""
    n = p["lanes"]
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    b, t, _ = x.shape
    with jax.named_scope("coef"):
        xf = x.astype(f32)
        z = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                               + p["norm_eps"])
        a = jnp.einsum("bte,fe->fbt", z, phi.astype(f32), precision=hi)
        al, bi = alpha.astype(f32), bias.astype(f32)[:, None, None]
        pre = jax.nn.sigmoid(al[0] * a[:n] + bi[:n])
        post = 2.0 * jax.nn.sigmoid(al[1] * a[n:2 * n] + bi[n:2 * n])
    with jax.named_scope("sinkhorn"):
        bres = bi[2 * n:]
        if p.get("res_diag"):
            bres = bres + p["res_diag"] * jnp.eye(n, dtype=f32).reshape(
                n * n, 1, 1)
        m = jnp.exp(jnp.clip(al[2] * a[2 * n:] + bres,
                             -p["clamp"], p["clamp"])).reshape(n, n, b, t)
        for _ in range(p["iters"]):
            m = m / (jnp.sum(m, axis=1, keepdims=True) + p["eps"])
            m = m / (jnp.sum(m, axis=0, keepdims=True) + p["eps"])
    return (jnp.moveaxis(pre, 0, -1), jnp.moveaxis(post, 0, -1),
            jnp.moveaxis(m, (0, 1), (-2, -1)))


_HC_PARAMS = {"lanes": Param("int"), "iters": Param("int", 20),
              "eps": Param("float", 1e-6), "clamp": Param("float", 30.0),
              "res_diag": Param("float", 0.0),
              "norm_eps": Param("float", 1e-6)}


@register
class HyperConnectionPre(OpSpec):
    """The reading half of a hyper-connection (the section above): from
    the stream ``data`` [B, T, n E] the sublayer's input ``u = H_pre X``
    [B, T, E] (output 0, the stream's type) and the token's
    coefficients ``[H_post (n) ; H_res (n n, row-major)]``
    [B, T, n + n n] in float32 (output 1), which
    ``HyperConnectionPost`` takes. ``phi`` [2n + n n, n E] (rows
    ``a_pre``, ``a_post``, ``a_res``), ``alpha`` [3], ``bias``
    [2n + n n] (``b_pre``, ``b_post``, ``B_res`` row-major; ``B_res``
    is stored as its departure from ``res_diag`` times the identity,
    ``B_res = res_diag I + stored``: a storage choice, 0 by default).
    Position-wise."""

    name = "HyperConnectionPre"
    params = dict(_HC_PARAMS)

    def arguments(self, p):
        return ["data", "phi", "alpha", "bias"]

    def outputs(self, p):
        return ["output", "mix"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None, None], []
        n = p["lanes"]
        if len(d) != 3 or d[2] % n:
            raise MXNetError("HyperConnectionPre: data must be "
                             "[B, T, lanes * E], got %s" % (d,))
        k = 2 * n + n * n
        ins = [d, shape_assign(in_shapes[1], (k, d[2]),
                               "HyperConnectionPre phi"),
               shape_assign(in_shapes[2], (3,), "HyperConnectionPre alpha"),
               shape_assign(in_shapes[3], (k,), "HyperConnectionPre bias")]
        return ins, [(d[0], d[1], d[2] // n), (d[0], d[1], n + n * n)], []

    def infer_type(self, p, in_types):
        dt = next((t for t in in_types if t is not None), None)
        return [dt] * len(in_types), [dt, np.dtype(np.float32)], []

    def forward(self, p, ins, aux, is_train, rng):
        x, phi, alpha, bias = ins
        n = p["lanes"]
        b, t, w = x.shape
        pre, post, res = hc_coefficients(p, x, phi, alpha, bias)
        with jax.named_scope("mix"):
            xl = x.reshape(b, t, n, w // n).astype(jnp.float32)
            u = sum(pre[..., i, None] * xl[:, :, i] for i in range(n))
        mix = jnp.concatenate([post, res.reshape(b, t, n * n)], axis=-1)
        return [u.astype(x.dtype), mix], []


@register
class HyperConnectionPost(OpSpec):
    """The writing half of a hyper-connection: ``X <- H_res X +
    outer(H_post, y)`` from the stream ``data`` [B, T, n E], the
    sublayer's output ``branch`` [B, T, E] and ``mix`` [B, T, n + n n]
    (``HyperConnectionPre``'s second output), in float32; the stream
    keeps its type. Position-wise."""

    name = "HyperConnectionPost"
    params = {"lanes": Param("int")}

    def arguments(self, p):
        return ["data", "branch", "mix"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return list(in_shapes), [None], []
        n = p["lanes"]
        return [d, shape_assign(in_shapes[1], (d[0], d[1], d[2] // n),
                                "HyperConnectionPost branch"),
                shape_assign(in_shapes[2], (d[0], d[1], n + n * n),
                             "HyperConnectionPost mix")], [d], []

    def infer_type(self, p, in_types):
        dt = in_types[0] if in_types[0] is not None else in_types[1]
        return [dt, dt, np.dtype(np.float32)], [dt], []

    def forward(self, p, ins, aux, is_train, rng):
        x, y, mix = ins
        n = p["lanes"]
        b, t, w = x.shape
        f32 = jnp.float32
        with jax.named_scope("mix"):
            xl = x.reshape(b, t, n, w // n).astype(f32)
            mix = mix.astype(f32)
            yf = y.astype(f32)
            lanes = [sum(mix[..., n + i * n + j, None] * xl[:, :, j]
                         for j in range(n)) + mix[..., i, None] * yf
                     for i in range(n)]
            out = jnp.concatenate(lanes, axis=-1)
        return [out.astype(x.dtype)], []
