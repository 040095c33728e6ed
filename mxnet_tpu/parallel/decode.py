"""KV-cache autoregressive decoding for Symbol-built transformer LMs.

The training graph computes all T positions at once; generation needs one
position at a time against everything decoded so far. Rather than asking
users to write a second, incremental model (and keep it in sync with the
training symbol), ``Decoder`` DERIVES the incremental program from the
same Symbol graph the trainer compiled: the topological walk of
``parallel.graph.make_graph_fn`` re-runs with every ``MultiHeadAttention``
node swapped for a cached variant (new tokens' K/V written into
[B, max_len, Hkv*D] buffers with ``lax.dynamic_update_slice``;
queries attend to the cache under the mask ``key_pos <= query_pos``),
every ``CCAttention`` node likewise (its K/V rows the same, and beside
them a short ring of the last positions' raw projections: the second
kind of cache leaf, ``STATE_ROWS`` below), every ``GatedAttention``
node likewise (K/V rows and nothing else), every ``GatedDeltaNet``
node for a variant that keeps NO rows at all (a recurrent state per
sequence: the third kind), every ``LatentAttention`` node for one that
keeps ONE buffer of latent rows, key and value at once and without a
head axis (the fourth kind; "THE KINDS OF CACHE ENTRY" below has all
four side by side) and
``PositionalEmbedding`` sliced at the current position. Every other LM op
(Embedding, LayerNorm, RMSNorm, FullyConnected, activations, elementwise
arithmetic, ResidualMerge, MoEFFN, the hyper-connection's two halves and
the stream's ends, BatchNorm-on-rank-2-data) is
position-wise and runs
its ordinary ``OpSpec.forward`` unchanged, so there is no duplicated
model math to drift. BatchNorm normalizes axis 1 — the TIME axis of
rank-3 [B, T, E] sequence data — so it is position-wise only on rank-2
inputs; rank>=3 BatchNorm is rejected at trace time.

TPU-native shape discipline: cache buffers are statically ``max_len``
long (no growing shapes — one compiled program serves every step),
prefill processes the whole prompt as one chunk, and ``generate`` runs
the entire decode loop as a single ``lax.scan`` program with donated
caches — one dispatch for N tokens, which matters through a
high-latency link (doc/performance.md).

No reference counterpart: the reference's generation story is the
explicitly unrolled LSTM sampler (/root/reference/example/rnn/lstm.py,
char-rnn inference); attention-era decoding is a TPU-build extension.
"""
from __future__ import annotations

import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..ops.fusion import node_scope

__all__ = ["Decoder"]

# ops whose forward acts independently per position on [B, C, ...] data
# (safe to run unchanged on a chunk of C new tokens); BatchNorm only
# qualifies on rank-2 data — _run rejects it on rank>=3 (time axis)
_POSITIONWISE = {
    "Embedding", "LayerNorm", "FullyConnected", "Activation", "LeakyReLU",
    "MoEFFN", "Dropout", "BlockGrad", "Cast", "ElementWiseSum",
    "BatchNorm", "RMSNorm", "ResidualMerge", "SoftmaxActivation",
    "StreamLanes", "HyperConnectionPre", "HyperConnectionPost",
    "_Plus", "_Minus", "_Mul", "_Div", "_PlusScalar", "_MinusScalar",
    "_MulScalar", "_DivScalar", "_RMinusScalar", "_RDivScalar",
}
# handled specially
_TEMPORAL = {"MultiHeadAttention", "PositionalEmbedding", "CCAttention",
             "GatedAttention", "GatedDeltaNet", "LatentAttention"}

# THE KINDS OF CACHE ENTRY. One entry per cached node, a tuple of leaves
# whose axis 0 is the batch row / serving slot:
#
# kind         | node                  | leaves (rank)                  | a re-run step              | a reused slot            | refused by
# -------------+-----------------------+--------------------------------+----------------------------+--------------------------+-----------------------------
# K/V ROWS     | MultiHeadAttention,   | K, V [B, rows, Hkv*D] (3);     | rewrites its row with the  | nothing: rows at or past | a windowed ring: the prefix
#              | GatedAttention        | int8: + scales [B, rows, Hkv]  | same values (idempotent)   | the position are masked  | pool, speculation, the
#              |                       | (3); a windowed ring: + the    |                            | until overwritten (a     | handoff, the bounded read
#              |                       | rows' positions [B, win] (2)   |                            | ring: positions reset)   |
# ROLLING      | CCAttention           | K, V rows as above (3) + the   | idempotent: three rows of  | nothing: rows of         | int8 rows, quantized
# STATE        |                       | last STATE_ROWS positions'     | state, a position reads    | positions before 0 are   | weights, tp, the prefix
#              |                       | [u ; v2], flat [B, 3 W'] (2)   | two and writes its own     | masked by position       | pool, the handoff,
#              |                       |                                |                            |                          | speculation
# RECURRENT    | GatedDeltaNet         | S [B, Hv, Dk, Dv] float32 (4)  | ADVANCES the state: a row  | a chunk at position 0    | the same as the rolling
# STATE        |                       | + the last kernel-1 positions' | that holds no request      | starts from zeros,       | state (refuse_rolling_state)
#              |                       | convolution inputs, flat (2);  | (``lens`` 0) leaves it     | whatever the slot held   |
#              |                       | NO rows                        | untouched                  |                          |
# LATENT ROWS  | LatentAttention       | ONE buffer [B, rows, R + Dr]   | rewrites its row with the  | nothing: as K/V rows     | int8 rows, quantized
#              |                       | (3): ``[c ; k_r]`` after norm  | same values (idempotent)   |                          | weights, tp (no head axis
#              |                       | and rotation, key and value at |                            |                          | to shard), the prefix pool,
#              |                       | once, no head axis             |                            |                          | the handoff
#              |                       |                                |                            |                          | (refuse_latent_rows)
#
# CCAttention's rolling state: per sequence the last STATE_ROWS
# positions' [u ; v2] (the raw q/k projections and the half of the
# values the NEXT token takes), the row of position p at
# p % STATE_ROWS, stored flat [B, STATE_ROWS * W] (rank 2: a leaf
# without a head axis, replicated under tp like the rings' position
# buffers). A position reads the two before it and writes its own, so
# THREE rows make a step idempotent: the engine re-runs a frozen slot's
# last step every round, and a ring of two would have overwritten
# u_{t-2} the first time. Rows of positions before 0 are never read
# (``ops.attention.cca_qkv`` masks by position), so a reused slot needs
# no clearing.
STATE_ROWS = 3

# THE STATE KIND: a GatedDeltaNet node's cache entry holds no rows. Per
# sequence it is (S [Hv, Dk, Dv] float32, the recurrence's matrix state
# whatever the compute dtype; the last ``kernel - 1`` positions'
# convolution inputs, flat [(kernel-1) * F] in the cache dtype, oldest
# first). Nothing re-derives it from a few positions, so the three
# contracts that rows (and CCAttention's ring) lean on do not hold and
# it has its own:
# - a decode step ADVANCES the state, so re-running one is not
#   idempotent: a batch row that holds no request (``lens`` 0 in the
#   slot walk: a finished slot, one parked between prefill pieces)
#   leaves its state exactly as it was. A decode step of the slot walk
#   (one position) does not even read it: the states advance through
#   ``pallas_kernels.gdn_state_step``, which visits live slots first,
#   fetches a live slot's state once, writes it back where it lay (the
#   leaf is the kernel's aliased operand) and parks every step of a
#   dead slot on the block visited last, body gated off; nothing
#   outside the kernel takes the whole leaf. With NO slot live every
#   step parks on the first block, which is copied through unchanged
#   (a parked block is written back once, at the end);
# - a chunk that starts at position 0 starts from the ZERO state,
#   whatever the slot held: a reused slot is cleared by its position,
#   not by the caller (in the decode step's kernel a flag per slot,
#   not a pass over the leaf);
# - a right-padded chunk leaves the state of its last REAL token
#   (``valid_len``): padding runs the recurrence with ``beta = 0,
#   g = 0`` and does not enter the convolution's window.
# What cannot carry such a leaf refuses by name
# (``Decoder.refuse_rolling_state``): int8 rows, quantized weights, tp,
# the prefix pool, the KV handoff, speculation.
#
# THE LATENT-ROWS KIND: a LatentAttention node's entry is ONE rank-3
# leaf [B, rows, R + Dr], a token's ``[c ; k_r]`` after norm and rotation
# (``ops.attention.mla_down``): every head's keys and values are re-made
# from it, so it has no head axis and is key and value at once. (The
# row is stored padded with zeros to whole lane tiles, 576 -> 640:
# ``Decoder.latent_row_lanes`` says why.) It keeps
# the contracts of K/V rows: a position writes its own row and nothing
# else, so a re-run step is idempotent, a right-padded piece's padding
# rows sit past the true length until overwritten, and a reused slot
# needs no clearing. A prefill piece writes its rows as one block and
# reads all the rows so far EXPANDED (per-head keys and values up-
# projected a block of rows at a time, as many blocks as the position
# needs); a decode step writes its row in place and reads ABSORBED
# through the bounded read over live slots
# (``pallas_kernels.latent_paged_attention``: one fetch of a block serves
# scores and values). What moves or re-types K/V rows by their (K, V,
# head) layout refuses by name (``Decoder.refuse_latent_rows``).

_LOSS_HEADS = {"SoftmaxOutput", "SoftmaxCELoss"}

# -- the stored layout of the KV cache, stated once ------------------------
# A cache buffer is [B, rows, Hkv*D]: axis 0 the batch row / serving slot,
# axis 1 the cache row (a position, or a ring slot of a windowed node),
# axis 2 every kv head's D values side by side, kv-major (head h owns
# lanes [h*D, (h+1)*D), so a tensor-parallel shard of axis 2 holds whole
# heads). It is the layout the decode read consumes: the minor dimension
# fills the chip's 128 lanes where D=64 alone would be padded to twice
# its bytes, and the compiled decode program holds no relayout of it.
# int8 caches keep [B, rows, Hkv] f32 row scales beside it. Everything
# that needs the head axis goes through these two functions.

# a chunk of at most this many tokens (decode, the speculative verify
# and draft chunks) is written row by row and read straight off the
# stored rows (Decoder._lane_attn); a longer one (prefill) is written
# as a block and read per head off an unfolded copy
_SHORT_CHUNK = 8


def fold_heads(x):
    """A [B, C, Hkv, D] chunk as stored cache rows [B, C, Hkv*D]."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def unfold_heads(rows, kv):
    """Stored rows [B, C, Hkv*D] as [B, C, Hkv, D]. On the chip this is
    a relayout copy of what it is given, not a view (D=64 fills half a
    lane tile): hand it a SMALL slice — a ring, a block, one slot's rows
    beside a prefill — never the whole cache inside a decode step."""
    return rows.reshape(rows.shape[:-1] + (kv, rows.shape[-1] // kv))


def head_segments(kv, d, dtype):
    """The constant 0/1 [Hkv*D, Hkv] matrix that maps a stored lane to
    its kv head: contracting with it sums each head's D lanes, its
    transpose spreads a per-head number over them."""
    lane = jnp.arange(kv * d, dtype=jnp.int32) // d
    return (lane[:, None] == jnp.arange(kv, dtype=jnp.int32)).astype(dtype)


def _logits_symbol(symbol):
    """Re-head a loss-ended LM at its [B, T, V] logits: strip the loss
    node, then the layout ops the loss variants insert between the head
    GEMM and the loss (SwapAxis for the reference's multi_output [B,V,T]
    layout; Reshape for the flat/ce [B*T,V] layouts)."""
    heads = symbol._heads
    if len(heads) == 1 and not heads[0][0].is_var \
            and heads[0][0].spec.name in _LOSS_HEADS:
        node = heads[0][0].inputs[0][0]
        while not node.is_var \
                and node.spec.name in ("SwapAxis", "Reshape", "Flatten"):
            node = node.inputs[0][0]
        return symbol.get_internals()[node.name + "_output"]
    return symbol


class Decoder:
    """Autoregressive KV-cache decoder over a Symbol LM.

    Parameters
    ----------
    symbol : Symbol
        The LM graph — either logits-headed or ending in
        SoftmaxOutput/SoftmaxCELoss (the loss head is stripped
        automatically, like ``Predictor`` does for deployment).
    params : dict[str, array]
        Parameter values by name (e.g. ``trainer.params`` or the
        ``arg_params`` of a loaded checkpoint).
    max_len : int
        Static cache length: prompt length + generated tokens must stay
        within it (and within the trained ``pos_embed`` table).
    aux_params : dict[str, array], optional
        Auxiliary states (BatchNorm moving stats) for graphs that carry
        them; evaluated frozen, as in inference.
    compute_dtype : str, optional
        Cast floating parameters (and caches) for the decode math, e.g.
        ``"bfloat16"``; token ids are integer-semantic and never cast.
    cache_dtype : str, optional
        ``"int8"`` stores K/V quantized — symmetric per-(position, head)
        row scales (``amax/127``, f32, D-fold smaller than the rows they
        scale) kept in side buffers; the decode read applies them to
        the scores and the weights, prefill dequantizes the rows.
        Halves cache RESIDENCY vs bf16 (2x the max_len x batch budget
        in the same HBM) at ~0.4% row RMS error (per-row scales, so one
        outlier position cannot poison its neighbours). NOT a speed
        default: measured SLOWER on this chip (doc/performance.md
        round 5 — 3.65 vs 1.79 ms/token at b8/L1024, 3.57 vs 2.78 at
        L4096: the per-step quantize + per-read dequantize arithmetic
        costs more than the halved cache bytes save), so use it for
        memory, not latency. NOT exact — greedy argmax is robust in
        practice but bit-parity tests use the default. Any float dtype
        string (e.g. ``"bfloat16"``) is also accepted and simply stores
        the cache at that dtype; default follows ``compute_dtype``.
    weight_dtype : {"float", "int8"}, optional
        Weight storage (default: the ``MXNET_SERVING_WEIGHT_DTYPE``
        env var, else ``"float"``). ``"int8"`` quantizes every matmul
        weight — attention QKV/out projections, FullyConnected (MLP
        and the unembedding head), Embedding tables, MoE gate/expert
        stacks — to int8 with per-output-channel f32 scales
        (``serving/quant.py``; LayerNorm gains, biases and positional
        tables stay float), and every derived program dequantizes ON
        THE FLY inside the traced matmul (scale-fused, chunked — no
        float copy of a weight is ever materialized), so decode reads
        the weight stream at 1 byte/elem. NOT exact: greedy outputs
        are argmax-stable on the tested configs, tolerance-bounded in
        general (the int8-KV contract). The serving engine can
        instead quantize its OWN parameter copy
        (``InferenceEngine(weight_dtype="int8")``) so one float
        decoder serves both a quantized engine and its fp oracle.
        doc/serving.md "Quantized weights".
    """

    def __init__(self, symbol, params, max_len, aux_params=None,
                 compute_dtype=None, cache_block=None,
                 cache_dtype=None, weight_dtype=None,
                 weight_group=None, matmul_impl=None):
        symbol = _logits_symbol(symbol)
        self._topo = symbol._topo()
        self._heads = symbol._heads
        if len(self._heads) != 1:
            raise MXNetError("Decoder needs a single-output symbol, got %d"
                             % len(self._heads))
        self.max_len = int(max_len)
        # the keyword stays only because benchmark/drivers/serve.py:32
        # passes cache_block=None and a PR may not edit benchmark/
        # (PERF.md section 7, "for the next benchmark issue")
        if cache_block is not None:
            raise MXNetError(
                "Decoder: cache_block=%r: the blocked cache read was "
                "removed; the read follows the cache kind (see "
                "_cached_mha). Pass nothing." % (cache_block,))

        self._mha = []      # MultiHeadAttention nodes
        self._cca = []      # CCAttention nodes (K/V rows + rolling state)
        self._gdn = []      # GatedDeltaNet nodes (a state, no rows)
        self._mla = []      # LatentAttention nodes (latent rows)
        self._cached = []   # all, in graph order: one cache entry each
        for n in self._topo:
            if n.is_var:
                continue
            name = n.spec.name
            if name == "MultiHeadAttention":
                if not n.params["causal"]:
                    raise MXNetError(
                        "Decoder: attention node %r is non-causal — "
                        "autoregressive decoding is defined only for "
                        "causal attention" % n.name)
                self._mha.append(n)
                self._cached.append(n)
            elif name == "CCAttention":
                self._cca.append(n)
                self._cached.append(n)
            elif name == "GatedAttention":      # K/V rows, nothing else
                self._cached.append(n)
            elif name == "GatedDeltaNet":
                self._gdn.append(n)
                self._cached.append(n)
            elif name == "LatentAttention":
                self._mla.append(n)
                self._cached.append(n)
            elif name == "SoftmaxActivation" \
                    and n.params["mode"] != "instance":
                raise MXNetError(
                    "Decoder: SoftmaxActivation node %r normalizes "
                    "axis 1, the time axis of sequence data; only "
                    "mode='instance' (the trailing axis) is "
                    "position-wise" % n.name)
            elif name in _TEMPORAL or name in _POSITIONWISE:
                pass
            else:
                raise MXNetError(
                    "Decoder: op %s (node %r) is not known to be "
                    "position-wise; the decode transform supports the "
                    "standard LM ops (%s)"
                    % (name, n.name, ", ".join(sorted(_POSITIONWISE))))

        arg_names = [n.name for n in self._topo if n.is_var]
        self._data_name = "data" if "data" in arg_names else arg_names[0]
        missing = [a for a in arg_names
                   if a != self._data_name and a not in params]
        if missing:
            raise MXNetError("Decoder: missing parameter values for %s"
                             % missing)
        cast = (lambda v: v) if compute_dtype is None else (
            lambda v: v.astype(compute_dtype)
            if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating) else v)
        self._params = {a: cast(jnp.asarray(params[a]))
                        for a in arg_names if a != self._data_name}
        aux_names = symbol.list_auxiliary_states()
        missing_aux = [a for a in aux_names if a not in (aux_params or {})]
        if missing_aux:
            raise MXNetError("Decoder: missing aux_params values for %s "
                             "(pass the checkpoint's aux_params, e.g. "
                             "BatchNorm moving stats)" % missing_aux)
        self._aux = [cast(jnp.asarray(aux_params[a])) for a in aux_names]
        if cache_dtype is None:
            self._cache_int8 = False
            self._cache_dtype = compute_dtype or "float32"
        else:
            try:
                cdt = jnp.dtype(cache_dtype)
            except TypeError:
                raise MXNetError(
                    "Decoder: cache_dtype must be 'int8' or a float "
                    "dtype, got %r" % (cache_dtype,))
            self._cache_int8 = cdt == jnp.int8
            if not self._cache_int8 \
                    and not jnp.issubdtype(cdt, jnp.floating):
                raise MXNetError(
                    "Decoder: cache_dtype must be 'int8' or a float "
                    "dtype, got %r" % (cache_dtype,))
            self._cache_dtype = cdt

        if self.has_state and self._cache_int8:
            self.refuse_rolling_state("cache_dtype='int8'")
        if self._mla and self._cache_int8:
            self.refuse_latent_rows("cache_dtype='int8'")

        # pos_embed bounds the decodable length
        for n in self._topo:
            if not n.is_var and n.spec.name == "PositionalEmbedding":
                pos_param = n.inputs[1][0].name
                rows = self._params[pos_param].shape[0]
                if rows < self.max_len:
                    raise MXNetError(
                        "Decoder: max_len=%d exceeds the %d trained "
                        "positions of %r" % (self.max_len, rows,
                                             pos_param))

        # weight-only quantization (doc/serving.md "Quantized
        # weights"): replace the matmul weights with QuantizedTensor
        # pytree leaves — the derived walk dequantizes them on the fly
        # at every consumer (_cached_mha, the _run interceptors)
        if weight_dtype is None:
            weight_dtype = os.environ.get(
                "MXNET_SERVING_WEIGHT_DTYPE") or "float"
        if weight_dtype not in ("float", "int8", "int4"):
            raise MXNetError(
                "Decoder: weight_dtype must be 'float', 'int8' or "
                "'int4', got %r (MXNET_SERVING_WEIGHT_DTYPE sets the "
                "default)" % (weight_dtype,))
        if weight_dtype != "float":
            if self.has_state:
                self.refuse_rolling_state("weight_dtype=%r"
                                          % (weight_dtype,))
            if self._mla:
                self.refuse_latent_rows("weight_dtype=%r"
                                        % (weight_dtype,))
            self.refuse_given_router("weight_dtype=%r" % (weight_dtype,))
        self.weight_dtype = weight_dtype
        self.weight_group = weight_group
        if matmul_impl is None:
            matmul_impl = os.environ.get(
                "MXNET_SERVING_MATMUL_IMPL") or "dense"
        if matmul_impl not in ("dense", "pallas"):
            raise MXNetError(
                "Decoder: matmul_impl must be 'dense' or 'pallas', got "
                "%r (MXNET_SERVING_MATMUL_IMPL sets the default)"
                % (matmul_impl,))
        self._matmul_impl = matmul_impl
        if weight_dtype in ("int8", "int4"):
            from ..serving.quant import (quantize_params,
                                         quantized_weight_names,
                                         resolve_group)
            bits = 8 if weight_dtype == "int8" else 4
            row_quant = self._embedding_weight_names()
            if bits == 4:
                # resolve (and validate) the group width against the
                # model's embedding dim ONCE, loudly, at build time
                e_axis = None
                for nn in self._topo:
                    if not nn.is_var and nn.spec.name \
                            == "MultiHeadAttention":
                        wname = nn.inputs[1][0].name
                        e_axis = self._params[wname].shape[-1]
                        break
                if e_axis is not None:
                    self.weight_group = resolve_group(e_axis,
                                                      weight_group)
            self._params = quantize_params(
                self._params, quantized_weight_names(self._topo),
                bits=bits, group=weight_group, row_quant=row_quant)

        # params/aux pass as explicit jit arguments: closed-over
        # arrays would be baked into the HLO as literal constants
        # (program bloat + slow compiles at 100M+ params)
        self._step_jit = jax.jit(self._run, donate_argnums=(2,))
        self._gen_jit = {}
        self._auto_key = 0  # advances per sampled generate(rng=None)

    @classmethod
    def from_checkpoint(cls, prefix, epoch, max_len, **kwargs):
        """Build a decoder straight from a saved checkpoint
        (``prefix-symbol.json`` + ``prefix-NNNN.params``, the reference
        format — so a FeedForward/ParallelTrainer-trained LM decodes
        without re-describing the model)."""
        from ..model import load_checkpoint

        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)

        def to_np(v):
            return v.asnumpy() if hasattr(v, "asnumpy") else v

        return cls(symbol, {k: to_np(v) for k, v in arg_params.items()},
                   max_len,
                   aux_params={k: to_np(v)
                               for k, v in aux_params.items()},
                   **kwargs)

    @property
    def has_state(self):
        """Whether a cached node keeps a per-sequence state leaf (a
        CCAttention's ring, a GatedDeltaNet's recurrent state) beside
        or instead of rows."""
        return bool(self._cca or self._gdn)

    def refuse_rolling_state(self, feature):
        """Raise for a feature that cannot carry a per-sequence state
        leaf, naming the first node that keeps one by its own kind and
        saying what its state is: never a silent wrong answer (the
        decoder's own options, and the serving engine's)."""
        node = next(n for n in self._cached
                    if n.spec.name in ("CCAttention", "GatedDeltaNet"))
        if node.spec.name == "CCAttention":
            what = ("its rolling state, the last %d positions' [u ; v2] "
                    "per sequence beside its K/V rows," % STATE_ROWS)
        else:
            p = node.params
            what = ("its recurrent state, a float32 matrix [%d, %d, %d] "
                    "and the last %d positions' convolution inputs per "
                    "sequence, which no rows re-derive,"
                    % (p["num_v_heads"], p["head_k_dim"],
                       p["head_v_dim"], p["conv_kernel"] - 1))
        raise MXNetError(
            "%s does not compose with %s (node %r): %s is a cache leaf "
            "that %s cannot carry yet (ROADMAP R-M5 / D3)"
            % (feature, node.spec.name, node.name, what, feature))

    @property
    def has_latent(self):
        """Whether a cached node keeps latent rows (a LatentAttention
        node: one buffer, key and value at once, no head axis)."""
        return bool(self._mla)

    @staticmethod
    def latent_row_lanes(p):
        """Lanes of one stored latent row: ``R + Dr`` numbers padded
        with zeros to whole tiles of 128 (576 -> 640). The chip keeps a
        576-wide row in five tiles whatever the buffer is called; a
        buffer declared [B, rows, 576] it prefers to lay out rows-minor,
        and every program then copies the whole cache on its way in and
        out (tests/test_chip_compile.py, the longctx decode program)."""
        w = p["kv_lora_rank"] + p["rope_dim"]
        return -(-w // 128) * 128

    def refuse_latent_rows(self, feature):
        """Raise for a feature that moves or re-types K/V rows by their
        (K, V, head) layout and so cannot carry a latent row, naming the
        first node that keeps one: never a silent dense or wrong read."""
        node = self._mla[0]
        p = node.params
        raise MXNetError(
            "%s does not compose with LatentAttention (node %r): its "
            "cache entry is one buffer of latent rows [c ; k_r], %d "
            "numbers a token with no head axis, key and value at once, "
            "which %s cannot carry yet (ROADMAP R-M4)"
            % (feature, node.name, p["kv_lora_rank"] + p["rope_dim"],
               feature))

    def refuse_given_router(self, feature):
        """Raise if a MoEFFN node takes its routing from the graph
        (``router='given'``) or has gated experts: the quantized and
        expert-parallel forms know the linear-gate ReLU pair only."""
        for n in self._topo:
            if not n.is_var and n.spec.name == "MoEFFN" and (
                    n.params.get("router", "linear") != "linear"
                    or n.params.get("gated")):
                raise MXNetError(
                    "%s does not compose with MoEFFN(router=%r, "
                    "gated=%r) (node %r): only the linear-gate, "
                    "biased ReLU experts have a quantized / "
                    "expert-parallel form (ROADMAP R-M2)"
                    % (feature, n.params.get("router"),
                       bool(n.params.get("gated")), n.name))

    @property
    def _slots_batched(self):
        """Whether the slot-addressed walk (``_run_slots``) is ONE
        batched walk with the position vector: it is, unless a cached
        node is a windowed ring, whose read is written for one
        position (see the table above ``_cached_mha``)."""
        return not any(self._node_window(n) for n in self._mha)

    def _node_window(self, node):
        """Ring-buffer slot count for a windowed attention node (0 for
        ordinary full-history nodes)."""
        w = node.params.get("window", 0)
        return min(int(w), self.max_len) if w else 0

    # -- cache ----------------------------------------------------------
    def init_cache(self, batch_size, kv_sharding=None):
        """Zeroed K/V buffers, [B, max_len, Hkv*D] per attention node:
        the stored layout stated at the top of this module (axis 0 the
        batch row or serving slot, axis 1 the cache row, axis 2 every
        kv head's D values side by side, kv-major), plus
        [B, max_len, Hkv] f32 row scales when ``cache_dtype="int8"``.
        ``Hkv < num_heads`` under grouped-query attention — the cache
        shrinks by the group factor. Sliding-window nodes get a RING
        of only ``window`` rows, [B, window, Hkv*D], plus a [B, window]
        int32 buffer of each ring row's absolute position (-1 = never
        written) — decode memory O(window) regardless of generation
        length. A CCAttention node gets K and V rows of the same
        layout and its rolling state, [B, STATE_ROWS * W] (rank 2: no
        head axis; see ``STATE_ROWS``). A GatedAttention node gets K
        and V rows. A GatedDeltaNet node gets NO rows: (S
        [B, Hv, Dk, Dv] float32, [B, (kernel-1) * F] convolution
        inputs): "THE STATE KIND" at the top of this module.

        ``kv_sharding`` (optional ``jax.sharding.NamedSharding`` whose
        spec names dimension 2, e.g.
        ``NamedSharding(mesh, P(None, None, "model"))``): every K/V
        and row-scale buffer is laid out sharded over the mesh's model
        axis on dimension 2 — kv-major lanes, so each shard holds
        ``Hkv/tp`` whole heads of every row — and ring-position
        buffers (rank 2, headless) replicate. This is the
        tensor-parallel serving cache layout (doc/serving.md
        "Tensor-parallel serving"); the matching compute runs through
        ``_run_slots``'s ``tp=`` axis."""
        from ..ops.attention import CCAttention as _CCA
        from ..ops.attention import GatedDeltaNet as _GDN
        from ..ops.attention import MultiHeadAttention as _MHA

        if kv_sharding is not None and self._gdn:
            self.refuse_rolling_state("init_cache(kv_sharding=...)")
        if kv_sharding is not None and self._mla:
            self.refuse_latent_rows("init_cache(kv_sharding=...)")
        caches = []
        for n in self._cached:
            if n.spec.name == "LatentAttention":
                caches.append((jnp.zeros(
                    (batch_size, self.max_len,
                     self.latent_row_lanes(n.params)),
                    self._cache_dtype),))
                continue
            if n.spec.name == "GatedDeltaNet":
                p = n.params
                caches.append((
                    jnp.zeros((batch_size, p["num_v_heads"],
                               p["head_k_dim"], p["head_v_dim"]),
                              jnp.float32),
                    jnp.zeros((batch_size, (p["conv_kernel"] - 1)
                               * _GDN.widths(p)[2]), self._cache_dtype)))
                continue
            if n.spec.name == "CCAttention":
                # K and V rows in the stored layout, and the rolling
                # state (STATE_ROWS above): [B, STATE_ROWS * (W + K/2)]
                qw, kw, _ = _CCA.widths(n.params)
                rows = (batch_size, self.max_len, kw)
                caches.append((
                    jnp.zeros(rows, self._cache_dtype),
                    jnp.zeros(rows, self._cache_dtype),
                    jnp.zeros((batch_size,
                               STATE_ROWS * (qw + kw + kw // 2)),
                              self._cache_dtype)))
                continue
            win = self._node_window(n)
            slots = win or self.max_len
            if n.spec.name == "GatedAttention":
                kv, d = n.params["num_kv_heads"], n.params["head_dim"]
            else:
                e = self._params[n.inputs[1][0].name].shape[1]  # [F, E]
                kv, d = _MHA.kv_heads(n.params), e // n.params["num_heads"]
            shape = (batch_size, slots, kv * d)
            if self._cache_int8:
                scales = (batch_size, slots, kv)
                entry = (jnp.zeros(shape, jnp.int8),
                         jnp.ones(scales, jnp.float32),
                         jnp.zeros(shape, jnp.int8),
                         jnp.ones(scales, jnp.float32))
            else:
                entry = (jnp.zeros(shape, self._cache_dtype),
                         jnp.zeros(shape, self._cache_dtype))
            if win:
                entry += (jnp.full((batch_size, slots), -1, jnp.int32),)
            caches.append(entry)
        if kv_sharding is not None:
            from jax.sharding import NamedSharding
            mesh = kv_sharding.mesh
            specs = self.cache_specs(caches, kv_sharding.spec[2])
            caches = jax.tree_util.tree_map(
                lambda c, s: jax.device_put(c, NamedSharding(mesh, s)),
                caches, specs)
        return caches

    @staticmethod
    def cache_specs(caches, axis="model"):
        """Per-leaf ``PartitionSpec`` tree for a cache pytree: K/V and
        scale buffers (rank 3) shard dimension 2 — the kv-major lanes
        [Hkv*D], or the [Hkv] scales — over ``axis``, so a shard holds
        whole kv heads; every other leaf replicates (rank 2: the rings'
        position buffers, CCAttention's rolling state; rank 4: a
        GatedDeltaNet's state, which tp refuses anyway). A
        LatentAttention's rows are rank 3 WITHOUT a head axis; tp
        refuses them before any spec is asked for
        (``refuse_latent_rows``).
        Shared by ``init_cache(kv_sharding=...)`` and the
        serving engine's shard_map program specs, so the two can never
        drift."""
        from jax.sharding import PartitionSpec as P

        return jax.tree_util.tree_map(
            lambda c: P(None, None, axis) if jnp.ndim(c) == 3 else P(),
            caches)

    @staticmethod
    def row_buffers(caches):
        """The K buffer [B, rows, Hkv*D] of every cache entry that
        holds rows, a LatentAttention's one buffer [B, rows, R + Dr]
        among them (a GatedDeltaNet's entry holds none: its first leaf
        is the rank-4 state)."""
        return [e[0] for e in caches if jnp.ndim(e[0]) == 3]

    @staticmethod
    def _quantize_rows(x):
        """[B, C, H, D] float -> (int8 values, [B, C, H] f32 scales):
        symmetric amax/127 per (position, head) row."""
        xf = x.astype(jnp.float32)
        s = jnp.max(jnp.abs(xf), axis=-1) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        q = jnp.round(xf / s[..., None]).astype(jnp.int8)
        return q, s

    # -- the derived incremental walk -----------------------------------
    # Inside an attention node's scope (``MultiHeadAttention/<name>``,
    # entered by _run) two sub-scopes name what ROADMAP S1 is about:
    # ``cache`` (the write of the new rows, the read / dequantization
    # of the stored ones) and ``attend`` (scores, softmax, values).
    # The projections stay directly under the node's scope.
    @jax.named_scope("cache")
    def _write_cache(self, entry, k, v, pos):
        """Insert a [B, C, Hkv, D] K/V chunk at ``pos`` into a cache
        entry, as stored rows [B, C, Hkv*D] (int8: quantized per
        (position, head) first, the scales beside them).

        A SHORT chunk (decode, the verify and draft chunks) is written
        row by row, a scatter over (batch row, position): the slot
        walk's ``vmap`` batches that into ONE fused scatter over the
        slots, where a batched dynamic-update-slice becomes a loop of
        one small update a slot (on the chip 0.4 ms an array a round
        of 16 slots x 8 steps against 0.03). A VECTOR ``pos`` ([B]
        int32 — the batched ``_run_slots`` walk) is the same
        scatter with each batch row's own positions. A LONG chunk
        (prefill) goes in as one dynamic-update-slice block.

        Index tuples are uniformly int32: under the package's x64 a
        python-int literal is an int64 index next to the traced int32
        ``pos``, and dynamic-slice wants one index dtype."""
        if self._cache_int8:
            k, ks = self._quantize_rows(k)
            v, vs = self._quantize_rows(v)
            new = (fold_heads(k), ks, fold_heads(v), vs)
        else:
            new = (fold_heads(k).astype(entry[0].dtype),
                   fold_heads(v).astype(entry[1].dtype))
        return self._put_rows(entry, new, pos)

    @staticmethod
    def _put_rows(entry, new, pos):
        """``new`` (one [B, C, ...] chunk per buffer of ``entry``) at
        rows ``[pos, pos + C)``: the scatter or the block of
        ``_write_cache``, by the chunk's length and the kind of
        ``pos``."""
        b, c = new[0].shape[:2]
        p = jnp.asarray(pos, jnp.int32)
        if p.ndim == 1 or c <= _SHORT_CHUNK:
            rows = p.reshape(-1, 1) + jnp.arange(c, dtype=jnp.int32)
            sidx = jnp.arange(b, dtype=jnp.int32)[:, None]
            return tuple(buf.at[sidx, rows].set(x)
                         for buf, x in zip(entry, new))
        z = jnp.int32(0)
        return tuple(lax.dynamic_update_slice(buf, x, (z, p, z))
                     for buf, x in zip(entry, new))

    @jax.named_scope("cache")
    def _read_cache(self, entry, dtype, kv, limit=None):
        """K/V with the head axis unfolded, [B, rows, Hkv, D], for the
        per-head read of a LONG query chunk (prefill): dequantized to
        ``dtype`` if int8, else at the stored dtype (jnp promotion
        governs mixed cache/compute float dtypes). The unfold is a
        copy of what it is given (``unfold_heads``): small beside a
        prefill, and never on the decode step's path — a short chunk
        reads the stored rows as they are (``_lane_attn``).

        ``limit`` (STATIC int, optional): read only rows [0, limit) —
        the max live position of the dispatch, when the caller knows
        it statically (offline generate/beam prefill at python-int
        pos). The gather AND the int8 dequant skip the dead suffix
        entirely. When the position is a traced operand (the engine's
        bucketed prefill, every per-step read) shapes cannot shrink,
        so the full read stays and the dead rows are MASKED at the
        score stage instead — value-identical, pinned by
        tests/test_paged_attention.py."""
        entry = self._live_rows(entry, limit)
        if self._cache_int8:
            ck, ks, cv, vs = entry
            return ((unfold_heads(ck, kv) * ks[..., None]).astype(dtype),
                    (unfold_heads(cv, kv) * vs[..., None]).astype(dtype))
        ck, cv = entry
        return unfold_heads(ck, kv), unfold_heads(cv, kv)

    @staticmethod
    def _live_rows(entry, limit):
        """Rows [0, limit) of every buffer of a linear cache entry
        (``limit`` static; None or past the end: the entry as it is)."""
        if limit is None or limit >= entry[0].shape[1]:
            return entry
        return tuple(lax.slice_in_dim(buf, 0, limit, axis=1)
                     for buf in entry)

    @jax.named_scope("attend")
    def _lane_attn(self, q, entry, pos, kv):
        """Attention of a SHORT query chunk (decode ``c=1``, the
        speculative verify and draft chunks) read straight off the
        stored rows: no reshape of the cache, so nothing cache-sized is
        copied inside the step and every stored byte streams once.

        ``q`` [B, C, H, D]; ``entry`` the (possibly row-limited) cache
        entry, K/V [B, L, Hkv*D]; ``pos`` a scalar (query row i sits at
        ``pos + i`` and sees keys ``<= pos + i``) or a [B] vector, each
        batch row at its own position. Returns
        [B, C, H, D] in ``q``'s dtype.

        Scores: each query row is spread into a block-diagonal
        [Hkv*D, Hkv] matrix (its head-h values in column h, zeros
        elsewhere — exact, a multiplication by 0/1) and the stored
        rows are contracted with it: ``K [L, Hkv*D] @ Qbd`` gives every
        head's score in ONE lane-dense product, operands in the compute
        dtype, products and sums in float32 — where the per-head
        einsum rounded its scores to the compute dtype. Softmax in
        float32 over the masked rows. Values: ``p^T [Hkv, L] @ V
        [L, Hkv*D]`` and the diagonal blocks of the result (head h's
        weights against head h's lanes), picked by the same 0/1
        matrix. Grouped-query attention folds the G query heads of a
        kv head into the row axis (row = (c, g)), the group fold of
        the per-head path. int8 caches apply the row scales to the
        [L, Hkv] scores and to ``p`` — exact, and cheaper than
        dequantizing the rows."""
        b, c, h, d = q.shape
        g = h // kv
        if self._cache_int8:
            ck, ks, cv, vs = entry
            # int8 values are exact in any float dtype
            ck, cv = ck.astype(q.dtype), cv.astype(q.dtype)
        else:
            ck, cv = entry
            ks = vs = None
        rows = ck.shape[1]
        f32 = jnp.float32
        seg = head_segments(kv, d, q.dtype)                  # [kv*d, kv]
        # query rows r = (c, g); lanes (kv, d) as the cache stores them
        qr = q.reshape(b, c, kv, g, d).transpose(0, 1, 3, 2, 4) \
            .reshape(b, c * g, kv * d)
        qbd = qr[..., None] * seg                            # [b,r,kv*d,kv]
        s = jnp.einsum("blk,brkh->brlh", ck, qbd,
                       preferred_element_type=f32)
        if ks is not None:
            s = s * ks[:, None]
        s = s * f32(1.0 / float(np.sqrt(d)))
        kpos = jnp.arange(rows)[None, None, :, None]
        if jnp.ndim(pos) == 1:
            pos = jnp.asarray(pos, jnp.int32)[:, None, None, None]
        qpos = pos + (jnp.arange(c * g) // g)[None, :, None, None]
        s = jnp.where(kpos <= qpos, s, f32(-1e30))
        p = jax.nn.softmax(s, axis=2)                        # [b,r,l,kv]
        if vs is not None:
            p = p * vs[:, None]
        m = jnp.einsum("brlh,blk->brhk", p.astype(cv.dtype), cv,
                       preferred_element_type=f32)           # [b,r,kv,kv*d]
        o = jnp.sum(m * seg.T.astype(f32), axis=2)           # [b,r,kv*d]
        return o.reshape(b, c, g, kv, d).transpose(0, 1, 3, 2, 4) \
            .reshape(b, c, h, d).astype(q.dtype)

    def _paged_read(self, q, entry, pos, kv, lens=None, stats=None):
        """Attention of a SHORT query chunk at a VECTOR of positions
        (the slot walk: decode, verify, draft) through the bounded read
        (ops/pallas_kernels.py ``paged_attention``): only the blocks of
        rows [0, lens) of each slot of the stored buffers are fetched
        (``lens``: ``pos + C``, or 0 for a slot that holds no request,
        whose output is zeros), the int8 side scales applied IN the
        kernel. ``entry`` is a linear cache entry, K/V [B, L, Hkv*D]
        as ``_lane_attn`` takes it, the chunk's rows already written.
        ``stats["attn_rows_read"]`` grows by the rows fetched."""
        from ..ops.pallas_kernels import (default_paged_block_k,
                                          paged_attention,
                                          paged_rows_fetched)
        posv = jnp.asarray(pos, jnp.int32)
        if lens is None:
            lens = posv + q.shape[1]
        ck, cv = entry[0], entry[2 if self._cache_int8 else 1]
        rows = ck.shape[1]
        bk = default_paged_block_k(rows, ck.shape[2] * ck.dtype.itemsize)
        scales = dict(k_scale=entry[1], v_scale=entry[3]) \
            if self._cache_int8 else {}
        with jax.named_scope("attend"):
            o = paged_attention(q, ck, cv, posv, kv_heads=kv,
                                lens=lens, block_k=bk, **scales)
        if stats is not None:
            stats["attn_rows_read"] = paged_rows_fetched(
                lens, rows, bk) + stats.get("attn_rows_read", 0)
        return o

    def _embedding_weight_names(self):
        """Parameter names consumed as Embedding tables — always
        per-row int8 under quantization (``row_quant``): a
        packed-nibble row gather would read-modify every byte for
        half its bits (serving/quant.py ``embedding_rows``)."""
        names = set()
        for n in self._topo:
            if not n.is_var and n.spec.name == "Embedding":
                names.add(n.inputs[1][0].name)
        return names

    def _qmm(self, x, qt, impl):
        """One quantized matmul ``x [..., E] @ qt [F, E]^T`` under the
        decoder's ``matmul_impl``. ``"dense"`` (default) is the
        chunked host-level ``fori_loop`` (``scale_fused_matmul``);
        ``"pallas"`` dispatches ``quant_matmul`` — the same
        output-channel partition at the SAME chunk size
        (``resolve_chunk``, lane-legal heights only), so the two impls
        stage identically and agree to f32 rounding; what "pallas" is
        held to is the serving gauntlet's token-level identity."""
        from ..serving.quant import resolve_chunk, scale_fused_matmul
        if impl in (None, "dense"):
            return scale_fused_matmul(x, qt)
        from ..ops.pallas_kernels import quant_matmul
        f = qt.shape[0]
        x2 = x.reshape(-1, x.shape[-1])
        out = quant_matmul(x2, qt.q, qt.scale, bits=qt.bits,
                           group=qt.group,
                           block_f=resolve_chunk(f) or f,
                           out_dtype=x.dtype)
        return out.reshape(x.shape[:-1] + (f,))

    # THE READ FOLLOWS THE CACHE KIND. Which read a cached node takes is
    # decided here (``_cached_mha`` / ``_cached_cca``: from the node, the
    # chunk's length and whether ``pos`` is a vector) and in
    # ``_run_slots`` (``_slots_batched``), and nowhere else. No option
    # names a read.
    #
    # MultiHeadAttention, windowed ring, any chunk:
    #     ``_window_attn``. The slot walk is the ``vmap`` of one-slot
    #     walks: a ring's read is written for one position, it has no
    #     other.
    # MultiHeadAttention on a linear cache and CCAttention (its K and V
    # rows are a linear cache), short chunk, position VECTOR (the slot
    # walk: decode, verify, draft):
    #     the bounded read ``paged_attention(lens=...)``, rows
    #     [0, lens) of each slot, in ONE batched walk (``_paged_read``).
    # MultiHeadAttention, linear cache, short chunk, one scalar position
    # (the offline step / ``generate`` / ``beam_search``):
    #     ``_lane_attn``, all rows masked by position; clamped
    #     statically where the position is a Python int.
    # MultiHeadAttention, linear cache, long chunk (prefill):
    #     ``_head_attn``.
    # CCAttention, one scalar position:
    #     ``_cached_cca`` (``_lane_attn`` for a short chunk,
    #     ``_head_attn`` for a long one).
    # LatentAttention (``_cached_mla``): a short chunk at a position
    # vector reads ABSORBED through the bounded latent read
    # (``pallas_kernels.latent_paged_attention``), a short chunk at one
    # position absorbed over all rows, a long chunk (prefill) EXPANDED a
    # block of rows at a time (``_latent_attn_blocks``).
    def _cached_mha(self, node, ins, entry, pos, valid_len=None,
                    tp=None, mm_impl=None, lens=None, stats=None):
        from ..ops.attention import MultiHeadAttention as _MHA
        from ..serving.quant import QuantizedTensor

        x, wqkv, bqkv, wo, bo = ins
        b, c, e = x.shape
        h = node.params["num_heads"]
        d = e // h
        kv = _MHA.kv_heads(node.params)
        if isinstance(wqkv, QuantizedTensor):
            # weight-only int8/int4: dequantized on the fly inside
            # the product (serving/quant.py; matmul_impl picks the
            # fori loop or the Pallas kernel) — the projection reads
            # the stored quantized stream, no float weight copy
            qkv = self._qmm(x, wqkv, mm_impl) + bqkv
        else:
            qkv = jnp.einsum("bte,fe->btf", x, wqkv) + bqkv
        q = qkv[..., :e].reshape(b, c, h, d)
        k = qkv[..., e:e + kv * d].reshape(b, c, kv, d)
        v = qkv[..., e + kv * d:].reshape(b, c, kv, d)
        if node.params.get("rope"):
            # rotate with ABSOLUTE positions (pos is traced); the cache
            # stores post-rotation K, matching the full forward exactly
            # (rotation is per-head, so rotating the kv heads before
            # their group broadcast equals the full forward's
            # rotate-after-repeat)
            from ..ops.attention import rope_rotate
            if jnp.ndim(pos) == 1:   # per-slot clocks (the slot walk)
                posv = jnp.asarray(pos, jnp.int32)[:, None] \
                    + jnp.arange(c, dtype=jnp.int32)
            else:
                posv = pos + jnp.arange(c)
            q = rope_rotate(q, posv, node.params["rope_base"])
            k = rope_rotate(k, posv, node.params["rope_base"])

        def out_proj(o):
            o = o.reshape(b, c, e)
            if isinstance(wo, QuantizedTensor):
                return self._qmm(o, wo, mm_impl) + bo
            return jnp.einsum("bte,fe->btf", o, wo) + bo

        if tp is not None:
            # tensor-parallel serving (inside the engine's shard_map —
            # doc/serving.md "Tensor-parallel serving"): everything up
            # to here ran REPLICATED with tp=1's exact shapes (the
            # byte-identity lever: per-device numerics never see the
            # shard count); each shard now slices out its OWN
            # contiguous kv-head block — query heads are kv-major, so
            # a kv-head slice keeps every GQA group whole — and the
            # per-head attention below runs on the local cache shard.
            ax, ntp = tp
            i = lax.axis_index(ax)
            kvl, hl = kv // ntp, h // ntp
            q = lax.dynamic_slice_in_dim(q, i * hl, hl, axis=2)
            k = lax.dynamic_slice_in_dim(k, i * kvl, kvl, axis=2)
            v = lax.dynamic_slice_in_dim(v, i * kvl, kvl, axis=2)
            h, kv = hl, kvl
        win = self._node_window(node)
        if win:
            if jnp.ndim(pos) == 1:
                raise MXNetError(
                    "Decoder: a windowed ring (node %r) is read at one "
                    "position; a position vector has no ring read"
                    % node.name)
            o, entry = self._window_attn(q, k, v, entry, pos, win,
                                         valid_len)
            if tp is not None:
                o = lax.all_gather(o, tp[0], axis=2, tiled=True)
            return out_proj(o), entry
        entry = self._write_cache(entry, k, v, pos)
        if jnp.ndim(pos) == 1:
            o = self._paged_read(q, entry, pos, kv, lens, stats)
        else:
            # dense read. A STATIC dispatch position (offline
            # generate/beam prefill call _run with a python-int pos)
            # bounds the live rows statically: the read is clamped to
            # [0, pos+c) instead of masking all max_len rows (the
            # masked full read remains for traced positions, where
            # shapes cannot shrink — see _read_cache)
            limit = self.max_len
            if isinstance(pos, (int, np.integer)):
                limit = min(self.max_len, int(pos) + c)
            if c <= _SHORT_CHUNK:
                # a matrix-vector read wants the lanes: straight off
                # the stored rows
                o = self._lane_attn(q, self._live_rows(entry, limit),
                                    pos, kv)
            else:
                # a matrix-matrix read (prefill) wants the heads
                o = self._head_attn(
                    q, *self._read_cache(entry, q.dtype, kv, limit),
                    pos)
        if tp is not None:
            # ONE collective per attention node: gather the per-shard
            # head outputs (axis 2 is kv-major in every o layout —
            # bqhd, bqKgd — so tiled concat reproduces tp=1's head
            # order exactly) and hand the REPLICATED [b, c, e] tensor
            # to the output projection: it and every downstream
            # position-wise op run with tp=1's shapes on every shard
            o = lax.all_gather(o, tp[0], axis=2, tiled=True)
        return out_proj(o), entry

    def _cached_cca(self, node, ins, entry, pos, valid_len=None,
                    lens=None, stats=None):
        """CCAttention on a chunk at ``pos`` (a scalar, or a [B] vector:
        every batch row at its own position) against its cache entry
        ``(K rows, V rows, rolling state)``. The mixing is the op's own
        (``ops.attention.cca_qkv``), fed the two positions before the
        chunk from the state; K and V go into the stored rows like
        MultiHeadAttention's and are read the same three ways
        (``_paged_read`` with ``lens`` and ``stats`` for a short chunk
        at a position vector: read densely there, a layer's whole
        buffer is an operand small enough for the compiler to stage
        through fast memory and back on every step, PERF.md PR 31); the
        state then takes the chunk's last STATE_ROWS REAL positions
        (``valid_len``, absolute: a right-padded prefill bucket must
        leave the state of its last real token, not of its padding)."""
        from ..ops.attention import CCAttention as _CCA, cca_qkv
        x, wqk, wv, c0w, c0b, c1w, c1b, temp, wo = ins
        p = node.params
        b, c, _ = x.shape
        qw, kw, d = _CCA.widths(p)
        kv = p["num_kv_heads"]
        i32 = jnp.int32
        with jax.named_scope("proj"):
            u = jnp.einsum("bte,fe->btf", x, wqk)
            vv = jnp.einsum("bte,fe->btf", x, wv)
        first = jnp.broadcast_to(
            jnp.asarray(pos, i32).reshape(-1, 1), (b, 1))     # [B, 1]
        with jax.named_scope("cache"):
            ck, cv, flat = entry
            state = flat.reshape(b, STATE_ROWS, -1)   # small: a view
            def before(k):           # the state's row of position -k
                row = jnp.take_along_axis(
                    state, ((first - k) % STATE_ROWS)[..., None], axis=1)
                return row[..., :qw + kw], row[..., qw + kw:]
            (u1, v2p), (u2, _) = before(1), before(2)
        with jax.named_scope("conv"):
            q, k, v = cca_qkv(p, u, vv, (c0w, c0b, c1w, c1b, temp),
                              first + jnp.arange(c, dtype=i32),
                              prev=(u1, u2, v2p))
        kvrows = self._write_cache((ck, cv), k, v, pos)
        limit = self.max_len
        if isinstance(pos, (int, np.integer)):
            limit = min(self.max_len, int(pos) + c)
        if jnp.ndim(pos) == 1:
            if c > _SHORT_CHUNK:
                raise MXNetError(
                    "Decoder: a chunk of %d tokens at per-slot "
                    "positions has no read (CCAttention node %r)"
                    % (c, node.name))
            o = self._paged_read(q, kvrows, pos, kv, lens, stats)
        elif c <= _SHORT_CHUNK:
            o = self._lane_attn(q, self._live_rows(kvrows, limit), pos, kv)
        else:
            o = self._head_attn(
                q, *self._read_cache(kvrows, q.dtype, kv, limit), pos)
        with jax.named_scope("cache"):
            # the chunk's last STATE_ROWS real positions, each to its
            # own row; rows of padding (and of a chunk shorter than
            # the ring) scatter out of bounds and are dropped
            real = jnp.full((b, 1), c, i32) if valid_len is None else \
                jnp.clip(jnp.asarray(valid_len, i32) - first, 0, c)
            idx = real - STATE_ROWS + jnp.arange(STATE_ROWS, dtype=i32)
            at = jnp.clip(idx, 0, c - 1)
            new = jnp.take_along_axis(
                jnp.concatenate([u, vv[..., kw // 2:]], axis=-1)
                .astype(state.dtype), at[..., None], axis=1)
            rows = jnp.where(idx >= 0, (first + at) % STATE_ROWS,
                             STATE_ROWS)
            state = state.at[jnp.arange(b, dtype=i32)[:, None], rows] \
                .set(new, mode="drop")
        with jax.named_scope("proj"):
            out = jnp.einsum("btq,eq->bte", o.reshape(b, c, qw), wo)
        return out, kvrows + (state.reshape(flat.shape),)

    def _cached_gattn(self, node, ins, entry, pos, lens=None, stats=None):
        """GatedAttention on a chunk at ``pos`` (a scalar, or a [B]
        vector) against its cache entry, K and V rows. Queries, keys,
        values and the gate are the op's own (``ops.attention.
        gattn_qkv``); the rows are written like MultiHeadAttention's
        (``_write_cache``) and read its ways: the bounded read for a
        short chunk at a position vector, ``_lane_attn`` for a short
        chunk at one position, and for a long chunk (prefill) per-head
        products a block of queries at a time (``_gqa_attn_blocks``: a
        piece of 2,048 queries against a slot of 9,216 rows is 1.2 GB
        of float32 scores at once)."""
        from ..ops.attention import gattn_out, gattn_qkv
        x = ins[0]
        p = node.params
        c = x.shape[1]
        kv = p["num_kv_heads"]
        i32 = jnp.int32
        positions = jnp.asarray(pos, i32).reshape(-1, 1) \
            + jnp.arange(c, dtype=i32)
        q, k, v, gate = gattn_qkv(p, x, ins[1:6], positions)
        entry = self._write_cache(entry, k, v, pos)
        if jnp.ndim(pos) == 1:
            if c > _SHORT_CHUNK:
                raise MXNetError(
                    "Decoder: a chunk of %d tokens at per-slot "
                    "positions has no read (GatedAttention node %r)"
                    % (c, node.name))
            o = self._paged_read(q, entry, pos, kv, lens, stats)
        else:
            limit = self.max_len
            if isinstance(pos, (int, np.integer)):
                limit = min(self.max_len, int(pos) + c)
            if c <= _SHORT_CHUNK:
                o = self._lane_attn(q, self._live_rows(entry, limit),
                                    pos, kv)
            else:
                o = self._gqa_attn_blocks(
                    q, *self._read_cache(entry, q.dtype, kv, limit), pos)
        return gattn_out(o, gate, ins[6]), entry

    # a prefill piece reads the latent rows so far this many at a time
    # (a power of two that divides the cache, else the whole cache): per
    # block the up-projection to per-head keys and values, scores and
    # values, merged by an online softmax; the number of blocks follows
    # the position, so a piece early in a long slot does not pay for the
    # slot's length
    _LATENT_BLOCK = 1024

    def _cached_mla(self, node, ins, entry, pos, lens=None, stats=None):
        """LatentAttention on a chunk at ``pos`` (a scalar, or a [B]
        vector) against its cache entry, ONE buffer of latent rows (the
        latent-rows kind, top of this module). Queries and the rows to
        store are the op's own (``ops.attention.mla_down``); the rows
        are written like K/V rows (``_put_rows``) and read three ways:
        a short chunk at a position vector (the slot walk) ABSORBED
        through the bounded read, which fetches a block of rows once
        for scores and values (``lens``, ``stats`` as ``_paged_read``;
        ``stats["latent_rows_live"]`` grows by the slots' true lengths);
        a short chunk at one position absorbed over all rows, masked;
        a long chunk (prefill) EXPANDED, a block of rows at a time
        (``_latent_attn_blocks``)."""
        from ..ops.attention import (mla_absorb_out, mla_absorb_q,
                                     mla_down, mla_out, mla_softmax_scale)
        x, wukv, wo = ins[0], ins[6], ins[7]
        p = node.params
        c = x.shape[1]
        r = p["kv_lora_rank"]
        i32, f32 = jnp.int32, jnp.float32
        positions = jnp.asarray(pos, i32).reshape(-1, 1) \
            + jnp.arange(c, dtype=i32)
        q, new = mla_down(p, x, ins[1:6], positions)
        with jax.named_scope("cache"):
            pad = entry[0].shape[2] - new.shape[2]
            new = jnp.pad(new.astype(entry[0].dtype),
                          [(0, 0), (0, 0), (0, pad)])
            (rows,) = self._put_rows(entry, (new,), pos)
        scale = mla_softmax_scale(p)
        if jnp.ndim(pos) == 1:
            if c > _SHORT_CHUNK:
                raise MXNetError(
                    "Decoder: a chunk of %d tokens at per-slot "
                    "positions has no read (LatentAttention node %r)"
                    % (c, node.name))
            from ..ops.pallas_kernels import (default_paged_block_k,
                                              latent_paged_attention,
                                              paged_rows_fetched)
            posv = jnp.asarray(pos, i32)
            if lens is None:
                lens = posv + c
            bk = default_paged_block_k(
                rows.shape[1], rows.shape[2] * rows.dtype.itemsize)
            with jax.named_scope("absorb"):
                qa = mla_absorb_q(p, q, wukv)
            with jax.named_scope("attend"):
                o = latent_paged_attention(qa, rows, posv, v_width=r,
                                           scale=scale, lens=lens,
                                           block_k=bk)
            if stats is not None:
                stats["attn_rows_read"] = paged_rows_fetched(
                    lens, rows.shape[1], bk) \
                    + stats.get("attn_rows_read", 0)
                stats["latent_rows_live"] = jnp.sum(
                    jnp.clip(jnp.asarray(lens, i32), 0, rows.shape[1])) \
                    + stats.get("latent_rows_live", 0)
            with jax.named_scope("absorb"):
                o = mla_absorb_out(p, o, wukv)
        else:
            limit = self.max_len
            if isinstance(pos, (int, np.integer)):
                limit = min(self.max_len, int(pos) + c)
            if c <= _SHORT_CHUNK:
                (live,) = self._live_rows((rows,), limit)
                with jax.named_scope("absorb"):
                    qa = mla_absorb_q(p, q, wukv)
                with jax.named_scope("attend"):
                    s = jnp.einsum(
                        "bchw,blw->bhcl", qa,
                        live[..., :qa.shape[-1]].astype(qa.dtype),
                        preferred_element_type=f32) * f32(scale)
                    kpos = jnp.arange(live.shape[1])[None, None, None]
                    qpos = pos + jnp.arange(c)[None, None, :, None]
                    pr = jax.nn.softmax(
                        jnp.where(kpos <= qpos, s, f32(-1e30)), axis=-1)
                    o = jnp.einsum("bhcl,blr->bchr", pr.astype(qa.dtype),
                                   live[..., :r].astype(qa.dtype),
                                   preferred_element_type=f32)
                with jax.named_scope("absorb"):
                    o = mla_absorb_out(p, o.astype(qa.dtype), wukv)
            else:
                o = self._latent_attn_blocks(p, q, rows, wukv, pos, scale)
        return mla_out(o, wo), (rows,)

    def _latent_attn_blocks(self, p, q, rows, wukv, pos, scale):
        """The EXPANDED read of a LONG query chunk ``q`` [B, C, H,
        Dn + Dr] at ``pos`` (one position, traced or static) against
        latent rows [B, L, R + Dr] that already hold the chunk's own:
        ``_LATENT_BLOCK`` rows at a time, for as many blocks as
        ``pos + C`` needs (a loop whose length follows the position),
        each block up-projected to per-head keys and values
        (``ops.attention.mla_expand``), scored in float32 and merged
        by an online softmax. Returns [B, C, H, Dv] in ``q``'s dtype."""
        from ..ops.attention import mla_expand
        b, c, h, _ = q.shape
        dn, dv = p["nope_dim"], p["v_dim"]
        total = rows.shape[1]
        bk = 1
        while bk * 2 <= self._LATENT_BLOCK and total % (bk * 2) == 0:
            bk *= 2
        if bk < 8:
            bk = total
        f32, i32 = jnp.float32, jnp.int32
        qn, qr = q[..., :dn], q[..., dn:]
        qpos = pos + jnp.arange(c, dtype=i32)
        neg = f32(-1e30)

        def body(j, carry):
            m, l, acc = carry
            with jax.named_scope("cache"):
                blk = lax.dynamic_slice_in_dim(rows, j * bk, bk, axis=1)
            with jax.named_scope("expand"):
                kn, v, kr = mla_expand(p, blk, wukv)
            with jax.named_scope("attend"):
                s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn,
                                preferred_element_type=f32)
                     + jnp.einsum("bqhd,bkd->bhqk", qr, kr,
                                  preferred_element_type=f32)) * f32(scale)
                kpos = j * bk + jnp.arange(bk, dtype=i32)
                ok = (kpos[None, :] <= qpos[:, None])[None, None]
                s = jnp.where(ok, s, neg)
                new_m = jnp.maximum(m, jnp.max(s, axis=-1))
                pe = jnp.where(ok, jnp.exp(s - new_m[..., None]), 0.0)
                corr = jnp.exp(m - new_m)
                l = l * corr + jnp.sum(pe, axis=-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bhqk,bkhd->bhqd", pe.astype(v.dtype), v,
                    preferred_element_type=f32)
            return new_m, l, acc

        init = (jnp.full((b, h, c), neg, f32), jnp.zeros((b, h, c), f32),
                jnp.zeros((b, h, c, dv), f32))
        if isinstance(pos, (int, np.integer)):
            nblk = min(-(-(int(pos) + c) // bk), total // bk)
        else:
            nblk = jnp.minimum((jnp.asarray(pos, i32) + c + bk - 1) // bk,
                               total // bk)
        _, l, acc = lax.fori_loop(0, nblk, body, init)
        with jax.named_scope("attend"):
            o = acc / jnp.maximum(l, 1e-30)[..., None]
            return jnp.swapaxes(o, 1, 2).astype(q.dtype)

    def _cached_gdn(self, node, ins, entry, pos, valid_len=None,
                    lens=None, stats=None):
        """GatedDeltaNet on a chunk at ``pos`` against its cache entry
        ``(state, convolution inputs)``: the op's own mixer
        (``ops.attention.gdn_mix``) continued from what the entry
        holds, under the state kind's three contracts (the top of this
        module): a batch row with ``lens`` 0 keeps its entry as it was,
        a chunk at position 0 starts from zeros, and a right-padded
        chunk (``valid_len``, absolute) leaves the state of its last
        real token. ``stats["state_advanced"]`` grows by the rows whose
        state this chunk advanced, ``stats["state_in_place"]`` by one
        where it advanced them through the kernel that visits live rows
        only (``ops.attention.gdn_steps_in_place``)."""
        from ..ops.attention import gdn_mix, gdn_steps_in_place
        x = ins[0]
        b, c, _ = x.shape
        i32 = jnp.int32
        state, flat = entry
        first = jnp.broadcast_to(
            jnp.asarray(pos, i32).reshape(-1, 1), (b, 1))     # [B, 1]
        if lens is not None:
            lens = jnp.asarray(lens, i32)
        live = None if lens is None else lens > 0
        with jax.named_scope("state"):
            fresh = first == 0
            if live is not None:
                fresh = fresh & live[:, None]
            prev = jnp.where(fresh[..., None], 0,
                             flat.reshape(b, node.params["conv_kernel"]
                                          - 1, -1))
        real = None if valid_len is None else \
            jnp.clip(jnp.asarray(valid_len, i32) - first, 0, c)
        y, state, prev = gdn_mix(node.params, x, ins[1:], state, prev,
                                 real=real, lens=lens, fresh=fresh[:, 0])
        if stats is not None:
            n = jnp.int32(b) if live is None else jnp.sum(live, dtype=i32)
            stats["state_advanced"] = n + stats.get("state_advanced", 0)
            stats["state_in_place"] = stats.get("state_in_place", 0) \
                + int(gdn_steps_in_place(node.params, c, lens))
        return y, (state, prev.reshape(flat.shape))

    @staticmethod
    @jax.named_scope("attend")
    def _gqa_attn_blocks(q, ck, cv, pos, block=256):
        """Grouped-query attention of a LONG query chunk against K/V
        unfolded to [B, rows, Hkv, D], masked by position, ``block``
        queries at a time (``lax.map``): scores and softmax in float32,
        the weights in the values' dtype for their product."""
        b, c, h, d = q.shape
        rows, kv = ck.shape[1], ck.shape[2]
        f32 = jnp.float32
        kpos = jnp.arange(rows)[None, None, None, None, :]

        def attend(qb, start):
            n = qb.shape[1]
            qg = qb.reshape(b, n, kv, h // kv, d)
            s = jnp.einsum("bqKgd,bkKd->bKgqk", qg, ck,
                           preferred_element_type=f32) \
                * f32(1.0 / float(np.sqrt(d)))
            qpos = start + jnp.arange(n)[None, None, None, :, None]
            p = jax.nn.softmax(jnp.where(kpos <= qpos, s, f32(-1e30)),
                               axis=-1)
            return jnp.einsum("bKgqk,bkKd->bqKgd", p.astype(cv.dtype), cv,
                              preferred_element_type=f32).astype(q.dtype)

        if c <= block or c % block:
            return attend(q, pos)
        nb = c // block
        qb = jnp.moveaxis(q.reshape(b, nb, block, h, d), 1, 0)
        starts = pos + jnp.arange(nb, dtype=jnp.int32) * block
        o = lax.map(lambda a: attend(*a), (qb, starts))
        return jnp.moveaxis(o, 0, 1).reshape(b, c, kv, h // kv, d)

    @staticmethod
    @jax.named_scope("attend")
    def _head_attn(q, ck, cv, pos):
        """Per-head matrix products of a LONG query chunk (the prefill
        buckets, offline ``generate``'s prompt) against K/V unfolded
        to [B, rows, Hkv, D] (``_read_cache``), masked by position."""
        b, c, h, d = q.shape
        rows, kv = ck.shape[1], ck.shape[2]
        if kv == h:
            s = jnp.einsum("bqhd,bkhd->bhqk", q, ck) / float(np.sqrt(d))
            kpos = jnp.arange(rows)[None, None, None, :]
            qpos = pos + jnp.arange(c)[None, None, :, None]
            s = jnp.where(kpos <= qpos, s,
                          jnp.float32(-1e30).astype(s.dtype))
            return jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(s, axis=-1), cv)
        # GQA: grouped einsums read the kv-head rows directly — query
        # heads fold to [B, C, Hkv, G, D] and contract against their
        # shared K/V head, no repeated copy of the rows
        qg = q.reshape(b, c, kv, h // kv, d)
        s = jnp.einsum("bqKgd,bkKd->bKgqk", qg, ck) / float(np.sqrt(d))
        kpos = jnp.arange(rows)[None, None, None, None, :]
        qpos = pos + jnp.arange(c)[None, None, None, :, None]
        s = jnp.where(kpos <= qpos, s, jnp.float32(-1e30).astype(s.dtype))
        return jnp.einsum("bKgqk,bkKd->bqKgd",
                          jax.nn.softmax(s, axis=-1), cv)

    def _window_attn(self, q, k, v, entry, pos, win, valid_len=None):
        """Sliding-window attention against a ring-buffer cache.

        EXACT for any chunk size: queries score the PRE-CHUNK ring
        (slots masked by their stored absolute positions — a slot is
        visible iff written, strictly before this chunk, and within
        the query's window) and the IN-CHUNK keys (dense causal+window
        mask) under ONE softmax; only then does the chunk's tail
        overwrite the ring. Reading before writing is what makes
        chunked prefill correct — a ring slot a mid-chunk query still
        needs is never clobbered by a later in-chunk key first.
        Returns (o [B, C, H, D], updated entry).

        ``valid_len`` (traced, optional): only chunk rows with absolute
        position < valid_len are written to the ring. A RIGHT-PADDED
        chunk (the serving engine's bucketed prefill) must not let pad
        rows into the ring: unlike the linear cache — where a pad row
        sits at a masked future position until decode overwrites it —
        a ring write at pad position p lands in slot ``p %% win`` and
        EVICTS the real key living there, which in-window queries still
        need. Invalid rows scatter to slot index ``win`` (out of
        bounds) under ``mode="drop"``."""
        b, c, h, d = q.shape
        kvh = k.shape[2]
        g = h // kvh
        def to_h(z):  # GQA: broadcast the (small) ring/chunk K/V rows
            return jnp.repeat(z, g, axis=2) if g > 1 else z

        with jax.named_scope("cache"):
            # the ring is small (``win`` rows): unfold it per head
            if self._cache_int8:
                ck, ks, cv, vs, cpos = entry
                ckf = unfold_heads(ck, kvh) * ks[..., None]
                cvf = unfold_heads(cv, kvh) * vs[..., None]
            else:
                ck, cv, cpos = entry
                ckf, cvf = unfold_heads(ck, kvh), unfold_heads(cv, kvh)
            ckf = to_h(ckf.astype(jnp.float32))
            cvf = to_h(cvf.astype(jnp.float32))
        qf = q.astype(jnp.float32)
        kf = to_h(k.astype(jnp.float32))
        vf = to_h(v.astype(jnp.float32))
        with jax.named_scope("attend"):
            qpos = pos + jnp.arange(c)
            scale = 1.0 / float(np.sqrt(d))

            s_ring = jnp.einsum("bqhd,bkhd->bhqk", qf, ckf) * scale
            cp = cpos[:, None, None, :]
            ring_ok = (cp >= 0) & (cp < pos) \
                & (cp > qpos[None, None, :, None] - win)
            s_ring = jnp.where(ring_ok, s_ring, -jnp.inf)

            s_chunk = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
            chunk_ok = (qpos[:, None] >= qpos[None, :]) \
                & (qpos[:, None] - qpos[None, :] < win)
            s_chunk = jnp.where(chunk_ok[None, None], s_chunk, -jnp.inf)

            # one softmax over ring + chunk keys (self is always valid, so
            # no empty rows)
            p = jax.nn.softmax(
                jnp.concatenate([s_ring, s_chunk], axis=-1), axis=-1)
            nring = ckf.shape[1]
            o = jnp.einsum("bhqk,bkhd->bqhd", p[..., :nring], cvf) \
                + jnp.einsum("bhqk,bkhd->bqhd", p[..., nring:], vf)
            o = o.astype(q.dtype)

        # write the last min(win, #valid) VALID rows of the chunk —
        # earlier valid rows would be overwritten within this same
        # chunk anyway. The write set is selected relative to the
        # VALID length, not the chunk length: a right-padded chunk's
        # "last win rows" would both push pad keys into the ring
        # (evicting real in-window keys — ring slots wrap, unlike the
        # linear cache's masked-until-overwritten pad rows) and skip
        # real keys displaced before the pad tail. Gather keeps the
        # write static-shaped ([win] rows); rows before the chunk
        # scatter out of bounds under mode="drop". valid_len=None
        # degenerates to the old last-min(c, win)-rows behavior.
        with jax.named_scope("cache"):
            p32 = jnp.asarray(pos, jnp.int32)
            if valid_len is None:
                vc = jnp.int32(c)
            else:
                vc = jnp.clip(jnp.asarray(valid_len, jnp.int32) - p32, 0, c)
            idx = vc - win + jnp.arange(win)       # chunk rows to write
            valid = idx >= 0
            gidx = jnp.clip(idx, 0, c - 1)
            newpos = p32 + gidx
            slots = jnp.where(valid, newpos % win, win)  # win: dropped
            kt = jnp.take(k, gidx, axis=1)
            vt = jnp.take(v, gidx, axis=1)
            posb = jnp.broadcast_to(newpos[None], (b, win)).astype(jnp.int32)
            if self._cache_int8:
                k8, ksc = self._quantize_rows(kt)
                v8, vsc = self._quantize_rows(vt)
                entry = (ck.at[:, slots].set(fold_heads(k8), mode="drop"),
                         ks.at[:, slots].set(ksc, mode="drop"),
                         cv.at[:, slots].set(fold_heads(v8), mode="drop"),
                         vs.at[:, slots].set(vsc, mode="drop"),
                         cpos.at[:, slots].set(posb, mode="drop"))
            else:
                entry = (ck.at[:, slots].set(
                             fold_heads(kt).astype(ck.dtype), mode="drop"),
                         cv.at[:, slots].set(
                             fold_heads(vt).astype(cv.dtype), mode="drop"),
                         cpos.at[:, slots].set(posb, mode="drop"))
        return o, entry

    def _run(self, params, aux, caches, pos, tokens, valid_len=None,
             tp=None, mm_impl=None, ep=None, stats=None, lens=None):
        """One chunk: tokens [B, C] at positions [pos, pos+C) →
        (logits [B, C, V], updated caches). ``valid_len`` marks a
        right-padded chunk's true length — windowed ring WRITES
        honor it (see ``_window_attn``) and so do the state leaves
        (CCAttention's ring, a GatedDeltaNet's state); linear-cache pad
        rows are self-correcting (masked until decode overwrites them).

        ``tp`` (optional ``(axis_name, degree)``): the walk is running
        INSIDE a tensor-parallel shard_map and ``caches`` hold only
        this shard's kv heads — attention slices its shard's heads
        out of the replicated projections and all-gathers its head
        outputs (see ``_cached_mha``); every other op runs replicated
        with tp=1's exact shapes.

        Quantized weights (``weight_dtype="int8"`` — or an engine that
        quantized its own parameter copy) ride the env as
        ``QuantizedTensor`` pytree leaves; the consumers that can see
        one (attention projections, FullyConnected, Embedding, MoEFFN
        — ``quant.quantized_weight_names`` guarantees no other op
        does) dequantize on the fly via the scale-fused forms
        below.

        ``stats`` (a dict, optional): what the walk counts on the
        device is summed into it — ``experts_touched`` (experts given
        a token, summed over the routed MoEFFN nodes): the serving
        engine's ``serving.moe_experts_touched``; ``pairs_held``
        (token-expert pairs that fell on experts the nodes hold):
        ``serving.moe_pairs_held``; ``rows_masked`` (batch rows that
        were not live, whose pairs those nodes dropped: under ``lens``
        only): ``serving.moe_rows_masked``; ``attn_rows_read``
        (cache rows the bounded reads fetched, block-rounded, summed
        over the attention nodes): ``serving.attn_rows_read``;
        ``state_advanced`` (batch rows whose recurrent state a
        GatedDeltaNet node advanced, summed over those nodes):
        ``serving.state_slots_advanced``; ``state_in_place`` (those
        nodes that advanced it through ``gdn_state_step``):
        ``serving.state_steps_in_place``; ``latent_rows_live`` (the
        batch rows' true lengths, summed over the LatentAttention
        nodes): ``serving.latent_rows_live``.

        ``lens`` ([B] int32, with a vector ``pos``): the rows of each
        batch row's cache that the bounded read may fetch — the slot
        walk's ``pos + C`` for a slot that holds a request, 0 for one
        that does not (its output is then discarded by the caller, a
        GatedDeltaNet node leaves its state untouched: the state
        kind's "not live", and a routed MoEFFN node gives its tokens
        no expert: ``moe_ffn_math``'s ``live``)."""
        from ..ops.attention import moe_ffn_math
        from ..serving.quant import (QuantizedTensor, embedding_rows,
                                     moe_ffn_forward)

        if mm_impl is None:
            mm_impl = self._matmul_impl
        qmm = None if mm_impl == "dense" \
            else (lambda x, qt: self._qmm(x, qt, mm_impl))
        # a batch row that holds no request routes nothing (MoEFFN)
        live = None if lens is None else jnp.asarray(lens, jnp.int32) > 0
        env = {}
        new_caches = list(caches)
        mha_i = 0
        aux_cursor = 0
        rng = jax.random.PRNGKey(0)
        for i, n in enumerate(self._topo):
            if n.is_var:
                env[(id(n), 0)] = tokens if n.name == self._data_name \
                    else params[n.name]
                continue
            ins = [env[(id(inp), idx)] for inp, idx in n.inputs]
            name = n.spec.name
            with jax.named_scope(node_scope(n)):
                if name == "MultiHeadAttention":
                    out, new_caches[mha_i] = self._cached_mha(
                        n, ins, new_caches[mha_i], pos, valid_len, tp,
                        mm_impl=mm_impl, lens=lens, stats=stats)
                    mha_i += 1
                    env[(id(n), 0)] = out
                    continue
                if name == "CCAttention":
                    out, new_caches[mha_i] = self._cached_cca(
                        n, ins, new_caches[mha_i], pos, valid_len,
                        lens=lens, stats=stats)
                    mha_i += 1
                    env[(id(n), 0)] = out
                    continue
                if name in ("GatedAttention", "GatedDeltaNet",
                            "LatentAttention"):
                    if tp is not None:
                        raise MXNetError(
                            "Decoder: %s (node %r) has no tensor-"
                            "parallel form" % (name, n.name))
                    if name == "LatentAttention":
                        out, new_caches[mha_i] = self._cached_mla(
                            n, ins, new_caches[mha_i], pos, lens=lens,
                            stats=stats)
                    elif name == "GatedAttention":
                        out, new_caches[mha_i] = self._cached_gattn(
                            n, ins, new_caches[mha_i], pos, lens=lens,
                            stats=stats)
                    else:
                        out, new_caches[mha_i] = self._cached_gdn(
                            n, ins, new_caches[mha_i], pos, valid_len,
                            lens=lens, stats=stats)
                    mha_i += 1
                    env[(id(n), 0)] = out
                    continue
                if name == "PositionalEmbedding":
                    x, posp = ins
                    if jnp.ndim(pos) == 1:
                        # per-slot clocks (the batched slot walk): gather each
                        # batch row's positions from the table
                        idx = jnp.asarray(pos, jnp.int32)[:, None] \
                            + jnp.arange(x.shape[1], dtype=jnp.int32)
                        # (clipped: a dead slot's stale position may
                        # end the chunk past the table, and its row
                        # must stay finite)
                        env[(id(n), 0)] = x + jnp.take(posp, idx, axis=0,
                                                       mode="clip")
                        continue
                    # all-int32 indices: see _write_cache on the vmapped
                    # batching rule's strict index dtypes
                    rows = lax.dynamic_slice(
                        posp, (jnp.asarray(pos, jnp.int32), jnp.int32(0)),
                        (x.shape[1], posp.shape[1]))
                    env[(id(n), 0)] = x + rows[None]
                    continue
                if name == "FullyConnected" \
                        and isinstance(ins[1], QuantizedTensor):
                    xin = ins[0]
                    if n.params["flatten"]:
                        xin = xin.reshape(xin.shape[0], -1)
                    out = self._qmm(xin, ins[1], mm_impl)
                    if not n.params["no_bias"]:
                        out = out + ins[2]
                    env[(id(n), 0)] = out
                    continue
                if name == "Embedding" \
                        and isinstance(ins[1], QuantizedTensor):
                    idx = lax.stop_gradient(ins[0]).astype(jnp.int32)
                    env[(id(n), 0)] = embedding_rows(ins[1], idx)
                    continue
                if name == "MoEFFN" and (ep is not None or any(
                        isinstance(z, QuantizedTensor) for z in ins[1:])):
                    env[(id(n), 0)] = moe_ffn_forward(n.params, ins,
                                                      mm=qmm, ep=ep)
                    continue
                if name == "MoEFFN" and n.params["top_k"] > 0 \
                        and (stats is not None or live is not None):
                    seen = {}
                    env[(id(n), 0)] = moe_ffn_math(n.params, ins,
                                                   stats=seen, live=live)
                    if stats is not None:
                        for key, val in seen.items():
                            stats[key] = val + stats.get(key, 0)
                    continue
                if name == "BatchNorm" and ins[0].ndim >= 3:
                    # BatchNorm normalizes axis 1, which for rank>=3 LM data
                    # [B, T, E] is the TIME axis: a [B, 1, E] decode chunk
                    # would silently broadcast against length-T moving stats
                    # instead of behaving position-wise. Refuse loudly.
                    raise MXNetError(
                        "Decoder: BatchNorm node %r normalizes axis 1 of "
                        "its rank-%d input — the time axis under decoding, "
                        "so it is not position-wise; use LayerNorm for "
                        "sequence models (or BatchNorm on rank-2 [B, E] "
                        "data only)"
                        % (n.name, ins[0].ndim))
                n_aux = len(n.spec.aux_states(n.params))
                aux_in = aux[aux_cursor:aux_cursor + n_aux]
                aux_cursor += n_aux
                outs, _ = n.spec.forward(n.params, ins, aux_in, False,
                                         jax.random.fold_in(rng, i))
                for j, o in enumerate(outs):
                    env[(id(n), j)] = o
        head, idx = self._heads[0]
        return env[(id(head), idx)], new_caches

    # -- slot-addressed forms (serving engine) --------------------------
    # The continuous-batching engine (mxnet_tpu/serving/) runs ONE
    # persistent cache of S slots in which every slot sits at its own
    # position. These helpers re-express _run and the cache read/write
    # in slot-addressed form so the engine's two compiled programs can
    # reuse the exact decode math above (quantized, windowed, GQA, rope
    # included) with zero duplication.

    def _run_slots(self, params, aux, caches, pos, tokens, tp=None,
                   mm_impl=None, ep=None, stats=None, lens=None):
        """Per-slot-position ``_run``: ``pos`` [S] int32 positions (one
        per cache slot), ``tokens`` [S, C] → (logits [S, C, V], updated
        caches).

        Unless a cached node is a windowed ring (``_slots_batched``)
        this is ONE batched walk with the position VECTOR:
        position-wise ops see [S, C, E] directly, cache writes scatter
        per slot, and an attention node's read (MultiHeadAttention's
        and CCAttention's alike) is the bounded one (the Pallas kernel
        of ops/pallas_kernels.py) that fetches only the rows
        ``[0, lens)`` of each slot — ``lens`` [S] int32, default
        ``pos + C``; the serving engine's programs hand 0 for a slot
        that holds no request, whose stale rows are then not read at
        all and whose logits (finite) the caller discards
        (doc/serving.md "The decode read").
        ``stats`` is filled there (see ``_run``): only this one walk
        sees every slot's token, so only here can experts be counted
        once per step.

        A windowed ring's read is written for one position, so a graph
        that holds one takes the ``vmap`` over the slot axis — each
        lane is a b=1 ``_run`` at its own traced position, cache
        writes become per-slot scatters and masks follow each slot's
        own clock.

        ``tp`` (``(axis_name, degree)``, optional): the call is
        running inside the serving engine's tensor-parallel shard_map
        and ``caches`` are this shard's kv-head slice — see ``_run``.
        Each shard runs the bounded read against its LOCAL cache
        shard — it is handed the shard's own lanes and local kv-head
        count — and the per-attention-node all-gather rebuilds the
        head output (doc/serving.md "Tensor-parallel serving")."""
        if self._slots_batched:
            return self._run(params, aux, caches,
                             jnp.asarray(pos, jnp.int32), tokens,
                             tp=tp, mm_impl=mm_impl, ep=ep, stats=stats,
                             lens=lens)

        def one(slot_caches, p, t):
            # vmap hands each lane the slot's cache WITHOUT its leading
            # axis; _run wants b=1 buffers — re-add and strip it
            sub = jax.tree_util.tree_map(lambda c: c[None], slot_caches)
            logits, sub = self._run(params, aux, sub, p, t[None],
                                    tp=tp, mm_impl=mm_impl, ep=ep)
            return logits[0], jax.tree_util.tree_map(
                lambda c: c[0], sub)

        return jax.vmap(one, in_axes=(0, 0, 0))(caches, pos, tokens)

    @staticmethod
    def slot_slice(caches, slot):
        """View one cache slot (a traced index) as a b=1 cache — the
        read half of slot addressing; pair with :meth:`slot_update`."""
        return jax.tree_util.tree_map(
            lambda c: lax.dynamic_slice_in_dim(c, slot, 1, axis=0),
            caches)

    @staticmethod
    def slot_update(caches, slot, sub):
        """Write a b=1 cache back into ``slot`` of the full S-slot
        cache (the write half of slot addressing)."""
        return jax.tree_util.tree_map(
            lambda full, s: lax.dynamic_update_slice_in_dim(
                full, s, slot, axis=0),
            caches, sub)

    def clear_window_positions(self, caches, only_if=None):
        """Reset the ring-position buffers of windowed attention nodes
        to -1 (= never written). Slot REUSE needs this: a recycled
        slot's non-window rows are hidden by the ``key_pos <= pos``
        mask until overwritten, but ring slots are visible by their
        STORED positions, so a previous occupant's entries would leak
        into a new request's window. No-op for non-windowed caches.

        ``only_if`` (traced bool, optional): reset only when true —
        the serving engine's chunked prefill runs every chunk through
        ONE compiled program per bucket, and only the FIRST chunk of a
        recycled slot (traced ``start == 0``) may wipe the ring; later
        chunks must keep the positions their predecessors wrote."""
        out = []
        for n, entry in zip(self._cached, caches):
            if self._node_window(n):
                wiped = jnp.full_like(entry[-1], -1)
                if only_if is not None:
                    wiped = jnp.where(only_if, wiped, entry[-1])
                entry = entry[:-1] + (wiped,)
            out.append(entry)
        return out

    @staticmethod
    def slot_prefix_rows(caches, slot, length):
        """Read rows ``[0, length)`` of one cache slot as a b=1 tree:
        the read half of the serving engine's prefix-cache copy
        (``length`` is STATIC — the engine buckets it like prefill, so
        one program serves every copy of that bucket; ``slot`` is a
        traced int32 index). Rows past the true cached length ride
        along as junk — in the destination they sit at positions the
        ``key_pos <= pos`` mask hides until the suffix prefill
        overwrites them, the same argument that makes right-padded
        bucketed prefill exact. NOT valid for windowed ring caches
        (ring rows are addressed by wrapped absolute position, not by
        prefix row index) — the engine bypasses the prefix cache for
        windowed models."""
        def read(c):
            s = lax.dynamic_slice_in_dim(c, jnp.asarray(slot, jnp.int32),
                                         1, axis=0)
            return lax.slice_in_dim(s, 0, length, axis=1)

        return jax.tree_util.tree_map(read, caches)

    @staticmethod
    def slot_write_prefix_rows(caches, slot, rows):
        """Write a ``slot_prefix_rows`` result into rows ``[0, C)`` of
        ``slot`` (traced int32) — the write half of the slot-to-slot
        prefix copy. Index tuples are uniformly int32 (see
        ``_write_cache``)."""
        def write(full, r):
            idx = (jnp.asarray(slot, jnp.int32),) \
                + (jnp.int32(0),) * (full.ndim - 1)
            return lax.dynamic_update_slice(full, r.astype(full.dtype),
                                            idx)

        return jax.tree_util.tree_map(write, caches, rows)

    @staticmethod
    def slot_set_state(state, slot, values):
        """Poke ONE slot's per-slot scheduler state (the serving
        engine's ``(pos, tok, live, temp, key, eos, last)`` vectors)
        host-side: pull each vector to host numpy, overwrite row
        ``slot`` with the matching entry of ``values``, and return the
        new tuple. No compiled program and no traced op — this is the
        KV-handoff import's state write, which runs once per handed-off
        request (the engine re-places the result on device, replicated
        under tp). The source arrays are never mutated."""
        out = []
        for arr, v in zip(state, values):
            host = np.array(np.asarray(arr))
            host[slot] = v
            out.append(host)
        return tuple(out)

    def verify_step_slots(self, params, aux, caches, state, drafts,
                          dlen, tp=None, mm_impl=None, ep=None):
        """Speculative draft-and-verify decode step over all S slots
        (the serving engine's verify program — doc/serving.md
        "Speculative decoding").

        ``state`` is the engine's per-slot state tuple ``(pos, tok,
        live, temp, keys, eos, last)``; ``drafts`` [S, K] int32 are
        proposed continuations of each slot's head token ``tok``;
        ``dlen`` [S] int32 how many of them are real (0 = no draft —
        the slot rides along and emits exactly its plain-decode
        token). Returns ``(caches, state2, out)`` with ``out``
        [K+1, S]: row i is the i-th token emitted this step per slot,
        -1 where none.

        One chunked run of the target scores all K drafted positions
        (the multi-token cache append): the chunk ``[tok, d_1..d_K]``
        is written at positions ``[pos, pos+K]`` and each position's
        logits give the target's OWN next-token choice there — greedy
        argmax, or for ``temp > 0`` the categorical draw keyed
        ``fold_in(key, position)``, the exact (seed, position)
        identity plain decode uses. Token i is emitted iff every
        earlier emitted token matched its draft and was not terminal;
        the first mismatch emits the target's corrected token and
        stops. Every emitted token is therefore the target's own
        choice at its position — byte-identical to plain decode by
        construction, drafts only change how many arrive per dispatch.

        Rejected-position cache rows: the chunk write covers
        ``[pos, pos+K]`` but only ``[pos, pos+e-1]`` hold real tokens
        afterwards (e = tokens emitted). The junk tail is provably
        harmless — it sits at positions STRICTLY ABOVE the slot's new
        head, every read masks keys to ``key_pos <= query_pos``, and
        every later step's write covers its read range first — the
        same overwrite-or-masked discipline as right-padded bucketed
        prefill and recycled-slot reuse. NOT ring-safe: a windowed
        ring wraps the junk onto live rows, so the engine refuses
        speculation for windowed decoders (prefix-cache precedent)."""
        pos, tok, live, temp, keys, eos, last = state
        k = drafts.shape[1]
        chunk = jnp.concatenate(
            [tok[:, None], drafts.astype(jnp.int32)], axis=1)
        logits, caches = self._run_slots(
            params, aux, caches, pos, chunk, tp=tp, mm_impl=mm_impl,
            ep=ep, lens=jnp.where(live, pos + (k + 1), 0))  # [S,K+1,V]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def with_sampling(_):
            t = jnp.where(temp > 0.0, temp, jnp.float32(1.0))

            def draw(key, p0, rows):
                def one(i, row):
                    return jax.random.categorical(
                        jax.random.fold_in(key, p0 + i + 1), row)

                return jax.vmap(one)(jnp.arange(k + 1, dtype=jnp.int32),
                                     rows)

            sampled = jax.vmap(draw)(
                keys, pos,
                logits.astype(jnp.float32) / t[:, None, None]
            ).astype(jnp.int32)
            return jnp.where(temp[:, None] > 0.0, sampled, greedy)

        # all-greedy rounds skip the per-position fold_in+categorical
        # (same lax.cond reasoning as the engine's plain decode step)
        nxt = lax.cond(jnp.any(temp > 0.0), with_sampling,
                       lambda _: greedy, None)              # [S, K+1]

        emit = live                       # token 0 = plain-step output
        outs = []
        e = jnp.zeros_like(pos)
        tok2 = tok
        done_any = jnp.zeros_like(live)
        for i in range(k + 1):
            tki = nxt[:, i]
            done_i = (tki == eos) | (pos + i + 1 >= last)
            outs.append(jnp.where(emit, tki, jnp.int32(-1)))
            e = e + emit.astype(jnp.int32)
            tok2 = jnp.where(emit, tki, tok2)
            done_any = done_any | (emit & done_i)
            if i < k:
                matched = (i < dlen) & (tki == drafts[:, i])
                emit = emit & matched & ~done_i
        state2 = (pos + e, tok2, live & ~done_any, temp, keys, eos,
                  last)
        return caches, state2, jnp.stack(outs)              # [K+1, S]

    def draft_propose_slots(self, params, aux, caches, pos, catchup,
                            clen, k, tp=None, mm_impl=None, ep=None,
                            live=None):
        """Greedy k-token proposal from a DRAFT model sharing the
        slot-paged layout (the serving engine's draft program —
        ``InferenceEngine(draft="model")``).

        Two phases in one program: (1) catch up — ``catchup`` [S, W]
        holds each slot's real tokens the draft cache has not seen yet
        (``clen`` [S] in [1, W] of them valid; pad rows write
        junk-above-head, healed by the next catch-up's overwrite, the
        same discipline as ``verify_step_slots``), written at
        positions ``[pos, pos+clen)``; (2) propose — from the last
        valid position's logits, scan k-1 greedy single-token steps.
        Returns ``(caches, drafts [S, k])``. Greedy always: for
        sampled requests the target's verify still gates acceptance
        against ITS sample, the draft just matches less often.
        ``live`` ([S] bool, optional): the slots that hold a request;
        the others' rows are not read (``_run_slots``'s ``lens``)."""
        pos = jnp.asarray(pos, jnp.int32)
        if live is None:
            live = jnp.ones(pos.shape, bool)

        def lens(p, c):
            return jnp.where(live, p + c, 0)

        logits, caches = self._run_slots(
            params, aux, caches, pos, catchup, tp=tp, mm_impl=mm_impl,
            ep=ep, lens=lens(pos, catchup.shape[1]))        # [S,W,V]
        idx = jnp.clip(clen - 1, 0, catchup.shape[1] - 1)
        lastlog = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1)[:, 0]       # [S, V]
        d1 = jnp.argmax(lastlog, axis=-1).astype(jnp.int32)
        pos2 = pos + clen

        def body(carry, _):
            caches, p, t = carry
            lg, caches = self._run_slots(params, aux, caches, p,
                                         t[:, None], tp=tp,
                                         mm_impl=mm_impl, ep=ep,
                                         lens=lens(p, 1))
            nx = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
            return (caches, p + 1, nx), nx

        (caches, _, _), rest = lax.scan(body, (caches, pos2, d1), None,
                                        length=k - 1)       # [k-1, S]
        drafts = jnp.concatenate([d1[None], rest], axis=0)
        return caches, drafts.T                             # [S, k]

    @staticmethod
    def buffers_ready(tree):
        """True when every dispatched device buffer in ``tree`` has
        materialized — a NON-blocking readiness probe (leaves without
        ``is_ready`` count as ready). The serving engine's round
        watchdog polls this instead of letting ``np.asarray`` block
        forever on a wedged dispatch: a bounded host-side wait is what
        turns "the device hung" from a silent `serve_forever` freeze
        into a typed, recoverable error (doc/serving.md robustness).
        Purely host-side — no device op, no sync, no compilation."""
        for leaf in jax.tree_util.tree_leaves(tree):
            ready = getattr(leaf, "is_ready", None)
            if ready is not None and not ready():
                return False
        return True

    # -- user API -------------------------------------------------------
    @staticmethod
    def clone_cache(caches):
        """Deep-copy cache buffers — needed to BRANCH from one prefix,
        because prefill/step DONATE their cache argument (see below)."""
        return jax.tree_util.tree_map(jnp.copy, caches)

    def prefill(self, caches, tokens):
        """Process a [B, P] prompt chunk from position 0; returns
        (logits [B, P, V], caches).

        The input ``caches`` are DONATED to the compiled step (the
        per-token update writes in place — no cache-sized copy per
        step) and are invalid afterwards; always continue with the
        RETURNED caches, and ``clone_cache`` first to keep a branch
        point alive."""
        tokens = jnp.asarray(tokens).astype(jnp.int32)
        if tokens.shape[1] > self.max_len:
            raise MXNetError(
                "Decoder: prompt length %d exceeds max_len %d"
                % (tokens.shape[1], self.max_len))
        return self._step_jit(self._params, self._aux, caches, 0, tokens)

    def step(self, caches, pos, token):
        """One token per sequence: token [B] at position ``pos`` →
        (logits [B, V], caches). Donates ``caches`` like ``prefill``."""
        if not 0 <= pos < self.max_len:
            # dynamic_update_slice would silently clamp an out-of-range
            # start, overwriting the LAST cache slot; fail loudly instead
            raise MXNetError(
                "Decoder: step position %d outside the cache [0, %d)"
                % (pos, self.max_len))
        logits, caches = self._step_jit(
            self._params, self._aux, caches, pos,
            jnp.asarray(token).astype(jnp.int32)[:, None])
        return logits[:, 0], caches

    def generate(self, prompt, num_steps, rng=None, temperature=0.0,
                 return_cache=False):
        """Greedy (``temperature=0``) or sampled continuation.

        prompt: [B, P] token ids. Returns [B, P + num_steps] int32 —
        prompt followed by generated ids — or ``(tokens, caches)`` with
        ``return_cache=True``. The returned caches hold K/V through
        position ``P + num_steps - 1`` (the last returned token's slot);
        to continue, RE-step that last token at its own position —

            logits, caches = dec.step(caches, P + num_steps - 1,
                                      tokens[:, -1])

        — which rewrites its K/V slot with identical values (idempotent)
        and yields the logits for the next position; from there loop
        ``step`` forward as usual (pinned by
        ``tests/test_decode.py::test_generate_resume``). The decode loop
        is ONE compiled ``lax.scan`` program; cache buffers are donated
        through it.

        Compiled-program cache (``_gen_jit``): ``temperature`` is a
        TRACED scalar operand — sweeping it never recompiles (a
        ``lax.cond`` picks argmax vs categorical at run time, so
        greedy runs do not execute the sampling math and stay
        bit-identical to the old greedy-only program). The remaining
        cache keys are
        genuinely SHAPE-keyed and must stay: ``generate`` compiles one
        program per ``(batch, prompt_len, num_steps)`` — each changes
        the traced array shapes or the scan trip count — and
        ``beam_search`` per ``(batch, prompt_len, num_steps,
        beam_size, eos_id, length_penalty)`` (beam folds into the
        batch shape; eos/length_penalty alter the traced graph
        structure). Serving traffic with varying prompt lengths should
        use ``mxnet_tpu.serving.InferenceEngine``, whose bucketed
        programs bound the compile count by design (doc/serving.md).
        """
        prompt = jnp.asarray(prompt).astype(jnp.int32)
        b, p = prompt.shape
        if p + num_steps > self.max_len:
            raise MXNetError(
                "Decoder: prompt %d + steps %d exceeds max_len %d"
                % (p, num_steps, self.max_len))
        if rng is None:
            # advance an internal counter so repeated sampled calls
            # draw DIFFERENT continuations (pass rng explicitly for
            # reproducibility); greedy decoding ignores the key
            rng = jax.random.PRNGKey(self._auto_key)
            self._auto_key += 1
        key = (b, p, int(num_steps))
        if key not in self._gen_jit:
            self._gen_jit[key] = self._build_generate(p, int(num_steps))
        toks, caches = self._gen_jit[key](
            self._params, self._aux, self.init_cache(b), prompt, rng,
            jnp.float32(temperature))
        return (toks, caches) if return_cache else toks

    def _build_generate(self, p, num_steps):
        def pick(logits, rng, temperature):
            # lax.cond, not a select: greedy decoding must not PAY for
            # the categorical (threefry per step) it will never take —
            # the traced temperature only chooses the branch at run
            # time (the safe divisor guards the untaken-branch trace)
            def sampled(_):
                t = jnp.where(temperature > 0.0, temperature,
                              jnp.float32(1.0))
                return jax.random.categorical(
                    rng, logits.astype(jnp.float32) / t,
                    axis=-1).astype(jnp.int32)

            def greedy(_):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

            return lax.cond(temperature > 0.0, sampled, greedy, None)

        def gen(params, aux, caches, prompt, rng, temperature):
            logits, caches = self._run(params, aux, caches, 0, prompt)
            tok = pick(logits[:, -1], jax.random.fold_in(rng, 0),
                       temperature)

            def body(carry, i):
                caches, tok = carry
                logits, caches = self._run(params, aux, caches,
                                           p + i, tok[:, None])
                nxt = pick(logits[:, 0],
                           jax.random.fold_in(rng, i + 1), temperature)
                return (caches, nxt), tok

            (caches, _), toks = lax.scan(body, (caches, tok),
                                         jnp.arange(num_steps))
            return jnp.concatenate([prompt, toks.T], axis=1), caches

        return jax.jit(gen, donate_argnums=(2,))

    def beam_search(self, prompt, num_steps, beam_size, eos_id=None,
                    length_penalty=0.0):
        """Beam-search continuation: keep the ``beam_size`` highest
        log-probability continuations at every step.

        prompt: [B, P] token ids. Returns ``(sequences, scores)`` —
        sequences [B, beam_size, P + num_steps] int32 and scores
        [B, beam_size] f32 (sum of token log-probs; with
        ``length_penalty`` > 0 the ranking divides by
        length**length_penalty), both sorted best-first per batch row.

        ``eos_id``: beams that emit it are FINISHED — they stop
        expanding (their continuation slots fill with token 0 at no
        score cost) but keep competing on their final score. The whole
        search is ONE compiled ``lax.scan`` program; beams live as a
        folded [B*K] batch and cache rows are re-gathered to follow
        their parent beams each step.
        """
        prompt = jnp.asarray(prompt).astype(jnp.int32)
        b, p = prompt.shape
        k = int(beam_size)
        if k < 1:
            raise MXNetError("beam_size must be >= 1, got %d" % k)
        if num_steps < 1:
            raise MXNetError("beam_search needs num_steps >= 1")
        if p + num_steps > self.max_len:
            raise MXNetError(
                "Decoder: prompt %d + steps %d exceeds max_len %d"
                % (p, num_steps, self.max_len))
        key = (b, p, int(num_steps), k,
               -1 if eos_id is None else int(eos_id),
               float(length_penalty))
        if key not in self._gen_jit:
            self._gen_jit[key] = self._build_beam(
                p, int(num_steps), k,
                None if eos_id is None else int(eos_id),
                float(length_penalty))
        return self._gen_jit[key](self._params, self._aux,
                                  self.init_cache(b), prompt)

    def _build_beam(self, p, num_steps, k, eos_id, length_penalty):
        neg = jnp.float32(-1e30)

        def expand_logp(logits, finished):
            """[B*K] step logits -> [B, K, V] log-probs; finished beams
            may only 'emit' token 0 at zero cost (score frozen)."""
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            bk, v = logp.shape
            logp = logp.reshape(-1, k, v)
            frozen = jnp.full((v,), neg).at[0].set(0.0)
            return jnp.where(finished[:, :, None], frozen[None, None],
                             logp)

        def bs(params, aux, caches, prompt):
            B = prompt.shape[0]
            # prefill on [B], then expand every cache row into K beams
            logits, caches = self._run(params, aux, caches, 0, prompt)
            logp0 = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32), -1)   # [B, V]
            v = logp0.shape[-1]
            kk = min(k, v)
            scores, tok = lax.top_k(logp0, kk)           # [B, kk]
            if kk < k:  # beam wider than vocab: pad with dead beams
                pad = k - kk
                scores = jnp.concatenate(
                    [scores, jnp.full((B, pad), neg)], 1)
                tok = jnp.concatenate(
                    [tok, jnp.zeros((B, pad), tok.dtype)], 1)
            caches = jax.tree_util.tree_map(
                lambda c: jnp.repeat(c, k, axis=0), caches)
            seqs = jnp.zeros((B, k, p + num_steps), jnp.int32)
            seqs = seqs.at[:, :, :p].set(prompt[:, None, :])
            seqs = seqs.at[:, :, p].set(tok)
            finished = (tok == eos_id) if eos_id is not None \
                else jnp.zeros((B, k), bool)
            lengths = jnp.ones((B, k), jnp.float32)

            def body(carry, i):
                caches, seqs, scores, tok, finished, lengths = carry
                logits, caches = self._run(
                    params, aux, caches, p + i,
                    tok.reshape(B * k)[:, None])
                logp = expand_logp(logits[:, 0], finished)  # [B,K,V]
                total = scores[:, :, None] + logp
                scores2, idx = lax.top_k(total.reshape(B, k * v), k)
                parent = idx // v                        # [B, K]
                tok2 = (idx % v).astype(jnp.int32)
                rows = (jnp.arange(B)[:, None] * k + parent).reshape(-1)
                caches = jax.tree_util.tree_map(
                    lambda c: jnp.take(c, rows, axis=0), caches)
                seqs = jnp.take_along_axis(seqs, parent[..., None], 1)
                fin_p = jnp.take_along_axis(finished, parent, 1)
                len_p = jnp.take_along_axis(lengths, parent, 1)
                seqs = seqs.at[:, :, p + 1 + i].set(
                    jnp.where(fin_p, 0, tok2))
                fin2 = fin_p | ((tok2 == eos_id) if eos_id is not None
                                else False)
                len2 = len_p + (~fin_p)
                return (caches, seqs, scores2, tok2, fin2, len2), None

            carry = (caches, seqs, scores, tok, finished, lengths)
            if num_steps > 1:
                carry, _ = lax.scan(body, carry,
                                    jnp.arange(num_steps - 1))
            _, seqs, scores, _, _, lengths = carry
            rank = scores / jnp.power(lengths, length_penalty) \
                if length_penalty > 0.0 else scores
            order = jnp.argsort(-rank, axis=1)
            seqs = jnp.take_along_axis(seqs, order[..., None], 1)
            scores = jnp.take_along_axis(scores, order, 1)
            return seqs, scores

        # no donation: the [B]-row prefill caches are REPLACED by the
        # [B*K] beam caches, so the input buffers cannot be aliased
        return jax.jit(bs)
