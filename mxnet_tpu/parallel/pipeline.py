"""Pipeline parallelism over the ``pp`` mesh axis.

The reference's only model parallelism is graph partitioning by the
``ctx_group`` attribute with automatic copy-node insertion between
devices (``/root/reference/src/symbol/graph_executor.cc:341-458``,
tested by ``tests/python/unittest/test_model_parallel.py``): each device
runs a different sub-graph serially. The TPU-native promotion of that
mechanism is an SPMD GPipe schedule driven by the SAME ``ctx_group``
attribute:

* ``partition_stages`` cuts a loss-headed Symbol into S stages from
  ``ctx_group="stageK"`` node attributes (the reference's graph-cut
  tags), validating that the cut is a chain with ONE boundary activation
  of a uniform shape between consecutive stages.
* ``PipelineTrainer`` compiles ONE program for the whole mesh: every
  device runs the same ``lax.fori_loop`` schedule; ``lax.switch`` on the
  stage index runs that device's sub-graph (stages may be UNEQUAL —
  different ops, different parameter counts — because each is its own
  switch branch), and activations advance one stage per tick via
  ``lax.ppermute`` over ICI neighbours.
* Microbatches stream through to fill the pipe: the default schedule is
  GPipe with bubble fraction (S-1)/(M+S-1) — documented, not hidden; the
  backward pass is ``jax.vjp`` THROUGH the schedule (the transpose of
  ``ppermute`` is the reverse rotation), so gradients drain the pipe in
  reverse order. ``schedule="1f1b"`` instead interleaves forward and
  backward EXPLICITLY (no vjp-through-the-loop): activation memory is
  bounded by the schedule depth (2S-1 in-flight microbatches per
  device) independent of M, so microbatch count can grow to amortize
  the bubble without growing memory — see
  ``_build_step_staged_1f1b``.

Parameter placement (``param_placement``):

* ``"stage"`` (default) — PER-STAGE placement, the memory-scalable
  form matching the reference's per-device parameter residency
  (``graph_executor.cc:341-458`` binds each sub-graph's arrays on its
  own device): every stage's parameters are flattened into one row of
  a ``[S, P_max]`` f32 buffer sharded over ``pp``, so each device
  physically holds ONLY its own stage's parameters and optimizer
  state (plus padding to the largest stage). Inside the compiled step
  each switch branch statically unflattens its stage's row — no
  gather, no replication; gradients arrive per-row from the vjp
  (psum over ``dp`` only). All shipped optimizers are elementwise
  over (weight, grad, state), so flat-row updates are bit-equivalent
  to per-name updates. Per-device parameter+optimizer HBM is
  ``P_max`` ≈ total/S for balanced cuts, instead of the total.
  Parameters BIGGER than an average stage (``pp_shard_min_size``,
  default auto = total/S; an LM's embedding is the canonical case)
  do NOT set ``P_max`` for everyone: they persist ZeRO-3-style as
  ``[S, size/S]`` chunks sharded over ``pp`` (optimizer state too),
  are all-gathered by the step at use time, and their gradients come
  back reduce-scattered through the all_gather's transpose — so a
  stage-0-heavy cut keeps per-device persistent memory ≈ total/S.
  ``partition_stages``-time imbalance of the remaining row-packed
  params warns with per-stage byte counts (``stage_param_bytes``).
* ``"replicated"`` — every device holds all parameters (the round-2
  form, kept for A/B): one SPMD program, non-taken switch branches
  contribute zero gradients, cross-stage psum reassembles them. Costs
  parameter HBM; useful when stages are tiny and the psum is cheaper
  than padding to ``P_max``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax import shard_map

from ..base import MXNetError
from .. import ndarray as nd
from .. import optimizer as opt_mod
from ..initializer import Uniform
from .shard import P
from .optim import make_functional
from .trainer import _as_jnp

__all__ = ["pipeline_spmd", "partition_stages", "PipelineTrainer"]


# ---------------------------------------------------------------------------
# legacy equal-shape helper (kept: dryrun/backward-compat surface)

def pipeline_spmd(stage_fn, stage_params, x_microbatches, axis_name="pp"):
    """Run a GPipe pipeline inside a ``shard_map`` over ``axis_name``
    with HOMOGENEOUS stages (one shared ``stage_fn``, per-stage params
    sharded over the axis). See ``PipelineTrainer`` for the
    heterogeneous Symbol-level form.

    stage_fn(params, x) -> y        shape-preserving across stages
    x_microbatches : [M, mb, ...]   microbatched input; stage 0 reads it
    returns        : [M, mb, ...]   valid on the LAST stage (zeros
                                    elsewhere); psum to broadcast.
    """
    S = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    n = int(S) if not hasattr(S, "aval") else None
    if n is None:
        raise ValueError("pipeline_spmd must run inside shard_map "
                         "(axis size must be static)")
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]

    state0 = jnp.zeros_like(x_microbatches[0])
    out0 = jnp.zeros_like(x_microbatches)

    def body(t, carry):
        state_in, out = carry
        mb = jnp.clip(t, 0, M - 1)
        x_first = lax.dynamic_index_in_dim(x_microbatches, mb,
                                           keepdims=False)
        x = jnp.where(idx == 0, x_first, state_in)
        y = stage_fn(stage_params, x)
        w = t - (n - 1)
        valid = (idx == n - 1) & (w >= 0) & (w < M)
        wclip = jnp.clip(w, 0, M - 1)
        written = lax.dynamic_update_index_in_dim(out, y, wclip, 0)
        out = jnp.where(valid, written, out)
        state_next = lax.ppermute(y, axis_name, perm_fwd)
        return state_next, out

    _, out = lax.fori_loop(0, M + n - 1, body, (state0, out0))
    return out


# ---------------------------------------------------------------------------
# Symbol-level stage partitioning (the reference's ctx_group cut)

def partition_stages(symbol, num_stages=None):
    """Cut a Symbol's topo order into stages from ``ctx_group`` attrs.

    Node attr ``ctx_group="stageK"`` assigns the node to stage K
    (reference: ``AttrScope(ctx_group=...)`` + ``group2ctx`` at bind).
    Untagged op nodes inherit the max stage of their inputs; variables
    belong to their (single-stage) consumers. Returns
    ``(stage_nodes, boundaries, stage_of)`` where ``boundaries[s]`` is
    the (node, idx) data entry crossing from stage s to s+1.
    """
    topo = symbol._topo()
    stage_of = {}
    for n in topo:
        if n.is_var:
            continue
        tag = n.attrs.get("ctx_group")
        if tag is not None:
            if not tag.startswith("stage"):
                raise MXNetError(
                    "pipeline: ctx_group %r is not 'stage<K>'" % tag)
            stage_of[id(n)] = int(tag[len("stage"):])
    if not stage_of:
        raise MXNetError(
            "pipeline: no ctx_group='stage<K>' attrs found — tag the "
            "symbol (e.g. models.get_transformer_lm(pipeline_stages=S), "
            "or AttrScope(ctx_group='stage0'), the reference's "
            "model-parallel mechanism)")

    # propagate: untagged op nodes inherit max input stage
    for n in topo:
        if n.is_var or id(n) in stage_of:
            continue
        in_stages = [stage_of.get(id(inp), 0) for inp, _ in n.inputs
                     if not inp.is_var]
        stage_of[id(n)] = max(in_stages) if in_stages else 0
    # variables join their consumers' stage
    for n in topo:
        if n.is_var:
            continue
        for inp, _ in n.inputs:
            if inp.is_var:
                s = stage_of[id(n)]
                prev = stage_of.get(id(inp))
                if prev is not None and prev != s:
                    raise MXNetError(
                        "pipeline: variable %s consumed by stages %d "
                        "and %d" % (inp.name, prev, s))
                stage_of[id(inp)] = s

    S = max(stage_of.values()) + 1
    if num_stages is not None and S != num_stages:
        raise MXNetError("pipeline: symbol has %d stages, mesh wants %d"
                         % (S, num_stages))
    stage_nodes = [[] for _ in range(S)]
    for n in topo:
        stage_nodes[stage_of[id(n)]].append(n)

    # boundary entries: edges from stage s to stage s+1 (chain only)
    boundaries = [None] * (S - 1)
    for n in topo:
        if n.is_var:
            continue
        s = stage_of[id(n)]
        for inp, idx in n.inputs:
            ps = stage_of[id(inp)]
            if ps == s or inp.is_var:
                continue
            if ps > s:
                raise MXNetError("pipeline: backward edge stage %d -> %d"
                                 % (ps, s))
            if ps != s - 1:
                raise MXNetError(
                    "pipeline: edge skips stages (%d -> %d); ctx_group "
                    "cuts must form a chain" % (ps, s))
            entry = (inp, idx)
            if boundaries[ps] is None:
                boundaries[ps] = entry
            elif boundaries[ps] != entry:
                raise MXNetError(
                    "pipeline: stage %d has multiple boundary "
                    "activations; exactly one tensor may cross each "
                    "cut" % ps)
    for s, b in enumerate(boundaries):
        if b is None:
            raise MXNetError("pipeline: no edge from stage %d to %d"
                             % (s, s + 1))
    return stage_nodes, boundaries, stage_of


class PipelineTrainer:
    """Train a ``ctx_group``-staged Symbol with GPipe over a ``pp`` mesh.

    Parameters
    ----------
    symbol : loss-headed Symbol with ``ctx_group='stage<K>'`` attrs
        (stage count must equal the mesh's ``pp`` size). Input data
        variables must be consumed by stage 0, labels by the last stage.
    input_shapes : dict of GLOBAL input shapes, batch-first.
    mesh : Mesh with a ``pp`` axis, optionally also ``dp`` — with both,
        the batch shards over ``dp`` replica groups and each group runs
        its own pipeline; gradients psum over (dp, pp).
    num_microbatches : each dp group's batch is split into M
        microbatches; GPipe bubble is (S-1)/(M+S-1).
    """

    def __init__(self, symbol, input_shapes, mesh, num_microbatches=None,
                 optimizer="sgd", optimizer_params=None, initializer=None,
                 seed=0, label_name="softmax_label",
                 param_placement="stage", remat=None,
                 pp_shard_min_size="auto", schedule="gpipe"):
        if "pp" not in mesh.shape:
            raise MXNetError("PipelineTrainer: mesh needs a 'pp' axis")
        if param_placement not in ("stage", "replicated"):
            raise MXNetError("param_placement must be 'stage' or "
                             "'replicated', got %r" % (param_placement,))
        if schedule not in ("gpipe", "1f1b"):
            raise MXNetError("schedule must be 'gpipe' or '1f1b', got %r"
                             % (schedule,))
        if schedule == "1f1b" and param_placement != "stage":
            raise MXNetError("schedule='1f1b' requires "
                             "param_placement='stage' (the activation-"
                             "bounded schedule accumulates per-stage "
                             "row gradients)")
        self.schedule = schedule
        self.param_placement = param_placement
        # remat=True checkpoints each stage branch: the backward
        # recomputes stage activations from the carried boundary instead
        # of keeping every microbatch's residuals across the whole GPipe
        # schedule — activation memory drops from O(M·stage) to
        # O(M·boundary) + one in-flight stage, the practical TPU answer
        # to 1F1B's memory motivation (the SCHEDULE stays GPipe: XLA
        # orders the recomputed backward wave for us). Default follows
        # MXNET_BACKWARD_DO_MIRROR like ParallelTrainer (the reference
        # knob, static_graph.cc:400-436).
        if remat is None:
            import os
            remat = os.environ.get("MXNET_BACKWARD_DO_MIRROR",
                                   "0") == "1"
        elif remat and schedule == "1f1b":
            import warnings
            warnings.warn("PipelineTrainer: remat is inherent to "
                          "schedule='1f1b' (the backward re-runs each "
                          "stage from its saved input); the flag has "
                          "no additional effect")
        self.remat = bool(remat)
        if symbol.list_auxiliary_states():
            raise MXNetError("PipelineTrainer: aux states unsupported "
                             "under the SPMD schedule")
        self.symbol = symbol
        self.mesh = mesh
        self.S = mesh.shape["pp"]
        self.dp = mesh.shape.get("dp", 1)
        self.label_name = label_name
        self.input_shapes = {k: tuple(v) for k, v in input_shapes.items()}
        batch = self.input_shapes["data"][0]
        self.M = num_microbatches or self.S
        if batch % (self.M * self.dp):
            raise MXNetError(
                "batch %d not divisible into %d microbatches x %d dp "
                "groups" % (batch, self.M, self.dp))
        self.mb = batch // (self.M * self.dp)
        self.global_batch = batch

        self.stage_nodes, self.boundaries, self.stage_of = \
            partition_stages(symbol, self.S)
        for h, _ in symbol._heads:
            if self.stage_of.get(id(h)) != self.S - 1:
                raise MXNetError(
                    "PipelineTrainer: head %r lives in stage %s, but "
                    "every output head must be computed by the LAST "
                    "stage (%d) — tag it (or what feeds it) with "
                    "ctx_group='stage%d'"
                    % (h.name, self.stage_of.get(id(h)), self.S - 1,
                       self.S - 1))

        self.arg_names = symbol.list_arguments()
        self.param_names = [n for n in self.arg_names
                            if n not in self.input_shapes]
        # shapes at MICROBATCH size (the per-tick compute unit)
        mb_shapes = {k: (self.mb,) + tuple(v[1:])
                     for k, v in self.input_shapes.items()}
        arg_shapes, out_shapes, _ = symbol.infer_shape(**mb_shapes)
        if arg_shapes is None:
            raise MXNetError("PipelineTrainer: shape inference failed")
        self.arg_shapes = dict(zip(self.arg_names, arg_shapes))
        self.out_shapes = [tuple(s) for s in out_shapes]
        self._mb_shapes = mb_shapes

        # boundary (uniform) activation shape — validated equal across cuts
        self._infer_boundary_meta()

        # input variables must sit at the pipe ends
        for n in symbol._topo():
            if not n.is_var or n.name not in self.input_shapes:
                continue
            s = self.stage_of.get(id(n), 0)
            if n.name == self.label_name:
                if s != self.S - 1:
                    raise MXNetError("pipeline: label %r consumed by "
                                     "stage %d, must be last stage"
                                     % (n.name, s))
            elif s != 0:
                raise MXNetError("pipeline: input %r consumed by stage "
                                 "%d, must be stage 0" % (n.name, s))

        # per-stage flat layout: stage s's params (topo order) packed
        # into one padded row of a [S, P_max] buffer sharded over pp.
        # Params BIGGER than an average stage (an LM's embedding table
        # is the canonical case: stage 0 would set P_max for everyone)
        # instead get ZeRO-3-style storage SHARDED over pp — each device
        # persists 1/S of the tensor (and of its optimizer state); the
        # owning stage all-gathers it at use time and the gradient
        # arrives back reduce-scattered. This keeps per-device param
        # memory near total/S for arbitrarily imbalanced cuts.
        all_params = []
        total = 0
        for n in symbol._topo():
            if not n.is_var or n.name not in self.param_names:
                continue
            shape = self.arg_shapes[n.name]
            size = int(np.prod(shape)) if shape else 1
            all_params.append((n, shape, size))
            total += size
        if pp_shard_min_size == "auto":
            # any single param above half an average stage would skew
            # P_max; the gather cost of sharding it is marginal
            pp_shard_min_size = max(1, total // (2 * self.S))
        self._flat_meta = [[] for _ in range(self.S)]
        self._big_meta = []  # (name, shape, size, padded, stage)
        sizes = [0] * self.S
        for n, shape, size in all_params:
            s = self.stage_of[id(n)]
            if (self.param_placement == "stage" and pp_shard_min_size
                    and size > pp_shard_min_size and self.S > 1):
                padded = -(-size // self.S) * self.S
                self._big_meta.append((n.name, shape, size, padded, s))
                continue
            self._flat_meta[s].append((n.name, shape, sizes[s], size))
            sizes[s] = sizes[s] + size
        self._stage_sizes = sizes
        self._pmax = max(sizes + [1])
        #: per-stage parameter bytes (row-packed + pp-sharded), for
        #: operators sizing a cut
        self.stage_param_bytes = [4 * sz for sz in sizes]
        for _, _, size, _, s in self._big_meta:
            self.stage_param_bytes[s] += 4 * size
        mean_sz = max(1.0, sum(sizes) / float(self.S))
        waste_bytes = 4.0 * (self._pmax - mean_sz)  # per-device padding
        if (self.param_placement == "stage"
                and self._pmax / mean_sz > 1.5
                and waste_bytes > 16384):
            import warnings
            warnings.warn(
                "PipelineTrainer: row-packed stage params are imbalanced "
                "(max %.0f vs mean %.0f elements; per-stage bytes %s): "
                "every device pays the max row. Re-cut the stages more "
                "evenly, or lower pp_shard_min_size so the heavy "
                "parameters take the pp-sharded path."
                % (self._pmax, mean_sz, self.stage_param_bytes))

        if isinstance(optimizer, str):
            okw = dict(optimizer_params or {})
            okw.setdefault("rescale_grad", 1.0 / batch)
            optimizer = opt_mod.create(optimizer, **okw)
        self.optimizer = optimizer
        self._opt_init, self._opt_update = make_functional(optimizer)
        self._initializer = initializer or Uniform(0.05)
        self._rng = jax.random.PRNGKey(seed)
        self.params = None
        self.opt_state = None
        self._t = 0
        self._jit_step = None

    # ------------------------------------------------------------------
    def _infer_boundary_meta(self):
        """Shapes of every node output at microbatch size (to fix the
        carried boundary shape and check uniformity)."""
        from ..ops.fusion import eval_graph
        topo = self.symbol._topo()
        heads = self.symbol._heads
        arg_vals = [jax.ShapeDtypeStruct(self.arg_shapes[n], jnp.float32)
                    for n in self.arg_names]

        def run(args):
            _, _, env = eval_graph(topo, heads, args, [], False,
                                   jax.random.PRNGKey(0), plan=None)
            return {k: v for k, v in env.items()}

        env = jax.eval_shape(run, arg_vals)
        shapes = set()
        self._boundary_dtype = jnp.float32
        for node, idx in self.boundaries:
            meta = env[(id(node), idx)]
            shapes.add(tuple(meta.shape))
            self._boundary_dtype = meta.dtype
        if len(shapes) != 1:
            raise MXNetError(
                "pipeline: boundary activations differ in shape (%s); "
                "the SPMD schedule carries ONE uniform tensor between "
                "stages — cut at equal-shape points" % (sorted(shapes),))
        self._boundary_shape = shapes.pop()

    # ------------------------------------------------------------------
    def _init_value(self, name, arg_params):
        if arg_params and name in arg_params:
            return np.asarray(_as_jnp(arg_params[name]))
        arr = nd.zeros(self.arg_shapes[name])
        self._initializer(name, arr)
        return np.asarray(arr._val)

    def init_params(self, arg_params=None):
        if self.param_placement == "stage":
            rows = np.zeros((self.S, self._pmax), np.float32)
            for s, meta in enumerate(self._flat_meta):
                for name, shape, off, size in meta:
                    val = self._init_value(name, arg_params)
                    if val.dtype != np.float32:
                        # the packed rows are f32; silently downcasting
                        # a non-f32 param would corrupt it (advisor r3)
                        raise MXNetError(
                            "param_placement='stage' packs f32 "
                            "parameters; %r is %s — use "
                            "param_placement='replicated'"
                            % (name, val.dtype))
                    rows[s, off:off + size] = val.ravel()
            row_sh = NamedSharding(self.mesh, P("pp"))
            big = {}
            for name, shape, size, padded, _s in self._big_meta:
                val = self._init_value(name, arg_params)
                if val.dtype != np.float32:
                    raise MXNetError(
                        "param_placement='stage' packs f32 parameters; "
                        "%r is %s — use param_placement='replicated'"
                        % (name, val.dtype))
                flat = np.zeros((padded,), np.float32)
                flat[:size] = val.ravel()
                big[name] = jax.device_put(
                    flat.reshape(self.S, padded // self.S), row_sh)
            self.params = {"rows": jax.device_put(rows, row_sh),
                           "big": big}
            struct = jax.eval_shape(self._opt_init_tree, self.params)
            out_sh = jax.tree.map(lambda _: row_sh, struct)
            with self.mesh:
                self.opt_state = jax.jit(
                    self._opt_init_tree,
                    out_shardings=out_sh)(self.params)
            self._t = 0
            return self
        params = {}
        for name in self.param_names:
            val = self._init_value(name, arg_params)
            params[name] = jax.device_put(
                val, NamedSharding(self.mesh, P()))
        with self.mesh:
            self.opt_state = jax.jit(lambda p: {
                k: self._opt_init(v) for k, v in p.items()})(params)
        self.params = params
        self._t = 0
        return self

    # ------------------------------------------------------------------
    def _make_branch(self, s, x_mb, label_mb, params, rng, is_train):
        """Branch fn for stage s: (state, t) -> (boundary_out, out_val).
        Stage 0 reads microbatch t from x_mb (ignoring state); the last
        stage reads label t-(S-1) and emits the head output."""
        nodes = self.stage_nodes[s]
        in_entry = None if s == 0 else self.boundaries[s - 1]
        out_entry = None if s == self.S - 1 else self.boundaries[s]
        heads = self.symbol._heads
        M, S = self.M, self.S

        def branch(state, t):
            env = {}
            if in_entry is not None:
                env[(id(in_entry[0]), in_entry[1])] = state
            mb_idx = jnp.clip(t - s, 0, M - 1)
            # pipe-fill/drain ticks process garbage microbatches whose
            # OUTPUT is masked — but loss ops inject gradients that
            # ignore the head cotangent (the reference loss contract),
            # so masking the output alone would let garbage ticks leak
            # spurious gradients. Gating the loss node's INPUT by the
            # validity flag zeroes the whole fused gradient chain on
            # invalid ticks (flag * dz == 0).
            tick_valid = ((t - s >= 0) & (t - s < M))
            for i, n in enumerate(nodes):
                if n.is_var:
                    if n.name in params:
                        env[(id(n), 0)] = params[n.name]
                    else:
                        # x_mb: dict of ALL non-label inputs keyed by
                        # name (a second data input gets its own array,
                        # never the tokens); label rides separately
                        src = label_mb if n.name == self.label_name \
                            else x_mb[n.name]
                        env[(id(n), 0)] = lax.dynamic_index_in_dim(
                            src, mb_idx, keepdims=False)
                    continue
                ins = [env[(id(inp), idx)] for inp, idx in n.inputs]
                if s == S - 1 and any(n is h for h, _ in heads):
                    ins[0] = ins[0] * tick_valid.astype(ins[0].dtype)
                node_rng = jax.random.fold_in(
                    jax.random.fold_in(rng, t), i + s * 10000)
                outs, _ = n.spec.forward(n.params, ins, [], is_train,
                                         node_rng)
                for j, o in enumerate(outs):
                    env[(id(n), j)] = o
            if s == S - 1:
                out_val = tuple(env[(id(h), j)] for h, j in heads)
                boundary = jnp.zeros(self._boundary_shape,
                                     self._boundary_dtype)
            else:
                out_val = tuple(jnp.zeros(os_, jnp.float32)
                                for os_ in self.out_shapes)
                boundary = env[(id(out_entry[0]), out_entry[1])]
            return boundary.astype(self._boundary_dtype), out_val

        return branch

    def _stage_param_dict(self, s, row, big_full=None):
        """Unflatten stage ``s``'s params from its flat row (static
        slices — resolved at trace time inside the switch branch),
        plus any pp-sharded big params owned by this stage (already
        all-gathered to full tensors by the caller)."""
        out = {name: row[off:off + size].reshape(shape)
               for name, shape, off, size in self._flat_meta[s]}
        if big_full:
            for name, shape, size, _padded, owner in self._big_meta:
                if owner == s:
                    out[name] = big_full[name][:size].reshape(shape)
        return out

    def _opt_init_tree(self, params):
        """Optimizer state matching the staged params pytree."""
        return {"rows": self._opt_init(params["rows"]),
                "big": {k: self._opt_init(v)
                        for k, v in params["big"].items()}}

    def _staged_specs(self):
        """shard_map in/out specs for the staged param/opt pytrees."""
        S = self.S
        row_spec = P("pp")
        param_struct = {
            "rows": jax.ShapeDtypeStruct((S, self._pmax), jnp.float32),
            "big": {name: jax.ShapeDtypeStruct((S, padded // S),
                                               jnp.float32)
                    for name, _sh, _sz, padded, _s in self._big_meta}}
        param_specs = jax.tree.map(lambda _: row_spec, param_struct)
        opt_specs = jax.tree.map(
            lambda _: row_spec,
            jax.eval_shape(self._opt_init_tree, param_struct))
        return param_specs, opt_specs

    def _staged_update(self, row, big_local, g_row, g_big, opt_state,
                       lr, t_opt, opt_rng):
        """Shared optimizer epilogue for the staged builders: update the
        local flat row and each pp-sharded big-param chunk, re-lifted to
        the leading length-1 shard dim shard_map expects."""
        local_opt = jax.tree.map(lambda a: a[0], opt_state)
        new_row, new_opt_rows = self._opt_update(
            row, g_row, local_opt["rows"], lr, t_opt, opt_rng)
        new_big, new_opt_big = {}, {}
        for ki, k in enumerate(sorted(big_local)):
            # stable per-param stream: fold by sorted index, NOT
            # hash(str) (PYTHONHASHSEED varies across processes)
            new_big[k], new_opt_big[k] = self._opt_update(
                big_local[k], g_big[k], local_opt["big"][k], lr,
                t_opt, jax.random.fold_in(opt_rng, 1 + ki))
        lift = lambda t: jax.tree.map(lambda a: a[None], t)
        return ({"rows": new_row[None],
                 "big": {k: v[None] for k, v in new_big.items()}},
                {"rows": lift(new_opt_rows),
                 "big": {k: lift(v) for k, v in new_opt_big.items()}})

    def _wrap_step(self, mapped):
        """Microbatch-reshape + jit wrapper shared by every builder."""
        def step(params, opt_state, data_dict, label, lr, t):
            t = t + 1  # 1-based update count (Adam bias correction)
            rng = jax.random.fold_in(self._rng, t)
            row = self.dp * self.mb
            data_mb = {k: v.reshape((self.M, row) + v.shape[1:])
                       for k, v in data_dict.items()}
            label_mb = label.reshape((self.M, row) + label.shape[1:])
            return mapped(params, opt_state, data_mb, label_mb, lr, t,
                          rng)
        return jax.jit(step, donate_argnums=(0, 1))

    def _build_step(self):
        if self.param_placement == "stage":
            if self.schedule == "1f1b":
                return self._build_step_staged_1f1b()
            return self._build_step_staged()
        S, M = self.S, self.M
        perm = [(i, (i + 1) % S) for i in range(S)]
        param_specs = {n: P() for n in self.param_names}
        data_names = [k for k in self.input_shapes
                      if k != self.label_name]
        has_dp = "dp" in self.mesh.shape
        # microbatch arrays are [M, dp*mb, ...]: dim 1 shards over dp
        batch_spec = P(None, "dp") if has_dp else P()
        grad_axes = ("dp", "pp") if has_dp else ("pp",)

        def local_step(params, opt_state, data_mb, label_mb, lr, t_opt,
                       rng):
            idx = lax.axis_index("pp")
            opt_rng = rng  # REPLICATED: stochastic optimizers (SGLD)
            # must apply identical noise to replicated params everywhere
            if has_dp:
                # decorrelate stochastic forward ops (dropout) across
                # dp replicas only
                rng = jax.random.fold_in(rng, lax.axis_index("dp"))

            def fwd(p):
                branches = [self._make_branch(s, data_mb, label_mb, p,
                                              rng, True)
                            for s in range(S)]
                if self.remat:
                    # prevent_cse=False: inside lax.scan the CSE hazard
                    # checkpoint guards against cannot occur, and the
                    # default optimization_barrier would pessimize the
                    # hot loop (jax.checkpoint docs)
                    branches = [jax.checkpoint(b, prevent_cse=False)
                                for b in branches]
                state0 = jnp.zeros(self._boundary_shape,
                                   self._boundary_dtype)
                out0 = tuple(jnp.zeros((M,) + os_, jnp.float32)
                             for os_ in self.out_shapes)

                def body(carry, t):
                    state, outs = carry
                    y, out_vals = lax.switch(idx, branches, state, t)
                    w = t - (S - 1)
                    valid = (idx == S - 1) & (w >= 0) & (w < M)
                    wc = jnp.clip(w, 0, M - 1)
                    outs = tuple(
                        jnp.where(valid,
                                  lax.dynamic_update_index_in_dim(
                                      o, v, wc, 0), o)
                        for o, v in zip(outs, out_vals))
                    state = lax.ppermute(y, "pp", perm)
                    return (state, outs), None

                # scan (not fori_loop): statically unrollable schedule
                # that reverse-differentiates — the vjp drains the pipe
                # backwards, the wave 1F1B schedules by hand
                (_, outs), _ = lax.scan(body, (state0, out0),
                                        jnp.arange(M + S - 1))
                # only the last stage wrote `outs`; broadcast to all
                return tuple(lax.psum(o, "pp") for o in outs)

            out, vjp_fn = jax.vjp(fwd, params)
            (grads,) = vjp_fn(tuple(jnp.ones_like(o) for o in out))
            new_params, new_state = {}, {}
            for name in self.param_names:
                # each param's gradient lives on its stage's device;
                # psum reassembles (other stages contribute zeros from
                # the non-taken switch branches); with dp, replicas'
                # batch-shard gradients sum in the same collective
                g = lax.psum(grads[name], grad_axes)
                w, st = self._opt_update(params[name], g,
                                         opt_state[name], lr, t_opt,
                                         opt_rng)
                new_params[name] = w
                new_state[name] = st
            return new_params, new_state, out

        mapped = shard_map(
            local_step, mesh=self.mesh,
            in_specs=(param_specs, param_specs,
                      {k: batch_spec for k in data_names}, batch_spec,
                      P(), P(), P()),
            out_specs=(param_specs, param_specs,
                       tuple(batch_spec for _ in self.out_shapes)),
            check_vma=False)
        # [B, ...] -> [M, dp*mb, ...]; dim 1 shards over dp
        return self._wrap_step(mapped)

    def _build_step_staged(self):
        """Per-stage placement: row-packed params/opt state are
        [S, P_max] rows sharded over ``pp``; each device computes with —
        and updates — only its own row. Gradients need no cross-stage
        psum (each row's cotangent IS its stage's gradient); with dp,
        replicas' rows sum over ``dp`` only.

        pp-sharded BIG params (``_big_meta``): persisted as
        [S, size/S] chunks (each device holds 1/S of the tensor and of
        its optimizer state), all-gathered over ``pp`` at use time; the
        all_gather's transpose delivers the gradient back
        reduce-scattered, so the chunk update is purely local."""
        S, M = self.S, self.M
        perm = [(i, (i + 1) % S) for i in range(S)]
        data_names = [k for k in self.input_shapes
                      if k != self.label_name]
        has_dp = "dp" in self.mesh.shape
        batch_spec = P(None, "dp") if has_dp else P()
        param_specs, opt_specs = self._staged_specs()

        def local_step(params, opt_state, data_mb, label_mb, lr, t_opt,
                       rng):
            idx = lax.axis_index("pp")
            # decorrelate stochastic optimizers (SGLD noise) across
            # stages — each device owns DIFFERENT params — but keep dp
            # replicas of the same stage identical (no dp fold)
            opt_rng = jax.random.fold_in(rng, idx)
            if has_dp:
                rng = jax.random.fold_in(rng, lax.axis_index("dp"))
            row = params["rows"][0]  # local pp-shard of [S, Pmax]
            big_local = {k: v[0] for k, v in params["big"].items()}

            def fwd(r, bl):
                # gather each pp-sharded big param to its full flat
                # value; only the owning stage's branch consumes it,
                # and the transpose (psum_scatter) hands back exactly
                # this device's chunk gradient
                big_full = {k: lax.all_gather(v, "pp", tiled=True)
                            for k, v in bl.items()}
                branches = [self._make_branch(
                    s, data_mb, label_mb,
                    self._stage_param_dict(s, r, big_full),
                    rng, True) for s in range(S)]
                if self.remat:
                    # prevent_cse=False: inside lax.scan the CSE hazard
                    # checkpoint guards against cannot occur, and the
                    # default optimization_barrier would pessimize the
                    # hot loop (jax.checkpoint docs)
                    branches = [jax.checkpoint(b, prevent_cse=False)
                                for b in branches]
                state0 = jnp.zeros(self._boundary_shape,
                                   self._boundary_dtype)
                out0 = tuple(jnp.zeros((M,) + os_, jnp.float32)
                             for os_ in self.out_shapes)

                def body(carry, t):
                    state, outs = carry
                    y, out_vals = lax.switch(idx, branches, state, t)
                    w = t - (S - 1)
                    valid = (idx == S - 1) & (w >= 0) & (w < M)
                    wc = jnp.clip(w, 0, M - 1)
                    outs = tuple(
                        jnp.where(valid,
                                  lax.dynamic_update_index_in_dim(
                                      o, v, wc, 0), o)
                        for o, v in zip(outs, out_vals))
                    state = lax.ppermute(y, "pp", perm)
                    return (state, outs), None

                (_, outs), _ = lax.scan(body, (state0, out0),
                                        jnp.arange(M + S - 1))
                return tuple(lax.psum(o, "pp") for o in outs)

            out, vjp_fn = jax.vjp(fwd, row, big_local)
            g_row, g_big = vjp_fn(tuple(jnp.ones_like(o) for o in out))
            if has_dp:
                g_row = lax.psum(g_row, "dp")
                g_big = jax.tree.map(lambda g: lax.psum(g, "dp"), g_big)
            new_params, new_opt = self._staged_update(
                row, big_local, g_row, g_big, opt_state, lr, t_opt,
                opt_rng)
            return new_params, new_opt, out

        mapped = shard_map(
            local_step, mesh=self.mesh,
            in_specs=(param_specs, opt_specs,
                      {k: batch_spec for k in data_names}, batch_spec,
                      P(), P(), P()),
            out_specs=(param_specs, opt_specs,
                       tuple(batch_spec for _ in self.out_shapes)),
            check_vma=False)
        return self._wrap_step(mapped)

    def _build_step_staged_1f1b(self):
        """Activation-bounded interleaved schedule (1F1B class,
        PipeDream-flush family — the reference has no pipeline at all,
        so this is a beat-the-reference feature; see GPipe docstring for
        the baseline schedule).

        GPipe differentiates through the whole ``lax.scan``, so the
        scan's reverse pass keeps one residual per TICK: O(M) live
        boundary activations per device — microbatch count buys bubble
        amortization at the price of activation memory. Here forward
        and backward are scheduled EXPLICITLY and nothing is ever
        differentiated through a loop:

        * tick ``t``: stage ``s`` runs the forward of microbatch
          ``t - s`` and then the backward of microbatch
          ``t - (2S-2-s)`` (cotangents arrive via the reverse
          ``ppermute`` ring exactly one stage per tick, the transposed
          wave of the forward schedule).
        * each device keeps only a ``[2S-1, boundary]`` ring buffer of
          its stage INPUTS; the backward re-runs the stage forward from
          the saved input under ``jax.vjp`` (per-stage recompute — the
          same trade GPipe-with-remat makes) with the SAME per-tick RNG
          folding, so dropout masks match the forward bit-for-bit.
        * per-stage gradients accumulate into the local flat row (and
          the full-size cotangent of each pp-sharded big param, handed
          back as this device's chunk by a final ``psum_scatter`` — the
          manual transpose of the gather in ``_build_step_staged``).

        In-flight activations per device are <= 2S-1 INDEPENDENT OF M
        (GPipe: M+S-1), so M — and with it the bubble fraction
        (S-1)/(M+S-1) — can grow without growing activation memory.
        Wall-clock pays (S-1) extra pipe ticks versus GPipe's unified
        reverse wave (M+2S-2 fwd+bwd ticks vs M+S-1 of each); the
        schedule is split into fwd-only / fwd+bwd / bwd-only phases so
        warmup and drain ticks don't execute the other half.
        ``remat`` is ignored: per-stage recompute is inherent.
        Exact-gradient equivalence with the GPipe path is pinned by
        ``test_parallel.py::test_pipeline_1f1b_matches_gpipe``."""
        S, M = self.S, self.M
        W = 2 * S - 1
        perm_f = [(i, (i + 1) % S) for i in range(S)]
        perm_b = [(i, (i - 1) % S) for i in range(S)]
        data_names = [k for k in self.input_shapes
                      if k != self.label_name]
        has_dp = "dp" in self.mesh.shape
        batch_spec = P(None, "dp") if has_dp else P()
        param_specs, opt_specs = self._staged_specs()

        def local_step(params, opt_state, data_mb, label_mb, lr, t_opt,
                       rng):
            idx = lax.axis_index("pp")
            opt_rng = jax.random.fold_in(rng, idx)
            if has_dp:
                rng = jax.random.fold_in(rng, lax.axis_index("dp"))
            row = params["rows"][0]
            big_local = {k: v[0] for k, v in params["big"].items()}
            big_full = {k: lax.all_gather(v, "pp", tiled=True)
                        for k, v in big_local.items()}

            def stage_f(s, r, bf, state, t):
                branch = self._make_branch(
                    s, data_mb, label_mb,
                    self._stage_param_dict(s, r, bf), rng, True)
                return branch(state, t)

            fwd_tick = [
                (lambda st, tt, s=s: stage_f(s, row, big_full, st, tt))
                for s in range(S)]

            def make_bwd(s):
                def bwd(saved_x, g_in, tt):
                    # tt is the tick this microbatch's FORWARD ran at
                    # (tt = mb + s), so the per-node RNG folding —
                    # dropout masks — replays identically
                    def f(r, bf, x):
                        return stage_f(s, r, bf, x, tt)
                    (y, outs), vjp_fn = jax.vjp(f, row, big_full,
                                                saved_x)
                    # loss heads ignore their cotangent (reference
                    # contract) and non-last stages emit constant-zero
                    # head slots, so ones is correct everywhere; the
                    # boundary cotangent rides the reverse ring
                    ct = (g_in.astype(y.dtype),
                          tuple(jnp.ones_like(o) for o in outs))
                    g_r, g_bf, g_x = vjp_fn(ct)
                    return g_x, g_r, g_bf
                return bwd

            bwd_tick = [make_bwd(s) for s in range(S)]

            def do_fwd(state_f, saved, outs, t):
                y, out_vals = lax.switch(idx, fwd_tick, state_f, t)
                # ring-buffer the input consumed this tick; mb index
                # t-idx < 0 / >= M writes garbage into a slot that is
                # provably re-written before any valid backward reads it
                slot = jnp.mod(t - idx, W)
                saved = lax.dynamic_update_index_in_dim(
                    saved, state_f.astype(saved.dtype), slot, 0)
                w = t - (S - 1)
                valid = (idx == S - 1) & (w >= 0) & (w < M)
                wc = jnp.clip(w, 0, M - 1)
                outs = tuple(
                    jnp.where(valid,
                              lax.dynamic_update_index_in_dim(
                                  o, v, wc, 0), o)
                    for o, v in zip(outs, out_vals))
                return lax.ppermute(y, "pp", perm_f), saved, outs

            def do_bwd(state_b, saved, g_row, g_big, t):
                b = t - (2 * S - 2 - idx)
                saved_x = lax.dynamic_index_in_dim(
                    saved, jnp.mod(b, W), 0, keepdims=False)
                g_x, g_r, g_bf = lax.switch(idx, bwd_tick, saved_x,
                                            state_b, b + idx)
                validb = (b >= 0) & (b < M)
                # where, not multiply: garbage ticks may produce inf
                g_row = g_row + jnp.where(validb, g_r,
                                          jnp.zeros_like(g_r))
                g_big = {k: g_big[k] + jnp.where(validb, g_bf[k],
                                                 jnp.zeros_like(g_bf[k]))
                         for k in g_big}
                return lax.ppermute(g_x, "pp", perm_b), g_row, g_big

            saved0 = jnp.zeros((W,) + self._boundary_shape,
                               self._boundary_dtype)
            state_f0 = jnp.zeros(self._boundary_shape,
                                 self._boundary_dtype)
            state_b0 = jnp.zeros(self._boundary_shape,
                                 self._boundary_dtype)
            g_row0 = jnp.zeros_like(row)
            g_big0 = {k: jnp.zeros_like(v) for k, v in big_full.items()}
            out0 = tuple(jnp.zeros((M,) + os_, jnp.float32)
                         for os_ in self.out_shapes)

            def bodyA(carry, t):  # warmup: forward only
                state_f, saved, outs = carry
                return do_fwd(state_f, saved, outs, t), None

            (state_f, saved, outs), _ = lax.scan(
                bodyA, (state_f0, saved0, out0), jnp.arange(S - 1))

            def bodyB(carry, t):  # steady state: one fwd then one bwd
                state_f, state_b, saved, g_row, g_big, outs = carry
                # fwd first: the LAST stage backwards the microbatch it
                # just forwarded in the same tick (classic 1F1B)
                state_f, saved, outs = do_fwd(state_f, saved, outs, t)
                state_b, g_row, g_big = do_bwd(state_b, saved, g_row,
                                               g_big, t)
                return (state_f, state_b, saved, g_row, g_big,
                        outs), None

            (state_f, state_b, saved, g_row, g_big, outs), _ = lax.scan(
                bodyB, (state_f, state_b0, saved, g_row0, g_big0, outs),
                jnp.arange(S - 1, M + S - 1))

            def bodyC(carry, t):  # drain: backward only
                state_b, saved, g_row, g_big = carry
                state_b, g_row, g_big = do_bwd(state_b, saved, g_row,
                                               g_big, t)
                return (state_b, saved, g_row, g_big), None

            (state_b, saved, g_row, g_big), _ = lax.scan(
                bodyC, (state_b, saved, g_row, g_big),
                jnp.arange(M + S - 1, M + 2 * S - 2))

            outs = tuple(lax.psum(o, "pp") for o in outs)
            # manual transpose of the big-param all_gather: sum the
            # full-size cotangents across pp and keep this device's
            # tile. Scatter BEFORE the dp reduction so the dp collective
            # moves 1/S of the bytes (the axes act on disjoint data, so
            # the order is mathematically free)
            g_big_local = {
                k: lax.psum_scatter(v, "pp", scatter_dimension=0,
                                    tiled=True)
                for k, v in g_big.items()}
            if has_dp:
                g_row = lax.psum(g_row, "dp")
                g_big_local = {k: lax.psum(v, "dp")
                               for k, v in g_big_local.items()}
            new_params, new_opt = self._staged_update(
                row, big_local, g_row, g_big_local, opt_state, lr,
                t_opt, opt_rng)
            return new_params, new_opt, outs

        mapped = shard_map(
            local_step, mesh=self.mesh,
            in_specs=(param_specs, opt_specs,
                      {k: batch_spec for k in data_names}, batch_spec,
                      P(), P(), P()),
            out_specs=(param_specs, opt_specs,
                       tuple(batch_spec for _ in self.out_shapes)),
            check_vma=False)
        return self._wrap_step(mapped)

    # ------------------------------------------------------------------
    def step(self, batch):
        """One pipelined train step on a GLOBAL batch dict. Returns the
        head output [B, ...] (microbatches re-flattened); a list when
        the symbol has multiple heads (every head's input is gated on
        fill/drain ticks, so none injects spurious gradients)."""
        if self.params is None:
            self.init_params()
        if self._jit_step is None:
            self._jit_step = self._build_step()
        data_dict = {k: _as_jnp(batch[k]) for k in self.input_shapes
                     if k != self.label_name}
        label = _as_jnp(batch[self.label_name])
        if self.optimizer.lr_scheduler is not None:
            lr = self.optimizer.lr_scheduler(self._t + 1)
        else:
            lr = self.optimizer.lr
        self.params, self.opt_state, outs = self._jit_step(
            self.params, self.opt_state, data_dict, label,
            np.float32(lr), np.int32(self._t))
        self._t += 1
        outs = [o.reshape((self.global_batch,) + tuple(o.shape[2:]))
                for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def get_params(self):
        if self.param_placement == "stage":
            tree = self.params
            if jax.process_count() > 1:
                with self.mesh:
                    tree = jax.jit(
                        lambda x: x,
                        out_shardings=jax.tree.map(
                            lambda _: NamedSharding(self.mesh, P()),
                            tree))(tree)
            rows = np.asarray(jax.device_get(tree["rows"]))
            out = {}
            for s, meta in enumerate(self._flat_meta):
                for name, shape, off, size in meta:
                    out[name] = nd.array(
                        rows[s, off:off + size].reshape(shape))
            for name, shape, size, _padded, _s in self._big_meta:
                flat = np.asarray(
                    jax.device_get(tree["big"][name])).ravel()
                out[name] = nd.array(flat[:size].reshape(shape))
            return out
        return {n: nd.array(np.asarray(jax.device_get(v)))
                for n, v in self.params.items()}
