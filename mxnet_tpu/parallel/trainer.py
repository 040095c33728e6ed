"""ParallelTrainer: the fused, sharded training step.

TPU-native replacement for the reference's training machinery
(``python/mxnet/model.py:118-308`` `_train_multi_device` +
``executor_manager.py`` DataParallelExecutorManager + kvstore reductions):
one ``jax.jit``-compiled program per step computes forward, backward,
gradient aggregation, and the optimizer update, partitioned over a
``jax.sharding.Mesh``. The batch is sharded over the ``dp`` axis; params
are placed by ``ShardingRules`` (replicated for pure data parallel,
sharded over ``tp`` for tensor parallelism). XLA's SPMD partitioner
inserts the gradient all-reduce the reference implements by hand in
``src/kvstore/kvstore_local.h:135-235``.

Loss semantics match the symbolic Executor: head gradients are ones, and
loss ops (SoftmaxOutput etc.) define their own fused gradients that ignore
the head cotangent and *sum* over the batch — so the optimizer's
``rescale_grad=1/global_batch`` gives identical updates to the reference's
multi-device loop, bit-for-bit modulo reduction order.
"""
from __future__ import annotations

import collections
import functools
import logging
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import optimizer as opt_mod
from .. import metric as metric_mod
from .. import profiler
from .. import telemetry as tele
from ..initializer import Uniform
from ..ops.pallas_kernels import kernel_mesh
from .graph import make_graph_fn, integer_semantic_inputs
from .mesh import local_mesh
from .shard import ShardingRules, P
from .optim import make_functional

__all__ = ["ParallelTrainer"]

# pre-resolved telemetry handles (doc/observability.md "trainer"): the
# per-event cost on the hot step path is one flag check + one lock'd add
_TM_STEPS = tele.counter("train.steps")
_TM_STEP_MS = tele.histogram("train.step_ms")          # dispatch (+device
# time on backends where dispatch blocks, e.g. the cpu CI mesh)
_TM_INPUT_MS = tele.histogram("train.input_wait_ms")   # blocked-on-input
_TM_DEVICE_MS = tele.histogram("train.device_wait_ms")  # blocked-on-device
_TM_H2D_BYTES = tele.counter("train.h2d_bytes")
_TM_COMPILES = tele.counter("train.compiles")


def _as_jnp(v):
    if isinstance(v, NDArray):
        return v._val
    return jnp.asarray(v)


class _StagedStream:
    """Depth-k host→device staging over a DataIter for the fused train
    loops: batch i+1 is pulled from the iterator and its ``device_put``
    (async dispatch, sharded over the data axis) runs while batch i's
    step executes — so the step stream never blocks on the h2d edge.
    Yields ``(data_batch, device_batch)`` pairs; iteration ends at the
    iterator's epoch end like the iterator itself would, and batches
    staged before an ``epoch_size`` break are served when iteration
    resumes (none are dropped). ``reset()`` forwards to the iterator
    and discards now-stale staged batches.

    Thin adapter over the unified ``io.StagedStream`` depth-k helper
    (inline mode — the same machinery behind ``DevicePrefetchIter``
    and the serving engine's prompt stager)."""

    def __init__(self, trainer, data, data_names, label_names, depth=2):
        from ..io import StagedStream

        names = (list(data_names), list(label_names))

        def place(dbatch):
            data_names_, label_names_ = names
            batch = dict(zip(data_names_, dbatch.data))
            batch.update(zip(label_names_, dbatch.label))
            return dbatch, trainer._stage_batch(batch, "staged fit")

        self._stream = StagedStream(data, place=place, depth=depth)

    def reset(self):
        self._stream.reset()

    def __iter__(self):
        return self

    def __next__(self):
        # blocked-on-input: everything the consumer thread waits on for
        # the next staged batch (decode pool, host collate, h2d
        # dispatch). Epoch ends (StopIteration) are not a wait sample.
        with tele.span("io.input_wait", cat="io", hist=_TM_INPUT_MS) as sp:
            try:
                return self._stream.next()
            except StopIteration:
                sp.drop()
                raise


class ParallelTrainer:
    """Compile a Symbol into a sharded train/eval step over a mesh.

    Parameters
    ----------
    symbol : Symbol
        Loss-headed graph (e.g. SoftmaxOutput head), as for FeedForward.
    input_shapes : dict name -> shape
        GLOBAL (unsharded) shapes of data/label inputs, batch first.
    optimizer : str or Optimizer
        If a string, created with ``rescale_grad=1/global_batch`` like
        FeedForward.fit (reference model.py:456-465).
    mesh : jax.sharding.Mesh, default: 1-axis dp mesh over all devices.
    rules : ShardingRules, default: dp-shard data, replicate params.
    zero1 : bool
        Shard optimizer state over ``dp`` (ZeRO-1); same update math
        (equal to reduction-reassociation), state memory 1/dp per chip.
    fsdp : bool
        Shard the PARAMETERS themselves over ``dp`` (ZeRO-3/FSDP):
        every param whose rules leave it replicated is sharded along
        its first dp-divisible axis, and optimizer state follows the
        param shards (zero1 is implied). Expressed purely as
        in/out shardings — GSPMD derives the use-site all-gathers and
        the gradient reduce-scatter, so param + state + gradient
        memory are all 1/dp per chip at the cost of re-gathering
        weights each step. Composes with tp ``param_rules`` (params a
        rule already shards are left to the rule).
    grad_accum : int
        Split each step's batch into this many sequentially-scanned
        microbatches with one update on the summed gradients
        (activation memory of one microbatch).
    clip_grad_norm : float, optional
        Clip the GLOBAL gradient norm (over all parameters together, the
        transformer-training standard) to this value before the update,
        inside the compiled step. Distinct from the per-element
        ``clip_gradient`` the reference optimizers apply per weight.
    """

    def __init__(self, symbol, input_shapes, optimizer="sgd", mesh=None,
                 rules=None, initializer=None, seed=None, optimizer_params=None,
                 compute_dtype=None, remat=None, zero1=False, fsdp=False,
                 grad_accum=1, clip_grad_norm=None):
        self.symbol = symbol
        # Mixed precision: forward/backward in compute_dtype (bfloat16 —
        # native MXU input width, halves HBM traffic for activations),
        # while params/optimizer state stay float32 master copies. The
        # cast's vjp accumulates gradients back to f32. The reference has
        # no AMP (2015, fp32-only mshadow); on TPU bf16 is the idiomatic
        # default for the compute path.
        if compute_dtype is not None:
            compute_dtype = jnp.dtype(compute_dtype)
        self.compute_dtype = compute_dtype
        # Gradient mirroring -> rematerialization: the reference trades
        # activation memory for recompute behind MXNET_BACKWARD_DO_MIRROR
        # (static_graph.cc:400-436); the TPU analogue is jax.checkpoint
        # over the forward, so XLA recomputes activations in the backward.
        if remat is None:
            remat = os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0") == "1"
        self.remat = bool(remat)
        self.mesh = mesh if mesh is not None else local_mesh()
        self.rules = rules if rules is not None else ShardingRules(self.mesh)
        self.input_shapes = {k: tuple(v) for k, v in input_shapes.items()}

        arg_names = symbol.list_arguments()
        self.arg_names = arg_names
        self.param_names = [n for n in arg_names
                            if n not in self.input_shapes]
        self.aux_names = symbol.list_auxiliary_states()

        arg_shapes, out_shapes, aux_shapes = \
            symbol.infer_shape(**self.input_shapes)
        if arg_shapes is None:
            raise MXNetError("ParallelTrainer: cannot infer shapes from %s"
                             % (self.input_shapes,))
        self.arg_shapes = dict(zip(arg_names, arg_shapes))
        self.out_shapes = out_shapes
        self.aux_shapes = aux_shapes

        # optimizer ------------------------------------------------------
        batch_size = next(iter(self.input_shapes.values()))[0]
        self.global_batch = batch_size
        # gradient accumulation: the step's batch is split into
        # grad_accum microbatches scanned sequentially inside the SAME
        # compiled program (activation memory = one microbatch), with
        # ONE optimizer update on the summed gradients. Exactly equals
        # the full-batch step for per-example losses; BatchNorm models
        # see MICROBATCH statistics (the standard accumulation caveat).
        # The reference has no analogue; on TPU this is how memory-bound
        # models reach large effective batches.
        self.clip_grad_norm = (None if clip_grad_norm is None
                               else float(clip_grad_norm))
        if self.clip_grad_norm is not None and self.clip_grad_norm <= 0:
            raise MXNetError("clip_grad_norm must be positive, got %g"
                             % self.clip_grad_norm)
        self.grad_accum = int(grad_accum)
        if self.grad_accum < 1 or batch_size % self.grad_accum:
            raise MXNetError("grad_accum=%d must divide batch %d"
                             % (grad_accum, batch_size))
        if isinstance(optimizer, str):
            opt_kwargs = dict(optimizer_params or {})
            opt_kwargs.setdefault("rescale_grad", 1.0 / batch_size)
            optimizer = opt_mod.create(optimizer, **opt_kwargs)
        self.optimizer = optimizer
        self._opt_init, self._opt_update = make_functional(optimizer)

        # shardings ------------------------------------------------------
        self._param_sh = {n: self.rules.param_sharding(n, self.arg_shapes[n])
                          for n in self.param_names}
        self._data_sh = {n: self.rules.data_sharding(n, s)
                         for n, s in self.input_shapes.items()}
        self._repl = self.rules.replicated()
        # FSDP / ZeRO-3: the params themselves live dp-sharded. Like
        # zero1 this is sharding annotations only — no manual gather
        # code: jit's in/out shardings pin the param (and state) layout,
        # and GSPMD inserts the all-gather at each weight's use site in
        # the forward/backward and reduce-scatters its gradient back to
        # the shard for the (now shard-local) optimizer update. The
        # reference has no analogue (one GPU holds whole weights;
        # dist kvstore shards only the SERVER copy — kvstore_dist.h);
        # this is the TPU-idiomatic route to models larger than one
        # chip's HBM without pipeline stages.
        self.fsdp = bool(fsdp)
        if self.fsdp:
            if "dp" not in self.mesh.shape:
                raise MXNetError("fsdp=True needs a 'dp' mesh axis")
            from jax.sharding import NamedSharding
            dp = self.mesh.shape["dp"]
            for n in self.param_names:
                spec = self._param_sh[n].spec
                if spec is not None and any(ax is not None
                                            for ax in spec):
                    continue  # a tp/custom rule already shards this param
                # all-None specs (e.g. P(None, None) when a tp rule
                # didn't fit the mesh) are replicated in effect and
                # still get the 1/dp treatment — only a spec that
                # actually names a mesh axis opts a param out
                shape = self.arg_shapes[n]
                ax = next((i for i, d in enumerate(shape)
                           if d % dp == 0 and d >= dp), None)
                if ax is not None:
                    spec = [None] * len(shape)
                    spec[ax] = "dp"
                    self._param_sh[n] = NamedSharding(self.mesh,
                                                      P(*spec))
        # ZeRO-1: shard OPTIMIZER STATE over dp. Params stay replicated
        # (their sharding is unchanged), but momentum/Adam moments — the
        # 1-2x param-sized buffers — live 1/dp per chip. Expressed purely
        # as out_shardings: GSPMD derives the reduce-scatter of grads
        # into the state shards and the all-gather of updated params,
        # the ZeRO-1 dataflow, from the sharding constraints alone.
        # Numerics match the replicated trainer to float reassociation
        # (the reduce-scatter reorders the gradient sum) — same-math,
        # not bitwise.
        self.zero1 = bool(zero1)
        if self.zero1 and "dp" not in self.mesh.shape:
            raise MXNetError("zero1=True needs a 'dp' mesh axis")
        from jax.sharding import NamedSharding

        def dim0_sh(leaf):
            # by LEAF shape, not param shape: factored states
            # (AdaFactor) carry lower-rank moment leaves
            dp = self.mesh.shape["dp"]
            if leaf.shape and leaf.shape[0] % dp == 0:
                return NamedSharding(
                    self.mesh, P("dp", *([None] * (len(leaf.shape) - 1))))
            return self._repl  # tiny/odd leaves: replicate

        def state_sh(leaf, name):
            """Where one optimizer-state leaf of param ``name`` lives.
            Always STATED, never left to the compiler: an unspecified
            sharding makes the step program specific to the layout its
            first arguments happen to have — the freshly initialised
            state has one, the step's own output another, and the
            SECOND step compiles the whole program again."""
            if self.zero1 and not self.fsdp:
                return dim0_sh(leaf)
            # a leaf shaped like its param lives where the param does
            # (under fsdp: the param's dp shards, a shard-local update)
            if tuple(leaf.shape) == tuple(self.arg_shapes[name]):
                return self._param_sh[name]
            # lower-rank leaves (AdaFactor's factored moments): the
            # dim-0 rule under fsdp — GSPMD derives whatever gathers
            # their reconstruction needs — else replicated
            return dim0_sh(leaf) if self.fsdp else self._repl

        self._opt_sh = {}
        for n in self.param_names:
            template = jax.eval_shape(
                self._opt_init,
                jax.ShapeDtypeStruct(self.arg_shapes[n], jnp.float32))
            self._opt_sh[n] = jax.tree_util.tree_map(
                lambda leaf, _n=n: state_sh(leaf, _n), template)

        # state ----------------------------------------------------------
        # default Pallas fusion only on a single-device mesh: under
        # multi-device GSPMD a pallas_call has no sharding rule, so XLA
        # would all-gather fused operands (defeating tp/dp shardings);
        # MXNET_PALLAS_FUSION=1 still forces it on for measurement
        self._graph_fn = make_graph_fn(
            symbol, allow_fusion=self.mesh.devices.size == 1)
        # index-valued inputs (labels, embedding tokens) are exempt from
        # the compute_dtype cast: bf16 spaces integers 4 apart near
        # 1000, so casting them silently retargets ids above 256
        self._no_cast = (
            integer_semantic_inputs(symbol) & set(self.input_shapes)
            if self.compute_dtype is not None else set())
        self.params = None
        self.opt_state = None
        self.aux = None
        self._t = 0
        self._rng = jax.random.PRNGKey(
            np.random.randint(0, 2**31 - 1) if seed is None else seed)
        self._jit_step = None
        self._jit_multi = {}  # num_steps -> compiled scan-of-steps
        self._jit_eval = None
        self._h2d_batch_bytes = None  # telemetry: computed on first stage
        self._prog_registered = False  # program.* introspection, once
        if initializer is None:
            initializer = Uniform(0.01)
        self._initializer = initializer

    # ------------------------------------------------------------------
    @staticmethod
    def _place(val, sharding):
        """Place a host value with a sharding; works in multi-process runs
        where the sharding spans non-addressable devices (every process
        holds the full host value — the replicated-init convention)."""
        if jax.process_count() == 1:
            # device-side copy first when val is already a jax array:
            # device_put may alias the caller's buffer when the sharding
            # already matches, and the fused step DONATES params — donating
            # an aliased buffer would delete the user's array out from
            # under them. (A host round-trip would also work but costs a
            # d2h+h2d per parameter.)
            if isinstance(val, jax.Array):
                val = jnp.copy(val)
            return jax.device_put(val, sharding)
        val = np.asarray(val)
        return jax.make_array_from_callback(val.shape, sharding,
                                            lambda idx: val[idx])

    def init_params(self, arg_params=None, aux_params=None):
        """Initialize (or load) params and place them on the mesh."""
        params = {}
        for name in self.param_names:
            shape = self.arg_shapes[name]
            if arg_params and name in arg_params:
                val = _as_jnp(arg_params[name])
            else:
                arr = nd.zeros(shape)
                self._initializer(name, arr)
                val = arr._val
            params[name] = self._place(val, self._param_sh[name])
        aux = []
        for name, shape in zip(self.aux_names, self.aux_shapes):
            if aux_params and name in aux_params:
                val = _as_jnp(aux_params[name])
            else:
                arr = nd.zeros(shape)
                self._initializer(name, arr)
                val = arr._val
            aux.append(self._place(val, self._repl))
        with self.mesh:
            opt_state = jax.jit(
                lambda p: {k: self._opt_init(v) for k, v in p.items()},
                out_shardings=self._opt_sh)(params)
        self.params = params
        self.aux = aux
        self.opt_state = opt_state
        self._t = 0
        return self

    # ------------------------------------------------------------------
    def _cast_compute(self, v):
        if self.compute_dtype is not None and \
                jnp.issubdtype(v.dtype, jnp.floating):
            return v.astype(self.compute_dtype)
        return v

    def _grads_of(self, params, aux, batch, rng):
        """(grads, new_aux, outs) for one (micro)batch — the fused
        forward+backward with the loss-head cotangent convention."""
        cast = self._cast_compute

        def fwd(p):
            # cast INSIDE the differentiated fn: the cast's vjp upcasts
            # gradients back to the f32 master params. Index-valued
            # inputs (self._no_cast) keep their exact dtype.
            vals = [cast(p[n]) if n in p else
                    (batch[n] if n in self._no_cast else cast(batch[n]))
                    for n in self.arg_names]
            outs, new_aux = self._graph_fn(vals, list(aux), True, rng)
            return tuple(outs), tuple(new_aux)

        if self.remat:
            fwd = jax.checkpoint(fwd)
        # Pallas kernels in the graph partition themselves over the
        # trainer's mesh (GSPMD cannot partition a Mosaic kernel).
        # mx.grads / mx.clip / mx.optimizer name the three parts of the
        # step in the compiled program's metadata (doc/observability.md
        # "Scopes inside the compiled programs").
        with jax.named_scope("mx.grads"), kernel_mesh(self.mesh):
            outs, vjp_fn, new_aux = jax.vjp(fwd, params, has_aux=True)
            if self.compute_dtype is not None:
                # moving stats stay f32 across steps (stable jit
                # signature)
                new_aux = tuple(a.astype(o.dtype)
                                for a, o in zip(new_aux, aux))
            head_grads = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            (grads,) = vjp_fn(head_grads)
        return grads, new_aux, outs

    def _step_impl(self, params, opt_state, aux, batch, lr, t, rng_base):
        # fold the step counter into the key INSIDE the compiled program —
        # doing it eagerly in step() costs a host dispatch per step
        rng = jax.random.fold_in(rng_base, t)
        A = self.grad_accum
        if A == 1:
            grads, new_aux, outs = self._grads_of(params, aux, batch, rng)
        else:
            # scan microbatches: grads SUM (loss grads are batch-sums, so
            # summing microbatch grads equals the full-batch gradient);
            # aux (BN moving stats) chain through the scan sequentially
            micro = {k: v.reshape((A, v.shape[0] // A) + v.shape[1:])
                     for k, v in batch.items()}
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), dict(params))

            def body(carry, mb_in):
                g_acc, aux_c, i = carry
                mb_rng = jax.random.fold_in(rng, i)
                g, new_aux, outs = self._grads_of(params, list(aux_c),
                                                  mb_in, mb_rng)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc,
                    dict(g))
                return (g_acc, list(new_aux), i + 1), tuple(outs)

            (grads, new_aux, _), outs_stacked = lax.scan(
                body, (g0, list(aux), jnp.int32(0)), micro)
            # [A, mb, ...] -> [batch, ...] per head (batch-major order)
            outs = [o.reshape((o.shape[0] * o.shape[1],) + o.shape[2:])
                    for o in outs_stacked]
        if self.clip_grad_norm is not None:
            # global-norm clip across ALL params, inside the program:
            # f32 accumulation; psum-free (grads here are already the
            # full-batch gradient under dp sharding). The norm is
            # measured on the RESCALED gradient (rescale_grad = 1/batch
            # on the string path), so the threshold means "norm of the
            # mean gradient" as in standard transformer recipes.
            with jax.named_scope("mx.clip"):
                sq = sum(jnp.sum(jnp.square(grads[n].astype(jnp.float32)))
                         for n in self.param_names)
                gnorm = jnp.sqrt(sq) * self.optimizer.rescale_grad
                scale = jnp.minimum(1.0, self.clip_grad_norm
                                    / jnp.maximum(gnorm, 1e-12))
                grads = {n: (grads[n].astype(jnp.float32)
                             * scale).astype(grads[n].dtype)
                         for n in self.param_names}
        new_params, new_state = {}, {}
        with jax.named_scope("mx.optimizer"):
            for name in self.param_names:
                w, s = self._opt_update(params[name], grads[name],
                                        opt_state[name], lr, t, rng)
                new_params[name] = w
                new_state[name] = s
        return new_params, new_state, list(new_aux), list(outs)

    def _shape_key(self):
        """Stable signature of the inputs this trainer compiles for —
        the recompile discriminator surfaced on compile events."""
        return ",".join("%s:%s" % (k, "x".join(map(str, v)))
                        for k, v in sorted(self.input_shapes.items()))

    def _note_compile(self, kind, **extra):
        _TM_COMPILES.inc()
        tele.mark("train.compile", kind=kind, shapes=self._shape_key(),
                  **extra)

    def _build_step(self):
        self._note_compile("step")
        # aux states carry an EXPLICIT (replicated) sharding on both
        # sides: left unspecified, the program is compiled for whatever
        # sharding the first call's aux arrays happen to have, the
        # step's own aux outputs come back with another, and the
        # second step compiles the whole program again
        aux_sh = [self._repl] * len(self.aux_names)
        in_sh = (self._param_sh, self._opt_sh, aux_sh,
                 self._data_sh, self._repl, self._repl, self._repl)
        out_sh = (self._param_sh, self._opt_sh, aux_sh, None)
        return jax.jit(self._step_impl, in_shardings=in_sh,
                       out_shardings=out_sh,
                       donate_argnums=(0, 1, 2))

    def _build_eval(self):
        self._note_compile("eval")

        def run(params, aux, batch, rng):
            vals = [params[n] if n in params else batch[n]
                    for n in self.arg_names]
            with kernel_mesh(self.mesh):
                outs, _ = self._graph_fn(vals, list(aux), False, rng)
            return list(outs)
        in_sh = (self._param_sh, [self._repl] * len(self.aux_names),
                 self._data_sh, self._repl)
        return jax.jit(run, in_shardings=in_sh)

    def prefetch(self, batches, depth=2):
        """Double-buffered infeed: yield device-resident batches while
        the NEXT ones transfer (SURVEY hard part (f) — the reference
        overlaps IO with compute via its Prefetcher thread + async
        engine copies; here device_put dispatches asynchronously, so
        keeping `depth` batches in flight overlaps h2d with the step).

        ``batches``: any iterable of host batch dicts (e.g. a DataIter
        adapter). Use as::

            for dev_batch in trainer.prefetch(host_batches):
                trainer.step(dev_batch)
        """
        import collections
        depth = max(1, int(depth))

        queue = collections.deque()
        it = iter(batches)
        try:
            for _ in range(depth):
                queue.append(self._stage_batch(next(it), "prefetch"))
        except StopIteration:
            pass
        while queue:
            ready = queue.popleft()
            try:
                queue.append(self._stage_batch(next(it), "prefetch"))
            except StopIteration:
                pass
            yield ready

    def _stage_batch(self, batch, what):
        """``_shard_batch`` + EAGER device placement — the one staging
        primitive behind :meth:`prefetch` and ``_StagedStream``.
        ``_shard_batch`` leaves plain numpy untouched in single-process
        mode (deferring h2d to jit dispatch), which would make staging
        a no-op — force the transfer to start now. Except on the cpu
        backend: there is no transfer to overlap there, and the
        per-batch dispatch is pure overhead (the CI path), so jit
        places lazily."""
        out = self._shard_batch(batch, what)
        # bytes handed to the h2d edge (staged now, or lazily placed at
        # jit dispatch on the cpu backend — either way infeed traffic).
        # Computed once: batch geometry is fixed per trainer, and
        # jax.Array.nbytes costs ~12 µs per array — per-step that would
        # dwarf every other probe on this path
        if self._h2d_batch_bytes is None:
            self._h2d_batch_bytes = sum(getattr(v, "nbytes", 0)
                                        for v in out.values())
        _TM_H2D_BYTES.inc(self._h2d_batch_bytes)
        if jax.default_backend() == "cpu":
            return out
        return {k: (v if isinstance(v, jax.Array)
                    else jax.device_put(v, self._data_sh[k]))
                for k, v in out.items()}

    def staged_batches(self, data, data_names, label_names, depth=2):
        """Overlapped host→device staging of a DataIter for a train
        loop: returns a ``_StagedStream`` yielding ``(data_batch,
        device_batch)`` with batch i+1's transfer dispatched while i is
        consumed. Used by :meth:`fit` and ``FeedForward.fit``'s fused
        path; compose with ``ImageRecordIter(num_workers=N)`` so decode
        happens in pool workers and the h2d edge overlaps compute —
        the whole reference prefetcher stack (iter_prefetcher.h), TPU
        style."""
        return _StagedStream(self, data, data_names, label_names,
                             depth=depth)

    def _shard_batch(self, batch, what):
        """Place batch arrays onto the mesh (the h2d infeed edge).

        Single process: arrays are GLOBAL batches, resharded by device_put.
        Multi-process: each process passes its LOCAL slice of the global
        batch (the reference's per-worker ``num_parts/part_index`` data
        sharding) and the global array is assembled across processes.
        """
        out = {}
        multiproc = jax.process_count() > 1
        try:
            for k in self.input_shapes:
                v = batch[k]
                if isinstance(v, NDArray):
                    v = v._val
                if multiproc:
                    if isinstance(v, jax.Array):
                        # already a GLOBAL array (a staged/prefetched
                        # batch went through this very branch once) —
                        # np.asarray on it would try to fetch
                        # non-addressable shards and throw
                        out[k] = v
                    else:
                        out[k] = jax.make_array_from_process_local_data(
                            self._data_sh[k], np.asarray(v))
                elif isinstance(v, jax.Array):
                    # committed arrays must be resharded explicitly —
                    # unless already laid out right (a staged/prefetched
                    # batch): re-dispatching a device_put per step would
                    # tax the path staging exists to clear
                    try:
                        placed = v.sharding.is_equivalent_to(
                            self._data_sh[k], v.ndim)
                    except Exception:
                        placed = False
                    out[k] = v if placed \
                        else jax.device_put(v, self._data_sh[k])
                else:
                    # hand numpy straight to jit — in_shardings places it
                    # during dispatch, cheaper than an eager device_put
                    out[k] = v
        except KeyError as e:
            raise MXNetError("%s: missing input %s" % (what, e))
        return out

    # ------------------------------------------------------------------
    def step(self, batch):
        """One fused train step. ``batch``: dict of global arrays
        (numpy/NDArray/jax) keyed by input names. Returns outputs list."""
        if self.params is None:
            self.init_params()
        if self._jit_step is None:
            self._jit_step = self._build_step()
        batch = self._shard_batch(batch, "step")
        self._t += 1
        if self.optimizer.lr_scheduler is not None:
            lr = self.optimizer.lr_scheduler(self._t)
        else:
            lr = self.optimizer.lr
        # numpy scalars (not jnp) keep this dispatch-only — no eager
        # device ops on the host critical path; the telemetry probe is
        # one span (two perf_counter reads, one histogram add, one
        # profiler annotation: host-side, no sync), pinned < 2% by
        # bench.py's overhead arm
        with tele.span("train.step", hist=_TM_STEP_MS), self.mesh:
            self.params, self.opt_state, self.aux, outs = \
                self._jit_step(self.params, self.opt_state, self.aux,
                               batch, np.float32(lr),
                               np.int32(self._t), self._rng)
        _TM_STEPS.inc()
        if not self._prog_registered:
            # one-time: register the step program for program.* cost/
            # memory introspection (doc/observability.md). Post-call
            # arrays carry the avals the dispatch traced with (the
            # pre-call train state may be donated); the registry keeps
            # only ShapeDtypeStructs — nothing device-resident.
            self._prog_registered = True
            # eager: the cost gauges are captured NOW, while the step
            # is alive — FeedForward.fit drops its trainer right after
            # fitting, so a scrape-time collection would find a dead
            # weakref and no gauges. Worst case (aval lowering-cache
            # miss on exotic layouts) is one extra abstract trace,
            # paid once right after the first step's full XLA compile
            # — noise next to it.
            profiler.register_program(
                "train_step", self._jit_step,
                (self.params, self.opt_state, self.aux, batch,
                 np.float32(lr), np.int32(self._t), self._rng))
        return outs

    def _build_multi_step(self, num_steps):
        self._note_compile("multi_step", num_steps=num_steps)

        def run(params, opt_state, aux, batch, lrs, t0, rng_base):
            def body(carry, lr_i):
                p, s, a = carry
                lr, idx = lr_i
                p, s, a, outs = self._step_impl(p, s, list(a), batch,
                                                lr, t0 + 1 + idx,
                                                rng_base)
                return (p, s, a), None

            (p, s, a), _ = lax.scan(
                body, (params, opt_state, list(aux)),
                (lrs, jnp.arange(num_steps)))
            return p, s, list(a)

        aux_sh = [self._repl] * len(self.aux_names)
        in_sh = (self._param_sh, self._opt_sh, aux_sh, self._data_sh,
                 self._repl, self._repl, self._repl)
        out_sh = (self._param_sh, self._opt_sh, aux_sh)
        return jax.jit(run, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1, 2))

    def multi_step(self, batch, num_steps):
        """Run ``num_steps`` consecutive train steps on the SAME batch
        as ONE compiled program — a ``lax.scan`` over the fused step
        with donated params/optimizer-state/aux.

        Per-step host dispatch disappears entirely, which matters when
        dispatch dominates the step itself: small models, or profiling
        where only steady-state device time should count. The rng/step-counter/lr-schedule
        sequence matches ``num_steps`` calls of :meth:`step` exactly
        (pinned by ``test_parallel.py::test_multi_step_matches_steps``).
        Returns nothing; params advance in place (use ``get_params``).
        """
        if self.params is None:
            self.init_params()
        if num_steps not in self._jit_multi:
            self._jit_multi[num_steps] = self._build_multi_step(num_steps)
        batch = self._shard_batch(batch, "multi_step")
        sched = self.optimizer.lr_scheduler
        lrs = np.asarray(
            [sched(self._t + 1 + i) if sched is not None
             else self.optimizer.lr for i in range(num_steps)],
            np.float32)
        with self.mesh:
            self.params, self.opt_state, self.aux = \
                self._jit_multi[num_steps](
                    self.params, self.opt_state, self.aux, batch,
                    lrs, np.int32(self._t), self._rng)
        self._t += num_steps

    def forward(self, batch):
        """Inference forward (no aux update); returns outputs list."""
        if self.params is None:
            self.init_params()
        if self._jit_eval is None:
            self._jit_eval = self._build_eval()
        batch = self._shard_batch(batch, "forward")
        with self.mesh:
            return self._jit_eval(self.params, self.aux, batch, self._rng)

    def _device_metric_fns(self, kind="acc", top_k=1):
        """Cached (update, zero_state) for a device-side metric
        accumulator — compiled once per (kind, k), not per fit() call.

        ``kind``: "acc" (argmax match), "topk" (label within top-k
        scores), "ce" (summed -log p[label]; assumes the monitored
        output is a probability distribution, as the reference's
        CrossEntropy metric does), or "loss" (sum of the outputs
        themselves, for loss-emitting heads like SoftmaxCELoss; label
        unused, count = output size). State is a replicated
        (sum, count) pair; value = sum / count in every kind."""
        cache = getattr(self, "_jit_metric", None)
        if cache is None:
            cache = self._jit_metric = {}
        if (kind, top_k) in cache:
            return cache[(kind, top_k)]
        from jax.sharding import NamedSharding
        repl = NamedSharding(self.mesh, P())

        @functools.partial(jax.jit, out_shardings=repl)
        def _update(state, out, label):
            if kind == "loss":
                # loss-emitting heads (SoftmaxCELoss): the output IS
                # the per-example loss; label unused (may be a dummy)
                return (state[0] + jnp.sum(out.astype(jnp.float32)),
                        state[1] + jnp.float32(out.size))
            lab = label.astype(jnp.int32)
            if kind == "acc":
                ok = jnp.sum((jnp.argmax(out, axis=-1) == lab)
                             .astype(jnp.float32))
            elif kind == "topk":
                if out.shape[-1] <= int(top_k):
                    raise MXNetError(
                        "top-k accuracy with k=%d over %d classes is "
                        "constant 1.0 — use a smaller top_k"
                        % (int(top_k), out.shape[-1]))
                _, idx = jax.lax.top_k(out, int(top_k))
                ok = jnp.sum(jnp.any(idx == lab[..., None], axis=-1)
                             .astype(jnp.float32))
            elif kind == "ce":
                prob = jnp.take_along_axis(
                    out, lab.reshape(out.shape[:-1] + (1,)),
                    axis=-1)[..., 0]
                ok = jnp.sum(-jnp.log(jnp.maximum(
                    prob.astype(jnp.float32), 1e-30)))
            else:  # pragma: no cover
                raise MXNetError("unknown device metric %r" % (kind,))
            return state[0] + ok, state[1] + jnp.float32(label.size)

        def _zero_state():
            z = jax.device_put(np.float32(0), repl)
            return (z, z)

        cache[(kind, top_k)] = (_update, _zero_state)
        return cache[(kind, top_k)]

    # ------------------------------------------------------------------
    def fit(self, train_data, eval_data=None, eval_metric="acc",
            num_epoch=1, batch_end_callback=None, epoch_end_callback=None,
            logger=None, device_metric=False):
        """Epoch loop over a DataIter, mirroring FeedForward.fit's protocol
        (metrics, Speedometer-style callbacks) on the fused step.

        ``device_metric=True`` (accuracy only): the per-batch metric
        update runs as jitted device ops accumulating a (correct, total)
        pair — NO host synchronization inside the epoch, one scalar
        fetch at epoch end. This keeps the step stream fully async. Batch-end callbacks still see the
        metric object but its value only materializes at epoch end.
        """
        from ..model import BatchEndParam, _run_callbacks
        if logger is None:
            logger = logging
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        if device_metric:
            if isinstance(eval_metric, metric_mod.TopKAccuracy):
                dm_kind, dm_k = "topk", eval_metric.top_k
            elif isinstance(eval_metric, metric_mod.Accuracy):
                dm_kind, dm_k = "acc", 1
            elif isinstance(eval_metric, metric_mod.CrossEntropy):
                dm_kind, dm_k = "ce", 1
            elif isinstance(eval_metric, metric_mod.Loss):
                dm_kind, dm_k = "loss", 1
            else:
                raise MXNetError(
                    "device_metric=True supports accuracy, top-k "
                    "accuracy, cross-entropy and loss; got %r"
                    % (eval_metric.name,))
        data_names = [x[0] for x in train_data.provide_data]
        label_names = [x[0] for x in train_data.provide_label]
        if device_metric:
            _acc_update, _zero_state = self._device_metric_fns(
                dm_kind, dm_k)

        self.last_train_metric = None
        # staged stream: batch i+1's h2d transfer is dispatched while
        # step i runs — with ImageRecordIter(num_workers=N) upstream,
        # decode is in pool workers and this loop never blocks on input
        staged = self.staged_batches(train_data, data_names, label_names)
        for epoch in range(num_epoch):
            staged.reset()
            eval_metric.reset()
            acc_state = _zero_state() if device_metric else None
            tic = time.time()
            with tele.span("train.epoch", epoch=epoch):
                for nbatch, (dbatch, dev_batch) in enumerate(staged):
                    outs = self.step(dev_batch)
                    if device_metric:
                        if dm_kind == "loss":
                            # label unused by the accumulator — works for
                            # label-free loss heads (MakeLoss-style) too
                            lab = np.float32(0)
                        else:
                            # single-process: uncommitted host numpy, jit
                            # places it with the other operands. Multi-
                            # process: each process holds only its local
                            # label slice, so build the GLOBAL sharded array
                            # the same way step() does for data
                            lab = dbatch.label[0]
                            if isinstance(lab, NDArray):
                                lab = lab._val
                            lab = np.asarray(lab)
                            if jax.process_count() > 1:
                                lab = jax.make_array_from_process_local_data(
                                    self._data_sh[label_names[0]], lab)
                        with self.mesh:
                            acc_state = _acc_update(acc_state, outs[0], lab)
                        if dm_kind == "ce" and epoch == 0 and nbatch == 0 \
                                and jax.process_count() == 1:
                            # the CE accumulator assumes the monitored output
                            # is a probability distribution (the reference
                            # CrossEntropy metric's contract); a logits-
                            # output symbol silently yields garbage. One
                            # cheap first-batch host check catches that.
                            row = np.asarray(
                                outs[0][(0,) * (outs[0].ndim - 1)],
                                dtype=np.float64)
                            if not 0.9 <= float(row.sum()) <= 1.1:
                                logger.warning(
                                    "device_metric cross-entropy expects "
                                    "probability outputs (rows summing to "
                                    "1); the first output row sums to %.4g "
                                    "- the reported CE will be meaningless "
                                    "if the symbol emits raw logits.",
                                    float(row.sum()))
                    else:
                        # this fetch is where the host actually BLOCKS on
                        # the device finishing step nbatch
                        with tele.span("train.device_wait",
                                       hist=_TM_DEVICE_MS):
                            out_nds = [nd.array(np.asarray(o))
                                       for o in outs]
                        eval_metric.update(dbatch.label, out_nds)
                    if batch_end_callback is not None:
                        _run_callbacks(batch_end_callback, BatchEndParam(
                            epoch=epoch, nbatch=nbatch,
                            eval_metric=eval_metric, locals=locals()))
            if device_metric:
                msum, total = (float(acc_state[0]),
                               float(acc_state[1]))  # ONE host sync
                name, value = eval_metric.name, msum / max(total, 1.0)
            else:
                name, value = eval_metric.get()
            self.last_train_metric = (name, value)
            logger.info("Epoch[%d] Train-%s=%f time=%.3f", epoch,
                        name, value, time.time() - tic)
            if epoch_end_callback is not None:
                ap, xp = self.get_params()
                for cb in (epoch_end_callback
                           if isinstance(epoch_end_callback, list)
                           else [epoch_end_callback]):
                    cb(epoch, self.symbol, ap, xp)
            if eval_data is not None:
                eval_metric.reset()
                eval_data.reset()
                for dbatch in eval_data:
                    batch = dict(zip(data_names, dbatch.data))
                    batch.update(zip(label_names, dbatch.label))
                    outs = self.forward(batch)
                    out_nds = [nd.array(np.asarray(o)) for o in outs]
                    eval_metric.update(dbatch.label, out_nds)
                logger.info("Epoch[%d] Validation-%s=%f", epoch,
                            *eval_metric.get())
        return self

    # ------------------------------------------------------------------
    def _to_host(self, v):
        """Gather a (possibly cross-process sharded) array to host."""
        if not v.is_fully_replicated and jax.process_count() > 1:
            from jax.sharding import NamedSharding
            with self.mesh:
                v = jax.jit(lambda x: x,
                            out_shardings=NamedSharding(self.mesh, P()))(v)
        return np.asarray(v)

    def get_params(self):
        """Gathered host copies as (arg_params, aux_params) NDArray dicts —
        checkpoint-compatible with FeedForward/save_checkpoint."""
        arg_params = {n: nd.array(self._to_host(v))
                      for n, v in self.params.items()}
        aux_params = {n: nd.array(self._to_host(v))
                      for n, v in zip(self.aux_names, self.aux)}
        return arg_params, aux_params

    def set_params(self, arg_params, aux_params=None):
        return self.init_params(arg_params, aux_params)

    # -- sharded (per-process) checkpointing ---------------------------
    def save_sharded_checkpoint(self, prefix, step=None,
                                async_write=False):
        """Write params + optimizer state + aux as per-process shard
        files (parallel/checkpoint.py) — checkpointing for models that
        only exist sharded across the mesh. Call from ALL processes.
        With ``async_write=True`` the device snapshot happens now and
        the file IO overlaps subsequent steps; returns a finalize()
        callable to join the writer (no-op when synchronous)."""
        from .checkpoint import save_sharded, flatten_train_state
        flat = flatten_train_state(self.params, self.opt_state,
                                   self.aux_names, self.aux)
        return save_sharded(prefix, flat,
                            step=self._t if step is None else step,
                            async_write=async_write)

    def restore_sharded_checkpoint(self, prefix):
        """Inverse of :meth:`save_sharded_checkpoint`; restores params,
        optimizer state, aux, and the step counter in place. Works on a
        freshly constructed trainer (no init_params needed)."""
        from .checkpoint import load_sharded, restore_opt_state
        flat, step, _ = load_sharded(prefix, self.mesh)
        self.params = {n: flat[n] for n in self.param_names}
        self.opt_state = restore_opt_state(flat, self.params,
                                           self._opt_init)
        self.aux = [flat["aux/%s" % n] for n in self.aux_names]
        self._t = step
        return self

    def resume_sharded_checkpoint(self, prefix):
        """Crash-resume: restore from ``prefix`` if a COMPLETE sharded
        checkpoint exists there (manifest + every shard file), else
        leave the trainer untouched. Returns the restored step, or None
        when there was nothing to resume from — callers use it as the
        ``begin_epoch``/step offset of the continued run."""
        from .checkpoint import latest_step
        step = latest_step(prefix)
        if step is None:
            return None
        self.restore_sharded_checkpoint(prefix)
        return step

    # -- optimizer-state blobs (FeedForward-style checkpoints) ---------
    def get_optimizer_states(self):
        """Picklable host snapshot of optimizer state + step counter —
        the gather-to-host analogue of the sharded ``opt/`` blobs, saved
        by ``fit(checkpoint_prefix=...)`` next to the .params file.

        Call from ALL processes (like ``load_sharded``): when state is
        sharded (zero1/fsdp) the host gather is a collective, and a
        single process calling alone deadlocks in it."""
        blob = {"step": int(self._t), "opt": {},
                # the per-step dropout keys are fold_in(_rng, t): without
                # the base key a resumed run of a stochastic model draws
                # different masks than the uninterrupted one
                "rng": np.asarray(self._rng)}
        for name, st in self.opt_state.items():
            blob["opt"][name] = [np.asarray(self._to_host(leaf))
                                 for leaf in
                                 jax.tree_util.tree_leaves(st)]
        return blob

    def set_optimizer_states(self, blob):
        """Restore a :meth:`get_optimizer_states` snapshot onto an
        initialized trainer (``init_params`` first — the state STRUCTURE
        is rebuilt from the optimizer's init on the live params, the
        same eval_shape trick as ``checkpoint.restore_opt_state``)."""
        from .checkpoint import restore_opt_state
        flat = {}
        for name, param in self.params.items():
            n_leaves = len(jax.tree_util.tree_leaves(
                jax.eval_shape(self._opt_init, param)))
            vals = blob["opt"].get(name)
            if vals is None or len(vals) != n_leaves:
                raise MXNetError(
                    "set_optimizer_states: checkpoint state for %r does "
                    "not match this trainer's optimizer (saved %s "
                    "leaves, need %d) — resuming a run under a "
                    "different optimizer is not supported" %
                    (name, "no" if vals is None else len(vals),
                     n_leaves))
            # place like init_params does (the jit step's in_shardings
            # expect mesh-placed state; bare host arrays break
            # multi-process resume)
            shs = jax.tree_util.tree_leaves(self._opt_sh[name])
            flat.update({"opt/%s/%d" % (name, i): self._place(v, s)
                         for i, (v, s) in enumerate(zip(vals, shs))})
        self.opt_state = restore_opt_state(flat, self.params,
                                           self._opt_init)
        self._t = int(blob["step"])
        if blob.get("rng") is not None:  # pre-rng blobs leave _rng alone
            self._rng = jnp.asarray(blob["rng"])
