"""Sequence/context-parallel training: ring attention under shard_map.

The long-context half of the parallel subsystem. ``ParallelTrainer``
shards the BATCH over ``dp`` and lets GSPMD place everything; that works
until a single sequence's activations no longer fit one chip. This
trainer shards the SEQUENCE axis over an ``sp`` mesh axis and runs the
whole train step inside ``shard_map``, so each device holds ``T/n``
positions and the only cross-device traffic is the K/V ring rotation
inside ``MultiHeadAttention(impl="ring")`` (parallel/ring.py) — the
blockwise/ring-attention recipe, with XLA overlapping the
``ppermute`` hops with block compute on ICI.

Gradient flow: ``jax.vjp`` inside shard_map differentiates through the
ring's ``ppermute`` (its transpose is the reverse rotation); per-shard
parameter gradients are then ``psum``'d over ``(dp, sp)`` for replicated
params, and over ``dp`` only for sequence-sharded params (e.g. the
learned positional embedding, whose rows live with their positions).

No reference counterpart (2015 predates sequence parallelism); this is
required TPU-scale machinery per SURVEY §5/§7.
"""
from __future__ import annotations

import re

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax import shard_map

from ..base import MXNetError
from .. import ndarray as nd
from .. import optimizer as opt_mod
from ..initializer import Uniform
from .graph import make_graph_fn
from .shard import P
from .optim import make_functional
from .trainer import _as_jnp

__all__ = ["SequenceParallelTrainer"]


class SequenceParallelTrainer:
    """Train a sequence model with the sequence axis sharded over ``sp``.

    Parameters
    ----------
    symbol : Symbol
        Loss-headed LM graph whose attention ops use ``impl="ring"``
        (e.g. ``models.get_transformer_lm(..., impl="ring")``). Must have
        no auxiliary states (transformers use LayerNorm, which has none).
    input_shapes : dict
        GLOBAL shapes: ``data`` [B, T] and the label [B, T]. B shards
        over ``dp``, T over ``sp``.
    mesh : Mesh with axes ``dp`` and ``sp``.
    seq_param_rules : list[(regex, PartitionSpec)]
        Params sharded WITH the sequence (first match wins); default
        ships the learned positional embedding ``pos_embed`` as
        ``P('sp', None)``. Everything else is replicated.
    """

    def __init__(self, symbol, input_shapes, mesh, optimizer="sgd",
                 optimizer_params=None, initializer=None, seed=0,
                 seq_param_rules=None, label_name="softmax_label"):
        if "sp" not in mesh.shape or "dp" not in mesh.shape:
            raise MXNetError("SequenceParallelTrainer: mesh needs axes "
                             "'dp' and 'sp', got %s" % (dict(mesh.shape),))
        if symbol.list_auxiliary_states():
            raise MXNetError("SequenceParallelTrainer: aux states are not "
                             "supported under shard_map")
        self.symbol = symbol
        self.mesh = mesh
        self.label_name = label_name
        self.input_shapes = {k: tuple(v) for k, v in input_shapes.items()}
        self.arg_names = symbol.list_arguments()
        self.param_names = [n for n in self.arg_names
                            if n not in self.input_shapes]
        arg_shapes, _, _ = symbol.infer_shape(**{
            k: self._local_shape(k, v) for k, v in self.input_shapes.items()})
        if arg_shapes is None:
            raise MXNetError("SequenceParallelTrainer: shape inference "
                             "failed")
        # param shapes are inferred from LOCAL input shapes; params are
        # either replicated (shape == global) or sequence-sharded (their
        # global shape scales with sp — pos_embed rows)
        self._local_arg_shapes = dict(zip(self.arg_names, arg_shapes))

        if seq_param_rules is None:
            seq_param_rules = [(r"pos_embed$", P("sp", None))]
        self._seq_rules = [(re.compile(pat), spec)
                           for pat, spec in seq_param_rules]

        batch = self.input_shapes["data"][0]
        seqlen = self.input_shapes["data"][1]
        self.global_batch = batch
        self.seq_len = seqlen
        if isinstance(optimizer, str):
            # multi_output LM gradients sum over batch AND positions;
            # default to per-token normalization (overridable)
            opt_kwargs = dict(optimizer_params or {})
            opt_kwargs.setdefault("rescale_grad", 1.0 / (batch * seqlen))
            optimizer = opt_mod.create(optimizer, **opt_kwargs)
        self.optimizer = optimizer
        self._opt_init, self._opt_update = make_functional(optimizer)
        self._initializer = initializer or Uniform(0.05)
        self._rng = jax.random.PRNGKey(seed)
        self._graph_fn = make_graph_fn(symbol)
        self.params = None
        self.opt_state = None
        self._t = 0
        self._jit_step = None

    # -- sharding helpers ------------------------------------------------
    def _param_spec(self, name):
        for pat, spec in self._seq_rules:
            if pat.search(name):
                return spec
        return P()

    def _local_shape(self, name, global_shape):
        """Global [B, T] -> local [B/dp, T/sp] for inputs."""
        dp = self.mesh.shape["dp"]
        sp = self.mesh.shape["sp"]
        s = list(global_shape)
        if s[0] % dp or (len(s) > 1 and s[1] % sp):
            raise MXNetError("global shape %s not divisible by mesh %s"
                             % (global_shape, dict(self.mesh.shape)))
        s[0] //= dp
        if len(s) > 1:
            s[1] //= sp
        return tuple(s)

    def _global_param_shape(self, name):
        """Undo the sp factor for sequence-sharded params."""
        spec = self._param_spec(name)
        shape = list(self._local_arg_shapes[name])
        for i, ax in enumerate(spec):
            if ax == "sp":
                shape[i] *= self.mesh.shape["sp"]
        return tuple(shape)

    # -- state -----------------------------------------------------------
    def init_params(self, arg_params=None):
        params = {}
        for name in self.param_names:
            shape = self._global_param_shape(name)
            if arg_params and name in arg_params:
                val = _as_jnp(arg_params[name])
                if tuple(val.shape) != shape:
                    raise MXNetError("param %s: shape %s != %s"
                                     % (name, val.shape, shape))
            else:
                arr = nd.zeros(shape)
                self._initializer(name, arr)
                val = arr._val
            sh = NamedSharding(self.mesh, self._param_spec(name))
            params[name] = jax.device_put(np.asarray(val), sh)
        with self.mesh:
            self.opt_state = jax.jit(lambda p: {
                k: self._opt_init(v) for k, v in p.items()})(params)
        self.params = params
        self._t = 0
        return self

    # -- the sharded step ------------------------------------------------
    def _build_step(self):
        graph_fn = self._graph_fn
        arg_names = self.arg_names
        param_names = self.param_names
        opt_update = self._opt_update
        spec_of = {n: self._param_spec(n) for n in param_names}
        data_spec = P("dp", "sp")
        base_rng = self._rng
        n_tokens = float(self.global_batch * self.seq_len)

        def local_step(params, opt_state, data, label, lr, t, rng):
            inputs = {"data": data, self.label_name: label}
            # decorrelate stochastic ops (dropout masks) across shards:
            # each (dp, sp) coordinate gets its own stream — but ONLY for
            # the forward. The optimizer gets the replicated `rng`:
            # stochastic optimizers (SGLD noise) must apply the SAME
            # update on every shard of a replicated param, or the
            # buffers silently diverge across devices.
            fwd_rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
            fwd_rng = jax.random.fold_in(fwd_rng, jax.lax.axis_index("sp"))

            def fwd(p):
                vals = [p[n] if n in p else inputs[n] for n in arg_names]
                outs, _ = graph_fn(vals, [], True, fwd_rng)
                return tuple(outs)

            outs, vjp_fn = jax.vjp(fwd, params)
            head_grads = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            (grads,) = vjp_fn(head_grads)
            new_params, new_state = {}, {}
            for name in param_names:
                g = grads[name]
                seq_sharded = "sp" in tuple(spec_of[name])
                axes = ("dp",) if seq_sharded else ("dp", "sp")
                g = jax.lax.psum(g, axes)
                if seq_sharded:
                    # shards hold DISTINCT rows — independent noise per
                    # shard is correct (and better mixing for SGLD)
                    upd_rng = jax.random.fold_in(
                        rng, jax.lax.axis_index("sp"))
                else:
                    upd_rng = rng  # replicated: identical noise everywhere
                w, s = opt_update(params[name], g, opt_state[name], lr, t,
                                  upd_rng)
                new_params[name] = w
                new_state[name] = s
            # global mean NLL per token (for logging)
            p_out = outs[0]  # [B_l, C, T_l] multi_output softmax
            lab = label.astype(jnp.int32)
            picked = jnp.take_along_axis(
                p_out, lab[:, None, :], axis=1)[:, 0, :]
            nll = jax.lax.psum(-jnp.log(picked + 1e-8).sum(),
                               ("dp", "sp")) / n_tokens
            return new_params, new_state, nll

        param_specs = {n: spec_of[n] for n in param_names}
        mapped = shard_map(
            local_step, mesh=self.mesh,
            in_specs=(param_specs, param_specs, data_spec, data_spec,
                      P(), P(), P()),
            out_specs=(param_specs, param_specs, P()),
            check_vma=False)

        def step(params, opt_state, data, label, lr, t):
            # fold the step counter in-program (no host dispatch per
            # step) and use the 1-based update count the functional
            # optimizers expect (Adam bias correction divides by
            # 1 - beta^t)
            t = t + 1
            rng = jax.random.fold_in(base_rng, t)
            return mapped(params, opt_state, data, label, lr, t, rng)

        return jax.jit(step, donate_argnums=(0, 1))

    def step(self, batch):
        """One global train step. batch: dict with GLOBAL 'data' and
        label arrays, host or device. Returns the mean NLL/token as a
        device scalar (reading it forces a sync — do so sparingly)."""
        if self.params is None:
            self.init_params()
        if self._jit_step is None:
            self._jit_step = self._build_step()
        data = jax.device_put(
            _as_jnp(batch["data"]),
            NamedSharding(self.mesh, P("dp", "sp")))
        label = jax.device_put(
            _as_jnp(batch[self.label_name]),
            NamedSharding(self.mesh, P("dp", "sp")))
        if self.optimizer.lr_scheduler is not None:
            lr = self.optimizer.lr_scheduler(self._t + 1)
        else:
            lr = self.optimizer.lr
        self.params, self.opt_state, nll = self._jit_step(
            self.params, self.opt_state, data, label,
            np.float32(lr), np.int32(self._t))
        self._t += 1
        return nll

    def get_params(self):
        return {n: nd.array(np.asarray(jax.device_get(v)))
                for n, v in self.params.items()}

    # -- sharded (per-process) checkpointing ---------------------------
    def save_sharded_checkpoint(self, prefix, step=None,
                                async_write=False):
        """Per-process shard files (parallel/checkpoint.py); includes
        optimizer state and the step counter. Call from ALL processes.
        ``async_write=True`` overlaps the file IO with training; call
        the returned finalize() before exiting/restoring."""
        from .checkpoint import save_sharded, flatten_train_state
        flat = flatten_train_state(self.params, self.opt_state)
        return save_sharded(prefix, flat,
                            step=self._t if step is None else step,
                            async_write=async_write)

    def restore_sharded_checkpoint(self, prefix):
        """Works on a freshly constructed trainer (no init_params
        needed): the state structure comes from the optimizer spec."""
        from .checkpoint import load_sharded, restore_opt_state
        flat, step, _ = load_sharded(prefix, self.mesh)
        self.params = {n: flat[n] for n in self.param_names}
        self.opt_state = restore_opt_state(flat, self.params,
                                           self._opt_init)
        self._t = step
        return self
