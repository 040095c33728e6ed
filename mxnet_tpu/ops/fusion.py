"""Graph-level fused-kernel selection — the CreateOp-time cuDNN analogue.

The reference picks its fused/fast operator variants when the executor
creates ops: ``CreateOp`` returns the ``cudnn_*`` implementation when
cuDNN is available (``/root/reference/src/operator/convolution.cu``,
``cudnn_convolution-inl.h``, ``cudnn_batch_norm-inl.h``). The TPU
analogue happens at graph-walk time: ``FusionPlan`` statically matches
fusible chains in the topo order, and the shared ``eval_graph`` walk
(used by both the Executor and ``parallel.make_graph_fn``) executes each
chain as ONE Pallas kernel instead of separate XLA ops:

* ``FullyConnected -> Activation`` (relu/sigmoid/tanh) — train and eval;
  gradient via ``fused_linear``'s custom_vjp.
* ``Convolution -> BatchNorm [-> Activation(relu)]`` — eval: the
  moving-stats normalization folds into a per-channel scale/bias GEMM
  epilogue (``fused_conv_bn_act``). TRAIN, for 1x1/stride-1/no-pad
  convs: the conv runs as a Pallas GEMM whose epilogue also emits the
  per-channel sum/sum-of-squares of its own output from the VMEM
  accumulator (``matmul_stats``) — the batch-stats HBM read of the
  activation disappears, the remaining normalize+relu is one fused
  elementwise pass, and the moving-stat updates keep reference
  semantics. Opt-in via MXNET_PALLAS_CONVBN_TRAIN=1 (measured SLOWER
  end-to-end than the XLA path on this chip — see
  ``_convbn_train_enabled``) and requires MXNET_BN_STATS=auto.

Selection control: ``MXNET_PALLAS_FUSION=1`` forces on (any backend,
interpreter on CPU), ``=0`` forces off; default = on when running on
TPU. A chain is only fused when the intermediate outputs have exactly
one consumer and are not executor heads.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

__all__ = ["FusionPlan", "eval_graph", "node_scope"]


def fusion_enabled():
    flag = os.environ.get("MXNET_PALLAS_FUSION")
    if flag == "0":
        return False
    if flag == "1":
        return True
    return jax.default_backend() == "tpu"


def _convbn_train_enabled():
    """Train-time conv+BN stats-epilogue fusion. Requires the default
    one-pass BN stats contract (the exact modes are defined by their own
    pass structure over the activation, which the epilogue replaces).

    DEFAULT OFF: measured end-to-end at 212.5 ms/step vs the 99.9 ms
    XLA baseline on ResNet-50 b256 (doc/performance.md round-4 table) —
    a pallas_call pins its operand layout, so every fused conv pays two
    materialized NCHW<->[M,C] conversions, and XLA's native conv
    emitters outrun a general Pallas GEMM on these shapes. Kept behind
    MXNET_PALLAS_CONVBN_TRAIN=1 with full exact-value tests
    (test_fusion.py) as the measured-and-rejected record."""
    from .nn import _BN_STATS_MODE
    if _BN_STATS_MODE() != "auto":
        return False
    return os.environ.get("MXNET_PALLAS_CONVBN_TRAIN") == "1"


_FC_ACTS = ("relu", "sigmoid", "tanh")


class FusionPlan:
    """Static chain matching over a Symbol's topo order."""

    def __init__(self, topo, heads):
        # chains are keyed by their LAST node: by the time the walk
        # reaches it, every outside input of every chain member (e.g. the
        # BatchNorm gamma/beta variables, which topo-sort AFTER the conv)
        # is in env. Earlier members are 'covered' (skipped while active).
        self.chains = {}   # id(last_node) -> (kind, [nodes...])
        self.covered = {}  # id(earlier_node) -> id(last_node of its chain)
        self.aux_off = {}  # id(node) -> aux cursor at that node
        cursor = 0
        consumers = {}
        for n in topo:
            if n.is_var:
                continue
            self.aux_off[id(n)] = cursor
            cursor += len(n.spec.aux_states(n.params))
            for inp, idx in n.inputs:
                consumers.setdefault((id(inp), idx), []).append(n)
        head_set = {(id(h), i) for h, i in heads}

        def sole_consumer(node, idx=0):
            if (id(node), idx) in head_set:
                return None
            cs = consumers.get((id(node), idx), [])
            return cs[0] if len(cs) == 1 else None

        for n in topo:
            if n.is_var or id(n) in self.covered:
                continue
            op = n.spec.name
            if op == "FullyConnected":
                act = sole_consumer(n)
                if act is not None and act.spec.name == "Activation" \
                        and act.params.get("act_type") in _FC_ACTS \
                        and act.inputs[0][0] is n:
                    self.chains[id(act)] = ("fc_act", [n, act])
                    self.covered[id(n)] = id(act)
            elif op == "Convolution" and n.params.get("num_group", 1) == 1:
                bn = sole_consumer(n)
                if bn is None or bn.spec.name != "BatchNorm" \
                        or bn.inputs[0][0] is not n:
                    continue
                act = sole_consumer(bn)
                if act is not None and act.spec.name == "Activation" \
                        and act.params.get("act_type") == "relu" \
                        and act.inputs[0][0] is bn:
                    self.chains[id(act)] = ("conv_bn_relu", [n, bn, act])
                    self.covered[id(n)] = id(act)
                    self.covered[id(bn)] = id(act)
                else:
                    self.chains[id(bn)] = ("conv_bn", [n, bn])
                    self.covered[id(n)] = id(bn)

    @staticmethod
    def _conv_is_pointwise(p):
        return (tuple(p["kernel"]) == (1, 1)
                and tuple(p["stride"]) == (1, 1)
                and tuple(p["pad"]) == (0, 0)
                and tuple(p["dilate"]) == (1, 1))

    @classmethod
    def _active(cls, kind, nodes, is_train):
        if kind == "fc_act":
            return True
        if not is_train:
            # eval conv+bn folds the moving stats — always available
            return True
        # train conv+bn: the stats epilogue serves 1x1 convs under the
        # default one-pass BN contract (exact modes need their own
        # pass structure over the activation)
        return (_convbn_train_enabled()
                and cls._conv_is_pointwise(nodes[0].params))

    def is_covered(self, n, is_train):
        last_id = self.covered.get(id(n))
        if last_id is None:
            return False
        kind, nodes = self.chains[last_id]
        return self._active(kind, nodes, is_train)

    def execute(self, n, env, aux_vals, is_train, new_aux=None):
        """If ``n`` ends an active chain, compute the fused result into
        its env slot and return True. ``new_aux`` receives the BN
        moving-stat updates on the fused TRAIN path."""
        entry = self.chains.get(id(n))
        if entry is None or not self._active(entry[0], entry[1], is_train):
            return False
        kind, nodes = entry
        # the chain is named after its first node (the GEMM or the
        # convolution, where the time goes) and its fused kind
        with jax.named_scope("%s/%s" % (node_scope(nodes[0]), kind)):
            if is_train and kind in ("conv_bn", "conv_bn_relu"):
                return self._execute_conv_bn_train(entry, env, aux_vals,
                                                   new_aux)
            return self._execute_eval(entry, env, aux_vals)

    def _execute_conv_bn_train(self, entry, env, aux_vals, new_aux):
        """1x1 conv as a Pallas GEMM whose epilogue emits sum/sumsq of
        its own output (``matmul_stats``): train BatchNorm stats without
        the activation re-read. A conv bias is algebraically absorbed —
        BN subtracts the batch mean, so the bias cancels out of the
        normalized output (its gradient is exactly 0, matching the
        unfused path) and only shifts the recorded moving_mean."""
        from . import pallas_kernels as pk
        kind, nodes = entry
        conv, bn = nodes[0], nodes[1]
        p, bp = conv.params, bn.params
        ins = [env[(id(inp), idx)] for inp, idx in conv.inputs]
        x, w = ins[0], ins[1]
        gamma, beta = (env[(id(inp), idx)] for inp, idx in bn.inputs[1:3])
        if bp["fix_gamma"]:
            gamma = jnp.ones_like(gamma)
        nb, c, h, wd = x.shape
        nf = p["num_filter"]
        xm = jnp.transpose(x, (0, 2, 3, 1)).reshape(-1, c)
        y, s1, s2 = pk.matmul_stats(xm, w.reshape(nf, c).T)
        m = xm.shape[0]
        acc = jnp.promote_types(x.dtype, jnp.float32)
        mean = s1.astype(acc) / m
        var = jnp.maximum(s2.astype(acc) / m - jnp.square(mean), 0.0)
        inv = jax.lax.rsqrt(var + float(bp["eps"]))
        scale = (gamma.astype(acc) * inv).astype(y.dtype)
        shift = (beta.astype(acc)
                 - mean * gamma.astype(acc) * inv).astype(y.dtype)
        out = y * scale[None, :] + shift[None, :]
        if kind == "conv_bn_relu":
            out = jnp.maximum(out, 0)
        env[(id(nodes[-1]), 0)] = \
            out.reshape(nb, h, wd, nf).transpose(0, 3, 1, 2)
        # moving-stat updates (reference momentum form); the absorbed
        # conv bias reappears in the recorded mean
        rec_mean = mean if p["no_bias"] else mean + ins[2].astype(acc)
        off = self.aux_off[id(bn)]
        mmean, mvar = aux_vals[off], aux_vals[off + 1]
        mom = bp["momentum"]
        new_aux[off] = (mom * mmean
                        + (1 - mom) * rec_mean.astype(mmean.dtype))
        new_aux[off + 1] = (mom * mvar
                            + (1 - mom) * var.astype(mvar.dtype))
        return True

    def _execute_eval(self, entry, env, aux_vals):
        from . import pallas_kernels as pk
        kind, nodes = entry
        ins = [env[(id(inp), idx)] for inp, idx in nodes[0].inputs]
        if kind == "fc_act":
            fc, act = nodes
            p = fc.params
            x = ins[0]
            orig_shape = x.shape
            if p["flatten"]:
                x = x.reshape(x.shape[0], -1)
            else:
                x = x.reshape(-1, x.shape[-1])
            b = ins[2] if not p["no_bias"] else \
                jnp.zeros((p["num_hidden"],), ins[1].dtype)
            out = pk.fused_linear(x, ins[1].T, b,
                                  act.params["act_type"])
            if not p["flatten"]:
                out = out.reshape(orig_shape[:-1] + (p["num_hidden"],))
            env[(id(act), 0)] = out
            return True
        # conv_bn / conv_bn_relu (eval: fold moving stats)
        conv, bn = nodes[0], nodes[1]
        p = conv.params
        bp = bn.params
        gamma, beta = (env[(id(inp), idx)] for inp, idx in bn.inputs[1:3])
        if bp["fix_gamma"]:
            gamma = jnp.ones_like(gamma)
        off = self.aux_off[id(bn)]
        mmean, mvar = aux_vals[off], aux_vals[off + 1]
        inv = gamma * jax.lax.rsqrt(mvar + bp["eps"])
        bias = beta - mmean * inv
        if not p["no_bias"]:
            bias = bias + ins[2] * inv  # conv bias folds through the BN
        out = pk.fused_conv_bn_act(
            ins[0], ins[1], inv, bias, stride=p["stride"], pad=p["pad"],
            dilate=p["dilate"],
            act="relu" if kind == "conv_bn_relu" else "linear")
        env[(id(nodes[-1]), 0)] = out
        return True


def node_scope(n):
    """``<OpType>/<node name>``: the name every operation a node lowers
    to carries in the compiled program's metadata (and so in the
    profiler's op view; JAX adds ``jvp(...)`` / ``transpose(jvp(...))``
    around it for forward and backward). doc/observability.md."""
    return "%s/%s" % (n.spec.name, n.name)


def eval_graph(topo, heads, arg_vals, aux_vals, is_train, rng, plan=None):
    """The shared topological walk (reference: per-node RunOps,
    ``graph_executor.cc:776-819``; here ONE trace → one XLA program).
    Every node's operations are traced under ``jax.named_scope(
    "<OpType>/<node name>")``: metadata only, no run-time cost.
    Returns (head_outs, new_aux, env)."""
    env = {}
    var_iter = iter(arg_vals)
    aux_cursor = 0
    new_aux = list(aux_vals)
    fuse = plan is not None and fusion_enabled()
    for i, n in enumerate(topo):
        if n.is_var:
            env[(id(n), 0)] = next(var_iter)
            continue
        n_aux = len(n.spec.aux_states(n.params))
        if fuse and plan.is_covered(n, is_train):
            # produced by a fused chain head; aux (BN moving stats) pass
            # through unchanged on eval paths, and the TRAIN conv+bn
            # chain head writes its BN aux updates into new_aux directly
            aux_cursor += n_aux
            continue
        if fuse and plan.execute(n, env, aux_vals, is_train, new_aux):
            aux_cursor += n_aux
            continue
        ins = [env[(id(inp), idx)] for inp, idx in n.inputs]
        aux_in = list(aux_vals[aux_cursor:aux_cursor + n_aux])
        node_rng = jax.random.fold_in(rng, i)
        with jax.named_scope(node_scope(n)):
            outs, aux_out = n.spec.forward(n.params, ins, aux_in,
                                           is_train, node_rng)
        for j, o in enumerate(outs):
            env[(id(n), j)] = o
        if n_aux:
            new_aux[aux_cursor:aux_cursor + n_aux] = list(aux_out)
        aux_cursor += n_aux
    outs = [env[(id(h), i)] for h, i in heads]
    return outs, new_aux, env
