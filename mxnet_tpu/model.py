"""FeedForward model: the high-level training API.

Parity: ``/root/reference/python/mxnet/model.py`` — ``FeedForward`` with
``fit`` (:681-767), ``_train_multi_device`` (:118-308, THE training loop),
kvstore selection heuristic (:36-76), checkpointing (:311-369),
``predict``/``score``, and the ``BatchEndParam`` callback protocol.

Checkpoint format matches the reference: ``prefix-symbol.json`` (symbol
JSON) + ``prefix-%04d.params`` (NDArray list binary with ``arg:``/``aux:``
name prefixes) — interchangeable with reference checkpoints.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import namedtuple

import numpy as np

from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray
from . import symbol as sym
from . import telemetry as tele
from .context import Context, cpu, current_context
from . import optimizer as opt
from . import metric
from . import kvstore as kvs
from .initializer import Uniform
from . import io
from .executor_manager import (DataParallelExecutorManager,
                               _check_arguments, _split_input_slice,
                               _load_general)

__all__ = ["FeedForward", "save_checkpoint", "load_checkpoint",
           "load_optimizer_states", "latest_checkpoint", "BatchEndParam"]

BASE_ESTIMATOR = object
BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])

_TM_DEVICE_MS = tele.histogram("train.device_wait_ms")
_TM_CKPT_MS = tele.histogram("checkpoint.write_ms")
# same registry objects the fused ParallelTrainer feeds — the legacy
# per-device executor loop reports under the SAME names so one
# snapshot covers whichever loop ran (doc/observability.md)
_TM_TRAIN_STEPS = tele.counter("train.steps")
_TM_TRAIN_STEP_MS = tele.histogram("train.step_ms")


def _create_kvstore(kvstore, num_device, arg_params):
    """KVStore selection heuristic (reference model.py:36-76): single
    device → no kvstore; 'local' picks update-on-kvstore vs allreduce by
    the largest parameter size (16 MB threshold)."""
    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, kvs.KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            kv = None
        else:
            if kvstore == "local":
                max_size = max(np.prod(param.shape)
                               for param in arg_params.values())
                if max_size < 1024 * 1024 * 16:
                    kvstore = "local_update_cpu"
                else:
                    kvstore = "local_allreduce_cpu"
                logging.info("Auto-select kvstore type = %s", kvstore)
            kv = kvs.create(kvstore)
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    if kv is None:
        update_on_kvstore = False
    else:
        update_on_kvstore = "allreduce" not in kv.type
    return kv, update_on_kvstore


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names,
                        update_on_kvstore):
    """Init kvstore keys and broadcast initial weights (reference :78-97).
    Keys go in ONE list-form init so dist stores pay a single
    cross-process broadcast for the whole model."""
    keys = list(range(len(param_arrays)))
    kvstore.init(keys, [arg_params[param_names[i]] for i in keys])
    if update_on_kvstore:
        for idx, param_on_devs in enumerate(param_arrays):
            kvstore.pull(idx, param_on_devs, priority=-idx)


def _updatable(param_arrays, grad_arrays):
    """Yield (key, weights-per-device, grads-per-device) for every param
    that actually has a gradient (grad_req='null' entries yield None)."""
    for key, (weights, grads) in enumerate(zip(param_arrays, grad_arrays)):
        if grads[0] is not None:
            yield key, weights, grads


def _update_params_on_kvstore(param_arrays, grad_arrays, kvstore):
    """update_on_kvstore step: the store aggregates each key's device
    grads, applies its optimizer, and the pull fans fresh weights back
    out (behavioral parity with reference model.py:88-97)."""
    for key, weights, grads in _updatable(param_arrays, grad_arrays):
        kvstore.push(key, grads, priority=-key)
        kvstore.pull(key, weights, priority=-key)


def _update_params(param_arrays, grad_arrays, updater, num_device,
                   kvstore=None):
    """Allreduce step: aggregate grads (via kvstore when present — the
    pull overwrites each device grad with the reduced value), then run
    the local updater once per (key, device) pair (behavioral parity
    with reference model.py:99-116)."""
    for key, weights, grads in _updatable(param_arrays, grad_arrays):
        if kvstore:
            kvstore.push(key, grads, priority=-key)
            kvstore.pull(key, grads, priority=-key)
        for dev, (w, g) in enumerate(zip(weights, grads)):
            updater(key * num_device + dev, g, w)


def _epoch_batches(train_data, epoch_size, logger, epoch):
    """Yield one epoch's worth of batches.

    With ``epoch_size`` set, an "epoch" is exactly that many batches and
    the iterator is rewound as often as needed to supply them; without
    it, an epoch is one full pass and the iterator is rewound once at
    the end (reference epoch_size semantics, model.py:118-308)."""
    served = 0
    while True:
        ran_dry = True
        for batch in train_data:
            yield batch
            served += 1
            if epoch_size is not None and served >= epoch_size:
                ran_dry = False
                break
        if ran_dry:
            logger.info("Epoch[%d] Resetting Data Iterator", epoch)
            train_data.reset()
        if epoch_size is None or served >= epoch_size:
            return


def _resume_blob_fits(resume_states, expected_format, live_opt_name,
                      logger):
    """Shared warn-and-degrade guard for checkpointed optimizer-state
    blobs: False (with a loud log line) when the blob was written by
    the other training loop, or under a different optimizer — e.g.
    adam (mean, var) tuples fed to sgd's momentum slot would crash
    deep inside update() with no hint it came from resume. The caller
    then continues with checkpointed params but FRESH optimizer
    state."""
    if resume_states.get("format") != expected_format:
        logger.warning(
            "resume: checkpointed optimizer state (format=%r) does not "
            "fit this training path — continuing with checkpointed "
            "params but fresh optimizer state",
            resume_states.get("format"))
        return False
    saved_opt = resume_states.get("optimizer")
    if saved_opt is not None and live_opt_name is not None \
            and saved_opt != live_opt_name:
        logger.warning(
            "resume: checkpoint was saved under optimizer %r but this "
            "run uses %r — continuing with checkpointed params but "
            "fresh optimizer state", saved_opt, live_opt_name)
        return False
    return True


def _restore_updater_states(updater, resume_states, logger):
    """Apply a checkpointed optimizer-state blob to an updater; skips
    blobs written by the fused loop or a different optimizer with a
    loud log line — the resumed run then continues with FRESH optimizer
    state but the checkpointed params."""
    if resume_states is None:
        return
    if updater is None or not hasattr(updater, "set_states"):
        logger.warning(
            "resume: checkpointed optimizer state (format=%r) does not "
            "fit this training path — continuing with checkpointed "
            "params but fresh optimizer state",
            resume_states.get("format"))
        return
    live_opt = getattr(updater, "optimizer", None)
    if not _resume_blob_fits(
            resume_states, "updater",
            type(live_opt).__name__ if live_opt is not None else None,
            logger):
        return
    updater.set_states(resume_states)
    logger.info("resume: restored optimizer state (%d param slots)",
                len(resume_states.get("states", {})))


def _is_checkpoint_writer(kvstore):
    """In multi-process dist training every worker runs the training
    loop, but only rank 0 publishes the shared checkpoint files: the
    save-path serialization (_SAVE_LOCKS) is in-process only and cannot
    arbitrate two ranks writing the same .tmp path on a shared FS."""
    if kvstore is None or "dist" not in getattr(kvstore, "type", ""):
        return True
    return getattr(kvstore, "rank", 0) == 0


def _updater_states_blob(updater):
    """Checkpointable blob for an updater that supports get_states
    (tagged so resume can detect cross-loop mismatches)."""
    if updater is None or not hasattr(updater, "get_states"):
        return None
    blob = updater.get_states()
    blob["format"] = "updater"
    if getattr(updater, "optimizer", None) is not None:
        blob["optimizer"] = type(updater.optimizer).__name__
    return blob


def _train_multi_device(symbol, ctx, arg_names, param_names, aux_names,
                        arg_params, aux_params, begin_epoch, end_epoch,
                        epoch_size, optimizer, kvstore, update_on_kvstore,
                        train_data, eval_data=None, eval_metric=None,
                        epoch_end_callback=None, batch_end_callback=None,
                        logger=None, work_load_list=None, monitor=None,
                        eval_batch_end_callback=None, sym_gen=None,
                        checkpoint_prefix=None, resume_states=None):
    """The training loop (reference model.py:118-308)."""
    if logger is None:
        logger = logging
    executor_manager = DataParallelExecutorManager(
        symbol=symbol, sym_gen=sym_gen, ctx=ctx, train_data=train_data,
        param_names=param_names, arg_names=arg_names, aux_names=aux_names,
        work_load_list=work_load_list, logger=logger)
    if monitor:
        executor_manager.install_monitor(monitor)
    executor_manager.set_params(arg_params, aux_params)

    if not update_on_kvstore:
        updater = opt.get_updater(optimizer)
        _restore_updater_states(updater, resume_states, logger)
    if kvstore:
        _initialize_kvstore(kvstore=kvstore,
                            param_arrays=executor_manager.execgrp.param_arrays,
                            arg_params=arg_params,
                            param_names=executor_manager.param_names,
                            update_on_kvstore=update_on_kvstore)
    if update_on_kvstore:
        kvstore.set_optimizer(optimizer)
        # local update-on-kvstore keeps its updater in-process — restore
        # there; a dist store's state lives server-side (params-only
        # resume, _restore_updater_states logs the downgrade)
        if resume_states is not None:
            _restore_updater_states(getattr(kvstore, "_updater", None),
                                    resume_states, logger)

    train_data.reset()
    for epoch in range(begin_epoch, end_epoch):
        epoch_start = time.time()
        nbatch = 0
        eval_metric.reset()
        for data_batch in _epoch_batches(train_data, epoch_size, logger,
                                         epoch):
            executor_manager.load_data_batch(data_batch)
            if monitor is not None:
                monitor.tic()
            # forward+backward+update = one training step: the legacy
            # loop's dispatch is host-blocking per phase, so this wall
            # time is the honest per-batch cost (the fused loop's
            # step/input/device split needs its staged stream)
            with tele.span("train.step", hist=_TM_TRAIN_STEP_MS):
                executor_manager.forward(is_train=True)
                executor_manager.backward()
                if update_on_kvstore:
                    _update_params_on_kvstore(
                        executor_manager.param_arrays,
                        executor_manager.grad_arrays, kvstore)
                else:
                    _update_params(executor_manager.param_arrays,
                                   executor_manager.grad_arrays,
                                   updater=updater, num_device=len(ctx),
                                   kvstore=kvstore)
            _TM_TRAIN_STEPS.inc()
            if monitor is not None:
                monitor.toc_print()
            executor_manager.update_metric(eval_metric, data_batch.label)
            nbatch += 1
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(epoch=epoch,
                                                 nbatch=nbatch,
                                                 eval_metric=eval_metric,
                                                 locals=locals())
                _run_callbacks(batch_end_callback, batch_end_params)
        logger.info("Epoch[%d] Time cost=%.3f", epoch,
                    time.time() - epoch_start)

        if epoch_end_callback \
                or (checkpoint_prefix and _is_checkpoint_writer(kvstore)) \
                or epoch + 1 == end_epoch:
            # non-writer dist ranks skip the per-epoch host gather —
            # they would only throw it away at the checkpoint gate below
            executor_manager.copy_to(arg_params, aux_params)
        if epoch_end_callback is not None:
            for callback in (epoch_end_callback
                             if isinstance(epoch_end_callback, list)
                             else [epoch_end_callback]):
                callback(epoch, symbol, arg_params, aux_params)
        if checkpoint_prefix and _is_checkpoint_writer(kvstore):
            # crash-resume checkpoint: params + optimizer state, every
            # epoch, published atomically (save_checkpoint's tmp+replace)
            if not update_on_kvstore:
                states = _updater_states_blob(updater)
            else:
                states = _updater_states_blob(
                    getattr(kvstore, "_updater", None))
            save_checkpoint(checkpoint_prefix, epoch + 1, symbol,
                            arg_params, aux_params,
                            optimizer_states=states)

        if eval_data:
            eval_metric.reset()
            eval_data.reset()
            for i, eval_batch in enumerate(eval_data):
                executor_manager.load_data_batch(eval_batch)
                executor_manager.forward(is_train=False)
                executor_manager.update_metric(eval_metric, eval_batch.label)
                if eval_batch_end_callback is not None:
                    _run_callbacks(eval_batch_end_callback,
                                   BatchEndParam(epoch=epoch, nbatch=i,
                                                 eval_metric=eval_metric,
                                                 locals=locals()))
            name, value = eval_metric.get()
            logger.info("Epoch[%d] Validation-%s=%f", epoch, name, value)
            eval_data.reset()

    # drain async writers (do_checkpoint(async_write=True)) before
    # returning so every checkpoint file is complete; fit() also drains
    # in a finally for the error/interrupt paths
    _drain_async_writers(epoch_end_callback)


def _fused_fit_eligible(ctx, kvstore, monitor, sym_gen, work_load_list,
                        optimizer):
    """Should fit() run the fused ParallelTrainer step instead of the
    per-device executor loop?

    Default policy: fused on an all-TPU ctx (the flagship path —
    train_imagenet.py on tpu devices runs ONE XLA program per step);
    legacy executors elsewhere (cpu debugging, parity with the
    reference's loop). ``MXNET_FUSED_FIT=1`` forces fused on any ctx,
    ``=0`` forces legacy. Features only the legacy loop supports
    (monitor hooks, bucketing sym_gen, uneven work loads, dist kvstore,
    per-index lr_scale, custom optimizers without functional adapters)
    fall back automatically.
    """
    flag = os.environ.get("MXNET_FUSED_FIT")
    if flag == "0":
        return False
    if monitor is not None or sym_gen is not None:
        return False
    if work_load_list is not None and len(set(work_load_list)) > 1:
        return False
    if kvstore is not None and "dist" in kvstore.type:
        return False
    if getattr(optimizer, "lr_scale", None):
        return False
    try:
        from .parallel.optim import make_functional
        make_functional(optimizer)
    except MXNetError:
        return False
    if flag == "1":
        return True
    if any(c.device_type != "tpu" for c in ctx):
        return False
    import jax
    return len(jax.devices()) >= len(ctx)


def _mesh_for_ctx(ctx):
    """A dp mesh over the jax devices the ctx list names (by device_id
    when resolvable, else the first len(ctx) devices)."""
    import jax
    from .parallel import build_mesh
    devices = jax.devices()
    by_id = {d.id: d for d in devices}
    picked = []
    for c in ctx:
        d = by_id.get(c.device_id)
        if d is None or d in picked:
            picked = devices[:len(ctx)]
            break
        picked.append(d)
    return build_mesh({"dp": len(picked)}, picked)


def _train_fused(symbol, ctx, arg_params, aux_params, begin_epoch,
                 end_epoch, epoch_size, optimizer, train_data,
                 eval_data=None, eval_metric=None, epoch_end_callback=None,
                 batch_end_callback=None, logger=None, kvstore=None,
                 eval_batch_end_callback=None, checkpoint_prefix=None,
                 resume_states=None, compute_dtype=None):
    """The fused training loop: protocol-identical to
    ``_train_multi_device`` (metrics, callbacks, epoch_size semantics),
    but each step is ONE donated XLA program on a dp mesh
    (``ParallelTrainer``) — forward, backward, gradient aggregation, and
    the optimizer update fused, with the cross-device reduce as an
    in-program psum instead of kvstore copies (reference
    model.py:118-308 runs these as separate host-driven phases)."""
    import jax

    from .parallel import ParallelTrainer
    if logger is None:
        logger = logging
    if kvstore is not None:
        logger.info("fused fit: '%s' kvstore is subsumed by the "
                    "in-program gradient reduction", kvstore.type)
    mesh = _mesh_for_ctx(ctx)
    input_shapes = dict(train_data.provide_data + train_data.provide_label)
    trainer = ParallelTrainer(symbol, input_shapes, optimizer=optimizer,
                              mesh=mesh, compute_dtype=compute_dtype)
    trainer.init_params(arg_params, aux_params)
    if resume_states is not None and _resume_blob_fits(
            resume_states, "fused", type(optimizer).__name__, logger):
        try:
            trainer.set_optimizer_states(resume_states)
            logger.info("resume: restored fused optimizer state at "
                        "step %d", trainer._t)
        except MXNetError as e:
            logger.warning(
                "resume: %s — continuing with checkpointed params "
                "but fresh optimizer state", e)
    data_names = [x[0] for x in train_data.provide_data]
    label_names = [x[0] for x in train_data.provide_label]

    def sync_params():
        ap, xp = trainer.get_params()
        for k, v in ap.items():
            v.copyto(arg_params[k])
        for k, v in xp.items():
            v.copyto(aux_params[k])

    # staged stream: the consumer thread never blocks on the h2d edge —
    # batch i+1 is device_put (async, sharded over dp) while step i
    # runs; with ImageRecordIter(num_workers=N) upstream, decode too is
    # off this thread (in the pool workers), the reference's threaded
    # parser + prefetcher stack end to end
    staged = trainer.staged_batches(train_data, data_names, label_names)
    staged.reset()
    for epoch in range(begin_epoch, end_epoch):
        tic = time.time()
        eval_metric.reset()
        nbatch = 0
        with tele.span("train.epoch", epoch=epoch):
            while True:
                do_reset = True
                for data_batch, dev_batch in staged:
                    outs = trainer.step(dev_batch)
                    # blocked-on-device: the host stalls HERE, fetching the
                    # step's outputs for the metric (step() itself only
                    # dispatched)
                    with tele.span("train.device_wait",
                                   hist=_TM_DEVICE_MS):
                        out_nds = [nd.array(np.asarray(o)) for o in outs]
                    eval_metric.update(data_batch.label, out_nds)
                    nbatch += 1
                    if batch_end_callback is not None:
                        batch_end_params = BatchEndParam(
                            epoch=epoch, nbatch=nbatch,
                            eval_metric=eval_metric, locals=locals())
                        _run_callbacks(batch_end_callback, batch_end_params)
                    if epoch_size is not None and nbatch >= epoch_size:
                        do_reset = False
                        break
                if do_reset:
                    logger.info("Epoch[%d] Resetting Data Iterator", epoch)
                    staged.reset()
                if epoch_size is None or nbatch >= epoch_size:
                    break
        toc = time.time()
        logger.info("Epoch[%d] Time cost=%.3f", epoch, toc - tic)

        if epoch_end_callback or checkpoint_prefix \
                or epoch + 1 == end_epoch:
            sync_params()
        if epoch_end_callback is not None:
            for callback in (epoch_end_callback
                             if isinstance(epoch_end_callback, list)
                             else [epoch_end_callback]):
                callback(epoch, symbol, arg_params, aux_params)
        if checkpoint_prefix:
            # the host gather inside get_optimizer_states is a
            # collective when state is sharded (zero1/fsdp): EVERY
            # process must dispatch it, or process 0 deadlocks waiting
            # for an SPMD program the others never launch
            states = trainer.get_optimizer_states()
            states["format"] = "fused"
            states["optimizer"] = type(optimizer).__name__
            if jax.process_index() == 0:
                # ...but only one writer per job: the save-path
                # serialization is in-process only
                save_checkpoint(checkpoint_prefix, epoch + 1, symbol,
                                arg_params, aux_params,
                                optimizer_states=states)

        if eval_data:
            eval_metric.reset()
            eval_data.reset()
            for i, eval_batch in enumerate(eval_data):
                batch = dict(zip(data_names, eval_batch.data))
                batch.update(zip(label_names, eval_batch.label))
                outs = trainer.forward(batch)
                out_nds = [nd.array(np.asarray(o)) for o in outs]
                eval_metric.update(eval_batch.label, out_nds)
                if eval_batch_end_callback is not None:
                    batch_end_params = BatchEndParam(epoch=epoch, nbatch=i,
                                                     eval_metric=eval_metric,
                                                     locals=locals())
                    _run_callbacks(eval_batch_end_callback, batch_end_params)
            name_value = [eval_metric.get()]
            for name, value in name_value:
                logger.info("Epoch[%d] Validation-%s=%f", epoch, name, value)
            eval_data.reset()

    _drain_async_writers(epoch_end_callback)


def _drain_async_writers(epoch_end_callback):
    if epoch_end_callback is None:
        return
    for callback in (epoch_end_callback
                     if isinstance(epoch_end_callback, list)
                     else [epoch_end_callback]):
        finalize = getattr(callback, "finalize", None)
        if finalize is not None:
            finalize()


def _run_callbacks(callbacks, params):
    for cb in (callbacks if isinstance(callbacks, list) else [callbacks]):
        cb(params)


def _clear_stale_tmp(tmp_name):
    """Remove a stale tmp file left by a writer that died before its
    os.replace — otherwise a later save's in-flight write to the same
    tmp path is indistinguishable from the corpse (and a crash between
    the two would surface the OLD half-written bytes as "in flight")."""
    if os.path.exists(tmp_name):
        logging.warning("removing stale checkpoint temp file %s (a "
                        "previous writer died mid-save)", tmp_name)
        try:
            os.remove(tmp_name)
        except OSError:
            pass


def _atomic_local_save(writer, final_path):
    """tmp + os.replace publication for local checkpoint files."""
    tmp_name = final_path + ".tmp"
    _clear_stale_tmp(tmp_name)
    writer(tmp_name)
    os.replace(tmp_name, final_path)


def _strip_file_uri(path):
    return path[len("file://"):] if path.startswith("file://") else path


def _is_remote(path):
    return path.startswith(("s3://", "hdfs://"))


def _publish(path, writer):
    """Write one checkpoint file: remote URIs (the dmlc::Stream surface)
    write directly — object stores publish atomically on successful
    close; local paths go through tmp + os.replace."""
    local = _strip_file_uri(path)
    if _is_remote(local):
        writer(local)
    else:
        _atomic_local_save(writer, local)


# per-prefix locks serializing in-process checkpoint writers:
# fit(checkpoint_prefix=...) and a do_checkpoint(async_write=True)
# callback on the SAME prefix would otherwise race on the same .tmp
# paths — _clear_stale_tmp would delete the other writer's in-flight
# file out from under its os.replace. Unrelated prefixes stay parallel.
_SAVE_LOCKS = {}
_SAVE_LOCKS_GUARD = threading.Lock()
# absolute .states paths the CURRENT fit run on a prefix published: a
# states-less writer for the same epoch (a do_checkpoint callback
# running next to fit's own checkpoint branch) must NOT remove them —
# only a genuinely stale file from a previous run is removed. fit
# clears a prefix's entries when a new run starts on it (see
# _forget_states_published), so "previous run" includes an earlier
# fit call in this same process, not just a dead process's leftovers.
_STATES_PUBLISHED = set()


def _forget_states_published(prefix):
    """A new fit run is starting on ``prefix``: .states files already
    on disk belong to a PREVIOUS run and become eligible for the
    stale-states cleanup again. Entries for the new run's epochs are
    re-added as it checkpoints. Anchored to the epoch pattern (like
    latest_checkpoint) so prefix 'cp' does not forget a sibling run's
    'cp-run2-0003.states'."""
    import re
    base = os.path.abspath(_strip_file_uri(prefix))
    pat = re.compile(re.escape(base) + r"-\d{4,}\.states$")
    with _SAVE_LOCKS_GUARD:  # vs a concurrent writer's .add
        _STATES_PUBLISHED.difference_update(
            {p for p in _STATES_PUBLISHED if pat.match(p)})


def _save_lock_for(prefix):
    key = _strip_file_uri(prefix)
    if not _is_remote(key):
        key = os.path.abspath(key)
    with _SAVE_LOCKS_GUARD:
        return _SAVE_LOCKS.setdefault(key, threading.Lock())


def save_checkpoint(prefix, epoch, symbol, arg_params, aux_params,
                    optimizer_states=None):
    """Save prefix-symbol.json + prefix-%04d.params (reference :311).

    ``optimizer_states`` (a picklable blob, e.g. ``updater.get_states()``
    or ``ParallelTrainer.get_optimizer_states()``) additionally writes
    ``prefix-%04d.states`` so a crash-resumed ``fit`` continues the same
    optimizer trajectory (momentum/adam moments/update counts) instead
    of restarting them cold.

    Local files (plain paths and file:// URIs) are written via tmp +
    os.replace so a writer dying mid-write (e.g.
    do_checkpoint(async_write=True)'s daemon thread at interpreter exit)
    never leaves a truncated file that looks complete; stale ``.tmp``
    corpses from a crashed writer are cleaned up first. Remote URIs
    (s3://, hdfs://; the dmlc::Stream surface) write directly — object
    stores publish atomically on successful close.
    """
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    param_name = "%s-%04d.params" % (prefix, epoch)
    local = _strip_file_uri(param_name)
    # .states is published BEFORE .params: the .params file is the
    # checkpoint's completeness marker (latest_checkpoint keys off it),
    # so a crash between the two hides the half-checkpoint instead of
    # leaving a params file that silently resumes with cold optimizer
    # state
    states_name = local[:-len(".params")] + ".states" \
        if local.endswith(".params") else local + ".states"
    with tele.span("checkpoint.save", hist=_TM_CKPT_MS, epoch=epoch), \
            _save_lock_for(prefix):
        # symbol.json is atomic like .params/.states: a crash mid-write
        # must not leave a truncated symbol file that breaks every
        # future resume while latest_checkpoint still reports good epochs
        _publish("%s-symbol.json" % prefix, symbol.save)
        if optimizer_states is None:
            # a states file from an EARLIER run at this prefix/epoch no
            # longer corresponds to the params about to be published —
            # left in place, a later resume would silently apply the old
            # run's momentum/update counts to the new run's params.
            # One THIS process published stays: that is fit's own
            # checkpoint branch next to a states-less do_checkpoint
            # callback on the same prefix, not a stale leftover.
            if not _is_remote(local) \
                    and os.path.abspath(states_name) \
                    not in _STATES_PUBLISHED \
                    and os.path.exists(states_name):
                logging.warning("removing stale optimizer-state file %s "
                                "(this checkpoint has no optimizer "
                                "state)", states_name)
                try:
                    os.remove(states_name)
                except OSError:
                    pass
        else:
            import pickle

            def _write_states(path):
                from .stream import open_stream  # URI dispatch, nd.save
                with open_stream(path, "wb") as f:
                    pickle.dump(optimizer_states, f,
                                protocol=pickle.HIGHEST_PROTOCOL)
            _publish(states_name, _write_states)
            if not _is_remote(local):
                with _SAVE_LOCKS_GUARD:
                    _STATES_PUBLISHED.add(os.path.abspath(states_name))
        _publish(param_name, lambda p: nd.save(p, save_dict))
    logging.info("Saved checkpoint to \"%s\"", param_name)


def load_checkpoint(prefix, epoch):
    """Load (symbol, arg_params, aux_params) (reference :338)."""
    symbol = sym.load("%s-symbol.json" % prefix)
    save_dict = nd.load("%s-%04d.params" % (prefix, epoch))
    arg_params = {}
    aux_params = {}
    for k, v in save_dict.items():
        tp, name = k.split(":", 1)
        if tp == "arg":
            arg_params[name] = v
        if tp == "aux":
            aux_params[name] = v
    return symbol, arg_params, aux_params


def load_optimizer_states(prefix, epoch):
    """The optimizer-state blob saved next to ``prefix-%04d.params``, or
    None when that epoch was checkpointed without one (pre-resume
    checkpoints, or a dist store whose state lives server-side)."""
    import pickle
    from .stream import open_stream  # plain paths and URIs alike
    try:
        # any open failure (missing local file, absent remote object)
        # means "no states were saved" — resume degrades to params-only
        f = open_stream("%s-%04d.states" % (prefix, epoch), "rb")
    except Exception:
        return None
    with f:
        return pickle.load(f)


def latest_checkpoint(prefix):
    """The largest epoch N for which ``prefix-%04d.params`` exists, or
    None. In-flight/stale ``.tmp`` files are ignored — only fully
    published checkpoints count (save_checkpoint's os.replace is the
    publication point).

    ``file://`` prefixes are searched like plain paths. Remote prefixes
    (s3://, hdfs://) cannot be listed through this surface and return
    None — auto-resume does not support them (fit logs this)."""
    import glob
    import re
    prefix = _strip_file_uri(prefix)
    if _is_remote(prefix):
        return None
    best = None
    pat = re.compile(re.escape(os.path.basename(prefix)) +
                     r"-(\d{4,})\.params$")  # %04d grows past 9999
    for path in glob.glob(glob.escape(prefix) + "-*.params"):
        # anchored match: 'cp-b-cp-0007.params' must not count as
        # epoch 7 of prefix 'cp' just because the suffix re-embeds it
        m = pat.match(os.path.basename(path))
        if m:
            epoch = int(m.group(1))
            best = epoch if best is None else max(best, epoch)
    return best


class FeedForward(BASE_ESTIMATOR):
    """Model estimator over a symbol (reference model.py:371-886)."""

    def __init__(self, symbol, ctx=None, num_epoch=None, epoch_size=None,
                 optimizer="sgd", initializer=Uniform(0.01), numpy_batch_size=128,
                 arg_params=None, aux_params=None, allow_extra_params=False,
                 begin_epoch=0, compute_dtype=None, **kwargs):
        if isinstance(symbol, sym.Symbol):
            self.symbol = symbol
            self.sym_gen = None
        else:
            assert callable(symbol)
            self.symbol = None
            self.sym_gen = symbol
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.allow_extra_params = allow_extra_params
        self.argument_checked = False
        if self.sym_gen is None:
            self._check_arguments()
        if ctx is None:
            ctx = [current_context()]
        elif isinstance(ctx, Context):
            ctx = [ctx]
        self.ctx = ctx
        self.num_epoch = num_epoch
        self.epoch_size = epoch_size
        self.kwargs = kwargs.copy()
        self.optimizer = optimizer
        self.initializer = initializer
        self.numpy_batch_size = numpy_batch_size
        self.begin_epoch = begin_epoch
        # forward/backward dtype of the FUSED fit path (ParallelTrainer
        # compute_dtype: bf16 compute, f32 master params); the legacy
        # executor loop has no mixed precision and refuses it in fit()
        self.compute_dtype = compute_dtype
        self._pred_exec = None

    def _check_arguments(self):
        if self.argument_checked:
            return
        assert self.symbol is not None
        self.argument_checked = True
        _check_arguments(self.symbol)
        if self.arg_params:
            arg_names = set(self.symbol.list_arguments())
            self.arg_params = {k: v for k, v in self.arg_params.items()
                               if k in arg_names or not self.allow_extra_params}
        if self.aux_params:
            aux_names = set(self.symbol.list_auxiliary_states())
            self.aux_params = {k: v for k, v in self.aux_params.items()
                               if k in aux_names or not self.allow_extra_params}

    @staticmethod
    def _is_data_arg(name):
        return name.endswith("data") or name.endswith("label")

    def _init_params(self, input_shapes, overwrite=False):
        """Infer shapes, allocate and initialize params (reference :478)."""
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**input_shapes)
        if arg_shapes is None:
            raise MXNetError("cannot infer shapes from %s" % (input_shapes,))
        arg_names = self.symbol.list_arguments()
        input_names = list(input_shapes.keys())
        param_names = [key for key in arg_names if key not in input_names]
        aux_names = self.symbol.list_auxiliary_states()
        param_name_shapes = [x for x in zip(arg_names, arg_shapes)
                             if x[0] in param_names]
        arg_params = {k: nd.zeros(s) for k, s in param_name_shapes}
        aux_params = {k: nd.zeros(s)
                      for k, s in zip(aux_names, aux_shapes)}
        for k, v in arg_params.items():
            if self.arg_params and k in self.arg_params and not overwrite:
                self.arg_params[k].copyto(v)
            else:
                self.initializer(k, v)
        for k, v in aux_params.items():
            if self.aux_params and k in self.aux_params and not overwrite:
                self.aux_params[k].copyto(v)
            else:
                self.initializer(k, v)
        self.arg_params = arg_params
        self.aux_params = aux_params
        return arg_names, param_names, aux_names

    def __getstate__(self):
        this = self.__dict__.copy()
        this["_pred_exec"] = None
        return this

    def __setstate__(self, state):
        self.__dict__.update(state)

    def _init_predictor(self, input_shapes):
        if self._pred_exec is not None:
            arg_shapes, _, _ = self.symbol.infer_shape(**dict(input_shapes))
            pred_shapes = [x.shape for x in self._pred_exec.arg_arrays]
            if arg_shapes == pred_shapes:
                return
        pred_exec = self.symbol.simple_bind(self.ctx[0], grad_req="null",
                                            **dict(input_shapes))
        pred_exec.copy_params_from(self.arg_params, self.aux_params)
        self._pred_exec = pred_exec

    def _init_iter(self, X, y, is_train):
        """Wrap numpy input into NDArrayIter (reference :530-560)."""
        if isinstance(X, (np.ndarray, NDArray)):
            if y is None:
                if is_train:
                    raise ValueError("y must be specified when X is numpy")
                y = np.zeros(X.shape[0])
            y = np.asarray(y.asnumpy() if isinstance(y, NDArray) else y)
            if y.ndim == 2 and y.shape[1] == 1:
                y = y.flatten()
            batch_size = min(X.shape[0], self.numpy_batch_size)
            return io.NDArrayIter(X, y, batch_size=batch_size,
                                  shuffle=is_train, last_batch_handle="pad"
                                  if not is_train else "roll_over")
        if not isinstance(X, io.DataIter):
            raise TypeError("X must be DataIter, NDArray or numpy.ndarray")
        return X

    def _init_eval_iter(self, eval_data):
        if eval_data is None:
            return None
        if isinstance(eval_data, (tuple, list)) and len(eval_data) == 2:
            return self._init_iter(eval_data[0], eval_data[1], is_train=True)
        return eval_data

    def _forward_batches(self, X, num_batch):
        """Feed each batch into the shared predictor executor, run it
        forward, and yield (index, batch, valid) where ``valid`` counts
        the non-padding rows (``batch.pad`` semantics). Stops after
        ``num_batch`` batches WITHOUT fetching the next one, so a
        reset=False caller can keep consuming the iterator."""
        if num_batch is not None and num_batch <= 0:
            return
        feeds = [self._pred_exec.arg_dict[name]
                 for name, _ in X.provide_data]
        for i, batch in enumerate(X):
            _load_general(batch.data, [[(slice(None), a)] for a in feeds])
            self._pred_exec.forward(is_train=False)
            yield i, batch, X.batch_size - (batch.pad or 0)
            if num_batch is not None and i + 1 >= num_batch:
                return

    def predict(self, X, num_batch=None, return_data=False, reset=True):
        """Run prediction; returns numpy output(s), and with
        ``return_data`` also the (unpadded) data/label streams
        (behavioral parity with reference model.py:573)."""
        X = self._init_iter(X, None, is_train=False)
        if reset:
            X.reset()
        self._init_predictor(X.provide_data)

        def _merge(streams):
            merged = [np.concatenate(chunks) for chunks in streams]
            return merged[0] if len(merged) == 1 else merged

        outs = [[] for _ in self._pred_exec.outputs]
        datas = [[] for _ in X.provide_data]
        labels = [[] for _ in (X.provide_label or [])]
        for _, batch, valid in self._forward_batches(X, num_batch):
            for sink, out_nd in zip(outs, self._pred_exec.outputs):
                sink.append(out_nd.asnumpy()[:valid])
            if return_data:
                for sink, x in zip(datas, batch.data):
                    sink.append(x.asnumpy()[:valid])
                for sink, x in zip(labels, batch.label):
                    sink.append(x.asnumpy()[:valid])
        if return_data:
            return _merge(outs), _merge(datas), _merge(labels)
        return _merge(outs)

    def score(self, X, eval_metric="acc", num_batch=None,
              batch_end_callback=None, reset=True):
        """Evaluate on a metric (behavioral parity with reference
        model.py:634)."""
        if not isinstance(eval_metric, metric.EvalMetric):
            eval_metric = metric.create(eval_metric)
        X = self._init_iter(X, None, is_train=False)
        if reset:
            X.reset()
        self._init_predictor(X.provide_data)
        for i, batch, _ in self._forward_batches(X, num_batch):
            eval_metric.update(batch.label, self._pred_exec.outputs)
            if batch_end_callback is not None:
                _run_callbacks(batch_end_callback,
                               BatchEndParam(epoch=0, nbatch=i,
                                             eval_metric=eval_metric,
                                             locals=locals()))
        return eval_metric.get()[1]

    def _resume_from_checkpoint(self, prefix, logger):
        """Auto-resume: load the latest fully published checkpoint at
        ``prefix`` (params, plus the optimizer-state blob when one was
        saved) and fast-forward ``begin_epoch`` so training continues
        where the dead run stopped. The constructed symbol stays
        authoritative — only params/state are read. Returns the
        optimizer-state blob or None."""
        if _is_remote(_strip_file_uri(prefix)):
            logger.warning(
                "fit: auto-resume does not support remote checkpoint "
                "prefixes (%s) — remote stores cannot be listed through "
                "this surface; training starts at begin_epoch=%d (pass "
                "resume=False to silence this)", prefix,
                self.begin_epoch)
            return None
        epoch = latest_checkpoint(prefix)
        if epoch is None or epoch <= self.begin_epoch:
            return None
        logger.info("fit: auto-resuming from \"%s-%04d.params\" "
                    "(begin_epoch %d -> %d)", prefix, epoch,
                    self.begin_epoch, epoch)
        _, arg_params, aux_params = load_checkpoint(prefix, epoch)
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.begin_epoch = epoch
        states = load_optimizer_states(prefix, epoch)
        if states is None:
            logger.warning(
                "fit: no optimizer-state blob next to \"%s-%04d.params\""
                " — resuming with checkpointed params but FRESH "
                "optimizer state (momentum/update counts restart cold)",
                prefix, epoch)
        return states

    def fit(self, X, y=None, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            logger=None, work_load_list=None, monitor=None,
            eval_batch_end_callback=None, checkpoint_prefix=None,
            resume=True):
        """Train (reference model.py:681-767).

        ``checkpoint_prefix`` turns on crash-resume: every epoch is
        checkpointed (params + optimizer state, atomically published)
        under that prefix, and — unless ``resume=False`` — a fresh call
        first looks for the latest complete ``prefix-%04d.params``,
        reloads params and optimizer state, and continues from that
        epoch instead of restarting at ``begin_epoch``. See
        doc/fault_tolerance.md.
        """
        if self.num_epoch is None:
            raise ValueError("num_epoch must be set when calling fit "
                             "(pass num_epoch= to FeedForward)")
        data = self._init_iter(X, y, is_train=True)
        eval_data = self._init_eval_iter(eval_data)

        resume_states = None
        if checkpoint_prefix is not None:
            _forget_states_published(checkpoint_prefix)
            if resume:
                log = logger if logger is not None else logging
                kv_type = kvstore if isinstance(kvstore, str) \
                    else getattr(kvstore, "type", "")
                if "dist" in (kv_type or ""):
                    # each rank decides begin_epoch from the files IT
                    # sees; with per-worker disks the ranks would resume
                    # at different epochs and hang in collectives
                    log.warning(
                        "fit: dist auto-resume assumes every worker "
                        "sees the same checkpoint files (shared "
                        "filesystem) — ranks resuming at different "
                        "epochs will desynchronize the job")
                resume_states = self._resume_from_checkpoint(
                    checkpoint_prefix, log)

        if self.sym_gen:
            self.symbol = self.sym_gen(data.default_bucket_key)
            self._check_arguments()
        self.kwargs["sym"] = self.symbol

        input_shapes = dict(data.provide_data + data.provide_label)
        arg_names, param_names, aux_names = self._init_params(input_shapes)

        if not isinstance(eval_metric, metric.EvalMetric):
            eval_metric = metric.create(eval_metric)

        # create kvstore
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self.ctx), self.arg_params)

        # init optimizer
        optimizer = self.optimizer
        if isinstance(optimizer, str):
            batch_size = data.batch_size
            if kvstore and kvstore.type == "dist_sync":
                batch_size *= kvstore.num_workers
            optimizer = opt.create(optimizer,
                                   rescale_grad=(1.0 / batch_size),
                                   **self.kwargs)
        elif isinstance(optimizer, opt.Optimizer):
            pass
        else:
            raise TypeError("optimizer must be str or Optimizer")

        try:
            if _fused_fit_eligible(self.ctx, kvstore, monitor, self.sym_gen,
                                   work_load_list, optimizer):
                _train_fused(
                    self.symbol, self.ctx, self.arg_params, self.aux_params,
                    begin_epoch=self.begin_epoch, end_epoch=self.num_epoch,
                    epoch_size=self.epoch_size, optimizer=optimizer,
                    train_data=data, eval_data=eval_data,
                    eval_metric=eval_metric,
                    epoch_end_callback=epoch_end_callback,
                    batch_end_callback=batch_end_callback,
                    kvstore=kvstore, logger=logger,
                    eval_batch_end_callback=eval_batch_end_callback,
                    checkpoint_prefix=checkpoint_prefix,
                    resume_states=resume_states,
                    compute_dtype=self.compute_dtype)
            else:
                if self.compute_dtype is not None:
                    raise MXNetError(
                        "FeedForward: compute_dtype=%r needs the fused "
                        "fit path (an all-tpu ctx, or MXNET_FUSED_FIT="
                        "1), and this fit() is not eligible for it — "
                        "the legacy executor loop computes in the "
                        "parameter dtype only" % (self.compute_dtype,))
                _train_multi_device(
                    self.symbol, self.ctx, arg_names, param_names, aux_names,
                    self.arg_params, self.aux_params,
                    begin_epoch=self.begin_epoch, end_epoch=self.num_epoch,
                    epoch_size=self.epoch_size, optimizer=optimizer,
                    train_data=data, eval_data=eval_data,
                    eval_metric=eval_metric,
                    epoch_end_callback=epoch_end_callback,
                    batch_end_callback=batch_end_callback,
                    kvstore=kvstore, update_on_kvstore=update_on_kvstore,
                    logger=logger, work_load_list=work_load_list,
                    monitor=monitor,
                    eval_batch_end_callback=eval_batch_end_callback,
                    sym_gen=self.sym_gen,
                    checkpoint_prefix=checkpoint_prefix,
                    resume_states=resume_states)
        finally:
            # drain async checkpoint writers even on error/interrupt so
            # no .params file is left truncated by a dying daemon thread
            _drain_async_writers(epoch_end_callback)
        return self

    def save(self, prefix, epoch=None):
        """Checkpoint (reference :769): prefix-symbol.json + .params."""
        if epoch is None:
            epoch = self.num_epoch
        assert epoch is not None
        save_checkpoint(prefix, epoch, self.symbol, self.arg_params,
                        self.aux_params)

    def as_serving_engine(self, max_len, slots=8, prefill_buckets=None,
                          max_queue=256, steps_per_round=1,
                          prefix_cache_mb=None, prefill_chunk=None,
                          overload=None, round_timeout_ms=None,
                          spec_k=None, draft=None, draft_decoder=None,
                          capture_dir=None, tp=None,
                          weight_dtype=None, **decoder_kwargs):
        """Trained estimator → continuous-batching inference engine
        (``mxnet_tpu.serving.InferenceEngine``, doc/serving.md): the
        online-serving analogue of :meth:`predict`. Works on a fitted
        model or one built from ``FeedForward.load`` — the same
        checkpoint-to-engine path ``InferenceEngine.from_checkpoint``
        takes, minus the file round-trip. ``decoder_kwargs`` reach the
        underlying ``Decoder`` (``compute_dtype``, ``cache_dtype``,
        ...); ``overload``/``round_timeout_ms`` are the robustness
        knobs (load shedding policy, round watchdog — doc/serving.md
        "Serving under hostile traffic"); ``spec_k``/``draft``/
        ``draft_decoder`` arm speculative decoding (doc/serving.md
        "Speculative decoding"); the decode / verify cache read
        follows the cache kind (doc/serving.md "The decode read");
        ``tp=N`` shards the KV cache and every compiled serving
        program over an N-device mesh's model axis (doc/serving.md
        "Tensor-parallel serving");
        ``weight_dtype="int8"`` quantizes the engine's copy of the
        matmul weights to int8 with per-output-channel scales —
        1 byte/elem weight reads, on-the-fly dequant (doc/serving.md
        "Quantized weights")."""
        from .parallel.decode import Decoder
        from .serving import InferenceEngine

        if self.symbol is None or not self.arg_params:
            raise MXNetError(
                "as_serving_engine needs a trained model: fit() it, "
                "pass arg_params, or use FeedForward.load")

        def to_np(v):
            return v.asnumpy() if hasattr(v, "asnumpy") else v

        # weight_dtype goes to the DECODER (the env-default owner) and
        # the engine inherits: an explicit "float" must override
        # MXNET_SERVING_WEIGHT_DTYPE=int8 (an env-quantized decoder
        # cannot serve a float engine)
        decoder_kwargs.setdefault("weight_dtype", weight_dtype)
        dec = Decoder(
            self.symbol,
            {k: to_np(v) for k, v in self.arg_params.items()},
            max_len,
            aux_params={k: to_np(v)
                        for k, v in (self.aux_params or {}).items()},
            **decoder_kwargs)
        return InferenceEngine(dec, slots=slots,
                               prefill_buckets=prefill_buckets,
                               max_queue=max_queue,
                               steps_per_round=steps_per_round,
                               prefix_cache_mb=prefix_cache_mb,
                               prefill_chunk=prefill_chunk,
                               overload=overload,
                               round_timeout_ms=round_timeout_ms,
                               spec_k=spec_k, draft=draft,
                               draft_decoder=draft_decoder,
                               capture_dir=capture_dir, tp=tp)

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        """Load from checkpoint (reference :793)."""
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(symbol, ctx=ctx, arg_params=arg_params,
                           aux_params=aux_params, begin_epoch=epoch,
                           **kwargs)

    @staticmethod
    def create(symbol, X, y=None, ctx=None, num_epoch=None, epoch_size=None,
               optimizer="sgd", initializer=Uniform(0.01), eval_data=None,
               eval_metric="acc", epoch_end_callback=None,
               batch_end_callback=None, kvstore="local", logger=None,
               work_load_list=None, eval_batch_end_callback=None, **kwargs):
        """Create + fit in one call (reference :821-886)."""
        model = FeedForward(symbol, ctx=ctx, num_epoch=num_epoch,
                            epoch_size=epoch_size, optimizer=optimizer,
                            initializer=initializer, **kwargs)
        model.fit(X, y, eval_data=eval_data, eval_metric=eval_metric,
                  epoch_end_callback=epoch_end_callback,
                  batch_end_callback=batch_end_callback,
                  kvstore=kvstore, logger=logger,
                  work_load_list=work_load_list,
                  eval_batch_end_callback=eval_batch_end_callback)
        return model
