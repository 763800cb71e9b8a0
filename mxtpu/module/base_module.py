"""BaseModule: the high-level train/eval interface with ``fit``.

Parity: python/mxnet/module/base_module.py (fit :376-525, score, predict,
forward_backward :189, init_params :593, init_optimizer :958)."""
from __future__ import annotations

import logging
import time
from collections import deque

from .. import diagnostics as _diag
from .. import metric as _metric
from .. import ndarray as nd
from .. import telemetry as _tel
from ..base import MXNetError, NativeError
from ..executor import device_wait as _device_wait
from ..model import BatchEndParam
from ..obs import corpus as _obs_corpus
from ..telemetry import tracing as _tracing


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith("_weight")
                      and not arg.endswith("_bias") and not arg.endswith("_gamma")
                      and not arg.endswith("_beta")]
        msg = "\033[91mYou created Module with Module(..., %s_names=%s) but " \
              "input with name '%s' is not found in symbol.list_arguments(). " \
              "Did you mean one of:\n\t%s\033[0m" % (
                  typename, str(names), name, "\n\t".join(candidates))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ------------------------------------------------ high-level
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise ValueError("Cannot merge batches: different outputs")
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, max_in_flight=None, metric_sync=None,
            device_metrics=None, device_prefetch=None, mesh=None,
            elastic=None, resume=None, health=None):
        """Training loop (parity base_module.py:376-525), pipelined.

        ``mesh`` — SPMD mesh execution (docs/sharding.md): train
        data-parallel across a device mesh with cross-replica
        weight-update sharding. Accepts anything
        :func:`mxtpu.sharding.resolve` understands (``"all"``, an int,
        ``"data:4,tp:2"``, a ``jax.sharding.Mesh`` or
        :class:`~mxtpu.sharding.MeshContext`); ``None`` defers to the
        ``MXTPU_MESH`` env var, ``False`` disables even with the env
        set. The mesh stays active for the whole fit, so the pipeline
        knobs below run unchanged on sharded state.

        The async-pipeline knobs (docs/training_pipeline.md):

        * ``max_in_flight`` — keep up to K dispatched steps in flight and
          only ``block_until_ready`` the oldest when the window is full
          (env ``MXTPU_FIT_INFLIGHT``, default 2). Pacing is skipped when
          the metric has no device kernels (the per-batch host sync of
          the numpy path bounds the pipeline anyway).
        * ``metric_sync`` — device->host metric sync cadence in batches.
          ``None`` auto-derives it: the minimum Speedometer ``frequent``
          among the batch callbacks; 1 when a non-Speedometer batch
          callback might read live values; epoch-end only otherwise.
        * ``device_metrics`` — accumulate eval metrics on device via
          their jitted kernels (env ``MXTPU_FIT_DEVICE_METRICS``,
          default on). Metrics without kernels fall back to numpy.
        * ``device_prefetch`` — wrap ``train_data`` in a
          :class:`~mxtpu.io.DevicePrefetchIter` so batch N+1's device
          transfer is issued from the producer thread while step N runs
          (env ``MXTPU_FIT_DEVICE_PREFETCH``, default off; the wrapper
          is closed when fit returns).

        Elastic training (docs/elastic.md):

        * ``elastic`` — arm async checkpointing: a prefix string, an
          :class:`~mxtpu.elastic.ElasticConfig`, or a kwargs dict
          (``None`` defers to the ``MXTPU_ELASTIC`` env prefix). Device
          state is snapshotted off the critical path at the configured
          step/epoch cadence — steps keep dispatching while the writer
          thread lands the file.
        * ``resume`` — restore before training: ``True`` resumes the
          elastic prefix's newest durable generation (no-op when none
          exists yet), or pass a prefix / manifest path explicitly. The
          resumed fit is bit-exact on weights against an uninterrupted
          run: step/epoch cursors, RNG streams, optimizer state (f32
          masters under ``MXTPU_PIPELINE=bf16``), metric accumulators
          and the data-iterator position are all restored.

        Training health (docs/observability.md):

        * ``health`` — arm device-resident per-layer training-health
          statistics + the anomaly detector suite
          (:mod:`mxtpu.obs.health`). Stats ride the ``metric_sync``
          cadence — zero additional host sync points. ``None`` defers
          to the ``MXTPU_HEALTH`` env var; ``MXTPU_HEALTH_ACTION=
          rollback`` additionally arms divergence auto-rollback via the
          elastic supervisor (docs/elastic.md). Needs the fused train
          step; disarmed (with a log line) otherwise.
        """
        # the outer span of the call: everything fit does, the resolution
        # of its knobs included, is inside it on the trace
        with _tracing.span("fit", category="module"):
            from ..initializer import Uniform
            from .. import tune as _tune
            assert num_epoch is not None, "please specify number of epochs"
            initializer = initializer or Uniform(0.01)

            # one resolution point for every pipeline knob (docs/tune.md:
            # default < environment < this call's explicit arguments)
            max_in_flight = _tune.resolve_int(
                "fit.max_in_flight", explicit=max_in_flight, floor=1)
            metric_sync = _tune.resolve(
                "fit.metric_sync", explicit=metric_sync)
            device_metrics = _tune.resolve(
                "fit.device_metrics", explicit=device_metrics)
            device_prefetch = _tune.resolve(
                "fit.device_prefetch", explicit=device_prefetch)
            self._fit_knobs = {"fit.max_in_flight": max_in_flight,
                               "fit.metric_sync": metric_sync,
                               "fit.device_metrics": device_metrics,
                               "fit.device_prefetch": device_prefetch}

            owned_iter = None
            if device_prefetch:
                from .. import io as _io
                if not isinstance(train_data, _io.DevicePrefetchIter):
                    ctxs = getattr(self, "_context", None)
                    device = ctxs[0].jax_device if ctxs else None
                    train_data = owned_iter = _io.DevicePrefetchIter(
                        train_data, device=device)

            from .. import sharding as _sharding
            mesh_ctx = _sharding.resolve(mesh)

            from .. import elastic as _elastic
            el_cfg = _elastic.ElasticConfig.resolve(elastic)
            resume_state = None
            if resume:
                spec = resume
                if resume is True:
                    if el_cfg is None:
                        raise MXNetError(
                            "fit(resume=True) needs elastic= (or MXTPU_ELASTIC)"
                            " to name the checkpoint prefix")
                    spec = el_cfg.prefix
                resume_state = _elastic.load_resume(spec)
                if resume_state is None:
                    self.logger.info(
                        "fit(resume): no durable generation at %r — starting "
                        "fresh", spec)

            # arm the hang watchdog (MXTPU_WATCHDOG=0 opts out) + the SIGUSR2
            # postmortem handler (only over SIG_DFL — a user's own USR2
            # handler is never replaced; MXTPU_DIAG_SIGNAL=0 opts out)
            _diag.on_session_start()
            try:
                with _sharding.use(mesh_ctx):
                    self._fit_impl(
                        train_data, eval_data, eval_metric, epoch_end_callback,
                        batch_end_callback, kvstore, optimizer, optimizer_params,
                        eval_end_callback, eval_batch_end_callback, initializer,
                        arg_params, aux_params, allow_missing, force_rebind,
                        force_init, begin_epoch, num_epoch, validation_metric,
                        monitor, max_in_flight, metric_sync, device_metrics,
                        el_cfg, resume_state, health)
            except Exception as exc:
                # fatal training exception: capture the flight ring / ledger /
                # engine state BEFORE the stack unwinds and the evidence GCs.
                # Plain MXNetError is a usage error (bad shape/name at bind),
                # not a backend failure — no forensics, match serving's
                # filter. NativeError (nonzero native-engine return) IS a
                # backend failure despite being an MXNetError subclass.
                if not isinstance(exc, MXNetError) or isinstance(exc,
                                                                 NativeError):
                    _diag.postmortem("fit_exception", exc=exc, source="fit")
                raise
            finally:
                if owned_iter is not None:
                    owned_iter.close()

    def _fit_impl(self, train_data, eval_data, eval_metric,
                  epoch_end_callback, batch_end_callback, kvstore, optimizer,
                  optimizer_params, eval_end_callback,
                  eval_batch_end_callback, initializer, arg_params,
                  aux_params, allow_missing, force_rebind, force_init,
                  begin_epoch, num_epoch, validation_metric, monitor,
                  max_in_flight, metric_sync, device_metrics,
                  el_cfg=None, resume_state=None, health=None):
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        # only now is the monitor's path settled: install_monitor may
        # have gone adapter mode (device taps over the fused step), and
        # init_optimizer may have walked that back when the fused step
        # declined — only the legacy per-op path reads per-batch host
        # stats that the device metric accumulator would miss
        monitor_adapter = monitor is not None and \
            getattr(self, "_monitor_adapter", None) is monitor
        if monitor is not None and not monitor_adapter:
            device_metrics = False  # monitor.toc reads per-batch host stats
        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        # elastic resume: applied AFTER bind/init so set_params restages
        # the fused device state and the restored RNG streams are not
        # consumed by the (now overwritten) initializer draws
        from .. import elastic as _elastic
        el_session = None
        restored_iter = False
        if resume_state is not None:
            restored_iter = _elastic.apply_resume(
                self, resume_state, eval_metric=eval_metric,
                train_data=train_data)
            begin_epoch = max(begin_epoch, resume_state.begin_epoch)
        if el_cfg is not None:
            el_session = _elastic.ElasticSession(
                self, el_cfg, logger=self.logger,
                resume_state=resume_state)

        accum = _metric.DeviceMetricAccum.wrap(eval_metric) \
            if device_metrics else None
        # Speedometer (and anything else reading the metric between
        # cadence syncs) consumes this snapshot instead of forcing a sync
        eval_metric._device_accum = accum

        # statistic heads beside the loss (an expert layer's loads) ride the
        # metric sync's transfer; a metric never sees them
        stat_rider = self.stat_heads_rider()
        if stat_rider is not None and accum is not None:
            accum.add_rider(stat_rider)

        # training health (docs/observability.md): the device-resident
        # stat kernels + detector suite, riding the metric-sync cadence.
        # The Monitor adapter reuses the same session detectors-off —
        # its taps need the identical cadence transport.
        from ..obs import health as _health
        if health is None:
            health = _health.armed_by_env()
        health_session = None
        fused = getattr(self, "_fused", None)
        if fused is not None and (health or monitor_adapter):
            health_session = _health.HealthSession(
                fused, monitor=monitor if monitor_adapter else None,
                detect=bool(health), logger=self.logger)
            if accum is not None:
                accum.add_rider(health_session)
        elif health:
            self.logger.info(
                "fit(health): the fused train step is not armed — "
                "training-health stats are computed inside it; disarmed "
                "for this fit")
            health = False
        callbacks = _as_list(batch_end_callback)
        if metric_sync is None:
            from .. import callback as _cb
            freqs = [c.frequent for c in callbacks
                     if isinstance(c, _cb.Speedometer)]
            known = [c for c in callbacks
                     if isinstance(c, (_cb.Speedometer, _cb.ProgressBar))]
            if len(known) < len(callbacks):
                metric_sync = 1   # unknown callbacks may read live values
                if accum is not None:
                    self.logger.info(
                        "fit: non-Speedometer batch callback present — "
                        "metric sync falls back to every batch (pass "
                        "metric_sync= to restore the cadence)")
            elif freqs:
                # gcd, not min: every Speedometer window boundary must be
                # a sync batch, or a meter with a non-multiple `frequent`
                # would emit (and auto_reset against) stale snapshots
                from math import gcd
                from functools import reduce
                metric_sync = reduce(gcd, freqs)
            else:
                metric_sync = 0   # no batch callbacks: epoch-end only
        metric_sync = max(0, int(metric_sync))
        if hasattr(self, "_fit_knobs"):
            self._fit_knobs["fit.metric_sync"] = metric_sync

        # one pipeline for training and serving: fit emits into the same
        # process-wide registry the serving /metrics endpoint scrapes
        step_ms = _tel.histogram(
            "fit_step_ms",
            help="wall time per step: dispatch + pipeline pacing wait")
        dispatch_ms = _tel.histogram(
            "fit_dispatch_ms",
            help="host time to issue one step (async dispatch, no device "
                 "wait) — fit_step_ms minus this is pacing/back-pressure")
        input_wait_ms = _tel.histogram(
            "fit_input_wait_ms",
            help="wall time fit waited for the next batch: the "
                 "iterator's next() and prepare() (the fit.input span)")
        sync_wait_ms = _tel.histogram(
            "fit_sync_wait_ms",
            help="pacing: wall time blocked on the oldest in-flight step")
        msync_ms = _tel.histogram(
            "fit_metric_sync_ms",
            help="device->host metric snapshot wall time (cadence sync)")
        samples_total = _tel.counter("fit_samples",
                                     help="training examples consumed")
        sps_gauge = _tel.gauge("fit_samples_per_sec",
                               help="epoch-level training throughput")
        eval_ms = _tel.histogram("fit_eval_ms",
                                 help="validation pass wall time")
        epochs_done = _tel.counter("fit_epochs", help="epochs completed")

        try:
            for epoch in range(begin_epoch, num_epoch):
                with _tracing.span("fit.epoch", category="module",
                                   tags={"epoch": epoch}):
                    tic = time.time()
                    # a mid-epoch resume continues THIS epoch: the restored
                    # metric sums and iterator cursor must survive, so skip
                    # the epoch-top reset exactly once
                    resumed_here = (resume_state is not None
                                    and not resume_state.epoch_boundary
                                    and epoch == resume_state.epoch)
                    if not resumed_here:
                        eval_metric.reset()
                        if accum is not None:
                            accum.reset()
                    nbatch = 0
                    skip_batches = 0
                    if resumed_here:
                        nbatch = resume_state.start_nbatch
                        if not restored_iter:
                            # iterator without a native cursor: replay the
                            # epoch head and discard (deterministic order,
                            # no training, no RNG draws)
                            skip_batches = nbatch
                    epoch_samples = 0
                    data_iter = iter(train_data)
                    for _ in range(skip_batches):
                        try:
                            next(data_iter)
                        except StopIteration:
                            break
                    end_of_batch = False
                    with _tracing.span("fit.input", category="module") as sp_in:
                        try:
                            next_data_batch = next(data_iter)
                        except StopIteration:
                            # resumed exactly at the epoch's last batch
                            next_data_batch = None
                            end_of_batch = True
                    input_wait_ms.observe(sp_in.duration_ms)
                    inflight = deque()
                    while not end_of_batch:
                        data_batch = next_data_batch
                        if monitor is not None:
                            monitor.tic()
                        # fit.step is the correlation root for everything one
                        # batch triggers (executor.forward -> engine dispatches,
                        # kvstore push/pull inside update)
                        with _tracing.span("fit.step", category="module",
                                           tags={"epoch": epoch,
                                                 "nbatch": nbatch},
                                           step_num=nbatch) as sp:
                            self.forward_backward(data_batch)
                            self.update()
                        dispatch_ms.observe(sp.duration_ms)
                        if health_session is not None:
                            # fold the step's device stat rows (async, no
                            # transfer) before anything can overwrite them
                            health_session.on_step()
                        if el_session is not None:
                            # BEFORE the lookahead fetch below: the only
                            # point where the iterator cursor still reads
                            # "batches 0..nbatch consumed"
                            el_session.pre_lookahead(train_data, epoch, nbatch)
                        view = self._device_step_view(data_batch) \
                            if accum is not None else None
                        if data_batch.data:
                            epoch_samples += data_batch.data[0].shape[0] - \
                                (data_batch.pad or 0)
                        # fetch batch N+1 FIRST: its host assembly overlaps step
                        # N's device execution (and, with DevicePrefetchIter, its
                        # transfer is already in flight on the producer thread)
                        with _tracing.span("fit.input",
                                           category="module") as sp_in:
                            try:
                                next_data_batch = next(data_iter)
                                self.prepare(next_data_batch)
                            except StopIteration:
                                end_of_batch = True
                        input_wait_ms.observe(sp_in.duration_ms)
                        pacing = 0.0
                        if view is not None:
                            labels, outs, token = view
                            accum.update(labels, outs)
                            if token is not None:
                                inflight.append(token)
                                # bounded in-flight window: block ONLY when more
                                # than K steps are outstanding, and only on the
                                # oldest — the device never idles waiting for the
                                # host between steps
                                while len(inflight) > max_in_flight:
                                    with _tracing.span(
                                            "fit.pace",
                                            category="module") as sp_w:
                                        _device_wait(inflight.popleft())
                                    sync_wait_ms.observe(sp_w.duration_ms)
                                    pacing += sp_w.duration_ms
                        else:
                            self.update_metric(eval_metric, data_batch.label)
                        step_ms.observe(sp.duration_ms + pacing)
                        if _obs_corpus.enabled():
                            # measurement-corpus service row: the same
                            # per-step wall time the histogram sees, keyed
                            # by batch rows for the cost-model fit
                            _obs_corpus.record_service(
                                "fit_step", sp.duration_ms + pacing,
                                rows=data_batch.data[0].shape[0]
                                if data_batch.data else None)
                        cadence_now = (end_of_batch or metric_sync == 1 or
                                       (metric_sync and nbatch and
                                        nbatch % metric_sync == 0))
                        if health_session is not None and monitor is not None \
                                and monitor.activated:
                            # a sampled (monitored) batch forces a cadence so
                            # its device taps land before toc_print below
                            cadence_now = True
                        if accum is not None and cadence_now:
                            if end_of_batch:
                                inflight.clear()  # metric sync covers every step
                            with _tracing.span("fit.metric_sync",
                                               category="module") as sp_m:
                                accum.sync()
                            msync_ms.observe(sp_m.duration_ms)
                        elif health_session is not None and cadence_now:
                            with _tracing.span("fit.metric_sync",
                                               category="module"):
                                health_session.sync_direct()
                        if health_session is not None and cadence_now:
                            # detectors run on the freshly landed window —
                            # BEFORE el_session.on_step below, so a rollback
                            # wedge aborts before the corrupted snapshot
                            health_session.on_cadence(eval_metric)
                        if monitor is not None or el_session is not None \
                                or callbacks:
                            # the per-batch hooks, in their contract order
                            with _tracing.span("fit.callbacks",
                                               category="module"):
                                if monitor is not None:
                                    monitor.toc_print()
                                if el_session is not None:
                                    # after the step's metrics accumulated,
                                    # before the callbacks: the cadence
                                    # snapshot point, and where supervisor
                                    # interrupts (wedge/SIGTERM) surface as
                                    # exceptions
                                    el_session.on_step(eval_metric, accum,
                                                       train_data)
                                if callbacks:
                                    batch_end_params = BatchEndParam(
                                        epoch=epoch, nbatch=nbatch,
                                        eval_metric=eval_metric,
                                        locals=locals())
                                    for callback in callbacks:
                                        callback(batch_end_params)
                        nbatch += 1

                    for name, val in eval_metric.get_name_value():
                        self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                    toc = time.time()
                    self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))
                    samples_total.inc(epoch_samples)
                    epochs_done.inc()
                    if toc > tic:
                        sps_gauge.set(epoch_samples / (toc - tic))

                    # the reference round-trips every parameter through the host
                    # here each epoch; with device-resident weights (fused step)
                    # that transfer is pure waste unless a callback wants them —
                    # elastic-aware checkpoint callbacks (_needs_host_params
                    # False: they snapshot the device state directly through
                    # the async writer) don't, so the round trip is skipped
                    # and _params_device_resident stays true through a
                    # checkpointing fit
                    epoch_cbs = _as_list(epoch_end_callback)
                    need_host = any(getattr(cb, "_needs_host_params", True)
                                    for cb in epoch_cbs)
                    arg_params_out = aux_params_out = None
                    if (epoch_cbs and need_host) or \
                            not self._params_device_resident():
                        arg_params_out, aux_params_out = self.get_params()
                        self.set_params(arg_params_out, aux_params_out)
                    for callback in epoch_cbs:
                        callback(epoch, self.symbol, arg_params_out,
                                 aux_params_out)

                    if eval_data:
                        if accum is not None:
                            # validation updates the metric live (score() runs the
                            # numpy path) — drop the training snapshot so an eval
                            # Speedometer reads real values, not the stale cadence
                            accum.last_snapshot = None
                        with _tracing.span("fit.eval", category="module") as sp:
                            res = self.score(eval_data, validation_metric,
                                             score_end_callback=eval_end_callback,
                                             batch_end_callback=eval_batch_end_callback,
                                             epoch=epoch)
                        eval_ms.observe(sp.duration_ms)
                        for name, val in res:
                            self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name,
                                             val)
                    train_data.reset()
                    if el_session is not None:
                        el_session.on_epoch(epoch, eval_metric, train_data)
            if el_session is not None:
                # fit returning implies its checkpoints are durable
                _elastic.writer().flush()
        finally:
            # post-fit reads (and the next fit) must see live values,
            # not this run's last cadence snapshot
            eval_metric._device_accum = None
            if stat_rider is not None and accum is not None:
                accum.remove_rider(stat_rider)
            if health_session is not None:
                if accum is not None:
                    accum.remove_rider(health_session)
                health_session.close()


    def check(self, passes=None, pipeline=None):
        """Run the mxtpu.analysis verifier passes with everything this
        module knows — the bound data/label shapes, the provided
        parameter names (unused-arg detection), and the live fused train
        step (donation-safety audit). Returns a
        :class:`~mxtpu.analysis.Report`; ``report.ok`` is False when
        anything at warning severity or above fired.

        ``pipeline`` (a transform-name list, comma string, or True for
        the configured pipeline) additionally dry-runs the compile
        pipeline's transform passes and merges what each did — per-node
        provenance, acceptance/rejection with the offending Finding —
        into the report."""
        from ..analysis import check_module
        return check_module(self, passes=passes, pipeline=pipeline)

    # ------------------------------------------------ symbol/params accessors
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError

    def prepare(self, data_batch):
        pass

    def _device_step_view(self, data_batch):
        """(labels, outputs, pacing_token) of the last step as device
        arrays, or None when this module can't expose them — the fit loop
        then falls back to the per-batch numpy metric path."""
        return None

    def _params_device_resident(self):
        """True when the live parameters already reside on device under
        this module's control, making fit's per-epoch get_params/set_params
        host round-trip a no-op worth skipping."""
        return False

    # ------------------------------------------------ computation interface
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def stat_heads_rider(self):
        """A rider for `DeviceMetricAccum.add_rider` that carries statistic
        heads beside the loss to the host with the metric sync, or None
        (`Module.stat_heads_rider`)."""
        return None

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError
