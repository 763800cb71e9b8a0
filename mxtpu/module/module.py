"""Module: symbol + data-parallel executor group + optimizer.

Parity: python/mxnet/module/module.py (bind :351, init_optimizer :460 with the
update_on_kvstore decision, update :615, save/load_checkpoint :152).

TPU-native fast path: when the optimizer and binding allow it,
``init_optimizer`` arms a fused train step (module/fused.py) and
``forward_backward`` runs forward+backward+update as ONE donated XLA
program instead of the reference's forward / backward / per-parameter
updater sequence. ``MXTPU_FUSED_MODULE=0`` disables it."""
from __future__ import annotations

import logging
import os

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..base import MXNetError
from ..initializer import Uniform, InitDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None):
        super().__init__(logger=logger)
        if context is None:
            context = [ctx_mod.current_context()]
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = list(fixed_param_names) \
            if fixed_param_names is not None else []
        for names, typ, required in ((data_names, "data", True),
                                     (label_names, "label", False),
                                     (state_names, "state", True),
                                     (fixed_param_names, "fixed_param",
                                      True)):
            _check_input_names(symbol, names, typ, required)

        input_names = data_names + label_names + state_names
        self._data_names, self._label_names = data_names, label_names
        self._state_names = state_names
        self._param_names = [x for x in symbol.list_arguments()
                             if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self._stat_heads_found = None

        self._arg_params = self._aux_params = None
        self._params_dirty = False
        self._optimizer = self._kvstore = self._update_on_kvstore = None
        self._updater = self._preload_opt_states = self._grad_req = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

        self._fused = None             # FusedTrainStep when armed
        self._last_step_fused = False
        self._execs_parked = False
        self._monitor_installed = False
        self._monitor_adapter = None   # default-stat Monitor riding the
        # fused step's device tap kernels (obs/health.py) instead of
        # forcing the per-op execution path

    # staleness flags live on the fused step's (possibly shared) state, so
    # every bucket module of a BucketingModule sees one truth about whether
    # the device weights are ahead of the host dict / executor arrays
    @property
    def _fused_host_stale_(self):
        return self._fused is not None and self._fused.state.host_stale

    @_fused_host_stale_.setter
    def _fused_host_stale_(self, v):
        if self._fused is not None:
            self._fused.state.host_stale = bool(v)

    @property
    def _fused_exec_stale_(self):
        return self._fused is not None and self._fused.state.exec_stale

    @_fused_exec_stale_.setter
    def _fused_exec_stale_(self, v):
        if self._fused is not None:
            self._fused.state.exec_stale = bool(v)

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        async_write=False):
        """Legacy-format checkpoint files (+ versioned manifest).

        ``async_write`` routes the writes through the elastic snapshot
        writer (docs/elastic.md): with the fused step armed, the params
        are captured as a donation-safe DEVICE copy and serialized /
        fsynced / atomically renamed on the writer thread — the training
        loop never blocks on a device→host transfer or the disk.
        ``mxtpu.model.wait_checkpoints()`` / ``nd.waitall()`` drain
        pending writes."""
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        from ..model import _checkpoint_manifest
        # ONE param export feeds both the data file and the manifest
        # (with the fused step armed this is a device-side snapshot —
        # export_params, zero host transfer)
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v
                          for k, v in (aux_params or {}).items()})
        manifest = _checkpoint_manifest(save_dict, epoch)
        if async_write:
            from .. import elastic as _elastic
            _elastic.async_save_ndarrays(
                param_name, save_dict, manifest=manifest,
                on_done=lambda job, _p=param_name: logging.info(
                    'Saved checkpoint to "%s"', _p))
        else:
            import json as _json
            from ..elastic import snapshot as _snap
            nd.save(param_name, save_dict)
            _snap._write_atomic(param_name + ".manifest.json",
                                _json.dumps(manifest, indent=1).encode())
            logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name, async_write=async_write)
            logging.info('Saved optimizer state to "%s"', state_name)

    # ------------------------------------------------ properties
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        outs = self._exec_group.execs[0]
        shapes = self._symbol.infer_shape(
            **{d[0]: d[1] for d in self._data_shapes +
               (self._label_shapes or [])})[1]
        return list(zip(self._output_names, shapes))

    # ------------------------------------------------ params
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def device_state(self):
        """The training state where it lives, on the device, with no host
        copy (``get_params`` makes one). Returns a dict:

        * ``params``, ``aux`` — name -> device array (``jax.Array``);
        * ``opt_state`` — name -> the optimizer's state for that
          trainable parameter (a pytree of device arrays: momentum, or
          Adam's ``(mean, var)``), or ``None`` while the fused train step
          is not armed (the classic path keeps it in the Updater — see
          ``save_optimizer_states``);
        * ``dtypes`` — name -> the dtype (as a string) the Module BOUND
          each parameter and auxiliary state in, which is what
          ``init_params(arg_params=)`` of another dtype silently replaces.

        With the fused step armed (any ``fit`` with a supported optimizer)
        the trees are its live state: the next step donates their buffers,
        so read or copy what you need before training goes on. The dict
        itself is a fresh shallow copy. Needs ``bind``; before
        ``init_params`` the arrays are the bound zeros."""
        assert self.binded, "call bind before device_state"
        grp = self._exec_group
        bound = dict(zip(grp._param_names_out, grp.param_arrays))
        bound.update(zip(grp.aux_names, grp.aux_arrays))
        dtypes = {n: str(blocks[0].dtype) for n, blocks in bound.items()}
        if self._fused is not None:
            f = self._fused
            return {"params": dict(f.params), "aux": dict(f.aux),
                    "opt_state": dict(f.opt_state), "dtypes": dtypes}
        aux_names = set(grp.aux_names)
        return {"params": {n: b[0]._data for n, b in bound.items()
                           if n not in aux_names},
                "aux": {n: b[0]._data for n, b in bound.items()
                        if n in aux_names},
                "opt_state": None, "dtypes": dtypes}

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        if initializer is None:
            initializer = Uniform(0.01)

        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(block[0].shape, dtype=block[0].dtype)
                for name, block in zip(self._exec_group._param_names_out,
                                       self._exec_group.param_arrays)}
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(block[0].shape, dtype=block[0].dtype)
                for name, block in zip(self._exec_group.aux_names,
                                       self._exec_group.aux_arrays)}

        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache_arr = cache[name]
                if cache_arr is not arr:
                    cache_arr.copyto(arr)
            else:
                if not allow_missing:
                    if cache is not None:
                        raise RuntimeError(
                            "%s is not presented" % name)
                if initializer is not None:
                    initializer(InitDesc(name, attrs.get(name)), arr)

        for name, arr in sorted(self._arg_params.items()):
            desc_cache = arg_params if arg_params is not None else None
            if desc_cache is not None and name in desc_cache:
                _impl(name, arr, desc_cache)
            else:
                if arg_params is not None and not allow_missing:
                    raise RuntimeError("%s is not presented" % name)
                initializer(InitDesc(name, attrs.get(name)), arr)
        for name, arr in sorted(self._aux_params.items()):
            if aux_params is not None and name in aux_params:
                if aux_params[name] is not arr:
                    aux_params[name].copyto(arr)
            else:
                if initializer is not None:
                    initializer(InitDesc(name, attrs.get(name)), arr)

        self.params_initialized = True
        self._params_dirty = False
        if self._fused is not None:
            # fused mode: the per-node executors are dormant — syncing all
            # params into them here is ~270 per-array device dispatches per
            # epoch (seconds on a remote runtime). They re-sync lazily via
            # _sync_fused_to_execs the moment the classic path is driven.
            self._fused_exec_stale_ = True
        else:
            self._exec_group.set_params(self._arg_params, self._aux_params,
                                        allow_extra=allow_extra)
        self._restage_fused_params(incoming=arg_params)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            return
        if self._fused is not None:
            self._fused_exec_stale_ = True  # lazy re-sync (see init_params)
        else:
            self._exec_group.set_params(arg_params, aux_params,
                                        allow_extra=allow_extra)
        self._arg_params = dict(self._arg_params or {}, **(arg_params or {}))
        self._aux_params = dict(self._aux_params or {}, **(aux_params or {}))
        self.params_initialized = True
        self._params_dirty = False
        self._restage_fused_params(incoming=arg_params)

    def _sync_params_from_devices(self):
        if self._fused is not None and self._fused_host_stale_:
            args, aux = self._fused.export_params()
            self._arg_params.update(
                {n: v for n, v in args.items() if n in self._arg_params})
            self._aux_params.update(aux)
            self._fused_host_stale_ = False
        else:
            self._sync_fused_to_execs()   # a parked executor holds nothing
            self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    # ------------------------------------------------ bind
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        self._grad_req = grad_req
        if not for_training:
            assert not inputs_need_grad

        self._data_shapes, self._label_shapes = _parse_shapes(
            data_shapes, label_shapes, self._data_names, self._label_names)

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and \
                shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names)
        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes, self._label_shapes = _parse_shapes(
            data_shapes, label_shapes, self._data_names, self._label_names)
        self._exec_group.reshape(self._data_shapes, self._label_shapes)

    # ------------------------------------------------ optimizer
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and \
                "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {}
            if update_on_kvstore:
                idx2name.update(enumerate(self._exec_group._param_names_out))
            else:
                for k in range(len(self._context)):
                    idx2name.update(
                        {i * len(self._context) + k: n for i, n in
                         enumerate(self._exec_group._param_names_out)})
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._exec_group._param_names_out,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
        self.optimizer_initialized = True
        self._arm_fused()
        if self._monitor_adapter is not None and self._fused is None:
            # the fused step declined to arm — the adapter has no device
            # tap kernels to ride, so the monitor falls back to the
            # legacy per-op collection path it was a drop-in for
            mon = self._monitor_adapter
            self._monitor_adapter = None
            mon._adapter = None
            self._monitor_installed = True
            self._disarm_fused()
            self._exec_group.install_monitor(mon)
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _arm_fused(self):
        """Enable the one-program train step when semantics allow it.

        With an active mesh (``Module.fit(mesh=...)``, a surrounding
        ``sharding.use(...)``, or ``MXTPU_MESH``) the step is built under
        a :class:`~mxtpu.sharding.ShardingPlan` over the mesh devices —
        the SPMD path with cross-replica weight-update sharding. The
        plain (multi-)context path is unchanged."""
        self._fused = None
        if os.environ.get("MXTPU_FUSED_MODULE", "1") == "0":
            return
        from . import fused as _fused
        if (self._state_names or self.inputs_need_grad
                or self._monitor_installed
                or self._grad_req != "write"
                or not _fused.supports(self._optimizer)):
            return
        if self._kvstore is not None and "dist" in self._kvstore.type:
            return  # multi-worker aggregation stays on the kvstore path
        if len(set(self._work_load_list)) > 1:
            return  # uneven slices can't be expressed as a uniform mesh
        plan = self._resolve_sharding_plan()
        if plan is not None:
            devices = plan.mesh_ctx.devices
        else:
            n = len(self._context)
            if n > 1 and self._exec_group.batch_size % n != 0:
                return
            devices = [c.jax_device for c in self._context]
        shapes, types = self._pipeline_hints()
        self._fused = _fused.FusedTrainStep(
            self._symbol, devices, self._param_names, self._data_names,
            self._label_names, self._optimizer,
            fixed_param_names=self._fixed_param_names, logger=self.logger,
            plan=plan, graph_shapes=shapes, graph_types=types, module=self)
        self._fused.load(self._arg_params, self._aux_params)
        self._fused_host_stale_ = False
        self._fused_exec_stale_ = False

    def _pipeline_hints(self):
        """Shape/dtype hints for the compile pipeline's analyses and the
        verifier re-run that gates every transform: the bound data/label
        shapes plus the initialized parameter/aux shapes — everything a
        real bind knows."""
        shapes = {}
        types = {}
        for d in (self._data_shapes or []) + (self._label_shapes or []):
            shapes[d.name] = tuple(d.shape)
        for params in (self._arg_params, self._aux_params):
            for n, v in (params or {}).items():
                shapes[n] = tuple(v.shape)
                types[n] = v.dtype
        return shapes, types

    def _resolve_sharding_plan(self):
        """The ShardingPlan for the active mesh, or None for the legacy
        per-context path. The mesh is declined (with a log line, never
        silently wrong math) when the batch does not divide over the
        data axis — the naive fallback of SNIPPETS [3] would replicate
        the batch and 'train' the same examples n times."""
        from .. import sharding as _sharding
        mctx = _sharding.current()
        if mctx is None or len(mctx.devices) <= 1:
            return None
        if mctx.n_data > 1 and \
                self._exec_group.batch_size % mctx.n_data != 0:
            self.logger.warning(
                "sharding: batch size %d does not divide over the %d-way "
                "data axis — mesh declined, falling back to the "
                "single-device fused path",
                self._exec_group.batch_size, mctx.n_data)
            return None
        from ..sharding import plan_for_module
        return plan_for_module(self, mctx)

    def _restage_fused_params(self, incoming=None):
        """Re-stage host params into the fused step after set_params,
        WITHOUT touching optimizer state (parity: set_params never resets
        momentum). The fit loop's epoch-end get_params/set_params round
        trip passes back the very dicts get_params returned — that no-op
        is skipped by identity."""
        if self._fused is None:
            return
        if incoming is not None and incoming is self._arg_params and \
                not self._fused_host_stale_:
            return
        import jax as _jax
        import jax.numpy as _jnp

        def _stage(n, v):
            data = v._data
            if isinstance(data, _jax.Array):
                # already on device: snapshot so the fused step's donation
                # can't invalidate the caller's NDArray through aliasing
                data = _jnp.copy(data)
            return self._fused._put(data, self._fused._param_spec(n))

        for n, v in (self._arg_params or {}).items():
            if n in self._fused.params:
                self._fused.params[n] = _stage(n, v)
        for n, v in (self._aux_params or {}).items():
            self._fused.aux[n] = _stage(n, v)
        self._fused_host_stale_ = False
        self._fused_exec_stale_ = True

    def forward_backward(self, data_batch):
        """One fused program (fwd+bwd+update) when armed; the update that
        follows in the fit loop is then a no-op."""
        from .. import profiler as _prof
        if self._fused is not None and _prof.ops_enabled():
            # operator-mode profiling needs the node-at-a-time executors;
            # the classic update() that follows will retire the fused step
            # (weights + optimizer state carried over)
            self._sync_fused_to_execs()
        if self._fused is None or _prof.ops_enabled():
            self._last_step_fused = False
            return super().forward_backward(data_batch)
        labels = data_batch.label if data_batch.label is not None else []
        if self._monitor_adapter is not None \
                and self._fused._health_taps is None:
            # stepping outside fit (manual train loop): arm the taps the
            # adapter install deferred
            self._fused.arm_health(
                taps=self._monitor_adapter.re_prog.pattern)
        if not self._execs_parked:
            self._park_execs()
        self._fused.step(data_batch.data, labels)
        self._last_step_fused = True
        self._fused_host_stale_ = True
        self._fused_exec_stale_ = True
        self._params_dirty = True

    def _park_execs(self):
        """While the fused step trains, the per-node executors are dormant:
        their parameter arrays go stale with its first step and nobody
        writes their gradient arrays; `_sync_fused_to_execs` re-fills them
        the moment the classic path is driven. Until then they would hold a
        second copy of every weight and a whole gradient beside the step's
        own (3.9 GB at 928 M parameters in bfloat16, which kept the step
        program from loading: PERF.md, PR 28). Hand those buffers back to
        the device; a placeholder of the same shape and dtype stands in,
        and any use of it before the re-fill raises."""
        import jax as _jax
        names = set(self._fused.params)
        for exe in self._exec_group.execs:
            for table in (exe.arg_dict, exe.grad_dict):
                for name, arr in table.items():
                    if name in names and arr is not None:
                        arr._data = _jax.ShapeDtypeStruct(arr.shape, arr.dtype)
        self._fused_exec_stale_ = True
        self._execs_parked = True

    def _sync_fused_to_execs(self):
        if self._fused is None or not self._fused_exec_stale_:
            return
        import jax as _jax
        import jax.numpy as _jnp
        for i, exe in enumerate(self._exec_group.execs):
            dev = self._context[i].jax_device
            for name, v in self._fused.params.items():
                if name in exe.arg_dict:
                    exe.arg_dict[name]._data = _jax.device_put(v, dev)
                g = exe.grad_dict.get(name) if self._execs_parked else None
                if g is not None:
                    g._data = _jnp.zeros(g.shape, g.dtype, device=dev)
            for name, v in self._fused.aux.items():
                if name in exe.aux_dict:
                    exe.aux_dict[name]._data = _jax.device_put(v, dev)
        self._fused_exec_stale_ = False
        self._execs_parked = False

    # ------------------------------------------------ compute
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._sync_fused_to_execs()
        self._last_step_fused = False
        curr_data_shapes = tuple(i.shape for i in self._data_shapes)
        new_data_shapes = tuple(i.shape for i in data_batch.data)
        if curr_data_shapes != new_data_shapes:
            if hasattr(data_batch, "provide_data") and data_batch.provide_data:
                new_dshape = data_batch.provide_data
            else:
                new_dshape = [(i.name, shape) for i, shape in
                              zip(self._data_shapes, new_data_shapes)]
            if hasattr(data_batch, "provide_label") and data_batch.provide_label:
                new_lshape = data_batch.provide_label
            elif data_batch.label:
                new_lshape = [(i.name, j.shape) for i, j in
                              zip(self._label_shapes, data_batch.label)]
            else:
                new_lshape = None
            self.reshape(new_dshape, new_lshape)
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Parity module.py:615: either optimizer-on-kvstore push/pull, or
        local updater after kvstore gradient aggregation."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if self._last_step_fused:
            return  # the fused program already applied the update
        if self._fused is not None:
            # The caller is driving the classic forward/backward/update loop;
            # keep ONE source of truth for weights and optimizer state by
            # retiring the fused step (its params were already synced into
            # the executors by forward(); hand its optimizer state to the
            # updater so momentum/Adam moments survive the switch).
            self._disarm_fused()
        if self._update_on_kvstore:
            _update_params_on_kvstore(self._exec_group.param_arrays,
                                      self._exec_group.grad_arrays,
                                      self._kvstore,
                                      self._exec_group._param_names_out)
        else:
            _update_params(self._exec_group.param_arrays,
                           self._exec_group.grad_arrays,
                           updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore,
                           param_names=self._exec_group._param_names_out)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._last_step_fused:
            outs = [nd.NDArray(o) for o in self._fused.outputs]
            return outs if merge_multi_context else [[o] for o in outs]
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        if self._last_step_fused:
            eval_metric.update(list(labels),
                               self._metric_outputs(self.get_outputs()))
            return
        self._exec_group.update_metric(eval_metric, labels,
                                       skip=self._stat_head_indices())

    # -------------------------------------------- statistics beside the loss
    def _stat_heads(self):
        """[(output index, op, attrs)] of the symbol's heads that are no
        prediction but a statistic riding beside the loss (an expert
        layer's loads): outputs of an op that says what to make of them
        (`OpDef.on_fetch`). A metric never sees them; `fit` fetches them
        with the metric's sums (`stat_heads_rider`)."""
        if self._stat_heads_found is None:
            self._stat_heads_found = [
                (i, node.op, node.parsed_attrs(), idx)
                for i, (node, idx) in enumerate(self._symbol._outputs)
                if node.op is not None and node.op.on_fetch is not None]
        return self._stat_heads_found

    def _stat_head_indices(self):
        return {h[0] for h in self._stat_heads()}

    def _metric_outputs(self, outs):
        skip = self._stat_head_indices()
        return [o for i, o in enumerate(outs) if i not in skip] if skip \
            else list(outs)

    def stat_heads_rider(self):
        """The rider (`DeviceMetricAccum.add_rider`) that takes the last
        step's statistic heads to the host with the metric sync and hands
        each op its own (`OpDef.on_fetch`); None where the symbol has no
        such head."""
        return _StatHeadsRider(self) if self._stat_heads() else None

    def _device_step_view(self, data_batch):
        """(labels, outputs, pacing_token) for the last step, all device
        arrays / device-backed NDArrays — the async fit loop feeds these
        to a DeviceMetricAccum and paces on the token, never touching the
        host. Fused steps reuse the labels the step already device-put."""
        if type(self).update_metric is not Module.update_metric:
            # a subclass customized per-batch metric semantics — the fit
            # loop must keep calling its override, not bypass it
            return None
        if self._last_step_fused:
            outs = self._metric_outputs(self._fused.outputs)
            labels = self._fused.last_labels
            if labels is None or len(labels) != len(data_batch.label or []):
                labels = list(data_batch.label or [])
            return labels, outs, (outs[0] if outs else None)
        if self._exec_group is None or len(self._exec_group.execs) != 1:
            # multi-exec classic path slices labels per executor — a
            # merged-batch device kernel would change mean-per-update
            # metrics (MSE/MAE/RMSE: mean over merged batch != mean of
            # per-slice means); keep the numpy path's exact numerics
            return None
        outs = self._metric_outputs(
            self._exec_group.get_outputs(merge_multi_context=True))
        return (list(data_batch.label or []), outs,
                (outs[0]._data if outs else None))

    def _params_device_resident(self):
        """True when the live weights are the fused step's device state —
        fit then skips its per-epoch get_params/set_params host round-trip
        (checkpoint callbacks still pull lazily via export_params)."""
        return self._fused is not None

    def _disarm_fused(self):
        """Retire the fused step: flush its weights/opt state to the classic
        path so training continues seamlessly on the executors."""
        if self._fused is None:
            return
        self._sync_fused_to_execs()
        if self._fused_host_stale_:
            self._sync_params_from_devices()
        import pickle
        if self._updater is not None:
            self._updater.set_states(pickle.dumps(
                self._fused.export_opt_state()))
        elif self._update_on_kvstore and \
                getattr(self._kvstore, "_updater", None) is not None:
            # optimizer-on-kvstore keys states by param NAME (model.py
            # _initialize_kvstore inits by name)
            from ..ndarray.ndarray import _bulk_tree_to_numpy
            states = _bulk_tree_to_numpy(
                {n: self._fused.opt_state[n]
                 for n in self._fused.trainable})
            self._kvstore._updater.set_states(pickle.dumps(states))
        self._fused = None

    def install_monitor(self, mon):
        assert self.binded
        if getattr(mon, "_default_stat", False) \
                and os.environ.get("MXTPU_MONITOR_ADAPTER", "1") != "0" \
                and (self._fused is not None
                     or not self.optimizer_initialized):
            # default abs-mean stat: ride the fused step's device tap
            # kernels (obs/health.py) — pattern-matched tensors reduce
            # on device and reach the host at the metric-sync cadence,
            # and the sampled batch stays on the fused path. Installed
            # before the optimizer, the choice is provisional:
            # init_optimizer falls back to the per-op path below when
            # the fused step declines to arm. Custom stat_funcs are
            # arbitrary host code — always the legacy path.
            self._monitor_adapter = mon
            mon.bind_adapter(self)
            if self._fused is not None:
                self._fused.arm_health(taps=mon.re_prog.pattern)
            return
        # per-op monitoring needs the unfused executors
        self._monitor_installed = True
        self._disarm_fused()
        self._exec_group.install_monitor(mon)

    # ------------------------------------------------ optimizer states
    def save_optimizer_states(self, fname, async_write=False):
        assert self.optimizer_initialized
        if self._fused is not None:
            from .. import elastic as _elastic
            plan = self._fused._plan
            if plan is not None and plan.sharded_opt_names():
                # active mesh with weight-update sharding: the legacy
                # pickle serialized the per-process shard view AS IF
                # global. Emit the sharded manifest instead — each
                # process writes only its addressable shards, specs
                # recorded, restore preserves the per-chip 1/n split.
                _elastic.save_sharded_opt_states(fname, self._fused,
                                                 async_write=async_write)
                return
            import pickle
            if async_write:
                # device snapshot + async D2H; materialize + pickle on
                # the writer — no training-thread transfer stall
                _elastic.async_save_opt_states_pickle(fname, self._fused)
                return
            with open(fname, "wb") as fout:
                fout.write(pickle.dumps(self._fused.export_opt_state()))
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        from ..model import wait_checkpoints
        wait_checkpoints()  # drain an in-flight async write of this file
        if self._fused is not None:
            with open(fname, "rb") as fin:
                head = fin.read(1)
            if head == b"{":  # sharded manifest (save path above)
                from .. import elastic as _elastic
                _elastic.load_sharded_opt_states(fname, self._fused)
                return
            import pickle
            with open(fname, "rb") as fin:
                self._fused.import_opt_state(pickle.loads(fin.read()))
        elif self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            self._updater.set_states(open(fname, "rb").read())

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True
        if shared_module._fused is not None:
            # train this symbol through the SAME fused device state
            # (BucketingModule: every bucket advances one set of weights
            # and optimizer moments, like the reference's shared executor
            # parameter arrays)
            from . import fused as _fused_mod
            shapes, types = self._pipeline_hints()
            self._fused = _fused_mod.FusedTrainStep(
                self._symbol, shared_module._fused.devices,
                self._param_names, self._data_names, self._label_names,
                self._optimizer,
                fixed_param_names=self._fixed_param_names,
                logger=self.logger, state=shared_module._fused.state,
                plan=shared_module._fused._plan,
                graph_shapes=shapes, graph_types=types, module=self)
            self._fused.adopt_state()


def _parse_shapes(data_shapes, label_shapes, data_names, label_names):
    from ..io import DataDesc
    ds = [x if isinstance(x, DataDesc) else DataDesc(*x) for x in data_shapes]
    ls = None
    if label_shapes is not None and len(label_shapes) > 0:
        ls = [x if isinstance(x, DataDesc) else DataDesc(*x)
              for x in label_shapes]
    return ds, ls


class _StatHeadsRider:
    """Takes a Module's statistic heads (`Module._stat_heads`) of the last
    step to the host in the metric sync's one transfer and hands each op its
    own outputs."""

    def __init__(self, module):
        self._module = module

    def pull(self):
        mod = self._module
        if mod._last_step_fused:
            outs = mod._fused.outputs
        else:
            outs = [o._data for o in
                    mod._exec_group.get_outputs(merge_multi_context=True)]
        if not outs:
            return None
        return [outs[h[0]] for h in mod._stat_heads()]

    def deliver(self, host):
        by_op = {}
        for (_, op, attrs, idx), value in zip(self._module._stat_heads(),
                                              host):
            by_op.setdefault(op.name, (op, []))[1].append((attrs, idx, value))
        for op, heads in by_op.values():
            op.on_fetch(heads)
