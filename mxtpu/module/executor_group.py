"""DataParallelExecutorGroup: batch-sharded executors over device contexts.

Parity: python/mxnet/module/executor_group.py:99 + executor_manager.py:31
(_split_input_slice). One Executor per context, each a whole-graph XLA program;
scatter slices inputs, gather concatenates outputs. On a real TPU pod the fused
pjit data-parallel path in mxtpu.parallel supersedes this per-device loop, but
this class preserves the reference's multi-context semantics (tested with
multiple CPU devices, the reference's own trick — SURVEY.md §4)."""
from __future__ import annotations

import numpy as _np

from .. import ndarray as nd
from ..base import MXNetError
from ..io import DataDesc


def _split_input_slice(batch_size, work_load_list):
    """Parity executor_manager.py:31."""
    total = sum(work_load_list)
    if batch_size < len(work_load_list):
        raise MXNetError("batch size must be >= number of devices")
    slices = []
    begin = 0
    for i, load in enumerate(work_load_list):
        end = batch_size if i == len(work_load_list) - 1 else \
            begin + int(round(batch_size * load / total))
        slices.append(slice(begin, end))
        begin = end
    return slices


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad, shared_group=None,
                 logger=None, fixed_param_names=None, grad_req="write",
                 state_names=None):
        self.symbol = symbol
        self.contexts = contexts
        self.workload = workload or [1] * len(contexts)
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = fixed_param_names or []
        self.state_names = state_names or []
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        self.grad_req = {}
        for name in self.arg_names:
            if name in self.param_names:
                self.grad_req[name] = "null" if name in self.fixed_param_names \
                    else grad_req
            elif name in [d[0] for d in (data_shapes or [])]:
                self.grad_req[name] = grad_req if inputs_need_grad else "null"
            else:
                self.grad_req[name] = "null"
        if not for_training:
            self.grad_req = {k: "null" for k in self.arg_names}

        self.execs = []
        self.data_names = None
        self.label_names = None
        self.slices = None
        self.batch_size = None
        self._default_execs = None
        if shared_group is not None:
            self.shared_data_arrays = shared_group.shared_data_arrays
        else:
            self.shared_data_arrays = [{} for _ in contexts]
        self.bind_exec(data_shapes, label_shapes, shared_group)

    # ------------------------------------------------ bind
    def decide_slices(self, data_shapes):
        self.batch_size = data_shapes[0][1][0]
        self.slices = _split_input_slice(self.batch_size, self.workload)
        return self.slices

    def _scaled_slice(self, islice, dim0):
        """Scale a batch slice for arrays whose leading dim is a multiple of
        the batch size (e.g. sequence-LM labels flattened to (B*T,)), so each
        context receives the rows that match its data shard. dim0 == batch
        (the common case) is the identity."""
        if self.batch_size and dim0 != self.batch_size \
                and dim0 % self.batch_size == 0:
            k = dim0 // self.batch_size
            return slice(islice.start * k, islice.stop * k)
        return islice

    def _sliced_shape(self, shapes, i, scale=False):
        out = []
        for desc in shapes:
            name, shape = desc[0], tuple(desc[1])
            islice = self._scaled_slice(self.slices[i], shape[0]) \
                if scale else self.slices[i]
            out.append(DataDesc(name,
                                (islice.stop - islice.start,) + shape[1:],
                                getattr(desc, "dtype", "float32")))
        return out

    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes
        self.data_names = [d[0] for d in data_shapes]
        self.label_names = [l[0] for l in label_shapes] if label_shapes else []
        self.decide_slices(data_shapes)
        self.execs = []
        for i, ctx in enumerate(self.contexts):
            dshapes = self._sliced_shape(data_shapes, i)
            # labels may carry a flattened (k*batch,) leading dim; bind
            # them at the scaled size that forward() will actually feed
            lshapes = self._sliced_shape(label_shapes, i, scale=True) \
                if label_shapes else []
            input_shapes = {d.name: d.shape for d in dshapes}
            input_shapes.update({l.name: l.shape for l in lshapes})
            type_dict = {d.name: str(d.dtype) for d in dshapes + lshapes}
            shared_exec = shared_group.execs[i] if shared_group else None
            exe = self.symbol.simple_bind(ctx=ctx, grad_req=self.grad_req,
                                          type_dict=type_dict,
                                          shared_exec=shared_exec,
                                          **input_shapes)
            self.execs.append(exe)
        self.param_arrays = [[e.arg_dict[name] for e in self.execs]
                             for name in self.arg_names
                             if name in self.param_names]
        self.grad_arrays = [[e.grad_dict.get(name) for e in self.execs]
                            for name in self.arg_names
                            if name in self.param_names]
        self.aux_arrays = [[e.aux_dict[name] for e in self.execs]
                           for name in self.aux_names]
        self._param_names_out = [n for n in self.arg_names
                                 if n in self.param_names]

    def reshape(self, data_shapes, label_shapes):
        if data_shapes == self.data_shapes and label_shapes == self.label_shapes:
            return
        self.bind_exec(data_shapes, label_shapes, reshape=True)

    # ------------------------------------------------ params
    def set_params(self, arg_params, aux_params, allow_extra=False):
        for exe in self.execs:
            exe.copy_params_from(arg_params, aux_params,
                                 allow_extra_params=allow_extra)

    def get_params(self, arg_params, aux_params):
        for name, block in zip(self._param_names_out, self.param_arrays):
            weight = block[0]
            if len(block) > 1:
                acc = block[0].asnumpy()
                for w in block[1:]:
                    acc = acc + w.asnumpy()
                weight_np = acc / len(block)
                arg_params[name] = nd.array(weight_np, dtype=block[0].dtype)
            else:
                arg_params[name] = weight.copy()
        for name, block in zip(self.aux_names, self.aux_arrays):
            arg = block[0]
            if len(block) > 1:
                acc = block[0].asnumpy()
                for w in block[1:]:
                    acc = acc + w.asnumpy()
                aux_params[name] = nd.array(acc / len(block), dtype=arg.dtype)
            else:
                aux_params[name] = arg.copy()

    # ------------------------------------------------ compute
    def forward(self, data_batch, is_train=None):
        if is_train is None:
            is_train = self.for_training
        data = data_batch.data
        labels = data_batch.label if data_batch.label is not None else []
        for i, exe in enumerate(self.execs):
            islice = self.slices[i]
            feed = {}
            for name, arr in zip(self.data_names, data):
                feed[name] = arr[islice].as_in_context(self.contexts[i])
            for name, arr in zip(self.label_names, labels):
                if name in exe.arg_dict:
                    lslice = self._scaled_slice(islice, arr.shape[0])
                    feed[name] = arr[lslice].as_in_context(self.contexts[i])
            exe.forward(is_train=is_train, **feed)

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True for backward"
        for i, exe in enumerate(self.execs):
            if out_grads is None:
                exe.backward()
            else:
                islice = self.slices[i]
                og = [g[self._scaled_slice(islice, g.shape[0])]
                      .as_in_context(self.contexts[i])
                      for g in out_grads]
                exe.backward(out_grads=og)

    def get_outputs(self, merge_multi_context=True):
        outputs = [[exe.outputs[i] for exe in self.execs]
                   for i in range(len(self.execs[0].outputs))]
        if merge_multi_context:
            return [out[0] if len(out) == 1 else
                    nd.concatenate(out, axis=0) for out in outputs]
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        grads = [[exe.grad_dict[name] for exe in self.execs]
                 for name in self.data_names]
        if merge_multi_context:
            return [g[0] if len(g) == 1 else nd.concatenate(g, axis=0)
                    for g in grads]
        return grads

    def update_metric(self, eval_metric, labels, skip=()):
        """`skip`: indices of outputs that are no prediction (statistic
        heads, `Module._stat_heads`)."""
        for texec, islice in zip(self.execs, self.slices):
            labels_slice = [label[self._scaled_slice(islice, label.shape[0])]
                            for label in labels]
            eval_metric.update(labels_slice,
                               [o for i, o in enumerate(texec.outputs)
                                if i not in skip])

    def install_monitor(self, mon):
        for exe in self.execs:
            mon.install(exe)
