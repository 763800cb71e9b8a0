"""Operator registry: every op is a pure JAX function plus metadata.

TPU-native replacement for the reference's NNVM op registry + FCompute kernels
(SURVEY.md L5/L6; include/mxnet/op_attr_types.h:171-240, 128x NNVM_REGISTER_OP +
54x MXNET_REGISTER_OP_PROPERTY). Instead of per-device kernel templates, each op
registers ONE pure function over jax arrays; imperative invoke jit-compiles it
per (attrs, shapes) and the graph executor inlines it into a whole-graph XLA
program, so memory planning / fusion / scheduling are XLA's job rather than
hand-written passes (replaces src/executor/*_pass.cc and the threaded engine's
per-op dispatch for compute).

Shape/type inference comes for free from ``jax.eval_shape`` over the same impl
(replaces src/executor/infer_graph_attr_pass.cc).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError, parse_attr

__all__ = ["OpDef", "register", "get_op", "list_ops", "Required", "invoke", "AttrDict"]

_OPS = {}


class Required:
    """Marker for a required attribute; carries the prototype type."""

    def __init__(self, proto):
        self.proto = proto

    def __repr__(self):
        return "Required(%s)" % getattr(self.proto, "__name__", self.proto)


class AttrDict(dict):
    """Hashable, attribute-access dict of parsed op attributes."""

    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError:
            raise AttributeError(k)

    def __hash__(self):
        return hash(tuple(sorted((k, _hashable(v)) for k, v in self.items())))


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    return v


class OpDef:
    """Metadata + impl for one operator.

    Parameters
    ----------
    name : canonical op name (MXNet-compatible, e.g. 'Convolution', 'elemwise_add')
    fn : callable(attrs, *inputs) -> jax array or tuple of arrays.
        Pure; traced under jit. If ``needs_rng``, signature is (attrs, rng, *inputs).
    arg_names : names of tensor inputs in order.
    attrs : dict of attr name -> default (or Required(type)).
    num_outputs : int or callable(attrs)->int.
    variadic : if set, name of the attr holding the input count ('num_args');
        tensor inputs are then arg0..argN.
    needs_rng : op consumes a PRNG key (random ops, Dropout).
    aliases : extra registered names.
    loss_like : output is a head-loss (backward ignores incoming grads -- the op's
        fn must use jax.custom_vjp to encode that, like SoftmaxOutput).
    """

    def __init__(self, name, fn, arg_names=("data",), attrs=None, num_outputs=1,
                 variadic=None, needs_rng=False, aliases=(), loss_like=False,
                 aux_names=(), mutate_inputs=(), infer_args=None, doc=None,
                 on_fetch=None):
        self.name = name
        self.fn = fn
        self.arg_names = arg_names if callable(arg_names) else list(arg_names)
        self.attrs_spec = dict(attrs or {})
        self.num_outputs = num_outputs
        self.variadic = variadic
        self.needs_rng = needs_rng
        self.aliases = aliases
        self.loss_like = loss_like
        # aux_names: trailing tensor inputs that are auxiliary states (reference:
        # BatchNorm moving_mean/moving_var). fn returns num_outputs visible outputs
        # followed by len(aux_names) updated aux values; the invoker writes those
        # back (imperative mutates the aux NDArrays; executor updates aux_states).
        self.aux_names = list(aux_names)
        # infer_args(attrs, in_shapes_with_None) -> full input shape list; fills
        # parameter shapes top-down (the only place the reference's bidirectional
        # InferShape pass is semantically required: weights/bias/bn stats)
        self.infer_args = infer_args
        self.mutate_inputs = mutate_inputs  # indices of inputs updated in place via out=
        # on_fetch([(attrs, output index, host value)]): what the op makes
        # of its own outputs among a Module's heads (a statistic that rides
        # beside the loss) when fit fetches them at a metric sync
        self.on_fetch = on_fetch
        self.doc = doc or (fn.__doc__ or "")
        self._jit_cache = {}

    # ---- attrs ----
    def parse_attrs(self, kwargs):
        out = AttrDict()
        for k, default in self.attrs_spec.items():
            if k in kwargs and kwargs[k] is not None:
                proto = default.proto if isinstance(default, Required) else default
                out[k] = parse_attr(kwargs[k], proto if proto is not None else None)
            elif isinstance(default, Required):
                raise MXNetError("op %s: required attr '%s' missing" % (self.name, k))
            else:
                out[k] = default
        extra = set(kwargs) - set(self.attrs_spec) - {"name", "out", "ctx", "dtype_hint"}
        # silently ignore unknown attrs the reference accepts for fwd-compat
        return out

    def n_out(self, attrs):
        return self.num_outputs(attrs) if callable(self.num_outputs) else self.num_outputs

    def input_names(self, attrs=None, n=None):
        if self.variadic:
            count = n if n is not None else (attrs or {}).get(self.variadic, 0)
            return ["arg%d" % i for i in range(count)]
        if callable(self.arg_names):
            return list(self.arg_names(attrs or AttrDict()))
        return self.arg_names

    # ---- compiled imperative execution ----
    def jitted(self, attrs):
        key = hash(attrs)
        f = self._jit_cache.get(key)
        if f is None:
            f = jax.jit(functools.partial(self.fn, attrs))
            self._jit_cache[key] = f
        return f

    def apply(self, attrs, inputs, rng=None):
        """Run the op eagerly (async via XLA dispatch). Returns tuple of arrays."""
        if self.needs_rng:
            out = self.jitted(attrs)(rng, *inputs)
        else:
            out = self.jitted(attrs)(*inputs)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return tuple(out)

    def trace(self, attrs, inputs, rng=None):
        """Run the op inside an outer trace (graph executor)."""
        if self.needs_rng:
            out = self.fn(attrs, rng, *inputs)
        else:
            out = self.fn(attrs, *inputs)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return tuple(out)

    def infer(self, attrs, in_avals):
        """Shape/dtype inference via jax.eval_shape (no FLOPs, no memory)."""
        structs = [jax.ShapeDtypeStruct(s, d) for s, d in in_avals]
        if self.needs_rng:
            rng = jax.ShapeDtypeStruct((2,), _np.uint32)
            out = jax.eval_shape(lambda r, *a: self.fn(attrs, r, *a), rng, *structs)
        else:
            out = jax.eval_shape(lambda *a: self.fn(attrs, *a), *structs)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return [(tuple(o.shape), o.dtype) for o in out]


def register(name, fn=None, **kwargs):
    """Register an op. Usable as decorator or direct call."""

    def _do(f):
        op = OpDef(name, f, **kwargs)
        _OPS[name] = op
        for a in op.aliases:
            _OPS[a] = op
        return f

    if fn is not None:
        _do(fn)
        return _OPS[name]
    return _do


def register_op(op):
    _OPS[op.name] = op
    for a in op.aliases:
        _OPS[a] = op
    return op


def get_op(name):
    if name not in _OPS:
        raise MXNetError("operator '%s' is not registered" % name)
    return _OPS[name]


def op_exists(name):
    return name in _OPS


def list_ops():
    return sorted(_OPS)


def invoke(name, inputs, attrs_kwargs, rng=None):
    """Imperative invoke on raw jax arrays: parse attrs, jit, run."""
    op = get_op(name)
    attrs = op.parse_attrs(attrs_kwargs)
    return op, attrs, op.apply(attrs, inputs, rng=rng)
