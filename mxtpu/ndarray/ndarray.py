"""NDArray: the imperative tensor, backed by a jax.Array.

Parity: include/mxnet/ndarray.h:93 + src/ndarray/ (SURVEY.md §2.1). TPU-native
mapping of the reference's async engine contract:
  - every op returns immediately (XLA async dispatch == engine PushAsync);
  - ``wait_to_read`` / ``asnumpy`` block (== WaitToRead / engine sync points);
  - per-var serialization is inherent: arrays are immutable, "mutation"
    (x[:]=, out=, aux updates) rebinds the wrapper to a new buffer, so the
    multi-reader/single-writer protocol of ThreadedVar is satisfied by
    construction -- no dependency engine needed.
Device placement follows the Context (committed jax buffers), mirroring
Context/ctx semantics of the reference.
"""
from __future__ import annotations

import itertools
import struct

import jax
import jax.numpy as jnp
import numpy as _np

from .. import autograd as _ag
from .. import random as _rnd
from ..base import MXNetError
from ..context import Context, current_context
from ..diagnostics import ledger_mod as _ledger_mod
from ..ops.registry import get_op


def _track_alloc(arr):
    """Memory-ledger seam for the creation functions (array/zeros/ones/
    full): ties the fresh device buffer's bytes to its lifetime, tagged
    with the ambient allocation origin ('ndarray' by default). Reads the
    module flag directly — one global load when diagnostics are off."""
    if _ledger_mod._ENABLED and isinstance(arr._data, jax.Array):
        _ledger_mod._LEDGER.track(arr._data, ctx=str(arr._ctx))
    return arr

__all__ = ["NDArray", "array", "invoke_op", "waitall", "zeros", "ones", "empty",
           "full", "arange", "concatenate", "save", "load", "imperative_invoke"]

_uid_counter = itertools.count()

_DTYPE_COERCE = {_np.dtype("float64"): _np.dtype("float32"),
                 _np.dtype("int64"): _np.dtype("int32")}


def _coerce_dtype(dt, explicit):
    dt = _np.dtype(dt)
    if explicit:
        return dt
    return _DTYPE_COERCE.get(dt, dt)


class NDArray:
    """An n-dimensional array on a device context."""

    __slots__ = ("_data", "_ctx", "_uid", "grad", "_grad_req", "_tape_entry",
                 "_deferred_shape", "stype", "__weakref__")

    def __init__(self, data, ctx=None):
        self._data = data
        self._ctx = ctx or current_context()
        self._uid = next(_uid_counter)
        self.grad = None
        self._grad_req = "null"
        self._tape_entry = None
        self.stype = "default"

    # ------------------------------------------------ properties
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def T(self):
        return invoke_op("transpose", [self], {})[0]

    @property
    def handle(self):
        return self._uid

    # ------------------------------------------------ sync / host transfer
    def wait_to_read(self):
        jax.block_until_ready(self._data)

    def wait_to_write(self):
        jax.block_until_ready(self._data)

    def asnumpy(self):
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def astype(self, dtype):
        return invoke_op("Cast", [self],
                         {"dtype": str(_np.dtype(dtype))})[0]

    def copy(self):
        # _copy yields a fresh buffer AND rides the autograd tape
        return invoke_op("_copy", [self], {})[0]

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other._ctx.jax_device)
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device), other)
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        # a cross-device copy is a real new allocation (per-device weight
        # staging in the serving pool): account it like a creation
        return _track_alloc(NDArray(jax.device_put(self._data,
                                                   ctx.jax_device), ctx))

    def reshape(self, shape):
        if isinstance(shape, int):
            shape = (shape,)
        return invoke_op("Reshape", [self], {"shape": tuple(shape)})[0]

    def broadcast_to(self, shape):
        return invoke_op("broadcast_to", [self],
                         {"shape": tuple(shape)})[0]

    def expand_dims(self, axis):
        return invoke_op("expand_dims", [self], {"axis": int(axis)})[0]

    def flatten(self):
        return invoke_op("Flatten", [self], {})[0]

    # ------------------------------------------------ autograd
    def attach_grad(self, grad_req="write", stype=None):
        grad = NDArray(jnp.zeros_like(self._data), self._ctx)
        _ag.mark_variables([self], [grad], grad_req)

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _ag.backward([self], [out_grad] if out_grad is not None else None,
                     retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------ indexing
    def __getitem__(self, key):
        if _ag.is_recording():
            # slicing must ride the tape or backward silently treats the
            # view as a constant (zero grads); basic keys lower to the
            # registered slice/take ops, anything fancier raises rather
            # than sever the tape
            if isinstance(key, NDArray):
                return invoke_op("take", [self, key],
                                 {"axis": 0, "mode": "clip"})[0]
            rec = self._basic_index_recorded(key)
            if rec is None:
                raise MXNetError(
                    "autograd: index %r is not differentiable-recordable; "
                    "use basic slices/ints or take() while recording"
                    % (key,))
            return rec
        if isinstance(key, NDArray):
            key = key._data.astype(jnp.int32)
        return NDArray(self._data[key], self._ctx)

    def _basic_index_recorded(self, key):
        """Lower int/slice (and tuples of them) onto the slice op (+ take
        for strided axes, Reshape for dropped integer axes); None for
        unsupported keys."""
        ks = key if isinstance(key, tuple) else (key,)
        if any(k is Ellipsis for k in ks):
            i = next(i for i, k in enumerate(ks) if k is Ellipsis)
            fill = self.ndim - (len(ks) - 1)
            if fill < 0 or any(k is Ellipsis for k in ks[i + 1:]):
                return None
            ks = ks[:i] + (slice(None),) * fill + ks[i + 1:]
        if len(ks) > self.ndim:
            return None
        begin, end, drop, strided = [], [], [], []
        for d, k in enumerate(ks):
            if isinstance(k, (bool, _np.bool_)):
                return None  # bool is an int subclass but means masking
            if isinstance(k, (int, _np.integer)):
                b = int(k) + (self.shape[d] if k < 0 else 0)
                begin.append(b)
                end.append(b + 1)
                drop.append(d)
            elif isinstance(k, slice):
                if k.step not in (None, 1):
                    # strided/reversed axis: leave it whole here, gather the
                    # selected indices afterwards with take (rides the tape)
                    begin.append(None)
                    end.append(None)
                    strided.append((d, k))
                else:
                    begin.append(k.start)
                    end.append(k.stop)
            else:
                return None
        out = invoke_op("slice", [self],
                        {"begin": tuple(begin), "end": tuple(end)})[0]
        for d, k in strided:
            idx = _np.arange(*k.indices(self.shape[d]), dtype=_np.int32)
            if idx.size == 0:
                return NDArray(self._data[key], self._ctx)  # empty: constant
            out = invoke_op("take", [out, NDArray(jnp.asarray(idx), self._ctx)],
                            {"axis": d, "mode": "clip"})[0]
        if out.size == 0:
            # empty view: gradient contribution is zero by construction, and
            # Reshape's shape mini-language cannot spell a literal 0 dim —
            # return the plain (constant) view
            return NDArray(self._data[key], self._ctx)
        if drop:
            kept = [s for i, s in enumerate(out.shape) if i not in drop]
            out = invoke_op("Reshape", [out],
                            {"shape": tuple(kept)})[0]
        return out

    def __setitem__(self, key, value):
        self._inplace_guard()
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, (int, float)):
            v = value
        else:
            v = jnp.asarray(_np.asarray(value), dtype=self.dtype)
        if not isinstance(v, (int, float)):
            # writes stay on THIS array's device: the value may be committed
            # elsewhere (a cpu-context NDArray assigned into a tpu-bound
            # executor arg), and following the value would either error on
            # the mixed computation or silently migrate self off its context
            v = jax.device_put(jnp.asarray(v, dtype=self.dtype),
                               self._data.sharding)
        if isinstance(key, slice) and key == slice(None):
            if isinstance(v, (int, float)):
                self._data = jnp.full_like(self._data, v)
            else:
                self._data = jnp.broadcast_to(v, self.shape)
            return
        if isinstance(key, NDArray):
            key = jax.device_put(key._data.astype(jnp.int32),
                                 self._data.sharding)
        self._data = self._data.at[key].set(v)

    def __len__(self):
        return self.shape[0]

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    def __bool__(self):
        # Scalar arrays truth-test by value; multi-element arrays are
        # ambiguous (parity with the reference / numpy, which raise).
        if self.size == 1:
            return bool(self.asnumpy().reshape(())[()])
        raise ValueError(
            "The truth value of an NDArray with %d elements is ambiguous; "
            "use asnumpy() with .any()/.all()" % self.size)

    # ------------------------------------------------ arithmetic
    def _binop(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            args = [other, self] if reverse else [self, other]
            return invoke_op(op, args, {})[0]
        return invoke_op(scalar_op, [self], {"scalar": float(other)})[0]

    def __add__(self, o):
        return self._binop(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return invoke_op("_rminus_scalar", [self], {"scalar": float(o)})[0] \
            if not isinstance(o, NDArray) else o.__sub__(self)

    def __mul__(self, o):
        return self._binop(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "broadcast_div", "_div_scalar")

    __div__ = __truediv__

    def __rtruediv__(self, o):
        return invoke_op("_rdiv_scalar", [self], {"scalar": float(o)})[0] \
            if not isinstance(o, NDArray) else o.__truediv__(self)

    __rdiv__ = __rtruediv__

    def __mod__(self, o):
        return self._binop(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return invoke_op("_rmod_scalar", [self], {"scalar": float(o)})[0]

    def __pow__(self, o):
        return self._binop(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return invoke_op("_rpower_scalar", [self], {"scalar": float(o)})[0]

    def __neg__(self):
        return invoke_op("negative", [self], {})[0]

    def __eq__(self, o):
        if isinstance(o, (NDArray, int, float)):
            return self._binop(o, "broadcast_equal", "_equal_scalar")
        return NotImplemented

    def __ne__(self, o):
        if isinstance(o, (NDArray, int, float)):
            return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")
        return NotImplemented

    def __gt__(self, o):
        return self._binop(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binop(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binop(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binop(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return self._uid

    def _inplace_guard(self):
        # an array is off-limits for mutation while recording if the tape
        # has captured it anywhere — as an op OUTPUT (_tape_entry) or as an
        # op INPUT (a leaf consumed by a recorded op); mutating the latter
        # silently desynchronizes the array from the value backward uses
        if _ag.is_recording() and (self._tape_entry is not None
                                   or _ag.on_tape(self._uid)):
            raise MXNetError("Inplace update of a recorded array is not "
                             "supported when recording with autograd")

    def __iadd__(self, o):
        self._inplace_guard()
        r = self.__add__(o)
        self._data = r._data
        return self

    def __isub__(self, o):
        self._inplace_guard()
        r = self.__sub__(o)
        self._data = r._data
        return self

    def __imul__(self, o):
        self._inplace_guard()
        r = self.__mul__(o)
        self._data = r._data
        return self

    def __itruediv__(self, o):
        self._inplace_guard()
        r = self.__truediv__(o)
        self._data = r._data
        return self

    def __repr__(self):
        return "%s\n<NDArray %s @%s>" % (
            str(self.asnumpy()), "x".join(str(s) for s in self.shape), self._ctx)

    # sum/max/etc convenience mirrors
    def sum(self, axis=None, keepdims=False):
        return invoke_op("sum", [self], {"axis": axis, "keepdims": keepdims})[0]

    def mean(self, axis=None, keepdims=False):
        return invoke_op("mean", [self], {"axis": axis, "keepdims": keepdims})[0]

    def max(self, axis=None, keepdims=False):
        return invoke_op("max", [self], {"axis": axis, "keepdims": keepdims})[0]

    def min(self, axis=None, keepdims=False):
        return invoke_op("min", [self], {"axis": axis, "keepdims": keepdims})[0]

    def argmax(self, axis=None):
        return invoke_op("argmax", [self], {"axis": axis})[0]

    def transpose(self, axes=None):
        return invoke_op("transpose", [self], {"axes": axes or ()})[0]

    def tostype(self, stype):
        if stype in (None, "default"):
            return self
        from .sparse import cast_storage
        return cast_storage(self, stype)


# ---------------------------------------------------------------- invoke


def invoke_op(name, nd_inputs, attr_kwargs, out=None):
    """Imperative invoke: parity with MXImperativeInvokeEx → PushFCompute
    (src/c_api/c_api_ndarray.cc:491-611), with XLA async dispatch replacing the
    engine push and the autograd tape hook (RecordOp) preserved."""
    # `name` may be an OpDef directly (gluon CachedOps invoke their private
    # opdef without polluting the global registry — the generated binding
    # surfaces stamp its size, so it must stay import-deterministic)
    op = get_op(name) if isinstance(name, str) else name
    if out is not None and _ag.is_recording():
        # matches the reference's error: in-place writes would silently sever
        # the tape (the dst keeps its old uid while the entry records a new one)
        raise MXNetError(
            "Inplace operations (out=) are not supported when recording with"
            " autograd")
    attrs = dict(attr_kwargs)
    if "__is_train__" in op.attrs_spec:
        attrs.setdefault("__is_train__", _ag.is_training())
    parsed = op.parse_attrs(attrs)
    raw = [x._data for x in nd_inputs]
    rng = _rnd.next_key() if op.needs_rng else None
    outs = op.apply(parsed, raw, rng=rng)
    ctx = nd_inputs[0]._ctx if nd_inputs else current_context()

    n_vis = op.n_out(parsed)
    n_aux = len(op.aux_names)
    vis, aux = outs[:n_vis], outs[n_vis:n_vis + n_aux]
    # write aux updates (e.g. BatchNorm moving stats) back into the aux inputs
    if n_aux:
        names = op.input_names(parsed, n=len(nd_inputs))
        for an, av in zip(op.aux_names, aux):
            idx = names.index(an)
            nd_inputs[idx]._data = av

    out_arrays = [NDArray(v, ctx) for v in vis]
    if _ag.is_recording():
        _ag.record_op(op, parsed, list(nd_inputs), out_arrays, rng=rng)

    if out is not None:
        outs_list = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs_list, out_arrays):
            dst._data = src._data
        return list(outs_list)
    return out_arrays


imperative_invoke = invoke_op


def waitall():
    """Block until all launched work completes (parity Engine::WaitForAll):
    device work (XLA dispatch queue), host tasks scheduled on the native
    engine (prefetch side effects), and pending async checkpoint writes
    on the elastic snapshot writer."""
    (jnp.zeros(()) + 0).block_until_ready()
    try:
        jax.effects_barrier()
    except Exception:
        pass
    from .. import engine as _engine

    _engine.get().wait_for_all()
    from ..elastic import snapshot as _snap

    if _snap._WRITER is not None:  # never instantiate just to drain
        _snap._WRITER.flush()


# ---------------------------------------------------------------- creation


def array(source_array, ctx=None, dtype=None):
    explicit = dtype is not None
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
    else:
        src = _np.asarray(source_array)
    dt = _coerce_dtype(dtype if explicit else src.dtype, explicit)
    ctx = ctx or current_context()
    return _track_alloc(NDArray(jax.device_put(jnp.asarray(src.astype(dt)),
                                               ctx.jax_device), ctx))


def empty(shape, ctx=None, dtype="float32"):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype="float32", **kw):
    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx or current_context()
    return _track_alloc(NDArray(jax.device_put(jnp.zeros(shape,
                                                         _np.dtype(dtype)),
                                               ctx.jax_device), ctx))


def ones(shape, ctx=None, dtype="float32", **kw):
    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx or current_context()
    return _track_alloc(NDArray(jax.device_put(jnp.ones(shape,
                                                        _np.dtype(dtype)),
                                               ctx.jax_device), ctx))


def full(shape, val, ctx=None, dtype="float32"):
    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx or current_context()
    return _track_alloc(NDArray(jax.device_put(jnp.full(shape, val,
                                                        _np.dtype(dtype)),
                                               ctx.jax_device), ctx))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    return invoke_op("_arange", [], {"start": start, "stop": stop, "step": step,
                                     "repeat": repeat, "dtype": dtype})[0]


def concatenate(arrays, axis=0, always_copy=True):
    return invoke_op("Concat", list(arrays),
                     {"num_args": len(arrays), "dim": axis})[0]


def moveaxis(tensor, source, destination):
    """Move one axis to a new position, via the transpose op so the
    result stays on the autograd tape (parity ndarray.py moveaxis)."""
    nd_ = tensor.ndim
    if not (-nd_ <= source < nd_ and -nd_ <= destination < nd_):
        raise MXNetError("moveaxis: axis out of range for %d-d array"
                         % nd_)
    src = source % nd_
    dst = destination % nd_
    axes = [i for i in range(nd_) if i != src]
    axes.insert(dst, src)
    return invoke_op("transpose", [tensor], {"axes": tuple(axes)})[0]


def onehot_encode(indices, out):
    res = invoke_op("one_hot", [indices], {"depth": out.shape[1]})[0]
    out._data = res._data
    return out


# ---------------------------------------------------------------- serialization
# Binary format (versioned, parity role of NDArray::Save/Load ndarray.h:361-373):
#   magic 'MXTPU001' | int64 n | per item: name_len,name | header(json) | raw bytes

_MAGIC = b"MXTPU001"


@jax.jit
def _pack_flat(xs):
    """Concatenate arrays (one dtype) into one flat device buffer.
    Module-level + jitted so repeated checkpoints hit the trace cache."""
    return jnp.concatenate([x.reshape(-1) for x in xs])


def _bulk_to_numpy(arrays):
    """Fetch many (possibly device-resident) arrays to host numpy.

    Group the on-device arrays by dtype, concatenate each group into ONE
    flat buffer in a single jitted program, fetch the few packed buffers,
    and split on the host: one transfer per dtype instead of one per
    array. Host-resident inputs pass straight through. Measured on one
    TPU v5e (PERF.md, PR 21) for ResNet-50's 255 parameter and aux arrays
    (102 MB): 0.049 s packed against 0.12-0.17 s array by array, after a
    first call that compiles the 255-operand concatenate for 15.6 s."""
    out = [None] * len(arrays)
    dev_idx = []
    for i, a in enumerate(arrays):
        if isinstance(a, jax.Array):
            dev_idx.append(i)
        else:
            out[i] = _np.asarray(a)
    groups = {}
    for i in dev_idx:
        groups.setdefault(str(arrays[i].dtype), []).append(i)
    for _, idxs in groups.items():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = _np.asarray(arrays[i])
            continue
        host = _np.asarray(_pack_flat([arrays[i] for i in idxs]))
        off = 0
        for i in idxs:
            n = arrays[i].size
            out[i] = host[off:off + n].reshape(arrays[i].shape)
            off += n
    return out


def _bulk_tree_to_numpy(tree):
    """Pytree variant of ``_bulk_to_numpy`` (same packed transfer)."""
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, _bulk_to_numpy(leaves))


def save(fname, data):
    """Save NDArrays: list or dict (parity mx.nd.save)."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        items = list(data.items())
    else:
        items = [("", v) for v in data]
    import json

    host = _bulk_to_numpy([getattr(v, "_data", v) for _, v in items])
    with open(fname, "wb") as f:
        f.write(_MAGIC)
        f.write(struct.pack("<q", len(items)))
        for (name, _), np_arr in zip(items, host):
            hdr = json.dumps({"shape": list(np_arr.shape),
                              "dtype": str(np_arr.dtype)}).encode()
            nb = name.encode()
            f.write(struct.pack("<q", len(nb)))
            f.write(nb)
            f.write(struct.pack("<q", len(hdr)))
            f.write(hdr)
            raw = np_arr.tobytes()
            f.write(struct.pack("<q", len(raw)))
            f.write(raw)


def load(fname):
    """Load NDArrays saved by ``save`` (returns list or dict like
    mx.nd.load). Accepts a path or a binary file-like object."""
    import json
    from contextlib import nullcontext

    ctx_mgr = (nullcontext(fname) if hasattr(fname, "read")
               else open(fname, "rb"))
    with ctx_mgr as f:
        magic = f.read(8)
        if magic != _MAGIC:
            raise MXNetError("invalid NDArray file %s" % fname)
        (n,) = struct.unpack("<q", f.read(8))
        named = {}
        unnamed = []
        for _ in range(n):
            (ln,) = struct.unpack("<q", f.read(8))
            name = f.read(ln).decode()
            (lh,) = struct.unpack("<q", f.read(8))
            hdr = json.loads(f.read(lh).decode())
            (lr,) = struct.unpack("<q", f.read(8))
            raw = f.read(lr)
            np_arr = _np.frombuffer(raw, dtype=_np.dtype(hdr["dtype"])).reshape(
                hdr["shape"])
            arr = array(np_arr)
            if name:
                named[name] = arr
            else:
                unnamed.append(arr)
    return named if named else unnamed
