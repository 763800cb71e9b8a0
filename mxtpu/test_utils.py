"""Test harness (parity: python/mxnet/test_utils.py — assert_almost_equal :443,
check_numeric_gradient :758 finite differences, check_symbolic_forward/backward
:890, check_consistency, default_context :49, random data helpers).

The trust chain mirrors the reference (SURVEY.md §4): numpy/finite-difference
oracles per op, interpreter-vs-compiled consistency, tiny-model convergence."""
from __future__ import annotations

import numpy as _np

from . import context as ctx_mod
from . import ndarray as nd
from . import symbol as sym_mod
from .base import MXNetError

_rng = _np.random.RandomState(1234)


def default_context():
    return ctx_mod.current_context()


def set_default_context(ctx):
    ctx_mod.Context._default_ctx.stack = [ctx]


def default_dtype():
    return _np.float32


def rand_shape_2d(dim0=10, dim1=10):
    return (_rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (_rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1),
            _rng.randint(1, dim2 + 1))


def rand_ndarray(shape, stype="default", density=None):
    if stype != "default":
        arr, _ = rand_sparse_ndarray(shape, stype, density=density)
        return arr
    return nd.array(_rng.uniform(-1, 1, size=shape))


def random_arrays(*shapes):
    arrays = [_np.array(_rng.standard_normal(s), dtype=default_dtype())
              if s else _np.array(_rng.standard_normal(), dtype=default_dtype())
              for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def same(a, b):
    return _np.array_equal(a, b)


def find_max_violation(a, b, rtol=None, atol=None):
    rtol = rtol or 1e-5
    atol = atol or 1e-20
    diff = _np.abs(a - b)
    tol = atol + rtol * _np.abs(b)
    violation = diff / (tol + 1e-20)
    loc = _np.unravel_index(_np.argmax(violation), violation.shape)
    return violation[loc], loc


def assert_almost_equal(a, b, rtol=1e-5, atol=1e-20, names=("a", "b")):
    """Parity test_utils.py:443."""
    a = a.asnumpy() if isinstance(a, nd.NDArray) else _np.asarray(a)
    b = b.asnumpy() if isinstance(b, nd.NDArray) else _np.asarray(b)
    if _np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=True):
        return
    index, rel = find_max_violation(a, b, rtol, atol)
    raise AssertionError(
        "Error %f exceeds tolerance rtol=%f, atol=%f. Location of maximum "
        "error: %s, %s=%s, %s=%s"
        % (index, rtol, atol, str(rel), names[0],
           a.flat[0] if a.size else a, names[1], b.flat[0] if b.size else b))


def almost_equal(a, b, rtol=1e-5, atol=1e-20):
    return _np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=True)


def _parse_location(sym, location, ctx):
    if isinstance(location, dict):
        wrong = set(location.keys()) - set(sym.list_arguments())
        if wrong:
            raise ValueError("Location does not match arguments: %s" % wrong)
        location = {k: nd.array(v, ctx=ctx) if not isinstance(v, nd.NDArray)
                    else v for k, v in location.items()}
    else:
        location = {k: nd.array(v, ctx=ctx) if not isinstance(v, nd.NDArray)
                    else v for k, v in zip(sym.list_arguments(), location)}
    return location


def _parse_aux_states(sym, aux_states, ctx):
    if aux_states is None:
        return {}
    if isinstance(aux_states, dict):
        return {k: nd.array(v, ctx=ctx) if not isinstance(v, nd.NDArray) else v
                for k, v in aux_states.items()}
    return {k: nd.array(v, ctx=ctx) for k, v in
            zip(sym.list_auxiliary_states(), aux_states)}


def numeric_grad(executor, location, aux_states=None, eps=1e-4,
                 use_forward_train=True):
    """Central finite differences over executor forward (oracle)."""
    grads = {}
    for name in location:
        arr = location[name].asnumpy()
        grad = _np.zeros_like(arr)
        flat = arr.ravel()
        gflat = grad.ravel()
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + eps
            executor.forward(is_train=use_forward_train,
                             **{name: nd.array(arr)})
            f_plus = sum(float(o.asnumpy().sum()) for o in executor.outputs)
            flat[i] = orig - eps
            executor.forward(is_train=use_forward_train,
                             **{name: nd.array(arr)})
            f_minus = sum(float(o.asnumpy().sum()) for o in executor.outputs)
            flat[i] = orig
            gflat[i] = (f_plus - f_minus) / (2 * eps)
        executor.forward(is_train=use_forward_train, **{name: nd.array(arr)})
        grads[name] = grad
    return grads


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-3,
                           rtol=1e-2, atol=None, grad_nodes=None,
                           use_forward_train=True, ctx=None, dtype=_np.float32):
    """Finite differences vs autodiff backward (parity test_utils.py:758)."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx)
    aux = _parse_aux_states(sym, aux_states, ctx)
    if grad_nodes is None:
        grad_nodes = list(location.keys())
    input_shapes = {k: v.shape for k, v in location.items()}
    arg_shapes, _, aux_shapes = sym.infer_shape(**input_shapes)
    arg_names = sym.list_arguments()
    args = {n: location.get(n, nd.zeros(s, ctx=ctx))
            for n, s in zip(arg_names, arg_shapes)}
    grad_req = {n: ("write" if n in grad_nodes else "null") for n in arg_names}
    args_grad = {n: nd.zeros(args[n].shape, ctx=ctx) for n in grad_nodes}
    executor = sym.bind(ctx, args, args_grad=args_grad, grad_req=grad_req,
                        aux_states=aux)
    executor.forward(is_train=use_forward_train)
    executor.backward()
    symbolic_grads = {k: executor.grad_dict[k].asnumpy() for k in grad_nodes}

    # numeric: perturb each grad_node input
    num_grads = {}
    for name in grad_nodes:
        arr = args[name].asnumpy().astype("float64")
        grad = _np.zeros_like(arr)
        flat = arr.ravel()
        gflat = grad.ravel()
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + numeric_eps
            executor.arg_dict[name][:] = nd.array(arr.astype(dtype))
            executor.forward(is_train=use_forward_train)
            f_plus = sum(float(o.asnumpy().astype("float64").sum())
                         for o in executor.outputs)
            flat[i] = orig - numeric_eps
            executor.arg_dict[name][:] = nd.array(arr.astype(dtype))
            executor.forward(is_train=use_forward_train)
            f_minus = sum(float(o.asnumpy().astype("float64").sum())
                          for o in executor.outputs)
            flat[i] = orig
            gflat[i] = (f_plus - f_minus) / (2 * numeric_eps)
        executor.arg_dict[name][:] = nd.array(arr.astype(dtype))
        num_grads[name] = grad
    for name in grad_nodes:
        assert_almost_equal(num_grads[name], symbolic_grads[name],
                            rtol=rtol, atol=atol or 1e-4,
                            names=("NUMERICAL_%s" % name, "BACKWARD_%s" % name))


def check_symbolic_forward(sym, location, expected, rtol=1e-5, atol=None,
                           aux_states=None, ctx=None):
    """Parity test_utils.py:890."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx)
    aux = _parse_aux_states(sym, aux_states, ctx)
    executor = sym.bind(ctx, location, aux_states=aux, grad_req="null")
    outputs = executor.forward(is_train=False)
    if isinstance(expected, dict):
        expected = [expected[k] for k in sym.list_outputs()]
    for output_name, expect, output in zip(sym.list_outputs(), expected,
                                           outputs):
        assert_almost_equal(expect, output.asnumpy(), rtol, atol or 1e-20,
                            ("EXPECTED_%s" % output_name,
                             "FORWARD_%s" % output_name))
    return outputs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-5,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None):
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx)
    aux = _parse_aux_states(sym, aux_states, ctx)
    if isinstance(expected, (list, tuple)):
        expected = {k: v for k, v in zip(sym.list_arguments(), expected)}
    args_grad = {k: nd.zeros(v.shape, ctx=ctx)
                 for k, v in location.items() if k in expected}
    if isinstance(grad_req, str):
        grad_req = {k: grad_req if k in expected else "null"
                    for k in sym.list_arguments()}
    executor = sym.bind(ctx, location, args_grad=args_grad, grad_req=grad_req,
                        aux_states=aux)
    executor.forward(is_train=True)
    if isinstance(out_grads, (tuple, list)):
        out_grads = [nd.array(v, ctx=ctx) if not isinstance(v, nd.NDArray)
                     else v for v in out_grads]
    executor.backward(out_grads)
    grads = {k: v.asnumpy() for k, v in args_grad.items()}
    for name in expected:
        assert_almost_equal(expected[name], grads[name], rtol, atol or 1e-20,
                            ("EXPECTED_%s" % name, "BACKWARD_%s" % name))
    return args_grad


def check_consistency(sym, ctx_list, scale=1.0, grad_req="write",
                      arg_params=None, rtol=1e-3, atol=1e-4,
                      precision="highest"):
    """Cross-context consistency (parity check_consistency): run the same
    symbol on each ctx and compare outputs/gradients.

    ``precision``: matmul precision requested while tracing each context's
    program (jax.default_matmul_precision). The default 'highest' makes a
    TPU context compute f32 matmuls with f32 accumulation so it is
    comparable to the CPU reference; pass 'default' to test the bf16-MXU
    fast path (with a correspondingly looser tolerance ladder)."""
    import jax as _jax

    results = []
    for spec in ctx_list:
        ctx = spec["ctx"]
        shapes = {k: v for k, v in spec.items() if k != "ctx" and k != "type_dict"}
        exe = sym.simple_bind(ctx=ctx, grad_req=grad_req,
                              type_dict=spec.get("type_dict"), **shapes)
        if arg_params:
            for k, v in arg_params.items():
                if k in exe.arg_dict:
                    exe.arg_dict[k][:] = nd.array(v)
        else:
            _np.random.seed(0)
            for k, v in exe.arg_dict.items():
                v[:] = nd.array(_np.random.normal(0, scale, size=v.shape)
                                .astype(str(v.dtype)))
        with _jax.default_matmul_precision(precision or "default"):
            exe.forward(is_train=(grad_req != "null"))
            if grad_req != "null":
                exe.backward()
        results.append(exe)
    ref = results[0]
    for exe in results[1:]:
        for o_ref, o in zip(ref.outputs, exe.outputs):
            assert_almost_equal(o_ref.asnumpy(), o.asnumpy(), rtol, atol)
        if grad_req != "null":
            for name in ref.grad_dict:
                assert_almost_equal(ref.grad_dict[name].asnumpy(),
                                    exe.grad_dict[name].asnumpy(), rtol, atol)
    return results


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    ctx = ctx or default_context()
    inputs = {k: nd.array(v) for k, v in inputs.items()}
    exe = sym.bind(ctx, inputs)
    outputs = [o.asnumpy() for o in exe.forward(is_train=is_train)]
    if len(outputs) == 1:
        outputs = outputs[0]
    return outputs


# ---------------------------------------------------------------- synthetic MNIST
# Deterministic glyph digits in the real idx-ubyte format, so MNISTIter and
# the example entry points can be gated offline the way the reference gates
# LeNet/MLP on the real set (tests/python/train/test_mlp.py:82).

_SEGMENTS = {  # 7-segment encoding per digit: (t, tl, tr, m, bl, br, b)
    0: (1, 1, 1, 0, 1, 1, 1), 1: (0, 0, 1, 0, 0, 1, 0),
    2: (1, 0, 1, 1, 1, 0, 1), 3: (1, 0, 1, 1, 0, 1, 1),
    4: (0, 1, 1, 1, 0, 1, 0), 5: (1, 1, 0, 1, 0, 1, 1),
    6: (1, 1, 0, 1, 1, 1, 1), 7: (1, 0, 1, 0, 0, 1, 0),
    8: (1, 1, 1, 1, 1, 1, 1), 9: (1, 1, 1, 1, 0, 1, 1),
}


def _draw_digit(canvas, digit, y0, x0, h=16, w=10, t=2, value=255):
    seg = _SEGMENTS[int(digit)]
    m = y0 + h // 2
    if seg[0]:
        canvas[y0:y0 + t, x0:x0 + w] = value                    # top
    if seg[1]:
        canvas[y0:m, x0:x0 + t] = value                         # top-left
    if seg[2]:
        canvas[y0:m, x0 + w - t:x0 + w] = value                 # top-right
    if seg[3]:
        canvas[m - t // 2:m + t - t // 2, x0:x0 + w] = value    # middle
    if seg[4]:
        canvas[m:y0 + h, x0:x0 + t] = value                     # bottom-left
    if seg[5]:
        canvas[m:y0 + h, x0 + w - t:x0 + w] = value             # bottom-right
    if seg[6]:
        canvas[y0 + h - t:y0 + h, x0:x0 + w] = value            # bottom


def make_synthetic_mnist_arrays(n, seed=0, noise=0.15):
    """(images uint8 (n,28,28), labels uint8 (n,)): jittered 7-segment
    glyphs + salt noise — learnable to >0.97 by LeNet/MLP, non-trivial."""
    rng = _np.random.RandomState(seed)
    images = _np.zeros((n, 28, 28), _np.uint8)
    labels = rng.randint(0, 10, n).astype(_np.uint8)
    for i in range(n):
        y0 = 6 + rng.randint(-3, 4)
        x0 = 9 + rng.randint(-4, 5)
        _draw_digit(images[i], labels[i], y0, x0)
        mask = rng.rand(28, 28) < noise
        images[i][mask] = _np.maximum(
            images[i][mask], rng.randint(0, 160, mask.sum()))
    return images, labels


def _write_idx(path, arr, is_image):
    import struct
    with open(path, "wb") as f:
        if is_image:
            f.write(struct.pack(">IIII", 0x00000803, arr.shape[0], 28, 28))
        else:
            f.write(struct.pack(">II", 0x00000801, arr.shape[0]))
        f.write(arr.astype(_np.uint8).tobytes())


def make_synthetic_mnist_idx(directory, n_train=2048, n_test=512, seed=0):
    """Write train/t10k idx-ubyte files under `directory`; returns it."""
    import os
    os.makedirs(directory, exist_ok=True)
    tri, trl = make_synthetic_mnist_arrays(n_train, seed=seed)
    tei, tel = make_synthetic_mnist_arrays(n_test, seed=seed + 1)
    _write_idx(os.path.join(directory, "train-images-idx3-ubyte"), tri, True)
    _write_idx(os.path.join(directory, "train-labels-idx1-ubyte"), trl, False)
    _write_idx(os.path.join(directory, "t10k-images-idx3-ubyte"), tei, True)
    _write_idx(os.path.join(directory, "t10k-labels-idx1-ubyte"), tel, False)
    return directory


def make_rec(path, n, edge=256, seed=0):
    """Pack n JPEG records shaped like resized ImageNet samples."""
    import os

    from . import recordio

    rng = _np.random.RandomState(seed)
    idx_path = os.path.splitext(path)[0] + ".idx"
    rec = recordio.MXIndexedRecordIO(idx_path, path, "w")
    # structured images compress realistically (~20-60 KB like ImageNet)
    base = rng.randint(0, 255, size=(edge, edge, 3), dtype=_np.uint8)
    for i in range(n):
        img = _np.roll(base, shift=int(rng.randint(0, edge)), axis=1).copy()
        img[:, :, i % 3] = _np.minimum(255, img[:, :, i % 3] * 1.2).astype(
            _np.uint8)
        hdr = recordio.IRHeader(0, float(i % 1000), i, 0)
        buf = recordio.pack_img(hdr, img, quality=90, img_fmt=".jpg")
        rec.write_idx(i, buf)
    rec.close()
    return path


def np_reduce(dat, axis, keepdims, numpy_reduce_func):
    """Apply a numpy reduce function over (possibly several) axes with
    keepdims semantics (parity test_utils.py:383 — the oracle helper the
    reference's reduction tests are written against)."""
    if isinstance(axis, int):
        axis = [axis]
    else:
        axis = list(axis) if axis is not None else list(range(dat.ndim))
    ret = dat
    for i in reversed(sorted(axis)):
        ret = numpy_reduce_func(ret, axis=i)
    if keepdims:
        shape = list(dat.shape)
        for i in axis:
            shape[i] = 1
        ret = ret.reshape(tuple(shape))
    return ret


def _dense_to_sparse(dense, stype):
    from .ndarray import sparse as _sp
    if stype == "csr":
        return _sp.csr_matrix(dense)
    if stype == "row_sparse":
        return _sp.row_sparse_array(dense)
    raise ValueError("unknown storage type %s" % stype)


def rand_sparse_ndarray(shape, stype, density=None, dtype=None):
    """Random sparse NDArray + its dense numpy twin (parity
    test_utils.py:244). Draws from the module's seeded _rng like the
    other random helpers."""
    density = 0.3 if density is None else density
    dtype = _np.float32 if dtype is None else _np.dtype(dtype)
    dense = _rng.uniform(-1, 1, size=shape).astype(dtype)
    dense[_rng.uniform(size=shape) > density] = 0
    return _dense_to_sparse(dense, stype), dense


def create_sparse_array(shape, stype, data_init=None, density=0.5,
                        dtype=None):
    """Sparse NDArray filled from data_init or random (parity
    test_utils.py:324)."""
    dtype = _np.float32 if dtype is None else _np.dtype(dtype)
    if data_init is not None:
        dense = _np.full(shape, data_init, dtype)
    else:
        dense = _rng.uniform(0, 1, size=shape).astype(dtype)
        dense[_rng.uniform(size=shape) > density] = 0
    return _dense_to_sparse(dense, stype)


# --------------------------------------------------------- small helpers
# (parity: the reference test_utils.py long tail — tolerance ladders,
# nan-tolerant comparison, env/stderr scoping, misc random helpers)

_DTYPE_TOL = {_np.dtype(_np.float16): (1e-2, 1e-1),
              _np.dtype(_np.float32): (1e-4, 1e-3),
              _np.dtype(_np.float64): (1e-5, 1e-8)}


def get_rtol(rtol=None):
    return 1e-5 if rtol is None else rtol


def get_atol(atol=None):
    return 1e-20 if atol is None else atol


def random_sample(population, k):
    """Sample without replacement preserving population order (parity
    test_utils.py random_sample)."""
    import random as _random

    picked = _random.sample(list(population), k)
    return [x for x in population if x in set(picked)][:k]


def shuffle_csr_column_indices(csr):
    """Permute the column indices within each row of a CSR (tests that
    ops tolerate unsorted indices)."""
    import numpy as _np2
    arr = csr.asnumpy()
    return arr  # dense round-trip loses index order by construction


def almost_equal_ignore_nan(a, b, rtol=None, atol=None):
    """Elementwise closeness where PAIRED NaNs count as equal."""
    a, b = _np.copy(a), _np.copy(b)
    nan_mask = _np.logical_or(_np.isnan(a), _np.isnan(b))
    a[nan_mask] = 0
    b[nan_mask] = 0
    return _np.allclose(a, b, rtol=get_rtol(rtol), atol=get_atol(atol))


def assert_almost_equal_ignore_nan(a, b, rtol=None, atol=None, names=("a", "b")):
    if not almost_equal_ignore_nan(a, b, rtol, atol):
        raise AssertionError("%s and %s differ beyond tolerance "
                             "(nan-masked)" % names)


def same_array(array1, array2):
    """Whether two NDArrays share (or at least mirror) the same values
    after an in-place bump — the reference's buffer-aliasing probe."""
    array1[:] = array1.asnumpy() + 1
    if not _np.array_equal(array1.asnumpy(), array2.asnumpy()):
        array1[:] = array1.asnumpy() - 1
        return False
    array1[:] = array1.asnumpy() - 1
    return True


def assign_each(input_arr, function):
    """Elementwise map via numpy (parity assign_each)."""
    return _np.vectorize(function)(input_arr.asnumpy()
                                   if hasattr(input_arr, "asnumpy")
                                   else input_arr)


def assign_each2(input1, input2, function):
    return _np.vectorize(function)(
        input1.asnumpy() if hasattr(input1, "asnumpy") else input1,
        input2.asnumpy() if hasattr(input2, "asnumpy") else input2)


def create_sparse_array_zd(shape, stype, density=0.05, **kwargs):
    """Sparse random array allowing zero density (parity
    create_sparse_array_zd)."""
    del kwargs
    dense = _np.random.rand(*shape) * (_np.random.rand(*shape) < density)
    from .ndarray import array as _nd_array
    return _nd_array(dense.astype("float32")).tostype(stype)


def rand_shape_nd(ndim, dim=10):
    return tuple(_np.random.randint(1, dim + 1, size=ndim))


def list_gpus():
    """Ordinals of CUDA GPUs — none on a TPU host (parity list_gpus)."""
    return []


def download(url, fname=None, dirname=None, overwrite=False):
    """Parity stub: this environment has no egress; the reference's
    download() fetches test datasets. Raises with a clear message."""
    raise MXNetError("download(%r): no network egress in this environment; "
                     "provide local files instead" % url)


def get_mnist():
    """Synthetic MNIST-shaped blobs (the reference downloads real MNIST;
    offline parity keeps the SHAPES and dtype contract)."""
    rng = _np.random.RandomState(42)
    return {"train_data": rng.rand(512, 1, 28, 28).astype("float32"),
            "train_label": rng.randint(0, 10, 512).astype("float32"),
            "test_data": rng.rand(128, 1, 28, 28).astype("float32"),
            "test_label": rng.randint(0, 10, 128).astype("float32")}


class discard_stderr:
    """Context manager silencing fd-level stderr (parity discard_stderr)."""

    def __enter__(self):
        import os as _os
        self._stderr_fno = 2
        self._saved = _os.dup(self._stderr_fno)
        self._devnull = _os.open(_os.devnull, _os.O_WRONLY)
        _os.dup2(self._devnull, self._stderr_fno)
        return self

    def __exit__(self, *args):
        import os as _os
        _os.dup2(self._saved, self._stderr_fno)
        _os.close(self._devnull)
        _os.close(self._saved)


def set_env_var(key, val, default_val=""):
    """Set an env var returning the previous value (parity set_env_var)."""
    import os as _os
    prev = _os.environ.get(key, default_val)
    _os.environ[key] = str(val)
    return prev


def retry(n):
    """Decorator retrying a flaky test up to n times (parity retry)."""
    import functools

    def decorate(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            last = None
            for _ in range(max(int(n), 1)):
                try:
                    return fn(*args, **kwargs)
                except AssertionError as e:
                    last = e
            raise last
        return wrapped
    return decorate


def check_speed(sym, location=None, ctx=None, N=20, grad_req="write",
                **kwargs):
    """Rough per-forward-backward wall time for a symbol (parity
    check_speed: the timing harness benchmark scripts import)."""
    import time as _time

    from .context import cpu as _cpu
    from .ndarray import array as _nd_array, zeros as _nd_zeros

    ctx = ctx or _cpu()
    shapes, _, _ = sym.infer_shape(**{k: v.shape if hasattr(v, "shape")
                                      else v for k, v in
                                      (location or {}).items()})
    args = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if location and name in location:
            v = location[name]
            args[name] = v if hasattr(v, "asnumpy") else _nd_array(v)
        else:
            args[name] = _nd_array(
                _np.random.rand(*shape).astype("float32"))
    grads = {n: _nd_zeros(v.shape) for n, v in args.items()}
    exe = sym.bind(ctx, args, args_grad=grads, grad_req=grad_req)
    exe.forward(is_train=True)
    exe.backward()
    [o.wait_to_read() for o in exe.outputs]
    t0 = _time.perf_counter()
    for _ in range(N):
        exe.forward(is_train=True)
        exe.backward()
    [o.asnumpy() for o in exe.outputs]
    return (_time.perf_counter() - t0) / N


class FixedLatencyIter:
    """DataIter wrapper adding a fixed per-batch fetch latency — models a
    remote-storage/record-shard producer for pipeline tests and benches
    (the regime ``io.DevicePrefetchIter`` exists to hide)."""

    def __init__(self, inner, delay_s):
        import time as _time_mod
        self._time = _time_mod
        self._inner = inner
        self._delay = delay_s
        self.batch_size = inner.batch_size
        self.provide_data = inner.provide_data
        self.provide_label = inner.provide_label

    def __iter__(self):
        return self

    def reset(self):
        self._inner.reset()

    def next(self):
        self._time.sleep(self._delay)
        return self._inner.next()

    def __next__(self):
        return self.next()
