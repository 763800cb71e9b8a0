"""NDArray imperative tests (model: reference tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd


def test_creation():
    a = nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert np.allclose(a.asnumpy(), 0)
    b = nd.ones((2, 2), dtype="int32")
    assert b.dtype == np.int32
    c = nd.full((2, 3), 7.5)
    assert np.allclose(c.asnumpy(), 7.5)
    d = nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2)


def test_elementwise():
    a = nd.array(np.array([[1.0, 2], [3, 4]]))
    b = nd.array(np.array([[4.0, 3], [2, 1]]))
    assert np.allclose((a + b).asnumpy(), 5)
    assert np.allclose((a * b).asnumpy(), [[4, 6], [6, 4]])
    assert np.allclose((a - 1).asnumpy(), [[0, 1], [2, 3]])
    assert np.allclose((2 * a).asnumpy(), [[2, 4], [6, 8]])
    assert np.allclose((1 / b).asnumpy(), [[0.25, 1 / 3.0], [0.5, 1]])
    assert np.allclose((a ** 2).asnumpy(), [[1, 4], [9, 16]])
    assert np.allclose((-a).asnumpy(), [[-1, -2], [-3, -4]])


def test_comparisons():
    a = nd.array(np.array([1.0, 2, 3]))
    b = nd.array(np.array([3.0, 2, 1]))
    assert np.allclose((a == b).asnumpy(), [0, 1, 0])
    assert np.allclose((a > b).asnumpy(), [0, 0, 1])
    assert np.allclose((a <= b).asnumpy(), [1, 1, 0])


def test_inplace_ops():
    a = nd.ones((2, 2))
    a += 1
    assert np.allclose(a.asnumpy(), 2)
    a *= 3
    assert np.allclose(a.asnumpy(), 6)


def test_indexing():
    a = nd.array(np.arange(12).reshape(3, 4).astype("float32"))
    assert np.allclose(a[1].asnumpy(), [4, 5, 6, 7])
    assert np.allclose(a[1:3].asnumpy(), np.arange(4, 12).reshape(2, 4))
    a[0] = 0
    assert np.allclose(a.asnumpy()[0], 0)
    a[:] = 1
    assert np.allclose(a.asnumpy(), 1)


def test_reshape_transpose():
    a = nd.array(np.arange(6).astype("float32"))
    b = a.reshape((2, 3))
    assert b.shape == (2, 3)
    assert b.T.shape == (3, 2)
    c = nd.transpose(b)
    assert c.shape == (3, 2)
    d = nd.Reshape(b, shape=(3, 2))
    assert d.shape == (3, 2)
    e = nd.Reshape(b, shape=(0, -1))
    assert e.shape == (2, 3)


def test_reduce():
    a = nd.array(np.arange(12).reshape(3, 4).astype("float32"))
    assert np.isclose(a.sum().asscalar(), 66)
    assert np.allclose(nd.sum(a, axis=0).asnumpy(), [12, 15, 18, 21])
    assert np.allclose(nd.max(a, axis=1).asnumpy(), [3, 7, 11])
    assert np.allclose(nd.mean(a, axis=1, keepdims=True).asnumpy().shape,
                       (3, 1))
    assert np.allclose(nd.sum(a, axis=1, exclude=True).asnumpy(), [12, 15, 18, 21])


def test_dot():
    a = np.random.randn(3, 4).astype("float32")
    b = np.random.randn(4, 5).astype("float32")
    c = nd.dot(nd.array(a), nd.array(b))
    assert np.allclose(c.asnumpy(), a @ b, atol=1e-5)
    c2 = nd.dot(nd.array(a.T), nd.array(b), transpose_a=True)
    assert np.allclose(c2.asnumpy(), a @ b, atol=1e-5)
    bd = nd.batch_dot(nd.array(np.random.randn(2, 3, 4).astype("f4")),
                      nd.array(np.random.randn(2, 4, 5).astype("f4")))
    assert bd.shape == (2, 3, 5)


def test_broadcast():
    a = nd.array(np.ones((3, 1)).astype("float32"))
    b = nd.array(np.ones((1, 4)).astype("float32"))
    c = nd.broadcast_add(a, b)
    assert c.shape == (3, 4)
    assert np.allclose(c.asnumpy(), 2)
    d = nd.broadcast_to(a, shape=(3, 5))
    assert d.shape == (3, 5)


def test_concat_split():
    a = nd.ones((2, 3))
    b = nd.zeros((2, 3))
    c = nd.concatenate([a, b], axis=0)
    assert c.shape == (4, 3)
    c2 = nd.Concat(a, b, dim=1)
    assert c2.shape == (2, 6)
    parts = nd.SliceChannel(c2, num_outputs=2, axis=1)
    assert parts[0].shape == (2, 3)


def test_save_load(tmp_path):
    fname = str(tmp_path / "arrs.bin")
    a = nd.array(np.random.randn(3, 4).astype("float32"))
    b = nd.array(np.arange(5).astype("int32"))
    nd.save(fname, {"a": a, "b": b})
    loaded = nd.load(fname)
    assert np.allclose(loaded["a"].asnumpy(), a.asnumpy())
    assert np.array_equal(loaded["b"].asnumpy(), b.asnumpy())
    nd.save(fname, [a, b])
    llist = nd.load(fname)
    assert np.allclose(llist[0].asnumpy(), a.asnumpy())


def test_wait_and_context():
    a = nd.ones((4,), ctx=mx.cpu())
    a.wait_to_read()
    nd.waitall()
    assert a.context.device_type == "cpu"
    b = a.as_in_context(mx.cpu(0))
    assert b.shape == (4,)


def test_accelerator_contexts_alias_cpu_only_when_forced():
    """tpu()/gpu() resolve to CPU devices on the forced-CPU test mesh and
    nowhere else: with no accelerator and no forcing, the resolver raises
    instead of running on the host under a device's name."""
    import collections
    from mxtpu import context
    assert mx.tpu(0).jax_device.platform == "cpu"      # JAX_PLATFORMS=cpu
    assert mx.gpu(0).jax_device == mx.tpu(0).jax_device
    Dev = collections.namedtuple("Dev", "platform id")
    cpus = [Dev("cpu", 0), Dev("cpu", 1)]
    mixed = cpus + [Dev("tpu", 0)]
    assert context._resolve_accel(cpus, forced=True) == cpus
    assert context._resolve_accel(mixed, forced=False) == [mixed[2]]
    assert context._resolve_accel(mixed, forced=True) == [mixed[2]]
    with pytest.raises(mx.MXNetError, match="no accelerator.*cpu"):
        context._resolve_accel(cpus, forced=False)


def test_compile_cache_dir_is_placed_from_outside_or_fixed(
        tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> the package configures nothing;
    unset -> one fixed path inside the checkout, whatever the pid, clock
    or cwd."""
    import os
    from mxtpu import base
    assert base.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}) is None
    repo = os.path.dirname(os.path.dirname(os.path.abspath(base.__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert base.compile_cache_dir({}) == want
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(os, "getpid", lambda: 4242)
    assert base.compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == want


def test_astype_copy():
    a = nd.ones((2, 2))
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = a.copy()
    c[:] = 5
    assert np.allclose(a.asnumpy(), 1)


def test_take_onehot():
    w = nd.array(np.arange(12).reshape(4, 3).astype("float32"))
    idx = nd.array(np.array([0, 2], dtype="float32"))
    t = nd.take(w, idx)
    assert np.allclose(t.asnumpy(), [[0, 1, 2], [6, 7, 8]])
    oh = nd.one_hot(idx, depth=4)
    assert np.allclose(oh.asnumpy(), [[1, 0, 0, 0], [0, 0, 1, 0]])


def test_arange_ordering():
    a = nd.arange(0, 10, 2)
    assert np.allclose(a.asnumpy(), [0, 2, 4, 6, 8])
    x = nd.array(np.array([3.0, 1, 2]))
    assert np.allclose(nd.sort(x).asnumpy(), [1, 2, 3])
    assert np.allclose(nd.argsort(x).asnumpy(), [1, 2, 0])
    assert np.allclose(nd.topk(x, k=2, ret_typ="value").asnumpy(), [3, 2])
    assert np.allclose(nd.argmax(x, axis=0).asnumpy(), 0)


def test_module_level_arithmetic():
    """mx.nd.add/subtract/multiply/divide/power/maximum/minimum accept
    array-or-scalar on either side (parity ndarray.py:1748-2610)."""
    a = nd.array(np.full((2, 3), 6.0, "float32"))
    b = nd.array(np.full((2, 3), 4.0, "float32"))
    assert float(nd.add(a, b).asnumpy()[0, 0]) == 10
    assert float(nd.subtract(a, 1).asnumpy()[0, 0]) == 5
    assert float(nd.multiply(2, a).asnumpy()[0, 0]) == 12
    assert float(nd.divide(a, b).asnumpy()[0, 0]) == 1.5
    assert float(nd.true_divide(a, 3).asnumpy()[0, 0]) == 2
    assert float(nd.modulo(a, b).asnumpy()[0, 0]) == 2
    assert float(nd.power(a, 2).asnumpy()[0, 0]) == 36
    assert float(nd.maximum(a, 7).asnumpy()[0, 0]) == 7
    assert float(nd.minimum(7, a).asnumpy()[0, 0]) == 6
    assert nd.add(2, 3) == 5 and nd.maximum(2, 3) == 3
    # scalar-LHS for the non-commutative ops (reflected dunders)
    assert float(nd.power(2, nd.array(np.full((2,), 3.0, "f")))
                 .asnumpy()[0]) == 8
    assert float(nd.modulo(7, b).asnumpy()[0, 0]) == 3
    assert float(nd.subtract(10, a).asnumpy()[0, 0]) == 4
    assert float(nd.divide(12, b).asnumpy()[0, 0]) == 3
