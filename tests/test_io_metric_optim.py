"""io / metric / optimizer / initializer / recordio unit tests (model:
reference tests/python/unittest/{test_io.py,test_metric.py,test_optimizer.py,
test_init.py,test_recordio.py})."""
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd


def test_ndarray_iter():
    X = np.arange(40).reshape(10, 4).astype("f4")
    y = np.arange(10).astype("f4")
    it = mx.io.NDArrayIter(X, y, batch_size=4, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (4, 4)
    assert batches[2].pad == 2
    it.reset()
    assert len(list(it)) == 3
    it2 = mx.io.NDArrayIter(X, y, batch_size=4, last_batch_handle="discard")
    assert len(list(it2)) == 2


def test_ndarray_iter_provide():
    X = np.zeros((8, 2, 3), dtype="f4")
    it = mx.io.NDArrayIter(X, batch_size=4)
    desc = it.provide_data[0]
    assert desc.name == "data"
    assert desc.shape == (4, 2, 3)


def test_resize_iter():
    X = np.zeros((8, 2), dtype="f4")
    it = mx.io.ResizeIter(mx.io.NDArrayIter(X, batch_size=4), size=5)
    assert len(list(it)) == 5


def test_prefetching_iter():
    X = np.random.randn(16, 3).astype("f4")
    y = np.zeros(16, dtype="f4")
    base = mx.io.NDArrayIter(X, y, batch_size=4)
    it = mx.io.PrefetchingIter(base)
    count = 0
    for batch in it:
        assert batch.data[0].shape == (4, 3)
        count += 1
    assert count == 4


def test_csv_iter(tmp_path):
    data_path = str(tmp_path / "data.csv")
    np.savetxt(data_path, np.arange(20).reshape(5, 4), delimiter=",")
    it = mx.io.CSVIter(data_csv=data_path, data_shape=(4,), batch_size=2)
    b = next(iter(it))
    assert b.data[0].shape == (2, 4)


def test_metrics():
    acc = mx.metric.create("acc")
    pred = nd.array(np.array([[0.9, 0.1], [0.2, 0.8]]))
    label = nd.array(np.array([0., 1]))
    acc.update([label], [pred])
    assert acc.get()[1] == 1.0
    mse = mx.metric.create("mse")
    mse.update([nd.zeros((2, 1))], [nd.ones((2, 1))])
    assert np.isclose(mse.get()[1], 1.0)
    top2 = mx.metric.create("top_k_accuracy", top_k=2)
    top2.update([label], [pred])
    assert top2.get()[1] == 1.0
    comp = mx.metric.create(["acc", "mse"])
    assert isinstance(comp, mx.metric.CompositeEvalMetric)
    ppl = mx.metric.Perplexity(ignore_label=None)
    ppl.update([label], [pred])
    assert ppl.get()[1] > 1.0


def test_custom_metric():
    def my_mse(label, pred):
        return float(((label.reshape(-1, 1) - pred) ** 2).mean())
    m = mx.metric.np(my_mse)
    m.update([nd.zeros((2,))], [nd.ones((2, 1))])
    assert np.isclose(m.get()[1], 1.0)


def test_optimizers_step():
    for name in ("sgd", "adam", "rmsprop", "adagrad", "adadelta", "ftrl",
                 "adamax", "nadam", "nag", "sgld"):
        opt = mx.optimizer.create(name, learning_rate=0.01, wd=0.0)
        w = nd.ones((4,))
        g = nd.ones((4,)) * 0.5
        state = opt.create_state(0, w)
        w_before = w.asnumpy().copy()
        opt.update(0, w, g, state)
        assert not np.allclose(w.asnumpy(), w_before), name


def test_lr_scheduler():
    opt = mx.optimizer.SGD(learning_rate=1.0,
                           lr_scheduler=mx.lr_scheduler.FactorScheduler(
                               step=2, factor=0.5))
    w = nd.ones((2,))
    g = nd.ones((2,))
    s = opt.create_state(0, w)
    lrs = []
    for _ in range(6):
        opt.update(0, w, g, s)
        lrs.append(opt._get_lr(0))
    assert lrs[-1] < lrs[0]
    multi = mx.lr_scheduler.MultiFactorScheduler(step=[2, 4], factor=0.1)
    multi.base_lr = 1.0
    assert np.isclose(multi(5), 0.01)


def test_updater_serialization():
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
    upd = mx.optimizer.get_updater(opt)
    w, g = nd.ones((2,)), nd.ones((2,))
    upd(0, g, w)
    states = upd.get_states()
    assert isinstance(states, bytes)


def test_initializers():
    for init, check in [
            (mx.initializer.Zero(), lambda a: np.allclose(a, 0)),
            (mx.initializer.One(), lambda a: np.allclose(a, 1)),
            (mx.initializer.Constant(2.5), lambda a: np.allclose(a, 2.5)),
            (mx.initializer.Uniform(0.1), lambda a: np.abs(a).max() <= 0.1),
            (mx.initializer.Xavier(), lambda a: a.std() > 0),
            (mx.initializer.Normal(0.01), lambda a: a.std() < 0.1),
            (mx.initializer.Orthogonal(), lambda a: a.std() > 0)]:
        arr = nd.zeros((8, 8))
        init("test_weight", arr)
        assert check(arr.asnumpy()), type(init).__name__
    # suffix dispatch
    arr = nd.zeros((4,))
    mx.initializer.Uniform()("fc1_bias", arr)
    assert np.allclose(arr.asnumpy(), 0)
    arr2 = nd.zeros((4,))
    mx.initializer.Uniform()("bn_gamma", arr2)
    assert np.allclose(arr2.asnumpy(), 1)


def test_recordio(tmp_path):
    from mxtpu import recordio
    path = str(tmp_path / "test.rec")
    writer = recordio.MXRecordIO(path, "w")
    for i in range(5):
        writer.write(b"record%d" % i)
    writer.close()
    reader = recordio.MXRecordIO(path, "r")
    for i in range(5):
        assert reader.read() == b"record%d" % i
    assert reader.read() is None


def test_indexed_recordio(tmp_path):
    from mxtpu import recordio
    path = str(tmp_path / "test.rec")
    idx_path = str(tmp_path / "test.idx")
    writer = recordio.MXIndexedRecordIO(idx_path, path, "w")
    for i in range(5):
        writer.write_idx(i, b"record%d" % i)
    writer.close()
    reader = recordio.MXIndexedRecordIO(idx_path, path, "r")
    assert reader.read_idx(3) == b"record3"
    assert reader.keys == list(range(5))


def test_recordio_pack_unpack():
    from mxtpu import recordio
    header = recordio.IRHeader(0, 3.0, 7, 0)
    packed = recordio.pack(header, b"payload")
    h2, content = recordio.unpack(packed)
    assert h2.label == 3.0
    assert h2.id == 7
    assert content == b"payload"
    # vector label
    header = recordio.IRHeader(0, np.array([1.0, 2, 3], dtype="f4"), 1, 0)
    packed = recordio.pack(header, b"x")
    h3, content = recordio.unpack(packed)
    assert np.allclose(h3.label, [1, 2, 3])


def test_kvstore_save_load_optimizer_states(tmp_path):
    store = mx.kv.create("local")
    store.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    store.init(0, nd.ones((2,)))
    store.push(0, nd.ones((2,)))
    fname = str(tmp_path / "states.bin")
    store.save_optimizer_states(fname)
    store.load_optimizer_states(fname)


def test_map_metric():
    import importlib.util
    import os
    import numpy as np
    import mxtpu as mx

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "ssd", "evaluate.py")
    spec = importlib.util.spec_from_file_location("ssd_evaluate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    m = mod.MApMetric(ovp_thresh=0.5)
    # one image, one gt box of class 0; detections: one perfect hit and
    # one false positive of class 1
    label = np.full((1, 4, 5), -1.0, "float32")
    label[0, 0] = [0, 0.1, 0.1, 0.5, 0.5]
    det = np.full((1, 4, 6), -1.0, "float32")
    det[0, 0] = [0, 0.9, 0.1, 0.1, 0.5, 0.5]   # matches gt -> tp
    det[0, 1] = [1, 0.8, 0.6, 0.6, 0.9, 0.9]   # class with no gt
    m.update([mx.nd.array(label)], [mx.nd.array(det)])
    name, val = m.get()
    assert name == "mAP"
    assert abs(val - 1.0) < 1e-6  # class 0 AP=1; class 1 has no gt -> skip

    # a missed gt halves recall
    m2 = mod.MApMetric()
    label2 = np.full((1, 4, 5), -1.0, "float32")
    label2[0, 0] = [0, 0.1, 0.1, 0.5, 0.5]
    label2[0, 1] = [0, 0.6, 0.6, 0.9, 0.9]
    m2.update([mx.nd.array(label2)], [mx.nd.array(det)])
    _, val2 = m2.get()
    assert abs(val2 - 0.5) < 1e-6


@pytest.mark.slow  # tier-1 time budget (ROADMAP ops note, PR 7):
# heaviest non-gate tests run in the slow tier (-m slow) so the
# 870s dots-in-window metric keeps measuring the whole fast tier
def test_benchmark_score_smoke():
    """tools/benchmark_score.py (parity example/image-classification/
    benchmark_score.py): the zoo inference sweep runs and reports img/s."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "benchmark_score.py"),
         "--networks", "resnet-18", "--batch-sizes", "2",
         "--num-batches", "2", "--cpu"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo))
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["images_per_sec"] > 0
