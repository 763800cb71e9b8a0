"""While the fused step trains, the per-node executors' parameter and
gradient buffers are handed back to the device (`Module._park_execs`) and
re-filled the moment the classic path is driven."""
import jax
import numpy as np

import mxtpu as mx


def _module():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32) + 2 * (x[:, 1] > 0)
    it = mx.io.NDArrayIter(x, y, batch_size=16, label_name="softmax_label")
    return mx.mod.Module(net, context=mx.cpu()), it


def _parked(mod):
    return [isinstance(a._data, jax.ShapeDtypeStruct)
            for exe in mod._exec_group.execs
            for table in (exe.arg_dict, exe.grad_dict)
            for n, a in table.items() if n in mod._fused.params]


def test_dormant_executors_hold_no_parameter_or_gradient_buffers():
    mod, it = _module()
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod._fused is not None and mod._execs_parked
    assert all(_parked(mod)) and len(_parked(mod)) == 8
    # shapes and dtypes are still there to read
    assert mod._exec_group.param_arrays[0][0].shape == (16, 8)
    assert str(mod._exec_group.param_arrays[0][0].dtype) == "float32"

    # the classic path re-fills them from the step's own state
    trained = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    it.reset()
    got = mod.predict(it).asnumpy()
    assert not mod._execs_parked and not any(_parked(mod))
    for exe in mod._exec_group.execs:
        for k, v in trained.items():
            np.testing.assert_array_equal(exe.arg_dict[k].asnumpy(), v)
            assert float(np.abs(exe.grad_dict[k].asnumpy()).max()) == 0.0
    x = np.asarray(it.data[0][1])
    h = np.maximum(x @ trained["fc1_weight"].T + trained["fc1_bias"], 0)
    z = h @ trained["fc2_weight"].T + trained["fc2_bias"]
    want = np.exp(z - z.max(1, keepdims=True))
    np.testing.assert_allclose(got, want / want.sum(1, keepdims=True),
                               rtol=1e-4, atol=1e-6)

    # and the next fused step parks them again, losing nothing
    it.reset()
    mod.fit(it, num_epoch=1, begin_epoch=0, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod._execs_parked and all(_parked(mod))
    moved = mod.get_params()[0]["fc1_weight"].asnumpy()
    assert np.abs(moved - trained["fc1_weight"]).max() > 0


def test_a_manual_loop_over_the_classic_path_is_unaffected():
    mod, it = _module()
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    batch = next(iter(it))
    mod.forward(batch, is_train=True)      # per-node executors
    mod.backward()
    grads = [g[0].asnumpy() for g in mod._exec_group.grad_arrays]
    assert any(np.abs(g).max() > 0 for g in grads)
    mod.update()
    assert not mod._execs_parked
