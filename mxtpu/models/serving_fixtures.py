"""Zoo models packaged as serving fixtures.

Each fixture is (symbol_json, params, example_shapes): an inference graph,
randomly-initialized weights in the checkpoint ``arg:``/``aux:`` naming,
and per-request input shapes with a leading batch dim of 1 — exactly what
``ServingSession`` / ``ExecutorPool`` consume. Used by the serving tests
and ``examples/serving``; sized so CPU tier-1
runs stay fast while the graphs remain real zoo topologies.
"""
from __future__ import annotations

import numpy as _np

from .. import ndarray as nd
from . import lenet as _lenet
from . import mlp as _mlp
from . import resnet as _resnet

__all__ = ["FIXTURES", "get_fixture"]


def _init_params(symbol, example_shapes, seed=0):
    """Xavier-ish random weights for every non-input arg + aux state."""
    rng = _np.random.RandomState(seed)
    arg_shapes, _, aux_shapes = symbol.infer_shape(**example_shapes)
    params = {}
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name in example_shapes:
            continue
        fan_in = int(_np.prod(shape[1:])) if len(shape) > 1 else shape[0]
        scale = 1.0 / max(1.0, _np.sqrt(fan_in))
        params["arg:" + name] = nd.array(
            rng.uniform(-scale, scale, size=shape).astype(_np.float32))
    for name, shape in zip(symbol.list_auxiliary_states(), aux_shapes):
        # moving_var-style states must be positive
        init = _np.ones(shape, dtype=_np.float32) \
            if "var" in name else _np.zeros(shape, dtype=_np.float32)
        params["aux:" + name] = nd.array(init)
    return params


def _mlp_fixture():
    sym = _mlp.get_symbol(num_classes=10)
    shapes = {"data": (1, 784)}
    return sym, shapes


def _lenet_fixture():
    sym = _lenet.get_symbol(num_classes=10)
    shapes = {"data": (1, 1, 28, 28)}
    return sym, shapes


def _resnet_fixture():
    # small-image resnet-8: the smallest legal (num_layers-2) % 6 == 0
    # depth on the <=28px three-stage path
    sym = _resnet.get_symbol(num_classes=10, num_layers=8,
                             image_shape=(3, 28, 28))
    shapes = {"data": (1, 3, 28, 28)}
    return sym, shapes


FIXTURES = {
    "mlp": _mlp_fixture,
    "lenet": _lenet_fixture,
    "resnet": _resnet_fixture,
}


def get_fixture(name, seed=0):
    """(symbol_json, params, example_shapes) for a named zoo fixture."""
    if name not in FIXTURES:
        raise KeyError("unknown serving fixture %r (have %s)"
                       % (name, sorted(FIXTURES)))
    sym, shapes = FIXTURES[name]()
    params = _init_params(sym, shapes, seed=seed)
    return sym.tojson(), params, shapes
