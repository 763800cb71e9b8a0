"""mxtpu: a TPU-native deep-learning framework with the MXNet v0.11 capability
surface (NDArray / Symbol / Module / Gluon / KVStore / DataIter) built on
JAX/XLA/Pallas. See SURVEY.md for the reference layer map this mirrors.

Usage parity with the reference Python package:

    import mxtpu as mx
    x = mx.nd.zeros((2, 3))
    net = mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=10)
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(net, name='softmax'))
"""
from __future__ import annotations

from .libinfo import __version__  # single source of truth

from . import base

# the persistent compile cache must be placed before the first compile
base.configure_compile_cache()
from .base import MXNetError, MXTPUError
from . import attribute
from .attribute import AttrScope
from .context import Context, cpu, gpu, tpu, current_context, num_gpus
from . import ndarray
from . import ndarray as nd
from . import symbol
from . import symbol as sym
from . import random
from . import random as rnd
from . import autograd
from . import name
from . import symbol_doc
from . import log
from . import registry
from . import libinfo
from . import telemetry
from . import diagnostics
from . import faults
from . import tune
from .executor import Executor
from . import analysis
# analysis/__init__ is deliberately light (lazy pass web); the
# sanitizer's MXTPU_SANITIZE env arming lives at ITS import, so import
# it explicitly here to preserve the arm-at-process-start contract
from .analysis import sanitizer as _sanitizer  # noqa: F401

# subsystems imported lazily-but-eagerly; order matters (no cycles)
from . import initializer
from .initializer import init  # noqa: F401  (registry namespace)
from . import optimizer
from . import lr_scheduler
from . import metric
from . import io
from . import recordio
from . import image
from . import image as img
from . import engine
from . import kvstore
from . import kvstore as kv
from . import callback
from . import monitor
from . import model
from . import module
from . import module as mod
from . import rnn
from . import gluon
from . import models
from . import visualization
from . import visualization as viz
from . import profiler
from . import test_utils
from . import parallel
from . import sharding
from . import elastic
from . import operator
from . import predict
from . import serving
from . import rtc
from . import contrib
from . import torch_bridge
from . import torch_bridge as th
from . import caffe_bridge
from . import caffe_bridge as caffe
# reference-parity call sites use mx.symbol.CaffeOp / CaffeLoss
# (plugin/caffe registers into the symbol namespace the same way)
symbol.CaffeOp = caffe_bridge.CaffeOp
symbol.CaffeLoss = caffe_bridge.CaffeLoss

from .model import FeedForward
from .kvstore import create as _kv_create


def kvstore_create(name="local"):
    return _kv_create(name)
