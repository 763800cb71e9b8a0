"""Device contexts mapped onto JAX devices.

Parity: include/mxnet/base.h ``Context{kCPU,kGPU,kCPUPinned}`` and
python/mxnet/context.py. TPU-native twist: ``tpu(i)`` is first-class and ``gpu(i)``
aliases the i-th accelerator so reference scripts (``ctx=mx.gpu(0)``) run unmodified
on TPU. Device placement uses ``jax.device_put``; there are no per-device streams to
manage -- XLA/PJRT owns scheduling (SURVEY.md L3 engine collapses into PJRT events).
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError, cpu_forced

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_devices"]


def _cpu_devices():
    # local (addressable) devices only: under jax.distributed, jax.devices()
    # is the GLOBAL list and other processes' devices can't back an NDArray
    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError:
        return jax.local_devices()


def _resolve_accel(devices, forced):
    """The devices ``tpu(i)``/``gpu(i)`` index into: the non-CPU ones of
    ``devices``. With none present the CPU devices stand in ONLY when the
    CPU platform was forced (``JAX_PLATFORMS=cpu``, the test mesh);
    otherwise a missing accelerator is an error, never a silent host run."""
    accel = [d for d in devices if d.platform != "cpu"]
    if accel:
        return accel
    if forced:
        return list(devices)
    raise MXNetError(
        "no accelerator: jax found only platform(s) %s. tpu()/gpu() "
        "contexts alias the CPU only under JAX_PLATFORMS=cpu"
        % sorted({d.platform for d in devices}))


def _accel_devices():
    return _resolve_accel(jax.local_devices(), cpu_forced())


class Context:
    """A device context. devtype 'cpu'|'gpu'|'tpu'; 'gpu' aliases accelerators."""

    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
    devid2type = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_id = device_type.device_id
            device_type = device_type.device_type
        if device_type not in self.devtype2id:
            raise MXNetError("unknown device type %s" % device_type)
        self.device_type = device_type
        self.device_id = device_id

    @property
    def device_typeid(self):
        return self.devtype2id[self.device_type]

    @property
    def jax_device(self):
        """The concrete jax.Device this context maps to."""
        if self.device_type in ("cpu", "cpu_pinned"):
            cpus = _cpu_devices()
            return cpus[min(self.device_id, len(cpus) - 1)]
        devs = _accel_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %s: device_id %d out of range (%d devices)"
                % (self.device_type, self.device_id, len(devs))
            )
        return devs[self.device_id]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __enter__(self):
        if not hasattr(self._default_ctx, "stack"):
            self._default_ctx.stack = []
        self._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        self._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls):
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return Context("cpu", 0)


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias for the i-th accelerator (TPU chip here); keeps reference scripts working."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def current_context():
    return Context.default_ctx()


def num_gpus():
    return len([d for d in jax.devices() if d.platform != "cpu"])


def num_devices():
    return len(jax.devices())
