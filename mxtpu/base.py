"""Base utilities: error type, registry, attribute parsing, env config.

TPU-native replacement for the reference's dmlc-core layer (SURVEY.md L0):
``dmlc::Registry`` -> :class:`Registry`, ``dmlc::Parameter`` -> op attr specs in
``mxtpu.ops.registry``, ``dmlc::GetEnv`` -> :func:`getenv`, logging/MXNetError ABI
-> plain Python exceptions (reference: include/mxnet/base.h, python/mxnet/base.py:56).
"""
from __future__ import annotations

import ast
import logging
import os

__all__ = ["MXNetError", "MXTPUError", "NativeError", "Registry", "getenv", "string_types", "numeric_types"]

string_types = (str,)
numeric_types = (float, int)


class MXNetError(RuntimeError):
    """Error raised by the framework (parity with python/mxnet/base.py:56)."""


# native name for the new framework; MXNetError kept as a compat alias
MXTPUError = MXNetError


class NativeError(MXNetError):
    """A nonzero return from the native engine/runtime — a backend
    failure, NOT a usage error. Kept as an MXNetError subclass so
    existing ``except MXNetError`` callers still catch it, but
    distinguishable where it matters (diagnostics postmortems capture
    backend failures and stay silent on bad user input)."""


class NumericsError(MXNetError):
    """A NaN/Inf tripped the runtime numerics sanitizer
    (``MXTPU_SANITIZE``, mxtpu/analysis/sanitizer.py). The sanitizer
    emits its own structured postmortem (``source="sanitizer"``) BEFORE
    raising, so the fit/serving exception filters treat this like any
    MXNetError (no second dump) while the HTTP layer maps it to 500 —
    a numerics failure is the server's fault, not the request's."""


def getenv(name, default):
    """Typed env lookup (parity with dmlc::GetEnv). Type taken from ``default``."""
    val = os.environ.get(name)
    if val is None:
        return default
    if isinstance(default, bool):
        return val not in ("0", "false", "False", "")
    if isinstance(default, int):
        return int(val)
    if isinstance(default, float):
        return float(val)
    return val


class Registry:
    """Generic name -> object registry (parity with dmlc::Registry).

    Used for optimizers, metrics, initializers, data iterators and ops.
    """

    def __init__(self, kind):
        self.kind = kind
        self._map = {}

    def register(self, obj, name=None, aliases=()):
        key = (name or getattr(obj, "__name__", None) or str(obj)).lower()
        self._map[key] = obj
        for a in aliases:
            self._map[a.lower()] = obj
        return obj

    def get(self, name):
        key = name.lower()
        if key not in self._map:
            raise MXNetError(
                "Cannot find %s '%s'. Registered: %s"
                % (self.kind, name, sorted(self._map))
            )
        return self._map[key]

    def find(self, name):
        return self._map.get(name.lower())

    def keys(self):
        return list(self._map)

    def create(self, name, *args, **kwargs):
        return self.get(name)(*args, **kwargs)


def parse_attr(value, proto):
    """Parse a (possibly string) attribute value to the type of ``proto``.

    Symbol JSON stores all attrs as strings (reference nnvm attr dicts);
    this is the counterpart of dmlc::Parameter string parsing.
    """
    if proto is None:
        return value
    if isinstance(proto, type):
        ty = proto
    else:
        ty = type(proto)
    if value is None:
        return value
    if ty is bool:
        if isinstance(value, str):
            return value.strip().lower() in ("1", "true", "yes")
        return bool(value)
    if ty in (tuple, list):
        if isinstance(value, str):
            v = ast.literal_eval(value) if value.strip() else ()
            # attr_repr writes one-element tuples without a trailing
            # comma ("(1)"), which literal_eval reads back as a scalar
            return (v,) if not isinstance(v, (tuple, list)) else tuple(v)
        if isinstance(value, (tuple, list)):
            return tuple(value)
        return (value,)
    if ty is int:
        if isinstance(value, str) and value.lower() == "none":
            return None
        return int(float(value)) if isinstance(value, str) else int(value)
    if ty is float:
        return float(value)
    if ty is str:
        return str(value)
    return value


def attr_repr(value):
    """Serialize an attribute for symbol JSON (everything becomes a string)."""
    if isinstance(value, (tuple, list)):
        return "(" + ", ".join(str(v) for v in value) + ")"
    return str(value)


def get_logger(name="mxtpu"):
    # deliberately no basicConfig() here: the library must not hijack the
    # application's logging setup
    return logging.getLogger(name)


class PrefixOpNamespace:
    """Sub-namespace over a module exposing prefix-registered ops, e.g.
    nd.contrib.MultiBoxPrior -> module attr '_contrib_MultiBoxPrior'
    (parity: the reference's _init_op_module sub-namespaces, base.py:_init_op_module)."""

    def __init__(self, module, prefix):
        self._module = module
        self._prefix = prefix

    def __getattr__(self, name):
        full = self._prefix + name
        if hasattr(self._module, full):
            return getattr(self._module, full)
        raise AttributeError("%s%s" % (self._prefix, name))

    def __dir__(self):
        n = len(self._prefix)
        return [k[n:] for k in dir(self._module)
                if k.startswith(self._prefix)]


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_forced():
    """True when JAX was held to the CPU platform (``JAX_PLATFORMS=cpu``
    or the ``jax_platforms`` config — what tests/conftest.py and the
    tier-1 command set). The one condition under which ``tpu()``/``gpu()``
    contexts may alias CPU devices."""
    import jax
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def compile_cache_dir(environ=None):
    """Where this checkout keeps JAX's persistent compilation cache, or
    None when ``JAX_COMPILATION_CACHE_DIR`` places it from outside (JAX
    reads that variable itself; nothing here may override it). The
    directory is fixed — never derived from a pid, the clock, a tempdir
    or the cwd — because a cache that moves never hits."""
    environ = os.environ if environ is None else environ
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_REPO_ROOT, ".jax_cache")


def configure_compile_cache():
    """Point JAX at :func:`compile_cache_dir` — the one place in the tree
    that sets a cache directory. Called once at package import. A process
    held to the CPU keeps JAX's default (no persistent cache): every
    XLA:CPU cache hit logs two multi-KB "machine type doesn't match"
    errors about tuning pseudo-features on the very machine that wrote
    the entry, and the programs worth keeping are the chip's."""
    path = compile_cache_dir()
    if path is not None and not cpu_forced():
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
