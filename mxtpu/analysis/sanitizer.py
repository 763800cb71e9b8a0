"""Runtime numerics sanitizer: device-side NaN/Inf output checks.

``MXTPU_SANITIZE=nan|inf|all`` makes the executor build seam wrap every
program kind it dispatches (``fwd_eval`` / ``fwd_bwd`` / ``fused_step`` /
``metric_accum`` / ...) with an output check: after each call, one small
jitted program reduces every floating-point output leaf to a per-leaf
flag ON DEVICE, a single transfer pulls the flag vector, and a trip
raises :class:`~mxtpu.base.NumericsError` AFTER emitting a structured
postmortem (``source="sanitizer"``) through the diagnostics path — the
flight-recorder ring and ``debug_state()`` captured at the moment the
bad value appeared, not three exceptions later when a metric finally
reads it.

Unset, the cost is one module-global ``None`` check per program call;
set, every call pays the check program plus a blocking host read of the
flag vector — a debugging mode, priced accordingly.
"""
from __future__ import annotations

import os as _os
import threading as _threading

from .. import diagnostics as _diag
from .. import telemetry as _tel
from ..base import MXNetError, NumericsError
from . import concurrency as _conc

__all__ = ["NumericsError", "enable", "disable", "mode", "sanitize_tree",
           "trip_count"]

_VALID = ("nan", "inf", "all")

_MODE = None
_CHECKERS = {}
_LOCK = _conc.lock("sanitizer", "_LOCK")
_TRIPS = 0


def mode():
    """The active sanitize mode ('nan' / 'inf' / 'all') or None."""
    return _MODE


def trip_count():
    """Monotone process-wide trip counter. The health divergence
    detector compares it across a cadence window to keep to ONE
    postmortem per root cause: a nonfinite the sanitizer already
    captured must not produce a second (health) postmortem for the same
    wreckage (obs/health.py)."""
    return _TRIPS


def enable(which="all"):
    """Arm the sanitizer at runtime (the env var sets the initial state).
    Installs the executor output hook, so every program dispatched from
    now on — including ones built earlier — is checked."""
    global _MODE
    which = str(which).lower()
    if which not in _VALID:
        raise MXNetError("MXTPU_SANITIZE must be one of %s, got %r"
                         % ("|".join(_VALID), which))
    _MODE = which
    from .. import executor as _executor
    _executor.set_output_sanitizer(_check_outputs)
    return which


def disable():
    """Disarm: the executor hook is removed, dispatch is check-free."""
    global _MODE
    _MODE = None
    from .. import executor as _executor
    _executor.set_output_sanitizer(None)


def _flag_fn(mode_, n_leaves):
    """Jitted reducer: list of float leaves -> uint8 flag per leaf, all
    on device. Cached per (mode, leaf avals) by the caller.

    Half-precision leaves (bf16/f16 — the mixed-precision rewrite's
    program outputs and optimizer-state views) are upcast to f32 BEFORE
    the finite check: the flag must classify the VALUE, and the upcast
    is exact (every bf16/f16 value, including every NaN/Inf, maps to
    the same f32 value), whereas reducing in 8-bit-mantissa arithmetic
    is exactly the numerics class this sanitizer exists to catch."""
    import jax
    import jax.numpy as jnp

    def flags(leaves):
        out = []
        for leaf in leaves:
            if leaf.dtype in (jnp.bfloat16, jnp.float16):
                leaf = leaf.astype(jnp.float32)
            bad = jnp.zeros((), jnp.bool_)
            if mode_ in ("nan", "all"):
                bad = bad | jnp.isnan(leaf).any()
            if mode_ in ("inf", "all"):
                bad = bad | jnp.isinf(leaf).any()
            out.append(bad)
        return jnp.stack(out)

    return jax.jit(flags)


def sanitize_tree(kind, out, precision=None):
    """Check every float leaf of ``out`` (any pytree) for NaN/Inf per the
    active mode; raise NumericsError naming the offending leaves. Public
    so tests and custom runners can sanitize arbitrary pytrees.

    ``precision`` is the tripping PROGRAM's precision tag as stamped at
    build time by the compile pipeline (e.g. ``mixed_bf16``); omitted,
    a label is derived from the checked leaf dtypes."""
    mode_ = _MODE
    if mode_ is None:
        return
    import jax
    import jax.numpy as jnp
    import numpy as _np
    scan = out
    if kind == "fused_step" and isinstance(out, tuple) and len(out) == 5:
        # health-armed step: the 5th element is the training-health stat
        # tree — sum-of-squares rows that may LEGITIMATELY overflow to
        # inf while the model state is the real root cause (and the
        # detectors classify them regardless). Check the model state
        # only; err.outputs below still carries the full tuple so the
        # donation recovery adopts everything.
        scan = out[:4]
    try:
        paths_leaves = jax.tree_util.tree_flatten_with_path(scan)[0]
    except Exception:
        paths_leaves = [((), leaf)
                        for leaf in jax.tree_util.tree_leaves(scan)]
    checked = []
    for path, leaf in paths_leaves:
        if isinstance(leaf, jax.Array) \
                and jnp.issubdtype(leaf.dtype, jnp.inexact):
            checked.append((jax.tree_util.keystr(path), leaf))
    if not checked:
        return
    key = (mode_, tuple((leaf.shape, str(leaf.dtype))
                        for _, leaf in checked))
    fn = _CHECKERS.get(key)
    if fn is None:
        with _LOCK:
            fn = _CHECKERS.get(key)
            if fn is None:
                fn = _CHECKERS[key] = _flag_fn(mode_, len(checked))
    # mxtpu: allow-sync(the sanitizer IS a sync point by contract — one
    # blocking flag-vector read per checked program call)
    flags = _np.asarray(jax.device_get(fn([leaf for _, leaf in checked])))
    if not flags.any():
        return
    bad = [(name, leaf) for flag, (name, leaf) in zip(flags, checked)
           if flag]
    desc = ", ".join("%s %s%s" % (name or "<out>", leaf.dtype,
                                  tuple(leaf.shape))
                     for name, leaf in bad[:6])
    if len(bad) > 6:
        desc += ", ... %d more" % (len(bad) - 6)
    what = {"nan": "NaN", "inf": "Inf", "all": "NaN/Inf"}[mode_]
    # the program's precision mode travels with the postmortem: a NaN in
    # a bf16-rewritten step is triaged differently from one in a pure
    # f32 program (overflow at bf16's ~3e38 ceiling vs a real div-by-0).
    # The BUILD-TIME tag wins — a bf16-rewritten program's outputs are
    # cast back to f32, so dtype scanning alone cannot see the rewrite,
    # and the current global pipeline config may not be what built it
    if not precision:
        lows = sum(1 for _, leaf in checked
                   if str(leaf.dtype) in ("bfloat16", "float16"))
        precision = "f32" if not lows else \
            ("bf16" if lows == len(checked) else "mixed")
    reason = "sanitizer: %s in outputs of program kind '%s' " \
             "(precision=%s, %d/%d leaves): %s" \
             % (what, kind, precision, len(bad), len(checked), desc)
    global _TRIPS
    _TRIPS += 1
    # registry-direct: a numerics trip must count even with the helper-
    # mediated telemetry disabled
    _tel.registry().counter(
        "sanitizer_trips", labels={"kind": kind},
        help="program calls whose outputs tripped the numerics "
             "sanitizer").inc()
    _diag.record("sanitizer", kind, desc)
    _diag.postmortem(reason, source="sanitizer")
    err = NumericsError(reason)
    # donation recovery: a fused_step call has already donated (deleted)
    # its old state trees — the caller must adopt the NEW state from the
    # exception or be left holding deleted buffers (FusedTrainStep.step
    # does; the DonationSafetyPass flags the orphaned alternative)
    err.outputs = out
    raise err


def _check_outputs(kind, out, precision=None):
    """The build-seam output hook (installed by :func:`enable`)."""
    sanitize_tree(kind, out, precision=precision)


# env arming is tolerant where the explicit enable() API is strict: a
# user writing MXTPU_SANITIZE=1 (the 0/1 convention every sibling
# MXTPU_DIAG_* var uses) means "arm everything", and an unrecognized
# value must not make `import mxtpu` itself raise in every process that
# inherits the environment — arm fully and say so instead.
_env = _os.environ.get("MXTPU_SANITIZE", "").strip().lower()
if _env in ("", "0", "false", "no", "off"):
    pass
elif _env in _VALID:
    enable(_env)
else:
    if _env not in ("1", "true", "yes", "on"):
        import logging
        logging.getLogger(__name__).warning(
            "MXTPU_SANITIZE=%r is not one of %s; arming 'all'",
            _env, "|".join(_VALID))
    enable("all")
