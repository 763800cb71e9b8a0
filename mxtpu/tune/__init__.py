"""mxtpu.tune — the knob registry.

:mod:`~mxtpu.tune.registry` declares every tunable once (name, kind,
default, environment override); ``fit`` / serving / decode / elastic /
compile pull their values through :func:`resolve`, which holds the one
precedence rule: default < environment < explicit argument.

See docs/tune.md.
"""
from __future__ import annotations

from .registry import (Knob, catalog_table, declare, get_knob, knobs,
                       registry_version, resolve, resolve_int)

__all__ = [
    "Knob", "declare", "get_knob", "knobs", "registry_version",
    "resolve", "resolve_int", "catalog_table",
]
