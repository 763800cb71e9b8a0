"""mxtpu.compile — the program-build pipeline.

Every device program in the process — executor forwards, the fused
train step, metric accumulators, serving binds — is constructed through
ONE seam (:mod:`~mxtpu.compile.pipeline`). The seam owns, in order:

1. **graph transforms**: an ordered list of analysis-licensed
   :class:`~mxtpu.analysis.rewrite.TransformPass` rewrites
   (``MXTPU_PIPELINE`` / :func:`configure`), each re-proven by the full
   verifier suite before it may compile — a rejected rewrite falls back
   to the unrewritten graph with the offending Finding;
2. **build notification**: the listener/counter seam the serving layer
   and telemetry watch (``executor_program_builds{kind=}``);
3. **instrumentation**: first-call AOT compile + cost capture into the
   diagnostics program registry, the compiled-executable dispatch fast
   path with signature-miss demotion back to jit, and the numerics
   sanitizer's output hook.

(2) and (3) lived inside ``executor.py`` through PRs 1–6; they are
carved out here so transforms have a real place to run, and so the
fused step / metric accumulators route through the identical sequence.
``mxtpu.executor`` re-exports the public names for compatibility.
"""
from __future__ import annotations

from .pipeline import (PipelineReport, add_build_listener, configure,
                       configured, instrument_program, named_jit,
                       notify_build, pipeline_scope, program_build_count,
                       record_program_build, remove_build_listener,
                       set_calib_observer, set_output_sanitizer,
                       transform_graph)
from . import quant

__all__ = [
    "PipelineReport", "transform_graph", "configure", "configured",
    "pipeline_scope",
    "add_build_listener", "remove_build_listener", "notify_build",
    "program_build_count", "record_program_build", "instrument_program",
    "named_jit",
    "set_output_sanitizer", "set_calib_observer", "quant",
]
