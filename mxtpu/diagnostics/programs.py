"""Per-program cost introspection: what every compiled program costs.

TVM-style frameworks treat per-program cost models (flops, bytes moved)
as first-class metadata — the substrate every later optimisation reads
("Learning to Optimize Tensor Programs", PAPERS.md). mxtpu builds every
device program through one seam (``executor._notify_build`` /
``record_program_build``), so this registry captures XLA's own numbers
at that seam: ``compiled.cost_analysis()`` (flops, bytes accessed) and
``compiled.memory_analysis()`` (argument/output/temp bytes, generated
code size) for every program kind in the process — executor forwards,
the fused train step, metric accumulators, serving binds.

The capture itself costs nothing extra at steady state: the build seam's
first call lowers and compiles the program explicitly (the same work
``jax.jit`` would do lazily), reads the analyses off the executable, and
keeps the compiled object as the dispatch fast path. ``MXTPU_DIAG_COST=0``
restores the plain lazy-jit path.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque

from .. import telemetry as _tel
from ..analysis import concurrency as _conc

__all__ = ["ProgramRecord", "record_program", "programs", "program_table",
           "latest_record", "keep_scopes", "cost_enabled", "set_cost_enabled", "clear",
           "summarize_shardings", "summarize_precision"]

_ENABLED = os.environ.get("MXTPU_DIAG_COST", "1") != "0"

#: retain at most this many program records (a long-lived serving
#: process rebinding shapes must not grow without bound)
MAX_RECORDS = int(os.environ.get("MXTPU_DIAG_COST_CAP", "1024"))

_ids = itertools.count(1)
_RECORDS = deque(maxlen=MAX_RECORDS)
_LOCK = _conc.lock("programs", "_LOCK")


def cost_enabled():
    return _ENABLED


def set_cost_enabled(flag):
    """Runtime toggle; affects programs built AFTER the flip (capture
    happens once, at first dispatch)."""
    global _ENABLED
    _ENABLED = bool(flag)


def owner_name(owner):
    """Normalize an owner to its display name. Callers that hold the
    name in a long-lived closure (executor._instrument_program) call
    this EARLY so the closure never pins the owner object itself."""
    if isinstance(owner, str):
        return owner
    return type(owner).__name__ if owner is not None else ""


class ProgramRecord:
    """One compiled program's captured cost/memory metadata."""

    __slots__ = ("id", "kind", "name", "owner", "created", "compile_ms",
                 "flops",
                 "bytes_accessed", "argument_bytes", "output_bytes",
                 "temp_bytes", "generated_code_bytes", "calls",
                 "n_devices", "sharded_args", "replicated_args",
                 "precision", "transforms", "cert", "_exe", "_hlo",
                 "_scopes", "_table")

    def __init__(self, kind, owner, compile_ms):
        self.id = next(_ids)
        self.kind = kind
        # the XLA module's name (``jit_mxtpu_<name>``, compile.named_jit):
        # what a profiler trace calls this program
        self.name = ""
        self.owner = owner_name(owner)
        self.created = time.time()
        self.compile_ms = compile_ms
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.generated_code_bytes = 0
        self.calls = 0
        self.n_devices = 1       # devices the program's args span (SPMD)
        self.sharded_args = 0    # arg leaves actually split over a mesh
        self.replicated_args = 0
        # dtype/precision mode: "f32"/"bf16"/"mixed" derived from the
        # captured argument dtypes, or the compile pipeline's explicit
        # tag ("mixed_bf16") when a precision rewrite built the program
        self.precision = "f32"
        # compile-pipeline passes that were APPLIED to the graph this
        # program compiled from (rejected passes never appear)
        self.transforms = ()
        # equivalence-certification tag: "ok" when every applied rewrite
        # carried a certificate, "off" when built with the gate
        # disarmed, "-" for untransformed programs
        self.cert = "-"
        self._exe = None  # weakref to the compiled executable (HLO source)
        # the scope table's raw material (``keep_scopes``): the program's
        # host-side HloModules and what the Symbol says of its nodes, until
        # ``op_scopes`` has made the table of them
        self._hlo = None
        self._scopes = None
        self._table = None

    def hlo_text(self):
        """The compiled program's HLO text, while the executable is still
        alive (held weakly — the record must not pin device programs).
        ``tools/hlo_analyze.py`` reads this instead of re-lowering."""
        exe = self._exe() if self._exe is not None else None
        if exe is None:
            return None
        try:
            return exe.as_text()
        except Exception:
            return None

    def op_scopes(self):
        """``{instruction name: OpScope(node, operator, block, phase,
        mixed)}`` for every instruction of the compiled program
        (``diagnostics.opscopes``), or None for a program that was built
        without scopes. Made on the first request from what the build seam
        kept on the host; it outlives the executable and holds nothing of
        the device."""
        with _LOCK:
            hlo, scopes = self._hlo, self._scopes
        if self._table is None and hlo is not None:
            from . import opscopes
            table = {}
            try:
                for module in hlo:
                    table.update(opscopes.build_table(module.to_string(),
                                                      scopes))
            except Exception:
                # introspection must not take down what it describes: a
                # module this reader cannot make a table of has none
                import logging
                logging.getLogger("mxtpu.diagnostics").warning(
                    "no scope table for %s", self.name, exc_info=True)
                table = None
            with _LOCK:
                self._table, self._hlo = table, None
        return self._table

    def to_dict(self):
        return {
            "id": self.id, "kind": self.kind, "name": self.name,
            "owner": self.owner,
            "created": round(self.created, 3),
            "compile_ms": round(self.compile_ms, 3),
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "calls": self.calls,
            "n_devices": self.n_devices,
            "sharded_args": self.sharded_args,
            "replicated_args": self.replicated_args,
            "precision": self.precision,
            "transforms": list(self.transforms),
            "cert": self.cert,
        }


def summarize_shardings(rec, args):
    """Annotate ``rec`` with the SPMD shape of a call's arguments: how
    many devices the arg leaves span, and how many leaves are actually
    split versus replicated. Computed from the live arrays at the build
    seam (executor ``_first_call``) — robust across jax versions, unlike
    ``Compiled.input_shardings`` introspection. Never raises."""
    try:
        import jax
        devices = set()
        sharded = replicated = 0
        for leaf in jax.tree_util.tree_leaves(args):
            if not isinstance(leaf, jax.Array):
                continue
            try:
                devs = leaf.sharding.device_set
            except Exception:
                continue
            devices |= devs
            if len(devs) <= 1:
                continue
            if leaf.sharding.is_fully_replicated:
                replicated += 1
            else:
                sharded += 1
        rec.n_devices = max(1, len(devices))
        rec.sharded_args = sharded
        rec.replicated_args = replicated
    except Exception:
        pass


def summarize_precision(rec, args, tag=None):
    """Stamp ``rec.precision``: the compile pipeline's explicit ``tag``
    wins — "mixed_bf16" after the bf16 rewrite, "int8_ptq" after an
    applied quant rewrite (a rewritten program's ARGS alone cannot tell
    the story: bf16 keeps f32 master weights, and int8 weight streams
    under per-site dequants would scan as "mixed"); otherwise the label
    derives from the captured argument dtypes ("bf16" when every float
    leaf is half-precision, "mixed" when both families appear, else the
    dominant float family). Never raises."""
    if tag:
        rec.precision = str(tag)
        return
    try:
        import jax
        import jax.numpy as jnp
        lo = hi = 0
        for leaf in jax.tree_util.tree_leaves(args):
            dt = getattr(leaf, "dtype", None)
            if dt is None or not jnp.issubdtype(dt, jnp.inexact):
                continue
            if dt in (jnp.bfloat16, jnp.float16):
                lo += 1
            else:
                hi += 1
        if lo and hi:
            rec.precision = "mixed"
        elif lo:
            rec.precision = "bf16"
        elif hi:
            rec.precision = "f32"
    except Exception:
        pass


def record_program(kind, owner, compiled, compile_ms, transforms=None,
                   cert=None, name=""):
    """Capture a freshly compiled executable's analyses into the registry
    (and the telemetry counters). Never raises — introspection must not
    take down the program it is describing. ``transforms`` stamps the
    applied compile-pipeline pass names on the record; ``cert`` the
    pipeline's equivalence-certification tag for those rewrites."""
    rec = ProgramRecord(kind, owner, compile_ms)
    rec.name = name
    if transforms:
        rec.transforms = tuple(transforms)
        rec.cert = cert or "off"
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        rec.flops = float(cost.get("flops", 0.0))
        rec.bytes_accessed = float(cost.get("bytes accessed", 0.0))
    except Exception:
        pass
    try:
        mem = compiled.memory_analysis()
        rec.argument_bytes = int(mem.argument_size_in_bytes)
        rec.output_bytes = int(mem.output_size_in_bytes)
        rec.temp_bytes = int(mem.temp_size_in_bytes)
        rec.generated_code_bytes = int(mem.generated_code_size_in_bytes)
    except Exception:
        pass
    try:
        import weakref
        rec._exe = weakref.ref(compiled)
    except TypeError:
        pass  # executable type without weakref support
    with _LOCK:
        _RECORDS.append(rec)
    reg = _tel.registry()
    labels = {"kind": kind}
    reg.counter("program_captured",
                help="programs whose cost/memory analysis was captured",
                labels=labels).inc()
    reg.counter("program_flops", labels=labels,
                help="total flops of captured programs (per execution, "
                     "summed over builds)").inc(rec.flops)
    reg.counter("program_bytes_accessed", labels=labels,
                help="total bytes-accessed of captured programs").inc(
        rec.bytes_accessed)
    g = reg.gauge("program_temp_bytes_peak", labels=labels,
                  help="largest XLA temp (scratch) allocation among "
                       "captured programs of this kind")
    if rec.temp_bytes > g.value:
        g.set(rec.temp_bytes)
    # the measurement corpus's build row (config half of the
    # config→measurement pair): appended OUTSIDE _LOCK — the durable
    # fsync append must never serialize the registry — and gated on the
    # env inside record_build itself. A corpus failure must not take
    # down the build it is describing, same contract as the analyses.
    try:
        from ..obs import corpus as _obs_corpus
        _obs_corpus.record_build(rec.to_dict())
    except Exception:
        pass
    return rec


#: records that still hold their raw HLO, oldest first: a process that
#: rebinds for ever keeps the newest few programs' modules, not all 1024
MAX_RAW_HLO = 8
_RAW = deque()


def keep_scopes(rec, compiled, scopes):
    """Keep, with ``rec``, what ``op_scopes`` makes its table of: the
    executable's host-side HloModules (copies: they pin no device program)
    and ``scopes`` (``opscopes.symbol_scopes``). Never raises."""
    try:
        hlo = list(compiled.runtime_executable().hlo_modules())
    except Exception:
        return
    with _LOCK:
        rec._hlo, rec._scopes = hlo, scopes
        _RAW.append(rec)
        while len(_RAW) > MAX_RAW_HLO:
            _RAW.popleft()._hlo = None


def programs(kind=None):
    """Snapshot of captured records (list of dicts, oldest first)."""
    with _LOCK:
        recs = list(_RECORDS)
    return [r.to_dict() for r in recs if kind is None or r.kind == kind]


def latest_record(kind=None, name=None):
    """The most recent live ProgramRecord (optionally of one kind, or of
    one XLA module name, ``jit_mxtpu_fused_step``: what a trace calls the
    program) — tooling reads its captured numbers, ``hlo_text()`` and
    ``op_scopes()`` instead of re-lowering the program
    (tools/hlo_analyze.py)."""
    with _LOCK:
        for r in reversed(_RECORDS):
            if (kind is None or r.kind == kind) \
                    and (name is None or r.name == name):
                return r
    return None


def program_table(kind=None):
    """Human-readable cost report, one row per captured program."""
    rows = programs(kind)
    header = ("id", "kind", "program", "owner", "calls", "compile_ms",
              "mflops", "mb_accessed", "arg_kb", "out_kb", "temp_kb", "devs",
              "prec", "cert", "xforms")
    lines = ["%4s %-12s %-26s %-16s %6s %10s %10s %11s %8s %8s %8s %9s "
             "%-10s %-4s %s" % header]
    for r in rows:
        devs = "%d" % r.get("n_devices", 1)
        if r.get("sharded_args"):
            devs += " (%ds)" % r["sharded_args"]
        lines.append("%4d %-12s %-26s %-16s %6d %10.1f %10.2f %11.2f %8d "
                     "%8d %8d %9s %-10s %-4s %s"
                     % (r["id"], r["kind"][:12],
                        (r.get("name") or "-")[:26], r["owner"][:16],
                        r["calls"], r["compile_ms"], r["flops"] / 1e6,
                        r["bytes_accessed"] / 1e6,
                        r["argument_bytes"] // 1024,
                        r["output_bytes"] // 1024,
                        r["temp_bytes"] // 1024, devs,
                        r.get("precision", "f32")[:10],
                        r.get("cert", "-"),
                        ",".join(r.get("transforms", ())) or "-"))
    return "\n".join(lines)


def clear():
    """Drop captured records (tests)."""
    with _LOCK:
        _RECORDS.clear()
        _RAW.clear()
