"""Executor: binds a Symbol and runs it as ONE jit-compiled XLA program.

Parity: src/executor/graph_executor.{h,cc} (Bind/SimpleBind :1560-1597,
Forward :80 / Backward :93) and python/mxnet/executor.py. TPU-native design
(SURVEY.md §7 stage 4): the reference's init pipeline -- gradient pass, device
placement, shape/type inference, PlanMemory, AttachOpExecs, bulk segments --
collapses into a single traced JAX function per (mode, input shapes):
  * forward graph      -> jit(trace)                       [eval path]
  * forward + backward -> jit(value + vjp in one program)  [train path]
XLA does memory planning, fusion, scheduling and rematerialization; gradients
come from jax.vjp instead of registered _backward_* ops; loss heads use their
custom_vjp (see ops/nn.py) so ``backward()`` with implicit ones-cotangents
reproduces MXNet's head-gradient semantics. grad_req write/add/null matches
include/mxnet/op_attr_types.h:44-59 (kWriteTo/kAddTo/kNullOp).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray, zeros
from . import random as _rnd
from . import telemetry as _tel
from . import diagnostics as _diag
from .faults import injection as _faults
from .telemetry import tracing as _tracing
from .compile import pipeline as _pipeline
# compat re-exports: the program-build seam (listeners, first-call AOT
# cost capture, dispatch/demotion instrumentation, the sanitizer hook)
# moved to mxtpu/compile/pipeline.py so graph transforms have a place to
# run before tracing; every name below keeps its historical home here.
from .compile.pipeline import (_AOT_MISS, _DEMOTE_MISS_TOTAL,  # noqa: F401
                               _DEMOTE_MISSES, add_build_listener,
                               in_prewarm, instrument_program
                               as _instrument_program,
                               notify_build as _notify_build,
                               prewarm_build_count, prewarm_scope,
                               named_jit, program_build_count,
                               record_program_build,
                               remove_build_listener, set_output_sanitizer)

__all__ = ["Executor", "add_build_listener", "remove_build_listener",
           "program_build_count", "record_program_build", "named_jit",
           "device_wait",
           "set_output_sanitizer", "prewarm_scope", "in_prewarm",
           "prewarm_build_count"]


def head_cotangent(out):
    """What a head takes when backward is given no head gradient: ones, as
    the reference's loss heads; an integer head (a count that rides beside
    the loss) takes no gradient, which JAX spells float0."""
    if jnp.issubdtype(out.dtype, jnp.inexact):
        return jnp.ones_like(out)
    return _np.zeros(out.shape, jax.dtypes.float0)


def device_wait(x):
    """Block until ``x`` — a device array / NDArray, or a list of them —
    has finished computing: the explicit engine-sync point of the
    pipelined ``Module.fit`` loop (the WaitToRead analogue the bounded
    in-flight window uses to pace dispatch). It is not timed here: the
    caller's span (``fit.pace``) is the region's one clock pair.

    The wait registers itself with the diagnostics watchdog: a thread
    stuck here past the deadline is the classic wedged-device signature
    and triggers a postmortem dump."""
    if isinstance(x, (list, tuple)):
        x = [getattr(a, "_data", a) for a in x]
    else:
        x = getattr(x, "_data", x)
    _diag.wait_begin("device_wait")
    try:
        # INSIDE the registered wait on purpose: an injected latency
        # here looks to the watchdog exactly like a wedged device
        _faults.point("executor.device_wait")
        # mxtpu: allow-sync(device_wait IS the explicit pacing sync point)
        jax.block_until_ready(x)
    finally:
        _diag.wait_end()

# standing series: registry-direct so they exist for /metrics even when
# MXTPU_TELEMETRY=0 was set at import (the flag silences the helper-
# mediated per-batch sites; these build/hit counters are too cheap and
# too load-bearing for cache observability to disappear with it)
_M_CACHE_HITS = _tel.registry().counter(
    "executor_program_cache_hits",
    help="per-executor program-table hits (no retrace, no compile)")


def _block_boundaries(symbol):
    """Node ids of the graph's dataflow cut vertices: non-variable nodes
    past which no earlier intermediate is live (every value computed before
    the node and consumed after it flows *through* it). For chain-of-blocks
    models these are exactly the block boundaries — in ResNet, the
    activations after each residual join (the reference's memory-mirroring
    stage markers, __mirror_stage__ in example symbols /
    src/executor/graph_executor.cc InitFullGraph mirror option). Runs of
    directly-chained cuts are collapsed to their most downstream node, so a
    stem like conv→bn→relu→pool contributes one boundary, not four."""
    topo = symbol._topo()
    idx = {id(n): i for i, n in enumerate(topo)}
    last_use = {}
    for n in topo:
        for src, _ in n.inputs:
            if not src.is_variable:
                last_use[id(src)] = max(last_use.get(id(src), -1), idx[id(n)])
    cuts = []
    live_horizon = -1  # furthest consumer of anything computed so far
    for i, n in enumerate(topo):
        if not n.is_variable and live_horizon <= i:
            cuts.append(n)
        live_horizon = max(live_horizon, last_use.get(id(n), -1))
    cut_ids = {id(n) for n in cuts}
    for n in cuts:
        srcs = [s for s, _ in n.inputs if not s.is_variable]
        if len(srcs) == 1 and id(srcs[0]) in cut_ids:
            cut_ids.discard(id(srcs[0]))
    # the graph outputs themselves are always saved; tagging them is noise
    for n, _ in symbol._outputs:
        cut_ids.discard(id(n))
    return cut_ids


def _trace_graph(symbol, is_train, placements=None, remat_tags=None,
                 tap_filter=None):
    """Return fn(arg_vals, aux_vals, rng) -> (outputs, aux_updates_dict).

    ``placements`` maps a ctx-group name to a jax Device or Sharding:
    nodes tagged ``__ctx_group__`` (AttrScope / group2ctx, the reference's
    model-parallel mechanism — graph_executor.cc AssignContext) get their
    outputs placed there; XLA inserts the cross-device transfers that the
    reference realized as _CrossDeviceCopy nodes.

    ``remat_tags`` maps node ids to checkpoint_name tags; under a
    ``jax.checkpoint`` wrapper with a save_only_these_names policy the
    tagged activations are the ONLY residuals kept for backward — the
    selective-rematerialization hook (see module/fused.py).

    ``tap_filter`` — a regex pattern (string): intermediate outputs
    whose name ``match``es get an abs-mean *tap* (a scalar f32 reduced
    on device) collected alongside the outputs, and ``run`` returns a
    3-tuple ``(outputs, aux_updates, taps)``. This is the Monitor
    adapter's device-side stat: the tensors themselves never leave the
    device, only the scalars ride the cadence sync (obs/health.py).
    Without a filter the return stays the historical 2-tuple."""
    topo = symbol._topo()
    node_index = {id(n): i for i, n in enumerate(topo)}
    scopes = _diag.opscopes.node_scopes(topo)
    aux_nodes = symbol._aux_node_set()
    out_entries = [(id(n), i) for n, i in symbol._outputs]
    tap_prog = None
    if tap_filter is not None:
        import re
        from .symbol.symbol import _output_names
        tap_prog = re.compile(tap_filter)

    def run(arg_vals, aux_vals, rng):
        env = {}
        aux_updates = {}
        taps = {}
        for node in topo:
            if node.is_variable:
                if id(node) in aux_nodes:
                    env[(id(node), 0)] = aux_vals[node.name]
                else:
                    env[(id(node), 0)] = arg_vals[node.name]
                continue
            attrs = node.parsed_attrs()
            if "__is_train__" in node.op.attrs_spec:
                attrs = type(attrs)(attrs)
                attrs["__is_train__"] = is_train
            ins = [env[(id(n), i)] for n, i in node.inputs]
            key = jax.random.fold_in(rng, node_index[id(node)]) \
                if node.op.needs_rng else None
            # named_scope stamps the layer name into HLO op metadata, so
            # XLA/xprof traces attribute device time per layer — the
            # TPU-native form of the engine's per-op OprExecStat stamps
            # (src/engine/threaded_engine.h:314-325). The scope is unique
            # to the node (a nameless or namesake node's carries its place
            # in the order): ``ProgramRecord.op_scopes`` reads it back
            with jax.named_scope(scopes[id(node)]):
                outs = node.op.trace(attrs, ins, rng=key)
            if placements:
                grp = node._extra_attrs.get("__ctx_group__")
                if grp is not None and grp in placements:
                    outs = tuple(jax.device_put(o, placements[grp])
                                 for o in outs)
            n_vis = node.op.n_out(attrs)
            if remat_tags and id(node) in remat_tags:
                from jax.ad_checkpoint import checkpoint_name
                tag = remat_tags[id(node)]
                outs = tuple(checkpoint_name(o, tag) if i < n_vis else o
                             for i, o in enumerate(outs))
            for i in range(n_vis):
                env[(id(node), i)] = outs[i]
            if tap_prog is not None and not node.is_variable:
                for i, oname in enumerate(_output_names(node, n_vis)):
                    o = outs[i]
                    if tap_prog.match(oname) and \
                            jnp.issubdtype(o.dtype, jnp.inexact):
                        taps[oname] = jnp.mean(
                            jnp.abs(o.astype(jnp.float32)))
            # aux updates propagate back to the feeding aux variable
            if node.op.aux_names and len(outs) > n_vis:
                names = node.op.input_names(attrs, n=len(node.inputs))
                for j, an in enumerate(node.op.aux_names):
                    idx = names.index(an)
                    src = node.inputs[idx][0]
                    if src.is_variable:
                        aux_updates[src.name] = outs[n_vis + j]
        outs_list = [env[e] for e in out_entries]
        if tap_prog is not None:
            return outs_list, aux_updates, taps
        return outs_list, aux_updates

    return run


def eager_run_range(symbol, env, aux_updates, start, stop, is_train,
                    raw_args, raw_aux, rng, topo=None, trace_hook=None,
                    output_hook=None):
    """Execute topo nodes ``[start, stop)`` eagerly into ``env`` — the one
    node-at-a-time walk shared by the profiled/monitored forward and the
    predict API's PartialForward stepping (reference
    GraphExecutor::PartialForward, src/executor/graph_executor.cc:86).

    ``trace_hook(node, fn)`` wraps the op call (profiling spans);
    ``output_hook(node, n_vis, outs)`` observes visible outputs (monitor).
    Aux-state updates (e.g. BN running stats in train mode) accumulate
    into ``aux_updates`` keyed by the feeding aux variable's name."""
    topo = topo if topo is not None else symbol._topo()
    node_index = {id(n): i for i, n in enumerate(topo)}
    aux_nodes = symbol._aux_node_set()
    for node in topo[start:stop]:
        if node.is_variable:
            src = raw_aux if id(node) in aux_nodes else raw_args
            env[(id(node), 0)] = src[node.name]
            continue
        attrs = node.parsed_attrs()
        if "__is_train__" in node.op.attrs_spec:
            attrs = type(attrs)(attrs)
            attrs["__is_train__"] = is_train
        ins = [env[(id(s), i)] for s, i in node.inputs]
        key = jax.random.fold_in(rng, node_index[id(node)]) \
            if node.op.needs_rng else None

        def call(node=node, attrs=attrs, ins=ins, key=key):
            return node.op.trace(attrs, ins, rng=key)

        outs = trace_hook(node, call) if trace_hook else call()
        n_vis = node.op.n_out(attrs)
        if output_hook is not None:
            output_hook(node, n_vis, outs)
        for i in range(n_vis):
            env[(id(node), i)] = outs[i]
        if node.op.aux_names and len(outs) > n_vis:
            names = node.op.input_names(attrs, n=len(node.inputs))
            for j, an in enumerate(node.op.aux_names):
                idx = names.index(an)
                src = node.inputs[idx][0]
                if src.is_variable:
                    aux_updates[src.name] = outs[n_vis + j]


class Executor:
    """Bound computation (one device context per executor, like the reference)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else (ctx or current_context())
        # group2ctx model parallelism: group name -> Context; tagged nodes'
        # outputs are placed on that context's device inside the program
        self._placements = None
        if group2ctx:
            self._placements = {g: (c.jax_device if isinstance(c, Context)
                                    else c)
                                for g, c in group2ctx.items()}
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        self.arg_dict = self._as_dict(args, self.arg_names, "args")
        self.aux_dict = self._as_dict(aux_states or {}, self.aux_names, "aux_states")
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}
        if args_grad is None:
            self.grad_dict = {}
        else:
            self.grad_dict = self._as_dict(args_grad, self.arg_names, "args_grad",
                                           allow_missing=True)
        self.outputs = []
        self._pending_grads = None
        self._fns = {}
        self._fns_config = ()   # (pipeline config, calib flag) of the table
        # compile-pipeline state: the (possibly transformed) graph the
        # traced programs are built from, cached per (pipeline config,
        # inference flag) — the quant pass rewrites ONLY the inference
        # variant, training kinds keep f32 masters — and the report of
        # what the transforms did/rejected (the latest build's report)
        self._xform = {}
        self.pipeline_report = None
        # quant's prepared-argument contract for the inference variant:
        # {new_arg: {"src", "scale", "axis"}}; the int8 copies are
        # quantized once per source array identity and re-streamed
        self._prepared_args = {}
        self._prep_cache = {}     # src name -> (source array, int8 copy)
        self._prep_src = {}       # src name -> array at transform time
        self._monitor_callback = None
        # Adaptive heads-mode: callers that drive backward(out_grads)
        # (Module's unfused path with an external loss — the reference's
        # GraphExecutor keeps backward a separate cached program,
        # src/executor/graph_executor.cc RunOps) flip this on; subsequent
        # forwards then run the "fwd_vjp" program, which returns the vjp
        # closure (a jax pytree) alongside the outputs so backward applies
        # it directly instead of recomputing the whole forward.
        self._heads_mode = False
        self._cached_vjp = None
        self._out_slot = None
        # memory ledger: account every bound buffer (args, grads, aux) at
        # bind time. Buffer-identity dedup in the ledger means arrays
        # shared with another executor (simple_bind shared_exec, serving
        # rebinds sharing weights) count once; the origin is the ambient
        # allocation site ('serving_pool' inside a pool bind, 'executor'
        # otherwise — outermost attribution wins).
        if _diag.mem_enabled():
            led = _diag.ledger()
            ctx_label = str(self._ctx)
            with _diag.alloc_origin("executor"):
                origin = _diag.current_origin()
                for d in (self.arg_dict, self.grad_dict, self.aux_dict):
                    for v in d.values():
                        if v is not None and isinstance(v._data, jax.Array):
                            led.track(v._data, origin=origin, ctx=ctx_label)

    def _as_dict(self, vals, names, what, allow_missing=False):
        if isinstance(vals, dict):
            out = dict(vals)
        else:
            out = dict(zip(names, vals))
        if not allow_missing:
            for n in names:
                if n not in out:
                    raise MXNetError("%s: missing array for '%s'" % (what, n))
        return out

    # -------------------------------------------------- compiled programs
    def _grad_arg_names(self):
        return [n for n in self.arg_names
                if self.grad_req.get(n, "null") != "null" and n in self.grad_dict]

    def _program_symbol(self, names, infer=False):
        """The graph the traced programs compile: the bind symbol run
        through the compile pipeline (mxtpu/compile/pipeline.py). With
        the pipeline empty — the default — this IS ``self._symbol``,
        cost one dict lookup per build. The transform result is cached
        per (pipeline config, inference flag): ``infer`` builds tag the
        pipeline ``kind="executor_infer"`` and expose the bound
        parameter VALUES, which licenses inference-only rewrites (the
        quant pass quantizes weights off them); training builds keep
        ``kind="executor"`` and the f32 masters. Every accepted rewrite
        was re-proven by the verifier suite before landing here.
        ``names`` is the config the CALLER resolved — resolved exactly
        once per build, so a concurrent ``configure()`` cannot split the
        table's config stamp from the graph the program was actually
        built against."""
        key = (names, bool(infer))
        hit = self._xform.get(key)
        if hit is not None:
            sym, report = hit
            self.pipeline_report = report
            if infer:
                self._prepared_args = report.prepared_args \
                    if report is not None else {}
            return sym
        if not names:
            sym, report = self._symbol, None
        else:
            shapes = {n: tuple(v.shape)
                      for d in (self.arg_dict, self.aux_dict)
                      for n, v in d.items() if v is not None}
            types = {n: v.dtype
                     for d in (self.arg_dict, self.aux_dict)
                     for n, v in d.items() if v is not None}
            values = None
            if infer:
                values = {n: v._data for n, v in self.arg_dict.items()
                          if v is not None}
            sym, report = _pipeline.transform_graph(
                self._symbol,
                kind="executor_infer" if infer else "executor",
                shapes=shapes, types=types, values=values)
        self._xform[key] = (sym, report)
        self.pipeline_report = report
        if infer:
            self._prepared_args = report.prepared_args \
                if report is not None else {}
            self._prep_cache = {}
            self._prep_src = {
                spec["src"]: values[spec["src"]]
                for spec in self._prepared_args.values()
                if values and spec["src"] in values}
        return sym

    def _precision_tag(self):
        rep = self.pipeline_report
        return rep.precision if rep is not None else None

    def _transform_tags(self):
        rep = self.pipeline_report
        return rep.transforms if rep is not None else None

    def _cert_tag(self):
        rep = self.pipeline_report
        return rep.cert if rep is not None else None

    def _get_fn(self, kind):
        from .compile import quant as _quant
        # the program table is valid for ONE pipeline config: flipping
        # the pipeline mid-life must not serve a program built from the
        # other graph, so a config change drops the table (programs
        # rebuild lazily; flipping back rebuilds too — correctness over
        # caching for a debugging-time toggle). Arming/disarming int8
        # calibration is a config change too: observed programs carry
        # extra output heads a clean table must not keep serving.
        names = _pipeline.configured()
        cfg = (names, _quant.calibrating())
        if getattr(self, "_fns_config", ()) != cfg:
            self._fns = {}
            self._fns_config = cfg
        infer = kind == "fwd_eval"
        if infer and self._prepared_args:
            # a quantized program bakes its weight scales into the
            # graph: a swapped-in parameter array (hot-swap/set_params)
            # invalidates them, so the inference variant rebuilds and
            # re-quantizes from the NEW weights (id compare per call —
            # the prepared set is a handful of entries)
            for src, built in self._prep_src.items():
                nd = self.arg_dict.get(src)
                if nd is not None and nd._data is not built:
                    self._fns.pop("fwd_eval", None)
                    self._xform.pop((names, True), None)
                    break
        fn = self._fns.get(kind)
        if fn is not None:
            _M_CACHE_HITS.inc()
            return fn
        _notify_build(kind, self)
        symbol = self._program_symbol(names, infer=infer)
        calib_heads = None
        if infer and _quant.calibrating():
            entries = self._calib_entries(symbol)
            if entries:
                from .symbol.symbol import Symbol as _Sym
                calib_heads = tuple(nm for nm, _n, _i in entries)
                symbol = _Sym(list(symbol._outputs)
                              + [(n, i) for _nm, n, i in entries])
        if kind == "fwd_eval":
            run = _trace_graph(symbol, is_train=False,
                               placements=self._placements)
            fn = run
        elif kind == "fwd_train":
            run = _trace_graph(symbol, is_train=True,
                               placements=self._placements)
            fn = run
        elif kind == "fwd_bwd":
            run = _trace_graph(symbol, is_train=True,
                               placements=self._placements)
            gnames = tuple(self._grad_arg_names())

            def fb(arg_vals, aux_vals, rng):
                gvals = {n: arg_vals[n] for n in gnames}
                other = {n: v for n, v in arg_vals.items() if n not in gnames}

                def f(gv):
                    av = dict(other)
                    av.update(gv)
                    outs, auxu = run(av, aux_vals, rng)
                    return outs, auxu

                (outs, auxu), vjp = jax.vjp(f, gvals)
                cts = [head_cotangent(o) for o in outs]
                (grads,) = vjp((cts, {k: jnp.zeros_like(v)
                                      for k, v in auxu.items()}))
                return outs, auxu, grads

            fn = fb
        elif kind == "fwd_bwd_heads":
            run = _trace_graph(symbol, is_train=True,
                               placements=self._placements)
            gnames = tuple(self._grad_arg_names())

            def fbh(arg_vals, aux_vals, rng, head_grads):
                gvals = {n: arg_vals[n] for n in gnames}
                other = {n: v for n, v in arg_vals.items() if n not in gnames}

                def f(gv):
                    av = dict(other)
                    av.update(gv)
                    outs, auxu = run(av, aux_vals, rng)
                    return outs, auxu

                (outs, auxu), vjp = jax.vjp(f, gvals)
                (grads,) = vjp((list(head_grads),
                                {k: jnp.zeros_like(v) for k, v in auxu.items()}))
                return outs, auxu, grads

            fn = fbh
        elif kind == "fwd_vjp":
            # Forward that also returns the vjp closure. jax.vjp's result
            # is a registered pytree (its leaves are the saved residuals),
            # so it round-trips through jit; holding it keeps the
            # residuals alive on device until backward consumes them.
            run = _trace_graph(symbol, is_train=True,
                               placements=self._placements)
            gnames = tuple(self._grad_arg_names())

            def fv(arg_vals, aux_vals, rng):
                gvals = {n: arg_vals[n] for n in gnames}
                other = {n: v for n, v in arg_vals.items() if n not in gnames}

                def f(gv):
                    av = dict(other)
                    av.update(gv)
                    return run(av, aux_vals, rng)

                (outs, auxu), vjp = jax.vjp(f, gvals)
                return outs, auxu, vjp

            fn = fv
        elif kind == "vjp_apply":
            def va(vjp, head_grads, auxu):
                (grads,) = vjp((list(head_grads),
                                {k: jnp.zeros_like(v)
                                 for k, v in auxu.items()}))
                return grads

            fn = va
        else:
            raise MXNetError("unknown program kind %s" % kind)
        fn = _instrument_program(kind, named_jit("exec_" + kind, fn),
                                 owner=self, matmul_env=True,
                                 precision=self._precision_tag(),
                                 transforms=self._transform_tags(),
                                 calib_heads=calib_heads,
                                 cert=self._cert_tag(),
                                 scopes=_diag.opscopes.symbol_scopes(symbol))
        self._fns[kind] = fn
        return fn

    def _calib_entries(self, symbol):
        """Observation heads for int8 activation calibration: the
        entries ``quant_plan`` wants watched, planned on the ORIGINAL
        bind symbol (stable names — a quantized or bf16-rewritten graph
        would hide its own sites) and located by producer name in the
        traced graph ``symbol``. Returns ``[(entry_name, node, idx)]``
        in plan order."""
        from .analysis import dataflow as _df
        from .tune import registry as _knobs
        shapes = {n: tuple(v.shape)
                  for d in (self.arg_dict, self.aux_dict)
                  for n, v in d.items() if v is not None}
        types = {n: v.dtype
                 for d in (self.arg_dict, self.aux_dict)
                 for n, v in d.items() if v is not None}
        plan = _df.quant_plan(
            self._symbol, shapes=shapes, types=types,
            min_layer_elems=int(_knobs.resolve("quant.min_layer_elems")))
        if not plan.observe:
            return []
        byname = {}
        for n in symbol._topo():
            if not n.is_variable:
                byname.setdefault(n.name, n)
        out = []
        for name, node, idx in plan.observe:
            n2 = byname.get(node.name)
            if n2 is not None:
                out.append((name, n2, idx))
        return out

    def _inject_prepared(self, raw_args):
        """Swap quant's prepared arguments into the eval-program feed:
        pop each quantized weight's f32 master and stream the int8 copy
        (quantized once per source array identity) under the rewrite's
        new argument name. No-op (zero copies) without an applied quant
        rewrite."""
        prep = self._prepared_args
        if not prep:
            return raw_args
        from .compile import quant as _quant
        out = dict(raw_args)
        for new, spec in prep.items():
            cur = out.pop(spec["src"], None)
            if cur is None:
                continue
            cached = self._prep_cache.get(spec["src"])
            if cached is None or cached[0] is not cur:
                cached = (cur, _quant.quantize_array(
                    cur, spec["scale"], spec["axis"]))
                self._prep_cache[spec["src"]] = cached
            out[new] = cached[1]
        return out

    def _raw_args(self):
        return {n: self.arg_dict[n]._data for n in self.arg_names}

    def _raw_aux(self):
        return {n: self.aux_dict[n]._data for n in self.aux_names}

    def _apply_aux(self, aux_updates):
        for n, v in aux_updates.items():
            self.aux_dict[n]._data = v

    def _wrap_outputs(self, outs):
        self.outputs = [NDArray(o, self._ctx) for o in outs]
        if _diag.mem_enabled():
            # outputs churn every forward but their SIZE is bind-fixed:
            # slot accounting (freed with the executor) instead of a
            # finalizer per step
            nbytes = sum(getattr(o, "nbytes", 0) for o in outs)
            if self._out_slot is None:
                self._out_slot = _diag.ledger().slot(
                    self, nbytes, "executor_outputs", ctx=str(self._ctx))
            else:
                self._out_slot.set(nbytes)
        return self.outputs

    def _forward_profiled(self, is_train, raw_args, raw_aux, rng):
        """Node-at-a-time eager execution with a device sync + trace span
        per node: true per-layer timings for mx.profiler (the role of the
        reference's per-op engine stats, src/engine/profiler.cc:152) and
        per-op tensor stats for mx.monitor (the reference's executor
        monitor callback sees EVERY op output, not just graph outputs —
        python/mxnet/monitor.py stat_helper). Slower than the fused
        program by design; only used while a profiler or monitor is
        active."""
        from . import profiler as _prof
        from .symbol.symbol import _output_names
        mon_live = (self._monitor_callback is not None and
                    getattr(self._monitor_callback, "is_active",
                            lambda: True)())
        topo = self._symbol._topo()
        env = {}
        aux_updates = {}
        import time as _time

        def trace_hook(node, call):
            # wall-clock start (the dump's shared timebase — profiler
            # scopes and telemetry spans use time.time too), monotonic
            # duration (NTP-step safe)
            t0_wall = _time.time() * 1e6
            t0 = _time.perf_counter()
            outs = call()
            # mxtpu: allow-sync(profiled mode: per-node timing needs a
            # sync per op by design; fused program path stays async)
            jax.block_until_ready(outs)
            _prof.record_span(node.name or node.op.name, t0_wall,
                              t0_wall + (_time.perf_counter() - t0) * 1e6,
                              category=node.op.name)
            return outs

        def output_hook(node, n_vis, outs):
            if mon_live:
                for i, oname in enumerate(_output_names(node, n_vis)):
                    self._monitor_callback(oname, NDArray(outs[i], self._ctx))

        eager_run_range(self._symbol, env, aux_updates, 0, len(topo),
                        is_train, raw_args, raw_aux, rng, topo=topo,
                        trace_hook=trace_hook, output_hook=output_hook)
        outs = [env[(id(n), i)] for n, i in self._symbol._outputs]
        return outs, aux_updates

    # -------------------------------------------------- public API
    def forward(self, is_train=False, **kwargs):
        # correlated span: nests under the caller's ambient span (a
        # module fit step, a serving batch) and parents any engine /
        # kvstore spans the program triggers
        with _tracing.span("executor.forward", category="executor"):
            return self._forward_impl(is_train, **kwargs)

    def _forward_impl(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = v._data if isinstance(v, NDArray) \
                    else jnp.asarray(v)
        rng = _rnd.next_key()
        raw_args, raw_aux = self._raw_args(), self._raw_aux()
        from . import profiler as _prof
        # monitor parity needs per-op outputs, but only on batches the
        # monitor is actually sampling (Monitor.tic arms `activated`);
        # off-interval batches keep the fused program
        mon_active = (self._monitor_callback is not None and
                      getattr(self._monitor_callback, "is_active",
                              lambda: True)())
        if _prof.ops_enabled() or mon_active:
            self._fwd_snapshot = (raw_args, raw_aux, rng)
            outs, auxu = self._forward_profiled(is_train, raw_args, raw_aux,
                                                rng)
            self._pending_grads = None
            self._cached_vjp = None
            self._profiled_pending = is_train and bool(self._grad_arg_names())
            if is_train:
                self._apply_aux(auxu)
            return self._wrap_outputs(outs)
        # remember the forward's exact inputs + rng so a later
        # backward(out_grads) replays the SAME computation (same dropout
        # masks, pre-update aux) instead of a fresh stochastic forward
        self._fwd_snapshot = (raw_args, raw_aux, rng)
        want_grad = bool(self._grad_arg_names())
        self._profiled_pending = False  # this forward is fused, not eager
        self._cached_vjp = None
        if is_train and want_grad:
            if self._heads_mode:
                outs, auxu, vjp = self._get_fn("fwd_vjp")(raw_args, raw_aux,
                                                          rng)
                self._cached_vjp = (vjp, auxu)
                self._pending_grads = None
            else:
                outs, auxu, grads = self._get_fn("fwd_bwd")(raw_args,
                                                            raw_aux, rng)
                self._pending_grads = grads
        else:
            kind = "fwd_train" if is_train else "fwd_eval"
            fn = self._get_fn(kind)
            if kind == "fwd_eval":
                # _get_fn just resolved the inference variant, so the
                # prepared-arg contract matches the program being fed
                raw_args = self._inject_prepared(raw_args)
            outs, auxu = fn(raw_args, raw_aux, rng)
            self._pending_grads = None
        if is_train:
            self._apply_aux(auxu)
        return self._wrap_outputs(outs)

    def backward(self, out_grads=None, is_train=True):
        with _tracing.span("executor.backward", category="executor"):
            return self._backward_impl(out_grads=out_grads,
                                       is_train=is_train)

    def _backward_impl(self, out_grads=None, is_train=True):
        if not self._grad_arg_names():
            return
        if out_grads is None:
            grads = self._pending_grads
            if grads is None and self._cached_vjp is not None:
                vjp, auxu = self._cached_vjp
                cts = [head_cotangent(o._data) for o in self.outputs]
                grads = self._get_fn("vjp_apply")(vjp, cts, auxu)
                self._cached_vjp = None
            if grads is None and getattr(self, "_profiled_pending", False):
                # profiled forward ran node-by-node; grads come from the
                # fused program, timed as one 'backward' span
                from . import profiler as _prof
                raw_args, raw_aux, rng = self._fwd_snapshot
                with _prof.scope("backward", category="backward"):
                    outs, _auxu, grads = self._get_fn("fwd_bwd")(
                        raw_args, raw_aux, rng)
                    # mxtpu: allow-sync(profiled mode: the backward span
                    # must cover the device work it times)
                    jax.block_until_ready(grads)
                self._profiled_pending = False
            if grads is None:
                raise MXNetError("backward: call forward(is_train=True) first")
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            if self._cached_vjp is not None:
                # fast path: the forward ran in heads-mode and kept its vjp
                # closure — apply it to the caller's head gradients without
                # re-running the forward
                vjp, auxu = self._cached_vjp
                grads = self._get_fn("vjp_apply")(
                    vjp, [g._data for g in out_grads], auxu)
                self._cached_vjp = None
            else:
                # first explicit-head backward on this executor: the
                # matching forward didn't save residuals, so replay
                # forward+backward as one program — and flip heads-mode so
                # every subsequent forward caches its vjp (kills the 2x
                # forward cost from iteration 2 on)
                self._heads_mode = True
                snap = getattr(self, "_fwd_snapshot", None)
                if snap is not None:
                    raw_args, raw_aux, rng = snap
                else:
                    raw_args, raw_aux, rng = (self._raw_args(),
                                              self._raw_aux(),
                                              _rnd.next_key())
                outs, _auxu, grads = self._get_fn("fwd_bwd_heads")(
                    raw_args, raw_aux, rng, [g._data for g in out_grads])
                # aux updates were already applied by the matching forward;
                # replaying here must not double-apply them
                self._wrap_outputs(outs)
        for n, g in grads.items():
            req = self.grad_req.get(n, "null")
            dst = self.grad_dict.get(n)
            if dst is None or req == "null":
                continue
            if req == "add":
                dst._data = dst._data + g
            else:
                dst._data = g.astype(dst._data.dtype)
        self._pending_grads = None

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name]._data = jax.device_put(
                    arr._data, self._ctx.jax_device)
            elif not allow_extra_params:
                raise MXNetError("Found name \"%s\" not in arguments" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._data = jax.device_put(
                        arr._data, self._ctx.jax_device)
                elif not allow_extra_params:
                    raise MXNetError("Found name \"%s\" not in aux states" % name)

    def set_monitor_callback(self, callback):
        """Install a per-op output callback (reference parity: the monitor
        sees EVERY op's outputs). While the callback is active, forwards
        run node-at-a-time — much slower than the fused program, and a
        training backward recomputes the fused forward. Attach an
        ``is_active`` attribute returning False on unsampled batches (as
        mx.monitor.Monitor does) to keep those on the fast path."""
        self._monitor_callback = callback

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes (cheap: jit retraces per shape)."""
        with _diag.alloc_origin("executor"):
            new_args = {}
            for n in self.arg_names:
                if n in kwargs:
                    new_args[n] = zeros(kwargs[n], ctx=self._ctx,
                                        dtype=self.arg_dict[n].dtype)
                else:
                    new_args[n] = self.arg_dict[n]
            new_grads = {n: zeros(new_args[n].shape, ctx=self._ctx,
                                  dtype=new_args[n].dtype)
                         for n in self.grad_dict}
        return Executor(self._symbol, self._ctx, new_args, args_grad=new_grads,
                        grad_req=self.grad_req, aux_states=self.aux_dict)

    # -------------------------------------------------- simple_bind
    @staticmethod
    def simple_bind(symbol, ctx=None, grad_req="write", type_dict=None,
                    shared_exec=None, shared_data_arrays=None, **kwargs):
        """Allocate args/grads/aux from inferred shapes (parity SimpleBind
        graph_executor.cc:1560; memory pooling is XLA's concern here)."""
        ctx = ctx or current_context()
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("simple_bind: cannot infer shapes")
        type_dict = type_dict or {}
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_types, _, aux_types = symbol.infer_type(**{
            k: v for k, v in type_dict.items() if k in arg_names})
        inferred = dict(zip(arg_names, arg_types or []))
        inferred_aux = dict(zip(aux_names, aux_types or []))
        # attribute the fresh buffers to 'executor' AT CREATION: track()
        # is first-origin-wins, so tagging them later (Executor.__init__)
        # would lose to the 'ndarray' default the zeros() seam applies
        with _diag.alloc_origin("executor"):
            args = {}
            for name, shape in zip(arg_names, arg_shapes):
                # explicit type_dict wins; else the type inferred from the
                # data dtypes (bf16 data => bf16 weights, reference
                # InferType flow)
                dt = type_dict.get(name) or inferred.get(name) or "float32"
                if shared_exec is not None and name in shared_exec.arg_dict \
                        and shared_exec.arg_dict[name].shape == tuple(shape):
                    args[name] = shared_exec.arg_dict[name]
                else:
                    args[name] = zeros(shape, ctx=ctx, dtype=dt)
            if isinstance(grad_req, str):
                req_of = {n: grad_req for n in arg_names}
            elif isinstance(grad_req, (list, tuple)):
                req_of = dict(zip(arg_names, grad_req))
            else:
                req_of = {n: grad_req.get(n, "null") for n in arg_names}
            args_grad = {}
            for name in arg_names:
                if req_of.get(name, "null") != "null":
                    if shared_exec is not None and \
                            name in shared_exec.grad_dict and \
                            shared_exec.grad_dict[name].shape == args[name].shape:
                        args_grad[name] = shared_exec.grad_dict[name]
                    else:
                        args_grad[name] = zeros(args[name].shape, ctx=ctx,
                                                dtype=args[name].dtype)
            aux = {}
            for name, shape in zip(aux_names, aux_shapes):
                if shared_exec is not None and name in shared_exec.aux_dict \
                        and shared_exec.aux_dict[name].shape == tuple(shape):
                    aux[name] = shared_exec.aux_dict[name]
                else:
                    aux[name] = zeros(shape, ctx=ctx,
                                      dtype=inferred_aux.get(name) or "float32")
            return Executor(symbol, ctx, args, args_grad=args_grad,
                            grad_req=req_of, aux_states=aux)
