"""Bind-time fused train step for Module: fwd+bwd+optimizer in ONE program.

The reference splits a training step into forward, backward, kvstore
push/pull, and a per-parameter updater loop (python/mxnet/module/module.py
:615 update -> model.py _update_params; graph_executor.cc:1322 runs the
graph in bulk segments). On TPU that split costs one device program per
parameter per step. Here the whole step — forward, vjp backward, gradient
averaging across devices, and the optimizer update for every parameter —
is a single jitted XLA program with donated buffers: zero per-parameter
dispatch, buffers reused in place, and (with several devices) GSPMD
inserting the gradient all-reduce over the mesh.

Arithmetic parity: the update rules call the SAME kernel functions the
NDArray optimizer path dispatches to (ops/optimizer_ops.py — the analogue
of src/operator/optimizer_op.cc:37-278), and per-parameter lr/wd
(schedulers, lr_mult/wd_mult) are computed each step by the Optimizer's
own _get_lr/_get_wd, so a fused step is bit-compatible with the unfused
one up to reduction order.
"""
from __future__ import annotations

import logging
import math
import threading

import jax
import jax.numpy as jnp
import numpy as _np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import diagnostics as _diag
from .. import random as _rnd
from ..base import NumericsError
from ..compile import pipeline as _pipeline
from ..diagnostics import opscopes as _opscopes
from ..executor import _trace_graph, head_cotangent
from ..ops import optimizer_ops as _ops


class _Hyper(dict):
    """Attribute-style view used to call the registered update kernels."""

    def __getattr__(self, k):
        return self.get(k)


@jax.jit
def _snapshot(tree):
    """On-device copy of a pytree in one program (fresh buffers, so later
    donations of the originals can't invalidate the snapshot)."""
    return jax.tree.map(jnp.copy, tree)


def _state_zeros(w):
    """Optimizer-state buffer for weight `w`, in the dtype the update rule
    will produce. lr/wd enter the fused step as traced f32 scalars, so
    every rule's state math promotes to (at least) f32 — initializing the
    state in the weight's low precision would flip the step signature
    bf16->f32 after the first call and force a full recompile. f32 state is
    also the numerically right choice (master momentum, as mp_sgd keeps)."""
    return jnp.zeros(jnp.shape(w), jnp.promote_types(jnp.result_type(w),
                                                     jnp.float32))


def _rule_sgd(opt):
    mom = float(getattr(opt, "momentum", 0.0) or 0.0)
    base = {"rescale_grad": opt.rescale_grad,
            "clip_gradient": opt.clip_gradient or -1.0, "momentum": mom}

    def init(w):
        return _state_zeros(w) if mom else None

    def apply(p, g, s, lr, wd):
        a = _Hyper(base, lr=lr, wd=wd)
        if mom:
            return _ops._sgd_mom_update(a, p, g, s)
        return _ops._sgd_update(a, p, g), None

    return init, apply, None


def _rule_nag(opt):
    mom = float(getattr(opt, "momentum", 0.0) or 0.0)
    rescale, clip = opt.rescale_grad, opt.clip_gradient

    def init(w):
        return _state_zeros(w) if mom else None

    def apply(p, g, s, lr, wd):
        g = g * rescale
        if clip:
            g = jnp.clip(g, -clip, clip)
        if mom:
            gw = g + wd * p
            s2 = mom * s + gw
            return p - lr * (gw + mom * s2), s2
        return p - lr * (g + wd * p), None

    return init, apply, None


def _rule_adam(opt):
    base = {"rescale_grad": opt.rescale_grad,
            "clip_gradient": opt.clip_gradient or -1.0,
            "beta1": opt.beta1, "beta2": opt.beta2, "epsilon": opt.epsilon}

    def init(w):
        return (_state_zeros(w), _state_zeros(w))

    def apply(p, g, s, lr, wd):
        a = _Hyper(base, lr=lr, wd=wd)
        w2, m2, v2 = _ops._adam_update(a, p, g, s[0], s[1])
        return w2, (m2, v2)

    # the Python path folds bias correction into lr (optimizer.py Adam.update)
    def lr_scale(t):
        return math.sqrt(1.0 - opt.beta2 ** t) / (1.0 - opt.beta1 ** t)

    return init, apply, lr_scale


def _rule_rmsprop(opt):
    base = {"rescale_grad": opt.rescale_grad,
            "clip_gradient": opt.clip_gradient or -1.0,
            "gamma1": opt.gamma1, "gamma2": getattr(opt, "gamma2", 0.9),
            "epsilon": opt.epsilon,
            "clip_weights": getattr(opt, "clip_weights", None) or -1.0}
    centered = bool(getattr(opt, "centered", False))

    def init(w):
        if centered:
            return (_state_zeros(w), _state_zeros(w), _state_zeros(w))
        return (_state_zeros(w),)

    def apply(p, g, s, lr, wd):
        a = _Hyper(base, lr=lr, wd=wd)
        if centered:
            w2, n2, g2, d2 = _ops._rmspropalex_update(a, p, g, *s)
            return w2, (n2, g2, d2)
        w2, n2 = _ops._rmsprop_update(a, p, g, s[0])
        return w2, (n2,)

    return init, apply, None


def _rule_adagrad(opt):
    rescale, clip, eps = opt.rescale_grad, opt.clip_gradient, opt.float_stable_eps

    def init(w):
        return _state_zeros(w)

    def apply(p, g, s, lr, wd):
        # history accumulates the raw (rescaled/clipped) gradient; weight
        # decay applies OUTSIDE the preconditioner (optimizer.py AdaGrad.update)
        g = g * rescale
        if clip:
            g = jnp.clip(g, -clip, clip)
        s2 = s + jnp.square(g)
        return p - lr * (g / jnp.sqrt(s2 + eps) + wd * p), s2

    return init, apply, None


_RULES = {"SGD": _rule_sgd, "NAG": _rule_nag, "Adam": _rule_adam,
          "RMSProp": _rule_rmsprop, "AdaGrad": _rule_adagrad}


def supports(optimizer):
    """Whether a fused-step update rule exists for this optimizer."""
    name = type(optimizer).__name__
    if name not in _RULES:
        return False
    if name == "SGD" and getattr(optimizer, "multi_precision", False):
        return False  # fp16 master-weight path stays on the NDArray kernels
    return True


class FusedState:
    """Mutable device-state store for fused training, shareable between
    several FusedTrainStep instances (BucketingModule: one step per bucket
    over ONE set of weights/optimizer moments, the analogue of the
    reference's shared-executor parameter arrays in
    python/mxnet/module/bucketing_module.py switch_bucket)."""

    def __init__(self):
        self.params = None     # name -> device array (all params incl fixed)
        self.aux = None
        self.opt_state = None  # name -> pytree for trainable params
        self.host_stale = False   # device params newer than host _arg_params
        self.exec_stale = False   # device params newer than executor arrays
        self.mem_slot = None   # ctx -> ledger slot: params+aux+opt bytes
        # (shared across bucket steps — one FusedState, one accounting
        # entry per device the state is sharded/replicated onto)
        from ..analysis import concurrency as _conc
        self._mem_lock = _conc.lock("FusedState", "_mem_lock")

    def update_mem_slot(self, devices):
        """(Re)account this state's device bytes in the memory ledger.
        Slot accounting, not per-buffer finalizers: the donated step
        replaces every buffer each iteration while the SIZE stays
        shape-fixed, so the slots stay exact with zero per-step cost.
        Bytes are attributed per device via ``addressable_shards`` — a
        replicated leaf really holds a full copy on every device, a
        batch-sharded opt state only its shard."""
        if not _diag.mem_enabled():
            return
        by_ctx = {}
        default = _diag.device_label(devices[0]) if devices else "unknown"
        for leaf in jax.tree.leaves((self.params, self.aux,
                                     self.opt_state)):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                for sh in shards:
                    ctx = _diag.device_label(sh.device)
                    by_ctx[ctx] = by_ctx.get(ctx, 0) + sh.data.nbytes
            elif getattr(leaf, "nbytes", 0):
                by_ctx[default] = by_ctx.get(default, 0) + leaf.nbytes
        # two fits sharing this state (bucket steps on threads) may
        # re-account concurrently: serialize the check-then-insert or
        # one ctx gets two slots and the bytes double-count
        with self._mem_lock:
            if self.mem_slot is None:
                self.mem_slot = {}
            for ctx, nbytes in by_ctx.items():
                cur = self.mem_slot.get(ctx)
                if cur is None:
                    self.mem_slot[ctx] = _diag.ledger().slot(
                        self, nbytes, "fused_step", ctx=ctx)
                else:
                    cur.set(nbytes)
            for ctx, cur in self.mem_slot.items():
                if ctx not in by_ctx:   # device dropped on a re-bind
                    cur.set(0)


class FusedTrainStep:
    """One-program train step bound to a Symbol and a set of devices.

    ``devices`` with more than one entry builds a ('data',) mesh: the batch
    shards over it, params/aux replicate, and the gradient mean implied by
    vjp-under-GSPMD reproduces the kvstore sum + rescale_grad semantics.

    ``plan``: a :class:`mxtpu.sharding.ShardingPlan` — the step then jits
    under the plan's mesh with explicit in/out shardings: params/aux on
    their plan specs (replicated for pure data parallel), the batch
    sharded over ``data``, and the optimizer state on the plan's
    **weight-update sharding** specs. Gradients entering the update are
    constrained to the optimizer-state sharding, so GSPMD lowers the
    gradient all-reduce to a reduce-scatter, runs the update on 1/n of
    the rows per replica, and the replicated ``out_shardings`` on the
    params force the weight all-gather — same numbers as the replicated
    update (up to reduction order), 1/n optimizer memory and update
    flops per chip.

    ``state``: pass an existing FusedState to share weights/opt-state with
    other steps (bucketing); omitted, a private store is created.

    ``graph_shapes``/``graph_types``: inference hints (data/label/param
    shapes) for the compile pipeline's analyses and its verifier re-run;
    ``module`` feeds the module-scoped verifier passes (donation,
    sharding_consistency) when a transform's output is re-proven.
    """

    def __init__(self, symbol, devices, param_names, data_names, label_names,
                 optimizer, fixed_param_names=(), logger=None, state=None,
                 plan=None, graph_shapes=None, graph_types=None,
                 module=None):
        self.symbol = symbol
        # the graph the step PROGRAM is built from: the bind symbol run
        # through the compile pipeline (bf16 mixed-precision rewrite
        # etc.); self.symbol stays the caller's unrewritten graph —
        # checkpoints, list_arguments and Module.check all speak it.
        # Every accepted rewrite was re-proven by the verifier suite
        # (transform_graph rejects and falls back otherwise).
        self._graph_symbol = symbol
        self.pipeline_report = None
        self._logger = logger
        # the step resolves the pipeline ONCE, here: the traced program
        # keeps this graph for its life. step() warns (once) if the
        # global config drifts afterwards — re-arm via
        # init_optimizer(force_init=True) to apply a new pipeline
        self._pipeline_config = _pipeline.configured()
        self._drift_warned = False
        if _pipeline.configured():
            self._graph_symbol, self.pipeline_report = \
                _pipeline.transform_graph(
                    symbol, kind="fused_step", shapes=graph_shapes,
                    types=graph_types, module=module)
            if logger is not None and self.pipeline_report.rejected:
                logger.warning(
                    "fused step: compile pipeline rejected transform(s) "
                    "%s — training on the unrewritten graph",
                    ",".join(self.pipeline_report.rejected))
            elif logger is not None and self.pipeline_report.applied:
                logger.info(
                    "fused step: compile pipeline applied %s",
                    ",".join(self.pipeline_report.applied))
        self.devices = list(devices)
        self.param_names = list(param_names)
        self.fixed = set(fixed_param_names or ())
        self.trainable = [n for n in self.param_names if n not in self.fixed]
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.aux_names = symbol.list_auxiliary_states()
        self.optimizer = optimizer
        init, apply, lr_scale = _RULES[type(optimizer).__name__](optimizer)
        self._state_init = init
        self._apply = apply
        self._lr_scale = lr_scale
        # lr_mult/wd_mult/update-count lookups go through the optimizer's
        # existing idx2name index scheme (i*num_device+k over all params,
        # module.py init_optimizer). Reuse those indices rather than
        # renumbering, so the fused and unfused paths share one scheme;
        # only names the optimizer has never seen get fresh indices.
        idx2name = dict(getattr(optimizer, "idx2name", {}) or {})
        name2idx = {}
        for idx in sorted(idx2name):
            name2idx.setdefault(idx2name[idx], idx)
        nxt = max(idx2name, default=-1) + 1
        for n in self.trainable:
            if n not in name2idx:
                idx2name[nxt] = n
                name2idx[n] = nxt
                nxt += 1
        optimizer.idx2name = idx2name
        self._idx2name = idx2name
        self._name_idx = [name2idx[n] for n in self.trainable]
        # Selective rematerialization (MXTPU_REMAT):
        #   none/0 — keep every residual XLA wants. DEFAULT: remat is
        #            the memory-capacity lever; its cost in step time on
        #            the chip is not measured (PERF.md)
        #   block  — save ONLY block-boundary activations (dataflow cut
        #            vertices, executor._block_boundaries); backward
        #            recomputes each block's interior. Largest memory
        #            saving short of 'all'.
        #   conv   — save boundaries + every Convolution output; backward
        #            recomputes only the cheap elementwise interior (BN
        #            normalize, relu) from the saved conv outputs.
        #   all/1  — whole-forward jax.checkpoint (the memory-mirroring
        #            analogue, MXNET_BACKWARD_DO_MIRROR)
        #   auto   — defer to the compile pipeline's remat_reuse pass:
        #            drop exactly the __remat__-annotated residuals the
        #            liveness/recompute-cost analysis licensed. The
        #            UNSET default behaves like auto (the pass must have
        #            effect when the operator only listed it in
        #            MXTPU_PIPELINE); an explicitly SET none/0 pins
        #            "no rematerialization" and suppresses the
        #            annotations, like block/conv/all pin their policy.
        import os
        from ..tune import registry as _knobs
        env_set = bool(os.environ.get("MXTPU_REMAT", "").strip())
        self._remat = str(_knobs.resolve("fit.remat") or "none").lower()
        self._remat_pinned_off = False
        if self._remat in ("0", "none", "", "false"):
            self._remat = "none"
            # the operator explicitly pinned "no remat" via the env —
            # that wins over the remat_reuse pass's annotations too
            self._remat_pinned_off = env_set
        elif self._remat in ("1", "all", "true"):
            self._remat = "all"
        elif self._remat == "auto":
            pass   # defer to the remat_reuse pass's annotations (none
            # applied = keep-all, same as the default)
        elif self._remat not in ("block", "conv"):
            raise ValueError(
                "fit.remat / MXTPU_REMAT = %r not recognized (use "
                "none/auto/block/conv/all)" % self._remat)
        tags = None
        if self._remat in ("block", "conv"):
            from ..executor import _block_boundaries
            # remat tags key on node ids, so they must come from the
            # SAME graph the step traces — the pipeline-transformed one
            tags = {i: "mxtpu_boundary"
                    for i in _block_boundaries(self._graph_symbol)}
            if self._remat == "conv":
                for n in self._graph_symbol._topo():
                    if (not n.is_variable
                            and n.op.name in ("Convolution", "FullyConnected")
                            and id(n) not in tags):
                        tags[id(n)] = "mxtpu_conv"
        elif self._remat in ("none", "auto") \
                and not self._remat_pinned_off:
            # the remat_reuse transform pass annotated the graph: drop
            # exactly the tagged residuals (policy saves everything
            # else), the analysis-driven inverse of block/conv's
            # save-only allowlists. An EXPLICIT mode wins over the
            # annotations — block/conv/all pin their policy, an
            # env-set none/0 pins "no remat at all".
            ann = {id(n): "mxtpu_remat"
                   for n in self._graph_symbol._topo()
                   if not n.is_variable
                   and n._extra_attrs.get("__remat__")}
            if ann:
                tags = ann
                self._remat = "annotated"
        self._remat_tags = tags   # kept: arm_health re-traces with taps
        self._run = _trace_graph(self._graph_symbol, is_train=True,
                                 remat_tags=tags)
        # optimizer-update fusion (the fuse_opt transform): trainable
        # parameters the pass annotated with a shared __update_class__
        # collapse into ONE batched update region per class in _build
        self._update_groups = self._derive_update_groups()
        self._mesh = None
        self._plan = None
        if plan is not None and len(plan.mesh_ctx.devices) > 1:
            self._plan = plan
            self._mesh = plan.mesh
            self.devices = plan.mesh_ctx.devices
        elif len(self.devices) > 1:
            # mxtpu: allow-sync(np.array over device HANDLES for the mesh
            # grid — no tensor data moves)
            self._mesh = Mesh(_np.array(self.devices), ("data",))
        self._step_fn = None
        # training-health stats (obs/health.py): armed by arm_health();
        # when armed the step program additionally returns per-class
        # stat rows, stashed on last_health for the cadence accumulator
        self._health_classes = None
        self._health_taps = None
        self.last_health = None
        self.state = state if state is not None else FusedState()
        self.outputs = None     # last step's outputs (device arrays)
        self.last_labels = None  # last step's labels, already device-put —
        # update_metric's device path reuses them instead of transferring
        # the same host arrays a second time

    # shared-state views ------------------------------------------------
    @property
    def params(self):
        return self.state.params

    @params.setter
    def params(self, v):
        self.state.params = v

    @property
    def aux(self):
        return self.state.aux

    @aux.setter
    def aux(self, v):
        self.state.aux = v

    @property
    def opt_state(self):
        return self.state.opt_state

    @opt_state.setter
    def opt_state(self, v):
        self.state.opt_state = v

    # ------------------------------------------------ state staging
    def _put(self, v, spec=P()):
        if self._mesh is not None:
            from ..parallel.mesh import mesh_put
            return mesh_put(self._mesh, v, spec)  # multi-host safe
        return jax.device_put(v, self.devices[0])

    def _param_spec(self, name):
        """Plan spec for a parameter/aux value (replicated without one)."""
        return self._plan.param_spec(name) if self._plan is not None else P()

    def _opt_spec(self, name):
        """Plan spec for a parameter's optimizer-state leaves — the
        weight-update sharding assignment (replicated without a plan)."""
        return self._plan.opt_spec(name) if self._plan is not None else P()

    def _stage(self, v, spec=P()):
        """Stage one value onto the device(s) WITHOUT aliasing the
        caller's buffer. ``device_put`` of an array already committed to
        the target device returns the SAME array — the step's donation
        would then delete the caller's buffer out from under it (found
        by the mxtpu.analysis donation audit: post-fit ``_arg_params``
        held deleted buffers). Snapshot device-resident inputs first."""
        data = getattr(v, "_data", v)
        if isinstance(data, jax.Array):
            data = jnp.copy(data)
        return self._put(data, spec)

    def load(self, arg_params, aux_params):
        """Stage host params onto the device(s), (re)creating opt state."""
        names = set(self.param_names)
        self.params = {n: self._stage(v, self._param_spec(n))
                       for n, v in arg_params.items() if n in names}
        self.aux = {n: self._stage(v, self._param_spec(n))
                    for n, v in (aux_params or {}).items()}
        self.opt_state = {n: jax.tree.map(
            lambda t, _s=self._opt_spec(n): self._put(t, _s),
            self._state_init(self.params[n])) for n in self.trainable}
        self.state.update_mem_slot(self.devices)

    def adopt_state(self):
        """Joining an already-populated shared FusedState (a new bucket):
        keep the live weights/opt-state, only init entries this symbol
        introduces (normally none -- buckets share all parameters)."""
        st = self.state
        assert st.params is not None, "adopt_state needs a populated state"
        for n in self.trainable:
            if n not in st.opt_state:
                st.opt_state[n] = jax.tree.map(
                    lambda t, _s=self._opt_spec(n): self._put(t, _s),
                    self._state_init(st.params[n]))
        st.update_mem_slot(self.devices)

    def _derive_update_groups(self):
        """(class key, member names) pairs from the fuse_opt pass's
        ``__update_class__`` annotations on the (transformed) graph,
        intersected with THIS step's trainables — an annotated variable
        that is fixed here, or a class left with one member, batches
        nothing."""
        groups = {}
        for n in self._graph_symbol._topo():
            if n.is_variable:
                key = n._extra_attrs.get("__update_class__")
                if key:
                    groups.setdefault(key, []).append(n.name)
        tidx = {n: i for i, n in enumerate(self.trainable)}
        out = []
        for key in sorted(groups):
            names = sorted((nm for nm in groups[key] if nm in tidx),
                           key=tidx.get)
            if len(names) >= 2:
                out.append((key, names))
        return out

    def _validated_update_groups(self):
        """Re-prove each annotated class against the LIVE state before
        the program traces it; an unsound group falls back to the
        per-parameter update chains with a logged warning (the same
        degrade-not-break contract as the pipeline's verifier gate)."""
        out = []
        for key, names in self._update_groups:
            why = None
            if any(n not in (self.params or {}) for n in names):
                why = "member missing from the staged params"
            elif len({(self.params[n].shape, str(self.params[n].dtype))
                      for n in names}) != 1:
                why = "members diverge in live shape/dtype"
            elif len({jax.tree.structure(self.opt_state[n])
                      for n in names}) != 1:
                why = "members diverge in optimizer-state structure"
            elif self._plan is not None and any(
                    tuple(self._opt_spec(n)) or tuple(self._param_spec(n))
                    for n in names):
                # sharded update state: the reduce-scatter/all-gather
                # choreography is per-parameter — batching would change
                # the sharding story, so the plan path keeps the chains
                why = "weight-update sharding active for a member"
            if why is not None:
                (self._logger or logging).warning(
                    "fused step: update-fusion class %s NOT batched "
                    "(%s); per-parameter update chains retained",
                    key, why)
                continue
            out.append(tuple(names))
        return out

    # ------------------------------------------------ training health
    def arm_health(self, taps=None):
        """Arm device-resident training-health stats (obs/health.py):
        the step program additionally computes per-parameter-class rows
        [grad_sq, weight_sq, update_sq, nonfinite] + grad max-abs, all
        reduced ON DEVICE inside the fused step — nothing extra crosses
        the host boundary until the metric-sync cadence pulls them.

        Classes reuse the fuse_opt batched-update grouping (stat row
        count stays bounded); ungrouped trainables get a row each.
        ``taps`` — a Monitor regex pattern: matching intermediate
        outputs also get device abs-mean taps (the Monitor adapter).
        Returns the ``(label, member names)`` class list. Idempotent
        for an unchanged spec; a change invalidates the compiled step
        so the next ``step()`` rebuilds through the build seam."""
        from ..obs.health import class_label
        classes = []
        seen = set()
        for names in self._validated_update_groups():
            classes.append((class_label(names), tuple(names)))
            seen.update(names)
        for n in self.trainable:
            if n not in seen:
                classes.append((n, (n,)))
        classes = tuple(classes)
        if classes == self._health_classes \
                and taps == self._health_taps:
            return classes
        if taps != self._health_taps:
            self._health_taps = taps
            self._run = _trace_graph(self._graph_symbol, is_train=True,
                                     remat_tags=self._remat_tags,
                                     tap_filter=taps)
        self._health_classes = classes
        self.last_health = None
        self._step_fn = None
        return classes

    # ------------------------------------------------ the program
    def _build(self):
        run = self._run
        trainable = tuple(self.trainable)
        apply_update = self._apply
        update_groups = self._validated_update_groups()
        grouped_names = {n for g in update_groups for n in g}
        tindex = {n: i for i, n in enumerate(trainable)}
        if update_groups and self._logger is not None:
            self._logger.info(
                "fused step: %d batched optimizer-update region(s) "
                "cover %d of %d parameter(s)", len(update_groups),
                len(grouped_names), len(trainable))

        remat = self._remat
        health_classes = self._health_classes
        tap_armed = self._health_taps is not None
        # weight-update sharding: constrain each gradient entering the
        # optimizer to the opt-state sharding BEFORE the update — GSPMD
        # then reduce-scatters the vjp gradient instead of all-reducing
        # it, and the whole update chain below runs on 1/n rows per
        # replica (the out_shardings on params force the all-gather of
        # the fresh weights afterwards)
        grad_shardings = None
        if self._plan is not None:
            grad_shardings = {}
            for n in trainable:
                spec = self._opt_spec(n)
                if tuple(spec):
                    grad_shardings[n] = NamedSharding(self._mesh, spec)

        def step(params, aux, opt_state, batch, lrs, wds, rng):
            fixed = {n: v for n, v in params.items() if n not in trainable}

            def f(train_p):
                env = dict(fixed)
                env.update(train_p)
                env.update(batch)
                if tap_armed:
                    # taps are vjp aux: forward-only device scalars the
                    # Monitor adapter reads — never differentiated
                    outs, auxu, taps = run(env, aux, rng)
                    return (outs, auxu), taps
                outs, auxu = run(env, aux, rng)
                return outs, auxu

            if remat == "all":
                # trade recompute for activation traffic / memory: mirrors
                # the reference's memory mirroring (__mirror_stage__,
                # src/executor/graph_executor.cc)
                f = jax.checkpoint(f)
            elif remat == "block":
                f = jax.checkpoint(
                    f, policy=jax.checkpoint_policies.save_only_these_names(
                        "mxtpu_boundary"))
            elif remat == "conv":
                f = jax.checkpoint(
                    f, policy=jax.checkpoint_policies.save_only_these_names(
                        "mxtpu_boundary", "mxtpu_conv"))
            elif remat == "annotated":
                # remat_reuse annotations: recompute ONLY the tagged
                # residuals; everything else stays saveable (the
                # inverse of the save-only allowlists above). NB:
                # save_anything_except_these_names, NOT
                # save_any_names_but_these — the latter saves ONLY
                # named values and would remat the entire forward
                f = jax.checkpoint(
                    f,
                    policy=jax.checkpoint_policies
                    .save_anything_except_these_names("mxtpu_remat"))
            train_p = {n: params[n] for n in trainable}
            taps = None
            if tap_armed:
                (outs, auxu), vjp, taps = jax.vjp(f, train_p,
                                                  has_aux=True)
            else:
                (outs, auxu), vjp = jax.vjp(f, train_p)
            with jax.named_scope(_opscopes.HEAD_GRAD):
                cts = ([head_cotangent(o) for o in outs],
                       {k: jnp.zeros_like(v) for k, v in auxu.items()})
            (grads,) = vjp(cts)
            new_params = dict(fixed)
            new_opt = {}
            # batched update regions (fuse_opt): every annotated
            # dtype/shape class runs its grad→update→assign chain ONCE
            # over stacked members — per-parameter lr/wd enter as a
            # leading-axis column, so the arithmetic is identical to
            # the per-parameter chains below, element for element
            # every update runs under ``mxtpu.update/<parameter>`` (a
            # batched region under its first member's name), so a trace's
            # operations are told from the graph's (diagnostics.opscopes)
            for names in update_groups:
                with jax.named_scope("%s/%s" % (_opscopes.UPDATE, names[0])):
                    p_stk = jnp.stack([params[n] for n in names])
                    g_stk = jnp.stack([grads[n] for n in names])
                    s_stk = jax.tree.map(lambda *ls: jnp.stack(ls),
                                         *[opt_state[n] for n in names])
                    col = (len(names),) + (1,) * (p_stk.ndim - 1)
                    lr_col = jnp.reshape(
                        jnp.stack([lrs[tindex[n]] for n in names]), col)
                    wd_col = jnp.reshape(
                        jnp.stack([wds[tindex[n]] for n in names]), col)
                    p2, s2 = apply_update(p_stk, g_stk, s_stk, lr_col,
                                          wd_col)
                    for j, n in enumerate(names):
                        new_params[n] = p2[j].astype(params[n].dtype)
                        new_opt[n] = jax.tree.map(lambda t, _j=j: t[_j], s2)
            for i, n in enumerate(trainable):
                if n in grouped_names:
                    continue
                with jax.named_scope("%s/%s" % (_opscopes.UPDATE, n)):
                    g = grads[n]
                    if grad_shardings is not None and n in grad_shardings:
                        g = jax.lax.with_sharding_constraint(
                            g, grad_shardings[n])
                    p2, s2 = apply_update(params[n], g, opt_state[n],
                                          lrs[i], wds[i])
                    new_params[n] = p2.astype(params[n].dtype)
                    new_opt[n] = s2
            new_aux = dict(aux)
            new_aux.update(auxu)
            if not health_classes:
                return new_params, new_aux, new_opt, outs
            # training-health rows (obs/health.py): per class, f32
            # sums [grad_sq, weight_sq, update_sq, nonfinite] + grad
            # max-abs — tiny reductions XLA fuses into the update
            # kernels it already runs over these same buffers. The
            # nonfinite count covers grads AND the fresh weights, so
            # an LR bomb is visible at the cadence of the step that
            # fired it, before the next step consumes the wreckage.
            with jax.named_scope(_opscopes.HEALTH):
                f32 = jnp.float32
                sum_rows, max_rows = [], []
                for _label, names in health_classes:
                    g2 = w2 = u2 = nf = None
                    gm = None
                    for n in names:
                        g = grads[n].astype(f32)
                        p_new = new_params[n].astype(f32)
                        d = p_new - params[n].astype(f32)
                        bad = (jnp.sum(~jnp.isfinite(g))
                               + jnp.sum(~jnp.isfinite(p_new))).astype(f32)
                        parts = (jnp.sum(g * g), jnp.sum(p_new * p_new),
                                 jnp.sum(d * d), bad)
                        if g2 is None:
                            g2, w2, u2, nf = parts
                            gm = jnp.max(jnp.abs(g))
                        else:
                            g2, w2, u2, nf = (g2 + parts[0], w2 + parts[1],
                                              u2 + parts[2], nf + parts[3])
                            gm = jnp.maximum(gm, jnp.max(jnp.abs(g)))
                    sum_rows.append(jnp.stack([g2, w2, u2, nf]))
                    max_rows.append(gm)
                hstats = {"sums": jnp.stack(sum_rows),
                          "max": jnp.stack(max_rows)}
            if taps is not None:
                hstats["taps"] = taps
            return new_params, new_aux, new_opt, outs, hstats

        if self._mesh is not None and self._plan is not None:
            plan = self._plan
            repl = NamedSharding(self._mesh, P())
            p_sh = {n: NamedSharding(self._mesh, plan.param_spec(n))
                    for n in self.params}
            a_sh = {n: NamedSharding(self._mesh, plan.param_spec(n))
                    for n in self.aux}
            o_sh = {n: jax.tree.map(
                lambda _, _s=plan.opt_spec(n):
                NamedSharding(self._mesh, _s), self.opt_state[n])
                for n in self.opt_state}
            b_sh = {n: NamedSharding(self._mesh, plan.batch_spec(n))
                    for n in self.data_names + self.label_names}
            # out_shardings pin params/aux back to their (replicated)
            # specs — with the update computed sharded, THIS is what
            # makes GSPMD insert the weight all-gather — and keep the
            # optimizer state sharded across steps; outputs propagate
            out_sh = (p_sh, a_sh, o_sh, None)
            if health_classes:
                out_sh += (None,)   # health rows: propagated (replicated)
            self._step_fn = _pipeline.named_jit(
                "fused_step", step, in_shardings=(p_sh, a_sh, o_sh, b_sh, repl, repl,
                                    repl),
                out_shardings=out_sh,
                donate_argnums=(0, 1, 2))
        elif self._mesh is not None:
            repl = NamedSharding(self._mesh, P())
            bshard = NamedSharding(self._mesh, P("data"))
            p_sh = {n: repl for n in self.params}
            a_sh = {n: repl for n in self.aux}
            o_sh = jax.tree.map(lambda _: repl, self.opt_state)
            b_sh = {n: bshard for n in self.data_names + self.label_names}
            self._step_fn = _pipeline.named_jit(
                "fused_step", step, in_shardings=(p_sh, a_sh, o_sh, b_sh, repl, repl, repl),
                donate_argnums=(0, 1, 2))
        else:
            self._step_fn = _pipeline.named_jit(
                "fused_step", step, donate_argnums=(0, 1, 2))
        return self._step_fn

    # ------------------------------------------------ per-step driver
    def step(self, data_arrays, label_arrays):
        """Run one fused step; returns the outputs (device arrays)."""
        if _pipeline.configured() != self._pipeline_config \
                and not self._drift_warned:
            # the Executor rebuilds its (cheap, stateless) programs on a
            # config flip; the fused step cannot — its state buffers are
            # donated into the compiled program — so a silent flip would
            # leave train on one graph and eval on another. Say so once.
            self._drift_warned = True
            (self._logger or logging).warning(
                "fused step: compile pipeline config changed %s -> %s "
                "after the step was built; the step keeps the graph it "
                "compiled. Re-run init_optimizer(force_init=True) or "
                "rebuild the module to apply the new pipeline",
                list(self._pipeline_config),
                list(_pipeline.configured()))
        opt = self.optimizer
        lrs = _np.empty(len(self.trainable), _np.float32)
        wds = _np.empty(len(self.trainable), _np.float32)
        for i, idx in enumerate(self._name_idx):
            opt._update_count(idx)
            lr = opt._get_lr(idx)
            if self._lr_scale is not None:
                lr *= self._lr_scale(opt._index_update_count[idx])
            lrs[i] = lr
            wds[i] = opt._get_wd(idx)
        batch = {}
        spec = P("data") if self._mesh is not None else P()
        for names, arrs in ((self.data_names, data_arrays),
                            (self.label_names, label_arrays)):
            for n, v in zip(names, arrs):
                nspec = self._plan.batch_spec(n) if self._plan is not None \
                    else spec
                batch[n] = self._put(getattr(v, "_data", v), nspec)
        self.last_labels = [batch[n] for n in self.label_names if n in batch]
        if self._step_fn is None:
            # route through the executor's build seam: program_build_count,
            # the build listeners, the telemetry build counters and the
            # first-call compile histogram all stay consistent with the
            # Executor program-table path
            from ..executor import record_program_build
            self._build()
            rep = self.pipeline_report
            self._step_fn = record_program_build(
                "fused_step", self, self._step_fn,
                precision=rep.precision if rep is not None else None,
                transforms=rep.transforms if rep is not None else None,
                cert=rep.cert if rep is not None else None,
                scopes=_opscopes.symbol_scopes(self._graph_symbol))
        try:
            res = self._step_fn(
                self.params, self.aux, self.opt_state, batch,
                self._put(lrs), self._put(wds), _rnd.next_key())
            self.params, self.aux, self.opt_state, outs = res[:4]
            if len(res) == 5:   # health armed: per-class stat rows
                self.last_health = res[4]
        except NumericsError as exc:
            # the step already ran and DONATED the old state trees; the
            # sanitizer raised before the unpack above could adopt the
            # new ones. Adopt from the exception so the state holds the
            # step's (NaN'd but readable) outputs instead of deleted
            # buffers — a caller that catches and checkpoints must not
            # hit "Array has been deleted".
            res = getattr(exc, "outputs", None)
            if isinstance(res, tuple) and len(res) in (4, 5):
                self.params, self.aux, self.opt_state, self.outputs = \
                    res[:4]
                if len(res) == 5:
                    self.last_health = res[4]
            raise
        self.outputs = outs
        return outs

    # ------------------------------------------------ elastic state seam
    def export_device_state(self):
        """Fresh device copies of (params, aux, opt_state) — the elastic
        snapshot capture point (docs/elastic.md). ONE jitted tree-copy
        program makes new buffers, so later donated steps cannot
        invalidate the snapshot, and each leaf's device→host transfer is
        kicked off asynchronously so the snapshot writer thread finds the
        bytes (mostly) landed without the training thread ever blocking.
        Under a plan the optimizer-state copies keep their weight-update
        sharding — the caller serializes per-shard (no gather)."""
        snap_p, snap_a, snap_o = _snapshot((self.params, self.aux,
                                            self.opt_state))
        for leaf in jax.tree.leaves((snap_p, snap_a, snap_o)):
            try:
                leaf.copy_to_host_async()
            except Exception:
                # mxtpu: allow-swallow(async D2H start is an
                # optimization: a backend without it makes the writer
                # block at materialization, nothing is lost)
                pass
        return snap_p, snap_a, snap_o

    def stage_opt_leaves(self, name, leaves):
        """Adopt restored optimizer-state leaves for ``name`` (checkpoint
        resume). jax arrays the caller already laid out (e.g. reassembled
        per-shard on the mesh) are adopted as-is; host values are staged
        onto the plan's weight-update sharding spec — a replicated
        restore would void the per-chip memory split. Leaf dtypes follow
        the live state (f32 masters stay f32)."""
        cur_leaves, treedef = jax.tree.flatten(self.opt_state[name])
        if len(cur_leaves) != len(leaves):
            raise ValueError(
                "opt-state restore for %r: %d leaves saved, %d live"
                % (name, len(leaves), len(cur_leaves)))
        spec = self._opt_spec(name)
        staged = []
        for cur, new in zip(cur_leaves, leaves):
            if isinstance(new, jax.Array) and new.shape == cur.shape \
                    and new.dtype == cur.dtype \
                    and getattr(new, "committed", False):
                staged.append(new)
                continue
            staged.append(self._put(
                jnp.asarray(getattr(new, "_data", new), cur.dtype), spec))
        self.opt_state[name] = jax.tree.unflatten(treedef, staged)

    # ------------------------------------------------ sync back
    def export_params(self):
        """Return (arg_params, aux_params) as NDArray dicts.

        The arrays stay ON DEVICE: a single jitted tree-copy snapshots
        every parameter (so the next step's donation can't invalidate the
        returned buffers), and the NDArrays wrap the copies zero-transfer.
        Host bytes are only materialized when something actually reads
        them (asnumpy / nd.save's packed bulk fetch), so Module.fit's
        epoch-end get_params moves nothing to the host by itself."""
        from .. import ndarray as nd
        snap_p, snap_a = _snapshot((self.params, self.aux))
        args = {n: nd.NDArray(v) for n, v in snap_p.items()}
        aux = {n: nd.NDArray(v) for n, v in snap_a.items()}
        return args, aux

    def export_opt_state(self):
        """Optimizer state as {index: numpy pytree} under the SAME index
        scheme the Updater uses (optimizer.idx2name keys), so a state file
        written by the fused path loads on the unfused path and vice versa.
        Every index aliasing a name (one per device copy in the unfused
        scheme) receives the same state."""
        from ..ndarray.ndarray import _bulk_tree_to_numpy
        name_indices = {}
        for idx, n in self._idx2name.items():
            name_indices.setdefault(n, []).append(idx)
        host_state = _bulk_tree_to_numpy(
            {n: self.opt_state[n] for n in self.trainable})
        out = {}
        for n in self.trainable:
            st = host_state[n]
            for idx in name_indices.get(n, []):
                out[idx] = st
        return out

    def import_opt_state(self, states):
        """Accept {index: state} keyed by the Updater's index scheme; for a
        name with several device-copy indices the lowest present wins.
        Restored leaves are staged on the plan's weight-update sharding
        spec (like load/adopt_state) — a replicated restore would make
        every step reshard and void the per-chip memory split."""
        for i, n in enumerate(self.trainable):
            cands = [states[j] for j in sorted(states)
                     if self._idx2name.get(j) == n and states[j] is not None]
            if not cands:
                continue
            self.opt_state[n] = jax.tree.map(
                lambda t, s, _spec=self._opt_spec(n): self._put(
                    jnp.asarray(getattr(s, "_data", s), t.dtype), _spec),
                self.opt_state[n], cands[0])
