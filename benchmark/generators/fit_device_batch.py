"""Traffic of kind `fit-device-batch`: `Module.fit` fed by a DataIter that
serves one device-resident batch (upstream's `train_imagenet.py --benchmark
1`, `common/data.py:SyntheticDataIter`; copied from bench.py
`_DeviceBatchIter`, `_bench_dp_scaling`, `_finish`). The mix's file gives the
batch per chip and the shapes; the configuration's `program.py` gives the
symbol and how a batch is drawn from the seed.

Set-up builds one Module from the seed's weights, drives its first three
steps through the window's own `fit` call and feed, and hands the same Module
to the window. After the window the program's state is freed and the plain
reference follows those three steps (benchmark/references/common.py `follow`).
"""
import gc
import time

from benchmark import compare, weights

TEL = ("fit_dispatch_ms", "fit_sync_wait_ms", "fit_metric_sync_ms")


class DeviceBatchIter:
    """Serves one pre-staged batch until `limit` batches are out or the
    deadline has passed; counts what it served and the time inside next()."""

    def __init__(self, batch, provide_data, provide_label, annotate):
        self._batch = batch
        self.provide_data = provide_data
        self.provide_label = provide_label
        self.batch_size = provide_data[0].shape[0]
        self._annotate = annotate
        self.served = 0
        self.wait_s = 0.0
        self.at = []        # when each batch went out: fit's pace, step by step
        self._limit, self._deadline, self._out = 0, None, 0

    def arm(self, limit=None, deadline=None):
        self._limit, self._deadline, self._out = limit, deadline, 0
        return self

    def reset(self):
        pass

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        with self._annotate("bench.input_next"):
            if self._limit is not None and self._out >= self._limit:
                raise StopIteration
            if self._deadline is not None and t0 >= self._deadline:
                raise StopIteration
            self._out += 1
            self.served += 1
            self.at.append(t0)
            self.wait_s += time.perf_counter() - t0
            return self._batch

    next = __next__


def _step_gaps(at):
    """Shape of the gaps between the batches going out, in ms: fit hands a
    batch out as a step's slot frees, so a run that reads far off shows here
    whether every step was slow or a few stalled."""
    gaps = sorted((b - a) * 1e3 for a, b in zip(at, at[1:]))
    if not gaps:
        return None
    mid = gaps[len(gaps) // 2]
    return {"p50": mid, "max": gaps[-1],
            "over_1.5x_p50": sum(1 for g in gaps if g > 1.5 * mid)}


def _hist_snapshot(tel):
    return {n: tel.histogram(n).snapshot() for n in TEL}


def _hist_delta(tel, before):
    """{name: {count, sum, p50}} of what each histogram saw since `before`."""
    out = {}
    for n in TEL:
        h = tel.histogram(n)
        c1, s1, _, _, cum1 = h.snapshot()
        c0, s0, _, _, cum0 = before[n]
        count = c1 - c0
        p50 = None
        if count:
            rank, lo = 0.5 * count, 0.0
            for b, a1, a0 in zip(h.bounds, cum1, cum0):
                if a1 - a0 >= rank:
                    p50 = (lo + b) / 2 if b != float("inf") else lo
                    break
                lo = b
        out[n] = {"count": count, "sum": s1 - s0, "p50": p50}
    return out


def prepare(cell, seed, chips):
    """What the program and the reference share: the batch drawn from the
    seed, the weights' maker and the optimizer as the configuration states
    it. Touches nothing of mxtpu but the configuration's program.py."""
    import jax
    cfg, traffic = cell.config, cell.traffic
    program = cell.config_module("program")
    reference = cell.config_module("reference")
    batch = int(traffic["batch_per_chip"]) * chips
    data_desc, label_desc, draw = program.inputs(cfg, traffic, batch)
    names = [n for n, _, _ in data_desc + label_desc]
    if chips != 1:
        raise SystemExit("fit-device-batch runs one chip; a mesh cell brings "
                         "its own path (PERF.md, open questions)")
    drawn = jax.jit(draw)(weights.batch_key(seed))
    jax.block_until_ready(drawn)
    specs = reference.param_specs(cfg)
    store = cfg["param_dtypes"]["default"]

    def make_params():
        return weights.make(seed, specs, round_to=store)

    items = int(program.items_per_row(cfg, traffic))
    opt = dict(cfg["optimizer"])
    per_item = opt.pop("rescale") == "per_item"
    opt["rescale_grad"] = 1.0 / (batch * items if per_item else batch)
    return {"seed": seed, "batch": batch, "drawn": drawn, "names": names,
            "data_desc": data_desc, "label_desc": label_desc, "opt": opt,
            "make_params": make_params,
            "program": program, "reference": reference, "items_per_row": items,
            "loss_reading": cfg.get("loss_reading", "metric")}


def build(cell, seed, chips):
    """The Module with the seed's weights and its feed; no step has run."""
    import jax
    import mxtpu as mx
    cfg = cell.config
    built = prepare(cell, seed, chips)
    data_desc, label_desc = built["data_desc"], built["label_desc"]
    drawn, reference = built["drawn"], built["reference"]
    pdata = [mx.io.DataDesc(n, s, dtype=d) for n, s, d in data_desc]
    plabel = [mx.io.DataDesc(n, s, dtype=d) for n, s, d in label_desc]
    batch_obj = mx.io.DataBatch(
        data=[mx.nd.NDArray(drawn[n]) for n, _, _ in data_desc],
        label=[mx.nd.NDArray(drawn[n]) for n, _, _ in label_desc],
        pad=0, index=None, provide_data=pdata, provide_label=plabel)
    mod = mx.mod.Module(built["program"].symbol(cfg, cell.traffic),
                        context=mx.tpu(0),
                        data_names=[n for n, _, _ in data_desc],
                        label_names=[n for n, _, _ in label_desc])
    mod.bind(data_shapes=pdata, label_shapes=plabel)
    # hand the weights over in the dtypes the Module bound: an arg_params of
    # another dtype silently replaces the bound one (PERF.md, open questions)
    bound = {n: str(b[0].dtype) for n, b in zip(
        mod._exec_group._param_names_out, mod._exec_group.param_arrays)}
    bound.update({n: str(b[0].dtype) for n, b in zip(
        mod._exec_group.aux_names, mod._exec_group.aux_arrays)})
    stated = cfg["param_dtypes"]
    wrong = {n: d for n, d in bound.items()
             if d != stated.get(n, stated["default"])}
    if wrong:
        raise SystemExit("the program binds %s, the configuration states "
                         "otherwise" % wrong)
    aux_specs = reference.aux_specs(cfg) if hasattr(reference, "aux_specs") else []
    w0 = built["make_params"]()
    aux0 = weights.make(seed, aux_specs, round_to=stated["default"]) \
        if aux_specs else {}
    cast = jax.jit(lambda t: {k: v.astype(bound[k]) for k, v in t.items()})
    mod.init_params(arg_params={k: mx.nd.NDArray(v) for k, v in cast(w0).items()},
                    aux_params={k: mx.nd.NDArray(v) for k, v in cast(aux0).items()})
    del w0, aux0
    opt = built["opt"]
    fit_kw = dict(num_epoch=1, eval_metric=mx.metric.create(cfg["eval_metric"]),
                  optimizer=opt["name"],
                  optimizer_params={k: v for k, v in opt.items() if k != "name"},
                  force_init=False, begin_epoch=0)
    built.update(mod=mod, batch_obj=batch_obj, pdata=pdata, plabel=plabel,
                 fit_kw=fit_kw)
    return built


def _ce(metric):
    got = dict(metric.get_name_value())
    return got["cross-entropy"]


def _ce_of_outputs(mod, labels, eps=1e-12):
    """The last step's loss as `mx.metric.CrossEntropy` counts it, from the
    step's own outputs (`Module.get_outputs`), reduced in float64 here: for
    a configuration whose metric is too coarse to compare (`loss_reading`)."""
    import numpy as np
    prob = np.asarray(mod.get_outputs()[0].asnumpy(), np.float64)
    lab = np.asarray(labels).reshape(-1).astype(np.int64)
    prob = prob.reshape(lab.shape[0], -1)
    return float(np.mean(-np.log(prob[np.arange(lab.shape[0]), lab] + eps)))


def first_steps(built, it, steps=3):
    """Drives the Module's first steps through `fit` and the window's feed,
    one step a call; returns the program's readings."""
    import jax
    import jax.numpy as jnp
    mod, kw = built["mod"], built["fit_kw"]
    f32 = jnp.float32
    norms = jax.jit(lambda tree: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(f32)))) for k, v in tree.items()})
    out = {"loss": []}
    for step in range(1, steps + 1):
        mod.fit(it.arm(limit=1), **kw)
        jax.block_until_ready(mod._fused.params)
        if built["loss_reading"] == "outputs":
            out["loss"].append(_ce_of_outputs(
                mod, built["drawn"][built["label_desc"][0][0]]))
        else:
            out["loss"].append(float(_ce(kw["eval_metric"])))
        if step == 1:
            state = mod._fused.opt_state
            first = {k: (s[0] if isinstance(s, (tuple, list)) else s)
                     for k, s in state.items() if s is not None}
            out["grad_norm"] = {k: float(v) for k, v in norms(first).items()}
            # kept on the host until the reference has its own to hold it
            # against (`grad_cos_gap`): the device keeps nothing for it
            out["first_grad"] = jax.device_get(first)
            del state, first
    trainable = set(out["grad_norm"])
    p0 = built["make_params"]()
    delta = jax.jit(lambda a, c: {
        k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(f32) - c[k])))
        for k in c if k in a})
    now = {k: v for k, v in mod._fused.params.items() if k in trainable}
    out["delta_norm"] = {k: float(v) for k, v in delta(now, p0).items()}
    del p0, now
    return out


def reference_readings(built, cell, quant=None, keep_one_in=1, against=None,
                       keep_first=False):
    """The plain reference's first three steps on the same seed's weights
    and batch (or the control's, or a planted fault's). `against` is the
    other side's first gradient, on the host; `keep_first` returns this
    side's (references/common.py `follow`)."""
    from benchmark.references import common
    ref, cfg = built["reference"], cell.config
    drawn = built["drawn"]
    rows = ref.split_rows(*(drawn[n] for n in built["names"]))
    return common.follow(
        ref.block_loss(cfg, quant), built["make_params"], rows,
        dict(built["opt"]), cfg["param_dtypes"], steps=3,
        rows_per_block=cfg.get("reference_rows_per_block"),
        keep_one_in=keep_one_in, items_per_row=built["items_per_row"],
        against=against, keep_first=keep_first)


def run(cell, args, rt):
    import jax
    import mxtpu as mx
    from mxtpu import telemetry as tel

    chips = cell.chips
    built = build(cell, args.seed, chips)
    mod, kw = built["mod"], built["fit_kw"]
    it = DeviceBatchIter(built["batch_obj"], built["pdata"], built["plabel"],
                         rt.annotate)
    prog = first_steps(built, it)
    if mod._fused is None:
        raise SystemExit("the fused Module step did not arm")
    # warm the multi-step path of fit once (metric sync at epoch end etc.)
    mod.fit(it.arm(limit=2), **kw)
    jax.block_until_ready(mod._fused.params)

    seconds = rt.window_seconds(cell.traffic)
    before = _hist_snapshot(tel)
    served0, wait0 = it.served, it.wait_s
    del it.at[:]
    rt.trace_starts()
    rt.window_opens()
    t0 = time.perf_counter()
    with rt.annotate("bench.window"):
        with rt.annotate("bench.fit"):
            mod.fit(it.arm(deadline=t0 + seconds), **kw)
        with rt.annotate("bench.finish"):
            jax.block_until_ready(mod._fused.params)
    window_s = time.perf_counter() - t0
    rt.window_closes()
    rt.trace_stops()
    steps = it.served - served0
    step_gaps = _step_gaps(it.at)
    items_per_step = built["batch"] * built["items_per_row"]
    facts = {
        "window_s": window_s, "steps": steps, "chips": chips,
        "items": steps * items_per_step, "items_per_step": items_per_step,
        "input_wait_s": it.wait_s - wait0, "telemetry": _hist_delta(tel, before),
        "memory_peak_bytes": rt.memory_peak(),
        "batch_per_chip": int(cell.traffic["batch_per_chip"]),
    }
    end_to_end = {"train_throughput": facts["items"] / window_s / chips}

    # free the program before the reference runs
    built["mod"] = mod = None
    kw["eval_metric"] = None
    it = None
    built["batch_obj"] = None
    gc.collect()
    live = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.devices()[:chips]]
    t_ref = time.perf_counter()
    ref = reference_readings(built, cell, against=prog.pop("first_grad"))
    facts["reference_s"] = time.perf_counter() - t_ref
    facts["bytes_in_use_before_reference"] = max(live)
    values, where = compare.training(prog, ref, ref["grad_cos_gap"])
    facts["compared_at"] = where
    facts["readings"] = {"program": prog["loss"], "reference": ref["loss"],
                         "step_gap_ms": step_gaps}
    return {"attempted": steps, "failed": 0, "end_to_end": end_to_end,
            "facts": facts, "values": values}
