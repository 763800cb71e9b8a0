"""Profiler: op-level attribution + config/dump API shaped like the
reference's MXSetProfilerConfig/MXSetProfilerState/MXDumpProfile
(src/engine/profiler.cc:152, python/mxnet/profiler.py)."""
import json
import os

import numpy as np

import mxtpu as mx
from mxtpu import profiler, sym


def _block(data, prefix, nf):
    c = sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=nf,
                        no_bias=True, name="%s_conv" % prefix)
    b = sym.BatchNorm(c, fix_gamma=False, name="%s_bn" % prefix)
    return sym.Activation(b, act_type="relu", name="%s_relu" % prefix)


def test_per_layer_spans_and_dump(tmp_path):
    """One train step of a conv stack attributes time per NAMED layer and
    dumps a valid chrome://tracing file."""
    net = sym.Variable("data")
    for i in range(3):
        net = _block(net, "stage%d" % i, 8)
    net = sym.FullyConnected(sym.Flatten(net), num_hidden=4, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    fname = str(tmp_path / "trace.json")
    profiler.clear()
    profiler.set_config(mode="symbolic", filename=fname)
    profiler.set_state("run")
    try:
        exe = net.simple_bind(ctx=mx.cpu(), data=(2, 3, 16, 16),
                              softmax_label=(2,))
        exe.arg_dict["data"][:] = mx.nd.array(
            np.random.rand(2, 3, 16, 16).astype("float32"))
        exe.forward(is_train=True)
        exe.backward()
    finally:
        profiler.set_state("stop")
    path = profiler.dump_profile()
    assert path == fname and os.path.exists(fname)
    trace = json.load(open(fname))
    names = {ev["name"] for ev in trace["traceEvents"]}
    # every named layer appears, plus the one-program backward span
    for expect in ("stage0_conv", "stage1_bn", "stage2_relu", "fc",
                   "softmax", "backward"):
        assert expect in names, (expect, sorted(names)[:20])
    # spans are well-formed B/E pairs with non-negative duration
    begins = {}
    for ev in trace["traceEvents"]:
        key = (ev["name"], ev["tid"])
        if ev["ph"] == "B":
            begins[key] = ev["ts"]
        elif ev["ph"] == "E":
            assert ev["ts"] >= begins[key]

    # aggregate table parity (dumps): per-op rows with counts
    table = profiler.dumps()
    assert "stage0_conv" in table and "Count" in table


def test_aggregate_stats_mode():
    """aggregate_stats=True folds spans into standing per-layer histograms
    at record time: the dumps() table gains percentile columns and
    SURVIVES raw-event truncation (MXAggregateProfileStats contract) —
    with the flag off, the table is recomputed from raw events and dies
    with them."""
    profiler.clear()
    profiler.set_config(mode="symbolic", filename="/tmp/unused_agg.json",
                        aggregate_stats=True)
    profiler.set_state("run")
    try:
        for _ in range(5):
            with profiler.scope("agg_layer"):
                pass
    finally:
        profiler.set_state("stop")
    table = profiler.dumps()
    assert "agg_layer" in table and "Count" in table
    assert "P50(ms)" in table and "P99(ms)" in table
    row = next(l for l in table.splitlines() if l.startswith("agg_layer"))
    assert int(row.split()[1]) == 5
    # the aggregation outlives the raw events (dump-and-truncate cycle)
    with profiler._lock:
        profiler._events.clear()
    assert "agg_layer" in profiler.dumps()
    # snapshot API exposes the standing histograms
    snap = profiler.aggregate_stats_snapshot()
    assert snap["agg_layer"].count == 5
    # reset clears the aggregation too
    profiler.dumps(reset=True)
    assert "agg_layer" not in profiler.dumps()

    # flag off: plain table, no percentile columns, computed from events
    profiler.clear()
    profiler.set_config(mode="symbolic", filename="/tmp/unused_agg.json",
                        aggregate_stats=False)
    profiler.set_state("run")
    try:
        with profiler.scope("raw_layer"):
            pass
    finally:
        profiler.set_state("stop")
    table = profiler.dumps()
    assert "raw_layer" in table and "P99(ms)" not in table
    profiler.clear()


def test_profiler_off_keeps_fused_path():
    """With the profiler stopped, forward uses the fused program and
    records nothing."""
    profiler.clear()
    assert not profiler.ops_enabled()
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4, name="fc")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 8))
    exe.forward(is_train=False)
    assert profiler.dumps().count("\n") == 1  # heading and header, no rows


def test_named_scope_in_hlo():
    """Layer names land in the compiled HLO metadata (xprof attribution
    for the fused path)."""
    import jax

    from mxtpu.executor import _trace_graph

    net = _block(sym.Variable("data"), "layerX", 4)
    run = _trace_graph(net, is_train=False)
    args = {"data": np.zeros((1, 3, 8, 8), "float32"),
            "layerX_conv_weight": np.zeros((4, 3, 3, 3), "float32"),
            "layerX_bn_gamma": np.ones(4, "float32"),
            "layerX_bn_beta": np.zeros(4, "float32")}
    aux = {"layerX_bn_moving_mean": np.zeros(4, "float32"),
           "layerX_bn_moving_var": np.ones(4, "float32")}
    rng = np.zeros(2, "uint32")
    lowered = jax.jit(lambda a, x, r: run(a, x, r)).lower(args, aux, rng)
    try:  # loc() metadata carries scopes (kwarg added in newer jax)
        txt = lowered.as_text(debug_info=True)
    except TypeError:  # jax 0.4.x: ask the MLIR module for debug info
        txt = lowered.compiler_ir(dialect="stablehlo").operation.get_asm(
            enable_debug_info=True)
    assert "layerX_conv" in txt, "named_scope missing from lowered IR"


def test_profiler_ops_mode_through_module_fit():
    """Operator-mode profiling reaches Module.fit training: per-layer spans
    appear even though the fused one-program step is normally active."""
    import mxtpu as mx

    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype("float32")
    y = rng.randint(0, 4, 64).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=4, name="fcp"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    profiler.clear()
    profiler.set_config(mode="operator", filename="/tmp/unused.json")
    profiler.set_state("run")
    try:
        mod.fit(it, num_epoch=1,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    finally:
        profiler.set_state("stop")
    table = profiler.dumps(reset=True)
    assert "fcp" in table and "backward" in table
    # training continued correctly on the classic path afterwards
    assert mod._fused is None  # retired by the first classic update
