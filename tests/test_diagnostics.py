"""mxtpu.diagnostics: ledger exactness under concurrency + live_arrays
reconciliation, per-program cost capture across every build kind, the
flight recorder ring, watchdog detection (wedged fake engine) and
silence (healthy fit), /debug/state schema, SIGUSR2 dump roundtrip, and
the satellite surfaces (print_summary memory column, monitor series)."""
import gc
import json
import os
import signal
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import diagnostics as diag
from mxtpu import telemetry as tel
from mxtpu.diagnostics.ledger import DeviceMemoryLedger
from mxtpu.diagnostics.flight import FlightRecorder
from mxtpu.diagnostics.watchdog import Watchdog


# ------------------------------------------------------------------ ledger
def test_ledger_concurrent_alloc_free_exact():
    """N threads hammering alloc/free: totals must be EXACT — the
    postmortem's memory numbers are worthless if they drift."""
    led = DeviceMemoryLedger(register_gauges=False)
    n_threads, n_iter = 8, 1500
    barrier = threading.Barrier(n_threads)
    leaks = [None] * n_threads

    def worker(i):
        barrier.wait()
        tokens = []
        for k in range(n_iter):
            tokens.append(led.alloc(64, ctx="cpu(0)",
                                    origin="w%d" % (i % 2)))
            if k % 2:
                led.free(tokens.pop())
        leaks[i] = tokens

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    outstanding = sum(len(t) for t in leaks)
    assert led.live_bytes() == outstanding * 64
    assert led.live_bytes(origin="w0") + led.live_bytes(origin="w1") \
        == outstanding * 64
    assert led.peak_bytes("cpu(0)") >= led.live_bytes()
    for toks in leaks:
        for t in toks:
            led.free(t)
    assert led.live_bytes() == 0
    assert led.live_bytes(origin="w0") == 0 and led.live_bytes("w1") == 0


def test_ledger_concurrent_slot_set_exact():
    """set() is a read-modify-write against the slot's recorded size:
    racing resizes must serialize — a lost delta would skew the
    fused_step totals for process life."""
    led = DeviceMemoryLedger(register_gauges=False)

    class Owner:
        pass

    o = Owner()
    s = led.slot(o, 0, "slot_race", ctx="cpu(0)")
    n_threads, n_iter = 8, 400
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        for k in range(n_iter):
            s.set((i * 131 + k * 17) % 4096)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s.set(777)   # whatever interleaving happened, totals must re-converge
    assert led.live_bytes(origin="slot_race") == 777
    s.set(0)
    assert led.live_bytes(origin="slot_race") == 0


def test_ledger_track_buffer_lifetime_and_dedup():
    import jax.numpy as jnp
    led = DeviceMemoryLedger(register_gauges=False)
    buf = jnp.zeros((128,), jnp.float32) + 1  # fresh buffer, not a constant
    assert led.track(buf, origin="probe")
    assert not led.track(buf, origin="other")  # dedup: same buffer counts once
    assert led.live_bytes(origin="probe") == 512
    assert led.live_bytes(origin="other") == 0
    del buf
    gc.collect()
    assert led.live_bytes(origin="probe") == 0
    assert led.tracked_buffers == 0


def test_ledger_slot_follows_owner():
    led = DeviceMemoryLedger(register_gauges=False)

    class Owner:
        pass

    o = Owner()
    s = led.slot(o, 1000, "slotted", ctx="cpu(0)")
    assert led.live_bytes(origin="slotted") == 1000
    s.set(2500)
    assert led.live_bytes(origin="slotted") == 2500
    del o, s
    gc.collect()
    assert led.live_bytes(origin="slotted") == 0


def test_mem_live_bytes_reconciles_with_jax_live_arrays():
    """The acceptance check: ledger-tracked allocations move in lockstep
    with jax.live_arrays() — drift stays flat while both grow/shrink."""
    gc.collect()
    r0 = diag.reconcile()
    arrs = [mx.nd.zeros((256, 1024)) for _ in range(4)]  # 4 MiB tracked
    r1 = diag.reconcile()
    grown = r1["ledger_bytes"] - r0["ledger_bytes"]
    assert grown == 4 * 256 * 1024 * 4
    # live_arrays grew by the same amount (small slack for cached jax
    # internals materialized on the way)
    assert abs((r1["live_bytes"] - r0["live_bytes"]) - grown) < (1 << 20)
    assert abs(r1["drift_bytes"] - r0["drift_bytes"]) < (1 << 20)
    del arrs
    gc.collect()
    r2 = diag.reconcile()
    assert abs(r2["ledger_bytes"] - r0["ledger_bytes"]) < (1 << 16)
    # the exported gauges carry the same numbers
    assert tel.registry().gauge(
        "mem_live_bytes",
        labels={"ctx": "cpu(0)", "origin": "ndarray"}).value >= 0
    assert tel.registry().gauge("mem_peak_bytes",
                                labels={"ctx": "cpu(0)"}).value >= grown


def test_alloc_origin_outermost_wins():
    with diag.alloc_origin("serving_pool"):
        with diag.alloc_origin("executor"):
            assert diag.current_origin() == "serving_pool"
        with diag.alloc_origin("executor", override=True):
            assert diag.current_origin() == "executor"
    assert diag.current_origin() == "ndarray"


# ------------------------------------------------------------------ programs
def _fit_once(**kw):
    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype("float32")
    y = rng.randint(0, 4, 64).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fcd"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1,
            optimizer_params={"learning_rate": 0.1}, **kw)
    return mod


def test_cost_capture_all_build_kinds():
    """fwd_eval, fwd_bwd (executor), fused_step, metric_accum all land in
    the program registry with XLA's own cost numbers."""
    diag.programs()  # import side effects settled
    _fit_once()      # fused_step + metric_accum
    x = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(x, num_hidden=4,
                                                     name="fcc"),
                               name="softmax")
    ex = mx.Executor.simple_bind(net, ctx=mx.cpu(), data=(8, 16),
                                 softmax_label=(8,))
    ex.forward(is_train=False)              # fwd_eval
    ex.forward(is_train=True)               # fwd_bwd (grads armed)
    ex.backward()
    by_kind = {}
    for p in diag.programs():
        by_kind.setdefault(p["kind"], []).append(p)
    for kind in ("fwd_eval", "fwd_bwd", "fused_step", "metric_accum"):
        assert kind in by_kind, "missing cost capture for %s" % kind
        rec = by_kind[kind][-1]
        assert rec["bytes_accessed"] > 0 or rec["flops"] > 0
        assert rec["calls"] >= 1
        assert rec["compile_ms"] > 0
    # the fused step moves real parameter bytes
    fused = by_kind["fused_step"][-1]
    assert fused["argument_bytes"] > 0 and fused["flops"] > 0
    # telemetry mirrors the capture
    assert tel.registry().counter("program_captured",
                                  labels={"kind": "fused_step"}).value >= 1
    assert tel.registry().counter("program_flops",
                                  labels={"kind": "fused_step"}).value > 0
    # the table renders every row
    table = diag.program_table()
    assert "fused_step" in table and "metric_accum" in table


def test_instrumented_program_first_call_race_single_record():
    """Concurrent first invocations of one shared wrapper (the
    _ACCUM_FN_CACHE case) must produce exactly one compile and one
    ProgramRecord — losers wait for the winner's executable."""
    import threading

    import jax
    import jax.numpy as jnp
    from mxtpu import executor as _executor

    compiles = [0]
    inner = jax.jit(lambda v: v + 1)
    orig_lower = inner.lower

    def counting_lower(*a, **k):
        compiles[0] += 1
        return orig_lower(*a, **k)

    inner.lower = counting_lower
    fn = _executor.record_program_build("diag_race_probe", None, inner)
    before = len([p for p in diag.programs()
                  if p["kind"] == "diag_race_probe"])
    barrier = threading.Barrier(4)
    outs, errs = [], []

    def call():
        try:
            barrier.wait()
            outs.append(float(fn(jnp.ones((3,), jnp.float32)).sum()))
        except Exception as exc:  # surface thread failures in the assert
            errs.append(exc)

    threads = [threading.Thread(target=call) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert outs == [6.0] * 4
    after = [p for p in diag.programs() if p["kind"] == "diag_race_probe"]
    assert len(after) - before == 1, "duplicate ProgramRecords: %r" % after
    assert compiles[0] == 1, "first-call race compiled %d times" % compiles[0]


def test_instrumented_program_falls_back_on_signature_change():
    """The AOT fast path must hand dispatch back to jit when a later call
    changes dtype/shape — same numerics, no crash."""
    import jax
    import jax.numpy as jnp
    from mxtpu import executor as _executor
    fn = _executor.record_program_build("diag_probe", None,
                                        jax.jit(lambda v: v * 2))
    a = fn(jnp.ones((4,), jnp.float32))
    assert float(a.sum()) == 8.0
    b = fn(jnp.ones((6,), jnp.float32))      # new shape -> jit retrace
    assert float(b.sum()) == 12.0
    c = fn(jnp.ones((4,), jnp.int32))        # new dtype
    assert int(c.sum()) == 8
    # a persistently-moved signature demotes the AOT fast path to jit
    # after _DEMOTE_MISSES consecutive misses — numerics stay correct
    # through and past the demotion point
    for _ in range(_executor._DEMOTE_MISSES + 4):
        d = fn(jnp.ones((6,), jnp.float32))
        assert float(d.sum()) == 12.0
    # ALTERNATING signatures (bucketed training) never trip the
    # consecutive counter; the lifetime total demotes instead — numerics
    # stay correct through and past that threshold too
    fn2 = _executor.record_program_build("diag_alt_probe", None,
                                         jax.jit(lambda v: v * 2))
    for i in range(2 * _executor._DEMOTE_MISS_TOTAL + 8):
        # only every other call misses: 2x the total to cross it
        shape = (4,) if i % 2 == 0 else (6,)
        out = fn2(jnp.ones(shape, jnp.float32))
        assert float(out.sum()) == 2.0 * shape[0]


# ------------------------------------------------------------------ flight
def test_flight_recorder_ring_order_and_capacity():
    rec = FlightRecorder(capacity=16)
    for i in range(40):
        rec.record("probe", "e%d" % i, i)
    snap = rec.snapshot()
    assert len(snap) == 16
    assert [e["seq"] for e in snap] == list(range(24, 40))
    assert snap[-1]["name"] == "e39" and snap[-1]["kind"] == "probe"
    assert rec.events_recorded == 40


def test_spans_land_in_flight_ring():
    rec = diag.recorder()
    assert rec is not None
    with tel.span("flight_probe_span"):
        pass
    names = [(e["kind"], e["name"]) for e in rec.snapshot()]
    assert ("span_start", "flight_probe_span") in names
    assert ("span_end", "flight_probe_span") in names


def test_engine_push_lands_in_flight_ring():
    rec = diag.recorder()
    eng = mx.engine.get()
    eng.push(lambda: None)
    eng.wait_for_all()
    assert any(e["kind"] == "engine" and e["name"] == "push"
               for e in rec.snapshot())


# ------------------------------------------------------------------ watchdog
def test_watchdog_fires_on_wedged_fake_engine():
    """Queue nonempty + completions frozen past the deadline -> exactly
    one postmortem, with ring + ledger + program table all present."""
    fired = []
    wd = Watchdog(interval=0.01, engine_stall_s=0.05, wait_stall_s=99,
                  engine_probe=lambda: (3, 7),
                  on_detect=lambda reason: fired.append(reason))
    t0 = time.monotonic()
    while not fired and time.monotonic() - t0 < 3.0:
        time.sleep(0.02)
        wd.check()
    assert fired and "engine stalled" in fired[0]
    assert wd.detections == 1
    for _ in range(5):   # stays wedged: still ONE dump per wedge
        time.sleep(0.02)
        wd.check()
    assert wd.detections == 1
    # the default sink (postmortem) carries all three sections
    pm = diag.postmortem("watchdog-test", source="test")
    assert "flight" in pm and "ledger" in pm and "programs" in pm
    assert "engine" in pm and isinstance(pm["flight"], list)
    assert pm["ledger"]["live_bytes_total"] >= 0


def test_watchdog_detects_stalled_device_wait():
    wd = Watchdog(interval=0.01, engine_stall_s=99, wait_stall_s=0.05,
                  engine_probe=lambda: (0, 0),
                  on_detect=lambda r: None)
    done = threading.Event()

    def stuck():
        diag.wait_begin("test_wait")
        done.wait(2.0)
        diag.wait_end()

    t = threading.Thread(target=stuck, daemon=True)
    t.start()
    time.sleep(0.15)
    reason = wd.check()
    done.set()
    t.join()
    assert reason is not None and "device_wait" in reason
    assert wd.check() is None or True  # wait gone after wait_end


def test_watchdog_silent_through_full_fit():
    """A healthy Module.fit must never trip the watchdog."""
    hits = []
    wd = Watchdog(interval=0.01, engine_stall_s=0.5, wait_stall_s=0.5,
                  on_detect=lambda r: hits.append(r)).start()
    try:
        _fit_once(batch_end_callback=mx.callback.Speedometer(
            16, frequent=2, auto_reset=False))
        time.sleep(0.1)
    finally:
        wd.stop()
    assert hits == []
    assert wd.detections == 0


# ------------------------------------------------------------------ dumps
def test_sigusr2_dump_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_DIAG_DUMP_DIR", str(tmp_path))
    assert diag.install_signal_handler()
    os.kill(os.getpid(), signal.SIGUSR2)
    deadline = time.monotonic() + 5.0
    files = []
    while not files and time.monotonic() < deadline:
        time.sleep(0.05)
        files = list(tmp_path.glob("mxtpu_postmortem_*.json"))
    assert files, "SIGUSR2 produced no dump file"
    dump = json.loads(files[0].read_text())
    assert dump["source"] == "signal"
    for section in ("flight", "ledger", "programs", "engine", "waits"):
        assert section in dump
    assert dump["ledger"]["live_bytes_total"] >= 0


def test_postmortem_on_fit_exception():
    class Boom(RuntimeError):
        pass

    def bad_callback(param):
        raise Boom("deliberate")

    before = diag.last_postmortem()
    with pytest.raises(Boom):
        _fit_once(batch_end_callback=bad_callback)
    pm = diag.last_postmortem()
    assert pm is not None and pm is not before
    assert pm["reason"] == "fit_exception" and "Boom" in pm["exception"]
    assert pm["source"] == "fit"


def test_postmortem_fires_on_native_error_not_usage_error():
    """MXNetError from fit is a usage error (silent); NativeError — a
    nonzero native-engine return — is a backend failure and must leave
    forensics despite being an MXNetError subclass."""
    from mxtpu.base import MXNetError, NativeError

    before = diag.last_postmortem()
    with pytest.raises(MXNetError):
        _fit_once(batch_end_callback=lambda p: (_ for _ in ()).throw(
            MXNetError("bad user input")))
    assert diag.last_postmortem() is before, \
        "plain MXNetError must not dump"
    with pytest.raises(NativeError):
        _fit_once(batch_end_callback=lambda p: (_ for _ in ()).throw(
            NativeError("engine push failed")))
    pm = diag.last_postmortem()
    assert pm is not None and pm is not before
    assert pm["reason"] == "fit_exception" and pm["source"] == "fit"
    assert "engine push failed" in pm["exception"]


def test_instrumented_program_defers_capture_under_precision_env(
        monkeypatch):
    """A first call under MXTPU_MATMUL_PRECISION must not consume the
    capture slot: the program table fills in at the first call after the
    env clears, instead of staying empty for the wrapper's life."""
    import jax
    import jax.numpy as jnp
    from mxtpu import executor as _executor
    fn = _executor._instrument_program(
        "diag_prec_probe", jax.jit(lambda v: v * 3), matmul_env=True)
    h = tel.registry().histogram("executor_compile_ms",
                                 labels={"kind": "diag_prec_probe"})
    before = h.snapshot()
    monkeypatch.setenv("MXTPU_MATMUL_PRECISION", "highest")
    assert float(fn(jnp.ones((2,), jnp.float32)).sum()) == 6.0
    assert not [p for p in diag.programs()
                if p["kind"] == "diag_prec_probe"]
    # the literal first call still lands in executor_compile_ms even
    # though capture was deferred (it paid jit's lazy compile)
    assert h.snapshot()[0] - before[0] == 1
    monkeypatch.delenv("MXTPU_MATMUL_PRECISION")
    assert float(fn(jnp.ones((2,), jnp.float32)).sum()) == 6.0
    assert [p for p in diag.programs() if p["kind"] == "diag_prec_probe"]


def test_dump_state_on_demand(tmp_path):
    p = diag.dump_state(str(tmp_path / "state.json"))
    state = json.loads(open(p).read())
    for section in ("ledger", "programs", "flight", "engine"):
        assert section in state


# ------------------------------------------------------------------ serving
def test_debug_state_http_schema():
    """GET /debug/state on a live serving session returns all three
    diagnostic sections (+ engine/serving) as JSON."""
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving.server import ServingHTTPServer, ServingSession
    sj, params, shapes = get_fixture("mlp")
    sess = ServingSession(sj, params, shapes, buckets=(1, 4),
                          contexts=[mx.cpu()])
    server = ServingHTTPServer(sess, port=0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        body = json.dumps({"inputs": {"data": [[0.0] * 784]}}).encode()
        urllib.request.urlopen(server.endpoint + "/v1/predict", data=body)
        state = json.loads(urllib.request.urlopen(
            server.endpoint + "/debug/state").read())
        # the three tentpole sections
        assert isinstance(state["ledger"]["live_bytes"], dict)
        assert state["ledger"]["live_bytes_total"] >= 0
        assert isinstance(state["programs"], list) and state["programs"]
        assert {"kind", "flops", "compile_ms"} <= set(state["programs"][0])
        assert isinstance(state["flight"], list) and state["flight"]
        assert {"seq", "kind", "name", "thread"} <= set(state["flight"][0])
        # plus engine + per-session serving stats
        assert "queue_depth" in state["engine"]
        assert "uptime_sec" in state["serving"]
        # serving requests visible in the ring
        assert any(e["name"] == "serving.request"
                   for e in state["flight"])
    finally:
        server.shutdown()


def test_serving_pool_origin_attribution():
    """Buffers first allocated inside a pool bind are tagged
    serving_pool (outermost-origin attribution through the executor)."""
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving.pool import ExecutorPool
    sj, params, shapes = get_fixture("mlp")
    led = diag.ledger()
    pool = ExecutorPool(sj, params, shapes, contexts=[mx.cpu()])
    assert led.live_bytes(origin="serving_pool") > 0
    del pool


# ------------------------------------------------------------------ satellites
def test_print_summary_memory_column_and_params(capsys):
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, name="c1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mx.viz.print_summary(net, shape={"data": (1, 3, 8, 8)})
    out = capsys.readouterr().out
    assert "Mem (KB)" in out
    # conv: 3*3*3*8 + 8 = 224; fc: 288*10 + 10 = 2890
    assert "Total params: 3114" in out
    assert "Total memory" in out


def test_print_summary_grouped_symbol_shapes(capsys):
    """Grouped symbols and multi-output layers report real shapes (the
    old name-keyed lookup showed blanks)."""
    s = mx.sym.SliceChannel(mx.sym.Variable("x"), num_outputs=2, name="sl")
    g = mx.sym.Group([s, mx.sym.FullyConnected(mx.sym.Variable("y"),
                                               num_hidden=3, name="gfc")])
    mx.viz.print_summary(g, shape={"x": (2, 4), "y": (2, 5)})
    out = capsys.readouterr().out
    assert "(2,), (2,)" in out        # both slice outputs, batch stripped
    assert "Total params: 18" in out  # 5*3 + 3


def test_monitor_stats_become_telemetry_series():
    mon = mx.monitor.Monitor(1, pattern="diagmon_.*")
    mon.tic()
    mon.stat_helper("diagmon_w", mx.nd.ones((2, 2)))
    res = mon.toc()
    assert res and res[0][1] == "diagmon_w"
    g = tel.registry().gauge("monitor_stat", labels={"name": "diagmon_w"})
    assert g.value == 1.0


def test_series_inventory_documented():
    """Every literal telemetry series emitted by mxtpu/ appears in the
    docs/observability.md inventory (the CI check tool) — and every
    span name in its span-inventory section."""
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "check_series_documented.py")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
