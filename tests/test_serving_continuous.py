"""Continuous batching, admission control, and hot-swap (PR 10).

Tier-1 (CPU, `not slow`). Contracts under test:

* the refill watermark releases a partial batch to a hungry device slot
  WITHOUT waiting for the deadline, and never lingers;
* byte-identity survives the K-in-flight pipeline (async dispatch +
  deferred retire must not perturb rows);
* the overload taxonomy is distinguishable over HTTP: 429 = admission
  shed, 504 = queue deadline, 503 = drain window only;
* a version hot-swap under load fails ZERO requests, and rolling back
  to a warm-cached version costs zero compiles.
"""
import json
import math
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxtpu as mx
from mxtpu.models.serving_fixtures import get_fixture
from mxtpu.predict import Predictor
from mxtpu.serving import (ACCEPTING, DEGRADED, SHEDDING, AdmissionShed,
                           AdmissionSignals, ContinuousBatcher,
                           ServingHTTPServer, ServingSession,
                           SignalAdmissionPolicy, derive_knobs, pad_rows,
                           prewarm)
from mxtpu.serving.admission import mix_service_model


def _rand(shape, seed):
    return np.random.RandomState(seed).rand(*shape).astype(np.float32)


# ------------------------------------------------------------------ batcher
def test_continuous_batcher_watermark_refill():
    """A hungry slot takes a partial batch the moment pending rows reach
    the refill watermark — no deadline wait, reason recorded."""
    b = ContinuousBatcher(["data"], buckets=(4, 8), max_delay_ms=10_000,
                          refill_watermark=2)
    assert b.refill_watermark == 2
    b.submit({"data": _rand((1, 3), 0)})
    b.submit({"data": _rand((1, 3), 1)})
    t0 = time.monotonic()
    batch = b.next_fill(timeout=5, hungry=True)
    assert time.monotonic() - t0 < 5  # did NOT wait the 10s deadline
    assert batch is not None and batch.n_valid == 2
    assert b.last_flush_reason == "watermark"
    # below the watermark + non-blocking poll: nothing comes back
    b.submit({"data": _rand((1, 3), 2)})
    assert b.next_fill(timeout=0, hungry=True) is None
    # a full largest bucket flushes with reason "full"
    for i in range(8):
        b.submit({"data": _rand((1, 3), 3 + i)})
    batch = b.next_fill(timeout=5, hungry=True)
    assert batch is not None and b.last_flush_reason == "full"


def test_continuous_batcher_not_hungry_behaves_like_burst():
    """With every slot occupied (hungry=False) the watermark is ignored:
    sub-bucket rows wait for the deadline exactly like the PR-1 batcher."""
    b = ContinuousBatcher(["data"], buckets=(8,), max_delay_ms=40,
                          refill_watermark=1)
    b.submit({"data": _rand((1, 3), 0)})
    assert b.next_fill(timeout=0, hungry=False) is None  # 1 row, not due
    t0 = time.monotonic()
    batch = b.next_fill(timeout=5, hungry=False)
    assert batch is not None and batch.n_valid == 1
    assert time.monotonic() - t0 >= 0.030  # held ~the deadline
    assert b.last_flush_reason == "deadline"


def test_continuous_batcher_default_watermark():
    b = ContinuousBatcher(["data"], buckets=(1, 8, 32, 128))
    assert b.refill_watermark == 32  # smallest bucket >= largest/4
    b2 = ContinuousBatcher(["data"], buckets=(4,))
    assert b2.refill_watermark == 1  # quarter of 4 -> smallest bucket


# ---------------------------------------------------------------- admission
def _signals(**kw):
    base = dict(queue_depth=0, queue_limit=256, pending_rows=0,
                inflight_depth=0, inflight_limit=2, replicas=1,
                est_batch_ms=2.0, est_queue_wait_ms=0.0,
                watchdog_age_s=0.0, mem_headroom_frac=None)
    base.update(kw)
    return AdmissionSignals(**base)


def test_admission_policy_signal_matrix():
    pol = SignalAdmissionPolicy(queue_wait_budget_ms=100.0,
                                watchdog_shed_s=10.0,
                                min_mem_headroom=0.05,
                                queue_frac_shed=0.9, degrade_frac=0.5)
    # healthy: admit, accepting
    d = pol.decide(_signals())
    assert d.admit and d.state == ACCEPTING
    # latency breach: shed with the reason naming the signal
    d = pol.decide(_signals(est_queue_wait_ms=150.0))
    assert not d.admit and d.state == SHEDDING and "latency" in d.reason
    # degrade band: admit but visible
    d = pol.decide(_signals(est_queue_wait_ms=60.0))
    assert d.admit and d.state == DEGRADED
    # watchdog stall dominates everything
    d = pol.decide(_signals(watchdog_age_s=11.0))
    assert not d.admit and "watchdog" in d.reason
    # memory headroom below floor sheds; missing budget never does
    d = pol.decide(_signals(mem_headroom_frac=0.01))
    assert not d.admit and "memory" in d.reason
    assert pol.decide(_signals(mem_headroom_frac=None)).admit
    # queue occupancy sheds a breath before QueueFull would
    d = pol.decide(_signals(queue_depth=240, queue_limit=256))
    assert not d.admit and "queue" in d.reason


def test_derive_knobs_from_cost_rows():
    # per-row cost: b=1 -> 1.0, b=8 -> 0.25, b=32 -> 0.125 (best),
    # 1.25x best = 0.15625 -> smallest qualifying bucket is 32
    costs = {1: {"exec_ms": 1.0}, 8: {"exec_ms": 2.0},
             32: {"exec_ms": 4.0}}
    k = derive_knobs(costs, (1, 8, 32))
    assert k["basis"] == "cost-registry"
    assert k["refill_watermark"] == 32
    assert k["est_batch_ms"] == 4.0
    # flat per-row cost (overhead-free model): dispatch at the smallest
    flat = {1: {"exec_ms": 1.0}, 8: {"exec_ms": 8.0}}
    assert derive_knobs(flat, (1, 8))["refill_watermark"] == 1
    # nothing measured -> structural default (None = batcher decides)
    assert derive_knobs({}, (1, 8))["basis"] == "default"
    assert derive_knobs({}, (1, 8))["refill_watermark"] is None


# ------------------------------------------------------------------ session
def test_continuous_session_byte_identical_inflight():
    """24 concurrent clients through the K=3-in-flight continuous
    pipeline: every response byte-identical to a direct Predictor at one
    of the bucket shapes (async dispatch + deferred retire must not
    perturb or cross rows)."""
    sj, params, shapes = get_fixture("mlp")
    buckets = (1, 8)
    refs = {b: Predictor(sj, dict(params), input_shapes={"data": (b, 784)})
            for b in buckets}

    def direct(x, b):
        refs[b].forward(data=pad_rows(x, b))
        return refs[b].get_output(0)[:1]

    with ServingSession(sj, params, shapes, buckets=buckets,
                        max_delay_ms=3, contexts=[mx.cpu(0)],
                        max_in_flight=3) as sess:
        results, errors = {}, []
        lock = threading.Lock()

        def client(i):
            x = _rand((1, 784), i)
            try:
                out = sess.predict({"data": x}, timeout=60)[0]
                with lock:
                    results[i] = (x, out)
            except Exception as exc:
                errors.append((i, exc))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        assert len(results) == 24
        for i, (x, out) in results.items():
            assert any(np.array_equal(out, direct(x, b)) for b in buckets), \
                "client %d response not byte-identical to any bucket" % i
        # a sequential tail: each dispatch after a retire re-occupies a
        # freed slot, which is what refill_latency_ms measures
        for i in range(3):
            sess.predict({"data": _rand((1, 784), 100 + i)}, timeout=30)
        stats = sess.stats()
        assert stats["requests_completed"] == 27
        # the continuous-path series exist and carry observations
        assert stats["batch_exec_ms"]["count"] >= 1
        assert stats["refill_latency_ms"]["count"] >= 1
        assert stats["admission_state"] == ACCEPTING
    # after drain every slot window is empty again
    assert sum(sess._inflight_n) == 0


def test_overload_taxonomy_http_429_504_503():
    """The three overload statuses are distinguishable: 429 = admission
    shed (policy, with "shed": true body), 504 = the request out-waited
    its own deadline in the queue, 503 = drain window only."""
    sj, params, shapes = get_fixture("mlp")
    sess = ServingSession(sj, params, shapes, buckets=(1, 4),
                          max_delay_ms=1, max_queue=64,
                          contexts=[mx.cpu(0)],
                          admission=SignalAdmissionPolicy(
                              queue_wait_budget_ms=1000.0))
    server = ServingHTTPServer(sess, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = server.endpoint

    def post(payload):
        req = urllib.request.Request(
            base + "/v1/predict", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=30)

    x = _rand((1, 784), 0).tolist()
    try:
        # healthy: 200
        with post({"inputs": {"data": x}}) as r:
            assert r.status == 200
        # wedge the (single) dispatcher inside dispatch, leave work in
        # the queue so pending_rows > 0, then tighten the latency budget
        gate = threading.Event()
        rep = sess.pool.replicas[0]
        orig = rep.dispatch
        rep.dispatch = lambda inputs: (gate.wait(15), orig(inputs))[1]
        stuck = sess.predict_async({"data": _rand((1, 784), 1)})
        deadline = time.time() + 5
        while sess.batcher.depth > 0 and time.time() < deadline:
            time.sleep(0.005)
        filler = sess.predict_async({"data": _rand((1, 784), 2)})
        sess._admission.queue_wait_budget_ms = 1e-6
        with pytest.raises(urllib.error.HTTPError) as ei:
            post({"inputs": {"data": x}})
        assert ei.value.code == 429
        body = json.loads(ei.value.read())
        assert body.get("shed") is True and "latency" in body["error"]
        assert sess.stats()["shed_rate"] > 0
        # restore the budget: now the same overload yields a 504 once
        # the request's own deadline expires in the queue
        sess._admission.queue_wait_budget_ms = 1e9
        with pytest.raises(urllib.error.HTTPError) as ei:
            post({"inputs": {"data": x}, "timeout_sec": 0.1})
        assert ei.value.code == 504
        gate.set()
        stuck.wait(30)
        filler.wait(30)
    finally:
        gate.set()
        sess.close()
    # drain window: the only time a healthy deploy serves 503
    with pytest.raises(urllib.error.HTTPError) as ei:
        post({"inputs": {"data": x}})
    assert ei.value.code == 503
    server.server_close()


def test_hot_swap_zero_failed_requests_under_load():
    """A version flip under concurrent load fails ZERO requests: every
    response is byte-identical to the old or the new weights, and after
    the flip quiesces new requests serve the new weights only."""
    sj, params_a, shapes = get_fixture("mlp")
    # same graph, perturbed weights — distinct version, same arg names
    params_b = {k: v + 0.25 for k, v in params_a.items()}
    buckets = (1, 8)
    refs = {}
    for tag, p in (("a", params_a), ("b", params_b)):
        for b in buckets:
            refs[(tag, b)] = Predictor(sj, dict(p),
                                       input_shapes={"data": (b, 784)})

    def direct(tag, x, b):
        refs[(tag, b)].forward(data=pad_rows(x, b))
        return refs[(tag, b)].get_output(0)[:1]

    sess = ServingSession(sj, params_a, shapes, buckets=buckets,
                          max_delay_ms=2, contexts=[mx.cpu(0)],
                          version_tag="swap-a")
    results, errors = [], []
    lock = threading.Lock()
    stop = threading.Event()

    def client(i):
        n = 0
        while not stop.is_set() and n < 12:
            x = _rand((1, 784), 1000 * i + n)
            try:
                out = sess.predict({"data": x}, timeout=60)[0]
                with lock:
                    results.append((x, out))
            except Exception as exc:
                errors.append(exc)
            n += 1

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.15)  # mid-load...
        info = sess.swap_model(sj, params_b, version_tag="swap-b")
        assert info["generation"] == 1 and info["version"] == "swap-b"
        for t in threads:
            t.join()
        stop.set()
        assert not errors, errors[:3]  # ZERO failed requests across the flip
        for x, out in results:
            assert any(np.array_equal(out, direct(tag, x, b))
                       for tag in ("a", "b") for b in buckets), \
                "a response matched neither version's weights"
        # post-flip requests serve the NEW weights only
        x = _rand((1, 784), 424242)
        out = sess.predict({"data": x}, timeout=30)[0]
        assert any(np.array_equal(out, direct("b", x, b)) for b in buckets)
        assert not any(np.array_equal(out, direct("a", x, b))
                       for b in buckets)
        assert sess.stats()["model_swaps"] == 1
    finally:
        stop.set()
        sess.close()


def test_warm_cache_prewarm_and_rollback_zero_compiles():
    """Deploy-time prewarm from a bucket manifest makes session startup
    compile-free, and a hot-swap BACK to a warm-cached version (rollback)
    adopts its predictors — zero compiles, correct (old) weights."""
    from mxtpu import executor as _ex
    sj, params_a, shapes = get_fixture("mlp")
    params_b = {k: v + 0.5 for k, v in params_a.items()}
    buckets = (1, 4)
    built = prewarm(sj, params_a, shapes, buckets=buckets,
                    contexts=[mx.cpu(0)], version_tag="roll-a")
    assert built == len(buckets)
    b0 = _ex.program_build_count()
    sess = ServingSession(sj, params_a, shapes, buckets=buckets,
                          max_delay_ms=1, contexts=[mx.cpu(0)],
                          version_tag="roll-a")
    try:
        assert _ex.program_build_count() == b0, \
            "prewarmed session still compiled at startup"
        assert sess.pool.adopted
        assert sorted(sess.pool.bucket_costs()) == list(buckets)
        sess.swap_model(sj, params_b, version_tag="roll-b")  # compiles
        b1 = _ex.program_build_count()
        assert b1 > b0
        sess.swap_model(sj, params_a, version_tag="roll-a")  # rollback
        assert _ex.program_build_count() == b1, \
            "rollback to a warm version recompiled"
        assert sess.stats()["warm_cache_adoptions"] >= 2
        # and it really serves the ORIGINAL weights again
        ref = Predictor(sj, dict(params_a), input_shapes={"data": (1, 784)})
        x = _rand((1, 784), 7)
        ref.forward(data=x)
        out = sess.predict({"data": x}, timeout=30)[0]
        assert np.array_equal(out, ref.get_output(0))
    finally:
        sess.close()


def test_stale_tag_never_serves_old_weights():
    """Re-using a version tag with DIFFERENT weights must rebuild, not
    adopt: params_token mismatch evicts the stale cache entry."""
    sj, params_a, shapes = get_fixture("mlp")
    params_b = {k: v + 1.0 for k, v in params_a.items()}
    s1 = ServingSession(sj, params_a, shapes, buckets=(1,), max_delay_ms=1,
                        contexts=[mx.cpu(0)], version_tag="stale-t")
    s1.close()
    s2 = ServingSession(sj, params_b, shapes, buckets=(1,), max_delay_ms=1,
                        contexts=[mx.cpu(0)], version_tag="stale-t")
    try:
        ref = Predictor(sj, dict(params_b), input_shapes={"data": (1, 784)})
        x = _rand((1, 784), 3)
        ref.forward(data=x)
        out = s2.predict({"data": x}, timeout=30)[0]
        assert np.array_equal(out, ref.get_output(0))
    finally:
        s2.close()


def test_version_endpoint_and_debug_panels():
    """GET /v1/version reports the active version; /debug/state carries
    the admission, version and warm-cache panels mxtpu_top renders."""
    sj, params, shapes = get_fixture("mlp")
    sess = ServingSession(sj, params, shapes, buckets=(1, 4),
                          max_delay_ms=1, contexts=[mx.cpu(0)],
                          version_tag="panel-v0")
    server = ServingHTTPServer(sess, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        base = server.endpoint
        sess.predict({"data": _rand((1, 784), 0)}, timeout=30)
        with urllib.request.urlopen(base + "/v1/version", timeout=10) as r:
            v = json.loads(r.read())
        assert v["version"] == "panel-v0" and v["generation"] == 0
        assert v["mode"] == "continuous" and len(v["symbol_hash"]) == 16
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            h = json.loads(r.read())
        assert h["mode"] == "continuous" and h["admission"] == "accepting"
        with urllib.request.urlopen(base + "/debug/state", timeout=10) as r:
            state = json.loads(r.read())
        adm = state["serving_admission"]
        assert adm["state"] == "accepting"
        assert adm["policy"] == "SignalAdmissionPolicy"
        assert "est_queue_wait_ms" in adm["signals"]
        assert state["serving_version"]["version"] == "panel-v0"
        assert any(e["version"] == "panel-v0"
                   for e in state["serving_warm_cache"])
        # in-process shed surfaces as AdmissionShed (the 429 mapping is
        # covered by the HTTP taxonomy test)
        sess._admission.queue_wait_budget_ms = -1.0
        with pytest.raises(AdmissionShed):
            sess.predict_async({"data": _rand((1, 784), 1)})
    finally:
        server.shutdown()
        server.server_close()


# --------------------------------------------- mix-aware admission (ISSUE)
def test_mix_service_model_learns_live_mix():
    buckets = (1, 128)
    cost_rows = {1: {"exec_ms": 2.0}, 128: {"exec_ms": 50.0}}
    prior = mix_service_model({}, cost_rows, buckets)
    assert prior["basis"] == "cost-rows"
    assert prior["est_batch_ms"] == 50.0
    assert prior["est_rows_per_batch"] == 128.0
    live = mix_service_model({1: (20, 2.0)}, cost_rows, buckets)
    assert live["basis"] == "live-mix"
    assert live["est_batch_ms"] == pytest.approx(2.0)   # tracks measured
    assert live["est_rows_per_batch"] == pytest.approx(1.0)
    # a mixed stream weights by traffic, not by the largest bucket
    mixed = mix_service_model({1: (30, 2.0), 128: (10, 50.0)},
                              cost_rows, buckets)
    assert mixed["est_batch_ms"] == pytest.approx((30 * 2 + 10 * 50) / 40)
    assert mixed["est_rows_per_batch"] == pytest.approx(
        (30 * 1 + 10 * 128) / 40)


def test_mix_aware_estimate_stops_over_shedding():
    """The ROADMAP item-1 acceptance: a small-bucket-heavy mix must not
    be priced at largest-bucket service. 4 pending single-row requests
    + 2 small batches in flight: the old largest-bucket model estimates
    3 batches x 50ms = 150ms and SHEDS at a 100ms budget; the live mix
    (bucket-1 batches measured at 2ms) estimates 12ms and ADMITS —
    tracking the measured per-bucket service, not the shape assumption."""
    buckets = (1, 128)
    cost_rows = {1: {"exec_ms": 2.0}, 128: {"exec_ms": 50.0}}
    pol = SignalAdmissionPolicy(queue_wait_budget_ms=100.0)

    def signals(model, pending, inflight):
        batches = math.ceil(pending / model["est_rows_per_batch"]) \
            + inflight
        return AdmissionSignals(
            queue_depth=pending, queue_limit=256, pending_rows=pending,
            inflight_depth=inflight, inflight_limit=4, replicas=1,
            est_batch_ms=model["est_batch_ms"],
            est_queue_wait_ms=model["est_batch_ms"] * batches)

    prior = mix_service_model({}, cost_rows, buckets)
    live = mix_service_model({1: (20, 2.0)}, cost_rows, buckets)
    assert pol.decide(signals(prior, 4, 2)).admit is False   # over-shed
    d = pol.decide(signals(live, 4, 2))
    assert d.admit is True                                   # mix-aware


def test_serving_session_service_model_goes_mix_aware():
    sym_json, params, shapes = get_fixture("mlp", seed=0)
    with ServingSession(sym_json, params, shapes,
                                   buckets=(1, 8), warmup=True) as s:
        pre = s._service_model()
        assert pre["basis"] == "cost-rows"
        assert pre["est_rows_per_batch"] == 8.0
        # a skewed single-row mix lands in the per-worker aggregates
        # (the same call the dispatcher makes at retire time)
        for _ in range(16):
            s._record_service(0, 1, 2.0)
        post = s._service_model()
        assert post["basis"] == "live-mix"
        assert post["est_batch_ms"] == pytest.approx(2.0, rel=0.1)
        assert post["est_rows_per_batch"] == pytest.approx(1.0)
        assert s._est_batch_ms() == pytest.approx(2.0, rel=0.1)
        # the signals consume the learned mix
        sig = s._signals()
        assert sig.est_batch_ms == pytest.approx(2.0, rel=0.1)
        # ...and the same observations were mirrored into the labeled
        # telemetry series for dashboards
        h = s.metrics.histogram("batch_service_ms",
                                labels={"bucket": "1"})
        assert h.count == 16


def test_swap_model_resets_service_aggregates():
    """A hot-swapped model has a new service profile: the mix-aware
    estimate must re-learn from its batches, not price them with the
    old model's history."""
    sym_json, params, shapes = get_fixture("mlp", seed=0)
    with ServingSession(sym_json, params, shapes,
                                   buckets=(1, 8), warmup=False) as s:
        for _ in range(16):
            s._record_service(0, 1, 2.0)
        assert s._service_model()["basis"] == "live-mix"
        s.swap_model(sym_json, params, version_tag="v-next", warmup=False)
        assert s._service_model()["basis"] != "live-mix"
        assert all(not d for d in s._bucket_service)


def test_serving_traffic_populates_per_bucket_series():
    """End to end: real single-row traffic produces labeled per-bucket
    service observations (the series the estimate learns from)."""
    sym_json, params, shapes = get_fixture("mlp", seed=0)
    rng = np.random.RandomState(0)
    payload = {"data": rng.rand(*shapes["data"]).astype(np.float32)}
    with ServingSession(sym_json, params, shapes,
                                   buckets=(1, 8), warmup=True,
                                   max_delay_ms=1.0) as s:
        for _ in range(12):
            s.predict(payload, timeout=30)
        labeled = [m for m in s.metrics.series()
                   if m.name == "batch_service_ms" and m.labels]
        assert labeled and sum(m.count for m in labeled) > 0
