"""mxtpu.obs.health + mxtpu.obs.detectors — device-resident training-
health statistics (docs/observability.md "Training health"). The
contracts:

* **detector determinism**: every detector is pure over explicit state —
  seeded synthetic stat streams assert EXACTLY which cadence fires, with
  frozen windows and no wall-clock anywhere;
* **zero added sync points** (cadence exactness): an armed fit performs
  the SAME number of ``jax.device_get`` transfers as a disarmed one —
  the stat accumulator rides the metric accum's cadence sync as a rider;
* **THE rollback gate**: an injected divergence mid-fit produces the
  divergence Finding + ``health_anomalies`` counter, fires the
  supervisor action seam, the wedged trajectory aborts BEFORE its
  snapshot, and the retry restores the last good generation — the fit
  completes with weights bit-exact against a clean run;
* **one postmortem per root cause**: a nonfinite the sanitizer already
  captured must not produce a second (health) postmortem, in either
  firing order;
* corpus ``health`` rows round-trip and keep the torn-tail tolerance;
* the Monitor adapter (default abs-mean stat) matches the legacy
  per-op path's values; a custom ``stat_func`` keeps the legacy path.
"""
import json
import os

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import metric as M
from mxtpu import telemetry as tel
from mxtpu.analysis.findings import ERROR, WARNING
from mxtpu.models import mlp as _mlp
from mxtpu.obs import corpus as _corpus
from mxtpu.obs import detectors as D
from mxtpu.obs import health as H


def _mnist_like(n=256, seed=7):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, 784).astype("float32"),
            rng.randint(0, 10, n).astype("float32"))


def _make_iter(batch_size=64, poison_batch=None):
    X, y = _mnist_like()
    if poison_batch is not None:
        X = X.copy()
        X[poison_batch * batch_size:(poison_batch + 1) * batch_size] = \
            np.inf
    return mx.io.NDArrayIter(X, y, batch_size=batch_size,
                             label_name="softmax_label")


def _fit(num_epoch=2, seed=11, module=None, it=None, **fit_kwargs):
    it = it or _make_iter()
    mod = module or mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    metric = M.create(["acc", "ce"])
    mx.random.seed(seed)
    np.random.seed(seed)
    fit_kwargs.setdefault("metric_sync", 2)
    mod.fit(it, num_epoch=num_epoch, eval_metric=metric,
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.initializer.Xavier(),
            **fit_kwargs)
    weights = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    return dict(metric.get_name_value()), weights, mod


def _stats(**per_class):
    """{class: stat dict} with the full stat vocabulary defaulted."""
    base = {"grad_norm": 1.0, "weight_norm": 1.0, "update_ratio": 0.01,
            "grad_max": 1.0, "nonfinite": 0}
    return {cls: dict(base, **override)
            for cls, override in per_class.items()}


# ------------------------------------------------------ detector units
def test_loss_spike_fires_only_after_full_window():
    det = D.LossSpikeDetector(window=4, spike_k=8.0)
    # window filling: nothing may fire, not even on a huge value
    assert det.observe(1.0, {}) is None
    assert det.observe(50.0, {}) is None
    assert det.observe(1.02, {}) is None
    assert det.observe(0.98, {}) is None
    # window full; next in-band value stays quiet
    assert det.observe(1.01, {}) is None


def test_loss_spike_exact_cadence_and_unpoisoned_baseline():
    det = D.LossSpikeDetector(window=4, spike_k=8.0)
    for v in (1.0, 1.02, 0.98, 1.01):
        assert det.observe(v, {}) is None
    f = det.observe(3.0, {})            # cadence 5: the spike
    assert f is not None and f.severity == WARNING
    assert f.details["kind"] == "loss_spike"
    assert f.details["threshold"] < 3.0
    # the tripping loss was NOT pushed into the window: the baseline is
    # intact, an in-band value is quiet and a repeat spike fires again
    assert det.observe(1.0, {}) is None
    assert det.observe(3.0, {}) is not None


def test_loss_spike_flat_stream_is_not_dust():
    det = D.LossSpikeDetector(window=4, spike_k=8.0)
    for _ in range(6):
        assert det.observe(1.0, {}) is None   # MAD 0, floored not zeroed


def test_divergence_nonfinite_fires_cadence_one_with_hysteresis():
    det = D.DivergenceDetector(window=4)
    f = det.observe(None, _stats(fc1_weight={"nonfinite": 3}))
    assert f is not None and f.severity == ERROR
    assert f.details["kind"] == "divergence"
    assert f.details["nonfinite"] == 3
    assert f.details["classes"] == ["fc1_weight"]
    # hysteresis: the wedged trajectory emits ONE Finding per excursion
    assert det.observe(None, _stats(fc1_weight={"nonfinite": 3})) is None
    # recovery re-arms it
    assert det.observe(1.0, _stats(fc1_weight={})) is None
    assert det.observe(None,
                       _stats(fc1_weight={"nonfinite": 1})) is not None


def test_divergence_nonfinite_loss_and_ratio_arms():
    det = D.DivergenceDetector(window=3, diverge_k=1e3)
    f = det.observe(float("nan"), _stats(fc1_weight={}))
    assert f is not None and "nonfinite" in f.message
    det = D.DivergenceDetector(window=3, diverge_k=1e3)
    for v in (1.0, 1.1, 0.9):
        assert det.observe(v, _stats(fc1_weight={})) is None
    assert det.observe(900.0, _stats(fc1_weight={})) is None  # < k*median
    f = det.observe(5000.0, _stats(fc1_weight={}))
    assert f is not None and f.details["kind"] == "divergence"


def test_dead_layer_exact_consecutive_cadence():
    det = D.DeadLayerDetector(n_cadences=3, eps=1e-12)
    dead = _stats(a={"grad_norm": 0.0}, b={"grad_norm": 1.0})
    assert det.observe(1.0, dead) is None      # run 1
    assert det.observe(1.0, dead) is None      # run 2
    f = det.observe(1.0, dead)                 # run 3: fires
    assert f is not None and f.details["class"] == "a"
    assert f.details["cadences"] == 3
    assert det.observe(1.0, dead) is None      # fired once, stays quiet
    alive = _stats(a={"grad_norm": 1.0}, b={"grad_norm": 1.0})
    assert det.observe(1.0, alive) is None     # revival re-arms
    for _ in range(2):
        assert det.observe(1.0, dead) is None
    assert det.observe(1.0, dead) is not None


def test_exploding_update_cold_start_suppression():
    det = D.ExplodingUpdateDetector(threshold=0.5, n_cadences=3)
    hot = _stats(fc1_bias={"update_ratio": 0.9})
    cool = _stats(fc1_bias={"update_ratio": 0.1})
    # a zero-init param's first cadences exceed the ratio by
    # construction; a transient excursion must never fire
    assert det.observe(1.0, hot) is None
    assert det.observe(1.0, hot) is None
    assert det.observe(1.0, cool) is None      # run reset
    assert det.observe(1.0, hot) is None
    assert det.observe(1.0, hot) is None
    f = det.observe(1.0, hot)                  # 3rd consecutive: fires
    assert f is not None and f.details["kind"] == "exploding_update"
    assert f.details["cadences"] == 3


def test_exploding_update_decaying_tail_never_fires():
    # a zero-init bias sits above threshold for many cadences while
    # ‖w‖ catches up, but the ratio decays ~1/t — that tail must not
    # fire no matter how long it lasts
    det = D.ExplodingUpdateDetector(threshold=0.5, n_cadences=3)
    r = 4.0
    for _ in range(12):
        assert det.observe(1.0, _stats(fc2_bias={"update_ratio": r})) \
            is None
        r *= 0.8                               # >2% decay per cadence
    # a genuinely growing run still fires in exactly n_cadences
    for i, rr in enumerate((0.6, 0.7, 0.9)):
        f = det.observe(1.0, _stats(fc2_bias={"update_ratio": rr}))
        assert (f is None) == (i < 2), (i, f)
    assert f.details["kind"] == "exploding_update"


def test_detector_suite_orders_error_first():
    suite = D.DetectorSuite(window=2, spike_k=4.0)
    clean = _stats(fc1_weight={})
    assert suite.observe(1.0, clean) == []
    assert suite.observe(1.0, clean) == []
    findings = suite.observe(10.0, _stats(fc1_weight={"nonfinite": 1}))
    kinds = [f.details["kind"] for f in findings]
    assert "divergence" in kinds and "loss_spike" in kinds
    assert findings[0].severity == ERROR


def test_health_policy_env_parsing(monkeypatch):
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "rollback")
    assert D.HealthPolicy.from_env().action == "rollback"
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "reformat-disk")
    assert D.HealthPolicy.from_env().action == "warn"   # unknown -> warn
    monkeypatch.delenv("MXTPU_HEALTH_ACTION")
    assert D.HealthPolicy.from_env().action == "warn"


def test_class_label_and_env_arming(monkeypatch):
    assert H.class_label(["fc1_weight"]) == "fc1_weight"
    assert H.class_label(["fc1_weight", "fc1_bias"]) == "fc1*[2]"
    monkeypatch.setenv("MXTPU_HEALTH", "1")
    assert H.armed_by_env()
    monkeypatch.setenv("MXTPU_HEALTH", "off")
    assert not H.armed_by_env()


def test_health_knobs_resolve(monkeypatch):
    from mxtpu.tune import registry as knobs
    assert knobs.resolve_int("health.cadence", floor=1) >= 1
    assert knobs.resolve_int("health.window", floor=2) >= 2
    assert float(knobs.resolve("health.spike_k")) > 0
    monkeypatch.setenv("MXTPU_HEALTH_CADENCE", "4")
    assert knobs.resolve_int("health.cadence", floor=1) == 4


def test_health_accum_fold_exact():
    import jax.numpy as jnp
    acc = H.HealthAccum(2)
    assert acc.pull() is None
    s1 = {"sums": jnp.array([[1., 2., 3., 0.], [4., 5., 6., 1.]]),
          "max": jnp.array([2., 7.])}
    s2 = {"sums": jnp.array([[10., 0., 1., 0.], [1., 1., 1., 0.]]),
          "max": jnp.array([9., 3.])}
    acc.update(s1)
    acc.update(s2)
    tree = acc.pull()
    np.testing.assert_allclose(np.asarray(tree["sums"]),
                               [[11., 2., 4., 0.], [5., 6., 7., 1.]])
    np.testing.assert_allclose(np.asarray(tree["max"]), [9., 7.])
    assert acc.finish() == 2
    assert acc.pull() is None


# ------------------------------------------------- fit-level contracts
def test_fit_health_stats_panel_and_corpus(tmp_path, monkeypatch):
    """Armed fit: finite per-class stats on every surface — gauges, the
    debug_state panel (kept after close, marked disarmed), and corpus
    health rows under the v2 schema."""
    monkeypatch.setenv("MXTPU_CORPUS_DIR", str(tmp_path))
    _corpus.reset()
    try:
        _, _, mod = _fit(health=True)
    finally:
        _corpus.reset()
    assert mod._fused is not None and mod._fused._health_classes
    panel = mx.diagnostics.debug_state().get("training_health")
    assert panel is not None and panel["armed"] is False  # fit closed
    assert panel["cadences"] > 0
    classes = {row["class"]: row for row in panel["classes"]}
    assert classes, panel
    for row in classes.values():
        for stat in H.STATS:
            assert np.isfinite(row[stat]), row
        assert row["nonfinite"] == 0
        assert row["grad_norm"] > 0 and row["weight_norm"] > 0
    # gauges landed for every (class, stat)
    health_series = [m for m in tel.registry().series()
                     if m.name == "train_health"]
    assert len(health_series) >= len(classes) * len(H.STATS)
    some = tel.registry().gauge(
        "train_health", labels={"layer_class": list(classes)[0],
                                "stat": "grad_norm"})
    assert some.value > 0
    # corpus: one health row per cadence, loadable, v2 schema
    rows = [r for r in _corpus.load(str(tmp_path))
            if r.get("row") == "health"]
    assert rows and rows[0]["v"] == _corpus.SCHEMA_VERSION == 2
    assert set(rows[0]["stats"]) == set(classes)
    for s in rows[0]["stats"].values():
        assert set(s) == set(H.STATS)


def test_corpus_health_row_roundtrip_and_torn_tail(tmp_path,
                                                   monkeypatch):
    monkeypatch.setenv("MXTPU_CORPUS_DIR", str(tmp_path))
    _corpus.reset()
    try:
        stats = {"fc1*[2]": {"grad_norm": 0.5, "weight_norm": 2.0,
                             "update_ratio": 0.01, "grad_max": 1.5,
                             "nonfinite": 0}}
        assert _corpus.record_health(3, stats, loss=1.25,
                                     anomalies=["divergence"])
        path = _corpus.corpus_path()
        rows = _corpus.load(str(tmp_path))
        assert len(rows) == 1
        row = rows[0]
        assert row["row"] == "health" and row["cadence"] == 3
        assert row["loss"] == 1.25
        assert row["anomalies"] == ["divergence"]
        assert row["stats"] == stats
        # writer killed mid-append: a torn FINAL line is tolerated and
        # every fully-appended row still loads
        with open(path, "a", encoding="utf-8") as f:
            f.write('{"v":2,"row":"hea')
        assert _corpus.load(str(tmp_path)) == rows
    finally:
        _corpus.reset()


def test_fit_health_adds_zero_sync_points():
    """Cadence exactness: the armed fit's jax.device_get call count
    equals the disarmed fit's — the stat window rides the metric
    accum's one cadence transfer."""
    import jax
    it = _make_iter()
    mod_off = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    mod_on = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    _fit(num_epoch=1, module=mod_off, it=it, health=False)  # warm
    _fit(num_epoch=1, module=mod_on, it=it, health=True)
    real_get, counts = jax.device_get, {"n": 0}

    def counting(*a, **kw):
        counts["n"] += 1
        return real_get(*a, **kw)

    def counted(mod, health):
        counts["n"] = 0
        jax.device_get = counting
        try:
            _fit(num_epoch=1, module=mod, it=it, health=health,
                 force_init=False)
        finally:
            jax.device_get = real_get
        return counts["n"]

    gets_off = counted(mod_off, False)
    gets_on = counted(mod_on, True)
    assert gets_off > 0
    assert gets_on - gets_off == 0, (gets_off, gets_on)


@pytest.mark.slow
def test_fit_health_bf16_parity():
    """bf16 mixed-precision fit: the stats observe the f32 masters —
    finite everywhere, zero nonfinite elements, and the panel stats are
    close to the plain-f32 fit's (same data, same seed)."""
    from mxtpu.compile import pipeline as P
    _, _, _ = _fit(health=True)
    f32_panel = mx.diagnostics.debug_state()["training_health"]
    os.environ["MXTPU_PIPELINE"] = "bf16"
    P.configure(None)
    try:
        _, _, mod = _fit(health=True)
        rep = mod._fused.pipeline_report
        assert rep is not None and "bf16" in rep.applied
    finally:
        os.environ.pop("MXTPU_PIPELINE", None)
        P.configure(None)
    panel = mx.diagnostics.debug_state()["training_health"]
    f32 = {r["class"]: r for r in f32_panel["classes"]}
    b16 = {r["class"]: r for r in panel["classes"]}
    assert set(f32) == set(b16)
    for cls, row in b16.items():
        assert row["nonfinite"] == 0
        for stat in H.STATS:
            assert np.isfinite(row[stat]), (cls, row)
        # masters are f32: the stat magnitudes track the f32 fit's
        assert row["grad_norm"] == pytest.approx(
            f32[cls]["grad_norm"], rel=0.25, abs=1e-4), cls


# ------------------------------------------------- THE rollback gate
def test_health_divergence_rollback_gate(tmp_path, monkeypatch):
    """Injected divergence (an inf batch mid-epoch) -> divergence
    Finding + health_anomalies counter -> the armed rollback policy
    fires the supervisor seam -> the wedged trajectory aborts BEFORE
    its snapshot -> the retry restores the last good generation and
    the fit completes with weights bit-exact against a clean run."""
    monkeypatch.setenv("MXTPU_HEALTH_ACTION", "rollback")
    prefix = str(tmp_path / "ck")
    m_full, w_full, _ = _fit(health=True, metric_sync=1)

    sup = mx.elastic.Supervisor(retries=2, backoff_s=0.0)
    cfg = mx.elastic.ElasticConfig(prefix, every_n_steps=1, sync=True,
                                   supervisor=sup)
    mod = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    metric = M.create(["acc", "ce"])
    attempts = []
    div0 = tel.registry().counter("health_anomalies",
                                  labels={"kind": "divergence"}).value

    def fit_fn(resume):
        attempts.append(resume)
        # attempt 1 feeds an all-inf batch 2; the retry's data is clean
        it = _make_iter(poison_batch=2 if len(attempts) == 1 else None)
        mx.random.seed(11)
        np.random.seed(11)
        mod.fit(it, num_epoch=2, eval_metric=metric, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05,
                                  "momentum": 0.9},
                initializer=mx.initializer.Xavier(), metric_sync=1,
                health=True, elastic=cfg, resume=resume)

    sup.run(fit_fn)

    assert attempts == [False, True]
    assert sup.retries_done == 1
    # the detector fired exactly once (hysteresis) and was surfaced
    div = tel.registry().counter("health_anomalies",
                                 labels={"kind": "divergence"}).value
    assert div == div0 + 1
    pm = mx.diagnostics.last_postmortem()
    assert pm is not None and pm["source"] == "health"
    assert "divergence" in pm["reason"]
    # the wedged step was never snapshotted: the retry replayed the
    # poisoned batch with clean data and the result is bit-exact
    w_sup = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in w_full:
        np.testing.assert_array_equal(w_full[k], w_sup[k], err_msg=k)
    assert m_full["accuracy"] == dict(metric.get_name_value())["accuracy"]


# ------------------------------------------- sanitizer interplay
def test_sanitizer_first_skips_health_postmortem():
    """Order A: the sanitizer already captured this window's nonfinite —
    the health action must NOT emit a duplicate postmortem for the same
    wreckage (and still fires the policy seam)."""
    import jax.numpy as jnp
    from mxtpu.analysis import sanitizer as san
    from mxtpu.analysis.findings import Finding
    from mxtpu.base import NumericsError
    _, _, mod = _fit(num_epoch=1)
    sess = H.HealthSession(mod._fused, detect=True)
    try:
        reg = tel.registry()
        h0 = reg.counter("diag_postmortems",
                         labels={"source": "health"}).value
        # a REAL sanitizer trip between the session's baseline and _act
        san.enable("all")
        try:
            with pytest.raises(NumericsError):
                san.sanitize_tree("fwd_eval",
                                  [jnp.array([float("nan")])])
        finally:
            san.disable()
        f = Finding("health", ERROR, "divergence: test",
                    details={"kind": "divergence"})
        sess._act(f)
        assert reg.counter("diag_postmortems",
                           labels={"source": "health"}).value == h0
        # Order B: baseline refreshed, no new trip -> health owns it
        sess._san_trips = san.trip_count()
        sess._act(f)
        assert reg.counter("diag_postmortems",
                           labels={"source": "health"}).value == h0 + 1
        assert mx.diagnostics.last_postmortem()["source"] == "health"
    finally:
        sess.close()


def test_sanitizer_armed_fit_one_postmortem_per_root_cause():
    """Order A end-to-end: with the sanitizer armed the poisoned step
    trips IN the step (NumericsError), and health — armed in the same
    fit — adds no second postmortem for the same nonfinite."""
    from mxtpu.analysis import sanitizer as san
    from mxtpu.base import NumericsError
    reg = tel.registry()
    s0 = reg.counter("diag_postmortems",
                     labels={"source": "sanitizer"}).value
    h0 = reg.counter("diag_postmortems",
                     labels={"source": "health"}).value
    san.enable("all")
    try:
        with pytest.raises(NumericsError):
            _fit(health=True, it=_make_iter(poison_batch=1),
                 num_epoch=1)
    finally:
        san.disable()
    assert reg.counter("diag_postmortems",
                       labels={"source": "sanitizer"}).value == s0 + 1
    assert reg.counter("diag_postmortems",
                       labels={"source": "health"}).value == h0


# --------------------------------------------- Monitor adapter parity
def _small_module():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[("data", (16, 8))],
             label_shapes=[("softmax_label", (16,))])
    mx.random.seed(3)
    np.random.seed(3)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.0})
    return mod


def _monitor_values(mod, mon):
    mod.install_monitor(mon)
    rng = np.random.RandomState(0)
    db = mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(16, 8).astype("float32"))],
        label=[mx.nd.array(rng.randint(0, 3, (16,)).astype("float32"))])
    mon.tic()
    mod.forward_backward(db)
    mod.update()
    return {name: float(stat.split()[0])
            for _, name, stat in mon.toc()}


def test_monitor_adapter_matches_legacy_values(monkeypatch):
    """Satellite: the default-stat Monitor rides the device tap kernels
    and reports the same abs-mean per tensor the legacy per-op path
    computes (lr=0 so both runs see identical weights)."""
    mod_leg = _small_module()
    monkeypatch.setenv("MXTPU_MONITOR_ADAPTER", "0")
    mon_leg = mx.monitor.Monitor(interval=1, pattern=".*")
    legacy = _monitor_values(mod_leg, mon_leg)
    assert mon_leg._adapter is None
    monkeypatch.delenv("MXTPU_MONITOR_ADAPTER")

    mod_ad = _small_module()
    mod_ad.set_params(*mod_leg.get_params())
    mon_ad = mx.monitor.Monitor(interval=1, pattern=".*")
    adapter = _monitor_values(mod_ad, mon_ad)
    assert mon_ad._adapter is mod_ad    # really the device-tap path
    shared = set(legacy) & set(adapter)
    assert any("fc1" in n for n in shared), (legacy, adapter)
    assert any("softmax" in n for n in shared)
    for name in shared:
        assert adapter[name] == pytest.approx(legacy[name], rel=1e-4), \
            name


def test_monitor_custom_stat_func_keeps_legacy_path():
    mod = _small_module()
    mon = mx.monitor.Monitor(interval=1, pattern=".*",
                             stat_func=lambda x: float(
                                 np.max(np.abs(x.asnumpy()))))
    vals = _monitor_values(mod, mon)
    assert mon._adapter is None and not mon._default_stat
    assert vals and all(np.isfinite(v) for v in vals.values())


def test_monitor_adapter_through_fit_collects_taps():
    """fit(monitor=) with an adapter-eligible monitor: sampled batches
    force a cadence so taps land before toc_print, and device metrics
    stay enabled (the legacy path had to disable them)."""
    mon = mx.monitor.Monitor(interval=2, pattern=".*fc.*")
    delivered = []
    orig = mon._deliver_taps

    def spy(host):
        delivered.append(dict(host))
        orig(host)

    mon._deliver_taps = spy
    _fit(num_epoch=1, monitor=mon)
    assert delivered, "no device taps were delivered through the fit"
    names = set().union(*delivered)
    assert any("fc1" in n for n in names), names
    for host in delivered:
        for v in host.values():
            assert np.isfinite(float(v))
