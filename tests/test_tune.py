"""mxtpu.tune: the knob registry and its one precedence rule.

* with the environment clean every knob resolves to its declared
  default;
* precedence ``default < environment < explicit argument``, for every
  declared knob and at the call sites that resolve them (fit, serving,
  elastic, the compile pipeline);
* every declared knob is in docs/tune.md.
"""
import os

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import tune

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    """No knob's environment name is set while a test of this file runs,
    so the suite's own environment never leaks into an assertion."""
    for k in tune.knobs():
        if k.env:
            monkeypatch.delenv(k.env, raising=False)


def _mlp_module_and_iter(steps=4, batch=16, seed=0):
    from mxtpu.models import mlp
    rng = np.random.RandomState(seed)
    data = rng.rand(batch * steps, 784).astype(np.float32)
    label = rng.randint(0, 10, (batch * steps,)).astype(np.float32)
    it = mx.io.NDArrayIter(data, label, batch, label_name="softmax_label")
    mod = mx.mod.Module(mlp.get_symbol(num_classes=10), context=mx.cpu(0))
    return mod, it


# ------------------------------------------------------------------ registry
def test_registry_defaults_are_the_hand_picked_constants():
    """With no env, every knob resolves to the constant its call site
    used to inline."""
    expect = {"fit.max_in_flight": 2, "fit.metric_sync": None,
              "fit.device_metrics": True, "fit.device_prefetch": False,
              "fit.remat": "none",
              "serving.max_in_flight": 2, "serving.refill_watermark": None,
              "serving.max_queue": 256, "serving.max_delay_ms": 5.0,
              "serving.queue_wait_budget_ms": None,
              "serving.watchdog_shed_s": 10.0,
              "serving.min_mem_headroom": 0.03,
              "serving.queue_frac_shed": 0.95,
              "serving.degrade_frac": 0.5, "serving.warm_versions": 4,
              "decode.slot_capacity": 8,
              "decode.max_new_tokens_default": 32,
              "decode.join_watermark": 4,
              "elastic.every_n_steps": 0, "elastic.epoch_period": 1,
              "elastic.keep": 2, "compile.pipeline": ""}
    for name, want in expect.items():
        assert tune.resolve(name) == want, name


def _case(knob):
    """For a knob's kind: the Python type a resolved value has, a string
    an operator would put in the environment (never the declared
    default), what it parses to, and an explicit argument that differs
    from that."""
    if knob.kind == "bool":
        return (bool, "0" if knob.default else "1", not knob.default,
                knob.default)
    return {"int": (int, "7", 7, 9),
            "float": (float, "123.5", 123.5, 77.25),
            "str": (str, "bf16", "bf16", "layout"),
            }[knob.kind.replace("_or_none", "")]


@pytest.mark.parametrize("name", [k.name for k in tune.knobs()])
def test_knob_default_env_explicit(name, monkeypatch):
    """default < environment < explicit argument, for every declared
    knob: the one rule of ``tune.resolve``."""
    knob = tune.get_knob(name)
    typ, raw, parsed, explicit = _case(knob)
    assert parsed != knob.default and explicit != parsed

    got = tune.resolve(name)
    assert got == knob.default
    assert got is None or type(got) is typ

    if knob.env:
        monkeypatch.setenv(knob.env, raw)
        got = tune.resolve(name)
        assert got == parsed and type(got) is typ
        # an empty string reads as unset (not a crash, not a zero)
        monkeypatch.setenv(knob.env, "")
        assert tune.resolve(name) == knob.default
        monkeypatch.setenv(knob.env, raw)

    got = tune.resolve(name, explicit=explicit)
    assert got == explicit and type(got) is typ


def test_registry_precedence_default_env_explicit(monkeypatch):
    # default
    assert tune.resolve("fit.max_in_flight") == 2
    assert tune.resolve_int("fit.max_in_flight", floor=4) == 4
    # default < env
    monkeypatch.setenv("MXTPU_FIT_INFLIGHT", "6")
    assert tune.resolve("fit.max_in_flight") == 6
    # env < explicit
    assert tune.resolve("fit.max_in_flight", explicit=3) == 3
    assert tune.resolve_int("fit.max_in_flight", explicit=0, floor=1) == 1
    # an "auto" knob stays None through resolve_int
    assert tune.resolve_int("fit.metric_sync") is None


def test_registry_version_is_stable_and_knob_sensitive():
    v1 = tune.registry_version()
    assert v1 == tune.registry_version()
    assert len(v1) == 12
    # every catalogued knob belongs to a known subsystem
    subs = {k.subsystem for k in tune.knobs()}
    assert subs == {"fit", "serving", "decode", "elastic", "compile",
                    "quant", "health"}


def test_bool_coercion_matches_env_contract():
    k = tune.get_knob("fit.device_metrics")
    assert k.coerce("0") is False
    assert k.coerce("1") is True
    assert k.coerce(False) is False


def test_unknown_knob_rejected():
    with pytest.raises(KeyError, match="unknown knob"):
        tune.resolve("fit.no_such_knob")


# ------------------------------------------------------- fit integration
def test_fit_resolves_knobs_with_precedence(monkeypatch):
    mod, it = _mlp_module_and_iter()
    # env beats default
    monkeypatch.setenv("MXTPU_FIT_INFLIGHT", "3")
    monkeypatch.setenv("MXTPU_FIT_METRIC_SYNC", "8")
    mod.fit(it, num_epoch=1, eval_metric="acc")
    assert mod._fit_knobs["fit.max_in_flight"] == 3
    assert mod._fit_knobs["fit.metric_sync"] == 8
    # explicit beats env
    it.reset()
    mod.fit(it, num_epoch=1, eval_metric="acc", max_in_flight=1,
            metric_sync=2, force_init=False)
    assert mod._fit_knobs["fit.max_in_flight"] == 1
    assert mod._fit_knobs["fit.metric_sync"] == 2


def test_fit_uses_defaults():
    from mxtpu import callback as cb
    mod, it = _mlp_module_and_iter(steps=2)
    mod.fit(it, num_epoch=1, eval_metric="acc")
    assert mod._fit_knobs["fit.max_in_flight"] == 2
    assert mod._fit_knobs["fit.device_metrics"] is True
    assert mod._fit_knobs["fit.device_prefetch"] is False
    assert mod._fit_knobs["fit.metric_sync"] == 0   # no batch callbacks
    # the callback-derived default: every Speedometer window boundary is
    # a sync batch (gcd of the meters' frequents)
    it.reset()
    mod.fit(it, num_epoch=1, eval_metric="acc", force_init=False,
            batch_end_callback=[cb.Speedometer(16, frequent=10, log=False),
                                cb.Speedometer(16, frequent=4, log=False)])
    assert mod._fit_knobs["fit.metric_sync"] == 2


# --------------------------------------------------- serving integration
def _serving_fixture():
    from mxtpu.models.serving_fixtures import get_fixture
    return get_fixture("mlp", seed=0)


def test_serving_session_resolves_knobs_with_precedence(monkeypatch):
    sym_json, params, shapes = _serving_fixture()
    monkeypatch.setenv("MXTPU_SERVING_INFLIGHT", "6")
    monkeypatch.setenv("MXTPU_SERVING_MAX_QUEUE", "64")
    monkeypatch.setenv("MXTPU_SERVING_WATERMARK", "4")
    monkeypatch.setenv("MXTPU_SERVING_QUEUE_WAIT_BUDGET_MS", "321.0")
    with mx.serving.ServingSession(sym_json, params, shapes,
                                   buckets=(1, 8), warmup=False) as s:
        assert s.max_in_flight == 6           # env beats default
        assert s.batcher.max_queue == 64
        assert s.batcher.refill_watermark == 4
        assert s._admission.queue_wait_budget_ms == 321.0
    with mx.serving.ServingSession(sym_json, params, shapes,
                                   buckets=(1, 8), warmup=False,
                                   max_in_flight=1, max_queue=32,
                                   refill_watermark=2,
                                   queue_wait_budget_ms=99.0) as s:
        assert s.max_in_flight == 1           # explicit beats env
        assert s.batcher.max_queue == 32
        assert s.batcher.refill_watermark == 2
        assert s._admission.queue_wait_budget_ms == 99.0


def test_serving_session_defaults():
    sym_json, params, shapes = _serving_fixture()
    with mx.serving.ServingSession(sym_json, params, shapes,
                                   buckets=(1, 8), warmup=False) as s:
        assert s.max_in_flight == 2
        assert s.batcher.max_queue == 256
        assert s.batcher.max_delay == pytest.approx(0.005)
        # no cost rows without warmup: the structural watermark default
        assert s.batcher.refill_watermark == 8 // 4


# --------------------------------------------------- elastic integration
def test_elastic_config_resolves_knobs(tmp_path, monkeypatch):
    ec = mx.elastic.ElasticConfig(str(tmp_path / "ck"))
    assert ec.every_n_steps == 0 and ec.keep == 2 and ec.epoch_period == 1
    monkeypatch.setenv("MXTPU_ELASTIC_KEEP", "7")
    monkeypatch.setenv("MXTPU_ELASTIC_EVERY_STEPS", "50")
    ec = mx.elastic.ElasticConfig(str(tmp_path / "ck"))
    assert ec.keep == 7 and ec.every_n_steps == 50   # env beats default
    ec = mx.elastic.ElasticConfig(str(tmp_path / "ck"), keep=3)
    assert ec.keep == 3                        # explicit beats env
    assert ec.every_n_steps == 50              # env only


# --------------------------------------------------- compile integration
def test_compile_pipeline_knob(monkeypatch):
    from mxtpu.compile import pipeline
    try:
        assert pipeline.configure(None) == ()          # the default
        monkeypatch.setenv("MXTPU_PIPELINE", "bf16, layout")
        assert pipeline.configure(None) == ("bf16", "layout")
        # set-but-empty and the "off" spellings read as the empty pipeline
        for off in ("", "0", "none", "off"):
            monkeypatch.setenv("MXTPU_PIPELINE", off)
            assert pipeline.configure(None) == ()
        # an explicit configure() beats the environment; a scope restores
        monkeypatch.setenv("MXTPU_PIPELINE", "layout")
        assert pipeline.configure(["bf16"]) == ("bf16",)
        with pipeline.pipeline_scope(()):
            assert pipeline.configured() == ()
        assert pipeline.configured() == ("bf16",)
    finally:
        monkeypatch.delenv("MXTPU_PIPELINE", raising=False)
        pipeline.configure(None)   # back to the env-derived (empty) one


# ---------------------------------------------------------------------- docs
def test_catalog_documented_in_docs():
    """Every declared knob appears in docs/tune.md (the catalog table
    there is generated from this registry — rot guard)."""
    path = os.path.join(REPO, "docs", "tune.md")
    text = open(path).read()
    missing = [k.name for k in tune.knobs() if "`%s`" % k.name not in text]
    assert not missing, "knobs missing from docs/tune.md: %s" % missing


def test_catalog_table_renders():
    table = tune.catalog_table()
    assert table.startswith("| knob |")
    assert "`fit.max_in_flight`" in table
    # header, rule and a row a declared knob
    assert len(table.splitlines()) == 2 + len(tune.knobs())
