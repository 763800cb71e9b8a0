"""One span primitive on the profiler's clock (PR 26).

A `telemetry.span` is an event of the JAX profiler's trace; `Module.fit` and
the decode worker have a span per phase, whose durations feed their
histograms; every program built through the compile seam has a stable name.
One trace is recorded on the CPU around a three-step fit and two decode
sessions and read back with `ProfileData`."""
import glob
import os
import threading

import jax
import numpy as np
import pytest

import mxtpu as mx
import mxtpu.diagnostics as diag
from mxtpu import profiler
from mxtpu import telemetry as tel
from mxtpu.compile import named_jit
from mxtpu.obs import trace as obs_trace
from mxtpu.serving import DecodeSession
from mxtpu.serving.decode import attn_decode_fixture, lm_decode_fixture
from mxtpu.telemetry import tracing

FIT_SPANS = ("fit", "fit.epoch", "fit.input", "fit.step", "fit.pace",
             "fit.metric_sync", "fit.callbacks")
DECODE_SPANS = ("decode.admit", "decode.prefill_chunk", "decode.step",
                "decode.gather", "decode.dispatch", "decode.scatter",
                "decode.logits_wait", "decode.sample", "decode.retire")
FIT_HISTS = {"fit_input_wait_ms": "fit.input", "fit_dispatch_ms": "fit.step",
             "fit_sync_wait_ms": "fit.pace",
             "fit_metric_sync_ms": "fit.metric_sync"}
PROGRAMS = ("mxtpu_fused_step", "mxtpu_metric_accum", "mxtpu_arena_gather",
            "mxtpu_arena_scatter", "mxtpu_arena_view", "mxtpu_exec_fwd_eval")


def _fit_module():
    x = np.random.RandomState(0).rand(96, 784).astype("float32")
    y = np.random.RandomState(1).randint(0, 10, 96).astype("float32")
    it = mx.io.NDArrayIter(x, y, batch_size=32)
    mod = mx.mod.Module(mx.models.get_mlp(), context=mx.cpu())
    kw = dict(num_epoch=1, optimizer="sgd", max_in_flight=1,
              batch_end_callback=mx.callback.Speedometer(32, 1))
    return mod, it, kw


def _counts(reg, names):
    return {n: reg.histogram(n).count for n in names}


@pytest.fixture(scope="module")
def timeline(tmp_path_factory):
    """Events of one CPU trace: {line: [(name, start, end, stats)]} of the
    host plane, with the ring's rows, the histogram counts the traced part
    added, and the decode results."""
    mod, it, kw = _fit_module()
    mod.fit(it, **kw)                     # compiles outside the trace
    lm = lm_decode_fixture(seed=0)
    fx = attn_decode_fixture(seed=0)
    slots = DecodeSession(lm[0], lm[1], lm[2], lm[3], buckets=(4,),
                          slot_capacity=2, version_tag="tl-lm",
                          trace_sample=1.0)
    kv = DecodeSession(fx["step_symbol_json"], fx["params"],
                       fx["step_example_shapes"], [], arena="paged",
                       paged=fx, buckets=(2,), slot_capacity=2,
                       prefill_chunk_tokens=2, prefill_buckets=(2,),
                       version_tag="tl-kv", trace_sample=1.0)
    try:
        slots.generate([3, 5], max_new_tokens=2, seed=0, timeout=None)
        kv.generate([5, 6, 7], max_new_tokens=2, seed=0, timeout=None)
        ring = obs_trace.install()
        ring.clear()
        fit0 = _counts(tel, FIT_HISTS)
        step0 = {s: _counts(s.metrics, ("decode_step_ms",))
                 for s in (slots, kv)}
        sums0 = {s: (s.metrics.counter("decode_steps_total").value,
                     s.metrics.counter("decode_slot_steps").value)
                 for s in (slots, kv)}
        tdir = str(tmp_path_factory.mktemp("xplane"))
        jax.profiler.start_trace(tdir)
        mod.fit(it, **kw)
        res = {"slots": slots.generate([3, 5], max_new_tokens=3, seed=0,
                                       timeout=None),
               "kv": kv.generate([5, 6, 7, 8], max_new_tokens=3, seed=0,
                                 timeout=None)}
        jax.profiler.stop_trace()
        out = {"rows": ring.snapshot(), "results": res,
               "fit_hists": {n: tel.histogram(n).count - fit0[n]
                             for n in FIT_HISTS},
               "decode_steps": {
                   k: s.metrics.histogram("decode_step_ms").count
                   - step0[s]["decode_step_ms"]
                   for k, s in (("slots", slots), ("kv", kv))},
               "sums": {k: (s.metrics.counter("decode_steps_total").value
                            - sums0[s][0],
                            s.metrics.counter("decode_slot_steps").value
                            - sums0[s][1],
                            s.metrics.counter("decode_kv_block_steps").value
                            if k == "kv" else None)
                        for k, s in (("slots", slots), ("kv", kv))}}
    finally:
        slots.close()
        kv.close()
    path = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            lines[(i, line.name)] = [
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                 dict(e.stats)) for e in line.events]
    out["lines"] = lines
    return out


def _events(timeline, name):
    return [(key, ev) for key, evs in timeline["lines"].items()
            for ev in evs if ev[0] == name]


@pytest.mark.parametrize("name", FIT_SPANS + DECODE_SPANS)
def test_span_is_an_event_of_the_host_plane(timeline, name):
    got = _events(timeline, name)
    assert got, "%s is not in /host:CPU" % name
    in_ring = [r for r in timeline["rows"] if r["name"] == name]
    assert len(got) == len(in_ring)
    # the ids ride as the annotation's arguments
    stats = got[0][1][3]
    assert stats["span_id"] in {r["span_id"] for r in in_ring}
    assert {"category", "parent_id", "trace_id"} <= set(stats)


def test_fit_step_is_a_step_annotation_with_its_tags(timeline):
    steps = sorted(ev[3]["step_num"] for _, ev in _events(timeline,
                                                         "fit.step"))
    assert steps == [0, 1, 2]
    stats = _events(timeline, "fit.step")[0][1][3]
    assert stats["epoch"] == 0 and "nbatch" in stats
    tags = [r["tags"] for r in timeline["rows"] if r["name"] == "fit.step"]
    assert tags == [{"epoch": 0, "nbatch": k} for k in range(3)]


@pytest.mark.parametrize("child,parent", [
    ("fit.epoch", "fit"), ("fit.input", "fit.epoch"),
    ("fit.step", "fit.epoch"), ("fit.pace", "fit.epoch"),
    ("fit.metric_sync", "fit.epoch"), ("fit.callbacks", "fit.epoch"),
    ("decode.gather", ("decode.step", "decode.prefill_chunk")),
    ("decode.dispatch", ("decode.step", "decode.prefill_chunk")),
    ("decode.scatter", ("decode.step", "decode.prefill_chunk")),
    ("decode.logits_wait", ("decode.step", "decode.prefill_chunk")),
    ("decode.sample", ("decode.step", "decode.prefill_chunk")),
    ("decode.retire", ("decode.sample",)),
])
def test_children_lie_inside_their_parents(timeline, child, parent):
    parents = (parent,) if isinstance(parent, str) else parent
    by_id = {r["span_id"]: r for r in timeline["rows"]}
    kids = _events(timeline, child)
    assert kids
    for key, (_, s, e, stats) in kids:
        up = by_id[stats["parent_id"]]
        assert up["name"] in parents
        # on the trace's clock too, on the same thread's line
        holds = [p for k, p in _events(timeline, up["name"])
                 if k == key and p[3]["span_id"] == up["span_id"]]
        assert len(holds) == 1 and holds[0][1] <= s and e <= holds[0][2]


@pytest.mark.parametrize("program", PROGRAMS)
def test_programs_have_stable_names(timeline, program):
    # JAX's own dispatch events on the host name the jitted function
    called = {ev[0] for evs in timeline["lines"].values() for ev in evs
              if ev[0].startswith("PjitFunction(")}
    recorded = {r["name"] for r in diag.programs()}
    assert "jit_" + program in recorded
    if program != "mxtpu_arena_gather":      # slot arena: compiled call
        assert any(program in c for c in called), sorted(called)
    assert "jit_" + program in diag.program_table()


def test_named_jit_names_the_xla_module():
    fn = named_jit("probe_double", lambda x: x * 2)
    text = fn.lower(np.ones(3, np.float32)).compile().as_text()
    assert text.startswith("HloModule jit_mxtpu_probe_double")
    from mxtpu.compile import pipeline
    assert pipeline.program_name(fn) == "jit_mxtpu_probe_double"
    assert pipeline.program_name(jax.jit(lambda x: x)) == ""


@pytest.mark.parametrize("hist", sorted(FIT_HISTS))
def test_fit_histograms_count_their_spans(timeline, hist):
    spans = [r for r in timeline["rows"] if r["name"] == FIT_HISTS[hist]]
    assert timeline["fit_hists"][hist] == len(spans) > 0
    # 3 batches: 4 next() calls, 3 steps, 3 cadence syncs (Speedometer 1)
    want = {"fit.input": 4, "fit.step": 3, "fit.metric_sync": 3}
    if FIT_HISTS[hist] in want:
        assert len(spans) == want[FIT_HISTS[hist]]


@pytest.mark.parametrize("kind", ["slots", "kv"])
def test_decode_step_ms_is_the_sum_of_its_phase_spans(timeline, kind):
    rows = timeline["rows"]
    steps = [r for r in rows if r["name"] == "decode.step"
             and ("kv_live" in r["tags"]) == (kind == "kv")]
    assert timeline["decode_steps"][kind] == len(steps) > 0
    n_steps, slot_steps, kv_steps = timeline["sums"][kind]
    assert n_steps == len(steps)
    assert slot_steps == sum(r["tags"]["n"] for r in steps)
    if kind == "kv":
        assert kv_steps >= sum(r["tags"]["kv_live"] for r in steps) > 0
    for st in steps:
        kids = [r for r in rows if r["parent_id"] == st["span_id"]]
        assert [k["name"] for k in sorted(kids, key=lambda r: r["t0_ns"])] \
            == ["decode.gather", "decode.dispatch", "decode.scatter",
                "decode.logits_wait", "decode.sample"]
        assert st["tags"]["bucket"] >= st["tags"]["n"] >= 1


@pytest.mark.parametrize("kind", ["slots", "kv"])
def test_exemplar_events_carry_the_span_that_served_them(timeline, kind):
    events = timeline["results"][kind]["trace"]
    units = {r["span_id"]: r["name"] for r in timeline["rows"]
             if r["name"] in ("decode.step", "decode.prefill_chunk")}
    stamped = [e for e in events if "span" in e]
    assert stamped and all(e["span"] in units for e in stamped)
    for e in events:
        if e["event"] in ("step", "token", "retire", "prefill_chunk"):
            assert "span" in e, e
        if e["event"] == "step":
            assert units[e["span"]] == "decode.step"
        if e["event"] == "prefill_chunk":
            assert units[e["span"]] == "decode.prefill_chunk"
        if e["event"] in ("enqueue", "admit"):
            assert "span" not in e
    # a token's step is the one whose span it carries
    steps = {e["span"] for e in events if e["event"] == "step"}
    tokens = [e for e in events if e["event"] == "token"]
    assert all(t["span"] in steps or units[t["span"]]
               == "decode.prefill_chunk" for t in tokens)


def test_span_reads_integer_nanoseconds_once_an_end():
    with tracing.span("tl.clock", tags={"k": 1}) as sp:
        pass
    assert isinstance(sp.t0_ns, int) and isinstance(sp.t1_ns, int)
    assert sp.t1_ns >= sp.t0_ns > 10**18
    assert sp.duration_ms == (sp.t1_ns - sp.t0_ns) / 1e6
    row = [r for r in obs_trace.ring().snapshot()
           if r["name"] == "tl.clock"][-1]
    assert (row["t0_ns"], row["t1_ns"]) == (sp.t0_ns, sp.t1_ns)


def test_flight_ring_formats_a_span_end_when_it_is_read():
    rec = diag.recorder()
    with tracing.span("tl.flight") as sp:
        pass
    raw = [e for e in rec._ring if e is not None and e[3] == "span_end"
           and e[4] == "tl.flight"][-1]
    assert raw[5] == (sp.span_id, sp.t1_ns - sp.t0_ns)     # not a string
    shown = [e for e in rec.snapshot() if e["kind"] == "span_end"
             and e["name"] == "tl.flight"][-1]
    assert shown["detail"] == "%d %.3fms" % (sp.span_id, sp.duration_ms)


def test_telemetry_off_and_no_profiler_is_still_the_noop_span():
    assert not profiler.is_running()
    tel.set_enabled(False)
    try:
        sp = tel.span("tl.off")
        assert sp is tracing._NULL and sp.duration_ms == 0.0
        with sp as inner:
            assert tracing.current_span() is None and inner is sp
        # a caller that reads the duration as its measurement asks for it
        with tel.span("tl.off.always", always=True) as real:
            pass
        assert isinstance(real, tracing.Span) and real.t1_ns >= real.t0_ns
        # the null histogram answers a snapshot reader with nothing seen
        h = tel.histogram("fit_dispatch_ms")
        assert h.snapshot() == (0, 0.0, 0.0, 0.0, []) and h.bounds == ()
    finally:
        tel.set_enabled(True)
    assert isinstance(tel.span("tl.on"), tracing.Span)


def test_spans_of_two_threads_keep_their_own_lines():
    seen = {}

    def work(tag):
        with tel.span("tl.thread." + tag) as sp:
            seen[tag] = (sp.parent_id, threading.get_ident())

    with tel.span("tl.thread.main"):
        t = threading.Thread(target=work, args=("other",))
        t.start()
        t.join(10)
        assert not t.is_alive()
        work("same")
    assert seen["other"][0] == 0 and seen["same"][0] != 0
    rows = {r["name"]: r for r in obs_trace.ring().snapshot()
            if r["name"].startswith("tl.thread.")}
    assert rows["tl.thread.other"]["thread"] == seen["other"][1]
    assert rows["tl.thread.same"]["thread"] == threading.get_ident()


def test_profiler_writes_its_jax_trace_beside_the_configured_file(tmp_path):
    dump = str(tmp_path / "run" / "profile.json")
    os.makedirs(os.path.dirname(dump))
    profiler.set_config(mode="symbolic", filename=dump)
    assert profiler.xplane_dir() == str(tmp_path / "run" / "profile_xplane")
    profiler.set_state("run")
    try:
        with tel.span("tl.profiled"):
            pass
    finally:
        profiler.set_state("stop")
        profiler.set_config(filename="profile.json")
    found = glob.glob(os.path.join(str(tmp_path), "run", "profile_xplane",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    assert len(found) == 1
    names = {e.name for plane in
             jax.profiler.ProfileData.from_file(found[0]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert "tl.profiled" in names
    profiler.clear()


@pytest.mark.parametrize("fused", [True, False])
def test_device_state(fused):
    mod, it, kw = _fit_module()
    if fused:
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        assert mod._fused is not None
    else:
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params()
    st = mod.device_state()
    assert set(st) == {"params", "aux", "opt_state", "dtypes"}
    args, aux = mod.get_params()
    assert set(st["params"]) == set(args) and set(st["aux"]) == set(aux)
    for n, v in st["params"].items():
        assert isinstance(v, jax.Array)
        assert st["dtypes"][n] == str(v.dtype) == "float32"
        np.testing.assert_array_equal(np.asarray(v), args[n].asnumpy())
    if fused:
        assert set(st["opt_state"]) == set(mod._fused.trainable)
        leaves = jax.tree.leaves(st["opt_state"])
        assert leaves and all(isinstance(x, jax.Array) for x in leaves)
        # a fresh dict: dropping a name does not touch the step's state
        st["params"].clear()
        assert mod.device_state()["params"]
    else:
        assert st["opt_state"] is None


def test_device_state_needs_bind():
    mod, _, _ = _fit_module()
    with pytest.raises(AssertionError, match="bind"):
        mod.device_state()
