"""Observability PR (mxtpu/obs): trace timeline export, per-token
decode latency attribution, and the persistent measurement corpus.

Tier-1 (CPU, `not slow`). The PR's acceptance gates, deterministic per
the repo convention:

* **trace schema** — a fit + a streaming decode produce a Perfetto-clean
  trace.json: every span an "X" slice on a named per-thread track
  ("M" metadata), cross-thread parent links as "s"/"f" flow pairs
  joining request → batch → pool.run;
* **retire-time latency** — with an injected frozen clock,
  `decode_ttft_ms`/`decode_tbt_ms` read exactly 0.0 even when the token
  stream is drained only after the clock advances: the stamps happen at
  token RETIRE, not HTTP flush — including multi-chunk chunked-prefill
  TTFT;
* **exemplar sampling** — the seeded sampler makes which requests carry
  a structured timeline a pure function of the enqueue ordinal, so
  capture is asserted exactly, not probabilistically;
* **corpus** — N builds + M service rows round-trip to exactly N+M
  schema-valid rows, a writer killed mid-append leaves a tolerated torn
  tail, and `summarize()` carries the ServiceLine fitted to the
  per-bucket means.
"""
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxtpu as mx
import mxtpu.diagnostics as diag
from mxtpu import telemetry as tel
from mxtpu.obs import corpus as obs_corpus
from mxtpu.obs import trace as obs_trace
from mxtpu.obs import trace_export
from mxtpu.obs.sampler import TraceSampler
from mxtpu.serving import DecodeSession, ServingHTTPServer
from mxtpu.serving.decode import attn_decode_fixture, lm_decode_fixture
from mxtpu.telemetry import tracing as _tracing

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# shared fixtures, one version tag per weight set (process warm cache:
# the suite pays each step-program compile once for this module)
_LM = {}
_ATTN = {}


def _lm(seed=0):
    if seed not in _LM:
        _LM[seed] = lm_decode_fixture(seed=seed)
    return _LM[seed]


def _attn(seed=0):
    if seed not in _ATTN:
        _ATTN[seed] = attn_decode_fixture(seed=seed)
    return _ATTN[seed]


def _session(seed=0, **kwargs):
    sym, params, shapes, state_names, _ = _lm(seed)
    kwargs.setdefault("buckets", (4,))
    kwargs.setdefault("slot_capacity", 2)
    kwargs.setdefault("version_tag", "to-v%d" % seed)
    return DecodeSession(sym, params, shapes, state_names, **kwargs)


def _kv_session(seed=0, **kwargs):
    fx = _attn(seed)
    kwargs.setdefault("buckets", (2,))
    kwargs.setdefault("slot_capacity", 2)
    kwargs.setdefault("prefill_chunk_tokens", 2)
    kwargs.setdefault("prefill_buckets", (2,))
    kwargs.setdefault("version_tag", "to-kv-v%d" % seed)
    return DecodeSession(fx["step_symbol_json"], fx["params"],
                         fx["step_example_shapes"], [], arena="paged",
                         paged=fx, **kwargs)


class FakeClock:
    """Injectable session clock (seconds)."""

    def __init__(self, t=100.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _hist(sess, name, **labels):
    return sess.metrics.histogram(name, labels=labels or None)


# ------------------------------------------------------------ span ring
def test_span_ring_records_finished_spans():
    ring = obs_trace.install()
    assert ring is not None and obs_trace.trace_enabled()
    ring.clear()
    with _tracing.span("obs.test.outer", category="test",
                       tags={"k": 1}) as outer:
        with _tracing.span("obs.test.inner", category="test"):
            pass
    rows = [r for r in ring.snapshot()
            if r["name"].startswith("obs.test.")]
    assert [r["name"] for r in rows] == ["obs.test.inner",
                                         "obs.test.outer"]
    inner, out = rows
    assert inner["parent_id"] == out["span_id"]
    assert inner["trace_id"] == out["trace_id"] == outer.trace_id
    assert out["t1_ns"] >= out["t0_ns"] > 0 and isinstance(out["t0_ns"], int)
    assert out["tags"] == {"k": 1}
    assert inner["thread"] == threading.get_ident()


def test_span_ring_bounded():
    ring = obs_trace.SpanRing(16)
    with _tracing.span("obs.bound") as sp:
        pass
    for _ in range(100):
        ring.record(sp)
    assert len(ring) == 16
    assert ring.snapshot()[-1]["seq"] == 99
    ring.clear()
    assert len(ring) == 0


def test_diagnostics_toggle_rides_trace():
    assert obs_trace.trace_enabled()
    diag.set_enabled(False)
    try:
        assert not obs_trace.trace_enabled()
        n0 = len(obs_trace.ring())
        with _tracing.span("obs.disabled"):
            pass
        assert len(obs_trace.ring()) == n0   # sink unhooked
    finally:
        diag.set_enabled(True)
    assert obs_trace.trace_enabled()


# ------------------------------------------------------- export schema
def _assert_perfetto_clean(body):
    """The schema contract docs/observability.md declares."""
    doc = json.loads(body)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    tids_named = set()
    for e in events:
        assert e["ph"] in ("X", "i", "M", "s", "f"), e
        if e["ph"] == "M":
            assert e["name"] in ("process_name", "thread_name")
            if e["name"] == "thread_name":
                tids_named.add(e["tid"])
            continue
        assert isinstance(e["ts"], (int, float))
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["name"] and "args" in e
            assert "span_id" in e["args"]
        if e["ph"] == "i":
            assert e["s"] == "t"
        if e["ph"] == "f":
            assert e["bp"] == "e" and "id" in e
    # every thread a slice/instant lands on has a named track
    used_tids = {e["tid"] for e in events
                 if e["ph"] in ("X", "i") and "tid" in e}
    assert used_tids <= tids_named
    return doc


def test_trace_export_cross_thread_flow_pair():
    obs_trace.install().clear()
    with _tracing.span("obs.flow.parent", category="test") as parent:
        captured = _tracing.current_span()

        def worker():
            with _tracing.span("obs.flow.child", category="test",
                               parent=captured):
                pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    doc = _assert_perfetto_clean(trace_export.dumps())
    events = doc["traceEvents"]
    child = [e for e in events
             if e["ph"] == "X" and e["name"] == "obs.flow.child"][0]
    par = [e for e in events
           if e["ph"] == "X" and e["name"] == "obs.flow.parent"][0]
    assert child["tid"] != par["tid"]
    assert child["args"]["parent_id"] == par["args"]["span_id"]
    flows = [e for e in events if e["ph"] in ("s", "f")
             and e["id"] == child["args"]["span_id"]]
    assert sorted(e["ph"] for e in flows) == ["f", "s"]
    s_ev = [e for e in flows if e["ph"] == "s"][0]
    f_ev = [e for e in flows if e["ph"] == "f"][0]
    assert s_ev["tid"] == par["tid"] and f_ev["tid"] == child["tid"]


def test_trace_export_merges_flight_instants():
    obs_trace.install().clear()
    diag.record("obstest", "ping", "detail=1")
    doc = _assert_perfetto_clean(trace_export.dumps())
    inst = [e for e in doc["traceEvents"]
            if e["ph"] == "i" and e["name"] == "obstest:ping"]
    assert inst and inst[0]["args"]["detail"] == "detail=1"


# --------------------------------------- decode attribution + timeline
def test_decode_streaming_trace_and_sampled_exemplar():
    """A streaming decode run produces (a) per-request exemplar
    timelines in causal order, (b) decode flight events, (c) a
    Perfetto-clean merged export with decode-thread tracks."""
    obs_trace.install().clear()
    with _session(trace_sample=1.0) as sess:
        res = sess.generate([3, 5], max_new_tokens=4, seed=0,
                            timeout=60)
        events = [e["event"] for e in res["trace"]]
        assert events[0] == "enqueue" and events[-1] == "retire"
        assert "admit" in events and events.count("token") == 4
        assert events.index("admit") < events.index("token")
        ts = [e["t"] for e in res["trace"]]
        assert ts == sorted(ts)
        assert sess.metrics.counter("decode_trace_sampled").value == 1
        panel = sess.debug_panel()["trace_sample"]
        assert panel["rate"] == 1.0 and panel["sampled"] == 1
        assert panel["held"] == 1
        # attribution series populated
        assert _hist(sess, "decode_ttft_ms").count == 1
        assert _hist(sess, "decode_tbt_ms").count == 3
        assert _hist(sess, "decode_phase_ms", phase="admission").count == 1
        assert _hist(sess, "decode_phase_ms", phase="step").count >= 4
        assert _hist(sess, "decode_phase_ms", phase="retire").count == 1
    flight = diag.recorder().snapshot(limit=2048)
    kinds = {(e["kind"], e["name"]) for e in flight}
    assert ("decode", "admit") in kinds
    assert ("decode", "step") in kinds
    assert ("decode", "token") in kinds
    doc = _assert_perfetto_clean(trace_export.dumps())
    xnames = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "warmup" in xnames    # the decode session's own spans landed


def test_decode_sampler_zero_rate_and_determinism():
    with _session(trace_sample=0.0) as sess:
        res = sess.generate([2], max_new_tokens=2, seed=1, timeout=60)
        assert "trace" not in res
        assert sess.metrics.counter("decode_trace_sampled").value == 0
    a = TraceSampler(rate=0.5, seed=7)
    b = TraceSampler(rate=0.5, seed=7)
    picks = [a.sampled(i) for i in range(1000)]
    assert picks == [b.sampled(i) for i in range(1000)]   # pure fn
    frac = sum(picks) / 1000.0
    assert 0.35 < frac < 0.65
    assert picks != [TraceSampler(rate=0.5, seed=8).sampled(i)
                     for i in range(1000)]                # seed matters
    assert all(TraceSampler(rate=1.0).sampled(i) for i in range(10))
    assert not any(TraceSampler(rate=0.0).sampled(i) for i in range(10))


def test_env_trace_sample_spec(monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_SAMPLE", "0.25:42")
    s = TraceSampler()
    assert s.rate == 0.25 and s.seed == 42
    monkeypatch.setenv("MXTPU_TRACE_SAMPLE", "garbage")
    s = TraceSampler()
    assert s.rate == 0.0


def test_injected_clock_ttft_tbt_stamped_at_retire():
    """The retire-time contract: with the session clock FROZEN through
    the whole decode, TTFT and every TBT read exactly 0.0 — and stay
    0.0 when the stream is drained only AFTER the clock has advanced.
    If the stamps happened at HTTP flush/stream read, the advanced
    clock would leak in."""
    clk = FakeClock(100.0)
    with _session(clock=clk, trace_sample=1.0) as sess:
        item = sess.generate_async([3, 5], max_new_tokens=4, seed=0,
                                   timeout=None, stream=True)
        res = item.wait(60)
        # tokens fully retired; NOW advance the clock, then drain
        clk.advance(50.0)
        drained = list(item.stream.events(timeout=30))
        assert any("done" in ev for ev in drained)
        assert len([ev for ev in drained if "token" in ev]) == 4
        ttft = _hist(sess, "decode_ttft_ms")
        tbt = _hist(sess, "decode_tbt_ms")
        assert ttft.count == 1 and ttft.max == 0.0
        assert tbt.count == 3 and tbt.max == 0.0
        adm = _hist(sess, "decode_phase_ms", phase="admission")
        assert adm.count == 1 and adm.max == 0.0    # same frozen clock
        # exemplar timeline carries the frozen stamp, not drain time
        assert all(e["t"] == 100.0 for e in res["trace"])


def test_injected_clock_chunked_prefill_multi_chunk_ttft():
    """kv layout: a prompt spanning >1 prefill chunk still stamps TTFT
    at the final chunk's token retire — 0.0 under a frozen clock, with
    ≥2 chunk dispatches recorded (so the multi-chunk path, not a
    single-shot prefill, produced the first token)."""
    clk = FakeClock(7.0)
    with _kv_session(clock=clk, trace_sample=1.0) as sess:
        res = sess.generate([5, 6, 7, 8], max_new_tokens=2, seed=0,
                            timeout=None)
        assert len(res["tokens"]) == 2
        assert sess.metrics.counter("decode_prefill_chunks").value >= 2
        ttft = _hist(sess, "decode_ttft_ms")
        assert ttft.count == 1 and ttft.max == 0.0
        pre = _hist(sess, "decode_phase_ms", phase="prefill")
        assert pre.count >= 2          # perf_counter-based, real time
        marks = [e["event"] for e in res["trace"]]
        assert marks.count("prefill_chunk") >= 2
        assert "block_alloc" in marks  # paged growth hit the timeline


# ------------------------------------------------------- HTTP endpoint
def test_debug_trace_endpoint_and_top_trace_out(tmp_path):
    obs_trace.install().clear()
    sess = _session(trace_sample=1.0)
    server = ServingHTTPServer(None, decode=sess, port=0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        url = server.endpoint
        body = json.dumps({"prompt": [3, 5], "max_new_tokens": 3,
                           "seed": 1}).encode()
        req = urllib.request.Request(
            url + "/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        assert len(out["tokens"]) == 3
        with urllib.request.urlopen(url + "/debug/trace",
                                    timeout=30) as r:
            assert r.headers["Content-Type"] == "application/json"
            doc = _assert_perfetto_clean(r.read())
        xnames = {e["name"] for e in doc["traceEvents"]
                  if e["ph"] == "X"}
        assert "warmup" in xnames          # the session's own spans
        inames = {e["name"] for e in doc["traceEvents"]
                  if e["ph"] == "i"}
        assert "decode:step" in inames     # decode flight instants
        assert "decode:token" in inames
        # debug_state advertises the ring fill
        with urllib.request.urlopen(url + "/debug/state",
                                    timeout=30) as r:
            state = json.loads(r.read())
        assert state["trace"]["enabled"] is True
        assert state["trace"]["spans"] > 0
        # mxtpu_top --trace-out fetches the same body to a file
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        try:
            import mxtpu_top
            dest = str(tmp_path / "trace.json")
            rc = mxtpu_top.main([url, "--trace-out", dest])
            assert rc == 0
            with open(dest) as f:
                _assert_perfetto_clean(f.read())
            # the decode panel renders the new attribution lines
            metrics, state = mxtpu_top.snapshot(url)
            frame = "\n".join(mxtpu_top.render(metrics, state))
            assert "tbt" in frame and "decode phases:" in frame
            assert "sampled traces" in frame
        finally:
            sys.path.remove(os.path.join(ROOT, "tools"))
    finally:
        server.shutdown()
        sess.close()


# -------------------------------------------------------------- corpus
def _build_row(i):
    return {"id": i, "kind": "fused_step", "owner": "Module",
            "compile_ms": 12.5, "flops": 1e6 * i,
            "bytes_accessed": 2e6, "argument_bytes": 1024,
            "output_bytes": 256, "temp_bytes": 0, "n_devices": 1,
            "precision": "f32", "transforms": ["fuse_opt"]}


def test_corpus_round_trip_exact_rows(tmp_path, monkeypatch):
    d = str(tmp_path)
    monkeypatch.setenv("MXTPU_CORPUS_DIR", d)
    obs_corpus.reset()
    N, M = 3, 5
    for i in range(N):
        assert obs_corpus.record_build(_build_row(i))
    for j in range(M):
        assert obs_corpus.record_service(
            "serving", 10.0 + j, bucket=8 if j % 2 else 1, rows=4)
    obs_corpus.reset()
    rows = obs_corpus.load(d)
    assert len(rows) == N + M
    builds = [r for r in rows if r["row"] == "build"]
    services = [r for r in rows if r["row"] == "service"]
    assert len(builds) == N and len(services) == M
    for r in rows:
        assert r["v"] == obs_corpus.SCHEMA_VERSION and r["t"] > 0
    assert builds[0]["kind"] == "fused_step"
    assert builds[0]["knobs"]["values"]        # resolved knob vector
    assert "registry_version" in builds[0]["knobs"]
    assert isinstance(builds[0]["pipeline"], list)
    assert services[0]["source"] == "serving"
    assert services[0]["bucket"] == 1 and services[0]["rows"] == 4


def test_corpus_torn_tail_tolerated_mid_file_raises(tmp_path,
                                                    monkeypatch):
    d = str(tmp_path)
    monkeypatch.setenv("MXTPU_CORPUS_DIR", d)
    obs_corpus.reset()
    for j in range(4):
        obs_corpus.record_service("decode_step", 1.0 + j, rows=2)
    obs_corpus.reset()
    path = obs_corpus.corpus_path(d)
    # writer killed mid-append: a torn, newline-less trailing fragment
    with open(path, "a") as f:
        f.write('{"v": 1, "row": "service", "source": "decode_st')
    rows = obs_corpus.load(d)
    assert len(rows) == 4              # every FULLY appended row survives
    # mid-file garbage is real corruption and must raise
    bad = os.path.join(d, "zz_corrupt.jsonl")
    with open(bad, "w") as f:
        f.write('{"v": 1, "row": "service", "source": "a", "ms": 1}\n')
        f.write("NOT JSON\n")
        f.write('{"v": 1, "row": "service", "source": "b", "ms": 2}\n')
    with pytest.raises(ValueError):
        obs_corpus.load(d)


def test_service_line_least_squares():
    from mxtpu.obs.corpus import ServiceLine
    line = ServiceLine.fit({1: {"exec_ms": 2.0}, 8: {"exec_ms": 3.0}})
    assert line.basis == "bucket-rows"
    assert line.fixed == pytest.approx(2.0 - line.marginal)
    assert line(8) == pytest.approx(3.0)
    assert line(1) == pytest.approx(2.0)


def test_corpus_summarize_reproduces_service_line(tmp_path,
                                                  monkeypatch):
    from mxtpu.obs.corpus import ServiceLine
    d = str(tmp_path)
    monkeypatch.setenv("MXTPU_CORPUS_DIR", d)
    obs_corpus.reset()
    measured = {1: [2.0, 2.2, 1.8], 8: [5.0, 5.4], 32: [14.0]}
    for b, costs in measured.items():
        for ms in costs:
            obs_corpus.record_service("serving", ms, bucket=b)
    obs_corpus.record_service("fit_step", 33.0, rows=64)
    obs_corpus.reset()
    out = obs_corpus.summarize(dirpath=d)
    assert out["services"] == 7 and out["builds"] == 0
    want_costs = {b: {"exec_ms": sum(c) / len(c)}
                  for b, c in measured.items()}
    assert out["bucket_costs"] == want_costs
    assert out["bucket_counts"] == {1: 3, 8: 2, 32: 1}
    assert out["source_ms_mean"]["fit_step"] == 33.0
    assert out["service_line"] == ServiceLine.fit(want_costs).to_dict()


def test_corpus_populated_by_decode_and_build_seams(tmp_path,
                                                    monkeypatch):
    d = str(tmp_path)
    monkeypatch.setenv("MXTPU_CORPUS_DIR", d)
    obs_corpus.reset()
    try:
        with _session() as sess:
            sess.generate([3, 5], max_new_tokens=3, seed=0, timeout=60)
        rows = obs_corpus.load(d)
        sources = {r.get("source") for r in rows
                   if r["row"] == "service"}
        assert "decode_step" in sources
        assert "decode_request" in sources
        steps = [r for r in rows if r.get("source") == "decode_step"]
        assert all(r["ms"] > 0 and r["rows"] >= 1 for r in steps)
    finally:
        obs_corpus.reset()


def test_corpus_disabled_is_free(monkeypatch):
    monkeypatch.delenv("MXTPU_CORPUS_DIR", raising=False)
    obs_corpus.reset()
    assert not obs_corpus.enabled()
    assert obs_corpus.record_service("serving", 1.0) is False
    assert obs_corpus.record_build(_build_row(0)) is False
    assert obs_corpus.load(None) == []
