"""Stateful autoregressive decode serving (mxtpu/serving/decode).

Tier-1 (CPU, `not slow`). The PR's acceptance gates, all on exact
counters / byte comparisons per the PR-2 deterministic convention:

* **correctness** — with requests joining and leaving the batch between
  steps under a seeded arrival schedule, every request's token sequence
  is byte-identical to the same request decoded alone — including with
  the bf16 compile pipeline active, and across a mid-run ``swap_model``
  (in-flight sequences finish on their admission-time version);
* **liveness** — zero decode steps run with admittable requests left
  outside a free slot (asserted from the tripwire counter, not
  timing), and a completed sequence's slot is reusable by the very
  next step;
* **admission** — length-aware est-completion pricing sheds (429) when
  the arena is full behind LONG sequences, while a short-remaining mix
  at the same queue state still admits;
* **chaos** — injected step errors + a worker kill mid-decode resolve
  every in-flight request (completion or clean failure, zero hung
  waiters) and the arena leaks nothing (ledger ``decode_state`` back
  to baseline);
* **concurrency** — the armed witness reports zero hierarchy
  violations and an acyclic observed graph under concurrent decode.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxtpu as mx
import mxtpu.diagnostics as diag
from mxtpu import faults
from mxtpu.analysis import concurrency as conc
from mxtpu.base import MXNetError
from mxtpu.compile import pipeline
from mxtpu.serving import (AdmissionShed, AdmissionSignals,
                           DecodeAdmissionPolicy, DecodeSession,
                           SequenceSlotArena, ServingHTTPServer)
from mxtpu.serving.decode import lm_decode_fixture


# one fixture per module: every session built from THE SAME weight
# arrays under one version tag adopts the process warm cache — the
# suite pays the step-program compile once, not per test
_FIXTURE = {}


def _fixture(seed=0):
    if seed not in _FIXTURE:
        _FIXTURE[seed] = lm_decode_fixture(seed=seed)
    return _FIXTURE[seed]


def _session(seed=0, **kwargs):
    sym, params, shapes, state_names, _ = _fixture(seed)
    kwargs.setdefault("buckets", (4,))
    kwargs.setdefault("slot_capacity", 2)
    kwargs.setdefault("version_tag", "t-v%d" % seed)
    return DecodeSession(sym, params, shapes, state_names, **kwargs)


REQS = [([3, 5], 5, 0, 0.0), ([2], 6, 1, 0.5), ([7, 8, 9], 4, 2, 0.5),
        ([4], 5, 3, 0.0), ([6, 2], 3, 4, 0.9)]


def _decode_alone(seed=0, reqs=REQS):
    """Each request decoded as the ONLY sequence in flight."""
    out = []
    with _session(seed=seed, slot_capacity=1) as sess:
        for prompt, max_new, rseed, temp in reqs:
            out.append(sess.generate(prompt, max_new_tokens=max_new,
                                     seed=rseed, temperature=temp,
                                     timeout=60)["tokens"])
    return out


def _decode_joined(seed=0, reqs=REQS, capacity=2):
    """The same requests under a seeded concurrent arrival schedule:
    they join/leave the in-flight batch between steps (capacity <
    request count forces queue + slot-reuse churn)."""
    res = [None] * len(reqs)
    with _session(seed=seed, slot_capacity=capacity) as sess:

        def run(i):
            prompt, max_new, rseed, temp = reqs[i]
            res[i] = sess.generate(prompt, max_new_tokens=max_new,
                                   seed=rseed, temperature=temp,
                                   timeout=60)

        ts = [threading.Thread(target=run, args=(i,))
              for i in range(len(reqs))]
        for j, t in enumerate(ts):
            t.start()
            if j % 2:           # seeded stagger: joins land mid-decode
                time.sleep(0.003)
        for t in ts:
            t.join(timeout=120)
        tripped = sess.metrics.counter(
            "decode_steps_with_admittable_waiting").value
    assert all(r is not None for r in res), "hung generate waiter"
    return [r["tokens"] for r in res], res, tripped


# ------------------------------------------------------------ satellites
def test_state_spec_lstm_gru_stacked():
    """rnn_cell satellite: concrete zero-state shapes without a warmup
    batch, for single cells, stacks, and the fused cell."""
    lstm = mx.rnn.LSTMCell(8, prefix="l_")
    specs = lstm.state_spec(3)
    assert [tuple(s["shape"]) for s in specs] == [(3, 8), (3, 8)]
    arrs = lstm.begin_state_arrays(3)
    assert all(a.shape == (3, 8) and a.dtype == np.float32
               and not a.any() for a in arrs)

    gru = mx.rnn.GRUCell(5, prefix="g_")
    assert [tuple(s["shape"]) for s in gru.state_spec(2)] == [(2, 5)]

    stack = mx.rnn.SequentialRNNCell()
    stack.add(mx.rnn.LSTMCell(4, prefix="s0_"))
    stack.add(mx.rnn.GRUCell(6, prefix="s1_"))
    specs = stack.state_spec(7)
    assert [tuple(s["shape"]) for s in specs] == [(7, 4), (7, 4), (7, 6)]
    names = [s["name"] for s in specs]
    assert len(set(names)) == 3  # unique state names across the stack

    fused = mx.rnn.FusedRNNCell(8, num_layers=2, mode="lstm")
    specs = fused.state_spec(3)
    assert [tuple(s["shape"]) for s in specs] == [(2, 3, 8), (2, 3, 8)]
    assert fused.begin_state_arrays(3, dtype="bfloat16")[0].dtype \
        == np.dtype("bfloat16")


def test_state_spec_matches_step_program_states():
    """The fixture's example state shapes ARE the cell stack's
    state_spec at batch 1 — the arena can size itself blind."""
    sym, params, shapes, state_names, meta = _fixture()
    stack = mx.rnn.SequentialRNNCell()
    for i in range(meta["num_layers"]):
        stack.add(mx.rnn.LSTMCell(meta["num_hidden"],
                                  prefix="lstm_l%d_" % i))
    specs = stack.state_spec(1)
    assert len(specs) == len(state_names)
    for name, spec in zip(state_names, specs):
        assert tuple(shapes[name]) == tuple(spec["shape"])


# ----------------------------------------------------------------- arena
def _tiny_specs():
    return [{"name": "h", "shape": (1, 3), "dtype": "float32"},
            {"name": "c", "shape": (1, 3), "dtype": "float32"}]


def test_arena_alloc_release_and_ledger():
    base = diag.ledger().live_bytes(origin="decode_state")
    arena = SequenceSlotArena(3, _tiny_specs())
    assert diag.ledger().live_bytes(origin="decode_state") \
        == base + 2 * 3 * 3 * 4
    slots = [arena.allocate() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert arena.allocate() is None          # full
    assert arena.free_slots == 0 and arena.occupancy == 1.0
    arena.release(slots[1])
    assert arena.allocate() == slots[1]      # reusable immediately
    with pytest.raises(MXNetError):
        arena.release(99)
    arena.release(slots[0])
    with pytest.raises(MXNetError):
        arena.release(slots[0])              # double free is loud
    arena.close()
    assert diag.ledger().live_bytes(origin="decode_state") == base


def test_arena_gather_scatter_exact():
    """Round-trip exactness: scatter writes land at their slots, fresh
    rows gather as zeros, pad rows (idx == capacity) are dropped."""
    arena = SequenceSlotArena(4, _tiny_specs())
    rows = np.arange(8, dtype=np.float32).reshape(4, 2)[:, :1] \
        * np.ones((4, 3), np.float32)
    new = [rows + 10, rows + 20]
    arena.scatter(np.array([0, 1, 2, 3]), new)
    got = arena.gather(np.array([2, 0, 4], np.int32),
                       np.array([0.0, 0.0, 1.0], np.float32))
    import jax
    h, c = jax.device_get(got)
    np.testing.assert_array_equal(h[0], new[0][2])
    np.testing.assert_array_equal(h[1], new[0][0])
    assert not h[2].any()                    # pad row zeroed
    np.testing.assert_array_equal(c[0], new[1][2])
    # scatter with a pad index must not corrupt live slots
    arena.scatter(np.array([1, 4], np.int32),
                  [np.full((2, 3), -1, np.float32)] * 2)
    h2 = jax.device_get(arena.gather(np.array([1, 0], np.int32),
                                     np.zeros(2, np.float32)))[0]
    np.testing.assert_array_equal(h2[0], np.full(3, -1, np.float32))
    np.testing.assert_array_equal(h2[1], new[0][0])  # slot 0 untouched
    # fresh mask zeroes IN the gather, not in the arena
    g = jax.device_get(arena.gather(np.array([0], np.int32),
                                    np.ones(1, np.float32)))[0]
    assert not g.any()
    arena.close()


def test_arena_fresh_mask_clears_nan_from_previous_occupant():
    """Slot reuse after a diverged sequence: a slot whose previous
    occupant scattered NaN/Inf state must gather as EXACT zeros for a
    fresh sequence (select, not multiply — 0*NaN is NaN)."""
    import jax
    arena = SequenceSlotArena(2, _tiny_specs())
    poison = [np.full((2, 3), np.nan, np.float32),
              np.full((2, 3), np.inf, np.float32)]
    arena.scatter(np.array([0, 1], np.int32), poison)
    got = jax.device_get(arena.gather(np.array([0, 1], np.int32),
                                      np.ones(2, np.float32)))
    for leaf in got:
        assert np.isfinite(leaf).all() and not leaf.any()
    arena.close()


def test_state_dtype_bf16_halves_arena_bytes_and_decodes():
    """DecodeSession(state_dtype="bfloat16"): the arena keeps sequence
    state in the narrow dtype (half the device bytes of f32) and decode
    still runs deterministically within the session."""
    with _session(slot_capacity=2) as f32:
        f32_bytes = f32.arena.state_bytes()
        with _session(slot_capacity=2, state_dtype="bfloat16",
                      version_tag="t-bf16") as bf:
            assert bf.arena.state_bytes() * 2 == f32_bytes
            assert all(s["dtype"] == "bfloat16" for s in bf.arena.specs)
            a = bf.generate([3, 5], max_new_tokens=4, timeout=60)
            b = bf.generate([3, 5], max_new_tokens=4, timeout=60)
            assert a["tokens"] == b["tokens"]  # state round-trip is
            # deterministic even through the narrow dtype


def test_arena_programs_have_cost_rows():
    """Gather/scatter ride the compile seam: `decode_state` programs
    appear in the diagnostics table with captured cost rows."""
    arena = SequenceSlotArena(2, _tiny_specs())
    arena.gather(np.array([0], np.int32), np.ones(1, np.float32))
    rec = diag.latest_record("decode_state")
    assert rec is not None and rec.kind == "decode_state"
    arena.close()


# ------------------------------------------------- THE correctness gate
def test_correctness_gate_joined_equals_alone():
    alone = _decode_alone()
    joined, results, tripped = _decode_joined()
    assert joined == alone, (joined, alone)
    assert tripped == 0
    # the schedule really did interleave: some sequence joined after
    # step 0 (otherwise this tested nothing)
    assert max(r["join_step"] for r in results) > 0


def test_correctness_gate_bf16_pipeline():
    """Same gate with the bf16 rewrite active: the step program is a
    first-class pipeline citizen and identity still holds bit-for-bit."""
    with pipeline.pipeline_scope(["bf16"]):
        alone = _decode_alone()
        joined, _, tripped = _decode_joined()
    assert joined == alone
    assert tripped == 0


def test_correctness_gate_mid_run_swap():
    """swap_model mid-decode: in-flight sequences finish on their
    admission-time version byte-for-byte; post-swap admissions run the
    new weights byte-for-byte."""
    alone_v1 = _decode_alone(seed=0, reqs=[([3], 24, 0, 0.0),
                                           ([5], 24, 0, 0.0)])
    alone_v2 = _decode_alone(seed=9, reqs=[([4], 6, 0, 0.0)])
    sym2, params2, _, _, _ = _fixture(9)
    res = [None] * 3
    with _session(seed=0, slot_capacity=2) as sess:

        def run(i, prompt, n):
            res[i] = sess.generate(prompt, max_new_tokens=n, timeout=120)

        ts = [threading.Thread(target=run, args=(0, [3], 24)),
              threading.Thread(target=run, args=(1, [5], 24))]
        for t in ts:
            t.start()
        # both sequences must be IN FLIGHT before the flip, so the gate
        # really tests admission-time pinning (not just ordering)
        deadline = time.monotonic() + 10
        while len(sess._active) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        info = sess.swap_model(sym2, params2, version_tag="t-v9")
        assert info["generation"] == 1
        run(2, [4], 6)
        for t in ts:
            t.join(timeout=120)
    assert [res[0]["version"], res[1]["version"]] == ["t-v0", "t-v0"]
    assert res[2]["version"] == "t-v9"
    assert [res[0]["tokens"], res[1]["tokens"]] == alone_v1
    assert [res[2]["tokens"]] == alone_v2


# --------------------------------------------------- THE liveness gate
def test_liveness_gate_zero_idle_steps_and_slot_reuse():
    """Under queue-non-empty load: the tripwire counter proves no step
    dispatched with an admittable request outside a free slot, and a
    retired sequence's slot is taken by the next sequence at the SAME
    step count (reusable by the next step)."""
    reqs = [([2], 6, 0, 0.0)] * 4
    tokens, results, tripped = _decode_joined(reqs=reqs, capacity=2)
    assert tripped == 0
    finishes = sorted(r["finish_step"] for r in results)
    late_joins = sorted(r["join_step"] for r in results)[2:]
    # the two queued requests joined at EXACTLY the step counts where
    # the first two finished — the freed slot is in the very next
    # dispatched step, not one later (exact counters, no timing)
    assert late_joins == finishes[:2], (late_joins, finishes)


def test_join_latency_and_series():
    with _session(slot_capacity=2) as sess:
        sess.generate([2], max_new_tokens=2, timeout=60)
        stats = sess.stats()
        # 1-token prompt + 2 generated = exactly 2 steps (the last
        # prompt token's logits emit the first generated token)
        assert stats["decode_steps_total"] == 2
        assert stats["decode_tokens_total"] == 2
        assert stats["decode_join_latency_ms"]["count"] == 1
        assert stats["decode_evictions{reason=length}"] == 1
        assert stats["decode_active_sequences"] == 0
        assert "decode_slot_occupancy" in stats
        assert "decode_tokens_per_sec" in stats
        panel = sess.debug_panel()
        assert panel["slot_capacity"] == 2
        assert panel["admission"]["step_cost_basis"] in (
            "cost-rows", "live-steps")
        assert panel["state_bytes"] > 0


# -------------------------------------------------- THE admission gate
def test_admission_gate_length_aware_pricing():
    """Arena full + queue at the watermark: LONG remaining sequences
    price the join wait over budget (429); a SHORT-remaining mix at the
    same queue state admits (the PR-11 mix-aware pattern)."""
    def load(max_new):
        sess = _session(slot_capacity=2, join_watermark=1,
                        join_wait_budget_ms=60.0)
        holders = [threading.Thread(
            target=lambda: _swallow(sess.generate, [2],
                                    max_new_tokens=max_new, timeout=120))
            for _ in range(2)]
        for t in holders:
            t.start()
        # wait until both holders occupy their slots
        deadline = time.monotonic() + 10
        while sess.arena.free_slots and time.monotonic() < deadline:
            time.sleep(0.002)
        # one queued request reaches the watermark
        queued = threading.Thread(
            target=lambda: _swallow(sess.generate, [3],
                                    max_new_tokens=max_new, timeout=120))
        queued.start()
        deadline = time.monotonic() + 10
        while not sess._queue and sess.arena.free_slots == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        return sess, holders + [queued]

    # (helper closes over nothing mutable — each mix builds fresh)

    # LONG mix: thousands of remaining tokens ahead -> shed
    sess, threads = load(max_new=4000)
    if sess.arena.free_slots == 0:        # still loaded, as scheduled
        with pytest.raises(AdmissionShed) as exc:
            sess.generate_async([5], max_new_tokens=4000)
        assert "slots" in str(exc.value)
        assert sess._sheds_by_reason.get("slots") == 1
    sess.close(drain=False)
    for t in threads:
        t.join(timeout=30)

    # SHORT mix at the same queue shape: est join wait is a few steps
    # -> admits (whether or not the holders already finished)
    sess, threads = load(max_new=2)
    item = sess.generate_async([5], max_new_tokens=2)
    assert item.wait(60)["finish_reason"] == "length"
    sess.close()
    for t in threads:
        t.join(timeout=30)


def _swallow(fn, *a, **kw):
    try:
        fn(*a, **kw)
    except Exception:
        pass


def test_decode_admission_policy_units():
    """Pure-function decisions over synthetic signals."""
    pol = DecodeAdmissionPolicy(join_wait_budget_ms=100.0,
                                join_watermark=2)
    base = dict(slot_capacity=4, slots_free=0, queue_depth=2,
                queue_limit=256)
    long = AdmissionSignals(est_join_wait_ms=500.0,
                            est_tokens_ahead=250, **base)
    d = pol.decide(long)
    assert not d.admit and d.reason.startswith("slots")
    short = AdmissionSignals(est_join_wait_ms=12.0, est_tokens_ahead=6,
                             **base)
    assert pol.decide(short).admit
    # below the watermark the queue absorbs long waits without a shed
    trickle = AdmissionSignals(est_join_wait_ms=500.0,
                               est_tokens_ahead=250,
                               slot_capacity=4, slots_free=0,
                               queue_depth=1, queue_limit=256)
    assert pol.decide(trickle).admit
    # free slots always admit
    free = AdmissionSignals(est_join_wait_ms=0.0, slot_capacity=4,
                            slots_free=2, queue_depth=0, queue_limit=256)
    assert pol.decide(free).admit
    wedged = AdmissionSignals(watchdog_age_s=99.0, slot_capacity=4,
                              slots_free=2)
    assert not pol.decide(wedged).admit


def test_est_join_wait_uses_exact_remaining_tokens():
    """The signal math: with the arena full, est_tokens_ahead is the
    exact sorted-remaining count for the arrival's queue position."""
    with _session(slot_capacity=2) as sess:
        holders = [threading.Thread(
            target=lambda: _swallow(sess.generate, [2],
                                    max_new_tokens=100, timeout=60))
            for _ in range(2)]
        for t in holders:
            t.start()
        deadline = time.monotonic() + 10
        while sess.arena.free_slots and time.monotonic() < deadline:
            time.sleep(0.002)
        s = sess._signals()
        if s.slots_free == 0:
            assert 0 < s.est_tokens_ahead <= 101
            assert s.est_join_wait_ms == pytest.approx(
                s.est_batch_ms * s.est_tokens_ahead)
        sess.close(drain=False)
        for t in holders:
            t.join(timeout=30)


# ------------------------------------------------------ THE chaos gate
def test_chaos_gate_step_errors_and_kill():
    """Injected step errors + a worker kill mid-decode: every in-flight
    request resolves (tokens or a clean error, zero hung waiters), the
    worker respawns, the arena leaks nothing and the ledger's
    decode_state origin returns to baseline."""
    base = diag.ledger().live_bytes(origin="decode_state")
    sess = _session(slot_capacity=2)
    outcomes = []

    def run(i):
        try:
            sess.generate([2 + i % 8], max_new_tokens=6, timeout=30)
            outcomes.append("ok")
        except Exception as exc:
            outcomes.append(type(exc).__name__)

    # the kill spec is FIRST for its point: specs fire in declaration
    # order, so the crossing that arms it really dies (a raise-spec
    # firing the same crossing would otherwise preempt it)
    with faults.scope("serving.decode.step:kind=kill,after=4;"
                      "serving.decode.step:p=0.4,seed=7;"
                      "serving.decode.evict:p=0.3,seed=3"):
        ts = [threading.Thread(target=run, args=(i,)) for i in range(10)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    assert len(outcomes) == 10, "hung waiters under chaos"
    assert "ok" not in outcomes or True  # any mix is legal; none hang
    # the schedule really fired, including the kill -> respawn: waiters
    # are answered BEFORE the death path increments the counter, so
    # poll it rather than race the handler's tail
    deadline = time.monotonic() + 10
    while sess.metrics.counter("decode_worker_respawns").value < 1 \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sess.metrics.counter("decode_worker_respawns").value >= 1
    # zero slot leaks: everything resolved, so the arena is empty again
    deadline = time.monotonic() + 10
    while sess.arena.free_slots < sess.arena.capacity \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sess.arena.free_slots == sess.arena.capacity
    # the respawned worker serves post-chaos traffic
    r = sess.generate([3], max_new_tokens=2, timeout=30)
    assert r["finish_reason"] == "length"
    sess.close()
    assert diag.ledger().live_bytes(origin="decode_state") == base


def test_max_new_tokens_cap_protects_the_data_plane():
    """An unauthenticated request cannot pin a slot for an unbounded
    number of steps: max_new_tokens over the server cap is refused
    (MXNetError in-process, 400 over HTTP)."""
    from mxtpu.serving.decode.session import (MAX_NEW_TOKENS_CAP,
                                              MAX_REQUEST_TOKENS_CAP)
    with _session(slot_capacity=1) as sess:
        with pytest.raises(MXNetError):
            sess.generate_async([2], max_new_tokens=MAX_NEW_TOKENS_CAP + 1)
        # a giant PROMPT pins a slot one prefill step per token — the
        # total-step cap refuses it even with a tiny generation budget
        with pytest.raises(MXNetError):
            sess.generate_async([2] * MAX_REQUEST_TOKENS_CAP,
                                max_new_tokens=1)
        # at the cap itself the request is admitted
        item = sess.generate_async([2], max_new_tokens=MAX_NEW_TOKENS_CAP,
                                   timeout=60)
        sess.close(drain=False)
        _swallow(item.wait, 5)


def test_fail_chunk_preserves_already_finished_results():
    """A chunk member that finished cleanly before a later member's
    eviction raised keeps its delivered result — fail() must never
    overwrite a completed generation (and it isn't double-counted)."""
    from mxtpu.serving.decode.session import _Sequence
    with _session(slot_capacity=2) as sess:
        done = _Sequence([2], 1, None, 0, 0.0, None)
        done.item.finish({"tokens": [7], "finish_reason": "length"})
        pending = _Sequence([3], 1, None, 0, 0.0, None)
        failed_before = sess.metrics.counter("requests_failed").value
        sess._fail_chunk([done, pending], RuntimeError("step died"))
        assert done.item.wait(1)["tokens"] == [7]      # result intact
        with pytest.raises(RuntimeError):
            pending.item.wait(1)
        assert sess.metrics.counter("requests_failed").value \
            == failed_before + 1


def test_evict_injection_never_leaks_slots():
    """An eviction fault alone: requests may fail but every slot comes
    back (the _evict finally contract)."""
    with _session(slot_capacity=2) as sess:
        with faults.scope("serving.decode.evict:p=1.0,seed=1,times=4"):
            for i in range(4):
                _swallow(sess.generate, [2], max_new_tokens=1,
                         timeout=30)
        assert sess.arena.free_slots == sess.arena.capacity
        evs = [v for k, v in sess.stats().items()
               if str(k).startswith("decode_evictions")]
        assert sum(evs) >= 4


# --------------------------------------------------- concurrency gate
def test_armed_witness_decode_gate():
    """Concurrent decode under the armed lock-order witness: zero
    hierarchy violations, zero blocking-under-lock, acyclic graph."""
    with conc.scope() as w:
        joined, _, tripped = _decode_joined(
            reqs=[([2], 4, 0, 0.0)] * 6, capacity=2)
        assert len(joined) == 6 and tripped == 0
    rep = w.report()
    assert w.violations == 0, rep.render()
    assert w.blocked_calls == 0, rep.render()
    assert w.state()["acyclic"], w.state()["cycles"]


# ------------------------------------------------------------- tuning
def test_decode_knobs_resolve_through_tune(monkeypatch):
    """DecodeSession's knobs: default < environment < explicit argument
    (warmup=False keeps this compile-free)."""
    for env in ("MXTPU_DECODE_SLOTS", "MXTPU_DECODE_MAX_NEW_TOKENS",
                "MXTPU_DECODE_JOIN_WATERMARK"):
        monkeypatch.delenv(env, raising=False)
    s = _session(slot_capacity=None, warmup=False)
    try:
        assert s.slot_capacity == 8
        assert s.max_new_tokens_default == 32
        assert s.join_watermark == 4
    finally:
        s.close()
    monkeypatch.setenv("MXTPU_DECODE_SLOTS", "5")
    monkeypatch.setenv("MXTPU_DECODE_MAX_NEW_TOKENS", "7")
    monkeypatch.setenv("MXTPU_DECODE_JOIN_WATERMARK", "2")
    s = _session(slot_capacity=None, warmup=False)
    try:
        assert s.slot_capacity == 5           # env beats default
        assert s.max_new_tokens_default == 7
        assert s.join_watermark == 2
    finally:
        s.close()
    s = _session(slot_capacity=4, warmup=False)
    try:
        assert s.slot_capacity == 4           # explicit beats env
    finally:
        s.close()


# ---------------------------------------------------------------- HTTP
def test_http_generate_roundtrip_and_debug_panel():
    sess = _session(slot_capacity=2, id2word={i: "w%d" % i
                                              for i in range(16)})
    server = ServingHTTPServer(None, decode=sess, port=0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        url = server.endpoint
        body = json.dumps({"prompt": [3, 5], "max_new_tokens": 3,
                           "seed": 1}).encode()
        req = urllib.request.Request(url + "/v1/generate", data=body,
                                     headers={"Content-Type":
                                              "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            out = json.loads(resp.read())
        assert len(out["tokens"]) == 3
        assert out["finish_reason"] == "length"
        assert out["text"].startswith("w")
        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["mode"] == "decode" and health["status"] == "ok"
        with urllib.request.urlopen(url + "/debug/state",
                                    timeout=30) as r:
            state = json.loads(r.read())
        assert state["decode"]["slot_capacity"] == 2
        assert state["decode"]["tokens_out"] >= 3
        assert "admission" in state["decode"]
        with urllib.request.urlopen(url + "/v1/metrics", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["decode_steps_total"] >= 1
        # bad request taxonomy
        req = urllib.request.Request(url + "/v1/generate",
                                     data=b'{"prompt": []}')
        try:
            urllib.request.urlopen(req, timeout=30)
            assert False, "empty prompt must 400"
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
    finally:
        server.shutdown()


def test_http_admin_swap_targets_decode(tmp_path):
    """On a combined server the swap payload's ``target`` routes the
    rollout: ``"decode"`` rolls the decode pool (predict untouched), a
    bogus target is 400 — a decode checkpoint can never land on the
    predict pool by routing accident."""
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving import ServingSession
    sym9, params9, _, _, _ = _fixture(9)
    symf = tmp_path / "step.json"
    symf.write_text(sym9)
    pf = str(tmp_path / "step.params")
    mx.nd.save(pf, params9)
    psym, pparams, pshapes = get_fixture("mlp")
    psess = ServingSession(psym, pparams, pshapes, buckets=(1,),
                           version_tag="p-v0")
    dsess = _session(seed=0, slot_capacity=2)
    server = ServingHTTPServer(psess, decode=dsess, port=0,
                               admin_token="hunter2")
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        url = server.endpoint

        def swap(body):
            req = urllib.request.Request(
                url + "/v1/admin/swap", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json",
                         "X-Admin-Token": "hunter2"})
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        info = swap({"symbol_file": str(symf), "params_file": pf,
                     "version_tag": "h-v9", "target": "decode"})
        assert info["version"] == "h-v9" and info["mode"] == "decode"
        assert dsess.version_tag == "h-v9"
        assert psess.version_tag == "p-v0"          # predict untouched
        try:
            swap({"symbol_file": str(symf), "params_file": pf,
                  "target": "bogus"})
            assert False, "bogus target must 400"
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
    finally:
        server.shutdown()


def test_http_combined_server_exposes_both_sessions():
    """Predict + decode on one port: distinct metric namespaces in one
    scrape (no duplicate Prometheus series, no clobbered JSON keys),
    decode visible in /healthz and /v1/version|metrics, and a closed
    decode session drains the WHOLE server."""
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving import ServingSession
    psym, pparams, pshapes = get_fixture("mlp")
    psess = ServingSession(psym, pparams, pshapes, buckets=(1,))
    dsess = _session(slot_capacity=2)
    server = ServingHTTPServer(psess, decode=dsess, port=0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        url = server.endpoint
        dsess.generate([2], max_new_tokens=2, timeout=60)
        with urllib.request.urlopen(url + "/metrics?format=json",
                                    timeout=30) as r:
            snap = json.loads(r.read())
        # distinct namespaces: decode steps under mxtpu_decode, the
        # predict session's series untouched under mxtpu_serving
        assert snap["mxtpu_decode"]["decode_steps_total"] >= 1
        assert "decode_steps_total" not in snap["mxtpu_serving"]
        assert "queue_depth" in snap["mxtpu_serving"]
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            prom = r.read().decode()
        assert "mxtpu_decode_decode_steps_total" in prom
        # exactly one sample per shared-name series per namespace
        assert prom.count("\nmxtpu_serving_queue_depth ") == 1
        assert prom.count("\nmxtpu_decode_queue_depth ") == 1
        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["decode"]["version"] == dsess.version_tag
        with urllib.request.urlopen(url + "/v1/version",
                                    timeout=30) as r:
            ver = json.loads(r.read())
        assert ver["decode"]["mode"] == "decode"
        with urllib.request.urlopen(url + "/v1/metrics",
                                    timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["decode"]["decode_steps_total"] >= 1
        # EITHER session draining drains the server
        dsess.close()
        try:
            urllib.request.urlopen(url + "/healthz", timeout=30)
            assert False, "closed decode session must 503"
        except urllib.error.HTTPError as exc:
            assert exc.code == 503
    finally:
        server.shutdown()
