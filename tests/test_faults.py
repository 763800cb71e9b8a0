"""mxtpu.faults — seeded fault injection, the shared RetryPolicy, and
the chaos gates (docs/faults.md).

The chaos gates are the point of the subsystem: they convert the
robustness claims of PRs 4/8/10 from "handled" to "demonstrated under
injected failure":

* **elastic under fire** — a fit with ENOSPC + torn-write + writer-kill
  faults injected still resumes BIT-EXACT from the last good generation
  (the PR-8 parity gate, with the disk actively failing);
* **serving under fire** — replica-kill + dispatch-error faults at 1×
  load: every request answers or errors (zero hung waiters), no stale
  weights after recovery, and capacity returns to full via
  quarantine/respawn;
* **prefetch crash** — a producer-thread death surfaces the ORIGINAL
  exception at the consumer within one batch (regression for the
  silent-hang bug);
* **watchdog × faults** — an injected ``executor.device_wait`` latency
  past the stall deadline fires the real detector, the postmortem's
  flight ring names the injected cause, and the supervisor's
  restore-retry completes end-to-end.

Everything is seeded and bounded: fault schedules replay exactly,
RetryPolicy gets a no-op sleep wherever real backoff would cost suite
time (the ISSUE ops budget).
"""
import errno
import json
import os
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import faults
from mxtpu import metric as M
from mxtpu.base import MXNetError
from mxtpu.elastic import snapshot as esnap
from mxtpu.faults import RetryPolicy
from mxtpu.models import mlp as _mlp


NOSLEEP = {"sleep": lambda s: None}


@pytest.fixture(autouse=True)
def _disarm():
    """No schedule may leak across tests (or in from the environment)."""
    faults.reset()
    yield
    faults.reset()


@pytest.fixture()
def fast_writer_retry():
    """The process snapshot writer with backoff sleeps removed (the
    injected-clock rule: chaos gates must not wait out real backoff)."""
    w = esnap.writer()
    old = w._retry
    w._retry = RetryPolicy(
        "elastic.snapshot.write", max_attempts=3, backoff_s=0.0,
        retryable=OSError, recover=w._recover_write, **NOSLEEP)
    yield w
    w.flush()
    w._retry = old


# ----------------------------------------------------------- injection unit
def test_schedule_grammar_and_validation():
    s = faults.parse_schedule(
        "elastic.snapshot.write:errno=ENOSPC,p=0.3,seed=7;"
        "serving.replica.dispatch:kind=kill,after=5")
    specs = {d["point"]: d for d in s.describe()}
    assert specs["elastic.snapshot.write"]["kind"] == "errno"
    assert specs["elastic.snapshot.write"]["errno"] == errno.ENOSPC
    assert specs["serving.replica.dispatch"]["kind"] == "kill"
    assert specs["serving.replica.dispatch"]["times"] == 1  # kill: once
    with pytest.raises(MXNetError):        # typo must fail loudly
        faults.parse_schedule("elastic.snapshott.write:kind=raise")
    with pytest.raises(MXNetError):        # unknown key too
        faults.parse_schedule("kvstore.push:frequency=2")
    with pytest.raises(MXNetError):
        faults.FaultSpec("kvstore.push", kind="explode")


def test_injection_is_seeded_deterministic():
    def firings(seed):
        s = faults.FaultSchedule(
            [faults.FaultSpec("kvstore.push", errno="EIO", p=0.3,
                              seed=seed)])
        out = []
        for _ in range(64):
            try:
                s.evaluate("kvstore.push")
                out.append(0)
            except OSError:
                out.append(1)
        return out

    a, b = firings(7), firings(7)
    assert a == b and sum(a) > 0          # replays exactly, and fires
    assert a != firings(8)                 # the seed is the schedule


def test_scope_arms_and_restores():
    assert faults.active() is None
    with faults.scope("kvstore.pull:kind=raise,times=1") as sched:
        assert faults.active() is sched
        with pytest.raises(faults.FaultInjected):
            faults.point("kvstore.pull")
        faults.point("kvstore.pull")       # times=1: spent
        assert sched.fired_total == 1
    assert faults.active() is None
    faults.point("kvstore.pull")           # disarmed: free no-op


def test_after_and_times_windows():
    with faults.scope("engine.dispatch:kind=raise,after=2,times=2"):
        faults.point("engine.dispatch")    # 1: within `after`
        faults.point("engine.dispatch")    # 2: within `after`
        for _ in range(2):                 # 3, 4: the firing window
            with pytest.raises(faults.FaultInjected):
                faults.point("engine.dispatch")
        faults.point("engine.dispatch")    # 5: `times` exhausted


def test_firing_emits_telemetry_and_flight_evidence():
    reg = mx.telemetry.registry()
    c = reg.counter("fault_injected",
                    labels={"point": "kvstore.push", "kind": "errno"})
    v0 = c.value
    with faults.scope("kvstore.push:errno=ENOSPC"):
        with pytest.raises(faults.InjectedIOError) as exc_info:
            faults.point("kvstore.push")
    assert exc_info.value.errno == errno.ENOSPC
    assert c.value == v0 + 1
    events = mx.diagnostics.recorder().snapshot()
    assert any(e["kind"] == "fault" and e["name"] == "kvstore.push"
               for e in events)


def test_env_arming(monkeypatch):
    monkeypatch.setenv("MXTPU_FAULTS", "engine.dispatch:kind=raise,times=1")
    sched = faults.configure(None)
    assert [d["point"] for d in sched.describe()] == ["engine.dispatch"]
    monkeypatch.setenv("MXTPU_FAULTS", "")
    assert faults.configure(None) is None  # empty = off
    # malformed numeric values are MXNetError (not ValueError), so the
    # tolerant import-time arming catches them and import survives a
    # fat-fingered canary schedule
    with pytest.raises(MXNetError):
        faults.parse_schedule("kvstore.push:p=bogus")
    with pytest.raises(MXNetError):
        faults.parse_schedule("kvstore.push:after=2.5x")


# --------------------------------------------------------------- retry unit
def test_retry_policy_bounded_backoff_deterministic_jitter():
    sleeps = []
    calls = []
    pol = RetryPolicy("unit.op", max_attempts=4, backoff_s=1.0,
                      backoff_cap_s=3.0, sleep=sleeps.append,
                      clock=lambda: 0.0)

    def flaky():
        calls.append(1)
        if len(calls) < 4:
            raise ConnectionError("transient")
        return "ok"

    assert pol.call(flaky) == "ok"
    assert len(calls) == 4 and len(sleeps) == 3
    # exponential base with the cap engaged on the third retry
    assert sleeps == [pol.backoff(1), pol.backoff(2), pol.backoff(3)]
    assert pol.backoff(3) <= 3.0 * 1.1
    # jitter is a pure function of (op, seed, attempt): replayable
    assert pol.backoff(1) == RetryPolicy(
        "unit.op", backoff_s=1.0).backoff(1)
    assert pol.backoff(1) != RetryPolicy(
        "other.op", backoff_s=1.0).backoff(1)


def test_retry_policy_exhaustion_and_predicate():
    reg = mx.telemetry.registry()
    ex0 = reg.counter("retry_exhausted", labels={"op": "unit.dead"}).value

    def dead():
        raise OSError("disk on fire")

    with pytest.raises(OSError):
        RetryPolicy("unit.dead", max_attempts=3, backoff_s=0.0,
                    **NOSLEEP).call(dead)
    assert reg.counter("retry_exhausted",
                       labels={"op": "unit.dead"}).value == ex0 + 1

    # non-retryable: propagates immediately, no attempts counted
    calls = []
    def usage_error():
        calls.append(1)
        raise MXNetError("caller bug")
    with pytest.raises(MXNetError):
        RetryPolicy("unit.usage", max_attempts=5, **NOSLEEP).call(
            usage_error)
    assert len(calls) == 1


def test_env_attempts_convention(monkeypatch):
    """`*_RETRIES` env vars count retries AFTER the first attempt
    (N+1 attempts, 0 = no retries), and a bad value falls back to the
    default instead of crashing the mechanism it configures."""
    monkeypatch.delenv("X_RETRIES", raising=False)
    assert faults.env_attempts("X_RETRIES", 3) == 4
    monkeypatch.setenv("X_RETRIES", "0")
    assert faults.env_attempts("X_RETRIES", 3) == 1   # never < 1
    monkeypatch.setenv("X_RETRIES", "2")
    assert faults.env_attempts("X_RETRIES", 3) == 3
    monkeypatch.setenv("X_RETRIES", "bogus")
    assert faults.env_attempts("X_RETRIES", 3) == 4   # tolerant


def test_retry_policy_recover_hook_skips_backoff():
    sleeps = []
    recovered = []
    calls = []

    def recover(exc, attempt):
        recovered.append((type(exc).__name__, attempt))
        return True                        # resource freed: retry NOW

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise OSError(errno.ENOSPC, "full")
        return 42

    pol = RetryPolicy("unit.recover", max_attempts=3, backoff_s=9.0,
                      recover=recover, sleep=sleeps.append)
    assert pol.call(flaky) == 42
    assert recovered == [("OSError", 1)] and sleeps == []


# ------------------------------------------------------- kvstore under fire
def test_kvstore_push_pull_retry_transient():
    reg = mx.telemetry.registry()
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.zeros((4,)))
    a0 = reg.counter("retry_attempts", labels={"op": "kvstore.push"}).value
    # deterministic window: evaluations 2 and 3 fire — the second push
    # fails once, retries once more into the window, then lands
    with faults.scope("kvstore.push:errno=ECONNRESET,after=1,times=2"):
        kv.push("w", mx.nd.ones((4,)))         # eval 1: clean
        # evals 2,3 fire; attempt 3 (eval 4) lands — exactly at the
        # default bound of 3 attempts
        kv.push("w", mx.nd.array(np.full(4, 2.0, "f4")))
    assert reg.counter("retry_attempts",
                       labels={"op": "kvstore.push"}).value == a0 + 2
    out = mx.nd.zeros((4,))
    p0 = reg.counter("retry_attempts", labels={"op": "kvstore.pull"}).value
    with faults.scope("kvstore.pull:errno=ETIMEDOUT,times=1"):
        kv.pull("w", out=out)
    assert reg.counter("retry_attempts",
                       labels={"op": "kvstore.pull"}).value == p0 + 1
    # no updater armed: push assigns, so the LAST push's value sticks
    np.testing.assert_array_equal(out.asnumpy(), np.full(4, 2.0, "f4"))


def test_kvstore_push_exhaustion_raises_original():
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.zeros((2,)))
    with faults.scope("kvstore.push:errno=ECONNRESET"):  # every attempt
        with pytest.raises(faults.InjectedIOError):
            kv.push("w", mx.nd.ones((2,)))


# --------------------------------------------------- snapshot writer's IO
def _gen_job(prefix, g, keep=2):
    return esnap.SnapshotJob(
        "generation", {"arg:w": np.full(4, float(g), "f4")}, prefix=prefix,
        generation=g, keep=keep,
        manifest={"format": esnap.FORMAT,
                  "cursor": {"epoch": 0, "nbatch": g, "global_step": g}})


def test_writer_enospc_prunes_then_retries(tmp_path, fast_writer_retry):
    """The named degradation contract: a disk-full generation write
    frees space (prune to keep-1) and retries immediately — the NEW
    state wins over history depth."""
    reg = mx.telemetry.registry()
    w = fast_writer_retry
    prefix = str(tmp_path / "run")
    for g in (1, 2):
        w.submit(_gen_job(prefix, g))
    w.flush()
    assert esnap.list_generations(prefix) == [1, 2]
    r0 = reg.counter("retry_attempts",
                     labels={"op": "elastic.snapshot.write"}).value
    with faults.scope("elastic.snapshot.write:errno=ENOSPC,times=1"):
        w.submit(_gen_job(prefix, 3))
        w.flush()
    assert reg.counter("retry_attempts",
                       labels={"op": "elastic.snapshot.write"}).value \
        == r0 + 1
    man = esnap.latest_manifest(prefix)
    assert man["_generation"] == 3          # the retried write LANDED
    assert 1 not in esnap.list_generations(prefix)  # prune freed space


def test_writer_exhaustion_degrades_not_raises(tmp_path,
                                               fast_writer_retry):
    """Retries exhausted: the generation is abandoned and COUNTED
    (elastic_write_failures), the previous one still loads, and the
    writer keeps serving later jobs — nothing raises anywhere near the
    training thread."""
    reg = mx.telemetry.registry()
    w = fast_writer_retry
    prefix = str(tmp_path / "run")
    w.submit(_gen_job(prefix, 1))
    w.flush()
    f0 = reg.counter("elastic_write_failures").value
    with faults.scope("elastic.snapshot.write:errno=EIO"):  # every attempt
        w.submit(_gen_job(prefix, 2))
        w.flush()
    assert reg.counter("elastic_write_failures").value == f0 + 1
    assert esnap.latest_manifest(prefix)["_generation"] == 1
    w.submit(_gen_job(prefix, 3))           # the writer is still alive
    w.flush()
    assert esnap.latest_manifest(prefix)["_generation"] == 3


def test_torn_rename_fault_leaves_previous_generation(tmp_path,
                                                      fast_writer_retry):
    """A fault between the tmp write and its rename (the crash window
    the atomic protocol exists for): the generation never completes,
    the pointer never flips, the previous generation loads."""
    w = fast_writer_retry
    prefix = str(tmp_path / "run")
    w.submit(_gen_job(prefix, 1))
    w.flush()
    # kind=raise is NOT retryable (not an OSError): the job dies on the
    # torn rename, simulating a crash mid-protocol
    with faults.scope("elastic.snapshot.fsync_rename:kind=raise,times=1"):
        w.submit(_gen_job(prefix, 2))
        w.flush()
    man = esnap.latest_manifest(prefix)
    assert man["_generation"] == 1
    np.testing.assert_array_equal(esnap.load_arrays(man)["arg:w"],
                                  np.ones(4, "f4"))


def test_writer_kill_respawns_on_next_use(tmp_path, fast_writer_retry):
    """An injected writer death loses its in-flight job but neither
    hangs flush() nor kills the process: the next submit respawns the
    thread and later generations land."""
    w = fast_writer_retry
    prefix = str(tmp_path / "run")
    w.submit(_gen_job(prefix, 1))
    w.flush()
    with faults.scope("elastic.snapshot.write:kind=kill"):
        w.submit(_gen_job(prefix, 2))
        assert w.flush(timeout=10)          # must NOT hang
    w.submit(_gen_job(prefix, 3))           # respawns the thread
    w.flush()
    assert esnap.latest_manifest(prefix)["_generation"] == 3


# ----------------------------------------------------- elastic chaos gate
def _mnist_like(n=256, seed=7):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, 784).astype("float32"),
            rng.randint(0, 10, n).astype("float32"))


def _make_iter(batch_size=64):
    X, y = _mnist_like()
    return mx.io.NDArrayIter(X, y, batch_size=batch_size,
                             label_name="softmax_label")


class Kill(Exception):
    """Simulated hard death of the training process."""


def _fit(num_epoch=2, seed=11, kill_at_step=None, module=None,
         **fit_kwargs):
    it = _make_iter()
    mod = module or mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    metric = M.create(["acc", "ce"])
    mx.random.seed(seed)
    np.random.seed(seed)
    steps = [0]
    cb = None
    if kill_at_step is not None:
        def cb(param):
            steps[0] += 1
            if steps[0] >= kill_at_step:
                raise Kill()
    try:
        mod.fit(it, num_epoch=num_epoch, eval_metric=metric,
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
                initializer=mx.initializer.Xavier(),
                batch_end_callback=cb, metric_sync=2, **fit_kwargs)
    except Kill:
        pass
    weights = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    return dict(metric.get_name_value()), weights, mod


def test_chaos_gate_elastic_resume_bit_exact_under_write_faults(
        tmp_path, fast_writer_retry):
    """THE elastic chaos gate: ENOSPC (retried through prune), a torn
    rename (abandons its generation), and a writer kill (thread death)
    all injected into a checkpointing fit — the kill-at-step-N resume
    is STILL bit-exact, from whichever generation survived."""
    reg = mx.telemetry.registry()
    prefix = str(tmp_path / "ck")
    m_full, w_full, _ = _fit()
    # epoch_period=0: generation count == step count, so the schedule's
    # `after` windows land on exact, documented jobs (determinism)
    cfg = mx.elastic.ElasticConfig(prefix, every_n_steps=1,
                                   epoch_period=0, sync=True)
    f0 = reg.counter("elastic_write_failures").value
    # write-point evals: g1=1 | g2=2 (ENOSPC fires) +retry=3 | g3=4 |
    # g4=5 | g5=6 (kill fires). fsync evals: g1=1..3, g2 retry=4..6,
    # g3=7 (torn data rename — generation abandoned, not retried:
    # kind=raise is not an OSError). Landed generations: 1, 2, 4.
    sched = ("elastic.snapshot.write:errno=ENOSPC,times=1,after=1;"
             "elastic.snapshot.fsync_rename:kind=raise,after=6,times=1;"
             "elastic.snapshot.write:kind=kill,after=5,times=1")
    with faults.scope(sched) as s:
        _fit(kill_at_step=5, elastic=cfg)
        fired = s.fired_total
    assert fired >= 3, s.describe()          # all three fault flavors
    assert reg.counter("elastic_write_failures").value > f0
    man = esnap.latest_manifest(prefix)
    assert man is not None                   # at least one gen survived
    assert man["cursor"]["global_step"] < 5  # ...and not the latest: the
    # injected failures really cost generations, so resume must replay
    m_res, w_res, _ = _fit(resume=prefix, elastic=False)
    for k in w_full:
        np.testing.assert_array_equal(
            w_full[k], w_res[k],
            err_msg="weights diverged at %s under injected faults" % k)
    assert m_full["accuracy"] == m_res["accuracy"]
    np.testing.assert_allclose(m_full["cross-entropy"],
                               m_res["cross-entropy"], rtol=1e-5)


# ----------------------------------------------------- serving chaos gate
def test_chaos_gate_serving_replica_kill_no_hung_waiters():
    """THE serving chaos gate: dispatch-error + replica-kill faults at
    1× load — every request answers or errors (zero hung waiters),
    capacity recovers to full via quarantine/respawn, and post-recovery
    outputs are byte-identical to pre-fault ones (zero stale weights)."""
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving import ServingSession
    sym, params, shapes = get_fixture("mlp")
    with ServingSession(sym, params, shapes, buckets=(1, 4),
                        max_delay_ms=2, contexts=[mx.cpu(0)]) as sess:
        x = np.random.RandomState(0).rand(1, 784).astype(np.float32)
        want = sess.predict({"data": x})[0]

        results = []
        def client():
            try:
                out = sess.predict({"data": x}, timeout=20)
                results.append(("ok", out))
            except Exception as exc:
                results.append(("err", exc))

        sched = ("serving.replica.dispatch:kind=raise,p=0.3,seed=5;"
                 "serving.replica.dispatch:kind=kill,after=4,times=1")
        with faults.scope(sched) as s:
            threads = [threading.Thread(target=client, daemon=True)
                       for _ in range(30)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            hung = sum(t.is_alive() for t in threads)
            assert s.fired_total > 0
        assert hung == 0, "hung waiters under injected replica faults"
        assert len(results) == 30            # every request resolved
        oks = [r for r in results if r[0] == "ok"]
        errs = [r for r in results if r[0] == "err"]
        assert oks and errs                  # both outcomes exercised
        for _, out in oks:
            # answered = the CURRENT weights' answer. Tolerance, not
            # byte-equality: a coalesced request runs the bucket-4
            # program, whose XLA:CPU reduction order differs in the
            # last bits from the bucket-1 reference
            np.testing.assert_allclose(out[0], want, rtol=1e-5,
                                       atol=1e-6)
        # the kill quarantined the replica and the respawn recovered it
        assert sess.metrics.counter("replica_quarantined").value >= 1
        deadline = time.monotonic() + 20
        while sess.healthy_replicas() < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sess.healthy_replicas() == len(sess.pool)  # full capacity
        assert sess.metrics.counter(
            "replica_respawned", labels={"outcome": "ok"}).value >= 1
        # zero stale weights: the rebuilt replica serves the same bytes
        out2 = sess.predict({"data": x}, timeout=10)[0]
        np.testing.assert_array_equal(want, out2)


def test_serving_degraded_capacity_is_reported():
    """While a replica is quarantined, /healthz-visible state and the
    admission signals must see the reduced capacity (est-wait honesty),
    and recover when the respawn lands."""
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving import ServingSession
    sym, params, shapes = get_fixture("mlp")
    with ServingSession(sym, params, shapes, buckets=(1, 4),
                        max_delay_ms=2, contexts=[mx.cpu(0)]) as sess:
        full_limit = sess._signals().inflight_limit
        assert full_limit == sess.max_in_flight
        with faults.scope("serving.replica.dispatch:kind=kill"):
            try:
                sess.predict({"data": np.zeros((1, 784), "f4")},
                             timeout=10)
            except Exception:
                pass
            deadline = time.monotonic() + 10
            while sess.healthy_replicas() > 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sess.healthy_replicas() == 0
            sig = sess._signals()
            assert sig.inflight_limit == 0 and sig.replicas == 0
            assert sess.metrics.gauge("replicas_healthy").value == 0
        deadline = time.monotonic() + 20
        while sess.healthy_replicas() < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sess._signals().inflight_limit == full_limit


def test_serving_collect_kill_answers_waiters():
    """A kill at the RETIRE seam (batch already out of the in-flight
    window) must still answer that batch's waiters before the thread
    unwinds — the hole a plain `except Exception` in _retire left."""
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving import ReplicaCrash, ServingSession
    sym, params, shapes = get_fixture("mlp")
    with ServingSession(sym, params, shapes, buckets=(1, 4),
                        max_delay_ms=2, contexts=[mx.cpu(0)]) as sess:
        x = np.zeros((1, 784), np.float32)
        sess.predict({"data": x})                 # warm
        with faults.scope("serving.replica.collect:kind=kill"):
            with pytest.raises(ReplicaCrash):
                sess.predict({"data": x}, timeout=10)
        deadline = time.monotonic() + 20
        while sess.healthy_replicas() < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sess.healthy_replicas() == len(sess.pool)
        sess.predict({"data": x}, timeout=10)     # serves again


def test_serving_respawn_failure_is_counted_not_silent(monkeypatch):
    """A rebuild that itself dies — including on a BaseException like a
    kill-mode fault — must land in `replica_respawned{outcome=failed}`
    with the replica still quarantined; a silently dead respawn thread
    is the exact capacity shrink this path exists to eliminate."""
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving import ServingSession
    from mxtpu.serving import pool as pool_mod
    sym, params, shapes = get_fixture("mlp")
    with ServingSession(sym, params, shapes, buckets=(1,),
                        max_delay_ms=2, contexts=[mx.cpu(0)]) as sess:
        sess.predict({"data": np.zeros((1, 784), "f4")})
        f0 = sess.metrics.counter("replica_respawned",
                                  labels={"outcome": "failed"}).value
        monkeypatch.setattr(
            pool_mod.ExecutorPool, "rebuild_replica",
            lambda self, idx: (_ for _ in ()).throw(
                faults.FaultKill("injected kill inside rebuild")))
        with faults.scope("serving.replica.dispatch:kind=kill"):
            try:
                sess.predict({"data": np.zeros((1, 784), "f4")},
                             timeout=10)
            except Exception:
                pass
        deadline = time.monotonic() + 20
        while sess.metrics.counter(
                "replica_respawned",
                labels={"outcome": "failed"}).value == f0 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sess.metrics.counter(
            "replica_respawned", labels={"outcome": "failed"}).value \
            == f0 + 1
        assert sess.healthy_replicas() == 0  # honest: still quarantined


# ---------------------------------------------------- prefetch chaos gate
class _CrashingIter(mx.io.NDArrayIter):
    def __init__(self, *args, fail_at=3, exc=None, **kwargs):
        super().__init__(*args, **kwargs)
        self._count = 0
        self._fail_at = fail_at
        self._exc = exc or ValueError("producer boom")

    def next(self):
        self._count += 1
        if self._count == self._fail_at:
            raise self._exc
        return super().next()


def test_chaos_gate_prefetch_producer_crash_surfaces_at_consumer():
    """THE prefetch gate (and the satellite bugfix's regression test):
    a producer-thread crash re-raises the ORIGINAL exception at the
    consumer within one batch — before the fix it hung the consumer
    forever on data_ready."""
    X, y = _mnist_like(n=256)
    base = _CrashingIter(X, y, batch_size=64, fail_at=3,
                         label_name="softmax_label")
    it = mx.io.PrefetchingIter(base)
    try:
        assert it.iter_next()                # batch 1
        assert it.iter_next()                # batch 2
        with pytest.raises(ValueError, match="producer boom"):
            it.iter_next()                   # batch 3: the crash surfaces
        # the iterator is poisoned, not half-working: every further use
        # re-raises the same original error
        with pytest.raises(ValueError, match="producer boom"):
            next(it)
        with pytest.raises(ValueError, match="producer boom"):
            it.reset()
        for t in it.prefetch_threads:        # the producer really exited
            t.join(timeout=5)
            assert not t.is_alive()
    finally:
        it.close()


def test_prefetch_injected_fault_surfaces():
    """Same contract through the injection point — and through
    Module.fit's consumption of the iterator: the fit dies with the
    injected error instead of hanging."""
    X, y = _mnist_like(n=256)
    it = mx.io.PrefetchingIter(
        mx.io.NDArrayIter(X, y, batch_size=64,
                          label_name="softmax_label"))
    try:
        with faults.scope("io.prefetch.produce:kind=raise,after=2,"
                          "times=1"):
            with pytest.raises(faults.FaultInjected):
                while True:
                    it.iter_next()
    finally:
        it.close()


# -------------------------------------------------- watchdog x faults gate
def test_watchdog_fires_on_injected_device_wait_latency(tmp_path,
                                                        fast_writer_retry):
    """End-to-end: an injected ``executor.device_wait`` latency past the
    watchdog's wait deadline fires the REAL detector (no hand-built
    wedged-engine plumbing), the postmortem's flight ring contains the
    ``fault_injected`` event naming the cause, and the supervisor's
    checkpoint-restore-retry completes with numbers equal to an
    uninterrupted fit."""
    from mxtpu.diagnostics import Watchdog
    prefix = str(tmp_path / "ck")
    m_full, w_full, _ = _fit()
    wd = Watchdog(interval=0.01, engine_stall_s=99,
                  wait_stall_s=0.05).start()
    sup = mx.elastic.Supervisor(retries=2, backoff_s=0.0, **NOSLEEP)
    cfg = mx.elastic.ElasticConfig(prefix, every_n_steps=1, sync=True,
                                   supervisor=sup)
    mod = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    metric = M.create(["acc", "ce"])
    attempts = []

    def fit_fn(resume):
        attempts.append(resume)
        mx.random.seed(11)
        np.random.seed(11)
        mod.fit(_make_iter(), num_epoch=2, eval_metric=metric,
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
                initializer=mx.initializer.Xavier(), metric_sync=2,
                elastic=cfg, resume=resume)

    d0 = wd.detections
    try:
        # one 500ms stall inside the pacing wait, several steps in —
        # 10x the 50ms deadline, sampled every 10ms
        with faults.scope("executor.device_wait:latency_ms=500,after=3,"
                          "times=1"):
            sup.run(fit_fn)
    finally:
        wd.stop()
    assert attempts == [False, True]         # wedge -> restore-retry
    assert wd.detections > d0
    pm = mx.diagnostics.last_postmortem()
    assert pm is not None and pm["source"] == "watchdog"
    assert any(e["kind"] == "fault"
               and e["name"] == "executor.device_wait"
               for e in pm.get("flight", [])), \
        "postmortem flight ring must name the injected cause"
    # recovery half: final numbers equal the uninterrupted fit
    assert m_full["accuracy"] == dict(metric.get_name_value())["accuracy"]
    w_sup = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in w_full:
        np.testing.assert_array_equal(w_full[k], w_sup[k], err_msg=k)


# ----------------------------------------------------- supervisor / series
def test_supervisor_runs_through_shared_retry_policy():
    """Supervisor.run's loop IS a RetryPolicy now: its knobs surface as
    the policy's, WedgeAbort is the only retryable, and exhaustion
    lands in retry_exhausted{op=elastic.supervisor}."""
    reg = mx.telemetry.registry()
    sup = mx.elastic.Supervisor(retries=2, backoff_s=0.0, **NOSLEEP)
    pol = sup.retry_policy()
    assert pol.max_attempts == 3
    assert pol.is_retryable(mx.elastic.WedgeAbort("x"))
    assert not pol.is_retryable(mx.elastic.Preempted("x"))
    assert not pol.is_retryable(OSError("x"))

    calls = []
    ex0 = reg.counter("retry_exhausted",
                      labels={"op": "elastic.supervisor"}).value
    def always_wedged(resume):
        calls.append(resume)
        raise mx.elastic.WedgeAbort("synthetic wedge")
    with pytest.raises(mx.elastic.WedgeAbort):
        sup.run(always_wedged)
    assert calls == [False, True, True]
    assert reg.counter("retry_exhausted",
                       labels={"op": "elastic.supervisor"}).value == ex0 + 1
    assert sup.retries_done == 3


def test_point_guard_is_noop_when_disarmed():
    """The zero-overhead contract's functional half: with nothing armed
    every point is a silent no-op."""
    for name in faults.POINTS:
        faults.point(name)
