"""mxtpu.elastic — async checkpointing, exact fit-resume, preemption
recovery (docs/elastic.md). The contracts:

* **kill-at-step-N resume parity** (THE gate): a fit killed at step N
  and resumed from its elastic snapshot matches an uninterrupted fit
  BIT-EXACT on weights and exactly on integer-summed metrics — on the
  plain fused path, under ``MXTPU_PIPELINE=bf16`` (f32 masters), and on
  the forced 8-device CPU mesh (weight-update sharding preserved);
* **crash-window atomicity**: a generation is durable only after its
  pointer flip; a writer killed mid-serialize (or a torn data file)
  leaves the previous generation loadable;
* **supervision**: a watchdog wedge detection triggers
  checkpoint-restore-retry through :class:`Supervisor.run` without
  human intervention, and SIGTERM flushes a final snapshot before
  :class:`Preempted` propagates;
* epoch checkpoint callbacks ride the async snapshot writer and keep
  the fused params device-resident through a checkpointing fit.
"""
import json
import os
import signal
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import metric as M
from mxtpu.elastic import snapshot as esnap
from mxtpu.models import mlp as _mlp


class Kill(Exception):
    """Simulated hard death of the training process."""


def _mnist_like(n=256, seed=7):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, 784).astype("float32"),
            rng.randint(0, 10, n).astype("float32"))


def _make_iter(batch_size=64, shuffle=False):
    X, y = _mnist_like()
    return mx.io.NDArrayIter(X, y, batch_size=batch_size, shuffle=shuffle,
                             label_name="softmax_label")


def _fit(num_epoch=2, seed=11, kill_at_step=None, module=None,
         optimizer="sgd", opt_params=None, **fit_kwargs):
    """One mlp fit; ``kill_at_step`` raises Kill after that many batch
    callbacks (1-based), simulating the process dying mid-epoch."""
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
                optimizer=optimizer,
                optimizer_params=opt_params or {"learning_rate": 0.05,
                                                "momentum": 0.9},
                initializer=mx.initializer.Xavier(),
                batch_end_callback=cb, metric_sync=2, **fit_kwargs)
    except Kill:
        pass
    weights = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    return dict(metric.get_name_value()), weights, mod


def _assert_resume_parity(tmp_path, kill_at_step=3, **fit_kwargs):
    """Uninterrupted vs killed-at-step-N + resumed: weights bit-exact,
    integer-summed metrics exact."""
    prefix = str(tmp_path / "ck")
    m_full, w_full, _ = _fit(**fit_kwargs)
    cfg = mx.elastic.ElasticConfig(prefix, every_n_steps=1, sync=True)
    _fit(kill_at_step=kill_at_step, elastic=cfg, **fit_kwargs)
    man = esnap.latest_manifest(prefix)
    assert man is not None and man["cursor"]["global_step"] == kill_at_step
    m_res, w_res, mod = _fit(resume=prefix, elastic=False, **fit_kwargs)
    for k in w_full:
        np.testing.assert_array_equal(
            w_full[k], w_res[k],
            err_msg="weights diverged at %s: resume is not exact" % k)
    assert m_full["accuracy"] == m_res["accuracy"], (m_full, m_res)
    # float sums may differ in summation order only
    np.testing.assert_allclose(m_full["cross-entropy"],
                               m_res["cross-entropy"], rtol=1e-5)
    return mod


# --------------------------------------------------------- THE parity gate
def test_kill_at_step_resume_parity(tmp_path):
    mod = _assert_resume_parity(tmp_path)
    assert mod._fused is not None


def test_kill_at_step_resume_parity_bf16(tmp_path):
    """Same gate under the bf16 mixed-precision rewrite: the snapshot
    carries the f32 masters (the fused state's params ARE the masters)
    and resume is still bit-exact."""
    from mxtpu.compile import pipeline as P
    os.environ["MXTPU_PIPELINE"] = "bf16"
    P.configure(None)
    try:
        mod = _assert_resume_parity(tmp_path)
        rep = mod._fused.pipeline_report
        assert rep is not None and "bf16" in rep.applied, \
            "bf16 rewrite was not applied — gate would not cover masters"
        for v in mod.get_params()[0].values():
            assert v.dtype == np.float32  # masters, not bf16
    finally:
        os.environ.pop("MXTPU_PIPELINE", None)
        P.configure(None)   # re-read: env now unset -> empty


def test_kill_at_step_resume_parity_mesh(tmp_path):
    """Same gate on the forced 8-device CPU mesh: the snapshot writes
    the optimizer state per-shard with specs in the manifest, and the
    restored state keeps the PR-6 weight-update sharding split."""
    import jax
    from jax.sharding import PartitionSpec as P
    mod = _assert_resume_parity(tmp_path, mesh=8)
    fused = mod._fused
    assert fused is not None and fused._plan is not None
    leaf = jax.tree.leaves(fused.opt_state["fc1_weight"])[0]
    assert leaf.sharding.spec == P("data"), leaf.sharding.spec
    assert len(leaf.sharding.device_set) == 8
    # the manifest really recorded per-shard pieces, not a global dump
    man = esnap.latest_manifest(str(tmp_path / "ck"))
    entry = man["opt_entries"]["fc1_weight"]
    assert entry["spec"] == ["data"]
    assert len(entry["shards"]["0"]["pieces"]) == 8


def test_resume_from_epoch_boundary_snapshot(tmp_path):
    """With epoch-cadence snapshots only, a mid-epoch kill resumes from
    the epoch boundary and replays the epoch — still bit-exact (RNG
    streams restored to the boundary state)."""
    prefix = str(tmp_path / "ck")
    m_full, w_full, _ = _fit()
    cfg = mx.elastic.ElasticConfig(prefix, every_n_steps=0, sync=True)
    _fit(kill_at_step=6, elastic=cfg)          # dies inside epoch 1
    man = esnap.latest_manifest(prefix)
    assert man["cursor"]["epoch_boundary"] is True
    assert man["cursor"]["epoch"] == 0
    # a manifest from before the tuner went may carry a "tuned" field:
    # it is ignored and the generation still loads
    path = esnap.manifest_path(prefix, man["_generation"])
    raw = json.load(open(path))
    raw["tuned"] = {"registry_version": "deadbeef0000",
                    "values": {"fit.max_in_flight": 4}}
    with open(path, "w") as f:
        json.dump(raw, f)
    m_res, w_res, _ = _fit(resume=prefix, elastic=False)
    for k in w_full:
        np.testing.assert_array_equal(w_full[k], w_res[k], err_msg=k)
    assert m_full["accuracy"] == m_res["accuracy"]


def test_epoch_boundary_snapshot_carries_post_reset_iterator(tmp_path):
    """An epoch-boundary generation must record the POST-reset iterator
    state: a reshuffling iterator (BucketSentenceIter) has already drawn
    the next epoch's schedule when the snapshot is taken, and a boundary
    resume must replay that schedule — not the fresh iterator's
    construction-time shuffle."""
    prefix = str(tmp_path / "ck")
    cfg = mx.elastic.ElasticConfig(prefix, every_n_steps=0, sync=True)
    _fit(num_epoch=1, elastic=cfg)
    man = esnap.latest_manifest(prefix)
    assert man["cursor"]["epoch_boundary"] is True
    assert man["iterator"]["supported"] is True
    state = mx.elastic.ResumeState(man, esnap.load_arrays(man))
    it_state = state.iterator_state()
    # post-reset NDArrayIter cursor: one batch BEFORE the first
    assert it_state["cursor"] == -64


def test_resume_adam_counters(tmp_path):
    """Adam's bias correction reads the per-index update counts — a
    resume that lost them would silently rescale lr. Exactness of the
    resumed weights proves the counters round-tripped."""
    _assert_resume_parity(tmp_path, optimizer="adam",
                          opt_params={"learning_rate": 0.003})


# ------------------------------------------------------- atomicity / files
def test_generation_pointer_and_prune(tmp_path):
    prefix = str(tmp_path / "run")
    w = esnap.writer()
    for g in (1, 2, 3, 4):
        w.submit(esnap.SnapshotJob(
            "generation", {"arg:w": np.full(4, g, "f4")}, prefix=prefix,
            generation=g, keep=2,
            manifest={"format": esnap.FORMAT,
                      "cursor": {"epoch": 0, "nbatch": g,
                                 "global_step": g}}))
    w.flush()
    man = esnap.latest_manifest(prefix)
    assert man["_generation"] == 4
    assert esnap.load_arrays(man)["arg:w"][0] == 4.0
    assert esnap.list_generations(prefix) == [3, 4]  # keep=2 pruned 1, 2


def test_torn_generation_falls_back(tmp_path):
    """Crash-window contract: a generation whose data file is torn (or
    missing) must not load — the previous generation does."""
    prefix = str(tmp_path / "run")
    w = esnap.writer()
    w.submit(esnap.SnapshotJob(
        "generation", {"arg:w": np.arange(4, dtype="f4")}, prefix=prefix,
        generation=1,
        manifest={"format": esnap.FORMAT,
                  "cursor": {"epoch": 0, "nbatch": 0, "global_step": 1}}))
    w.flush()
    # a torn gen 2: manifest + pointer landed, data file truncated
    # (the reverse order of the writer's protocol — simulates the worst
    # case of a crash + a buggy writer; load must still not trust it)
    base = esnap.data_basename(prefix, 2)
    data_path = str(tmp_path / base)
    with open(data_path, "wb") as f:
        f.write(b"MXTPU001\x00")  # truncated mid-header
    man2 = {"format": esnap.FORMAT,
            "cursor": {"epoch": 0, "nbatch": 1, "global_step": 2},
            "data_files": {base: {"bytes": 9999}}}
    with open(esnap.manifest_path(prefix, 2), "w") as f:
        json.dump(man2, f)
    with open(esnap.pointer_path(prefix), "w") as f:
        json.dump({"format": esnap.FORMAT, "generation": 2,
                   "manifest": os.path.basename(
                       esnap.manifest_path(prefix, 2))}, f)
    man = esnap.latest_manifest(prefix)
    assert man is not None and man["_generation"] == 1
    np.testing.assert_array_equal(esnap.load_arrays(man)["arg:w"],
                                  np.arange(4, dtype="f4"))


def test_writer_killed_mid_serialize_keeps_previous(tmp_path,
                                                    monkeypatch):
    """Kill the writer inside the data serialize: the tmp file may be
    torn but no manifest/pointer flips — the previous generation loads
    and the error is counted, not raised into training."""
    from mxtpu import telemetry as tel
    prefix = str(tmp_path / "run")
    w = esnap.writer()
    w.submit(esnap.SnapshotJob(
        "generation", {"arg:w": np.ones(4, "f4")}, prefix=prefix,
        generation=1,
        manifest={"format": esnap.FORMAT,
                  "cursor": {"epoch": 0, "nbatch": 0, "global_step": 1}}))
    w.flush()

    def _die(path, arrays):
        with open(path, "wb") as f:
            f.write(b"MXTPU0")      # partial magic, then "power loss"
        raise OSError("simulated writer death mid-serialize")

    monkeypatch.setattr(esnap, "_write_ndsave_atomic", _die)
    errs0 = tel.registry().counter("elastic_snapshot_errors").value
    w.submit(esnap.SnapshotJob(
        "generation", {"arg:w": np.full(4, 2.0, "f4")}, prefix=prefix,
        generation=2,
        manifest={"format": esnap.FORMAT,
                  "cursor": {"epoch": 0, "nbatch": 1, "global_step": 2}}))
    w.flush()
    monkeypatch.undo()
    assert tel.registry().counter("elastic_snapshot_errors").value == \
        errs0 + 1
    man = esnap.latest_manifest(prefix)
    assert man["_generation"] == 1
    assert esnap.load_arrays(man)["arg:w"][0] == 1.0


# ----------------------------------------------------------- supervision
def test_watchdog_action_hook_fires_after_postmortem():
    from mxtpu.diagnostics import Watchdog, add_action, remove_action
    seen = []
    add_action(seen.append)
    try:
        wd = Watchdog(interval=0.01, engine_stall_s=0.02, wait_stall_s=99,
                      engine_probe=lambda: (3, 7))
        t0 = time.monotonic()
        while not seen and time.monotonic() - t0 < 3.0:
            time.sleep(0.03)
            wd.check()
    finally:
        remove_action(seen.append)
    assert seen and "engine stalled" in seen[0]
    pm = mx.diagnostics.last_postmortem()
    assert pm is not None and pm["source"] == "watchdog"


def test_watchdog_restore_retry_end_to_end(tmp_path):
    """The acceptance gate's recovery half: a fit wedged mid-flight (the
    wedged-fake-engine fixture) is detected by the watchdog, aborted at
    the next step boundary, restored from the last durable generation,
    retried, and completes — no human in the loop, and the final numbers
    match an uninterrupted fit."""
    from mxtpu.diagnostics import Watchdog
    prefix = str(tmp_path / "ck")
    m_full, w_full, _ = _fit()

    wedge = {"on": False}
    wd = Watchdog(interval=0.01, engine_stall_s=0.03, wait_stall_s=99,
                  engine_probe=lambda: (3, 7) if wedge["on"] else (0, 0)
                  ).start()
    sup = mx.elastic.Supervisor(retries=2, backoff_s=0.05)
    cfg = mx.elastic.ElasticConfig(prefix, every_n_steps=1, sync=True,
                                   supervisor=sup)
    mod = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    metric = M.create(["acc", "ce"])
    attempts = []

    def fit_fn(resume):
        attempts.append(resume)
        if len(attempts) == 1:
            def cb(param):
                if param.nbatch == 2:     # wedge mid-epoch, attempt 1
                    wedge["on"] = True
                    time.sleep(0.2)       # let the watchdog sample it
        else:
            wedge["on"] = False
            cb = None
        mx.random.seed(11)
        np.random.seed(11)
        mod.fit(_make_iter(), num_epoch=2, eval_metric=metric,
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
                initializer=mx.initializer.Xavier(),
                batch_end_callback=cb, metric_sync=2,
                elastic=cfg, resume=resume)

    try:
        sup.run(fit_fn)
    finally:
        wd.stop()
    assert attempts == [False, True]
    assert sup.retries_done == 1
    assert m_full["accuracy"] == dict(metric.get_name_value())["accuracy"]
    w_sup = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in w_full:
        np.testing.assert_array_equal(w_full[k], w_sup[k], err_msg=k)


def test_supervisor_gives_up_after_bounded_retries():
    sup = mx.elastic.Supervisor(retries=2, backoff_s=0.0)
    calls = []

    def always_wedged(resume):
        calls.append(resume)
        raise mx.elastic.WedgeAbort("synthetic wedge")

    with pytest.raises(mx.elastic.WedgeAbort):
        sup.run(always_wedged)
    assert calls == [False, True, True]     # 1 try + 2 bounded retries


def test_sigterm_flushes_final_snapshot_then_resume(tmp_path):
    """SIGTERM-as-preemption-warning: the handler flags, the fit flushes
    a FINAL durable snapshot at the next step boundary and raises
    Preempted; a later fit(resume=) continues from it."""
    prefix = str(tmp_path / "ck")
    sup = mx.elastic.Supervisor()
    assert sup.install_sigterm()
    cfg = mx.elastic.ElasticConfig(prefix, supervisor=sup)  # no cadence

    def cb(param):
        if param.nbatch == 1:
            os.kill(os.getpid(), signal.SIGTERM)

    mod = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    mx.random.seed(11)
    np.random.seed(11)
    try:
        with pytest.raises(mx.elastic.Preempted):
            mod.fit(_make_iter(), num_epoch=2, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.05},
                    initializer=mx.initializer.Xavier(),
                    batch_end_callback=cb, elastic=cfg)
    finally:
        sup.uninstall_sigterm()
    man = esnap.latest_manifest(prefix)
    assert man is not None and man["cursor"]["global_step"] == 3
    # the next incarnation resumes and completes
    metric = M.create(["acc", "ce"])
    mod2 = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    mx.random.seed(11)
    np.random.seed(11)
    mod2.fit(_make_iter(), num_epoch=2, eval_metric=metric,
             optimizer="sgd", optimizer_params={"learning_rate": 0.05},
             initializer=mx.initializer.Xavier(), resume=prefix)
    assert metric.get_name_value()


# ------------------------------------------------- epoch checkpoints / io
def test_epoch_checkpoint_callbacks_ride_async_writer(tmp_path):
    """module_checkpoint/do_checkpoint go through the snapshot writer:
    the fused step stays armed with device-resident params, fit never
    round-trips params for the elastic-aware callback (set_params spy),
    and the files load back equal to the live weights."""
    prefix_m = str(tmp_path / "modck")
    prefix_d = str(tmp_path / "dock")
    mod = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    set_calls = []
    orig = mod.set_params
    mod.set_params = lambda *a, **k: (set_calls.append(1),
                                      orig(*a, **k))[1]
    mx.random.seed(11)
    np.random.seed(11)
    mod.fit(_make_iter(), num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            initializer=mx.initializer.Xavier(),
            epoch_end_callback=mx.callback.module_checkpoint(
                mod, prefix_m, save_optimizer_states=True))
    assert mod._fused is not None and mod._params_device_resident()
    assert not set_calls, \
        "fit round-tripped params for an elastic-aware checkpoint callback"
    mx.model.wait_checkpoints()
    sym, args, auxs = mx.model.load_checkpoint(prefix_m, 2)
    live = mod.get_params()[0]
    for k, v in args.items():
        np.testing.assert_array_equal(v.asnumpy(), live[k].asnumpy(),
                                      err_msg=k)
    # versioned manifest landed beside the legacy file
    man = json.load(open(prefix_m + "-0002.params.manifest.json"))
    assert man["format"] == "mxtpu-checkpoint-1"
    assert sorted(args) == man["params"]
    # optimizer states file round-trips through the writer too
    mod.load_optimizer_states(prefix_m + "-0002.states")

    # do_checkpoint still receives (device-backed) params and writes
    mod2 = mx.mod.Module(_mlp.get_symbol(10), context=mx.cpu())
    mx.random.seed(11)
    np.random.seed(11)
    mod2.fit(_make_iter(), num_epoch=1, optimizer="sgd",
             optimizer_params={"learning_rate": 0.05},
             initializer=mx.initializer.Xavier(),
             epoch_end_callback=mx.callback.do_checkpoint(prefix_d))
    assert mod2._fused is not None
    mx.model.wait_checkpoints()
    _, args2, _ = mx.model.load_checkpoint(prefix_d, 1)
    live2 = mod2.get_params()[0]
    for k, v in args2.items():
        np.testing.assert_array_equal(v.asnumpy(), live2[k].asnumpy(),
                                      err_msg=k)


def test_ndarrayiter_cursor_roundtrip():
    """The shuffle permutation travels with the cursor: a freshly
    constructed (differently shuffled) iterator restored from the state
    yields the exact continuation of the original stream."""
    X, y = _mnist_like(n=96)
    np.random.seed(3)
    it1 = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=True)
    batches = []
    for i, b in enumerate(it1):
        if i == 2:
            state = it1.checkpoint_state()
        batches.append(b.data[0].asnumpy())
    np.random.seed(99)  # a resumed process draws a different shuffle
    it2 = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=True)
    assert it2.restore_state(state)
    for want in batches[3:]:
        got = next(it2).data[0].asnumpy()
        np.testing.assert_array_equal(want, got)
    # shape mismatch declines (resume then replays instead)
    it3 = mx.io.NDArrayIter(X[:32], y[:32], batch_size=16, shuffle=True)
    assert not it3.restore_state(state)


def test_bucket_sentence_iter_cursor_roundtrip():
    import random as pyrandom
    sent = [[i % 17 + 1] * (3 + i % 5) for i in range(60)]
    pyrandom.seed(5)
    np.random.seed(5)
    it1 = mx.rnn.BucketSentenceIter(sent, batch_size=4, buckets=[4, 8])
    firsts = []
    for i, b in enumerate(it1):
        if i == 1:
            state = it1.checkpoint_state()
        firsts.append((b.bucket_key, b.data[0].asnumpy()))
    pyrandom.seed(77)
    np.random.seed(77)
    it2 = mx.rnn.BucketSentenceIter(sent, batch_size=4, buckets=[4, 8])
    assert it2.restore_state(state)
    for want_key, want in firsts[2:]:
        got = next(it2)
        assert got.bucket_key == want_key
        np.testing.assert_array_equal(want, got.data[0].asnumpy())


def test_snapshot_series_emitted(tmp_path):
    from mxtpu import telemetry as tel
    reg = tel.registry()
    prefix = str(tmp_path / "ck")
    cfg = mx.elastic.ElasticConfig(prefix, every_n_steps=2, sync=True)
    b0 = reg.counter("elastic_snapshot_bytes").value
    s0 = reg.histogram("elastic_snapshot_stall_ms").count
    _fit(num_epoch=1, elastic=cfg)
    assert reg.counter("elastic_snapshot_bytes").value > b0
    assert reg.histogram("elastic_snapshot_stall_ms").count > s0
    assert reg.gauge("elastic_snapshot_age_s").value >= 0.0
