"""Device-resident training-health statistics over the fused step.

The per-layer window into training the stack was missing: ``Monitor``
forces the per-op execution path and a host sync per sampled tensor,
which is unusable at production cadence and blind on the fused step
where real training runs. This module computes the health stats **on
device, inside the fused step program itself** — per parameter class:

  * grad L2 norm                  (vanishing/exploding gradients)
  * weight L2 norm                (weight blow-up)
  * update ratio ‖Δw‖/‖w‖         (lr too high/low)
  * grad max-abs                  (bf16 overflow precursor: the ~3e38
                                   f32 ceiling is unreachable, the
                                   ~3.4e38-but-8-bit-mantissa bf16 path
                                   saturates much earlier)
  * nonfinite element count       (grads AND fresh weights — an LR bomb
                                   is caught on the step that fired it)

— batched per **parameter class** (the ``fuse_opt`` update grouping,
so the stat row count stays bounded on transformer-scale graphs), and
synced to host **only at the existing metric-sync cadence**: the stat
accumulator registers as a *rider* on the fit loop's
:class:`~mxtpu.metric.DeviceMetricAccum`, whose ``sync()`` already is
the one intended host round-trip — health adds exactly zero sync
points (``tests/test_health.py`` pins the counter delta at 0).

On the host side of each cadence a deterministic
:class:`~mxtpu.obs.detectors.DetectorSuite` turns the stats + the
metric's window loss into Findings, ``health_anomalies{kind}``
counters and flight events; ``MXTPU_HEALTH_ACTION=rollback`` arms the
supervisor action seam so a divergence aborts the wedged trajectory
and restores the last good elastic generation (docs/elastic.md).

Arm with ``Module.fit(health=True)`` or ``MXTPU_HEALTH=1``; tune via
``health.cadence`` / ``health.window`` / ``health.spike_k``
(docs/tune.md). Surfaces: ``train_health{layer_class,stat}`` gauges,
the ``training_health`` block of ``/debug/state``, the ``mxtpu_top``
health panel, corpus ``health`` rows.
"""
from __future__ import annotations

import logging
import os

from .. import telemetry as _tel
from ..analysis import concurrency as _conc
from .detectors import DetectorSuite, HealthPolicy

__all__ = ["HealthAccum", "HealthSession", "HealthPolicy",
           "DetectorSuite", "class_label", "armed_by_env", "panel",
           "STATS"]

log = logging.getLogger("mxtpu.obs.health")

#: the stat columns of one class row, in device layout order: the fused
#: step returns a (C, 4) sum matrix [grad_sq, weight_sq, update_sq,
#: nonfinite] plus a (C,) grad max-abs vector per step
SUM_COLS = ("grad_sq", "weight_sq", "update_sq", "nonfinite")
#: the derived per-cadence stats the gauges/panel/corpus expose
STATS = ("grad_norm", "weight_norm", "update_ratio", "grad_max",
         "nonfinite")

_LOCK = _conc.lock("health", "_PANEL_LOCK")
_ACTIVE = None        # the live fit's HealthSession
_LAST_PANEL = None    # the closed fit's final panel (postmortem reads)


def armed_by_env():
    """True when ``MXTPU_HEALTH`` requests the health stats."""
    v = os.environ.get("MXTPU_HEALTH", "").strip().lower()
    return v not in ("", "0", "false", "no", "off")


def class_label(names):
    """Stable display label for a parameter class: the members' common
    prefix when they share one (``fc*[3]``), else the (single) name."""
    names = list(names)
    if len(names) == 1:
        return names[0]
    prefix = os.path.commonprefix(names).rstrip("_.:")
    return "%s*[%d]" % (prefix or names[0], len(names))


def panel():
    """The ``training_health`` block for ``diagnostics.debug_state()``:
    the live session's snapshot, or the most recently closed fit's
    final panel (marked ``armed: False``) so a post-fit postmortem
    still shows the last known training state. None when health never
    armed in this process."""
    s = _ACTIVE
    if s is None:
        return _LAST_PANEL
    try:
        return s.panel_snapshot()
    except Exception:
        # mxtpu: allow-swallow(a debug panel read must never break the
        # postmortem that asked for it)
        return _LAST_PANEL


class HealthAccum:
    """Device-resident accumulator over the fused step's per-class stat
    rows — the health twin of :class:`~mxtpu.metric.DeviceMetricAccum`.
    ``update`` folds one step's (C,4) sums / (C,) maxes with a jitted
    add/maximum program (async dispatch, nothing transferred); ``pull``
    hands the device tree to whoever owns the cadence's ONE host round
    trip (the metric accum's rider sync, or the session's direct pull
    when no device metric path exists)."""

    def __init__(self, n_classes):
        self.n_classes = int(n_classes)
        self._fn = None
        self._sums = None   # device (C, 4) after the first step
        self._max = None    # device (C,)
        self._taps = None   # latest step's monitor-tap dict (device)
        self._steps = 0

    def _build_fn(self):
        import jax
        import jax.numpy as jnp

        def fold(sums, mx, batch_sums, batch_max):
            return sums + batch_sums, jnp.maximum(mx, batch_max)

        from ..executor import named_jit, record_program_build
        return record_program_build(
            "health_accum", self, named_jit("health_accum", fold))

    def update(self, hstats):
        """Fold one fused step's stat rows in (device-only)."""
        sums, mx = hstats["sums"], hstats["max"]
        if self._sums is None:
            self._sums, self._max = sums, mx
        else:
            if self._fn is None:
                self._fn = self._build_fn()
            self._sums, self._max = self._fn(self._sums, self._max,
                                             sums, mx)
        self._taps = hstats.get("taps", self._taps)
        self._steps += 1

    def pull(self):
        """The pending device tree for the cadence's bulk host read, or
        None when nothing accumulated."""
        if self._steps == 0 and self._taps is None:
            return None
        tree = {"sums": self._sums, "max": self._max}
        if self._taps is not None:
            tree["taps"] = self._taps
        return tree

    def finish(self):
        """Close the window after its host values landed: returns the
        step count and zeroes the device state."""
        steps = self._steps
        self._sums = self._max = self._taps = None
        self._steps = 0
        return steps


# loss-like metric children (CrossEntropy 'cross-entropy', Loss 'loss',
# MSE/MAE/RMSE, NegativeLogLikelihood, Perplexity): the detector
# baselines need a loss, not an accuracy — a metric with no loss-like
# child runs the nonfinite/stat detectors only
_LOSSY = ("entropy", "loss", "mse", "mae", "rmse", "perplex",
          "likelihood")


class HealthSession:
    """One fit's health pipeline: arms the fused step's stat kernels,
    accumulates per step, rides the metric-sync cadence, runs the
    detector suite, and owns every surface (gauges, flight, corpus,
    panel, policy action)."""

    def __init__(self, fused, monitor=None, detect=True, logger=None):
        from ..tune import registry as _knobs
        self.fused = fused
        self.monitor = monitor
        self.detect = bool(detect)
        self.logger = logger or log
        taps = monitor.re_prog.pattern if monitor is not None else None
        self.classes = fused.arm_health(taps=taps)
        self.labels = [lbl for lbl, _ in self.classes]
        self.accum = HealthAccum(len(self.labels))
        self.window = _knobs.resolve_int("health.window", floor=2)
        self.spike_k = float(_knobs.resolve("health.spike_k"))
        self.cadence = _knobs.resolve_int("health.cadence", floor=1)
        self.suite = DetectorSuite(window=self.window,
                                   spike_k=self.spike_k)
        self.policy = HealthPolicy.from_env()
        self.cadences = 0          # cadence syncs consumed
        self.detections = 0
        self.findings = []         # bounded recent-Finding ring
        self._delivered = None     # (host tree, steps) awaiting on_cadence
        self._loss_prev = None     # (sum_metric, num_inst) at last window
        self._last = {}            # label -> latest stat dict (panel)
        self._last_steps = None    # fused steps in the latest window
        self._last_loss = None
        self._panel = None
        self._san_trips = self._sanitizer_trips()
        global _ACTIVE
        _ACTIVE = self

    def close(self):
        global _ACTIVE, _LAST_PANEL
        if _ACTIVE is self:
            _ACTIVE = None
        with _LOCK:
            if self._panel:
                _LAST_PANEL = dict(self._panel, armed=False)

    # ------------------------------------------------------- per step
    def on_step(self):
        """Fold the step the module just dispatched (device-only)."""
        h = self.fused.last_health
        if h is not None:
            self.accum.update(h)
            self.fused.last_health = None   # never double-count a step

    # ------------------------------------------------- cadence plumbing
    # rider protocol (DeviceMetricAccum.add_rider): pull() hands the
    # device tree into the accum's ONE cadence device_get; deliver()
    # receives the host values from that same transfer
    def pull(self):
        return self.accum.pull()

    def deliver(self, host_tree):
        self._delivered = (host_tree, self.accum.finish())

    def sync_direct(self):
        """The cadence pull when no DeviceMetricAccum exists to ride
        (``device_metrics=False`` paths): health then owns the cadence's
        single round trip itself."""
        tree = self.pull()
        if tree is None:
            return
        import jax
        # mxtpu: allow-sync(the health cadence sync point when no device
        # metric accum exists — the cadence's one intended round trip)
        self.deliver(jax.device_get(tree))

    # ---------------------------------------------------- the cadence
    def on_cadence(self, eval_metric=None):
        """Consume the delivered window: derive stats, emit gauges/
        series, run detectors at the ``health.cadence`` stride, act."""
        if self._delivered is None:
            return None
        host, steps = self._delivered
        self._delivered = None
        self.cadences += 1
        taps = host.get("taps")
        if taps is not None and self.monitor is not None:
            self.monitor._deliver_taps(taps)
        if steps <= 0:
            return None
        self._last_steps = steps
        stats = self._derive(host, steps)
        self._emit_gauges(stats)
        self._last = stats
        loss = self._window_loss(eval_metric)
        findings = []
        if self.detect and self.cadences % self.cadence == 0:
            findings = self.suite.observe(loss, stats)
            for f in findings:
                self._surface(f)
        # EVERY cadence advances the corpus record — off-stride and
        # anomaly-free ones included — so the learned cost/outcome
        # model sees the full stat stream, not just the wreckage
        from . import corpus as _corpus
        if _corpus.enabled():
            _corpus.record_health(
                self.cadences, stats, loss=loss,
                anomalies=[f.details.get("kind")
                           for f in findings] or None)
        div = [f for f in findings
               if f.details.get("kind") == "divergence"]
        if div:
            self._act(div[0])
        self._san_trips = self._sanitizer_trips()
        self._refresh_panel(stats, loss)
        return findings

    # ------------------------------------------------------- internals
    def _derive(self, host, steps):
        import numpy as np
        # mxtpu: allow-sync(host payload already materialized by the
        # metric-sync rider device_get; these are host-numpy views)
        sums = np.asarray(host["sums"], dtype=np.float32)
        # mxtpu: allow-sync(same rider payload as above)
        gmax = np.asarray(host["max"], dtype=np.float32)
        stats = {}
        inv = 1.0 / float(steps)
        for i, label in enumerate(self.labels):
            g2, w2, u2, nf = (float(sums[i, 0]), float(sums[i, 1]),
                              float(sums[i, 2]), float(sums[i, 3]))
            stats[label] = {
                "grad_norm": float(np.sqrt(max(0.0, g2 * inv))),
                "weight_norm": float(np.sqrt(max(0.0, w2 * inv))),
                # ratio of window sums == ratio of window means: the
                # steps factor cancels, so no extra rounding enters
                "update_ratio": float(np.sqrt(u2 / w2)) if w2 > 0
                else 0.0,
                "grad_max": float(gmax[i]),
                "nonfinite": int(nf),
            }
        return stats

    def _emit_gauges(self, stats):
        for label, s in stats.items():
            for stat in STATS:
                try:
                    _tel.gauge(
                        "train_health",
                        labels={"layer_class": label, "stat": stat},
                        help="per-parameter-class training-health stat "
                             "as of the latest metric-sync cadence "
                             "(obs/health.py)").set(float(s[stat]))
                except (TypeError, ValueError):
                    continue

    def _window_loss(self, eval_metric):
        """Mean loss over the cadence window from the metric's own
        sums — exact deltas of (sum_metric, num_inst), no extra device
        work, deterministic. None when the metric has no loss-like
        child or the window is empty (epoch reset)."""
        child = self._loss_child(eval_metric)
        if child is None:
            self._last_loss = None
            return None
        cur = (float(child.sum_metric), int(child.num_inst))
        prev = self._loss_prev
        self._loss_prev = cur
        if prev is None or cur[1] <= prev[1]:
            return None   # first window, or an epoch reset in between
        loss = (cur[0] - prev[0]) / float(cur[1] - prev[1])
        self._last_loss = loss
        return loss

    def _loss_child(self, eval_metric):
        if eval_metric is None:
            return None
        from ..metric import _flatten_metrics
        try:
            children = _flatten_metrics(eval_metric)
        except Exception:
            return None
        for c in children:
            name = str(getattr(c, "name", "")).lower()
            if any(t in name for t in _LOSSY):
                return c
        return None

    def _surface(self, finding):
        kind = finding.details.get("kind", "unknown")
        self.detections += 1
        _tel.counter(
            "health_anomalies", labels={"kind": kind},
            help="training-health detector firings by anomaly kind "
                 "(obs/detectors.py)").inc()
        from .. import diagnostics as _diag
        _diag.record("health", kind, finding.message)
        self.logger.warning("training health: %s", finding.message)
        self.findings.append(finding)
        del self.findings[:-16]

    def _sanitizer_trips(self):
        from ..analysis import sanitizer as _san
        return _san.trip_count()

    def _act(self, finding):
        """The divergence action: postmortem (unless the sanitizer
        already captured one for the SAME nonfinite this window — one
        postmortem per root cause), then the rollback seam if armed."""
        from .. import diagnostics as _diag
        if self._sanitizer_trips() == self._san_trips:
            _diag.postmortem("health: %s" % finding.message,
                             source="health")
        else:
            self.logger.info(
                "training health: sanitizer already captured this "
                "window's nonfinite — skipping the duplicate postmortem")
        if self.policy.action == "rollback":
            reason = "health divergence: %s" % finding.message
            self.logger.warning(
                "training health: rollback armed — firing the "
                "supervisor action seam (%s)", reason)
            from ..diagnostics import watchdog as _wd
            _wd.fire_actions(reason)

    def _refresh_panel(self, stats, loss):
        anomalies = {}
        for f in self.findings:
            k = f.details.get("kind", "unknown")
            anomalies[k] = anomalies.get(k, 0) + 1
        snap = {
            "armed": True,
            "detect": self.detect,
            "action": self.policy.action,
            "cadences": self.cadences,
            "steps_per_cadence": self._last_steps,
            "window_loss": loss,
            "classes": [dict(stats[lbl], **{"class": lbl})
                        for lbl in self.labels if lbl in stats],
            "anomalies": anomalies,
            "recent": [f.message for f in self.findings[-4:]],
        }
        with _LOCK:
            self._panel = snap

    def panel_snapshot(self):
        with _LOCK:
            return dict(self._panel) if self._panel else {
                "armed": True, "detect": self.detect,
                "action": self.policy.action, "cadences": 0,
                "classes": [], "anomalies": {}, "recent": []}
