"""The knob registry: every declared tunable constant of the framework.

One :class:`Knob` declaration per tunable: name, owning subsystem,
value kind, the default, and the environment name that overrides it.
``fit`` / serving / decode / elastic / compile pull their defaults
through :func:`resolve`, which holds the one precedence rule:

    default  <  environment variable  <  explicit argument

``registry_version()`` fingerprints the declarations; the measurement
corpus (``obs/corpus.py``) stamps its rows with it.

This module is intentionally stdlib-only at import time: consumers
(``compile.pipeline``, ``serving.pool``) resolve knobs during their own
module import, before the ``mxtpu`` package finishes initializing.
"""
from __future__ import annotations

import hashlib
import os
from collections import OrderedDict

from ..analysis import concurrency as _conc

__all__ = ["Knob", "declare", "get_knob", "knobs", "registry_version",
           "resolve", "resolve_int", "catalog_table"]


class Knob:
    """One declared tunable.

    * ``name``       — dotted ``<subsystem>.<knob>`` id;
    * ``kind``       — ``int`` / ``float`` / ``bool`` / ``str``; the
      ``*_or_none`` suffix admits None ("auto" — the consumer derives
      the value itself when the knob resolves to None);
    * ``default``    — the value used when nothing overrides it;
    * ``env``        — the environment override (empty-string env
      values read as unset);
    * ``help``       — one line for the generated catalog.
    """

    __slots__ = ("name", "subsystem", "kind", "default", "env", "help")

    def __init__(self, name, kind, default, env=None, help=""):
        self.name = str(name)
        self.subsystem = self.name.split(".", 1)[0]
        self.kind = kind
        self.default = default
        self.env = env
        self.help = help

    # ------------------------------------------------------------ coerce
    def coerce(self, value):
        """Normalize ``value`` to this knob's kind. String inputs follow
        the SAME parse the subsystem's env read used (bools via
        ``!= "0"``), so moving an env behind the registry cannot change
        what any existing setting means."""
        base = self.kind.replace("_or_none", "")
        if value is None:
            if self.kind.endswith("_or_none") or base == "str":
                return None if self.kind.endswith("_or_none") else ""
            raise ValueError("knob %s: None is not a legal %s"
                             % (self.name, self.kind))
        if base == "int":
            return int(float(value)) if isinstance(value, str) \
                else int(value)
        if base == "float":
            return float(value)
        if base == "bool":
            if isinstance(value, str):
                return value != "0"   # the env contract: only "0" is off
            return bool(value)
        if base == "str":
            return str(value)
        raise ValueError("knob %s: unknown kind %r" % (self.name, self.kind))

    def fingerprint(self):
        """Identity + semantics of the declaration, NOT the default."""
        return (self.name, self.kind, self.env)


_KNOBS = OrderedDict()
_LOCK = _conc.lock("registry", "_LOCK")


def declare(*args, **kwargs):
    """Register a knob (module import time; idempotent re-declare of an
    identical knob is allowed for reload-tolerance)."""
    k = Knob(*args, **kwargs)
    with _LOCK:
        prev = _KNOBS.get(k.name)
        if prev is not None and prev.fingerprint() != k.fingerprint():
            raise ValueError("knob %r re-declared with different "
                             "semantics" % k.name)
        _KNOBS[k.name] = k
    return k


def get_knob(name):
    try:
        return _KNOBS[name]
    except KeyError:
        raise KeyError("unknown knob %r (catalog: %s)"
                       % (name, ", ".join(sorted(_KNOBS))))


def knobs():
    """All declared knobs, in declaration order."""
    return list(_KNOBS.values())


def registry_version():
    """Stable fingerprint of the declared knob set (the measurement
    corpus stamps its rows with it)."""
    h = hashlib.sha1()
    for name in sorted(_KNOBS):
        h.update(repr(_KNOBS[name].fingerprint()).encode())
    return h.hexdigest()[:12]


# ------------------------------------------------------------------ resolve
def resolve(name, explicit=None):
    """The single knob-resolution point every subsystem pulls through.

    ``explicit`` — the caller's keyword argument (None = not passed).
    Precedence: default < environment < explicit.
    """
    knob = get_knob(name)
    if explicit is not None:
        return knob.coerce(explicit)
    if knob.env:
        raw = os.environ.get(knob.env)
        if raw is not None and raw.strip() != "":
            return knob.coerce(raw)
    return knob.coerce(knob.default) if knob.default is not None else None


def resolve_int(name, explicit=None, floor=None):
    """``resolve`` + integer floor — the common ``max(1, int(v))``
    pattern at the old call sites."""
    v = resolve(name, explicit=explicit)
    if v is None:
        return None
    v = int(v)
    if floor is not None and v < floor:
        v = floor
    return v


# ------------------------------------------------------------------ catalog
def catalog_table():
    """The knob catalog as a markdown table — docs/tune.md embeds this
    output so the doc can be regenerated instead of hand-maintained."""
    lines = ["| knob | kind | default | env | meaning |",
             "|---|---|---|---|---|"]
    for k in knobs():
        default = "auto" if k.default is None else repr(k.default)
        lines.append(
            "| `%s` | %s | %s | %s | %s |"
            % (k.name, k.kind, default,
               "`%s`" % k.env if k.env else "—", k.help))
    return "\n".join(lines)


# =================================================================== catalog
# The declarations. docs/tune.md's table and the defaults test both read
# them from this single place.

# --- fit (Module.fit async-pipeline knobs, docs/training_pipeline.md)
declare("fit.max_in_flight", "int", 2, env="MXTPU_FIT_INFLIGHT",
        help="dispatched steps kept in flight before fit blocks on the "
             "oldest (pipeline depth)")
declare("fit.metric_sync", "int_or_none", None, env="MXTPU_FIT_METRIC_SYNC",
        help="device->host metric sync cadence in batches (auto: derived "
             "from the batch callbacks; 0 = epoch-end only)")
declare("fit.device_metrics", "bool", True, env="MXTPU_FIT_DEVICE_METRICS",
        help="accumulate eval metrics on device via jitted kernels")
declare("fit.device_prefetch", "bool", False,
        env="MXTPU_FIT_DEVICE_PREFETCH",
        help="stage batch N+1's device transfer from a producer thread "
             "while step N runs")
declare("fit.batch_size", "int_or_none", None, env="MXTPU_FIT_BATCH_SIZE",
        help="training batch size for drivers that build their own "
             "iterator; fit itself keeps the caller's iterator (no "
             "reader in the tree)")
declare("fit.remat", "str", "none", env="MXTPU_REMAT",
        help="selective rematerialization policy of the fused step: "
             "none/auto/block/conv/all (memory-capacity lever). "
             "Unset or auto honor the remat_reuse "
             "pass's per-node annotations; an env-SET none/0 pins no-"
             "remat and suppresses them, like block/conv/all pin "
             "their explicit policy")

# --- training health (device-resident stats + detectors,
#     docs/observability.md "Training health")
declare("health.cadence", "int", 1, env="MXTPU_HEALTH_CADENCE",
        help="detector stride in metric-sync cadences: the stat rows "
             "land every sync, the detector suite runs every Nth")
declare("health.window", "int", 8, env="MXTPU_HEALTH_WINDOW",
        help="rolling-window length (in detector cadences) of the loss "
             "spike / divergence baselines")
declare("health.spike_k", "float", 8.0, env="MXTPU_HEALTH_SPIKE_K",
        help="loss-spike threshold in MADs above the rolling median")

# --- serving (ServingSession / batcher / admission, docs/serving.md)
declare("serving.max_in_flight", "int", 2, env="MXTPU_SERVING_INFLIGHT",
        help="device batches each dispatcher keeps in flight per replica")
declare("serving.refill_watermark", "int_or_none", None,
        env="MXTPU_SERVING_WATERMARK",
        help="pending rows that trigger an immediate refill of a freed "
             "slot (auto: derived from the measured per-bucket cost rows)")
declare("serving.max_queue", "int", 256, env="MXTPU_SERVING_MAX_QUEUE",
        help="bounded request-queue depth; beyond it submit raises "
             "QueueFull (429)")
declare("serving.max_delay_ms", "float", 5.0,
        env="MXTPU_SERVING_MAX_DELAY_MS",
        help="batching deadline: latency donated to coalescing before a "
             "padded partial batch flushes")
declare("serving.queue_wait_budget_ms", "float_or_none", None,
        env="MXTPU_SERVING_QUEUE_WAIT_BUDGET_MS",
        help="admission latency budget (auto: half the request timeout "
             "when set, else 1000ms)")
declare("serving.watchdog_shed_s", "float", 10.0,
        help="no-progress seconds after which admission sheds (wedge "
             "signal)")
declare("serving.min_mem_headroom", "float", 0.03,
        help="ledger headroom fraction below which admission sheds")
declare("serving.queue_frac_shed", "float", 0.95,
        help="queue occupancy fraction at which admission sheds before "
             "QueueFull would")
declare("serving.degrade_frac", "float", 0.5,
        help="fraction of the latency budget past which admission "
             "reports DEGRADED")
declare("serving.mem_budget_bytes", "float", 0.0,
        env="MXTPU_SERVING_MEM_BUDGET",
        help="device-memory budget for the admission headroom signal "
             "(0 = signal off)")
declare("serving.warm_versions", "int", 4,
        env="MXTPU_SERVING_WARM_VERSIONS",
        help="model versions the process-wide WarmExecutableCache retains")

# --- decode (stateful autoregressive decode serving, docs/decode.md)
declare("decode.slot_capacity", "int", 8, env="MXTPU_DECODE_SLOTS",
        help="sequence slots in the device-resident decode state arena "
             "(in-flight sequences per DecodeSession)")
declare("decode.max_new_tokens_default", "int", 32,
        env="MXTPU_DECODE_MAX_NEW_TOKENS",
        help="generated-token budget a /v1/generate request gets when it "
             "does not name its own max_new_tokens")
declare("decode.join_watermark", "int", 4,
        env="MXTPU_DECODE_JOIN_WATERMARK",
        help="requests allowed to queue while the slot arena is full "
             "before length-aware est-completion pricing starts "
             "shedding (429)")
declare("decode.block_size", "int", 16, env="MXTPU_DECODE_BLOCK_SIZE",
        help="tokens per KV-cache block in the paged decode arena "
             "(allocation granularity: a sequence holds "
             "ceil(tokens/block_size) blocks)")
declare("decode.max_blocks_per_seq", "int", 16,
        env="MXTPU_DECODE_MAX_BLOCKS_PER_SEQ",
        help="block-table length per sequence slot — block_size × this "
             "is the per-request token budget AND the bucketed "
             "attention view's time extent")
declare("decode.prefill_chunk_tokens", "int", 32,
        env="MXTPU_DECODE_PREFILL_CHUNK",
        help="prompt tokens per chunked-prefill dispatch — the prefill "
             "latency quantum: a longer prompt never occupies the "
             "decode loop for more than one chunk per iteration")

# --- elastic (async checkpoint cadence, docs/elastic.md)
declare("elastic.every_n_steps", "int", 0, env="MXTPU_ELASTIC_EVERY_STEPS",
        help="mid-epoch snapshot cadence in global steps (0 = epoch "
             "boundaries only)")
declare("elastic.epoch_period", "int", 1, env="MXTPU_ELASTIC_EPOCH_PERIOD",
        help="epoch-boundary snapshot period (0 disables)")
declare("elastic.keep", "int", 2, env="MXTPU_ELASTIC_KEEP",
        help="checkpoint generations retained")

# --- compile (the pipeline seam, docs/compile.md)
declare("compile.pipeline", "str", "", env="MXTPU_PIPELINE",
        help="transform-pass list the compile pipeline runs (comma-"
             "separated registry names; empty = no rewrites)")
declare("compile.fuse_opt_max_kb", "float", 32.0,
        env="MXTPU_FUSE_OPT_MAX_KB",
        help="fuse_opt class bound: only parameters at or under this "
             "many KB batch into a shared update region (small-param "
             "chains are launch-bound; big weight chains are bandwidth-"
             "bound and the stack would cost real movement)")
declare("compile.remat_threshold", "float", 4.0,
        env="MXTPU_REMAT_THRESHOLD",
        help="remat_reuse annotation bar: a node's residual is "
             "recomputed in backward when its recompute-flops per saved "
             "byte is at or below this ratio")

# --- quant (int8 post-training quantization, docs/compile.md)
declare("quant.calibration_percentile", "float", 99.9,
        env="MXTPU_QUANT_PERCENTILE",
        help="activation clipping statistic: per-batch percentile of "
             "|x| whose running max sets the per-tensor int8 scale "
             "(100.0 = plain abs-max, no clipping)")
declare("quant.per_channel", "bool", True, env="MXTPU_QUANT_PER_CHANNEL",
        help="weight scales per output channel (axis 0) when on; one "
             "per-tensor scale per weight when off")
declare("quant.min_layer_elems", "int", 64, env="MXTPU_QUANT_MIN_ELEMS",
        help="smallest weight (elements) the quant pass rewrites — "
             "below it the dequantize overhead beats the byte savings")
