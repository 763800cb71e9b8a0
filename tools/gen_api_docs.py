#!/usr/bin/env python
"""Generate the user-facing API reference into docs/api/ (parity: the
reference's autogenerated docs/api tree — sphinx over NDArray/Symbol/
Module/Gluon/IO/KVStore docstrings plus the operator reference that
AddTo/describe emits per registered op).

Markdown, no sphinx: one page per public module (signatures + docstrings
introspected from the live package) and an operators.md with every
registered op's attrs/defaults straight from the registry — so the pages
can never drift from the code that registers them.

Usage: PYTHONPATH=/root/repo JAX_PLATFORMS=cpu python tools/gen_api_docs.py
"""
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "docs", "api")

MODULES = [
    ("ndarray", "mxtpu.ndarray", "NDArray — imperative tensors"),
    ("symbol", "mxtpu.symbol", "Symbol — declarative graphs"),
    ("module", "mxtpu.module", "Module — training harness"),
    ("gluon", "mxtpu.gluon", "Gluon — imperative blocks"),
    ("gluon_nn", "mxtpu.gluon.nn", "gluon.nn — layers"),
    ("gluon_rnn", "mxtpu.gluon.rnn", "gluon.rnn — recurrent layers"),
    ("gluon_loss", "mxtpu.gluon.loss", "gluon.loss — losses"),
    ("gluon_data", "mxtpu.gluon.data", "gluon.data — datasets/loaders"),
    ("gluon_model_zoo", "mxtpu.gluon.model_zoo.vision", "model zoo"),
    ("io", "mxtpu.io", "IO — data iterators"),
    ("image", "mxtpu.image", "Image — decode/augment pipeline"),
    ("recordio", "mxtpu.recordio", "RecordIO"),
    ("kvstore", "mxtpu.kvstore", "KVStore — distributed training"),
    ("optimizer", "mxtpu.optimizer", "Optimizers"),
    ("lr_scheduler", "mxtpu.lr_scheduler", "LR schedulers"),
    ("metric", "mxtpu.metric", "Evaluation metrics"),
    ("initializer", "mxtpu.initializer", "Initializers"),
    ("autograd", "mxtpu.autograd", "Autograd"),
    ("executor", "mxtpu.executor", "Executor"),
    ("rnn", "mxtpu.rnn", "Symbolic RNN cells"),
    ("random", "mxtpu.random", "Random"),
    ("profiler", "mxtpu.profiler", "Profiler"),
    ("diagnostics", "mxtpu.diagnostics",
     "Diagnostics — memory ledger, program costs, flight recorder, "
     "watchdog"),
    ("obs", "mxtpu.obs",
     "Obs — trace timeline export, decode latency attribution, "
     "measurement corpus, training health"),
    ("obs_health", "mxtpu.obs.health",
     "obs.health — device-resident training-health stats, detector "
     "cadence, divergence auto-rollback"),
    ("obs_detectors", "mxtpu.obs.detectors",
     "obs.detectors — deterministic training-anomaly detector suite"),
    ("analysis", "mxtpu.analysis",
     "Analysis — graph verifier passes, dataflow analyses, transform "
     "passes, numerics sanitizer"),
    ("equiv", "mxtpu.analysis.equiv",
     "Equiv — translation validation: per-pass rewrite-algebra "
     "certification of compile-pipeline transforms"),
    ("graphgen", "mxtpu.analysis.graphgen",
     "Graphgen — seeded random-graph generation + transform fuzzing "
     "(certification + numeric differentials)"),
    ("concurrency", "mxtpu.analysis.concurrency",
     "Concurrency — tracked-lock factory, runtime lock-order witness, "
     "blocking-under-lock detection, seeded schedule fuzzer"),
    ("compile", "mxtpu.compile",
     "Compile — the program-build pipeline: analysis-gated graph "
     "transforms, build listeners, AOT cost capture"),
    ("monitor", "mxtpu.monitor", "Monitor"),
    ("callback", "mxtpu.callback", "Callbacks"),
    ("visualization", "mxtpu.visualization", "Visualization"),
    ("model", "mxtpu.model", "FeedForward model API"),
    ("parallel", "mxtpu.parallel", "Parallel — mesh/dp/tp/pp/sp/ep"),
    ("sharding", "mxtpu.sharding",
     "Sharding — SPMD mesh execution: SpecLayout, MeshContext, "
     "ShardingPlan, weight-update sharding"),
    ("elastic", "mxtpu.elastic",
     "Elastic — async checkpointing, exact fit-resume, watchdog-driven "
     "preemption recovery"),
    ("serving", "mxtpu.serving",
     "Serving — continuous-batching inference: admission control, warm "
     "executable cache, versioned hot-swap"),
    ("serving_decode", "mxtpu.serving.decode",
     "Serving decode — stateful autoregressive decode: sequence slot "
     "arena, step-granularity continuous batching, length-aware "
     "admission"),
    ("faults", "mxtpu.faults",
     "Faults — seeded fault injection (declared points, schedule "
     "grammar) and the shared RetryPolicy"),
    ("tune", "mxtpu.tune",
     "Tune — the knob registry: every tunable declared once, resolved "
     "default < environment < explicit argument"),
    ("export", "mxtpu.export", "AOT export"),
    ("test_utils", "mxtpu.test_utils", "Test utilities"),
    ("torch_bridge", "mxtpu.torch_bridge",
     "mx.th — torch interop (in-graph torch layers)"),
    ("rtc", "mxtpu.rtc", "mx.rtc — runtime-compiled kernels"),
    ("operator", "mxtpu.operator", "CustomOp — user python ops"),
]


def _strip_addrs(text):
    """repr() of callable defaults embeds process addresses; strip them so
    regeneration is deterministic."""
    import re
    return re.sub(r" at 0x[0-9a-f]+", "", text)


def _sig(obj):
    try:
        return _strip_addrs(str(inspect.signature(obj)))
    except (ValueError, TypeError):
        return "(...)"


def _doc(obj, limit=None):
    d = inspect.getdoc(obj) or ""
    if limit and len(d) > limit:
        d = d[:limit].rsplit("\n", 1)[0] + "\n..."
    return d


def _is_public(name, obj, modname):
    if name.startswith("_"):
        return False
    m = getattr(obj, "__module__", "") or ""
    return m.startswith(modname.rsplit(".", 1)[0]) or m.startswith("mxtpu")


def page_for_module(slug, modname, title):
    import importlib
    try:
        mod = importlib.import_module(modname)
    except Exception as e:  # pragma: no cover - doc build resilience
        return "# %s\n\nimport failed: %s\n" % (title, e)
    lines = ["# %s" % title, "", "`%s`" % modname, ""]
    top = _doc(mod)
    if top:
        lines += [top, ""]
    classes, funcs = [], []
    for name in sorted(dir(mod)):
        obj = getattr(mod, name)
        if not _is_public(name, obj, modname):
            continue
        if inspect.isclass(obj):
            classes.append((name, obj))
        elif inspect.isfunction(obj):
            funcs.append((name, obj))
    if funcs:
        lines += ["## Functions", ""]
        for name, fn in funcs:
            lines += ["### `%s%s`" % (name, _sig(fn)), ""]
            d = _doc(fn)
            if d:
                lines += [d, ""]
    for name, cls in classes:
        lines += ["## class `%s%s`" % (name, _sig(cls)), ""]
        d = _doc(cls)
        if d:
            lines += [d, ""]
        for mname, meth in sorted(vars(cls).items()):
            if mname.startswith("_") or not callable(meth):
                continue
            lines += ["- `%s%s`" % (mname, _sig(meth))]
            md = _doc(meth, 300)
            if md:
                first = md.split("\n")[0]
                lines += ["  - %s" % first]
        lines += [""]
    return "\n".join(lines)


def operators_page():
    from mxtpu.ops import registry
    lines = ["# Operator reference", "",
             "Every op registered in `mxtpu.ops.registry` (callable as "
             "`mx.nd.<op>` / `mx.sym.<op>`; the same census the C API and "
             "the generated cpp-package surface expose).", ""]
    names = sorted(registry.list_ops())
    lines += ["%d operators." % len(names), ""]
    for name in names:
        op = registry.get_op(name)
        attrs = getattr(op, "attrs_spec", None) or {}
        args = getattr(op, "arg_names", None) or []
        if callable(args):  # variadic input-count ops compute their names
            args = ["*inputs"]
        parts = list(args)
        for k, v in attrs.items():
            if type(v).__name__ == "Required":
                parts.append("%s=<required>" % k)
            else:
                parts.append("%s=%r" % (k, v))
        lines.append("### `%s(%s)`" % (name, ", ".join(parts)))
        d = _doc(getattr(op, "fn", None) or op, 400)
        if d:
            lines += ["", d]
        lines.append("")
    return "\n".join(lines)


def main():
    os.makedirs(OUT, exist_ok=True)
    index = ["# mxtpu API reference", "",
             "Generated by `tools/gen_api_docs.py` — regenerate after "
             "changing public APIs.", ""]
    for slug, modname, title in MODULES:
        content = page_for_module(slug, modname, title)
        with open(os.path.join(OUT, slug + ".md"), "w") as f:
            f.write(content)
        index.append("- [%s](%s.md)" % (title, slug))
    with open(os.path.join(OUT, "operators.md"), "w") as f:
        f.write(operators_page())
    index.append("- [Operator reference](operators.md)")
    with open(os.path.join(OUT, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print("wrote %d pages to %s" % (len(MODULES) + 2, OUT))


if __name__ == "__main__":
    main()
