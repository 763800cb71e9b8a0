#!/usr/bin/env python
"""Analyze the compiled HLO of the fused ResNet-50 train step: per-opcode
materialized bytes (fusion bodies excluded) and the largest single
materializations, each with the graph node and phase it came from (the
instructions are read by mxtpu.diagnostics.opscopes, the repository's one
reader of HLO text). Compile-only (abstract inputs), so it never allocates on
the device and can run alongside a benchmark.

The cost/memory numbers and the HLO text come from the diagnostics
program registry (mxtpu.diagnostics.record_program — the same capture
every live program gets at the executor build seam) instead of a second
ad-hoc cost_analysis extraction; ``--from-dump`` skips compilation
entirely and prints the program table of a postmortem / debug_state
JSON dump from a live process.

Usage: python tools/hlo_analyze.py [batch]
       python tools/hlo_analyze.py --from-dump mxtpu_postmortem_*.json
"""
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_DT = {'bf16': 2, 'f32': 4, 's32': 4, 'u32': 4, 'f16': 2, 'pred': 1, 's8': 1,
       'u8': 1, 's64': 8, 'f64': 8}


def shape_bytes(s):
    tot = 0
    for m in re.finditer(r'(\w+)\[([\d,]*)\]', s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DT:
            continue
        n = 1
        for d in dims.split(','):
            if d:
                n *= int(d)
        tot += n * _DT[dt]
    return tot


def analyze(txt, top=25, scopes=None):
    """Tally output bytes of materializing ops (outside fusion bodies and
    reducers), read through the repository's one reader of HLO text
    (mxtpu.diagnostics.opscopes); the largest name the graph node they
    came from."""
    from mxtpu.diagnostics import opscopes
    module = opscopes.parse(txt)
    table = opscopes.build_table(module, scopes)
    stats = collections.Counter()
    counts = collections.Counter()
    biggest = []
    for comp, ins in module.instructions():
        if 'fused' in comp or 'region' in comp:
            continue
        if ins.opcode in ('parameter', 'constant', 'get-tuple-element',
                          'tuple', 'bitcast'):
            continue
        b = shape_bytes(ins.shape)
        stats[ins.opcode] += b
        counts[ins.opcode] += 1
        if b > 50e6:
            biggest.append((b, ins.opcode, comp, ins.name, ins.shape,
                            table[ins.name]))
    print('total materialized output bytes: %.1f GB' %
          (sum(stats.values()) / 1e9))
    for k, v in stats.most_common(20):
        print('%-22s %8.2f GB  x%d' % (k, v / 1e9, counts[k]))
    biggest.sort(reverse=True)
    print('--- largest materializations ---')
    print('%12s %-12s %-28s %-9s %s' % ('', 'opcode', 'node', 'phase',
                                        'instruction'))
    for b, opk, comp, name, shape, sc in biggest[:top]:
        print('%9.0f MB %-12s %-28s %-9s [%s] %s = %s' % (
            b / 1e6, opk, (sc.node or '-')[:28], sc.phase, comp, name,
            shape[:80]))


def table_from_dump(path):
    """Print the program-cost table of a diagnostics dump (postmortem or
    debug_state JSON) — no jax, no compilation: the registry already
    captured every program the process built."""
    with open(path) as f:
        dump = json.load(f)
    rows = dump.get("programs") or []
    print("%d captured programs from %s" % (len(rows), path))
    hdr = ("id", "kind", "owner", "calls", "compile_ms", "mflops",
           "temp_kb", "prec")
    print("%4s %-12s %-16s %6s %10s %10s %8s %-10s" % hdr)
    for r in rows:
        print("%4d %-12s %-16s %6d %10.1f %10.2f %8d %-10s"
              % (r["id"], r["kind"][:12], r["owner"][:16], r["calls"],
                 r["compile_ms"], r["flops"] / 1e6,
                 r["temp_bytes"] // 1024,
                 # precision column is absent in pre-PR-7 dumps
                 r.get("precision", "f32")[:10]))
    return 0


def main():
    if "--from-dump" in sys.argv:
        i = sys.argv.index("--from-dump")
        if i + 1 >= len(sys.argv):
            print("usage: python tools/hlo_analyze.py --from-dump "
                  "<postmortem.json>", file=sys.stderr)
            return 2
        return table_from_dump(sys.argv[i + 1])
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxtpu  # noqa: F401
    from mxtpu import diagnostics as diag
    from mxtpu.models import resnet
    from mxtpu.parallel import make_mesh
    from mxtpu.parallel.dp import DataParallelTrainer

    batch = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() \
        else 256
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224))
    mesh = make_mesh(shape=(len(jax.devices()),))
    trainer = DataParallelTrainer(
        sym, mesh=mesh, optimizer='sgd',
        optimizer_params={'learning_rate': 0.1, 'momentum': 0.9,
                          'rescale_grad': 1.0 / batch}, dtype='bfloat16')

    # abstract init: shapes only, no device arrays
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(batch, 3, 224, 224), softmax_label=(batch,))
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    ashapes = dict(zip(sym.list_auxiliary_states(), aux_shapes))
    sds = jax.ShapeDtypeStruct
    params = {n: sds(shapes[n], jnp.bfloat16) for n in trainer.param_names}
    aux = {n: sds(ashapes[n], jnp.bfloat16) for n in trainer.aux_names}
    # optimizer state is kept in f32 (master momentum, module/fused.py)
    opt = {n: sds(shapes[n], jnp.float32) for n in trainer.param_names}
    batch_in = {'data': sds((batch, 3, 224, 224), jnp.bfloat16),
                'softmax_label': sds((batch,), jnp.float32)}
    rng = sds((2,), jnp.uint32)
    trainer._pspecs = {n: jax.sharding.PartitionSpec()
                       for n in trainer.param_names}
    trainer._ospecs = trainer._pspecs
    trainer._opt_state = opt
    fn = trainer._build_step()
    print('lowering...', flush=True)
    t0 = time.perf_counter()
    c = fn.lower(params, aux, opt, batch_in, rng, 1).compile()
    # register through the diagnostics seam and READ the numbers back
    # from the registry record — one cost-extraction implementation for
    # live programs and this tool (no second as-hoc parse), and the HLO
    # text comes off the record's weakly-held executable
    diag.record_program('hlo_analyze', 'tools/hlo_analyze', c,
                        (time.perf_counter() - t0) * 1e3)
    rec = diag.latest_record('hlo_analyze')
    print('cost: %.2f TFLOP, %.1f GB accessed (compile %.0f ms, '
          'temp %.1f GB)' % (rec.flops / 1e12, rec.bytes_accessed / 1e9,
                             rec.compile_ms, rec.temp_bytes / 1e9))
    print(diag.program_table('hlo_analyze'))
    from mxtpu.diagnostics import opscopes
    analyze(rec.hlo_text() or c.as_text(),
            scopes=opscopes.symbol_scopes(sym))
    return 0


if __name__ == '__main__':
    sys.exit(main())
