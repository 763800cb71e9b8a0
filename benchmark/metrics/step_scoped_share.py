"""Layer: step programs. Source: device_trace. Share of the fused step's
device time (the first chip's operations inside whole runs of the step in
the traced window, every instant counted once: benchmark/opscopes.py) in
operations that the program's scope table gives a graph node or one of the
step's own scopes (`mxtpu.update/<parameter>`, `mxtpu.head_grad`,
`mxtpu.health`). The rest carries no usable `op_name` metadata: operations
XLA made itself. Reads nothing from a program that keeps no table."""
from benchmark import opscopes


def read(facts):
    sc = opscopes.load(facts)
    if sc is None:
        return None
    return sc.share(lambda node, op, block, phase: phase != "unscoped")
