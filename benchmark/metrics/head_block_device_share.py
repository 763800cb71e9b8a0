"""Layer: step programs. Source: device_trace. Share of the fused step's
device time (benchmark/opscopes.py) in the nodes the model builder marked
`block="embed"` or `block="head"` (`mx.AttrScope`), all phases: the
embedding lookup and its scatter backward, the final norm, the head
projection, the loss, and the updates of their parameters (an update counts
with the block of the parameter's consumer)."""
from benchmark import opscopes


def read(facts):
    sc = opscopes.load(facts)
    if sc is None:
        return None
    return sc.share(lambda node, op, block, phase: block in ("embed", "head"))
