"""Layer: step programs. Source: device_trace. Share of the fused step's
device time (benchmark/opscopes.py) in the nodes the model builder marked
`block="experts"` (`mx.AttrScope`), all phases: the router, the top-k, the
dispatch, the grouped products and the combine, inside the expert layers'
loops or outside them, the sublayer's norm and residual add, and the
updates of their parameters. The shared expert beside them is of block
`ffn` and is not in this share."""
from benchmark import opscopes


def read(facts):
    sc = opscopes.load(facts)
    if sc is None:
        return None
    return sc.share(lambda node, op, block, phase: block == "experts")
