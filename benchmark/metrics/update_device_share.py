"""Layer: step programs. Source: device_trace. Share of the fused step's
device time (benchmark/opscopes.py) in operations of phase `update`: traced
under `mxtpu.update/<parameter>` (module/fused.py) and holding no gradient's
matmul, since a fusion takes the scope of the matmul it holds: an update
that XLA fused into a weight gradient's matmul counts with the gradient."""
from benchmark import opscopes


def read(facts):
    sc = opscopes.load(facts)
    if sc is None:
        return None
    return sc.share(lambda node, op, block, phase: phase == "update")
