"""Layer: step programs. Source: device_trace. Share of the fused step's
device time (benchmark/opscopes.py) in operations whose `op_name` metadata
names a node of operator `BatchNorm`, forward and backward. A fusion takes
the scope of the convolution it holds, so statistics that XLA fused into a
convolution count with the convolution and not here."""
from benchmark import opscopes


def read(facts):
    sc = opscopes.load(facts)
    if sc is None:
        return None
    return sc.share(lambda node, op, block, phase: op == "BatchNorm"
                    and phase in ("forward", "backward"))
