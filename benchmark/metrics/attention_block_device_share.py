"""Layer: step programs. Source: device_trace. Share of the fused step's
device time (benchmark/opscopes.py) in the nodes the model builder marked
`block="attention"` (`mx.AttrScope`), forward and backward, every operator
but the projections' `FullyConnected`: the attention kernels, the q/k norm,
rotation and gate operators, the head transposes, the sublayer's norm and
residual add. Found by the `__block__` attribute of the node that an
operation's `op_name` metadata names, not by the operation's name."""
from benchmark import opscopes


def read(facts):
    sc = opscopes.load(facts)
    if sc is None:
        return None
    return sc.share(lambda node, op, block, phase: block == "attention"
                    and op != "FullyConnected"
                    and phase in ("forward", "backward"))
