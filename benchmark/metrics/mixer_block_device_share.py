"""Layer: step programs. Source: device_trace. Share of the fused step's
device time (benchmark/opscopes.py) in the nodes the model builder marked
`block="delta_rule"` or `block="mamba2"` (`mx.AttrScope`), forward and
backward, every operator but the projections' `FullyConnected`: the
recurrence's kernels and what runs beside them (short convolutions, gates,
decays, norms, transposes, the residual add)."""
from benchmark import opscopes


def read(facts):
    sc = opscopes.load(facts)
    if sc is None:
        return None
    return sc.share(lambda node, op, block, phase:
                    block in ("delta_rule", "mamba2")
                    and op != "FullyConnected"
                    and phase in ("forward", "backward"))
