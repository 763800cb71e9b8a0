"""Layer: step programs. Source: device_trace. Device time of the
state-space scan's kernels, forward and backward (found by their names in
their HLO text, as ssd_roofline.py finds them), over the device time of the
step program that holds them, both as whole runs inside the traced window.
The in- and out-projections, the short convolution, the softplus and the
gated norm run outside the kernels under fusion names of XLA's own and are
not in this share. Returns nothing where the trace holds no such call."""

KERNELS = r"^%?mxtpu_ssd_(fwd|bwd)"


def read(facts):
    tr = facts.get("trace")
    step = tr.module_time() if tr is not None else None
    if step is None:
        return None
    seconds, calls = tr.op_time(KERNELS)
    if not calls:
        return None
    return 100.0 * seconds / (step[1] * step[2])
