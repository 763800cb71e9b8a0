"""Layer: kernels. Source: device_trace. Device time of the gated delta
rule's kernels, forward and backward (found by their names in their HLO
text, as delta_rule_roofline.py finds them), over the device time of the
step program that holds them, both as whole runs inside the traced window.
Returns nothing where the trace holds no such call."""

KERNELS = r"^%?mxtpu_delta_rule_(fwd|bwd)"


def read(facts):
    tr = facts.get("trace")
    step = tr.module_time() if tr is not None else None
    if step is None:
        return None
    seconds, calls = tr.op_time(KERNELS)
    if not calls:
        return None
    return 100.0 * seconds / (step[1] * step[2])
