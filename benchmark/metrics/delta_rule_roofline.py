"""Layer: kernels. Source: device_trace. The gated delta rule's share of its
roofline: the larger of flops / peak flops and bytes / peak bytes of one
forward and one backward call (the configuration's flops.py `delta_rule`,
`delta_rule_bwd`, counted from the recurrence) over the mean device time of
one call of each. The calls are found by the kernels' names in their HLO
text (mxtpu/ops/delta_rule.py FWD_KERNEL_NAME, BWD_KERNEL_NAME). At d_k 96,
d_v 192 the bytes bound it. Returns nothing where the trace holds no such
call."""

FWD = r"^%?mxtpu_delta_rule_fwd"
BWD = r"^%?mxtpu_delta_rule_bwd"


def read(facts):
    tr = facts.get("trace")
    if tr is None or "cell" not in facts:
        return None
    flops = facts["cell"].config_module("flops")
    if not hasattr(flops, "delta_rule"):
        return None
    least, spent = 0.0, 0.0
    for pattern, need in ((FWD, flops.delta_rule), (BWD, flops.delta_rule_bwd)):
        seconds, calls = tr.op_time(pattern)
        if not calls:
            return None
        need_f, need_b = need(facts["config"], facts["traffic"],
                              facts["batch_per_chip"])
        least += max(need_f / facts["peaks"]["bf16_flops"],
                     need_b / facts["peaks"]["hbm_bytes_per_s"])
        spent += seconds / calls
    return 100.0 * least / spent
