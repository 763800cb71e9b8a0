"""Layer: kernels. Source: device_trace. The state-space scan's share of its
roofline: the larger of flops / peak flops and bytes / peak bytes of one
forward and one backward call (the configuration's flops.py `ssd`,
`ssd_bwd`, counted from the recurrence as written) over the mean device time
of one call of each. The calls are found by the kernels' names in their HLO
text (mxtpu/ops/ssd.py FWD_KERNEL_NAME, BWD_KERNEL_NAME). At state 128, head
64 and 8 heads a group the bytes bound it. Returns nothing where the trace
holds no such call or the configuration counts no `ssd`."""

FWD = r"^%?mxtpu_ssd_fwd"
BWD = r"^%?mxtpu_ssd_bwd"


def read(facts):
    tr = facts.get("trace")
    if tr is None or "cell" not in facts:
        return None
    flops = facts["cell"].config_module("flops")
    if not hasattr(flops, "ssd"):
        return None
    least, spent = 0.0, 0.0
    for pattern, need in ((FWD, flops.ssd), (BWD, flops.ssd_bwd)):
        seconds, calls = tr.op_time(pattern)
        if not calls:
            return None
        need_f, need_b = need(facts["config"], facts["traffic"],
                              facts["batch_per_chip"])
        least += max(need_f / facts["peaks"]["bf16_flops"],
                     need_b / facts["peaks"]["hbm_bytes_per_s"])
        spent += seconds / calls
    return 100.0 * least / spent
