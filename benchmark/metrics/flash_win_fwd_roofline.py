"""Layer: kernels. Source: device_trace. The windowed flash forward kernel's
share of its roofline: the larger of flops / peak flops and bytes / peak
bytes of one call (the configuration's flops.py `flash_win_fwd`: the pairs
a window leaves under the causal mask, as written) over the kernel's mean
device time. The calls are found by the kernel's name in their HLO text
(mxtpu/ops/attention.py WIN_FWD_KERNEL_NAME); full-attention calls have
another name and another reader. Returns nothing where the trace holds no
such call or the configuration counts none."""

KERNEL = r"^%?mxtpu_flash_win_fwd"


def read(facts):
    tr = facts.get("trace")
    if tr is None or "cell" not in facts:
        return None
    flops = facts["cell"].config_module("flops")
    if not hasattr(flops, "flash_win_fwd"):
        return None
    seconds, calls = tr.op_time(KERNEL)
    if not calls:
        return None
    need_f, need_b = flops.flash_win_fwd(
        facts["config"], facts["traffic"], facts["batch_per_chip"])
    least = max(need_f / facts["peaks"]["bf16_flops"],
                need_b / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
