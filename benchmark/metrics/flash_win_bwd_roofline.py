"""Layer: kernels. Source: device_trace. The windowed flash backward
kernel's share of its roofline, as flash_win_fwd_roofline.py reads the
forward's: flops.py `flash_win_bwd` (five products a pair the window
leaves) over the mean device time of the calls named
mxtpu/ops/attention.py WIN_BWD_KERNEL_NAME. Returns nothing where the trace
holds no such call or the configuration counts none."""

KERNEL = r"^%?mxtpu_flash_win_bwd"


def read(facts):
    tr = facts.get("trace")
    if tr is None or "cell" not in facts:
        return None
    flops = facts["cell"].config_module("flops")
    if not hasattr(flops, "flash_win_bwd"):
        return None
    seconds, calls = tr.op_time(KERNEL)
    if not calls:
        return None
    need_f, need_b = flops.flash_win_bwd(
        facts["config"], facts["traffic"], facts["batch_per_chip"])
    least = max(need_f / facts["peaks"]["bf16_flops"],
                need_b / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
