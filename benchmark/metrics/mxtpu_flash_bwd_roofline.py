"""Layer: kernels. Source: device_trace. The flash backward kernel's share of
its roofline: the larger of flops / peak flops and bytes / peak bytes of one
call (the configuration's flops.py `flash_bwd`, counted from causal attention
as written) over the kernel's mean device time. The calls are found by the
kernel's name in their HLO text (mxtpu/ops/attention.py BWD_KERNEL_NAME), so
no other Mosaic call is taken, which `flash_fwd_roofline`'s pattern does.
Returns nothing where the trace holds no call of that name (a program from
before the kernels were named) or the configuration counts no `flash_bwd`."""

KERNEL = r"^%?mxtpu_flash_bwd"


def read(facts):
    tr = facts.get("trace")
    if tr is None or "cell" not in facts:
        return None
    flops = facts["cell"].config_module("flops")
    if not hasattr(flops, "flash_bwd"):
        return None
    seconds, calls = tr.op_time(KERNEL)
    if not calls:
        return None
    need_f, need_b = flops.flash_bwd(
        facts["config"], facts["traffic"], facts["batch_per_chip"])
    least = max(need_f / facts["peaks"]["bf16_flops"],
                need_b / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
