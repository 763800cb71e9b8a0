"""Layer: kernels. Source: device_trace. The Pallas flash forward's share of
its roofline: the larger of flops / peak flops and bytes / peak bytes per
call (configuration's flops.py `flash_fwd`) over the kernel's mean device
time. At head size 64 and T=1024 the flops bound it. Returns nothing where
the trace holds no Mosaic call."""

KERNEL = r"custom-call.*tpu_custom_call|^%?(flash|_fwd_kernel)"


def read(facts):
    tr = facts.get("trace")
    if tr is None or "cell" not in facts:
        return None
    seconds, calls = tr.op_time(KERNEL)
    if not calls:
        return None
    flops = facts["cell"].config_module("flops")
    if not hasattr(flops, "flash_fwd"):
        return None
    need_f, need_b = flops.flash_fwd(facts["config"], facts["traffic"],
                                     facts["batch_per_chip"])
    least = max(need_f / facts["peaks"]["bf16_flops"],
                need_b / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
