"""Layer: step programs. Source: device_trace. Device time of the attention
kernels of both kinds, forward and backward (the four Mosaic calls named
mxtpu_flash_fwd / _bwd / mxtpu_flash_win_fwd / _bwd in their HLO text),
over the device time of the step program that holds them, both as whole
runs inside the traced window. The rotation and the gate are elementwise
operations that XLA fuses into their neighbours under names of its own
(`fusion.N`); the trace the harness keeps holds an operation's HLO text
alone, so they cannot be told from the projections they ride in and are
not in this share. Returns nothing where the trace holds no such call."""

KERNELS = r"^%?mxtpu_flash_(win_)?(fwd|bwd)"


def read(facts):
    tr = facts.get("trace")
    step = tr.module_time() if tr is not None else None
    if step is None:
        return None
    seconds, calls = tr.op_time(KERNELS)
    if not calls:
        return None
    return 100.0 * seconds / (step[1] * step[2])
