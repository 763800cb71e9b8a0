"""Layer: step programs. Source: device_trace. Device time of one run of the
program that took most of the traced window (the fused fit step)."""


def read(facts):
    tr = facts.get("trace")
    got = tr.module_time() if tr is not None else None
    return None if got is None else got[1] * 1e3
